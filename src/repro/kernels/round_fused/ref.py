"""Pure-jnp reference for the fused gDDIM round update.

This is the *exact* post-score-eval chain of the historical
`make_diffusion_serve_step` bank mode + `make_diffusion_round_step`
masking, transplanted op-for-op (the PR-5 `_apply_factored_canonical`
discipline, extended to the whole round): same gathers, same
factor applies in the same order (`apply_factored_ref`, pure jnp on every
backend — this chain is the oracle the kernel is checked against on the
chip), same left-associated term sums,
same `jnp.where` masking with identical operand order.  Under jit the
graph is therefore the same program as the stitched chain it replaces,
and the result is BITWISE equal to it — which is what lets the serving
engine swap the chain for `ops.round_update` without perturbing a single
sample (tests/test_round_fused.py compares against
`make_diffusion_round_step_stitched` at the coefficient, round-step and
engine levels).

Split in two because the Eq. 45 corrector needs a *second* score eval at
the predictor iterate, which must happen between the history shift and
the commit:

  * `round_predict_ref`  — Eq. 19a predictor only: eps-history shift +
    u_lin + pC terms -> u_pred (the corrector eval's input).  Recomputed
    inside `round_update_ref` with the identical ops, so the two values
    agree bitwise under jit.
  * `round_update_ref`   — the full commit: shift, predictor, Eq. 22
    stochastic branch (noise keyed by fold_in(fold_in(key, alg), kc) via
    `draw_step_noise`, drawn in state space exactly like the stitched
    chain), corrector select, family/precision retire masking, k-advance.

The stochastic-branch noise can be passed in pre-canonicalized
(`noise_c`) — the Pallas path does this for BDM, whose canonicalize is a
DCT rather than a reshape — or drawn internally from `sde`/`keys`,
reproducing the stitched chain's `vmap(fold_in)` draw bit-for-bit.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

from ...core.coeffs import ALG_GMM, GMM_C, GMM_SALT
from ..ei_update.ops import pad_channels
from ..ei_update.ref import apply_factored_ref

Array = jax.Array


def draw_step_noise(sde, keys, kc, alg, state_shape, dtype) -> Array:
    """Per-slot Eq. 22 noise draw, algorithm-aware — THE noise law of the
    serving tier.  Shared verbatim by this ref chain, the stitched serve
    step (launch/steps.py bank mode), the outside-the-kernel BDM stream
    of the Pallas path (ops._draw_noise_c) and the dense differential
    oracle (tests/dense_reference.py), so all four stay bitwise identical.

    Chain per slot: key -> fold_in(alg) -> fold_in(kc) -> normal z.
    Folding the algorithm id FIRST keys distinct noise streams for
    same-seed different-algorithm co-residents (the PR-10 keying bugfix;
    previously only (seed, k) entered the stream).  For algorithm='gmm' a
    second stream fold_in(step_key, GMM_SALT) draws s_norm and the
    innovation becomes z + GMM_C * sign(s_norm) — Gabbur's moment-matched
    K=2 mixture, whose sqrt(1 - rho^2) scale lives in the bank's P_chol
    rows (core/coeffs.algorithm_coeff_stacks).  The in-kernel threefry
    path replicates this chain bit for bit (kernel.py reads the sign off
    the uniform stage: erf_inv is odd and monotone, so
    sign(normal) == sign(centered uniform) exactly).
    """
    def draw(key, kk, a):
        step_key = jax.random.fold_in(jax.random.fold_in(key, a), kk)
        z = sde.noise_like(step_key, state_shape, dtype)
        s_norm = sde.noise_like(jax.random.fold_in(step_key, GMM_SALT),
                                state_shape, dtype)
        s = jnp.where(s_norm >= 0, jnp.float32(1.0),
                      jnp.float32(-1.0)).astype(dtype)
        return jnp.where(a == ALG_GMM, z + GMM_C * s, z)

    return jax.vmap(draw)(keys, kc, alg)


def _gat(bank, nm, cfg, kc, kf):
    """One factor pair gathered by (cfg, kc): a (B, kf, kf) block sliced
    statically to this family's width + the (B, D) diagonal pool row its
    int32 id points at — the exact gather of the stitched serve step."""
    return (getattr(bank, nm + "_blk")[cfg, kc][:, :kf, :kf],
            bank.diag[getattr(bank, nm + "_di")[cfg, kc]])


def _gatq(bank, nm, j, cfg, kc, kf):
    return (getattr(bank, nm + "_blk")[cfg, kc, j][:, :kf, :kf],
            bank.diag[getattr(bank, nm + "_di")[cfg, kc, j]])


def _shift_hist(hist: Array, eps_c: Array, K: int) -> Array:
    """q-step eps-history shift: hist[:, 0] <- pad(eps_c), rest slide."""
    return jnp.concatenate(
        [pad_channels(eps_c, K)[:, None], hist[:, :-1]], axis=1)


def _predict(u, hist2, kc, cfg, bank, *, kf):
    """Eq. 19a on an already-shifted history: u_lin + sum_j pC_j hist_j.
    Returns (u_lin, u_pred); term order matches the stitched chain."""
    ub = u[:, :kf]
    u_lin = apply_factored_ref(*_gat(bank, "psi", cfg, kc, kf), ub)
    u_pred = u_lin
    for j in range(hist2.shape[1]):
        u_pred = u_pred + apply_factored_ref(
            *_gatq(bank, "pC", j, cfg, kc, kf), hist2[:, j, :kf])
    return u_lin, u_pred


def round_predict_ref(u, hist, kc, cfg, bank, eps_c, *, kf: int):
    """Predictor iterate u_pred (B, kf, D) — the corrector eval's input."""
    hist2 = _shift_hist(hist, eps_c, u.shape[1])
    _, u_pred = _predict(u, hist2, kc, cfg, bank, kf=kf)
    return u_pred


def round_update_ref(u, hist, k, kc, cfg, fam, prec, keys, active, bank,
                     eps_c, *, sde, state_shape, kf: int,
                     fam_index: int = 0, prec_index: int = 0,
                     with_corrector: bool = False, eps_n_c=None,
                     noise_c=None):
    """The full post-score-eval round commit; returns
    (u_next, hist_next, k_next, active_next).

    `eps_c` is this round's canonicalized score eval; `eps_n_c` (required
    iff `with_corrector`) the canonicalized corrector eval at
    `round_predict_ref`'s iterate.  Slots whose (active, fam, prec) do not
    match this variant are frozen verbatim — the stitched round step's
    retire masking, with the precision class as a third mask term (all
    zeros for a single-precision engine, so the masked values are
    unchanged from the two-term chain)."""
    K = u.shape[1]
    hist2 = _shift_hist(hist, eps_c, K)
    u_lin, u_pred = _predict(u, hist2, kc, cfg, bank, kf=kf)

    # stochastic branch (Eq. 22/23): noise keyed by fold_in(fold_in(key,
    # alg), kc), drawn in state space by the shared algorithm-aware law —
    # identical draw to the stitched chain — unless the caller supplies it
    # pre-canonicalized (the BDM Pallas path)
    if noise_c is None:
        noise = draw_step_noise(sde, keys, kc, bank.alg[cfg],
                                state_shape, u.dtype)
        noise_c = sde.canonicalize(noise)
    u_sto = u_lin \
        + apply_factored_ref(*_gat(bank, "B", cfg, kc, kf), eps_c) \
        + apply_factored_ref(*_gat(bank, "P_chol", cfg, kc, kf), noise_c)
    bmask = lambda m: m.reshape((-1, 1, 1))
    u_next = jnp.where(bmask(bank.stochastic[cfg]), u_sto, u_pred)

    if with_corrector:
        if eps_n_c is None:
            raise ValueError("with_corrector=True needs eps_n_c (the "
                             "canonicalized corrector eval at u_pred)")
        u_corr = u_lin + apply_factored_ref(
            *_gatq(bank, "cC", 0, cfg, kc, kf), eps_n_c)
        for j in range(1, hist2.shape[1]):
            u_corr = u_corr + apply_factored_ref(
                *_gatq(bank, "cC", j, cfg, kc, kf), hist2[:, j - 1, :kf])
        # Alg. 1: no corrector on the final step (k == N_c - 1)
        use_c = bank.corrector[cfg] & (kc < bank.n_steps[cfg] - 1)
        u_next = jnp.where(bmask(use_c), u_corr, u_next)

    # re-attach padding rows, then freeze every slot that is not this
    # variant's (active, family, precision-class) — the stitched round
    # step's masking, op for op
    u_full = jnp.concatenate([u_next, u[:, kf:]], axis=1)
    mine = active & (fam == fam_index) & (prec == prec_index)
    rmask = lambda x: mine.reshape((-1,) + (1,) * (x.ndim - 1))
    k_next = jnp.where(mine, k + 1, k)
    return (jnp.where(rmask(u), u_full, u),
            jnp.where(rmask(hist), hist2, hist),
            k_next,
            jnp.where(mine, k_next < bank.n_steps[cfg], active))
