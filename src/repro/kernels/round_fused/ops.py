"""Dispatch wrapper for the fused gDDIM round megakernel.

`round_update(...)` is the serving engine's whole post-score-eval state
update — the factored coefficient apply, q-step eps-history shift, Eq. 22
stochastic branch, corrector select, and (active, fam, prec) retire
masking + k-advance — behind one impl switch:

  * `ref`              — `ref.round_update_ref`: the historical stitched
                         chain transplanted op-for-op, BITWISE equal to it
                         under jit (the differential anchor; the CPU
                         serving path).
  * `pallas`           — one `kernel.round_fused` launch per round after
                         the score eval (TPU; noise drawn in-kernel).
  * `pallas_interpret` — the same kernel on the CPU interpreter (tests).
  * `auto`             — pallas on TPU, ref elsewhere.

`round_predict(...)` is the Eq. 19a predictor iterate the corrector's
second score eval consumes (ref / fused predict kernel under the same
switch; it runs *before* the eval, so the post-eval launch count stays 1
either way).

Families whose `canonicalize` is not a reshape (BDM: DCT) cannot draw
Eq. 22 noise inside the kernel — for those (`sde.canonical_noise_is_
reshape` False) the canonical noise is drawn outside with the exact
stitched-chain fold_in/normal draw and streamed in as an input.

`fused_round_cost(...)` is the analytic bytes/FLOPs model of one fused
launch — the deterministic `round_bytes_moved` /
`kernel_launches_per_round` counters gated in tools/perf_guard.py and
reported by benchmarks/roofline.py's serving mode.
"""
from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp

from ...distributed.sharding import per_batch_shard
from .kernel import N_INTS, round_fused, round_predict as _predict_pallas
from .ref import draw_step_noise, round_predict_ref, round_update_ref

Array = jax.Array


def _resolve(impl: str) -> str:
    if impl == "auto":
        return "pallas" if jax.default_backend() == "tpu" else "ref"
    return impl


def _stage_factors(bank, cfg, kc, kf: int, with_corrector: bool,
                   predict_only: bool = False):
    """Gather this round's factor pairs and stack them into the kernel's
    SMEM layout: blks (B, C, kf, kf) f32 + dis (B, C) int32 diag-pool ids,
    slot order per kernel.py (predict layout: [psi, pC_j])."""
    blk = lambda nm: getattr(bank, nm + "_blk")[cfg, kc][:, None, :kf, :kf]
    di = lambda nm: getattr(bank, nm + "_di")[cfg, kc][:, None]
    pC_b = bank.pC_blk[cfg, kc][:, :, :kf, :kf]         # (B, Qb, kf, kf)
    pC_i = bank.pC_di[cfg, kc]                          # (B, Qb)
    if predict_only:
        return (jnp.concatenate([blk("psi"), pC_b], axis=1),
                jnp.concatenate([di("psi"), pC_i], axis=1))
    parts_b = [blk("psi"), blk("B"), blk("P_chol"), pC_b]
    parts_i = [di("psi"), di("B"), di("P_chol"), pC_i]
    if with_corrector:
        parts_b.append(bank.cC_blk[cfg, kc][:, :, :kf, :kf])
        parts_i.append(bank.cC_di[cfg, kc])
    return jnp.concatenate(parts_b, axis=1), jnp.concatenate(parts_i, axis=1)


def _draw_noise_c(sde, keys, kc, alg, state_shape, dtype):
    """The stitched chain's algorithm-aware Eq. 22 noise draw
    (ref.draw_step_noise), canonicalized — used when the family's
    canonicalize is not a reshape (kernel can't draw it)."""
    return sde.canonicalize(
        draw_step_noise(sde, keys, kc, alg, state_shape, dtype))


def round_predict(u, hist, kc, cfg, bank, eps_c, *, kf: int,
                  impl: str = "auto", block_d: int = 2048) -> Array:
    """Eq. 19a predictor iterate u_pred (B, kf, D)."""
    impl = _resolve(impl)
    if impl == "ref":
        return round_predict_ref(u, hist, kc, cfg, bank, eps_c, kf=kf)
    if impl not in ("pallas", "pallas_interpret"):
        raise ValueError(impl)
    blks, dis = _stage_factors(bank, cfg, kc, kf, False, predict_only=True)
    launch = functools.partial(_predict_pallas, kf=kf, block_d=block_d,
                               interpret=(impl == "pallas_interpret"))
    return per_batch_shard(launch, blks, dis, bank.diag, u, hist, eps_c,
                           replicated=(2,))


def round_update(u, hist, k, kc, cfg, fam, prec, keys, active, bank, eps_c,
                 *, sde, state_shape, kf: int, fam_index: int = 0,
                 prec_index: int = 0, with_corrector: bool = False,
                 eps_n_c: Optional[Array] = None, impl: str = "auto",
                 block_d: int = 2048):
    """The whole post-score-eval round commit; returns
    (u_next, hist_next, k_next, active_next).  See ref.round_update_ref
    for argument semantics — the pallas path stages the identical gathers
    into SMEM and runs one launch."""
    impl = _resolve(impl)
    if impl == "ref":
        return round_update_ref(
            u, hist, k, kc, cfg, fam, prec, keys, active, bank, eps_c,
            sde=sde, state_shape=state_shape, kf=kf, fam_index=fam_index,
            prec_index=prec_index, with_corrector=with_corrector,
            eps_n_c=eps_n_c)
    if impl not in ("pallas", "pallas_interpret"):
        raise ValueError(impl)

    gen_noise = bool(getattr(sde, "canonical_noise_is_reshape", True))
    noise_c = None
    if not gen_noise:
        noise_c = _draw_noise_c(sde, keys, kc, bank.alg[cfg], state_shape,
                                u.dtype)

    if gen_noise and not jax.config.jax_threefry_partitionable:
        raise NotImplementedError(
            "the in-kernel noise draw reproduces jax.random under the "
            "default jax_threefry_partitionable=True counter layout only")

    mine = active & (fam == fam_index) & (prec == prec_index)
    use_c = (bank.corrector[cfg] & (kc < bank.n_steps[cfg] - 1)) \
        if with_corrector else jnp.zeros_like(active)
    ints = jnp.stack(
        [kc, mine.astype(jnp.int32), bank.stochastic[cfg].astype(jnp.int32),
         use_c.astype(jnp.int32), bank.alg[cfg]],
        axis=1).astype(jnp.int32)

    blks, dis = _stage_factors(bank, cfg, kc, kf, with_corrector)
    launch = functools.partial(
        round_fused, kf=kf, with_corrector=with_corrector,
        gen_noise=gen_noise, block_d=block_d,
        interpret=(impl == "pallas_interpret"))
    u2, h2 = per_batch_shard(launch, ints, keys, blks, dis, bank.diag, u,
                             hist, eps_c, eps_n_c, noise_c, replicated=(4,))
    # k-advance + retirement: the ref chain's ops on the (B,) slot ints
    k2 = jnp.where(mine, k + 1, k)
    return u2, h2, k2, jnp.where(mine, k2 < bank.n_steps[cfg], active)


def fused_round_cost(*, B: int, K: int, Qb: int, kf: int, D: int,
                     pool_rows: int, with_corrector: bool = False,
                     gen_noise: bool = True, itemsize: int = 4) -> dict:
    """Analytic per-launch cost of one fused round commit: bytes moved
    between HBM and VMEM (every stream read/written exactly once — the
    kernel's contract) and the VPU FLOPs of the factor applies.  All
    inputs are static shapes, so both counters are deterministic — they
    are the `round_bytes_moved` EXACT gate in tools/perf_guard.py."""
    state = B * K * D
    hist = B * Qb * K * D
    eps = B * kf * D
    streams_in = state + hist + eps + pool_rows * D
    if with_corrector:
        streams_in += eps
    if not gen_noise:
        streams_in += eps
    n_coef = 3 + Qb + (Qb if with_corrector else 0)
    smem = B * (N_INTS + 2 + n_coef * (kf * kf + 1))
    bytes_moved = itemsize * (streams_in + state + hist)
    # per element of the kf-row output: each factor apply is 2 mul + 1 add
    # per (c, c2) term; predictor sums Qb + 1 applies, stochastic 2 more,
    # corrector Qb more; noise gen ~ const * eps elements (VPU transcendental)
    applies = (1 + Qb) + 2 + (Qb if with_corrector else 0)
    flops = B * kf * kf * D * 3 * applies + B * kf * D * 2 * (applies + 2)
    return {"bytes_moved": int(bytes_moved + itemsize * smem),
            "flops": int(flops),
            "kernel_launches": 1,
            "n_coef": n_coef}


def staticcheck_entries():
    """Named Pallas traces at representative serve shapes for
    tools/staticcheck layer 2 (PL200-203: launch present, BlockSpec
    divisibility, index-map bounds, VMEM/SMEM budgets).  Trace-only —
    nothing is lowered or executed, so it runs on the CPU CI runner."""
    B, K, kf, Qb, D, Pb = 4, 2, 2, 2, 3072, 4   # CIFAR row, CLD width
    ints = jnp.zeros((B, N_INTS), jnp.int32)
    keys = jnp.zeros((B, 2), jnp.uint32)
    dis = jnp.zeros((B, 3 + 2 * Qb), jnp.int32)
    pool = jnp.zeros((Pb, D), jnp.float32)
    u = jnp.zeros((B, K, D), jnp.float32)
    hist = jnp.zeros((B, Qb, K, D), jnp.float32)
    eps = jnp.zeros((B, kf, D), jnp.float32)

    def pred_trace(bl, di_, po, uu, hh, ee):
        return _predict_pallas(bl, di_, po, uu, hh, ee, kf=kf)

    def commit_trace(ii, kk, bl, di_, po, uu, hh, ee):
        return round_fused(ii, kk, bl, di_, po, uu, hh, ee,
                           kf=kf, with_corrector=False)

    def commit_corr_trace(ii, kk, bl, di_, po, uu, hh, ee, en):
        return round_fused(ii, kk, bl, di_, po, uu, hh, ee, eps_n_c=en,
                           kf=kf, with_corrector=True)

    blks_p = jnp.zeros((B, 1 + Qb, kf, kf), jnp.float32)
    blks = jnp.zeros((B, 3 + Qb, kf, kf), jnp.float32)
    blks_c = jnp.zeros((B, 3 + 2 * Qb, kf, kf), jnp.float32)
    return [
        ("kernels/round_fused/round_fused[B4,K2,q2,D3072]",
         jax.make_jaxpr(commit_trace)(
             ints, keys, blks, dis[:, :3 + Qb], pool, u, hist, eps)),
        ("kernels/round_fused/round_fused+corr[B4,K2,q2,D3072]",
         jax.make_jaxpr(commit_corr_trace)(
             ints, keys, blks_c, dis, pool, u, hist, eps, eps)),
        ("kernels/round_fused/round_predict[B4,K2,q2,D3072]",
         jax.make_jaxpr(pred_trace)(
             blks_p, dis[:, :1 + Qb], pool, u, hist, eps)),
    ]
