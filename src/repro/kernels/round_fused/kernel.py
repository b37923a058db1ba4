"""Pallas TPU megakernel: the whole post-score-eval gDDIM round in ONE pass.

The stitched serving chain pays a separate memory-bound VMEM round-trip
over the (B, K, D) state for every piece — six `apply_factored` launches,
the eps-history shift, the Eq. 22 noise draw + add, the stochastic/
corrector selects, and the retire masking each re-read and re-write state
volume.  This kernel does the entire commit in one grid pass: each grid
step (b, d) loads one (K, block_d) state tile, the (Qb, K, block_d)
history tile and the eps tile once, applies every gathered factor pair
from SMEM, draws the stochastic-branch noise *in VREGs* (threefry2x32,
keys folded with the slot's step index in-kernel), resolves the selects
and the (active, fam, prec) retire mask, and stores each output tile
once.  The per-slot k-advance and retirement are (B,) XLA ops beside the
launch (`ops.round_update`).

Layouts: grid (B, Dp // block_d) (`pick_block_d`); per-slot block
factors, diag-pool ids, config scalars and PRNG keys in SMEM, each a
(B, 1, n) array read one (1, 1, n) block per slot (`_slot_smem`); the
deduplicated diagonal pool streams as a (Pb, block_d) VMEM tile with
dynamic row selection.  Coefficient stacking order (the `_PSI`/`_B`/`_P`
constants + `ops._stage_factors`):

    0 psi | 1 B | 2 P_chol | 3..3+Qb-1 pC_j | 3+Qb..3+2Qb-1 cC_j (corr)

Bitwise discipline: every factor apply reassembles the dense coefficient
per term — `(blk[c, c2] * diag) * z[c2]`, left-associated sum — which is
the exact multiply-reduce graph of `apply_factored_ref`, and the noise
path replicates jax's threefry2x32 / fold_in / uniform->erf_inv normal
under the default partitionable counter layout, bit-for-bit (against
`jax.random.normal(fold_in(fold_in(key, alg), k), .)`; the 'gmm'
Rademacher stream reads sign(normal) off the uniform stage of a second
GMM_SALT-folded draw — exact, erf_inv being odd and monotone).  In
interpret mode the kernel is bitwise equal to `ref.round_update_ref`
(tests/test_kernels.py); on the chip `chip_smoke.py` holds it to 1e-5
relative at full width.

`gen_noise=False` takes the canonical noise as an input stream instead —
the BDM path, whose canonicalize is a DCT, not a reshape (`ops` selects
via the SDE's `canonical_noise_is_reshape`).
"""
from __future__ import annotations

import functools

import numpy as np
import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from ...core.coeffs import ALG_GMM, GMM_C, GMM_SALT

Array = jax.Array

# coefficient slots in the stacked (B, C, kf, kf) SMEM block-factor array
_PSI, _B, _P = 0, 1, 2
_N_FIXED = 3                       # pC_j at _N_FIXED + j; cC_j after the pCs

# per-slot int32 SMEM scalar row: [kc, mine, stoch, use_c, alg]
N_INTS = 5

_U32 = jnp.uint32
_TF_MAGIC = np.uint32(0x1BD11BDA)
_ROT_A = (13, 15, 26, 6)
_ROT_B = (17, 29, 16, 24)
# key-schedule index pairs injected after each 4-round group i (i = 1..5)
_INJECT = ((1, 2), (2, 0), (0, 1), (1, 2), (2, 0))


def _rotl(x, r: int):
    return (x << r) | (x >> (32 - r))


def _threefry2x32(k0, k1, x0, x1):
    """The 20-round threefry2x32 block cipher on uint32 scalars/vectors —
    the same schedule jax's PRNG lowers (jax._src.prng), so counters
    encrypted here match `jax.random` bit-for-bit."""
    ks = (k0, k1, k0 ^ k1 ^ _TF_MAGIC)
    x0 = x0 + ks[0]
    x1 = x1 + ks[1]
    for i in range(5):
        for r in (_ROT_A if i % 2 == 0 else _ROT_B):
            x0 = x0 + x1
            x1 = _rotl(x1, r) ^ x0
        a, b = _INJECT[i]
        x0 = x0 + ks[a]
        x1 = x1 + ks[b] + np.uint32(i + 1)
    return x0, x1


def _fold_in(k0, k1, data):
    """jax.random.fold_in on a raw uint32 key pair: encrypt the pair
    (0, data) — threefry_seed of a uint32 is [0, data]."""
    return _threefry2x32(k0, k1, jnp.zeros_like(data), data)


_NORM_LO = np.float32(np.nextafter(np.float32(-1.0), np.float32(0.0)))
_NORM_SCALE = np.float32(1.0) - _NORM_LO
_SQRT2 = np.float32(np.sqrt(2.0))


def _bits(k0, k1, f):
    """uint32 random bits of flat element `f` (int32 vector) of a
    `jax.random.bits(key, shape)` call under jax's default partitionable
    threefry: element f encrypts the 64-bit counter (hi, lo) = (0, f) and
    keeps o0 ^ o1.  Each element's bits depend only on its own index, so
    the draw needs neither the total size nor a half-split pairing."""
    o0, o1 = _threefry2x32(k0, k1, jnp.zeros_like(f, _U32), f.astype(_U32))
    return o0 ^ o1


def _centered_uniform(bits):
    """uint32 bits -> the uniform(nextafter(-1, 0), 1) f32 stage of
    jax.random.normal, bit for bit."""
    fb = (bits >> 9) | np.uint32(0x3F800000)
    fl = jax.lax.bitcast_convert_type(fb, jnp.float32) - np.float32(1.0)
    return jnp.maximum(_NORM_LO, fl * _NORM_SCALE + _NORM_LO)


def _normal_row(k0, k1, f):
    """`jax.random.normal(key, shape).reshape(-1)[f]`: sqrt(2) *
    erf_inv(centered uniform), replicated bit for bit."""
    return _SQRT2 * jax.lax.erf_inv(_centered_uniform(_bits(k0, k1, f)))


def _sign_row(k0, k1, f):
    """sign(`_normal_row(k0, k1, f)`) without the erf_inv: the normal is
    sqrt(2) * erf_inv(un) with erf_inv odd and strictly monotone
    (erf_inv(0) = 0), so its sign IS the sign of the centered uniform
    `un` — bitwise the sign the ref chain reads off `jax.random.normal`.
    Drives the Rademacher component of the 'gmm' mixture draw
    (core/coeffs ALGORITHMS block)."""
    un = _centered_uniform(_bits(k0, k1, f))
    return jnp.where(un >= 0, np.float32(1.0), np.float32(-1.0))


def _factor_apply(blks_ref, dis_ref, pool_ref, kf: int):
    """fapply(ci, rows): apply stacked factor pair `ci` — block factor from
    the slot's SMEM row, diagonal from the VMEM pool tile at the slot's
    dynamic pool id — to the kf channel rows.  (blk * diag) * z per term,
    left-associated sum over c2: the exact apply_factored_ref
    multiply-reduce, so interpret mode is bitwise against the ref chain."""
    def fapply(ci: int, rows):
        d = pool_ref[pl.ds(dis_ref[0, 0, ci], 1), :][0]
        base = ci * kf * kf
        out = []
        for c in range(kf):
            r = (blks_ref[0, 0, base + c * kf] * d) * rows[0]
            for c2 in range(1, kf):
                r = r + (blks_ref[0, 0, base + c * kf + c2] * d) * rows[c2]
            out.append(r)
        return out

    return fapply


def _make_round_kernel(*, kf: int, K: int, Qb: int, D: int, block_d: int,
                       with_corrector: bool, gen_noise: bool):
    def kernel(ints_ref, keys_ref, blks_ref, dis_ref, pool_ref,
               u_ref, hist_ref, eps_ref, *rest):
        i = 0
        epsn_ref = None
        if with_corrector:
            epsn_ref, i = rest[0], 1
        noise_ref = None
        if not gen_noise:
            noise_ref, i = rest[i], i + 1
        u_out, hist_out = rest[i:i + 2]

        kc = ints_ref[0, 0, 0]
        mine = ints_ref[0, 0, 1] != 0
        stoch = ints_ref[0, 0, 2] != 0
        use_c = ints_ref[0, 0, 3] != 0
        alg = ints_ref[0, 0, 4]

        u_rows = [u_ref[0, c] for c in range(K)]            # (bd,) each
        eps_rows = [eps_ref[0, c] for c in range(kf)]
        zero = jnp.zeros_like(u_rows[0])

        # q-step history shift: slot 0 <- pad(eps_c), the rest slide
        h2 = [[eps_rows[c] if c < kf else zero for c in range(K)]]
        for j in range(1, Qb):
            h2.append([hist_ref[0, j - 1, c] for c in range(K)])

        fapply = _factor_apply(blks_ref, dis_ref, pool_ref, kf)
        u_lin = fapply(_PSI, u_rows[:kf])
        u_pred = list(u_lin)
        for j in range(Qb):
            tj = fapply(_N_FIXED + j, h2[j][:kf])
            u_pred = [a + b for a, b in zip(u_pred, tj)]

        if gen_noise:
            # the ref chain's fold order: key -> alg -> kc (draw_step_noise)
            ak0, ak1 = _fold_in(keys_ref[0, 0, 0].astype(_U32),
                                keys_ref[0, 0, 1].astype(_U32),
                                alg.astype(_U32))
            fk0, fk1 = _fold_in(ak0, ak1, kc.astype(_U32))
            lanes = jax.lax.broadcasted_iota(jnp.int32, (1, block_d), 1)[0]
            d_abs = pl.program_id(1) * block_d + lanes
            noise_rows = [_normal_row(fk0, fk1, c * D + d_abs)
                          for c in range(kf)]
            # 'gmm' Rademacher stream (second fold, GMM_SALT): computed
            # unconditionally (one extra threefry + compare per tile, no
            # transcendental) and selected per slot — keeps the launch
            # branch-free across mixed-algorithm batches
            gk0, gk1 = _fold_in(fk0, fk1, jnp.asarray(GMM_SALT, _U32))
            sign_rows = [_sign_row(gk0, gk1, c * D + d_abs)
                         for c in range(kf)]
            is_gmm = alg == ALG_GMM
            noise_rows = [jnp.where(is_gmm, z + GMM_C * s, z)
                          for z, s in zip(noise_rows, sign_rows)]
        else:
            noise_rows = [noise_ref[0, c] for c in range(kf)]

        tB = fapply(_B, eps_rows)
        tP = fapply(_P, noise_rows)
        u_sto = [(u_lin[c] + tB[c]) + tP[c] for c in range(kf)]
        sel = [jnp.where(stoch, u_sto[c], u_pred[c]) for c in range(kf)]

        if with_corrector:
            epsn_rows = [epsn_ref[0, c] for c in range(kf)]
            t0 = fapply(_N_FIXED + Qb, epsn_rows)
            u_corr = [u_lin[c] + t0[c] for c in range(kf)]
            for j in range(1, Qb):
                tj = fapply(_N_FIXED + Qb + j, h2[j - 1][:kf])
                u_corr = [a + b for a, b in zip(u_corr, tj)]
            sel = [jnp.where(use_c, u_corr[c], sel[c]) for c in range(kf)]

        # retire masking: freeze rows that are not this variant's
        # (active, family, precision) class; padding rows pass through
        for c in range(K):
            u_out[0, c] = jnp.where(mine, sel[c], u_rows[c]) if c < kf \
                else u_rows[c]
        for j in range(Qb):
            for c in range(K):
                hist_out[0, j, c] = jnp.where(mine, h2[j][c],
                                              hist_ref[0, j, c])

    return kernel


def _make_predict_kernel(*, kf: int, Qb: int):
    def kernel(blks_ref, dis_ref, pool_ref, u_ref, hist_ref, eps_ref, o_ref):
        u_rows = [u_ref[0, c] for c in range(kf)]
        eps_rows = [eps_ref[0, c] for c in range(kf)]
        h2 = [eps_rows] + [[hist_ref[0, j - 1, c] for c in range(kf)]
                           for j in range(1, Qb)]
        fapply = _factor_apply(blks_ref, dis_ref, pool_ref, kf)
        u_pred = fapply(0, u_rows)                    # psi at slot 0
        for j in range(Qb):
            tj = fapply(1 + j, h2[j])                 # pC_j at 1 + j
            u_pred = [a + b for a, b in zip(u_pred, tj)]
        for c in range(kf):
            o_ref[0, c] = u_pred[c]

    return kernel


def pick_block_d(D: int, block_d: int) -> tuple:
    """(block_d, Dp): the d-tile width and the padded extent it covers.

    The tile is the whole row when it fits (a block dim equal to the array
    dim is always legal), else the widest multiple of 128 lanes <= block_d
    that divides D — so a 32x32x3 row (D=3072) runs as 2 x 1536 with no
    padding — and only a D with no such divisor is padded up."""
    if D <= block_d:
        return D, D
    Dp = -(-D // 128) * 128
    for w in range(block_d // 128 * 128, 0, -128):
        if Dp % w == 0:
            return w, Dp
    raise ValueError(f"block_d={block_d} is narrower than one 128-lane tile")


def _pad_last(x, Dp: int):
    if x is None or x.shape[-1] == Dp:
        return x
    pad = [(0, 0)] * (x.ndim - 1) + [(0, Dp - x.shape[-1])]
    return jnp.pad(x, pad)


def _slot_smem(x):
    """Per-slot SMEM operand: (B, ...) -> (B, 1, prod(...)) with block
    (1, 1, prod(...)).  The unit axis makes the block's last two dims equal
    the array's, the only SMEM block shape the TPU compiler accepts for a
    one-row-per-grid-step scalar table."""
    B = x.shape[0]
    x = x.reshape(B, 1, -1)
    spec = pl.BlockSpec((1, 1, x.shape[-1]), lambda b, d: (b, 0, 0),
                        memory_space=pltpu.SMEM)
    return x, spec


@functools.partial(jax.jit, static_argnames=(
    "kf", "with_corrector", "gen_noise", "block_d", "interpret"))
def round_fused(ints, keys, blks, dis, pool, u, hist, eps_c,
                eps_n_c=None, noise_c=None, *, kf: int,
                with_corrector: bool = False, gen_noise: bool = True,
                block_d: int = 2048, interpret: bool = False):
    """One fused launch for the whole post-score-eval round commit.

    ints (B, N_INTS) int32 [kc, mine, stoch, use_c, alg]; keys (B, 2)
    uint32 raw PRNG keys; blks (B, C, kf, kf) stacked block factors (see
    module docstring for slot order); dis (B, C) int32 diag-pool ids;
    pool (Pb, D); u (B, K, D); hist (B, Qb, K, D); eps_c/eps_n_c/noise_c
    (B, kf, D).  Returns (u_next, hist_next)."""
    B, K, D = u.shape
    Qb = hist.shape[1]
    block_d, Dp = pick_block_d(D, block_d)
    u, hist, eps_c, eps_n_c, noise_c, pool = (
        _pad_last(x, Dp) for x in (u, hist, eps_c, eps_n_c, noise_c, pool))
    Pb = pool.shape[0]

    kernel = _make_round_kernel(
        kf=kf, K=K, Qb=Qb, D=D, block_d=block_d,
        with_corrector=with_corrector, gen_noise=gen_noise)

    smem = [_slot_smem(x) for x in (
        ints, jax.lax.bitcast_convert_type(keys, jnp.int32),
        blks.astype(jnp.float32), dis)]
    row = pl.BlockSpec((1, kf, block_d), lambda b, d: (b, 0, d))
    in_specs = [spec for _, spec in smem] + [
        pl.BlockSpec((Pb, block_d), lambda b, d: (0, d)),
        pl.BlockSpec((1, K, block_d), lambda b, d: (b, 0, d)),
        pl.BlockSpec((1, Qb, K, block_d), lambda b, d: (b, 0, 0, d)),
        row,
    ]
    args = [x for x, _ in smem] + [pool, u, hist, eps_c]
    if with_corrector:
        in_specs.append(row)
        args.append(eps_n_c)
    if not gen_noise:
        in_specs.append(row)
        args.append(noise_c)

    u2, h2 = pl.pallas_call(
        kernel,
        grid=(B, Dp // block_d),
        in_specs=in_specs,
        out_specs=[
            pl.BlockSpec((1, K, block_d), lambda b, d: (b, 0, d)),
            pl.BlockSpec((1, Qb, K, block_d), lambda b, d: (b, 0, 0, d)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((B, K, Dp), u.dtype),
            jax.ShapeDtypeStruct((B, Qb, K, Dp), hist.dtype),
        ],
        interpret=interpret,
    )(*args)
    return u2[..., :D], h2[..., :D]


@functools.partial(jax.jit, static_argnames=("kf", "block_d", "interpret"))
def round_predict(blks, dis, pool, u, hist, eps_c, *, kf: int,
                  block_d: int = 2048, interpret: bool = False):
    """Fused Eq. 19a predictor iterate (the corrector eval's input):
    blks (B, 1 + Qb, kf, kf) stacked [psi, pC_0..pC_{Qb-1}]; returns
    u_pred (B, kf, D)."""
    B, K, D = u.shape
    Qb = hist.shape[1]
    block_d, Dp = pick_block_d(D, block_d)
    u, hist, eps_c, pool = (_pad_last(x, Dp)
                            for x in (u, hist, eps_c, pool))
    Pb = pool.shape[0]
    (blks, blk_spec), (dis, dis_spec) = (
        _slot_smem(blks.astype(jnp.float32)), _slot_smem(dis))

    out = pl.pallas_call(
        _make_predict_kernel(kf=kf, Qb=Qb),
        grid=(B, Dp // block_d),
        in_specs=[
            blk_spec, dis_spec,
            pl.BlockSpec((Pb, block_d), lambda b, d: (0, d)),
            pl.BlockSpec((1, K, block_d), lambda b, d: (b, 0, d)),
            pl.BlockSpec((1, Qb, K, block_d), lambda b, d: (b, 0, 0, d)),
            pl.BlockSpec((1, kf, block_d), lambda b, d: (b, 0, d)),
        ],
        out_specs=pl.BlockSpec((1, kf, block_d), lambda b, d: (b, 0, d)),
        out_shape=jax.ShapeDtypeStruct((B, kf, Dp), u.dtype),
        interpret=interpret,
    )(blks, dis, pool, u, hist, eps_c)
    return out[..., :D]
