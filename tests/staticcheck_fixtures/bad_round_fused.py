"""Layer-2 fixture: the REAL fused-round kernel body behind a broken
launch — the padding/divisibility contract `ops.round_fused` maintains
(pad D up to a block_d multiple, grid covers exactly the padded extent)
is deliberately dropped, so PL201 and PL202 must fire on the state
streams while the SMEM scalar operands stay exempt.

Traced by tests/test_staticcheck.py — never executed.  The clean control
for the same kernel is `round_fused.ops.staticcheck_entries()`, which
tools/staticcheck/menu.py feeds to the sanitizer.
"""
import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.kernels.round_fused.kernel import N_INTS, _make_round_kernel

_SMEM = pltpu.SMEM

# one vpsde-like slot class, but a ragged last tile: 32 does not divide
# D=48, and the 2-step d-axis grid walks the second tile off the array
_B, _K, _KF, _QB, _D, _PB, _BLK = 2, 1, 1, 1, 48, 4, 32
_C = 3 + _QB                       # [psi, B, P_chol, pC_0]


def bad_round_fused_trace():
    """The megakernel launched without `_pad_last`: block_d=32 on D=48
    (PL201) and grid (B, 2) whose second d-tile spans [32, 64) (PL202)."""
    kernel = _make_round_kernel(
        kf=_KF, K=_K, Qb=_QB, D=_D, block_d=_BLK,
        with_corrector=False, gen_noise=False)

    def launch(ints, keys, blks, dis, pool, u, hist, eps, noise):
        return pl.pallas_call(
            kernel,
            grid=(_B, 2),
            in_specs=[
                pl.BlockSpec((1, 1, N_INTS), lambda b, d: (b, 0, 0),
                             memory_space=_SMEM),
                pl.BlockSpec((1, 1, 2), lambda b, d: (b, 0, 0),
                             memory_space=_SMEM),
                pl.BlockSpec((1, 1, _C * _KF * _KF), lambda b, d: (b, 0, 0),
                             memory_space=_SMEM),
                pl.BlockSpec((1, 1, _C), lambda b, d: (b, 0, 0),
                             memory_space=_SMEM),
                pl.BlockSpec((_PB, _BLK), lambda b, d: (0, d)),
                pl.BlockSpec((1, _K, _BLK), lambda b, d: (b, 0, d)),
                pl.BlockSpec((1, _QB, _K, _BLK), lambda b, d: (b, 0, 0, d)),
                pl.BlockSpec((1, _KF, _BLK), lambda b, d: (b, 0, d)),
                pl.BlockSpec((1, _KF, _BLK), lambda b, d: (b, 0, d)),
            ],
            out_specs=[
                pl.BlockSpec((1, _K, _BLK), lambda b, d: (b, 0, d)),
                pl.BlockSpec((1, _QB, _K, _BLK), lambda b, d: (b, 0, 0, d)),
            ],
            out_shape=[
                jax.ShapeDtypeStruct((_B, _K, _D), jnp.float32),
                jax.ShapeDtypeStruct((_B, _QB, _K, _D), jnp.float32),
            ],
            interpret=True,
        )(ints, keys, blks, dis, pool, u, hist, eps, noise)

    return jax.make_jaxpr(launch)(
        jnp.zeros((_B, 1, N_INTS), jnp.int32),
        jnp.zeros((_B, 1, 2), jnp.int32),
        jnp.zeros((_B, 1, _C * _KF * _KF), jnp.float32),
        jnp.zeros((_B, 1, _C), jnp.int32),
        jnp.zeros((_PB, _D), jnp.float32),
        jnp.zeros((_B, _K, _D), jnp.float32),
        jnp.zeros((_B, _QB, _K, _D), jnp.float32),
        jnp.zeros((_B, _KF, _D), jnp.float32),
        jnp.zeros((_B, _KF, _D), jnp.float32))
