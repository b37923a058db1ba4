"""Deviceless TPU v5e compiles of the serve round's Pallas kernels at full
width (the 32x32x3 CIFAR row, D=3072; the DiT's 256-token, 12-head
attention).

Interpret mode checks what a kernel computes, not whether the chip's
compiler takes it: it accepted SMEM blocks that the v5e compiler refuses.
Each case here lowers and compiles the kernel for a described v5e chip
(nothing runs), asserts that the Mosaic kernel is in the program
(`tpu_custom_call`) and reads its `memory_analysis()`.

The topology is described inside a fixture, never at import: only one
process at a time may load the TPU compiler library, and every test
worker imports this file.
"""
import numpy as np
import pytest
import jax
import jax.numpy as jnp

B = 32                       # slots: the batch chip_smoke.py serves
D = 32 * 32 * 3              # CIFAR row in the canonical (B, K, D) layout
K, QB, POOL = 2, 2, 64       # k_max (CLD), multistep bucket, diag pool rows


@pytest.fixture(scope="module")
def topo():
    from jax.experimental import topologies
    try:
        return topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.fixture(scope="module")
def one_chip(topo):
    """A sharding on one described v5e chip, with the persistent compile
    cache off meanwhile: an entry written for a described chip cannot be
    read back without one."""
    from jax.experimental.compilation_cache import compilation_cache
    from jax.sharding import SingleDeviceSharding
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield SingleDeviceSharding(topo.devices[0])
    jax.config.update("jax_enable_compilation_cache", was)
    compilation_cache.reset_cache()


def _compile(fn, shapes, sharding):
    args = [jax.ShapeDtypeStruct(s, dt, sharding=sharding)
            for s, dt in shapes]
    compiled = jax.jit(fn).lower(*args).compile()
    assert 'custom_call_target="tpu_custom_call"' in compiled.as_text()
    mem = compiled.memory_analysis()
    assert mem.argument_size_in_bytes >= sum(
        int(np.prod(s)) * np.dtype(dt).itemsize for s, dt in shapes)
    return compiled


# (kf, gen_noise): VPSDE draws its Eq. 22 noise in the kernel, CLD too at
# kf=2, BDM streams it in (its canonical basis is a DCT)
ROUND_CASES = [(1, True), (2, True), (1, False)]


@pytest.mark.parametrize("corrector", [False, True],
                         ids=["predictor", "corrector"])
@pytest.mark.parametrize("kf,gen_noise", ROUND_CASES,
                         ids=["vpsde", "cld", "bdm"])
def test_round_fused_compiles_for_v5e(one_chip, kf, gen_noise, corrector):
    from repro.kernels.round_fused.kernel import N_INTS, round_fused
    C = 3 + QB + (QB if corrector else 0)
    f32 = jnp.float32
    shapes = [((B, N_INTS), jnp.int32), ((B, 2), jnp.uint32),
              ((B, C, kf, kf), f32), ((B, C), jnp.int32), ((POOL, D), f32),
              ((B, K, D), f32), ((B, QB, K, D), f32), ((B, kf, D), f32)]
    extra = []
    if corrector:
        extra.append("eps_n_c")
    if not gen_noise:
        extra.append("noise_c")
    shapes += [((B, kf, D), f32)] * len(extra)

    def launch(*a):
        return round_fused(*a[:8], **dict(zip(extra, a[8:])), kf=kf,
                           with_corrector=corrector, gen_noise=gen_noise)

    _compile(launch, shapes, one_chip)


def test_round_predict_compiles_for_v5e(one_chip):
    from repro.kernels.round_fused.kernel import round_predict
    f32 = jnp.float32
    _compile(lambda *a: round_predict(*a, kf=2),
             [((B, 1 + QB, 2, 2), f32), ((B, 1 + QB), jnp.int32),
              ((POOL, D), f32), ((B, K, D), f32), ((B, QB, K, D), f32),
              ((B, 2, D), f32)], one_chip)


def test_flash_attention_compiles_for_v5e(one_chip):
    from repro.kernels.attention.kernel import flash_attention
    qkv = ((B, 256, 12, 64), jnp.bfloat16)
    _compile(lambda q, k, v: flash_attention(q, k, v, causal=False),
             [qkv] * 3, one_chip)


@pytest.mark.parametrize("inverse", [False, True], ids=["dct", "idct"])
def test_dct2_compiles_for_v5e(one_chip, inverse):
    from repro.kernels.dct2.kernel import dct2
    _compile(lambda x: dct2(x, inverse=inverse),
             [((B, 32, 32, 3), jnp.float32)], one_chip)
