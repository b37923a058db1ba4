"""Per-kernel validation: Pallas (interpret=True) vs pure-jnp ref oracles,
swept over shapes and dtypes (assignment deliverable (c))."""
import functools

import numpy as np
import jax
import jax.numpy as jnp
import pytest

from suite_support import default_cld
from repro.kernels.attention.ref import attention_ref
from repro.kernels.attention.kernel import _head_group, attention_plan, \
    flash_attention, flash_attention_tiled
from repro.kernels.attention.ops import blocked_attention
from repro.kernels.decode_attention.ref import decode_attention_ref
from repro.kernels.decode_attention.kernel import decode_attention
from repro.kernels.ei_update.ref import ei_update_ref
from repro.kernels.ei_update.kernel import ei_update
from repro.kernels.dct2 import ref as dct_ref
from repro.kernels.dct2 import kernel as dct_kernel


def tol(dtype):
    return dict(rtol=2e-2, atol=2e-2) if dtype == jnp.bfloat16 else \
        dict(rtol=2e-5, atol=2e-5)


# ---------------------------------------------------------------------------
# flash attention
# ---------------------------------------------------------------------------
ATTN_CASES = [
    # (B, Sq, Sk, Hq, Hkv, Dh, causal, window, q_off, block_q, block_k)
    (1, 128, 128, 4, 4, 32, True, None, 0, 64, 64),
    (2, 128, 128, 8, 2, 64, True, None, 0, 128, 64),     # GQA
    (1, 256, 256, 4, 1, 32, True, 64, 0, 64, 64),        # MQA + window
    (1, 128, 128, 2, 2, 32, False, None, 0, 64, 64),     # bidirectional
    (2, 64, 256, 4, 2, 32, True, None, 192, 64, 64),     # offset (chunked)
    (4, 256, 256, 12, 12, 64, False, None, 0, 128, 128), # DiT head layout
    (2, 128, 128, 8, 2, 64, False, None, 0, 128, 64),    # GQA, bidirectional
    (7, 256, 256, 12, 12, 64, False, None, 0, 128, 128), # prime batch: bt=1
    (1, 2048, 2048, 1, 1, 32, False, None, 0, 256, 256), # too long: tiled
    (2, 128, 128, 8, 8, 32, False, None, 0, 64, 64),     # 4 heads a window
    (2, 1, 1500, 8, 8, 64, False, None, 0, 128, 128),    # whisper x-attn, decode
    (2, 7, 1500, 8, 8, 64, False, None, 0, 128, 128),    # whisper x-attn, prompt
]


@pytest.mark.parametrize("case", ATTN_CASES)
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_flash_attention_matches_ref(case, dtype):
    B, Sq, Sk, Hq, Hkv, Dh, causal, window, q_off, bq, bk = case
    ks = jax.random.split(jax.random.PRNGKey(0), 3)
    q = jax.random.normal(ks[0], (B, Sq, Hq, Dh), dtype)
    k = jax.random.normal(ks[1], (B, Sk, Hkv, Dh), dtype)
    v = jax.random.normal(ks[2], (B, Sk, Hkv, Dh), dtype)
    ref = attention_ref(q, k, v, causal=causal, window=window, q_offset=q_off)
    out = flash_attention(q, k, v, causal=causal, window=window,
                          q_offset=q_off, block_q=bq, block_k=bk,
                          interpret=True)
    np.testing.assert_allclose(np.asarray(out, np.float32),
                               np.asarray(ref, np.float32), **tol(dtype))


@pytest.mark.parametrize("Sq", [1, 7])
def test_flash_attention_bf16_q_f32_kv_matches_ref(Sq):
    """whisper-base's cross-attention as served: bf16 queries against the
    float32 encoder memory's keys and values."""
    ks = jax.random.split(jax.random.PRNGKey(1), 3)
    q = jax.random.normal(ks[0], (2, Sq, 8, 64), jnp.bfloat16)
    k = jax.random.normal(ks[1], (2, 1500, 8, 64), jnp.float32)
    v = jax.random.normal(ks[2], (2, 1500, 8, 64), jnp.float32)
    ref = attention_ref(q, k, v, causal=False)
    out = flash_attention(q, k, v, causal=False, interpret=True)
    assert out.dtype == jnp.bfloat16
    np.testing.assert_allclose(np.asarray(out, np.float32),
                               np.asarray(ref, np.float32),
                               **tol(jnp.bfloat16))


@pytest.mark.parametrize("case", [c for c in ATTN_CASES if c[6]])
def test_flash_attention_causal_dispatch_is_the_tiled_kernel(case):
    """Causal, windowed and offset calls run the tiled kernel unchanged."""
    B, Sq, Sk, Hq, Hkv, Dh, causal, window, q_off, bq, bk = case
    ks = jax.random.split(jax.random.PRNGKey(2), 3)
    q = jax.random.normal(ks[0], (B, Sq, Hq, Dh), jnp.bfloat16)
    k = jax.random.normal(ks[1], (B, Sk, Hkv, Dh), jnp.bfloat16)
    v = jax.random.normal(ks[2], (B, Sk, Hkv, Dh), jnp.bfloat16)
    kw = dict(causal=causal, window=window, q_offset=q_off, block_q=bq,
              block_k=bk, interpret=True)
    tiled = jax.jit(functools.partial(flash_attention_tiled, **kw))
    np.testing.assert_array_equal(np.asarray(flash_attention(q, k, v, **kw)),
                                  np.asarray(tiled(q, k, v)))


DIT = (256, 256, 12, 12, 64)          # Sq, Sk, Hq, Hkv, Dh of a DiT-B/2 eval
XATTN = (1, 1500, 8, 8, 64)           # whisper-base's cross-attention, decode
PLAN_CASES = [
    # (B, (Sq, Sk, Hq, Hkv, Dh), itemsize, causal, window, budget, plan)
    (64, DIT, 2, False, None, None, ("whole", 4)),       # the backlog cells
    (32, DIT, 2, False, None, None, ("whole", 4)),       # chip_smoke.py
    (64, DIT, 4, False, None, None, ("whole", 2)),       # float32
    (7, DIT, 2, False, None, None, ("whole", 1)),        # prime batch
    (8, (256, 256, 12, 4, 64), 2, False, None, None, ("whole", 4)),  # GQA
    (1, (1024, 1024, 8, 8, 128), 2, True, None, None, ("tiled", 0)),
    (1, (1024, 1024, 8, 8, 128), 2, False, 256, None, ("tiled", 0)),
    (1, (4096, 4096, 8, 8, 128), 2, False, None, None, ("tiled", 0)),
    (64, DIT, 2, False, None, 2**21, ("tiled", 0)),      # bt=1 does not fit
    (8, XATTN, 2, False, None, None, ("whole", 2)),
    (8, XATTN, 4, False, None, None, ("whole", 1)),      # float32 k and v
    (8, (1500, 1500, 8, 8, 64), 2, False, None, None, ("tiled", 0)),  # encoder
]


@pytest.mark.parametrize("case", PLAN_CASES)
def test_attention_plan(case):
    B, (Sq, Sk, Hq, Hkv, Dh), itemsize, causal, window, budget, want = case
    kw = {} if budget is None else dict(vmem_budget=budget)
    assert attention_plan(B, Sq, Sk, Hq, Hkv, Dh, itemsize, causal=causal,
                          window=window, **kw) == want


@pytest.mark.parametrize("shape, group", [
    ((12, 12, 64), 2),          # DiT-B/2: two heads of 64 per 128 lanes
    ((8, 8, 32), 4),
    ((2, 2, 32), 1),            # fewer heads than a window holds
    ((12, 4, 64), 1),           # GQA: one head per window
    ((8, 8, 128), 1),
])
def test_head_group(shape, group):
    assert _head_group(*shape) == group


@pytest.mark.parametrize("case", ATTN_CASES[:3])
def test_blocked_attention_matches_ref(case):
    B, Sq, Sk, Hq, Hkv, Dh, causal, window, q_off, bq, bk = case
    ks = jax.random.split(jax.random.PRNGKey(1), 3)
    q = jax.random.normal(ks[0], (B, Sq, Hq, Dh))
    k = jax.random.normal(ks[1], (B, Sk, Hkv, Dh))
    v = jax.random.normal(ks[2], (B, Sk, Hkv, Dh))
    ref = attention_ref(q, k, v, causal=causal, window=window, q_offset=q_off)
    out = blocked_attention(q, k, v, causal=causal, window=window,
                            q_offset=q_off, block_k=64)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               rtol=2e-5, atol=2e-5)


# ---------------------------------------------------------------------------
# decode attention
# ---------------------------------------------------------------------------
@pytest.mark.parametrize("Hq,Hkv,Dh,S,clen,bk", [
    (8, 2, 32, 256, 100, 64),
    (4, 4, 64, 512, 511, 128),
    (4, 1, 32, 128, 1, 64),
    (16, 8, 64, 256, 256, 256),
])
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_decode_attention_matches_ref(Hq, Hkv, Dh, S, clen, bk, dtype):
    B = 2
    ks = jax.random.split(jax.random.PRNGKey(2), 3)
    q = jax.random.normal(ks[0], (B, Hq, Dh), dtype)
    k = jax.random.normal(ks[1], (B, S, Hkv, Dh), dtype)
    v = jax.random.normal(ks[2], (B, S, Hkv, Dh), dtype)
    cl = jnp.int32(clen)
    ref = decode_attention_ref(q, k, v, cl)
    out = decode_attention(q, k, v, cl, block_k=bk, interpret=True)
    np.testing.assert_allclose(np.asarray(out, np.float32),
                               np.asarray(ref, np.float32), **tol(dtype))


def test_decode_attention_skips_invalid_blocks():
    """cache_len = 0 -> fully masked -> zeros (not NaN)."""
    B, Hq, Hkv, Dh, S = 1, 2, 2, 32, 128
    q = jnp.ones((B, Hq, Dh))
    k = jnp.ones((B, S, Hkv, Dh))
    v = jnp.ones((B, S, Hkv, Dh))
    out = decode_attention(q, k, v, jnp.int32(0), block_k=64, interpret=True)
    assert np.isfinite(np.asarray(out)).all()
    np.testing.assert_allclose(np.asarray(out), 0.0)


@pytest.mark.parametrize("window", [None, 32, 64, 300])
def test_decode_attention_per_row_clen_and_window(window):
    """Continuous-batching paths of the Pallas kernel: per-row (B,)
    cache_len vectors (each row masks/skips at its own valid length) and
    sliding-window masking, against the jnp oracle in interpret mode."""
    B, Hq, Hkv, Dh, S = 4, 8, 2, 32, 256
    ks = jax.random.split(jax.random.PRNGKey(5), 3)
    q = jax.random.normal(ks[0], (B, Hq, Dh))
    k = jax.random.normal(ks[1], (B, S, Hkv, Dh))
    v = jax.random.normal(ks[2], (B, S, Hkv, Dh))
    clen = jnp.array([3, 64, 129, 256], jnp.int32)      # straddles blocks
    ref = decode_attention_ref(q, k, v, clen, window=window)
    out = decode_attention(q, k, v, clen, block_k=64, window=window,
                           interpret=True)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               rtol=2e-5, atol=2e-5)


# ---------------------------------------------------------------------------
# ei_update (fused gDDIM state update)
# ---------------------------------------------------------------------------
@pytest.mark.parametrize("B,k,D,q", [
    (2, 1, 128, 1), (2, 1, 2048, 3), (3, 2, 300, 2), (1, 2, 4096, 4),
])
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_ei_update_matches_ref(B, k, D, q, dtype):
    ks = jax.random.split(jax.random.PRNGKey(3), 4)
    u = jax.random.normal(ks[0], (B, k, D), dtype)
    eh = jax.random.normal(ks[1], (q, B, k, D), dtype)
    psi = jax.random.normal(ks[2], (k, k))
    C = jax.random.normal(ks[3], (q, k, k))
    ref = ei_update_ref(u, eh, psi, C)
    out = ei_update(u, eh, psi, C, block_d=256, interpret=True)
    np.testing.assert_allclose(np.asarray(out, np.float32),
                               np.asarray(ref, np.float32), **tol(dtype))


@pytest.mark.parametrize("B,k,D", [
    (2, 1, 128), (2, 1, 2048), (3, 2, 300), (1, 2, 4096),
])
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_apply_factored_kernel_matches_ref(B, k, D, dtype):
    """The fused factored-coefficient kernel (the FactoredBank's gather
    form: per-example block factor applied in VREGs + diagonal scale, one
    VMEM pass) against the reference two-contraction path."""
    from repro.kernels.ei_update.kernel import apply_factored
    from repro.kernels.ei_update.ref import apply_factored_ref
    ks = jax.random.split(jax.random.PRNGKey(11), 3)
    z = jax.random.normal(ks[0], (B, k, D), dtype)
    blk = jax.random.normal(ks[1], (B, k, k))
    diag = jax.random.normal(ks[2], (B, D))
    ref = apply_factored_ref(blk, diag.astype(dtype), z)
    out = apply_factored(blk, diag.astype(dtype), z, block_d=256,
                         interpret=True)
    np.testing.assert_allclose(np.asarray(out, np.float32),
                               np.asarray(ref, np.float32), **tol(dtype))


# ---------------------------------------------------------------------------
# dct2 + fused BDM update
# ---------------------------------------------------------------------------
@pytest.mark.parametrize("H,W,Ch", [(8, 8, 3), (16, 16, 1), (32, 32, 3), (16, 8, 2)])
def test_dct2_roundtrip_and_ref(H, W, Ch):
    x = jax.random.normal(jax.random.PRNGKey(4), (2, H, W, Ch))
    y = dct_kernel.dct2(x, interpret=True)
    np.testing.assert_allclose(np.asarray(y), np.asarray(dct_ref.dct2_ref(x)),
                               rtol=1e-5, atol=1e-5)
    back = dct_kernel.dct2(y, inverse=True, interpret=True)
    np.testing.assert_allclose(np.asarray(back), np.asarray(x),
                               rtol=1e-5, atol=1e-5)


@pytest.mark.parametrize("q", [1, 2, 3])
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_bdm_ei_update_matches_ref(q, dtype):
    B, H, W, Ch = 2, 16, 16, 3
    ks = jax.random.split(jax.random.PRNGKey(5), 4)
    u = jax.random.normal(ks[0], (B, H, W, Ch), dtype)
    eh = jax.random.normal(ks[1], (q, B, H, W, Ch), dtype)
    psi = jax.random.normal(ks[2], (H, W, 1))
    C = jax.random.normal(ks[3], (q, H, W, 1))
    ref = dct_ref.bdm_ei_update_ref(u, eh, psi, C)
    out = dct_kernel.bdm_ei_update(u, eh, psi, C, interpret=True)
    np.testing.assert_allclose(np.asarray(out, np.float32),
                               np.asarray(ref, np.float32), **tol(dtype))


def test_ei_update_is_the_gddim_step(cld):
    """The kernel reproduces one sample_gddim predictor step on CLD."""
    from repro.sde import GaussianMixture, ExactScore
    from repro.core import build_sampler_coeffs, time_grid
    sde = cld
    ts = time_grid(sde, 6)
    co = build_sampler_coeffs(sde, ts, q=2)
    mix = GaussianMixture(np.array([[0.4, -0.2]]), np.array([0.05]), np.array([1.0]))
    oracle = ExactScore(sde, mix)
    eps_fn, _ = oracle.eps_fn_for_grid(ts)
    u = sde.prior_sample(jax.random.PRNGKey(0), 4, (2,))   # (B, 2, 2)
    N = co.psi.shape[0]
    k = 0
    i = N - k
    e0 = eps_fn(u, jnp.int32(i))
    hist = jnp.stack([e0, jnp.zeros_like(e0)])             # q=2, warm start
    # reference step
    u_ref = sde.apply(co.psi[k], u) + sde.apply(co.pC[k, 0], hist[0]) \
        + sde.apply(co.pC[k, 1], hist[1])
    # kernel step (pack channel axis)
    from repro.kernels.ei_update.ops import pack_state, unpack_state
    up, shape = pack_state(u, 2)
    ep = jnp.stack([pack_state(h, 2)[0] for h in hist])
    out = ei_update(up, ep, co.psi[k], co.pC[k], interpret=True)
    np.testing.assert_allclose(np.asarray(unpack_state(out, shape)),
                               np.asarray(u_ref), rtol=1e-5, atol=1e-5)


# ---------------------------------------------------------------------------
# round_fused (the whole post-score-eval round commit, one launch)
# ---------------------------------------------------------------------------
@functools.cache
def _round_fused_parts():
    from repro.core import CoeffCache, SamplerConfig
    from repro.sde import VPSDE, BDM
    shape = (4, 4, 3)
    cache = CoeffCache({"vpsde": VPSDE(), "cld": default_cld(),
                        "bdm": BDM(data_shape=shape)},
                       data_shape=shape)
    cfgs = [SamplerConfig(nfe=4), SamplerConfig(nfe=5, q=2),
            SamplerConfig(nfe=6, lam=0.7),
            SamplerConfig(nfe=4, family="cld"),
            SamplerConfig(nfe=4, family="cld", q=2, corrector=True),
            SamplerConfig(nfe=5, family="cld", lam=0.5),
            SamplerConfig(nfe=4, family="bdm", q=2, corrector=True),
            SamplerConfig(nfe=3, family="bdm", lam=0.5)]
    idx = [cache.index_of(c) for c in cfgs]
    return cache, cfgs, idx, shape


# corners: family x q x corrector x stochastic — each case's slot list
# cycles the matching configs, so every case also mixes q/nfe per slot
ROUND_CASES = [
    # (family, want_q2, with_corrector, want_stochastic)
    ("vpsde", False, False, False),
    ("vpsde", True, False, False),
    ("vpsde", False, False, True),
    ("vpsde", True, True, True),
    ("cld", False, False, False),
    ("cld", True, True, False),
    ("cld", False, False, True),
    ("bdm", True, False, False),
    ("bdm", True, True, True),
]


@pytest.mark.parametrize("family,q2,corr,sto", ROUND_CASES)
def test_round_fused_kernel_matches_ref(family, q2, corr, sto):
    """One interpret-mode launch of the fused round commit vs the jitted
    reference chain: BITWISE for the kf=1 families (VPSDE/BDM — the
    in-kernel threefry/erf_inv noise draw reproduces the stitched
    fold_in draw exactly), and within one rounding of the CLD kf=2 block
    contraction (the ref einsum lowers to an FMA dot_general; see
    apply_factored_ref's docstring — same gap class as the
    `test_apply_factored_kernel_matches_ref` tolerance)."""
    from repro.kernels.round_fused import ops as rf
    cache, cfgs, idx, shape = _round_fused_parts()
    bank = cache.factored_bank
    sde = cache.sdes[family]
    kf, fi = sde.packed_k, cache.fam_index(family)
    K, D = cache.k_max, int(np.prod(shape))
    Qb = bank.pC_blk.shape[2]
    slots = [c for c, cfg in zip(idx, cfgs)
             if cache.resolve(cfg) == family
             and (cfg.q == 2) == q2 and (cfg.lam > 0) == sto] \
        or [c for c, cfg in zip(idx, cfgs) if cache.resolve(cfg) == family]
    B = 3
    rng = np.random.default_rng(
        abs(hash((family, q2, corr, sto))) % 99991)
    cfg_ids = jnp.asarray([slots[i % len(slots)] for i in range(B)],
                          jnp.int32)
    k = jnp.asarray(rng.integers(0, 5, B), jnp.int32)
    kc = jnp.clip(k, 0, bank.n_steps[cfg_ids] - 1)
    u = jnp.asarray(rng.standard_normal((B, K, D)), jnp.float32)
    hist = jnp.asarray(rng.standard_normal((B, Qb, K, D)), jnp.float32)
    eps_c = jnp.asarray(rng.standard_normal((B, kf, D)), jnp.float32)
    eps_n_c = jnp.asarray(rng.standard_normal((B, kf, D)), jnp.float32)
    keys = jnp.asarray(rng.integers(0, 2**32, (B, 2), dtype=np.uint64),
                       jnp.uint32)
    args = (u, hist, k, kc, cfg_ids, jnp.full((B,), fi, jnp.int32),
            jnp.zeros((B,), jnp.int32), keys,
            jnp.asarray([True, True, False]), bank, eps_c)
    call = functools.partial(
        rf.round_update, sde=sde, state_shape=sde.state_shape(shape),
        kf=kf, fam_index=fi, prec_index=0, with_corrector=corr)
    out_ref = jax.jit(functools.partial(call, impl="ref"))(
        *args, eps_n_c=eps_n_c)
    out_pl = call(*args, eps_n_c=eps_n_c, impl="pallas_interpret",
                  block_d=64)
    p_ref = jax.jit(functools.partial(rf.round_predict, kf=kf, impl="ref"))(
        u, hist, kc, cfg_ids, bank, eps_c)
    p_pl = rf.round_predict(u, hist, kc, cfg_ids, bank, eps_c, kf=kf,
                            impl="pallas_interpret", block_d=64)
    for nm, a, b in list(zip(("u", "hist", "k", "active"),
                             out_ref, out_pl)) + [("u_pred", p_ref, p_pl)]:
        a, b = np.asarray(a), np.asarray(b)
        if kf == 1:
            np.testing.assert_array_equal(
                a, b,
                err_msg=f"{family} q2={q2} corr={corr} sto={sto} {nm}: "
                        "kf=1 must be bitwise")
        else:
            np.testing.assert_allclose(
                a, b, rtol=1e-5, atol=1e-5,
                err_msg=f"{family} {nm}: beyond the kf=2 FMA gap")


@pytest.mark.parametrize("D,block_d,want", [
    (48, 2048, (48, 48)),          # whole row: one tile, no padding
    (3072, 2048, (1536, 3072)),    # CIFAR row: 2 x 1536, no padding
    (192, 128, (128, 256)),        # no 128-multiple divides 192: pad
])
def test_round_fused_block_d(D, block_d, want):
    """The d-tile is legal for the TPU compiler (the whole row, or a
    multiple of 128 lanes) and pads only when no such width divides D."""
    from repro.kernels.round_fused.kernel import pick_block_d
    assert pick_block_d(D, block_d) == want


_MESH_KERNELS = """
import functools
import numpy as np, jax, jax.numpy as jnp
from repro.core import CoeffCache, SamplerConfig
from repro.sde import VPSDE
from repro.kernels.attention import ops as attn_ops
from repro.kernels.dct2 import ops as dct_ops
from repro.kernels.round_fused import ops as rf
from repro.launch.mesh import make_local_mesh

shape, sde = (4, 4, 3), VPSDE()
cache = CoeffCache({"vpsde": sde}, data_shape=shape)
idx = [cache.index_of(SamplerConfig(nfe=4, q=2, corrector=True)),
       cache.index_of(SamplerConfig(nfe=6, lam=0.7))]
bank = cache.factored_bank
K, D, Qb = cache.k_max, int(np.prod(shape)), bank.pC_blk.shape[2]
pallas = dict(impl="pallas_interpret")

def launches(fn, *args):
    # leading output dim of every pallas_call in fn's jaxpr
    dims = []
    def walk(jaxpr):
        for e in jaxpr.eqns:
            if e.primitive.name == "pallas_call":
                dims.append(e.outvars[0].aval.shape[:3])
                continue
            for v in e.params.values():
                for j in (v if isinstance(v, (list, tuple)) else [v]):
                    j = getattr(j, "jaxpr", j)
                    if hasattr(j, "eqns"):
                        walk(j)
    walk(jax.make_jaxpr(fn)(*args).jaxpr)
    return dims

for B in (8, 1):
    rng = np.random.default_rng(B)
    normal = lambda *s: jnp.asarray(rng.standard_normal(s), jnp.float32)
    ci = jnp.asarray([idx[i % 2] for i in range(B)], jnp.int32)
    k = jnp.asarray(rng.integers(0, 4, B), jnp.int32)
    kc = jnp.clip(k, 0, bank.n_steps[ci] - 1)
    u, hist, eps = normal(B, K, D), normal(B, Qb, K, D), normal(B, 1, D)
    keys = jnp.asarray(rng.integers(0, 2**32, (B, 2), dtype=np.uint64),
                       jnp.uint32)
    cases = [
        ("round_update", lambda *a: rf.round_update(
            *a[:-1], eps_n_c=a[-1], sde=sde, kf=1, fam_index=0,
            state_shape=sde.state_shape(shape), with_corrector=True,
            **pallas),
         (u, hist, k, kc, ci, jnp.zeros((B,), jnp.int32),
          jnp.zeros((B,), jnp.int32), keys,
          jnp.asarray(rng.random(B) < 0.8), bank, eps, normal(B, 1, D))),
        ("round_predict", functools.partial(rf.round_predict, kf=1, **pallas),
         (u, hist, kc, ci, bank, eps)),
        ("dct2", functools.partial(dct_ops.dct2, **pallas),
         (normal(B, 3, 8, 8),)),
        ("attention", functools.partial(attn_ops.attention, causal=False,
                                        **pallas),
         (normal(B, 16, 4, 8), normal(B, 16, 4, 8), normal(B, 16, 4, 8))),
    ]
    for name, fn, args in cases:
        want = jax.jit(fn)(*args)
        for data, model in [(4, 1)] + [(2, 2)] * (name == "attention"):
            with make_local_mesh(data=data, model=model):
                got = jax.jit(fn)(*args)
                dims = launches(fn, *args)
            for a, b in zip(jax.tree.leaves(want), jax.tree.leaves(got)):
                np.testing.assert_array_equal(np.asarray(a), np.asarray(b),
                                              err_msg=name)
            print(name, B, data, model, dims)
"""


def test_round_fused_under_data_mesh_matches_single_device():
    """On 4 host devices, every Pallas call of the diffusion round
    (round_update with the corrector and in-kernel noise, round_predict,
    dct2, flash attention; interpret mode) launches through
    `per_batch_shard` once per batch shard and gives the plain launch's
    bits: B/4 rows per launch on a data=4 mesh, the whole row on every
    device when B=1 does not split, and attention's heads split over
    `model` on a data=2, model=2 mesh."""
    import ast
    import os
    import subprocess
    import sys
    root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    env = dict(os.environ, PYTHONPATH="src",
               XLA_FLAGS="--xla_force_host_platform_device_count=4")
    r = subprocess.run([sys.executable, "-c", _MESH_KERNELS],
                       capture_output=True, text=True, env=env, cwd=root)
    assert r.returncode == 0, r.stdout + r.stderr
    # per launch: leading dims of the first output (attention's is
    # (B, S, H*Dh), the others' lead with B)
    want = {(n, B, m): d for n, B, m, d in [
        ("round_update", 8, "4 1", (2,)), ("round_update", 1, "4 1", (1,)),
        ("round_predict", 8, "4 1", (2,)), ("round_predict", 1, "4 1", (1,)),
        ("dct2", 8, "4 1", (2,)), ("dct2", 1, "4 1", (1,)),
        ("attention", 8, "4 1", (2, 16, 32)),
        ("attention", 1, "4 1", (1, 16, 32)),
        ("attention", 8, "2 2", (4, 16, 16)),
        ("attention", 1, "2 2", (1, 16, 16))]}
    got = {}
    for line in r.stdout.splitlines():
        name, B, data, model, dims = line.split(" ", 4)
        got[(name, int(B), f"{data} {model}")] = ast.literal_eval(dims)
    assert set(got) == set(want), r.stdout
    for key, lead in want.items():
        assert len(got[key]) == 1, (key, got[key])
        assert got[key][0][:len(lead)] == lead, (key, got[key])
