"""Differential lockdown of the sampler-coefficient layer (the bank tier).

The production bank is the exact *factored* form
(`repro.core.coeffs.FactoredBank`): every structured coefficient a (K, K)
block factor times a pooled (D,) diagonal factor, applied as two
contractions.  Its correctness story is differential, at three levels,
all **bit-exact**:

  1. coefficient level — `apply_factored(*factor_coeff(...))` equals the
     dense `apply_packed(pack_coeff(...))` einsum it replaced *and* the
     family-native `sde.apply`, for arbitrary coefficients of every family;
  2. bank level — `FactoredBank` rows materialize to the PR-4 dense
     `PackedBank` rows (tests/dense_reference.py), and one factored
     bank-mode serve step equals one dense bank step on the same state;
  3. engine level — a mixed VPSDE/CLD/BDM serve on the factored-bank
     engine is bitwise-identical per request to a PR-4 dense-bank engine
     (the lockstep `dense_reference_sample`).

The parametrized classes run everywhere (tier-1); the hypothesis classes
re-run the same checks over arbitrary family x K x data_shape x q x
corrector draws under the profile in tests/conftest.py (the CI
hypothesis job pins the larger derandomized `ci` budget).
"""
import functools
import zlib

import numpy as np
import jax
import jax.numpy as jnp
import pytest

import dense_reference
from repro.core import CoeffCache, SamplerConfig, factor_coeff
from repro.core.coeffs import DIAG_BUCKET_MIN, bucket_size
from repro.kernels.ei_update.ops import (apply_factored, apply_packed,
                                         pad_channels)
from repro.launch.steps import make_diffusion_serve_step
from repro.sde import BDM, VPSDE
from suite_support import default_cld

try:
    from hypothesis import given, strategies as st
    HAVE_HYPOTHESIS = True
except ImportError:
    HAVE_HYPOTHESIS = False

FAMILIES = ["vpsde", "cld", "bdm"]
SHAPES = [(6,), (3, 5), (4, 4, 3), (2, 3, 2, 2)]
DATA_SHAPE = (4, 4, 3)                 # the bank-level shared shape


def make_sde(family, data_shape):
    if family == "vpsde":
        return VPSDE()
    if family == "cld":
        return default_cld()
    return BDM(data_shape=tuple(data_shape))


def _raw_coeff(sde, rng):
    """A random coefficient in the family's native structured shape."""
    if sde.ops.family == "scalar":
        return np.float64(rng.standard_normal())
    if sde.ops.family == "block":
        return rng.standard_normal((2, 2))
    return rng.standard_normal(sde.ops.freq_shape)


# ---------------------------------------------------------------------------
# level 1: factored == dense == family-native, per coefficient
# ---------------------------------------------------------------------------
def _check_coeff_differential(family, pad, data_shape, B, seed):
    sde = make_sde(family, data_shape)
    rng = np.random.default_rng(seed)
    coeff = _raw_coeff(sde, rng)
    kf = sde.packed_k
    K = kf + pad
    D = int(np.prod(data_shape))

    u = jnp.asarray(rng.standard_normal(
        (B,) + sde.state_shape(tuple(data_shape))), jnp.float32)
    z = pad_channels(sde.canonicalize(u), K)

    dense = jnp.asarray(
        dense_reference.pack_coeff(sde.ops, coeff, data_shape, K),
        jnp.float32)
    blk64, diag64 = factor_coeff(sde.ops, coeff, data_shape, K)
    blk = jnp.asarray(blk64, jnp.float32)
    diag = jnp.ones((D,), jnp.float32) if diag64 is None \
        else jnp.asarray(diag64, jnp.float32)

    # the factored pair IS the dense embedding
    np.testing.assert_array_equal(
        np.asarray(blk)[..., None] * np.asarray(diag), np.asarray(dense))

    # kernel level: two contractions == one dense einsum, bitwise
    out_dense = apply_packed(jnp.broadcast_to(dense, (B,) + dense.shape), z)
    blk_b = jnp.broadcast_to(blk, (B, K, K))
    diag_b = jnp.broadcast_to(diag, (B, D))
    out_fact = apply_factored(blk_b, diag_b, z, impl="ref")
    np.testing.assert_array_equal(np.asarray(out_fact), np.asarray(out_dense),
                                  err_msg=f"{family}: factored != dense")
    # the Pallas kernel path (interpret mode off-TPU) computes the same op
    out_pallas = apply_factored(blk_b, diag_b, z, impl="pallas_interpret")
    np.testing.assert_allclose(np.asarray(out_pallas), np.asarray(out_fact),
                               rtol=1e-6, atol=1e-6,
                               err_msg=f"{family}: pallas != ref")

    # family-native level: sde.apply_factored vs sde.apply.  Bitwise for
    # scalar/freq-diagonal families; for block (CLD) the native einsum
    # lowers to a dot_general whose FMA contraction differs in the last
    # ulp from the multiply-reduce bank program — a property the dense
    # PR-4 bank had too, so the differential contract there is
    # tight-allclose native + bitwise vs the dense path.
    out_native = sde.apply(jnp.asarray(np.asarray(coeff, np.float32)), u)
    out_fact_native = sde.apply_factored(blk, diag, u)
    if sde.ops.family == "block":
        np.testing.assert_allclose(
            np.asarray(out_fact_native), np.asarray(out_native),
            rtol=1e-6, atol=1e-6,
            err_msg=f"{family}: factored != native sde.apply")
    else:
        np.testing.assert_array_equal(
            np.asarray(out_fact_native), np.asarray(out_native),
            err_msg=f"{family}: factored != native sde.apply")

    if sde.ops.family != "freqdiag":
        # pixel-basis families: the canonical bank path IS the native-basis
        # factored application — bitwise at matching channel width (the
        # serve path always compares same-K programs); with extra padding
        # rows XLA may reassociate the wider reduce, so ulp-tight there
        got = np.asarray(out_fact[:, :kf]).reshape(out_fact_native.shape)
        if pad == 0:
            np.testing.assert_array_equal(got, np.asarray(out_fact_native))
        else:
            np.testing.assert_allclose(got, np.asarray(out_fact_native),
                                       rtol=1e-6, atol=1e-6)


class TestFactoredCoeffDifferential:
    @pytest.mark.parametrize("family", FAMILIES)
    @pytest.mark.parametrize("pad", [0, 1])
    @pytest.mark.parametrize("data_shape", SHAPES)
    def test_factored_equals_dense_equals_native(self, family, pad,
                                                 data_shape):
        # process-stable seed (python's hash() is salted per run)
        seed = zlib.crc32(repr((family, pad, data_shape)).encode()) % 997
        _check_coeff_differential(family, pad, data_shape, B=2, seed=seed)

    def test_zero_freqdiag_collapses_to_zero_block(self):
        sde = make_sde("bdm", DATA_SHAPE)
        blk, diag = factor_coeff(sde.ops, np.zeros(sde.ops.freq_shape),
                                 DATA_SHAPE, 2)
        assert diag is None and not blk.any()


# ---------------------------------------------------------------------------
# level 2: FactoredBank rows / serve step vs the PR-4 dense bank
# ---------------------------------------------------------------------------
@functools.lru_cache(maxsize=1)
def _bank_parts():
    """One multi-family cache (all families, q/corrector/stochastic configs)
    with both its factored bank and the dense oracle bank."""
    cache = CoeffCache({"vpsde": VPSDE(), "cld": default_cld(),
                        "bdm": BDM(data_shape=DATA_SHAPE)},
                       data_shape=DATA_SHAPE)
    cfgs = [SamplerConfig(nfe=4),
            SamplerConfig(nfe=5, q=2),
            SamplerConfig(nfe=4, family="cld"),
            SamplerConfig(nfe=4, family="cld", q=2, corrector=True),
            SamplerConfig(nfe=4, family="bdm"),
            SamplerConfig(nfe=4, family="bdm", q=2, corrector=True),
            SamplerConfig(nfe=6, lam=0.7),
            SamplerConfig(nfe=3, family="bdm", lam=0.5),
            # PR-10 algorithm axis: accel widens rows to effective q=2,
            # gmm scales P_chol — both must materialize to the dense
            # oracle's rows (which embed the same transformed stacks)
            SamplerConfig(nfe=4, algorithm="accel"),
            SamplerConfig(nfe=6, lam=0.7, algorithm="gmm"),
            SamplerConfig(nfe=3, family="bdm", lam=0.5, algorithm="gmm"),
            SamplerConfig(nfe=5, family="cld", algorithm="accel")]
    idx = [cache.index_of(c) for c in cfgs]
    return cache, cfgs, idx, cache.factored_bank, \
        dense_reference.build_dense_bank(cache)


class _ToySpec:
    """Minimal DiffusionSpec stand-in: a cheap deterministic eps model so
    the step differential isolates the bank arithmetic."""

    def __init__(self, sde, data_shape):
        self.sde = sde
        self.data_shape = tuple(data_shape)

    def eps_model(self, params, u, t):
        tb = t.reshape((-1,) + (1,) * (u.ndim - 1)).astype(u.dtype)
        return jnp.tanh(u) * (0.5 + tb)


def _family_slots(fam):
    cache, cfgs, idx, _, _ = _bank_parts()
    return [(c, cfg) for c, cfg in zip(idx, cfgs)
            if cache.resolve(cfg) == fam]


def _check_bank_step(fam, with_corrector, B, seed):
    cache, cfgs, idx, fbank, dbank = _bank_parts()
    sde = cache.sdes[fam]
    spec = _ToySpec(sde, DATA_SHAPE)
    step_f = make_diffusion_serve_step(spec)
    step_d = dense_reference.make_dense_bank_step(spec)

    rng = np.random.default_rng(seed)
    K = cache.k_max
    D = int(np.prod(DATA_SHAPE))
    Qb = fbank.pC_blk.shape[2]
    slots = _family_slots(fam)
    rows = [slots[i % len(slots)] for i in range(B)]
    cfg_ids = jnp.asarray([c for c, _ in rows], jnp.int32)
    # mix of in-range and clipped step indices
    k = jnp.asarray(rng.integers(0, 7, B), jnp.int32)
    u = jnp.asarray(rng.standard_normal((B, K, D)), jnp.float32)
    hist = jnp.asarray(rng.standard_normal((B, Qb, K, D)), jnp.float32)
    keys = jnp.asarray(rng.integers(0, 2**32, (B, 2), dtype=np.uint64),
                       jnp.uint32)

    uf, hf = step_f(None, u, hist, k, cfg_ids, keys, fbank,
                    with_corrector=with_corrector)
    ud, hd = step_d(None, u, hist, k, cfg_ids, keys, dbank,
                    with_corrector=with_corrector)
    np.testing.assert_array_equal(np.asarray(uf), np.asarray(ud),
                                  err_msg=f"{fam}: factored step != dense")
    np.testing.assert_array_equal(np.asarray(hf), np.asarray(hd))


class TestFactoredBankDifferential:
    def test_bank_rows_materialize_to_dense_rows(self):
        cache, cfgs, idx, fbank, dbank = _bank_parts()
        from repro.core.coeffs import effective_q
        for c, cfg in zip(idx, cfgs):
            N, q = cfg.nfe, effective_q(cfg)
            assert int(fbank.n_steps[c]) == int(dbank.n_steps[c]) == N
            assert bool(fbank.stochastic[c]) == bool(dbank.stochastic[c])
            assert bool(fbank.corrector[c]) == bool(dbank.corrector[c])
            assert int(fbank.fam[c]) == int(dbank.fam[c])
            assert int(fbank.alg[c]) == int(dbank.alg[c])
            for k in range(N):
                np.testing.assert_array_equal(
                    fbank.materialize("psi", c, k), np.asarray(dbank.psi[c, k]))
                for j in range(q):
                    np.testing.assert_array_equal(
                        fbank.materialize("pC", c, k, j),
                        np.asarray(dbank.pC[c, k, j]))
                    np.testing.assert_array_equal(
                        fbank.materialize("cC", c, k, j),
                        np.asarray(dbank.cC[c, k, j]))
                if cfg.lam > 0.0:
                    np.testing.assert_array_equal(
                        fbank.materialize("B", c, k),
                        np.asarray(dbank.B[c, k]))
                    np.testing.assert_array_equal(
                        fbank.materialize("P_chol", c, k),
                        np.asarray(dbank.P_chol[c, k]))
                else:
                    # deterministic configs store zero B/P factors: the
                    # Eq. 22 branch is masked off (observationally exact)
                    assert not fbank.materialize("B", c, k).any()
                    assert not fbank.materialize("P_chol", c, k).any()
        np.testing.assert_array_equal(np.asarray(fbank.t_cur),
                                      np.asarray(dbank.t_cur))
        np.testing.assert_array_equal(np.asarray(fbank.t_nxt),
                                      np.asarray(dbank.t_nxt))

    def test_diag_pool_is_deduplicated(self):
        """Scalar/block rows all share pool row 0 (ones); only freqdiag
        rows occupy real slots, so the pool stays far below the dense
        row-slot count and the bank wins ~D-fold."""
        cache, cfgs, idx, fbank, dbank = _bank_parts()
        np.testing.assert_array_equal(np.asarray(fbank.diag[0]), 1.0)
        bdm_rows = sum(cfg.nfe * (1 + 2 * cfg.q) + 2 * cfg.nfe * (cfg.lam > 0)
                       for cfg in cfgs if cache.resolve(cfg) == "bdm")
        assert fbank.diag.shape[0] == bucket_size(
            len(cache._pool), DIAG_BUCKET_MIN)
        assert len(cache._pool) <= 1 + bdm_rows
        # non-BDM index leaves all point at the shared ones row
        for c, cfg in zip(idx, cfgs):
            if cache.resolve(cfg) != "bdm":
                assert not np.asarray(fbank.psi_di[c]).any()
        assert fbank.nbytes * 10 < fbank.dense_equiv_nbytes

    @pytest.mark.parametrize("fam", FAMILIES)
    @pytest.mark.parametrize("with_corrector", [False, True])
    def test_bank_step_matches_dense_step(self, fam, with_corrector):
        seed = zlib.crc32(repr((fam, with_corrector)).encode()) % 997
        _check_bank_step(fam, with_corrector, B=3, seed=seed)


# ---------------------------------------------------------------------------
# level 3: the factored-bank engine == a PR-4 dense-bank engine, end to end
# ---------------------------------------------------------------------------
@pytest.fixture(scope="module")
def family_parts():
    from repro.configs import get_diffusion
    specs, params = {}, {}
    for i, (fam, name) in enumerate((("vpsde", "cifar10-ddpm"),
                                     ("cld", "cifar10-cld"),
                                     ("bdm", "cifar10-bdm"))):
        specs[fam] = get_diffusion(name, reduced=True)
        params[fam] = specs[fam].init(jax.random.PRNGKey(100 + i))
    return specs, params


def test_mixed_family_serve_bitwise_equals_dense_reference(family_parts):
    """End to end: a mixed VPSDE/CLD/BDM serve (staggered admission,
    co-residency, q=2 multistep, corrector, stochastic lambda) through the
    factored-bank engine must reproduce, bitwise per request, what the
    PR-4 dense-bank engine computed (the lockstep dense reference)."""
    from repro.serve import DiffusionEngine, SampleRequest
    specs, params = family_parts
    reqs = [SampleRequest(rid=0, seed=0),                          # vpsde
            SampleRequest(rid=1, seed=1, family="cld", nfe=5),
            SampleRequest(rid=2, seed=2, family="bdm", nfe=4),
            SampleRequest(rid=3, seed=3, family="cld", nfe=6, q=2,
                          corrector=True),
            SampleRequest(rid=4, seed=4, family="vpsde", nfe=8, lam=0.5),
            SampleRequest(rid=5, seed=5, family="bdm", nfe=3, lam=0.5),
            SampleRequest(rid=6, seed=6, algorithm="accel"),
            SampleRequest(rid=7, seed=7, nfe=8, lam=0.5, algorithm="gmm")]
    engine = DiffusionEngine(specs, params, batch_size=2, nfe=6)
    out = engine.serve(reqs)
    assert set(out) == {r.rid for r in reqs}

    dbank = dense_reference.build_dense_bank(engine.cache)
    for r in reqs:
        cfg = engine.config_of(r)
        ref = dense_reference.dense_reference_sample(
            specs[cfg.family], params[cfg.family], engine.cache, dbank,
            cfg, r.seed, batch=engine.batch_size)
        np.testing.assert_array_equal(
            out[r.rid], ref,
            err_msg=f"rid {r.rid} ({cfg.family}): factored engine != "
                    "PR-4 dense-bank reference")


# ---------------------------------------------------------------------------
# hypothesis tier: same checks over arbitrary draws (CI pins profile `ci`)
# ---------------------------------------------------------------------------
if not HAVE_HYPOTHESIS:
    def test_hypothesis_tier_skipped():
        pytest.skip("hypothesis not installed (optional dev dependency, "
                    "see requirements-dev.txt); the differential tier "
                    "still ran via the parametrized classes above")
else:
    shapes_st = st.lists(st.integers(min_value=1, max_value=5),
                         min_size=1, max_size=4).map(tuple)

    # settings (budget, deadline, health checks) come entirely from the
    # active profile registered in tests/conftest.py
    class TestHypothesisCoeffDifferential:
        @given(family=st.sampled_from(FAMILIES),
               pad=st.integers(min_value=0, max_value=2),
               data_shape=shapes_st,
               B=st.integers(min_value=1, max_value=3),
               seed=st.integers(min_value=0, max_value=2**30))
        def test_factored_equals_dense_equals_native(self, family, pad,
                                                     data_shape, B, seed):
            _check_coeff_differential(family, pad, data_shape, B, seed)

    class TestHypothesisBankStepDifferential:
        @given(fam=st.sampled_from(FAMILIES),
               with_corrector=st.booleans(),
               B=st.integers(min_value=1, max_value=4),
               seed=st.integers(min_value=0, max_value=2**30))
        def test_bank_step_matches_dense_step(self, fam, with_corrector,
                                              B, seed):
            _check_bank_step(fam, with_corrector, B, seed)
