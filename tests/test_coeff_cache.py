"""Stage-I coefficient cache: memoization, bank stacking, and bucketing.

The cache is the host half of heterogeneous-config serving: Stage-I
quadrature runs once per distinct (sde family, grid, NFE, q, corrector,
lambda) key, and the stacked `CoeffBank` pads every entry to shared
bucketed shapes so the device step program is reused across any traffic
mix (see tests/test_serve_engine.py for the engine-level lockdown).
"""
import numpy as np
import pytest

from dense_reference import pack_coeff
from repro.core import (CoeffCache, SamplerConfig, bucket_size,
                        build_sampler_coeffs, time_grid)
from repro.core.coeffs import (C_BUCKET_MIN, DIAG_BUCKET_MIN, N_BUCKET_MIN,
                               Q_BUCKET_MIN)
from repro.sde import VPSDE, BDM
from suite_support import default_cld


def test_cache_hit_returns_identical_bank_object():
    cache = CoeffCache(VPSDE())
    cfg = SamplerConfig(nfe=6, q=2)
    co1 = cache.get(cfg)
    co2 = cache.get(SamplerConfig(nfe=6, q=2))    # equal key, fresh object
    assert co1 is co2
    # a different key is a different bank
    assert cache.get(SamplerConfig(nfe=6, q=1)) is not co1
    assert cache.get(SamplerConfig(nfe=6, q=2, grid="uniform")) is not co1


def test_cached_coeffs_match_direct_stage1():
    sde = VPSDE()
    cache = CoeffCache(sde)
    cfg = SamplerConfig(nfe=5, q=2)
    co = cache.get(cfg)
    ref = build_sampler_coeffs(sde, time_grid(sde, 5), q=2)
    for a, b in zip(co[:-1], ref[:-1]):           # skip the lam float
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))


def test_index_of_is_stable_and_len_counts_configs():
    cache = CoeffCache(VPSDE())
    a = cache.index_of(SamplerConfig(nfe=4))
    b = cache.index_of(SamplerConfig(nfe=8, q=2))
    assert (a, b) == (0, 1)
    assert cache.index_of(SamplerConfig(nfe=4)) == 0      # hit, no growth
    assert len(cache) == 2


def test_bank_rows_reproduce_unstacked_coeffs():
    """Bank slot c must carry config c's Stage-I arrays verbatim, padded
    with zero coefficients (so out-of-order terms vanish) beyond N_c/q_c."""
    sde = VPSDE()
    cache = CoeffCache(sde)
    cfgs = [SamplerConfig(nfe=4), SamplerConfig(nfe=6, q=2),
            SamplerConfig(nfe=5, lam=0.5)]
    idx = [cache.index_of(c) for c in cfgs]
    bank = cache.bank

    for c, cfg in zip(idx, cfgs):
        co = cache.get(cfg)
        N, q = cfg.nfe, cfg.q
        ts = np.asarray(co.ts)
        np.testing.assert_array_equal(np.asarray(bank.psi[c, :N]),
                                      np.asarray(co.psi))
        np.testing.assert_array_equal(np.asarray(bank.pC[c, :N, :q]),
                                      np.asarray(co.pC))
        np.testing.assert_array_equal(np.asarray(bank.cC[c, :N, :q]),
                                      np.asarray(co.cC))
        np.testing.assert_array_equal(np.asarray(bank.B[c, :N]),
                                      np.asarray(co.B))
        np.testing.assert_array_equal(np.asarray(bank.P_chol[c, :N]),
                                      np.asarray(co.P_chol))
        # time rows follow the step convention k: t_i with i = N - k
        np.testing.assert_array_equal(np.asarray(bank.t_cur[c, :N]),
                                      ts[N - np.arange(N)])
        np.testing.assert_array_equal(np.asarray(bank.t_nxt[c, :N]),
                                      ts[N - 1 - np.arange(N)])
        assert int(bank.n_steps[c]) == N
        assert bool(bank.stochastic[c]) == (cfg.lam > 0)
        # padding beyond N_c is zero coefficients
        assert not np.asarray(bank.pC[c, N:]).any()
        assert not np.asarray(bank.pC[c, :N, q:]).any()


def test_bank_bucket_shapes_and_stability():
    cache = CoeffCache(VPSDE())
    cache.index_of(SamplerConfig(nfe=5, q=2))
    bank = cache.bank
    Cb, Nb, Qb = bank.shape_key
    assert Cb == C_BUCKET_MIN and Nb == N_BUCKET_MIN and Qb == Q_BUCKET_MIN

    # anything inside the buckets reuses the shape (same compiled step)
    cache.index_of(SamplerConfig(nfe=8))
    cache.index_of(SamplerConfig(nfe=3, corrector=True))
    assert cache.bank.shape_key == (Cb, Nb, Qb)

    # overflow doubles only the overflowing axis
    cache.index_of(SamplerConfig(nfe=2 * N_BUCKET_MIN - 1))
    assert cache.bank.shape_key == (Cb, 2 * N_BUCKET_MIN, Qb)


def test_bucket_size():
    assert bucket_size(1, 8) == 8
    assert bucket_size(8, 8) == 8
    assert bucket_size(9, 8) == 16
    assert bucket_size(33, 8) == 64


def test_bank_works_for_block_family(cld):
    """CLD's (2,2) block coefficients stack with trailing coeff dims."""
    cache = CoeffCache(cld)
    cache.index_of(SamplerConfig(nfe=4, q=2))
    bank = cache.bank
    assert bank.psi.shape[2:] == (2, 2)
    assert bank.pC.shape[3:] == (2, 2)


@pytest.mark.parametrize("bad", [
    dict(nfe=0),
    dict(nfe=4, q=0),
    dict(nfe=4, lam=-0.1),
    dict(nfe=4, lam=0.5, q=2),             # stochastic is single-step
    dict(nfe=4, lam=0.5, corrector=True),
    dict(nfe=4, grid="geometric"),
])
def test_sampler_config_validation(bad):
    with pytest.raises(ValueError):
        SamplerConfig(**bad)


# ---------------------------------------------------------------------------
# multi-family cache: one FactoredBank stacking VPSDE + CLD + BDM configs
# ---------------------------------------------------------------------------
DATA_SHAPE = (4, 4, 3)


def _multi_cache():
    return CoeffCache({"vpsde": VPSDE(), "cld": default_cld(),
                       "bdm": BDM(data_shape=DATA_SHAPE)},
                      data_shape=DATA_SHAPE)


def test_multi_family_keys_and_resolution():
    cache = _multi_cache()
    assert cache.families == ["vpsde", "cld", "bdm"]
    assert cache.default_family == "vpsde"
    assert cache.k_max == 2                        # CLD's (x, v) channels
    # an unset family resolves to the default and shares its slot with the
    # explicit spelling
    a = cache.index_of(SamplerConfig(nfe=4))
    b = cache.index_of(SamplerConfig(nfe=4, family="vpsde"))
    c = cache.index_of(SamplerConfig(nfe=4, family="cld"))
    assert a == b and a != c
    with pytest.raises(ValueError, match="family"):
        cache.resolve(SamplerConfig(nfe=4, family="edm"))


def test_multi_family_bank_requires_factored():
    cache = _multi_cache()
    cache.index_of(SamplerConfig(nfe=4))
    with pytest.raises(ValueError, match="factored_bank"):
        cache.bank                                  # family-native shapes
    bank = cache.factored_bank                      # canonical shapes work
    D = int(np.prod(DATA_SHAPE))
    assert bank.psi_blk.shape[2:] == (2, 2)
    assert bank.diag.shape[1] == D
    Cb, Nb = bank.psi_blk.shape[:2]
    Qb, Pb = bank.pC_blk.shape[2], bank.diag.shape[0]
    assert bank.shape_key == (Cb, Nb, Qb, 2, D, Pb)


def test_factored_bank_rows_embed_family_coeffs():
    """Materialized factored rows must be `pack_coeff` embeddings of the
    family-native Stage-I arrays, with `fam` recording each config's
    family index.  (The full bit-exact differential against the dense
    PR-4 bank lives in tests/test_factored_bank.py.)"""
    cache = _multi_cache()
    cfgs = [SamplerConfig(nfe=4),
            SamplerConfig(nfe=5, family="cld", q=2),
            SamplerConfig(nfe=4, family="bdm"),
            SamplerConfig(nfe=4, family="vpsde", lam=0.5)]
    idx = [cache.index_of(c) for c in cfgs]
    bank = cache.factored_bank
    K = cache.k_max
    for c, cfg in zip(idx, cfgs):
        name = cache.resolve(cfg)
        ops = cache.sdes[name].ops
        co = cache.get(cfg)
        assert int(bank.fam[c]) == cache.fam_index(name)
        assert int(bank.n_steps[c]) == cfg.nfe
        for k in range(cfg.nfe):
            np.testing.assert_allclose(
                bank.materialize("psi", c, k),
                pack_coeff(ops, np.asarray(co.psi, np.float64)[k],
                           DATA_SHAPE, K).astype(np.float32))
            for j in range(cfg.q):
                np.testing.assert_allclose(
                    bank.materialize("pC", c, k, j),
                    pack_coeff(ops, np.asarray(co.pC, np.float64)[k, j],
                               DATA_SHAPE, K).astype(np.float32))
            if cfg.lam > 0.0:                 # stochastic rows stay exact
                np.testing.assert_allclose(
                    bank.materialize("B", c, k),
                    pack_coeff(ops, np.asarray(co.B, np.float64)[k],
                               DATA_SHAPE, K).astype(np.float32))
            else:                             # Eq. 22 branch masked off
                assert not bank.materialize("B", c, k).any()
        # padding beyond this config's rows is zero (block factor zero)
        assert not np.asarray(bank.pC_blk[c, cfg.nfe:]).any()
        assert not np.asarray(bank.pC_blk[c, :cfg.nfe, cfg.q:]).any()


def test_single_family_cache_keeps_native_bank(cld):
    """Back-compat: a single-family cache still exposes the family-native
    CoeffBank AND (given data_shape) the factored bank."""
    cache = CoeffCache(cld, data_shape=DATA_SHAPE)
    cache.index_of(SamplerConfig(nfe=4))
    assert cache.bank.psi.shape[2:] == (2, 2)
    D = int(np.prod(DATA_SHAPE))
    bank = cache.factored_bank
    assert bank.psi_blk.shape[2:] == (2, 2)
    # a pure scalar/block cache needs only the shared all-ones pool row
    assert bank.diag.shape == (DIAG_BUCKET_MIN, D)
    assert cache.sde is cache.sdes["cld"]


def test_factored_bank_registration_is_incremental():
    """Satellite lockdown: registration appends factored rows (memoized
    per config) instead of restacking the whole bank; only a bucket
    overflow re-pads every row.  `bank_restack_rows` counts the rows
    (re)written — the deterministic counter the perf guard gates."""
    cache = _multi_cache()
    cache.index_of(SamplerConfig(nfe=4))
    b1 = cache.factored_bank
    assert cache.bank_restack_rows == 1
    assert cache.factored_bank is b1          # no growth -> identical obj

    # three more configs inside every bucket: pure appends (3 new rows),
    # and the already-registered config is NOT rewritten
    for cfg in (SamplerConfig(nfe=8), SamplerConfig(nfe=6, q=2),
                SamplerConfig(nfe=5, family="cld")):
        cache.index_of(cfg)
    b2 = cache.factored_bank
    assert b2 is not b1
    assert cache.bank_restack_rows == 4
    assert b2.shape_key == b1.shape_key

    # C-bucket overflow (5th config): every row re-padded once
    cache.index_of(SamplerConfig(nfe=3))
    b3 = cache.factored_bank
    assert cache.bank_restack_rows == 4 + 5
    assert b3.shape_key != b2.shape_key

    # a first-seen BDM config appends rows AND grows the diag pool; the
    # block/index layout is untouched (no re-pad of existing rows)
    cache.index_of(SamplerConfig(nfe=4, family="bdm"))
    b4 = cache.factored_bank
    assert cache.bank_restack_rows == 4 + 5 + 1
    assert b4.diag.shape[0] > b3.diag.shape[0]


def test_kt_mapping_must_cover_families(cld):
    with pytest.raises(ValueError, match="missing"):
        CoeffCache({"vpsde": VPSDE(), "cld": cld}, kt={"vpsde": "R"})
