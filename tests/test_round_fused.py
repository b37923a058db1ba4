"""Differential lockdown of the fused round megakernel (the PR-8 tier).

The fused post-score-eval update (`kernels/round_fused`) replaces the
XLA-stitched chain the engine ran through PR 7.  The old chain survives as
`make_diffusion_round_step_stitched`, and this suite locks the swap at
three levels, mirroring the PR-5 factored-bank lockdown:

  1. coefficient level — `ops._stage_factors`'s stacked SMEM slots are
     exactly the stitched chain's per-term gathers (same rows, same
     diag-pool ids, slot for slot);
  2. round-step level — `make_diffusion_round_step` (ref impl: the CPU
     serving path) is BITWISE equal to the stitched step on co-resident
     mixed-config states, across family x q x corrector x stochastic,
     including frozen other-family / retired slots;
  3. engine level — a mixed-family serve on the fused-step engine equals,
     bitwise per request, the same engine running the stitched steps
     (staggered admission, retire-and-refill, q=2, corrector, lambda>0).

The Pallas kernel itself is parity-tested in tests/test_kernels.py
(bitwise for kf=1 families; the CLD kf=2 block contraction is allowed the
documented one-rounding FMA gap — see `apply_factored_ref`'s docstring).
"""
import functools
import zlib

import numpy as np
import jax
import jax.numpy as jnp
import pytest

from repro.core import CoeffCache, SamplerConfig
from repro.launch.steps import (make_diffusion_round_step,
                                make_diffusion_round_step_stitched)
from repro.sde import BDM, VPSDE
from repro.serve.state import DiffusionState
from repro.kernels.round_fused import ops as rf_ops
from suite_support import default_cld

DATA_SHAPE = (4, 4, 3)
FAMILIES = ["vpsde", "cld", "bdm"]


@functools.lru_cache(maxsize=1)
def _bank_parts():
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
            # the PR-10 algorithm axis rides the same differential: accel
            # widens its rows to effective q=2, gmm transforms P_chol and
            # the noise law — both must track the stitched chain bitwise
            SamplerConfig(nfe=4, algorithm="accel"),
            SamplerConfig(nfe=6, lam=0.7, algorithm="gmm"),
            SamplerConfig(nfe=3, family="bdm", lam=0.5, algorithm="gmm"),
            SamplerConfig(nfe=5, family="cld", algorithm="accel")]
    idx = [cache.index_of(c) for c in cfgs]
    return cache, cfgs, idx, cache.factored_bank


class _ToySpec:
    """Cheap deterministic eps model: the differential isolates the
    post-eval chain, not the net."""

    def __init__(self, sde, data_shape):
        self.sde = sde
        self.data_shape = tuple(data_shape)

    def eps_model(self, params, u, t):
        tb = t.reshape((-1,) + (1,) * (u.ndim - 1)).astype(u.dtype)
        return jnp.tanh(u) * (0.5 + tb)


def _mixed_state(fam, B, seed, *, other_retired=False):
    """A co-resident state: B slots of `fam` (cycled over its configs)
    plus one slot of another family and one retired slot — the step must
    freeze both verbatim."""
    cache, cfgs, idx, bank = _bank_parts()
    rng = np.random.default_rng(seed)
    K, D = cache.k_max, int(np.prod(DATA_SHAPE))
    Qb = bank.pC_blk.shape[2]
    slots = [(c, cfg) for c, cfg in zip(idx, cfgs)
             if cache.resolve(cfg) == fam]
    other = [(c, cfg) for c, cfg in zip(idx, cfgs)
             if cache.resolve(cfg) != fam][0]
    rows = [slots[i % len(slots)] for i in range(B)] + [other, slots[0]]
    Bt = len(rows)
    fam_ids = [cache.fam_index(cache.resolve(cfg)) for _, cfg in rows]
    active = [True] * (Bt - 1) + [False]
    return DiffusionState(
        u=jnp.asarray(rng.standard_normal((Bt, K, D)), jnp.float32),
        hist=jnp.asarray(rng.standard_normal((Bt, Qb, K, D)), jnp.float32),
        k=jnp.asarray(rng.integers(0, 4, Bt), jnp.int32),
        cfg=jnp.asarray([c for c, _ in rows], jnp.int32),
        fam=jnp.asarray(fam_ids, jnp.int32),
        prec=jnp.zeros((Bt,), jnp.int32),
        keys=jnp.asarray(rng.integers(0, 2**32, (Bt, 2), dtype=np.uint64),
                         jnp.uint32),
        active=jnp.asarray(active))


# ---------------------------------------------------------------------------
# level 1: the staged SMEM factor slots ARE the stitched chain's gathers
# ---------------------------------------------------------------------------
@pytest.mark.parametrize("fam,kf", [("vpsde", 1), ("cld", 2), ("bdm", 1)])
@pytest.mark.parametrize("with_corrector", [False, True])
def test_staged_factors_equal_stitched_gathers(fam, kf, with_corrector):
    cache, cfgs, idx, bank = _bank_parts()
    state = _mixed_state(fam, 3, zlib.crc32(fam.encode()) % 997)
    kc = jnp.clip(state.k, 0, bank.n_steps[state.cfg] - 1)
    blks, dis = rf_ops._stage_factors(bank, state.cfg, kc, kf,
                                      with_corrector)
    Qb = bank.pC_blk.shape[2]
    names = [("psi", None), ("B", None), ("P_chol", None)] \
        + [("pC", j) for j in range(Qb)] \
        + ([("cC", j) for j in range(Qb)] if with_corrector else [])
    assert blks.shape[1] == len(names) == dis.shape[1]
    for s, (nm, j) in enumerate(names):
        blk = getattr(bank, nm + "_blk")[state.cfg, kc]
        di = getattr(bank, nm + "_di")[state.cfg, kc]
        if j is not None:
            blk, di = blk[:, j], di[:, j]
        np.testing.assert_array_equal(np.asarray(blks[:, s]),
                                      np.asarray(blk[:, :kf, :kf]))
        np.testing.assert_array_equal(np.asarray(dis[:, s]),
                                      np.asarray(di))


# ---------------------------------------------------------------------------
# level 2: fused round step == stitched round step, bitwise
# ---------------------------------------------------------------------------
@pytest.mark.parametrize("fam", FAMILIES)
@pytest.mark.parametrize("with_corrector", [False, True])
def test_round_step_bitwise_equals_stitched(fam, with_corrector):
    cache, cfgs, idx, bank = _bank_parts()
    spec = _ToySpec(cache.sdes[fam], DATA_SHAPE)
    fi = cache.fam_index(fam)
    step_f = jax.jit(make_diffusion_round_step(spec, fam_index=fi),
                     static_argnames=("with_corrector",))
    step_s = jax.jit(make_diffusion_round_step_stitched(spec, fam_index=fi),
                     static_argnames=("with_corrector",))
    seed = zlib.crc32(repr((fam, with_corrector)).encode()) % 997
    state = _mixed_state(fam, 4, seed)
    out_f = step_f(None, state, bank, with_corrector=with_corrector)
    out_s = step_s(None, state, bank, with_corrector=with_corrector)
    for nm, a, b in zip(DiffusionState._fields, out_f, out_s):
        np.testing.assert_array_equal(
            np.asarray(a), np.asarray(b),
            err_msg=f"{fam} corr={with_corrector}: fused {nm} != stitched")


def test_round_step_chains_bitwise_over_trajectory():
    """Not just one step: iterating the fused step from admission to
    retirement tracks the stitched chain bitwise the whole way (the
    history shift / k-advance / retire feedback loop is exact too)."""
    cache, cfgs, idx, bank = _bank_parts()
    spec = _ToySpec(cache.sdes["vpsde"], DATA_SHAPE)
    fi = cache.fam_index("vpsde")
    step_f = jax.jit(make_diffusion_round_step(spec, fam_index=fi),
                     static_argnames=("with_corrector",))
    step_s = jax.jit(make_diffusion_round_step_stitched(spec, fam_index=fi),
                     static_argnames=("with_corrector",))
    state = _mixed_state("vpsde", 3, 11)
    state = state._replace(k=jnp.zeros_like(state.k))
    sf = ss = state
    for _ in range(7):                       # past the nfe=4 retirements
        sf = step_f(None, sf, bank, with_corrector=False)
        ss = step_s(None, ss, bank, with_corrector=False)
    for nm, a, b in zip(DiffusionState._fields, sf, ss):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b),
                                      err_msg=f"trajectory {nm} diverged")


# ---------------------------------------------------------------------------
# level 3: fused-step engine == stitched-step engine, end to end
# ---------------------------------------------------------------------------
def _stitched_engine(specs, params, **kw):
    """A DiffusionEngine whose round variants run the PRE-FUSION chain —
    the end-to-end oracle (f32 only: the stitched chain predates the
    precision axis)."""
    from repro.serve import DiffusionEngine
    from repro.serve.engine import _jit_state_update
    eng = DiffusionEngine(specs, params, **kw)
    eng._steps = {
        (n, "f32"): _jit_state_update(
            make_diffusion_round_step_stitched(
                s, fam_index=eng.cache.fam_index(n)),
            (1,), eng._state_sh, static_argnames=("with_corrector",))
        for n, s in eng.specs.items()}
    return eng


def test_mixed_family_serve_bitwise_equals_stitched_engine():
    from repro.configs import get_diffusion
    from repro.serve import DiffusionEngine, SampleRequest
    specs, params = {}, {}
    for i, (fam, name) in enumerate((("vpsde", "cifar10-ddpm"),
                                     ("cld", "cifar10-cld"),
                                     ("bdm", "cifar10-bdm"))):
        specs[fam] = get_diffusion(name, reduced=True)
        params[fam] = specs[fam].init(jax.random.PRNGKey(100 + i))
    reqs = [SampleRequest(rid=0, seed=0),                          # vpsde
            SampleRequest(rid=1, seed=1, family="cld", nfe=5),
            SampleRequest(rid=2, seed=2, family="bdm", nfe=4),
            SampleRequest(rid=3, seed=3, family="cld", nfe=6, q=2,
                          corrector=True),
            SampleRequest(rid=4, seed=4, family="vpsde", nfe=8, lam=0.5)]
    out = DiffusionEngine(specs, params, batch_size=2, nfe=6).serve(reqs)
    ref = _stitched_engine(specs, params, batch_size=2, nfe=6).serve(reqs)
    assert set(out) == set(ref) == {r.rid for r in reqs}
    for rid in sorted(out):
        np.testing.assert_array_equal(
            out[rid], ref[rid],
            err_msg=f"rid {rid}: fused engine != stitched engine")
