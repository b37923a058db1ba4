"""Property-based tests (hypothesis) over the system's invariants."""
import functools

import numpy as np
import jax
import jax.numpy as jnp
import pytest

pytest.importorskip(
    "hypothesis",
    reason="optional dev dependency (see requirements-dev.txt); "
           "property tests degrade to a skip without it")
from hypothesis import given, settings, strategies as st, HealthCheck

from repro.sde import VPSDE, BDM
from suite_support import default_cld
from repro.models import rwkv6, common
from repro.kernels.ei_update.ref import ei_update_ref
from repro.kernels.ei_update.kernel import ei_update

# example budget comes from the active hypothesis profile (tests/conftest.py:
# `dev` = small local budget, `ci` = the CI job's pinned derandomized budget)
SLOW = dict(deadline=None,
            suppress_health_check=[HealthCheck.too_slow])

ts_strategy = st.floats(min_value=1e-3, max_value=0.999)


class TestSDEInvariants:
    @given(t=ts_strategy)
    @settings(**SLOW)
    def test_cld_R_factorizes_sigma(self, cld, t):
        R = cld.R_np(t)
        S = cld.Sigma_np(t)
        np.testing.assert_allclose(R @ R.T, S, rtol=1e-4, atol=1e-8)

    @given(t=ts_strategy, s=ts_strategy, r=ts_strategy)
    @settings(**SLOW)
    def test_cld_psi_group_property(self, cld, t, s, r):
        lhs = cld.Psi_np(t, s) @ cld.Psi_np(s, r)
        rhs = cld.Psi_np(t, r)
        np.testing.assert_allclose(lhs, rhs, rtol=1e-6, atol=1e-10)

    @given(t=ts_strategy, s=ts_strategy)
    @settings(**SLOW)
    def test_bdm_psi_group_property(self, t, s):
        sde = BDM(data_shape=(8, 8, 1))
        lhs = sde.Psi_np(t, s) * sde.Psi_np(s, 0.5)
        rhs = sde.Psi_np(t, 0.5)
        np.testing.assert_allclose(lhs, rhs, rtol=1e-6, atol=1e-10)

    @given(t=ts_strategy)
    @settings(**SLOW)
    def test_vpsde_eq17(self, t):
        """dR/dt == (F + G2/(2 Sigma)) R (the paper's Eq. 17), FD check."""
        sde = VPSDE()
        h = 1e-6
        t = min(max(t, 1e-3 + h), 0.999 - h)
        dR = (sde.R_np(t + h) - sde.R_np(t - h)) / (2 * h)
        rhs = (sde.F_np(t) + 0.5 * sde.G2_np(t) / sde.Sigma_np(t)) * sde.R_np(t)
        np.testing.assert_allclose(dR, rhs, rtol=1e-3)

    @given(t=st.floats(min_value=0.05, max_value=0.95))
    @settings(**SLOW)
    def test_bdm_g2_nonnegative(self, t):
        sde = BDM(data_shape=(8, 8, 1))
        assert (sde.G2_np(t) >= 0).all()


class TestRecurrenceProperties:
    @given(
        s_chunks=st.integers(min_value=1, max_value=4),
        chunk=st.sampled_from([8, 16]),
        h=st.integers(min_value=1, max_value=3),
        dk=st.sampled_from([4, 8]),
        seed=st.integers(min_value=0, max_value=2**30),
    )
    @settings(**SLOW)
    def test_rwkv_chunked_equals_sequential(self, s_chunks, chunk, h, dk, seed):
        B, S = 1, s_chunks * chunk
        ks = jax.random.split(jax.random.PRNGKey(seed), 5)
        r = jax.random.normal(ks[0], (B, S, h, dk))
        k = jax.random.normal(ks[1], (B, S, h, dk))
        v = jax.random.normal(ks[2], (B, S, h, dk))
        w_log = -jnp.exp(jax.random.normal(ks[3], (B, S, h, dk)))
        u = jax.random.normal(ks[4], (h, dk)) * 0.5
        y1, s1 = rwkv6.rwkv6_chunked(r, k, v, w_log, u, chunk=chunk)
        y2, s2 = rwkv6.rwkv6_sequential(r, k, v, w_log, u)
        np.testing.assert_allclose(np.asarray(y1), np.asarray(y2),
                                   rtol=1e-3, atol=1e-3)

    @given(
        e=st.sampled_from([4, 8]),
        k=st.integers(min_value=1, max_value=3),
        seed=st.integers(min_value=0, max_value=2**30),
    )
    @settings(**SLOW)
    def test_moe_sorted_equals_dense(self, e, k, seed):
        B, S, D = 1, 8, 16
        p = common.moe_params(jax.random.PRNGKey(seed), D, 32, e, jnp.float32)
        x = jax.random.normal(jax.random.PRNGKey(seed + 1), (B, S, D))
        dense = common.moe_apply(p, x, top_k=k)
        srt = common.moe_sorted_apply(p, x, top_k=k, capacity_factor=float(e))
        np.testing.assert_allclose(np.asarray(srt), np.asarray(dense),
                                   rtol=1e-4, atol=1e-4)


class TestKernelProperties:
    @given(
        B=st.integers(min_value=1, max_value=3),
        k=st.sampled_from([1, 2]),
        D=st.sampled_from([64, 100, 256]),
        q=st.integers(min_value=1, max_value=3),
        seed=st.integers(min_value=0, max_value=2**30),
    )
    @settings(**SLOW)
    def test_ei_update_kernel(self, B, k, D, q, seed):
        ks = jax.random.split(jax.random.PRNGKey(seed), 4)
        u = jax.random.normal(ks[0], (B, k, D))
        eh = jax.random.normal(ks[1], (q, B, k, D))
        psi = jax.random.normal(ks[2], (k, k))
        C = jax.random.normal(ks[3], (q, k, k))
        ref = ei_update_ref(u, eh, psi, C)
        out = ei_update(u, eh, psi, C, block_d=64, interpret=True)
        np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                                   rtol=1e-5, atol=1e-5)

    @given(
        B=st.integers(min_value=1, max_value=3),
        k=st.sampled_from([1, 2]),
        D=st.sampled_from([64, 100, 256]),
        seed=st.integers(min_value=0, max_value=2**30),
    )
    @settings(**SLOW)
    def test_apply_factored_kernel(self, B, k, D, seed):
        """The fused factored-coefficient Pallas kernel (block contraction
        + diagonal scale in one VMEM pass) matches the reference path."""
        from repro.kernels.ei_update.kernel import apply_factored
        from repro.kernels.ei_update.ref import apply_factored_ref
        ks = jax.random.split(jax.random.PRNGKey(seed), 3)
        z = jax.random.normal(ks[0], (B, k, D))
        blk = jax.random.normal(ks[1], (B, k, k))
        diag = jax.random.normal(ks[2], (B, D))
        ref = apply_factored_ref(blk, diag, z)
        out = apply_factored(blk, diag, z, block_d=64, interpret=True)
        np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                                   rtol=1e-5, atol=1e-5)


class TestPackingProperties:
    """The family-generic packing layer behind multi-family serving
    (kernels/ei_update/ops.py): canonical (B, k, D) layout + the dense
    embedded coefficient application."""

    @given(
        B=st.integers(min_value=1, max_value=3),
        k=st.sampled_from([1, 2]),
        pad=st.integers(min_value=0, max_value=2),
        data_shape=st.sampled_from([(4,), (8,), (3, 5), (4, 4, 3),
                                    (2, 3, 2, 2)]),
        seed=st.integers(min_value=0, max_value=2**30),
    )
    @settings(**SLOW)
    def test_pack_unpack_round_trip(self, B, k, pad, data_shape, seed):
        from repro.kernels.ei_update.ops import pack_state, unpack_state
        shape = (B,) + ((k,) if k > 1 else ()) + data_shape
        u = jax.random.normal(jax.random.PRNGKey(seed), shape)
        k_pad = k + pad
        z, orig = pack_state(u, k, k_pad=k_pad)
        D = int(np.prod(data_shape))
        assert z.shape == (B, k_pad, D)
        # padding rows are identically zero
        assert not np.asarray(z[:, k:]).any()
        np.testing.assert_array_equal(np.asarray(unpack_state(z, orig, k=k)),
                                      np.asarray(u))

    @given(
        B=st.integers(min_value=1, max_value=3),
        seed=st.integers(min_value=0, max_value=2**30),
        family=st.sampled_from(["scalar", "block", "freqdiag"]),
    )
    @settings(**SLOW)
    def test_factored_coeff_matches_family_native_apply(self, cld, B, seed,
                                                        family):
        """factor_coeff's (k_max, k_max)-block x pooled-(D,)-diagonal pair
        applied via apply_factored equals the family's native structured
        apply AND the dense embedding it replaced (the full bit-exact
        differential tier lives in tests/test_factored_bank.py)."""
        from dense_reference import pack_coeff
        from repro.core import factor_coeff
        from repro.kernels.ei_update.ops import (apply_factored,
                                                 apply_packed, pad_channels)
        data_shape, k_max = (4, 4, 3), 2
        D = int(np.prod(data_shape))
        rng = np.random.default_rng(seed)
        if family == "scalar":
            sde, coeff = VPSDE(), np.float64(rng.standard_normal())
        elif family == "block":
            sde, coeff = cld, rng.standard_normal((2, 2))
        else:
            sde = BDM(data_shape=data_shape)
            coeff = rng.standard_normal((4, 4, 1))
        u = jax.random.normal(jax.random.PRNGKey(seed),
                              (B,) + sde.state_shape(data_shape))
        ref = sde.apply(jnp.asarray(coeff, jnp.float32), u)
        blk64, diag64 = factor_coeff(sde.ops, coeff, data_shape, k_max)
        blk = jnp.broadcast_to(jnp.asarray(blk64, jnp.float32),
                               (B, k_max, k_max))
        diag = jnp.ones((D,), jnp.float32) if diag64 is None \
            else jnp.asarray(diag64, jnp.float32)
        diag = jnp.broadcast_to(diag, (B, D))
        # canonicalize (BDM: DCT basis), apply the factor pair, decanonicalize
        z = pad_channels(sde.canonicalize(u), k_max)
        out = apply_factored(blk, diag, z, impl="ref")
        got = sde.decanonicalize(out[:, :sde.packed_k], data_shape)
        np.testing.assert_allclose(np.asarray(got), np.asarray(ref),
                                   rtol=1e-4, atol=1e-5)
        # bitwise vs the dense oracle layout
        packed = jnp.asarray(pack_coeff(sde.ops, coeff, data_shape, k_max),
                             jnp.float32)
        dense = apply_packed(jnp.broadcast_to(packed, (B,) + packed.shape), z)
        np.testing.assert_array_equal(np.asarray(out), np.asarray(dense))


class TestSchedulerProperties:
    @given(
        seq=st.lists(st.tuples(st.sampled_from(["vpsde", "cld", "bdm"]),
                               st.booleans()),
                     min_size=0, max_size=24),
        free=st.integers(min_value=1, max_value=8),
        seed=st.integers(min_value=0, max_value=2**30),
    )
    @settings(**SLOW)
    def test_family_corrector_waves_never_mix_classes(self, seq, free, seed):
        """For ANY request order and free-slot budget, admission waves are
        homogeneous in the (family, corrector) cost class, FIFO order is
        preserved, and nothing is dropped or duplicated."""
        from repro.serve import SampleRequest, Scheduler
        sched = Scheduler(group_key=lambda r: (r.family, bool(r.corrector)))
        reqs = [SampleRequest(rid=i, family=f, corrector=c)
                for i, (f, c) in enumerate(seq)]
        sched.submit_all(reqs)
        admitted = []
        while sched.has_pending():
            wave = sched.take_group(free)
            assert wave, "pending queue must always yield a head wave"
            classes = {(r.family, bool(r.corrector)) for r in wave}
            assert len(classes) == 1, \
                f"wave mixed cost classes: {sorted(classes)}"
            assert len(wave) <= free
            admitted.extend(r.rid for r in wave)
        assert admitted == [r.rid for r in reqs]


class TestOnlineServingProperties:
    """serve_stream invariants (ISSUE 7), driven through the pure-host
    simulation rig (tests/sim_clock.py) — the same ServeLoop machinery
    the real engines inherit, so these run the actual admission /
    preemption / parking / poll code at hypothesis example counts."""

    @given(
        jobs=st.lists(st.tuples(st.integers(0, 20),     # arrival time
                                st.integers(1, 8)),     # work
                      min_size=1, max_size=3),
        seed=st.integers(min_value=0, max_value=2**30),
    )
    @settings(**SLOW)
    def test_no_starvation_when_capacity_suffices(self, jobs, seed):
        """With at most B concurrent requests (capacity always suffices),
        integer arrivals and unit rounds, a request is admitted the moment
        it arrives and completes at exactly t_arrival + work — so even the
        *tight* deadline t_arrival + work is always met, nothing is
        preempted, and nothing starves."""
        from tests.sim_clock import HostSimEngine, SimRequest, trace_of
        from repro.serve import serving_metrics
        eng = HostSimEngine(batch_size=3, sync_every=4, greedy=True)
        trace = trace_of(*[
            (float(t), SimRequest(rid=i, work=w, deadline=float(t + w)))
            for i, (t, w) in enumerate(jobs)])
        results = eng.serve_stream(trace)
        assert len(results) == len(jobs)
        assert eng.n_preemptions == 0
        for i, (t, w) in enumerate(jobs):
            timing = eng.request_log[i]
            assert timing.t_admit == float(t)
            assert timing.t_done == float(t + w)
            assert timing.met_slo
        assert serving_metrics(eng.request_log)["deadline_misses"] == 0

    @given(
        jobs=st.lists(st.tuples(st.integers(0, 12),     # arrival time
                                st.integers(1, 6),      # work
                                st.integers(0, 3),      # priority
                                st.sampled_from(["a", "b"])),
                      min_size=1, max_size=14),
        seed=st.integers(min_value=0, max_value=2**30),
    )
    @settings(**SLOW)
    def test_preemption_strictness_waves_and_drain(self, jobs, seed):
        """For ANY arrival stream over a 2-slot engine with mixed
        priorities and cost classes:

          * every preemption evicts a *strictly* lower-priority victim
            (equal priority never churns),
          * every admission wave — preemption-driven or not — is
            homogeneous in the cost class,
          * every suspension is eventually resumed and every request
            completes (the parking table drains; no starvation by churn),
          * the replay is deterministic (same stream -> same logs)."""
        from tests.sim_clock import HostSimEngine, SimRequest, trace_of

        def run():
            eng = HostSimEngine(batch_size=2, sync_every=4)
            trace = trace_of(*[
                (float(t), SimRequest(rid=i, work=w, priority=p, cls=c))
                for i, (t, w, p, c) in enumerate(jobs)])
            return eng, eng.serve_stream(trace)

        eng, results = run()
        assert set(results) == set(range(len(jobs)))
        for preemptor_rid, p_prio, victim_rid, v_prio in eng.preemption_log:
            assert v_prio < p_prio, eng.preemption_log
        for wave in eng.wave_log:
            assert len(set(wave)) == 1, eng.wave_log
        assert eng.n_resumes == eng.n_preemptions
        assert len(eng.parking) == 0
        for i, (t, w, p, c) in enumerate(jobs):
            assert int(results[i]) == w      # full work done exactly once
        eng2, results2 = run()
        assert results == results2
        assert eng.preemption_log == eng2.preemption_log
        assert eng.wave_log == eng2.wave_log
        assert eng.request_log == eng2.request_log

    @given(
        B=st.integers(min_value=1, max_value=4),
        extra_dims=st.lists(st.sampled_from([(), (3,), (2, 4), (5,)]),
                            min_size=1, max_size=4),
        seed=st.integers(min_value=0, max_value=2**30),
    )
    @settings(**SLOW)
    def test_parked_row_save_restore_round_trips_pytrees(self, B,
                                                         extra_dims, seed):
        """row_fetch -> host -> row_restore is the bitwise identity on the
        written row of an arbitrary batch-leading pytree (mixed dtypes,
        mixed ranks) and leaves every other row of the destination
        untouched — the generic mechanism preemption parking rides."""
        from repro.serve import row_fetch, row_restore
        rng = np.random.default_rng(seed)
        dtypes = [np.float32, np.int32, np.bool_, np.uint32]

        def tree_of(rng):
            leaves = {}
            for li, dims in enumerate(extra_dims):
                dt = dtypes[li % len(dtypes)]
                raw = rng.standard_normal((B,) + dims) * 100
                leaves[f"leaf{li}"] = jnp.asarray(raw.astype(dt))
            return {"nested": leaves, "flat": jnp.asarray(
                rng.integers(0, 2**31, size=(B,)).astype(np.int32))}

        src = tree_of(rng)
        dst = tree_of(rng)
        i = int(rng.integers(0, B))
        j = int(rng.integers(0, B))
        payload = jax.device_get(row_fetch(src, np.int32(i)))  # like park()
        restored = row_restore(dst, payload, np.int32(j))
        flat_src = jax.tree.leaves(src)
        flat_dst = jax.tree.leaves(dst)
        flat_out = jax.tree.leaves(restored)
        for s, d, o in zip(flat_src, flat_dst, flat_out):
            np.testing.assert_array_equal(np.asarray(o[j]), np.asarray(s[i]))
            for b in range(B):
                if b != j:
                    np.testing.assert_array_equal(np.asarray(o[b]),
                                                  np.asarray(d[b]))


class TestDataProperties:
    @given(step=st.integers(min_value=0, max_value=10_000),
           seed=st.integers(min_value=0, max_value=2**30))
    @settings(**SLOW)
    def test_token_pipeline_pure_function_of_step(self, step, seed):
        from repro.data.pipeline import TokenPipeline
        p1 = TokenPipeline(vocab=64, seq_len=8, global_batch=2, seed=seed)
        p2 = TokenPipeline(vocab=64, seq_len=8, global_batch=2, seed=seed)
        a, _ = p1.batch_at(step)
        b, _ = p2.batch_at(step)
        np.testing.assert_array_equal(a, b)
        assert (a >= 0).all() and (a < 64).all()


class TestCoeffProperties:
    @given(n=st.integers(min_value=2, max_value=12),
           seed=st.integers(min_value=0, max_value=2**30))
    @settings(**SLOW)
    def test_vpsde_gddim_coeff_matches_ddim(self, n, seed):
        """Prop 2 as a property: for any grid size, the q=1 quadrature
        coefficient equals the closed-form DDIM coefficient."""
        from repro.core import build_sampler_coeffs, time_grid, \
            ddim_closed_form_check
        sde = VPSDE()
        ts = time_grid(sde, n)
        co = build_sampler_coeffs(sde, ts, q=1)
        ddim = ddim_closed_form_check(sde, ts)
        np.testing.assert_allclose(np.asarray(co.pC[:, 0]), ddim,
                                   rtol=1e-4, atol=1e-6)


class TestRoundFusedProperties:
    """The fused round megakernel (kernels/round_fused) as a property,
    mirroring `test_apply_factored_kernel`: for any family / batch /
    corrector flag / seed, one interpret-mode launch of the commit kernel
    reproduces the jitted reference chain — bitwise for the kf=1 families
    (VPSDE/BDM, in-kernel threefry noise included), and within one
    rounding of the kf=2 (CLD) block contraction (the ref's XLA-lowered
    dot_general accumulates with FMA; see `apply_factored_ref`)."""

    @staticmethod
    @functools.cache
    def _parts():
        from repro.core import CoeffCache, SamplerConfig
        shape = (4, 4, 3)
        cache = CoeffCache({"vpsde": VPSDE(), "cld": default_cld(),
                            "bdm": BDM(data_shape=shape)},
                           data_shape=shape)
        cfgs = [SamplerConfig(nfe=4), SamplerConfig(nfe=5, q=2),
                SamplerConfig(nfe=6, lam=0.7),
                SamplerConfig(nfe=4, family="cld"),
                SamplerConfig(nfe=4, family="cld", q=2, corrector=True),
                SamplerConfig(nfe=4, family="bdm", q=2),
                SamplerConfig(nfe=3, family="bdm", lam=0.5)]
        idx = [cache.index_of(c) for c in cfgs]
        return cache, cfgs, idx, shape

    @given(
        B=st.integers(min_value=1, max_value=3),
        family=st.sampled_from(["vpsde", "cld", "bdm"]),
        with_corrector=st.booleans(),
        seed=st.integers(min_value=0, max_value=2**30),
    )
    @settings(**SLOW)
    def test_round_fused_kernel_matches_ref(self, B, family, with_corrector,
                                            seed):
        from repro.kernels.round_fused import ops as rf
        cache, cfgs, idx, shape = self._parts()
        bank = cache.factored_bank
        sde = cache.sdes[family]
        kf = sde.packed_k
        fi = cache.fam_index(family)
        K, D = cache.k_max, int(np.prod(shape))
        Qb = bank.pC_blk.shape[2]
        slots = [c for c, cfg in zip(idx, cfgs)
                 if cache.resolve(cfg) == family]
        rng = np.random.default_rng(seed)
        cfg_ids = jnp.asarray(rng.choice(slots, B), jnp.int32)
        k = jnp.asarray(rng.integers(0, 5, B), jnp.int32)
        kc = jnp.clip(k, 0, bank.n_steps[cfg_ids] - 1)
        u = jnp.asarray(rng.standard_normal((B, K, D)), jnp.float32)
        hist = jnp.asarray(rng.standard_normal((B, Qb, K, D)), jnp.float32)
        eps_c = jnp.asarray(rng.standard_normal((B, kf, D)), jnp.float32)
        eps_n_c = jnp.asarray(rng.standard_normal((B, kf, D)), jnp.float32)
        keys = jnp.asarray(rng.integers(0, 2**32, (B, 2), dtype=np.uint64),
                           jnp.uint32)
        fam_ids = jnp.full((B,), fi, jnp.int32)
        prec = jnp.zeros((B,), jnp.int32)
        active = jnp.asarray(rng.integers(0, 2, B, dtype=np.int64) > 0)
        call = functools.partial(
            rf.round_update, sde=sde, state_shape=sde.state_shape(shape),
            kf=kf, fam_index=fi, prec_index=0,
            with_corrector=with_corrector)
        out_ref = jax.jit(functools.partial(call, impl="ref"))(
            u, hist, k, kc, cfg_ids, fam_ids, prec, keys, active, bank,
            eps_c, eps_n_c=eps_n_c)
        out_pl = call(u, hist, k, kc, cfg_ids, fam_ids, prec, keys, active,
                      bank, eps_c, eps_n_c=eps_n_c,
                      impl="pallas_interpret", block_d=64)
        for nm, a, b in zip(("u", "hist", "k", "active"), out_ref, out_pl):
            a, b = np.asarray(a), np.asarray(b)
            if kf == 1:
                np.testing.assert_array_equal(
                    a, b, err_msg=f"{family} {nm}: kf=1 must be bitwise")
            else:
                np.testing.assert_allclose(
                    a, b, rtol=1e-5, atol=1e-5,
                    err_msg=f"{family} {nm}: beyond the FMA gap")
