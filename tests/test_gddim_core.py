"""gDDIM core tests: every proposition/theorem of the paper has a check.

Prop 1/4  eps-constancy along exact prob-flow solutions (R_t vs L_t)
Prop 2    deterministic DDIM == exponential integrator on VPSDE (exact coeff)
Prop 3/5  one score evaluation recovers the score everywhere (Gaussian data)
Thm 1     stochastic gDDIM == DDIM update on VPSDE (mean + variance coeffs)
Prop 7    stochastic gDDIM with lambda=0 == deterministic gDDIM
plus multistep-order convergence and end-to-end exact recovery.
"""
import numpy as np
import jax
import jax.numpy as jnp
import pytest

from repro.sde import VPSDE, BDM, GaussianMixture, ExactScore
from repro.core import (build_sampler_coeffs, time_grid, ddim_closed_form_check,
                        sample_gddim, sample_gddim_stochastic, sample_em,
                        sample_heun, sample_ancestral_bdm)


@pytest.fixture(scope="module")
def vp():
    return VPSDE()


# ---------------------------------------------------------------------------
# Prop 2 / DDIM equivalence on VPSDE
# ---------------------------------------------------------------------------
class TestProp2DDIM:
    def test_q1_coeff_matches_ddim_closed_form(self, vp):
        ts = time_grid(vp, 20)
        co = build_sampler_coeffs(vp, ts, q=1)
        ddim = ddim_closed_form_check(vp, ts)
        assert np.abs(np.asarray(co.pC[:, 0]) - ddim).max() < 1e-5

    def test_psi_matches_alpha_ratio(self, vp):
        ts = time_grid(vp, 10)
        co = build_sampler_coeffs(vp, ts, q=1)
        N = len(ts) - 1
        for k in range(N):
            i = N - k
            assert float(co.psi[k]) == pytest.approx(
                np.sqrt(vp.alpha(ts[i - 1]) / vp.alpha(ts[i])), rel=1e-5)

    def test_sampler_step_equals_ddim_reference(self, vp):
        """One full grid of gDDIM(q=1) steps == iterated closed-form DDIM."""
        ts = time_grid(vp, 8)
        co = build_sampler_coeffs(vp, ts, q=1)
        mix = GaussianMixture(np.array([[0.7, -0.3]]), np.array([1e-6]), np.array([1.0]))
        oracle = ExactScore(vp, mix)
        eps_fn, _ = oracle.eps_fn_for_grid(ts)
        uT = vp.prior_sample(jax.random.PRNGKey(0), 8, (2,))
        out = sample_gddim(vp, co, eps_fn, uT, q=1)
        # manual DDIM iteration with the same eps oracle
        u = uT
        N = len(ts) - 1
        for k in range(N):
            i = N - k
            eps = eps_fn(u, i)
            u = vp.ddim_step_reference(u, eps, float(ts[i]), float(ts[i - 1]))
        assert float(jnp.abs(out - u).max()) < 1e-4


# ---------------------------------------------------------------------------
# Prop 1 / Prop 4: eps-constancy along exact solutions
# ---------------------------------------------------------------------------
class TestEpsConstancy:
    def _trajectory_eps_std(self, sde, K_fn, n_steps=300):
        mix = GaussianMixture(np.array([[0.8, -1.2, 0.3]]), np.array([1e-9]),
                              np.array([1.0]))
        oracle = ExactScore(sde, mix)
        uT = np.asarray(sde.prior_sample(jax.random.PRNGKey(1), 4, (3,)), np.float64)
        ts = np.linspace(sde.T, 0.01, n_steps)
        u = uT.copy()

        def rhs(t, u):
            sc = oracle.score_np(u, t)
            F, G2 = sde.F_np(t), sde.G2_np(t)
            if sde.ops.family == "block":
                return (np.einsum("ij,bj...->bi...", F, u)
                        - 0.5 * np.einsum("ij,bj...->bi...", G2, sc))
            return F * u - 0.5 * G2 * sc

        eps = []
        for k in range(len(ts) - 1):
            t, tn = ts[k], ts[k + 1]
            h = tn - t
            k1 = rhs(t, u); k2 = rhs(t + h / 2, u + h / 2 * k1)
            k3 = rhs(t + h / 2, u + h / 2 * k2); k4 = rhs(tn, u + h * k3)
            u = u + h / 6 * (k1 + 2 * k2 + 2 * k3 + k4)
            sc = oracle.score_np(u, tn)
            K = K_fn(tn)
            if sde.ops.family == "block":
                eps.append(-np.einsum("ij,bj...->bi...", np.asarray(K).T, sc))
            else:
                eps.append(-K * sc)
        return np.stack(eps).std(axis=0).max()

    def test_prop1_vpsde_constant(self, vp):
        assert self._trajectory_eps_std(vp, vp.R_np) < 5e-3

    def test_prop4_cld_R_constant_L_oscillates(self, cld):
        std_R = self._trajectory_eps_std(cld, cld.R_np)
        std_L = self._trajectory_eps_std(cld, cld.L_np)
        assert std_R < 5e-3
        assert std_L > 0.5
        assert std_L / std_R > 100.0  # Fig. 1's contrast, quantified


# ---------------------------------------------------------------------------
# Prop 3 / Prop 5: score recovery from a single evaluation
# ---------------------------------------------------------------------------
class TestScoreRecovery:
    def _check(self, sde):
        mix = GaussianMixture(np.array([[0.5, -0.9]]), np.array([1e-9]), np.array([1.0]))
        oracle = ExactScore(sde, mix)
        rng = np.random.default_rng(0)
        s_t, t = sde.T, 0.3
        shape = (1,) + sde.state_shape((2,))
        u_s = rng.normal(size=shape)
        u = rng.normal(size=shape)
        score_s = oracle.score_np(u_s, s_t)
        # Eq. 20: score_t(u) = Sigma_t^{-1} Psi(t,s) Sigma_s score_s - Sigma_t^{-1}(u - Psi u_s)
        ops = sde.ops
        Sig_t, Sig_s = sde.Sigma_np(t), sde.Sigma_np(s_t)
        Psi_ts = sde.Psi_np(t, s_t)
        Sit = ops.inv(Sig_t)
        A = ops.mul(Sit, ops.mul(Psi_ts, Sig_s))

        def ap(M, x):
            if ops.family == "block":
                return np.einsum("ij,bj...->bi...", M, x)
            return M * x

        rec = ap(A, score_s) - ap(Sit, u - ap(Psi_ts, u_s))
        truth = oracle.score_np(u, t)
        assert np.abs(rec - truth).max() < 1e-4 * max(1.0, np.abs(truth).max())

    def test_prop3_vpsde(self, vp):
        self._check(vp)

    def test_prop5_cld(self, cld):
        self._check(cld)


# ---------------------------------------------------------------------------
# Thm 1: stochastic gDDIM == stochastic DDIM on VPSDE
# ---------------------------------------------------------------------------
class TestThm1:
    @pytest.mark.parametrize("lam", [0.3, 1.0])
    def test_psi_hat_closed_form(self, vp, lam):
        ts = time_grid(vp, 10)
        co = build_sampler_coeffs(vp, ts, q=1, lam=lam)
        N = len(ts) - 1
        for k in [0, 3, N - 1]:
            i = N - k
            ph = vp.Psi_hat_np(float(ts[i - 1]), float(ts[i]), lam)
            assert float(co.psi_hat[k]) == pytest.approx(ph, rel=1e-3)

    @pytest.mark.parametrize("lam", [0.3, 1.0])
    def test_variance_closed_form(self, vp, lam):
        ts = time_grid(vp, 10)
        co = build_sampler_coeffs(vp, ts, q=1, lam=lam)
        N = len(ts) - 1
        for k in [0, 3, N - 1]:
            i = N - k
            P = vp.P_np(float(ts[i]), float(ts[i - 1]), lam)
            assert float(co.P_chol[k]) ** 2 == pytest.approx(P, rel=2e-3, abs=1e-8)

    @pytest.mark.parametrize("lam", [0.5])
    def test_mean_coeff_is_ddim_eq9(self, vp, lam):
        """B = (Psi_hat - Psi) R_s must equal the DDIM eps coefficient
        sqrt(1 - a_{t-1} - sigma^2) - sqrt(a_{t-1}/a_t) sqrt(1 - a_t)."""
        ts = time_grid(vp, 10)
        co = build_sampler_coeffs(vp, ts, q=1, lam=lam)
        N = len(ts) - 1
        for k in [0, 4, N - 1]:
            i = N - k
            t, s = float(ts[i]), float(ts[i - 1])
            a_t, a_s = vp.alpha(t), vp.alpha(s)
            sig2 = vp.P_np(t, s, lam)
            expect = np.sqrt(1 - a_s - sig2) - np.sqrt(a_s / a_t) * np.sqrt(1 - a_t)
            assert float(co.B[k]) == pytest.approx(expect, rel=2e-3, abs=1e-6)


# ---------------------------------------------------------------------------
# Prop 7: lambda=0 stochastic == deterministic
# ---------------------------------------------------------------------------
class TestProp7:
    def test_lambda0_reduces_to_deterministic(self, cld):
        ts = time_grid(cld, 12)
        co = build_sampler_coeffs(cld, ts, q=1, lam=0.0)
        # Lemma 2: int 1/2 Psi G2 R^{-T} == (Psi_hat - Psi) R_s  elementwise
        assert np.abs(np.asarray(co.pC[:, 0]) - np.asarray(co.B)).max() < 2e-3
        # and P == 0
        assert np.abs(np.asarray(co.P_chol)).max() < 1e-6

    def test_stochastic_sampler_matches_deterministic(self, cld):
        ts = time_grid(cld, 8)
        co = build_sampler_coeffs(cld, ts, q=1, lam=0.0)
        mix = GaussianMixture(np.array([[0.4, -0.6]]), np.array([1e-6]), np.array([1.0]))
        oracle = ExactScore(cld, mix)
        eps_fn, _ = oracle.eps_fn_for_grid(ts)
        uT = cld.prior_sample(jax.random.PRNGKey(2), 8, (2,))
        det = sample_gddim(cld, co, eps_fn, uT, q=1)
        sto = sample_gddim_stochastic(cld, co, eps_fn, uT, jax.random.PRNGKey(3))
        assert float(jnp.abs(det - sto).max()) < 5e-3


# ---------------------------------------------------------------------------
# Exact recovery & multistep order
# ---------------------------------------------------------------------------
class TestExactRecovery:
    def test_one_step_dirac_recovery_vpsde(self, vp):
        """Prop 2: with the exact eps, ONE gDDIM step solves the ODE exactly
        (up to the stop-time contraction)."""
        x0 = np.array([[1.5, -0.7]])
        mix = GaussianMixture(x0, np.array([1e-9]), np.array([1.0]))
        oracle = ExactScore(vp, mix)
        ts = time_grid(vp, 1, kind="uniform")
        co = build_sampler_coeffs(vp, ts, q=1)
        eps_fn, _ = oracle.eps_fn_for_grid(ts)
        uT = vp.prior_sample(jax.random.PRNGKey(0), 32, (2,))
        out = sample_gddim(vp, co, eps_fn, uT, q=1)
        # invert the t_min contraction: x0_hat = (u - sqrt(1-a) eps)/sqrt(a)
        t0 = float(ts[0])
        eps0 = eps_fn(out, 0)
        x0_hat = (out - np.sqrt(1 - vp.alpha(t0)) * eps0) / np.sqrt(vp.alpha(t0))
        assert float(jnp.abs(x0_hat - jnp.asarray(x0)).max()) < 5e-3

    def test_few_step_gaussian_recovery_cld(self, cld):
        """Prop 4: for Gaussian data the CLD prob-flow is solved exactly by
        gDDIM steps of any size when K_t = R_t."""
        mix = GaussianMixture(np.array([[0.9, -0.4]]), np.array([1e-9]), np.array([1.0]))
        oracle = ExactScore(cld, mix)
        ts = time_grid(cld, 3, kind="uniform")
        co = build_sampler_coeffs(cld, ts, q=1)
        eps_fn, _ = oracle.eps_fn_for_grid(ts)
        uT = cld.prior_sample(jax.random.PRNGKey(5), 16, (2,))
        out = sample_gddim(cld, co, eps_fn, uT, q=1)
        # reference: dense-grid host RK4 of the prob-flow ODE from same uT
        ref = np.asarray(uT, np.float64)

        def rhs(t, u):
            sc = oracle.score_np(u, t)
            return (np.einsum("ij,bj...->bi...", cld.F_np(t), u)
                    - 0.5 * np.einsum("ij,bj...->bi...", cld.G2_np(t), sc))

        tgrid = np.linspace(cld.T, float(ts[0]), 600)
        for k in range(len(tgrid) - 1):
            t, tn = tgrid[k], tgrid[k + 1]
            h = tn - t
            k1 = rhs(t, ref); k2 = rhs(t + h / 2, ref + h / 2 * k1)
            k3 = rhs(t + h / 2, ref + h / 2 * k2); k4 = rhs(tn, ref + h * k3)
            ref = ref + h / 6 * (k1 + 2 * k2 + 2 * k3 + k4)
        assert np.abs(np.asarray(out, np.float64) - ref).max() < 5e-3

    def test_multistep_order_improves_accuracy(self, vp):
        """On mixture data (eps NOT constant) higher q should track the exact
        ODE better at fixed NFE — Tab. 5's trend."""
        mix = GaussianMixture(np.array([[2.0, 0.0], [-2.0, 0.5]]),
                              np.array([0.15, 0.1]), np.array([0.5, 0.5]))
        oracle = ExactScore(vp, mix)
        uT = vp.prior_sample(jax.random.PRNGKey(7), 64, (2,))
        # reference: fine-grid host RK4
        ref = np.asarray(uT, np.float64)

        def rhs(t, u):
            return vp.F_np(t) * u - 0.5 * vp.G2_np(t) * oracle.score_np(u, t)

        tgrid = np.linspace(vp.T, vp.t_min, 1200)
        for k in range(len(tgrid) - 1):
            t, tn = tgrid[k], tgrid[k + 1]
            h = tn - t
            k1 = rhs(t, ref); k2 = rhs(t + h / 2, ref + h / 2 * k1)
            k3 = rhs(t + h / 2, ref + h / 2 * k2); k4 = rhs(tn, ref + h * k3)
            ref = ref + h / 6 * (k1 + 2 * k2 + 2 * k3 + k4)
        errs = {}
        ts = time_grid(vp, 12)
        eps_fn, _ = oracle.eps_fn_for_grid(ts)
        for q in (1, 2, 3):
            co = build_sampler_coeffs(vp, ts, q=q)
            out = sample_gddim(vp, co, eps_fn, uT, q=q)
            errs[q] = float(np.abs(np.asarray(out, np.float64) - ref).mean())
        assert errs[2] < errs[1]
        assert errs[3] < errs[1]

    def test_corrector_improves_over_predictor(self, vp):
        mix = GaussianMixture(np.array([[2.0, 0.0], [-2.0, 0.5]]),
                              np.array([0.15, 0.1]), np.array([0.5, 0.5]))
        oracle = ExactScore(vp, mix)
        uT = vp.prior_sample(jax.random.PRNGKey(9), 64, (2,))
        ref = np.asarray(uT, np.float64)

        def rhs(t, u):
            return vp.F_np(t) * u - 0.5 * vp.G2_np(t) * oracle.score_np(u, t)

        tgrid = np.linspace(vp.T, vp.t_min, 1200)
        for k in range(len(tgrid) - 1):
            t, tn = tgrid[k], tgrid[k + 1]
            h = tn - t
            k1 = rhs(t, ref); k2 = rhs(t + h / 2, ref + h / 2 * k1)
            k3 = rhs(t + h / 2, ref + h / 2 * k2); k4 = rhs(tn, ref + h * k3)
            ref = ref + h / 6 * (k1 + 2 * k2 + 2 * k3 + k4)
        ts = time_grid(vp, 8)
        eps_fn, _ = oracle.eps_fn_for_grid(ts)
        co = build_sampler_coeffs(vp, ts, q=2)
        out_p = sample_gddim(vp, co, eps_fn, uT, q=2, corrector=False)
        out_pc = sample_gddim(vp, co, eps_fn, uT, q=2, corrector=True)
        err_p = float(np.abs(np.asarray(out_p, np.float64) - ref).mean())
        err_pc = float(np.abs(np.asarray(out_pc, np.float64) - ref).mean())
        assert err_pc < err_p  # Tab. 8's trend


# ---------------------------------------------------------------------------
# Golden values: closed forms / slow quadrature, independent of any bank
# ---------------------------------------------------------------------------
class TestGoldenValues:
    """The sampler-coefficient layer's anchor tests: Stage-I output pinned
    directly against the analytic DDIM update of Song et al. (2010.02502)
    and against an independent slow float64 quadrature — no CoeffCache, no
    bank (dense or factored) in the loop, so a defect in either bank
    implementation cannot mask a defect in the coefficients themselves."""

    def test_vpsde_lambda0_step_coefficients_match_song_ddim(self, vp):
        """Per step t_i -> t_{i-1}, the gDDIM (lam=0, q=1) update on VPSDE
        must be exactly Song et al.'s Eq. 12 deterministic DDIM update
          u <- sqrt(a_{i-1}/a_i) u + (sqrt(1-a_{i-1})
                                      - sqrt(1-a_i) sqrt(a_{i-1}/a_i)) eps
        (paper Prop 2): psi is the closed-form signal ratio and the
        quadrature eps coefficient reproduces the closed form."""
        ts = time_grid(vp, 12)
        co = build_sampler_coeffs(vp, ts, q=1)
        N = len(ts) - 1
        i = N - np.arange(N)
        a_t, a_s = vp.alpha(ts[i]), vp.alpha(ts[i - 1])
        psi_gold = np.sqrt(a_s / a_t)
        eps_gold = np.sqrt(1 - a_s) - np.sqrt(1 - a_t) * np.sqrt(a_s / a_t)
        np.testing.assert_allclose(np.asarray(co.psi), psi_gold, rtol=1e-6)
        np.testing.assert_allclose(np.asarray(co.pC[:, 0]), eps_gold,
                                   rtol=2e-5, atol=1e-7)

    def test_eq45_corrector_rows_match_slow_float64_quadrature(self, vp):
        """Eq. 46's corrector constants (the Eq. 45 update's weights) must
        match an independent slow reference: dense-trapezoid float64
        quadrature of 1/2 Psi(t_{i-1}, tau) G2(tau) R(tau)^{-T} ell_j(tau)
        with an inline Lagrange basis — nothing shared with the production
        quadrature (composite Simpson + solve.lagrange_basis)."""
        nfe, q = 6, 2
        ts = time_grid(vp, nfe)
        co = build_sampler_coeffs(vp, ts, q=q)

        def core(t_end, tau):                      # the Eq. 41/46 integrand
            return (0.5 * vp.Psi_np(t_end, tau) * vp.G2_np(tau)
                    * vp.R_np(tau) / vp.Sigma_np(tau))

        for k in (0, 2, nfe - 1):
            i = nfe - k
            t_i, t_im1 = float(ts[i]), float(ts[i - 1])
            q_corr = min(q, nfe - i + 2)
            nodes = [t_im1] + [float(ts[min(i + j, nfe)])
                               for j in range(q_corr - 1)]
            tau = np.linspace(t_i, t_im1, 20001)
            for j in range(q_corr):
                ell = np.ones_like(tau)
                for m, tm in enumerate(nodes):
                    if m != j:
                        ell *= (tau - tm) / (nodes[j] - tm)
                vals = core(t_im1, tau) * ell
                ref = 0.5 * float(np.sum((vals[1:] + vals[:-1])
                                         * np.diff(tau)))
                assert float(co.cC[k, j]) == pytest.approx(
                    ref, rel=5e-5, abs=1e-7), (k, j)
            # beyond the warm-start order the rows are zero-padded
            assert not np.asarray(co.cC[k, q_corr:]).any()


# ---------------------------------------------------------------------------
# Baselines behave
# ---------------------------------------------------------------------------
class TestBaselines:
    def test_em_converges_with_many_steps(self, vp):
        mix = GaussianMixture(np.array([[1.0, -1.0]]), np.array([0.05]), np.array([1.0]))
        oracle = ExactScore(vp, mix)
        ts = time_grid(vp, 200)
        co = build_sampler_coeffs(vp, ts, q=1)
        eps_fn, _ = oracle.eps_fn_for_grid(ts)
        uT = vp.prior_sample(jax.random.PRNGKey(11), 256, (2,))
        out = sample_em(vp, co, eps_fn, uT, jax.random.PRNGKey(12), lam=0.0)
        mean = np.asarray(out).mean(0)
        assert np.abs(mean - np.array([1.0, -1.0])).max() < 0.1

    def test_heun_beats_euler_at_fixed_grid(self, vp):
        mix = GaussianMixture(np.array([[2.0, 0.0], [-2.0, 0.5]]),
                              np.array([0.15, 0.1]), np.array([0.5, 0.5]))
        oracle = ExactScore(vp, mix)
        uT = vp.prior_sample(jax.random.PRNGKey(13), 64, (2,))
        ref = np.asarray(uT, np.float64)

        def rhs(t, u):
            return vp.F_np(t) * u - 0.5 * vp.G2_np(t) * oracle.score_np(u, t)

        tg = np.linspace(vp.T, vp.t_min, 1200)
        for k in range(len(tg) - 1):
            t, tn = tg[k], tg[k + 1]
            h = tn - t
            k1 = rhs(t, ref); k2 = rhs(t + h / 2, ref + h / 2 * k1)
            k3 = rhs(t + h / 2, ref + h / 2 * k2); k4 = rhs(tn, ref + h * k3)
            ref = ref + h / 6 * (k1 + 2 * k2 + 2 * k3 + k4)
        ts = time_grid(vp, 16)
        eps_fn, _ = oracle.eps_fn_for_grid(ts)
        co = build_sampler_coeffs(vp, ts, q=1)
        out_e = sample_heun(vp, co, eps_fn, uT, second_order=False)
        out_h = sample_heun(vp, co, eps_fn, uT, second_order=True)
        err_e = np.abs(np.asarray(out_e, np.float64) - ref).mean()
        err_h = np.abs(np.asarray(out_h, np.float64) - ref).mean()
        assert err_h < err_e

    def test_bdm_ancestral_runs_and_gddim_beats_it(self):
        bdm = BDM(data_shape=(4, 1))
        x0 = np.array([[[1.0], [-0.5], [0.2], [0.8]]])
        mix = GaussianMixture(x0, np.array([1e-6]), np.array([1.0]))
        oracle = ExactScore(bdm, mix)
        ts = time_grid(bdm, 10)
        co = build_sampler_coeffs(bdm, ts, q=1)
        eps_fn, _ = oracle.eps_fn_for_grid(ts)
        uT = bdm.prior_sample(jax.random.PRNGKey(15), 128, (4, 1))
        out_g = sample_gddim(bdm, co, eps_fn, uT, q=1)
        out_a = sample_ancestral_bdm(bdm, eps_fn, uT, np.asarray(ts), jax.random.PRNGKey(16))
        err_g = np.abs(np.asarray(out_g).mean(0) - x0[0]).max()
        err_a = np.abs(np.asarray(out_a).mean(0) - x0[0]).max()
        assert err_g < 0.05
        assert err_g <= err_a + 0.05  # gDDIM at least as good at 10 NFE
