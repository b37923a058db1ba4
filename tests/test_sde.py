"""SDE substrate tests: closed forms, solver invariants, forward-marginal
agreement (Monte Carlo), and the exact-score oracle."""
import numpy as np
import jax
import jax.numpy as jnp
import pytest

from repro.sde import VPSDE, BDM, GaussianMixture, ExactScore, dct_nd, idct_nd


# ---------------------------------------------------------------------------
# VPSDE closed forms
# ---------------------------------------------------------------------------
class TestVPSDE:
    def test_alpha_endpoints(self):
        vp = VPSDE()
        assert vp.alpha(0.0) == pytest.approx(1.0)
        assert vp.alpha(vp.T) < 1e-4  # essentially pure noise at T

    def test_psi_group_property(self):
        vp = VPSDE()
        for (t, s, r) in [(0.9, 0.5, 0.2), (1.0, 0.7, 0.1)]:
            assert vp.Psi_np(t, s) * vp.Psi_np(s, r) == pytest.approx(vp.Psi_np(t, r))

    def test_R_is_sqrt_sigma(self):
        vp = VPSDE()
        for t in [0.1, 0.5, 0.9]:
            assert vp.R_np(t) ** 2 == pytest.approx(vp.Sigma_np(t))

    def test_R_solves_eq17(self):
        # dR/dt = (F + 0.5 G2 / Sigma) R  — finite-difference check
        vp = VPSDE()
        t, h = 0.5, 1e-6
        dR = (vp.R_np(t + h) - vp.R_np(t - h)) / (2 * h)
        rhs = (vp.F_np(t) + 0.5 * vp.G2_np(t) / vp.Sigma_np(t)) * vp.R_np(t)
        assert dR == pytest.approx(rhs, rel=1e-4)


# ---------------------------------------------------------------------------
# CLD: Lyapunov / Eq. 17 invariants + Monte-Carlo marginal agreement
# ---------------------------------------------------------------------------
class TestCLD:
    def test_RRt_equals_sigma_on_range(self, cld):
        for t in [1e-3, 0.01, 0.05, 0.2, 0.5, 0.8, 1.0]:
            S, R = cld.Sigma_np(t), cld.R_np(t)
            assert np.abs(R @ R.T - S).max() < 5e-4, t

    def test_L_is_cholesky(self, cld):
        L = cld.L_np(0.4)
        assert L[0, 1] == pytest.approx(0.0)
        assert np.abs(L @ L.T - cld.Sigma_np(0.4)).max() < 1e-12

    def test_R_differs_from_L(self, cld):
        # the paper's whole point: the gDDIM branch is NOT the Cholesky factor
        assert np.abs(cld.R_np(0.5) - cld.L_np(0.5)).max() > 0.5

    def test_sigma_solves_lyapunov(self, cld):
        t, h = 0.3, 1e-6
        dS = (cld.Sigma_np(t + h) - cld.Sigma_np(t - h)) / (2 * h)
        S = cld.Sigma_np(t)
        rhs = cld.A @ S + S @ cld.A.T + cld.G2_np(t)
        assert np.abs(dS - rhs).max() < 1e-5

    def test_psi_transition_ode(self, cld):
        t, h = 0.6, 1e-6
        dP = (cld.Psi_np(t + h, 0.0) - cld.Psi_np(t - h, 0.0)) / (2 * h)
        assert np.abs(dP - cld.A @ cld.Psi_np(t, 0.0)).max() < 1e-4

    def test_forward_marginal_monte_carlo(self, cld):
        """Simulate the forward CLD with EM; sample mean/cov must match
        Psi(t,0) u0 / Sigma_t.  This validates F, G, Psi, Sigma jointly."""
        rng = np.random.default_rng(0)
        n, t_end, n_steps = 20000, 0.5, 400
        x0 = np.array([1.3])
        u = np.zeros((n, 2, 1))
        u[:, 0, 0] = x0
        u[:, 1, 0] = rng.normal(0, np.sqrt(cld.gamma / cld.M_inv), n)
        dt = t_end / n_steps
        g = np.sqrt(2 * cld.Gamma * cld.beta * dt)
        for _ in range(n_steps):
            drift = np.einsum("ij,bjd->bid", cld.A, u)
            u = u + drift * dt
            u[:, 1, 0] += g * rng.normal(size=n)
        mean_mc = u.mean(0)[:, 0]
        cov_mc = np.cov(u[:, :, 0].T)
        mean_an = (cld.Psi_np(t_end, 0.0) @ np.array([x0[0], 0.0]))
        cov_an = cld.Sigma_np(t_end)
        assert np.abs(mean_mc - mean_an).max() < 0.03
        assert np.abs(cov_mc - cov_an).max() < 0.03


# ---------------------------------------------------------------------------
# BDM: DCT basis + frequency schedule
# ---------------------------------------------------------------------------
class TestBDM:
    def test_dct_orthonormal(self):
        x = jnp.asarray(np.random.default_rng(1).normal(size=(2, 8, 8, 3)), jnp.float32)
        y = idct_nd(dct_nd(x, (1, 2)), (1, 2))
        assert jnp.abs(y - x).max() < 1e-5

    def test_g2_nonnegative(self):
        bdm = BDM(data_shape=(8, 8, 1))
        for t in np.linspace(1e-3, 1 - 1e-3, 50):
            assert bdm.G2_np(t).min() >= 0.0

    def test_psi_is_alpha_ratio(self):
        bdm = BDM(data_shape=(8, 8, 1))
        p = bdm.Psi_np(0.3, 0.7)
        assert np.allclose(p, bdm.alpha_k(0.3) / bdm.alpha_k(0.7))

    def test_high_freq_blurs_faster(self):
        # blur dissipation must shrink high frequencies more than DC
        bdm = BDM(data_shape=(8, 8, 1))
        a = bdm.alpha_k(0.5)
        assert a.flat[0] == a.max()          # DC least attenuated
        assert a[-1, -1, 0] == a.min()       # highest frequency most attenuated

    def test_sigma_isotropic_R_equals_L(self):
        bdm = BDM(data_shape=(8, 8, 1))
        assert np.allclose(bdm.R_np(0.4), bdm.L_np(0.4))

    def test_forward_marginal_monte_carlo(self):
        """EM-simulate the BDM SDE on a tiny 1-D signal; marginal mean must
        match Psi(t,0) x0 (i.e. blur+scale) and variance sigma_t^2."""
        bdm = BDM(data_shape=(4, 1))  # 4-pixel 1-D signal
        rng = np.random.default_rng(2)
        n, t_end, n_steps = 20000, 0.4, 600
        x0 = np.array([1.0, -0.5, 0.25, 0.8])[:, None]
        u = np.tile(x0[None], (n, 1, 1))
        dt = t_end / n_steps
        from repro.sde.base import dct_matrix
        C = dct_matrix(4)
        for k in range(n_steps):
            t = k * dt
            F = bdm.F_np(t)[:, 0]  # (4,) freq diag
            G2 = bdm.G2_np(t)[:, 0]
            y = np.einsum("fk,bkc->bfc", C, u)
            y = y + F[None, :, None] * y * dt
            y = y + np.sqrt(np.maximum(G2, 0) * dt)[None, :, None] * rng.normal(size=y.shape)
            u = np.einsum("kf,bfc->bkc", C.T, y)
        mean_mc = u.mean(0)
        # analytic: V diag(alpha_t/alpha_0) V^T x0
        ratio = bdm.alpha_k(t_end)[:, 0] / bdm.alpha_k(0.0)[:, 0]
        mean_an = C.T @ (ratio[:, None] * (C @ x0))
        assert np.abs(mean_mc - mean_an).max() < 0.03
        var_mc = u.var(0).mean()
        assert abs(var_mc - bdm.sigma2(t_end)) < 0.03


# ---------------------------------------------------------------------------
# Exact-score oracle
# ---------------------------------------------------------------------------
class TestExactScore:
    def _fd_check(self, sde, mix, u, t):
        """Finite-difference the mixture log-density and compare to score."""
        oracle = ExactScore(sde, mix)
        s = oracle.score_np(u, t)
        # log density via mode constants
        _, consts = oracle._mode_constants(t)

        def logp(uu):
            vals = []
            for mu, Cinv, logdet, logw in consts:
                d = (uu - mu).reshape(-1)
                if sde.ops.family == "block":
                    dd = (uu - mu)
                    tmp = np.einsum("ij,j...->i...", Cinv, dd)
                    qf = float(np.sum(dd * tmp))
                elif sde.ops.family == "scalar":
                    qf = float(Cinv * np.sum(d * d))
                else:
                    dh = oracle._dct_np((uu - mu)[None])[0]
                    qf = float(np.sum(dh * dh * Cinv))
                vals.append(logw - 0.5 * qf - 0.5 * logdet)
            m = max(vals)
            return m + np.log(sum(np.exp(v - m) for v in vals))

        h = 1e-5
        g = np.zeros_like(u[0])
        it = np.nditer(u[0], flags=["multi_index"])
        while not it.finished:
            idx = it.multi_index
            up, dn = u[0].copy(), u[0].copy()
            up[idx] += h
            dn[idx] -= h
            g[idx] = (logp(up) - logp(dn)) / (2 * h)
            it.iternext()
        assert np.abs(g - s[0]).max() < 1e-3 * max(1.0, np.abs(s).max())

    def test_score_vs_fd_vpsde(self):
        vp = VPSDE()
        mix = GaussianMixture(np.array([[1.0, -1.0], [-1.0, 0.5]]),
                              np.array([0.3, 0.2]), np.array([0.6, 0.4]))
        u = np.array([[0.3, 0.1]])
        self._fd_check(vp, mix, u, 0.4)

    def test_score_vs_fd_cld(self, cld):
        mix = GaussianMixture(np.array([[1.0, -1.0]]), np.array([0.3]), np.array([1.0]))
        u = np.array([[[0.3, 0.1], [-0.2, 0.4]]])  # (1, 2, 2)
        self._fd_check(cld, mix, u, 0.4)

    def test_score_vs_fd_bdm(self):
        bdm = BDM(data_shape=(4, 1))
        mix = GaussianMixture(np.array([[[1.0], [-0.5], [0.2], [0.8]]]),
                              np.array([0.3]), np.array([1.0]))
        u = np.array([[[0.3], [0.1], [-0.2], [0.5]]])
        self._fd_check(bdm, mix, u, 0.4)

    def test_device_score_matches_host(self):
        vp = VPSDE()
        mix = GaussianMixture(np.array([[1.0, -1.0], [-1.0, 0.5]]),
                              np.array([0.3, 0.2]), np.array([0.5, 0.5]))
        oracle = ExactScore(vp, mix)
        u = np.random.default_rng(3).normal(size=(16, 2))
        s_host = oracle.score_np(u, 0.3)
        s_dev = np.asarray(oracle.score(jnp.asarray(u, jnp.float32), 0.3))
        assert np.abs(s_host - s_dev).max() < 1e-3

    def test_mixture_sample_moments(self):
        mix = GaussianMixture(np.array([[2.0], [-2.0]]), np.array([0.1, 0.1]),
                              np.array([0.5, 0.5]))
        x = np.asarray(mix.sample(jax.random.PRNGKey(0), 40000))
        assert abs(x.mean()) < 0.05
        assert abs(x.var() - (4.0 + 0.01)) < 0.1
