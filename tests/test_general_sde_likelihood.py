"""gDDIM generality (arbitrary anisotropic SDE) + the App. C.8 likelihood."""
import numpy as np
import jax
import jax.numpy as jnp

from repro.sde import VPSDE, CLD, GaussianMixture, ExactScore
from repro.sde.general import GeneralSDE
from repro.core import build_sampler_coeffs, time_grid, sample_gddim
from repro.core.likelihood import log_likelihood


class TestGeneralSDE:
    def test_R_factorizes_sigma(self, gsde):
        for t in (0.05, 0.3, 0.7, 1.0):
            R = gsde.R_np(t)
            np.testing.assert_allclose(R @ R.T, gsde.Sigma_np(t),
                                       rtol=1e-3, atol=1e-8)

    def test_R_differs_from_L(self, gsde):
        """Away from every special case, the Cholesky choice is NOT Eq. 17."""
        R, L = gsde.R_np(0.5), gsde.L_np(0.5)
        assert np.abs(R - L).max() > 1e-3

    def test_eps_constancy_prop4(self, gsde):
        """eps_GT = -R^T score is constant along exact prob-flow solutions."""
        mix = GaussianMixture(np.array([[0.6]]), np.array([1e-4]), np.array([1.0]))
        oracle = ExactScore(gsde, mix)
        ts = time_grid(gsde, 64, "uniform")
        co = build_sampler_coeffs(gsde, ts, q=1)
        eps_fn, _ = oracle.eps_fn_for_grid(ts)
        u = gsde.prior_sample(jax.random.PRNGKey(0), 8, (1,))
        N = co.psi.shape[0]
        eps0 = eps_fn(u, jnp.int32(N))
        for k in range(N):
            i = N - k
            e = eps_fn(u, jnp.int32(i))
            np.testing.assert_allclose(np.asarray(e), np.asarray(eps0),
                                       rtol=2e-2, atol=2e-3)
            u = gsde.apply(co.psi[k], u) + gsde.apply(co.pC[k, 0], e)

    def test_one_step_dirac_recovery(self):
        """Prop 2/4: exact score + K=R recovers the data point in ONE step.

        The achievable accuracy is floored by the diffusion width at the
        stopping time — the flow transports the prior to p_{t_min}, whose
        x-channel std is sqrt(Sigma_x(t_min)) ~ sqrt(G2_xx * t_min)
        (verified: the residual spread tracks this scale exactly and is
        NFE-independent, i.e. it is not sampler error).  The default
        t_min=1e-3 gives a 0.025 floor, wider than these bounds, so the
        recovery test stops at t_min=1e-4 (floor 0.008)."""
        gsde = GeneralSDE(t_min=1e-4)
        mix = GaussianMixture(np.array([[0.37]]), np.array([1e-5]), np.array([1.0]))
        oracle = ExactScore(gsde, mix)
        ts = np.array([gsde.t_min, gsde.T])
        co = build_sampler_coeffs(gsde, ts, q=1)
        eps_fn, _ = oracle.eps_fn_for_grid(ts)
        u_T = gsde.prior_sample(jax.random.PRNGKey(1), 16, (1,))
        u0 = sample_gddim(gsde, co, eps_fn, u_T, q=1)
        x0 = np.asarray(gsde.project_data(u0))
        # ONE step from pure noise lands within a few percent of the data
        # point (grid-interpolated R_t on a fully anisotropic SDE); a
        # one-step Euler from N(0, Sigma_T) would leave O(1) spread.
        assert np.abs(x0 - 0.37).mean() < 0.025, x0.ravel()
        assert np.abs(x0 - 0.37).max() < 0.06, x0.ravel()
        assert np.std(x0) < 0.05  # collapsed onto the Dirac, not spread

    def test_R_smoother_than_L(self, gsde):
        """The paper's mechanism on the general SDE: eps under K=R_t is
        markedly smoother along prob-flow solutions than under the Cholesky
        L_t (the property that lets multistep EI take large steps).
        Measured: TV_L ~ 1.03 vs TV_R ~ 0.47 at these coefficients."""
        from repro.core.coeffs import _K_fn
        mix = GaussianMixture(np.array([[1.0], [-1.0]]), np.array([0.05, 0.05]),
                              np.array([1.0, 1.0]))
        oracle = ExactScore(gsde, mix)
        tv = {}
        for kt in ("L", "R"):
            ts = time_grid(gsde, 100, "uniform")
            co = build_sampler_coeffs(gsde, ts, q=1, kt=kt)
            eps_fn, _ = oracle.eps_fn_for_grid(ts, _K_fn(gsde, kt))
            u = gsde.prior_sample(jax.random.PRNGKey(2), 32, (1,))
            N = co.psi.shape[0]
            prev, acc = None, 0.0
            for k in range(N):
                e = eps_fn(u, jnp.int32(N - k))
                if prev is not None:
                    acc += float(jnp.abs(e - prev).mean())
                prev = e
                u = gsde.apply(co.psi[k], u) + gsde.apply(co.pC[k, 0], e)
            tv[kt] = acc
        assert tv["R"] < 0.7 * tv["L"], tv


class TestLikelihood:
    def test_vpsde_gaussian_exact(self):
        """Single tight Gaussian: prob-flow NLL == analytic log-density."""
        sde = VPSDE()
        std = 0.3
        mix = GaussianMixture(np.array([[0.2, -0.4]]), np.array([std]),
                              np.array([1.0]))
        oracle = ExactScore(sde, mix)
        x = jnp.asarray(np.array([[0.2, -0.4], [0.5, 0.0], [-0.1, -0.7]],
                                 np.float32))
        ll = log_likelihood(sde, lambda u, t: oracle.score(u, t), x,
                            n_steps=150)
        # analytic: N(mu, (std^2 + t_min-ish smoothing)) — compare at the
        # sde-smoothed time t_min
        a = sde.alpha(sde.t_min)
        var = a * std**2 + (1 - a)
        mu = np.sqrt(a) * np.array([0.2, -0.4])
        d = np.asarray(x) - mu
        ref = -0.5 * (d**2).sum(-1) / var - np.log(2 * np.pi * var)
        np.testing.assert_allclose(np.asarray(ll), ref, rtol=1e-2, atol=5e-2)

    def test_hutchinson_matches_exact(self):
        sde = VPSDE()
        mix = GaussianMixture(np.array([[0.0, 0.0]]), np.array([0.5]),
                              np.array([1.0]))
        oracle = ExactScore(sde, mix)
        x = jnp.asarray(np.array([[0.1, 0.2]], np.float32))
        exact = log_likelihood(sde, lambda u, t: oracle.score(u, t), x,
                               n_steps=100)
        keys = jax.random.split(jax.random.PRNGKey(0), 16)
        hut = jnp.mean(jnp.stack([
            log_likelihood(sde, lambda u, t: oracle.score(u, t), x,
                           n_steps=100, hutchinson=True, key=k)
            for k in keys]), axis=0)
        np.testing.assert_allclose(np.asarray(hut), np.asarray(exact),
                                   rtol=5e-2, atol=0.1)
