"""The reference's coefficient arithmetic and chain as they were written
for one family of (k, k) blocks over the state's own entries, kept as the
oracle that the family seam (`chipbench/families/`) must reproduce for
VPSDE and CLD.  The families, the DiT and the time grid are the
reference's own; everything below is the old single-family code,
unchanged."""
from __future__ import annotations

import math
from typing import List

import numpy as np
import scipy.integrate

from chipbench import reference as R
from chipbench.reference import GL_POINTS, ODE_TOL, _lagrange, time_grid


def _ei_integral(sde, t_from: float, t_to: float, weight) -> np.ndarray:
    """int_{t_from}^{t_to} 1/2 Psi(t_to, s) G2(s) K(s)^{-T} weight(s) ds,
    K^{-T} = Sigma^{-1} K (K K^T = Sigma)."""
    x, w = np.polynomial.legendre.leggauss(GL_POINTS)
    half = 0.5 * (t_to - t_from)
    s = t_from + half * (x + 1.0)
    ws = weight(s)
    acc = np.zeros((sde.k, sde.k))
    for si, wi, li in zip(s, w, ws):
        core = 0.5 * sde.Psi(t_to, si) @ sde.G2(si) \
            @ np.linalg.solve(sde.Sigma(si), sde.K(si))
        acc += wi * li * core
    return half * acc


def _stochastic(sde, t_i: float, t_im1: float, lam: float):
    """Psi_hat(t_{i-1}, t_i) and the injected covariance P (Eq. 22/23)."""
    def F_hat(s):
        return sde.F(s) + 0.5 * (1.0 + lam * lam) * sde.G2(s) \
            @ np.linalg.inv(sde.Sigma(s))

    k = sde.k
    ph = scipy.integrate.solve_ivp(
        lambda s, y: (F_hat(s) @ y.reshape(k, k)).ravel(), (t_i, t_im1),
        np.eye(k).ravel(), method="DOP853", **ODE_TOL).y[:, -1]

    def p_rhs(sig, y):
        P, fh = y.reshape(k, k), F_hat(t_i - sig)
        return (-(fh @ P + P @ fh.T) + lam * lam * sde.G2(t_i - sig)).ravel()

    P = scipy.integrate.solve_ivp(p_rhs, (0.0, t_i - t_im1),
                                  np.zeros(k * k), method="DOP853",
                                  **ODE_TOL).y[:, -1].reshape(k, k)
    return ph.reshape(k, k), 0.5 * (P + P.T)


def sampler_coeffs(sde, fields: dict, sampler: dict) -> dict:
    n, q, lam = fields["nfe"], fields["q"], fields["lam"]
    alg = fields["algorithm"]
    if alg not in ("gddim", "gmm"):
        raise NotImplementedError(f"reference has no algorithm {alg!r}")
    ts = time_grid(sde, n, sampler["grid"])
    steps = []
    for k in range(n):
        i = n - k
        t_i, t_im1 = float(ts[i]), float(ts[i - 1])
        st = {"t": t_i, "t_next": t_im1, "psi": sde.Psi(t_im1, t_i)}
        nodes = [float(ts[min(i + j, n)]) for j in range(min(q, k + 1))]
        st["pC"] = [_ei_integral(sde, t_i, t_im1,
                                 lambda s, j=j: _lagrange(nodes, j, s))
                    for j in range(len(nodes))]
        if fields["corrector"] and k < n - 1:
            qc = min(q, k + 2)
            nodes_c = [t_im1] + [float(ts[min(i + j, n)])
                                 for j in range(qc - 1)]
            st["cC"] = [_ei_integral(sde, t_i, t_im1,
                                     lambda s, j=j: _lagrange(nodes_c, j, s))
                        for j in range(qc)]
        if lam > 0:
            ph, P = _stochastic(sde, t_i, t_im1, lam)
            st["B"] = (ph - st["psi"]) @ sde.K(t_i)
            # P is PSD; lift round-off negativity before the Cholesky
            w, v = np.linalg.eigh(P)
            P = (v * np.maximum(w, 1e-14 * abs(np.trace(P)))) @ v.T
            L = np.linalg.cholesky(P)
            if alg == "gmm":
                L = L * math.sqrt(1.0 - sampler["gmm_rho"] ** 2)
            st["P_chol"] = L
        steps.append(st)
    return {"steps": steps, "q": q,
            "chol_T": np.linalg.cholesky(sde.Sigma(sde.T))}


class SingleFamilyReference(R.Reference):
    """`Reference` with the single-family chain in place of the seam."""

    def _eps(self, u: np.ndarray, t: np.ndarray) -> np.ndarray:
        """u (b, k, D) float64 -> eps (b, k, D) float64, padded to chunk."""
        import jax.numpy as jnp
        b = u.shape[0]
        x = np.zeros((self.chunk,) + self.state_shape, np.float32)
        x[:b] = u.reshape((b,) + self.state_shape)
        tt = np.full((self.chunk,), t[0], np.float32)
        tt[:b] = t
        e = self._fwd(self.p32, jnp.asarray(x), jnp.asarray(tt))
        return np.asarray(e, np.float64)[:b].reshape(u.shape)

    def _noise(self, seeds, alg: str, k: int) -> np.ndarray:
        import jax
        import jax.numpy as jnp
        s = self.sampler
        out = []
        for seed in seeds:
            key = jax.random.fold_in(jax.random.PRNGKey(seed),
                                     s["noise_salt"])
            step = jax.random.fold_in(
                jax.random.fold_in(key, s["algorithm_ids"][alg]), k)
            z = jax.random.normal(step, self.state_shape, jnp.float32)
            if alg == "gmm":
                sn = jax.random.normal(jax.random.fold_in(step, s["gmm_salt"]),
                                       self.state_shape, jnp.float32)
                c = np.float32(s["gmm_rho"] / math.sqrt(1 - s["gmm_rho"] ** 2))
                z = z + c * jnp.where(sn >= 0, 1.0, -1.0).astype(jnp.float32)
            out.append(np.asarray(z, np.float64).reshape(self.sde.k, self.D))
        return np.stack(out)

    def _prior(self, seeds, chol_T) -> np.ndarray:
        import jax
        import jax.numpy as jnp
        z = np.stack([np.asarray(jax.random.normal(
            jax.random.PRNGKey(s), (1,) + self.state_shape, jnp.float32),
            np.float64).reshape(self.sde.k, self.D) for s in seeds])
        return np.einsum("ij,bjd->bid", chol_T, z)

    def samples(self, requests: List[dict]) -> List[np.ndarray]:
        """requests: sampler fields + seed each; returns data-space samples
        (float64) in the same order."""
        out: List[np.ndarray] = [None] * len(requests)
        groups: Dict[tuple, List[int]] = {}
        for n, r in enumerate(requests):
            key = (r["nfe"], r["q"], r["corrector"], r["lam"],
                   r["algorithm"])
            groups.setdefault(key, []).append(n)
        for key, idx in groups.items():
            if key not in self._coeffs:
                self._coeffs[key] = sampler_coeffs(
                    self.sde, dict(zip(("nfe", "q", "corrector", "lam",
                                        "algorithm"), key)), self.sampler)
            co = self._coeffs[key]
            for c0 in range(0, len(idx), self.chunk):
                part = idx[c0:c0 + self.chunk]
                xs = self._chain(co, [requests[n] for n in part])
                for n, x in zip(part, xs):
                    out[n] = x
        return out

    def _chain(self, co: dict, reqs: List[dict]) -> List[np.ndarray]:
        ap = lambda c, z: np.einsum("ij,bjd->bid", c, z)
        seeds = [r["seed"] for r in reqs]
        alg, b = reqs[0]["algorithm"], len(reqs)
        u = self._prior(seeds, co["chol_T"])
        hist: List[np.ndarray] = []
        for k, st in enumerate(co["steps"]):
            eps = self._eps(u, np.full((b,), st["t"]))
            hist = [eps] + hist
            u_lin = ap(st["psi"], u)
            u_pred = u_lin
            for c, e in zip(st["pC"], hist):
                u_pred = u_pred + ap(c, e)
            if "B" in st:
                u = u_lin + ap(st["B"], eps) \
                    + ap(st["P_chol"], self._noise(seeds, alg, k))
            elif "cC" in st:
                eps_n = self._eps(u_pred, np.full((b,), st["t_next"]))
                u = u_lin + ap(st["cC"][0], eps_n)
                for c, e in zip(st["cC"][1:], hist):
                    u = u + ap(c, e)
            else:
                u = u_pred
            hist = hist[:co["q"]]
        return [x[0].reshape(self.cfg["data_shape"]) for x in u]
