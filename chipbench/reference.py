"""The plain reference of a served sample: the configuration's DiT in
float32 and the gDDIM sampler chain in float64, written from the model's
and the paper's equations and importing nothing of the program.

What a served request must equal, given its seed and sampler fields:

  prior     u_T = chol(Sigma_T) V^T z,  z = normal(PRNGKey(seed),
            state_shape)
  grid      t_j = t_min + (T - t_min) (j/N)^2,  j = 0..N; step k moves
            t_i -> t_{i-1} with i = N - k
  eps       eps_i = DiT(u_i, t_i)   (the configuration's net, float32)
  predictor u_lin = Psi(t_{i-1}, t_i) u_i;
            u_pred = u_lin + sum_j pC_j eps_{i+j}   (Eq. 19/41: exponential
            integrator over the Lagrange extrapolation of the last
            q_cur = min(q, k+1) evals, pC_j = int_{t_i}^{t_{i-1}}
            1/2 Psi(t_{i-1}, s) G2(s) K(s)^{-T} l_j(s) ds, K = R of Eq. 17)
  corrector (Eq. 45/46, all steps but the last) u = u_lin + cC_0
            DiT(u_pred, t_{i-1}) + sum_{j>=1} cC_j eps_{i+j-1}, nodes
            (t_{i-1}, t_i, ...), q_corr = min(q, k+2)
  lambda>0  (Eq. 22/23) u = u_lin + (Psi_hat - Psi) K(t_i) eps_i
            + chol(P) n, Psi_hat the transition of F + (1+lam^2)/2 G2
            Sigma^{-1}, P its injected covariance; n is drawn from
            fold_in(fold_in(fold_in(PRNGKey(seed), noise_salt), alg), k);
            algorithm 'gmm' adds c sign(s) to it (s from a further
            fold_in(., gmm_salt), c = rho/sqrt(1-rho^2)) and scales chol(P)
            by sqrt(1-rho^2)
  output    the data channel of V u_0

The chain runs in the coordinates of the family's orthonormal basis V
(the state's own entries for VPSDE and CLD): u above is V^T of the state,
the DiT sees V u and its eps is carried back by V^T, and the noise n is
V^T of the state-space draw.  Each coefficient is a (k, k) block, the
same at every coordinate or one per coordinate (`families/__init__.py`);
a configuration's `sde.kind` names its family file.

Coefficients are computed here in float64 by Gauss-Legendre quadrature and
adaptive ODE solves (scipy), independently of the program's Stage-I code.
"""
from __future__ import annotations

import functools
import math
from typing import Dict, List

import numpy as np
import scipy.integrate

from chipbench import families

GL_POINTS = 64
ODE_TOL = dict(rtol=1e-10, atol=1e-13)


def _T(a: np.ndarray) -> np.ndarray:
    """Each (k, k) block transposed."""
    return np.swapaxes(a, -1, -2)


def apply(c: np.ndarray, z: np.ndarray) -> np.ndarray:
    """A coefficient, (k, k) or one (k, k) block per coordinate (D, k, k),
    on states z (b, k, D)."""
    if c.ndim == 2:
        return np.einsum("ij,bjd->bid", c, z)
    return np.einsum("dij,bjd->bid", c, z)


# ---------------------------------------------------------------------------
# Stage-I coefficients of one sampler config, float64
# ---------------------------------------------------------------------------
def time_grid(sde, n: int, kind: str) -> np.ndarray:
    x = np.linspace(0.0, 1.0, n + 1)
    if kind == "quadratic":
        x = x * x
    elif kind != "uniform":
        raise ValueError(kind)
    return sde.t_min + (sde.T - sde.t_min) * x


def _lagrange(nodes: List[float], j: int, s: np.ndarray) -> np.ndarray:
    out = np.ones_like(s)
    for m, tm in enumerate(nodes):
        if m != j:
            out = out * (s - tm) / (nodes[j] - tm)
    return out


def _ei_integral(sde, t_from: float, t_to: float, weight) -> np.ndarray:
    """int_{t_from}^{t_to} 1/2 Psi(t_to, s) G2(s) K(s)^{-T} weight(s) ds,
    K^{-T} = Sigma^{-1} K (K K^T = Sigma)."""
    x, w = np.polynomial.legendre.leggauss(GL_POINTS)
    half = 0.5 * (t_to - t_from)
    s = t_from + half * (x + 1.0)
    ws = weight(s)
    acc = 0.0
    for si, wi, li in zip(s, w, ws):
        core = 0.5 * sde.Psi(t_to, si) @ sde.G2(si) \
            @ np.linalg.solve(sde.Sigma(si), sde.K(si))
        acc = acc + wi * li * core
    return half * acc


def _stochastic(sde, t_i: float, t_im1: float, lam: float):
    """Psi_hat(t_{i-1}, t_i) and the injected covariance P (Eq. 22/23)."""
    def F_hat(s):
        return sde.F(s) + 0.5 * (1.0 + lam * lam) * sde.G2(s) \
            @ np.linalg.inv(sde.Sigma(s))

    shape = np.shape(F_hat(t_i))            # (k, k) or (D, k, k)
    ph = scipy.integrate.solve_ivp(
        lambda s, y: (F_hat(s) @ y.reshape(shape)).ravel(), (t_i, t_im1),
        np.broadcast_to(np.eye(sde.k), shape).ravel(), method="DOP853",
        **ODE_TOL).y[:, -1]

    def p_rhs(sig, y):
        P, fh = y.reshape(shape), F_hat(t_i - sig)
        return (-(fh @ P + P @ _T(fh))
                + lam * lam * sde.G2(t_i - sig)).ravel()

    P = scipy.integrate.solve_ivp(p_rhs, (0.0, t_i - t_im1),
                                  np.zeros(int(np.prod(shape))),
                                  method="DOP853",
                                  **ODE_TOL).y[:, -1].reshape(shape)
    return ph.reshape(shape), 0.5 * (P + _T(P))


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
            floor = 1e-14 * np.abs(np.trace(P, axis1=-2, axis2=-1))
            P = (v * np.maximum(w, floor[..., None])[..., None, :]) @ _T(v)
            L = np.linalg.cholesky(P)
            if alg == "gmm":
                L = L * math.sqrt(1.0 - sampler["gmm_rho"] ** 2)
            st["P_chol"] = L
        steps.append(st)
    return {"steps": steps, "q": q,
            "chol_T": np.linalg.cholesky(sde.Sigma(sde.T))}


# ---------------------------------------------------------------------------
# the configuration's DiT, float32 at "highest" matmul precision
# ---------------------------------------------------------------------------
def fp8(x):
    """x rounded to float8 e4m3 under a per-tensor scale (amax -> 448),
    back in float32: what an fp8 matmul input holds."""
    import jax.numpy as jnp
    s = jnp.maximum(jnp.max(jnp.abs(x)), 1e-30) / 448.0
    return (x / s).astype(jnp.float8_e4m3fn).astype(jnp.float32) * s


def dit_forward(p, u, t, cfg: dict, low=None):
    """u (B, [2,] H, W, C) float32, t (B,) -> eps of u's shape.  `low`
    (the control) rounds every matmul input, weights and activations,
    through it before the float32 product.  The net
    of the configuration file: patchify (state channels outermost), a
    sinusoidal time embedding through a 2-layer SiLU MLP, `depth`
    adaLN-zero blocks (LayerNorm without affine, modulated; multi-head
    self-attention; tanh-GELU MLP), a modulated final LayerNorm and a
    linear unpatchify."""
    import jax
    import jax.numpy as jnp

    hi = jax.lax.Precision.HIGHEST
    rd = (lambda x: x) if low is None else low
    mm = lambda a, b: jnp.matmul(rd(a), rd(b), precision=hi)
    B, img, P = u.shape[0], cfg["input_size"], cfg["patch_size"]
    d, H = cfg["hidden_size"], cfg["num_heads"]
    dh, C, side = d // H, cfg["in_channels"], img // P
    if u.ndim == 5:
        x = u.transpose(0, 1, 4, 2, 3).reshape(B, C, img, img)
    else:
        x = u.transpose(0, 3, 1, 2)
    x = x.reshape(B, C, side, P, side, P).transpose(0, 2, 4, 1, 3, 5)
    h = mm(x.reshape(B, side * side, C * P * P), p["patch_in"]) + p["pos"]

    half = cfg["frequency_embedding_size"] // 2
    freqs = jnp.exp(-math.log(1e4) * jnp.arange(half, dtype=jnp.float32)
                    / half)
    ang = t[:, None] * freqs[None] * 1000.0
    te = jnp.concatenate([jnp.cos(ang), jnp.sin(ang)], axis=-1)
    te = jax.nn.silu(mm(jax.nn.silu(mm(te, p["t_mlp1"])), p["t_mlp2"]))
    ste = jax.nn.silu(te)

    def ln(z):
        mu = z.mean(-1, keepdims=True)
        return (z - mu) * jax.lax.rsqrt(((z - mu) ** 2).mean(-1, keepdims=True)
                                        + 1e-5)

    def block(h, lp):
        sh1, sc1, g1, sh2, sc2, g2 = jnp.split(
            mm(ste, lp["ada_w"]) + lp["ada_b"], 6, axis=-1)
        z = ln(h) * (1.0 + sc1[:, None]) + sh1[:, None]
        a = lp["attn"]
        q, k, v = (mm(z, a[n]).reshape(B, -1, H, dh) for n in ("wq", "wk",
                                                               "wv"))
        s = jnp.einsum("bqhd,bkhd->bhqk", rd(q), rd(k),
                       precision=hi) / math.sqrt(dh)
        o = jnp.einsum("bhqk,bkhd->bqhd", rd(jax.nn.softmax(s, axis=-1)),
                       rd(v),
                       precision=hi).reshape(B, -1, d)
        h = h + g1[:, None] * mm(o, a["wo"])
        z = ln(h) * (1.0 + sc2[:, None]) + sh2[:, None]
        f = mm(jax.nn.gelu(mm(z, lp["mlp"]["w_up"]), approximate=True),
               lp["mlp"]["w_down"])
        return h + g2[:, None] * f, None

    h, _ = jax.lax.scan(block, h, p["layers"])
    sh, sc = jnp.split(mm(ste, p["final_ada_w"]) + p["final_ada_b"], 2,
                       axis=-1)
    out = mm(ln(h) * (1.0 + sc[:, None]) + sh[:, None], p["patch_out"])
    out = out.reshape(B, side, side, C, P, P).transpose(0, 3, 1, 4, 2, 5)
    out = out.reshape(B, C, img, img)
    if u.ndim == 5:
        out = out.reshape(B, 2, C // 2, img, img)
        return out.transpose(0, 1, 3, 4, 2)
    return out.transpose(0, 2, 3, 1)


# ---------------------------------------------------------------------------
# the chain
# ---------------------------------------------------------------------------
class Reference:
    """Reference samples for one family of a configuration (the
    configuration as that family sees it), in chunks of `chunk` rows (one
    compiled float32 forward per family).  `low=fp8` makes it the
    correctness check's control: the same chain with every matmul input
    in fp8."""

    def __init__(self, cfg: dict, params, chunk: int = 8, low=None):
        import jax
        import jax.numpy as jnp
        self.cfg, self.chunk = cfg, chunk
        self.sde = families.make(cfg["sde"], cfg["data_shape"])
        self.sampler = cfg["sampler"]
        self.state_shape = ((self.sde.k,) if self.sde.k > 1 else ()) \
            + tuple(cfg["data_shape"])
        self.D = int(np.prod(cfg["data_shape"]))
        self.p32 = jax.tree.map(lambda w: w.astype(jnp.float32), params)
        self._fwd = jax.jit(functools.partial(dit_forward, cfg=cfg, low=low))
        self._coeffs: Dict[tuple, dict] = {}

    def _eps(self, u: np.ndarray, t: np.ndarray) -> np.ndarray:
        """u (b, k, D) in the basis, float64 -> eps (b, k, D) in the
        basis, float64; the net runs in state space, padded to chunk."""
        import jax.numpy as jnp
        b = u.shape[0]
        x = np.zeros((self.chunk,) + self.state_shape, np.float32)
        x[:b] = self.sde.from_basis(u).reshape((b,) + self.state_shape)
        tt = np.full((self.chunk,), t[0], np.float32)
        tt[:b] = t
        e = self._fwd(self.p32, jnp.asarray(x), jnp.asarray(tt))
        return self.sde.to_basis(
            np.asarray(e, np.float64)[:b].reshape(u.shape))

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
        return self.sde.to_basis(np.stack(out))

    def _prior(self, seeds, chol_T) -> np.ndarray:
        import jax
        import jax.numpy as jnp
        z = np.stack([np.asarray(jax.random.normal(
            jax.random.PRNGKey(s), (1,) + self.state_shape, jnp.float32),
            np.float64).reshape(self.sde.k, self.D) for s in seeds])
        return apply(chol_T, self.sde.to_basis(z))

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
        seeds = [r["seed"] for r in reqs]
        alg, b = reqs[0]["algorithm"], len(reqs)
        u = self._prior(seeds, co["chol_T"])
        hist: List[np.ndarray] = []
        for k, st in enumerate(co["steps"]):
            eps = self._eps(u, np.full((b,), st["t"]))
            hist = [eps] + hist
            u_lin = apply(st["psi"], u)
            u_pred = u_lin
            for c, e in zip(st["pC"], hist):
                u_pred = u_pred + apply(c, e)
            if "B" in st:
                u = u_lin + apply(st["B"], eps) \
                    + apply(st["P_chol"], self._noise(seeds, alg, k))
            elif "cC" in st:
                eps_n = self._eps(u_pred, np.full((b,), st["t_next"]))
                u = u_lin + apply(st["cC"][0], eps_n)
                for c, e in zip(st["cC"][1:], hist):
                    u = u + apply(c, e)
            else:
                u = u_pred
            hist = hist[:co["q"]]
        return [x[0].reshape(self.cfg["data_shape"])
                for x in self.sde.from_basis(u)]
