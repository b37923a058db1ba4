"""Critically-damped Langevin diffusion (Dockhorn et al. 2022; gDDIM paper
Eq. 10): every coefficient a 2x2 block over (x, v), the same at every
pixel."""
from __future__ import annotations

import functools

import numpy as np
import scipy.integrate
import scipy.linalg

from chipbench.families import IdentityBasis
from chipbench.reference import ODE_TOL


class CLD(IdentityBasis):
    """State (x, v): F = beta [[0, M^-1], [-1, -Gamma M^-1]],
    G2 = diag(0, 2 Gamma beta), Sigma_0 = diag(0, gamma M).  K = R solves
    Eq. 17, dR/dt = (F + 1/2 G2 Sigma^-1) R, from the symmetric square
    root of Sigma at t = r_anchor."""
    k = 2

    def __init__(self, beta: float, M_inv: float, Gamma: float, gamma: float,
                 T: float, t_min: float, r_anchor: float):
        self.T, self.t_min, self.r_anchor = T, t_min, r_anchor
        self.A = beta * np.array([[0.0, M_inv], [-1.0, -Gamma * M_inv]])
        self.Q = np.array([[0.0, 0.0], [0.0, 2.0 * Gamma * beta]])
        self.S0 = np.array([[0.0, 0.0], [0.0, gamma / M_inv]])

    def F(self, t):
        return self.A

    def G2(self, t):
        return self.Q

    def Sigma(self, t):
        # Van Loan: expm(t [[A, Q], [0, -A^T]]) = [[E, E V], [0, E^-T]],
        # V = int_0^t e^{-As} Q e^{-A^T s} ds, Sigma = E S0 E^T + E V E^T
        M = np.zeros((4, 4))
        M[:2, :2], M[:2, 2:], M[2:, 2:] = self.A, self.Q, -self.A.T
        X = scipy.linalg.expm(M * t)
        E, EV = X[:2, :2], X[:2, 2:]
        return E @ self.S0 @ E.T + EV @ E.T

    def Psi(self, t, s):
        return scipy.linalg.expm(self.A * (t - s))

    @functools.cached_property
    def _R(self):
        t0 = self.r_anchor
        w, v = np.linalg.eigh(self.Sigma(t0))
        R0 = (v * np.sqrt(np.clip(w, 0.0, None))) @ v.T

        def rhs(t, y):
            R = y.reshape(2, 2)
            return ((self.A + 0.5 * self.Q @ np.linalg.inv(self.Sigma(t)))
                    @ R).ravel()

        sol = scipy.integrate.solve_ivp(rhs, (t0, self.T), R0.ravel(),
                                        method="Radau", dense_output=True,
                                        **ODE_TOL)
        return sol.sol

    def K(self, t):
        return self._R(t).reshape(2, 2)


def make(params: dict, data_shape: tuple) -> CLD:
    return CLD(**params)
