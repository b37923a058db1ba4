"""Test fixture family: VPSDE written per coordinate, one 1x1 block for
each of the D coordinates, in the identity basis."""
from __future__ import annotations

import math

import numpy as np

from chipbench.families import IdentityBasis


class VPSDEPerCoordinate(IdentityBasis):
    k = 1

    def __init__(self, beta0: float, beta1: float, T: float, t_min: float,
                 D: int):
        self.b0, self.b1, self.T, self.t_min, self.D = beta0, beta1, T, \
            t_min, D

    def _each(self, x: float) -> np.ndarray:
        return np.full((self.D, 1, 1), x)

    def alpha(self, t):
        return math.exp(-(self.b0 * t + 0.5 * (self.b1 - self.b0) * t * t))

    def beta(self, t):
        return self.b0 + (self.b1 - self.b0) * t

    def F(self, t):
        return self._each(-0.5 * self.beta(t))

    def G2(self, t):
        return self._each(self.beta(t))

    def Sigma(self, t):
        return self._each(1.0 - self.alpha(t))

    def Psi(self, t, s):
        return self._each(math.sqrt(self.alpha(t) / self.alpha(s)))

    def K(self, t):
        return self._each(math.sqrt(1.0 - self.alpha(t)))


def make(params: dict, data_shape: tuple) -> VPSDEPerCoordinate:
    return VPSDEPerCoordinate(D=int(np.prod(data_shape)), **params)
