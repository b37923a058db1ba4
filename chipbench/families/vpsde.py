"""VPSDE (Song et al. 2021; gDDIM paper Eq. 8): every coefficient a 1x1
block, the same at every pixel."""
from __future__ import annotations

import math

import numpy as np

from chipbench.families import IdentityBasis


class VPSDE(IdentityBasis):
    k = 1

    def __init__(self, beta0: float, beta1: float, T: float, t_min: float):
        self.b0, self.b1, self.T, self.t_min = beta0, beta1, T, t_min

    def alpha(self, t):
        return math.exp(-(self.b0 * t + 0.5 * (self.b1 - self.b0) * t * t))

    def beta(self, t):
        return self.b0 + (self.b1 - self.b0) * t

    def F(self, t):
        return np.array([[-0.5 * self.beta(t)]])

    def G2(self, t):
        return np.array([[self.beta(t)]])

    def Sigma(self, t):
        return np.array([[1.0 - self.alpha(t)]])

    def Psi(self, t, s):
        return np.array([[math.sqrt(self.alpha(t) / self.alpha(s))]])

    def K(self, t):
        return np.array([[math.sqrt(1.0 - self.alpha(t))]])


def make(params: dict, data_shape: tuple) -> VPSDE:
    return VPSDE(**params)
