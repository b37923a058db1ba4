"""Test fixture family: the shipped VPSDE in a seeded random orthonormal
basis V of the D coordinates.  VPSDE's blocks are the same at every
coordinate, so in any orthonormal basis it gives VPSDE's samples; only
the basis's plumbing (prior, noise, the net's input and output, the
sample) can make them differ."""
from __future__ import annotations

import numpy as np

from chipbench import families


class Rotated:
    def __init__(self, base, V: np.ndarray):
        self.base, self.V = base, V

    def __getattr__(self, name):
        return getattr(self.base, name)

    def to_basis(self, z):
        return z @ self.V

    def from_basis(self, y):
        return y @ self.V.T


def make(params: dict, data_shape: tuple) -> Rotated:
    D = int(np.prod(data_shape))
    V, _ = np.linalg.qr(np.random.default_rng(0).standard_normal((D, D)))
    return Rotated(families.make(dict(params, kind="vpsde"), data_shape), V)
