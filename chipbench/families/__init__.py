"""The reference's SDE families, one file each, found by a configuration's
`sde.kind`: `families/<kind>.py`.

A family file defines `make(params, data_shape)`, where `params` is the
configuration's `sde` block without `kind`, and returns an object with

  k                       state rows (1 for pixels, 2 for CLD's (x, v))
  T, t_min                the time interval the sampler's grid spans
  F(t), G2(t), Sigma(t),  float64 coefficients of the linear SDE
  Psi(t, s), K(t)         du = F u dt + G dw, G2 = G G^T, Sigma the marginal
                          covariance, Psi the transition of F, K K^T = Sigma
                          (K = R of gDDIM Eq. 17)
  to_basis(z)             (b, k, D) state-space entries -> coordinates of
  from_basis(y)           the family's orthonormal basis, and back

Each coefficient is a (k, k) array, the same block at every coordinate of
the basis, or a (D, k, k) array, one block per coordinate.  The reference
keeps its chain in the basis's coordinates: the prior and the per-step
noise are drawn in state space and carried into the basis, the score
net's input is carried out of it and its output back in, and the sample is
read out of it.  `IdentityBasis` is the basis of the state's own entries.
"""
from __future__ import annotations

import functools
import importlib.util
from pathlib import Path

# where family files are looked for, in order
PATHS = [Path(__file__).resolve().parent]


class IdentityBasis:
    """Coordinates are the state's own entries."""

    @staticmethod
    def to_basis(z):
        return z

    @staticmethod
    def from_basis(y):
        return y


def path_of(kind: str) -> Path:
    for d in PATHS:
        p = Path(d) / f"{kind}.py"
        if p.is_file():
            return p
    raise FileNotFoundError(f"no family file {kind}.py in "
                            f"{[str(d) for d in PATHS]}")


@functools.cache
def _module(path: Path):
    spec = importlib.util.spec_from_file_location(
        f"chipbench_family_{path.stem.replace('.', '_').replace('-', '_')}",
        path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def make(sde_cfg: dict, data_shape) -> object:
    """The family that `sde_cfg["kind"]` names, built from the rest of the
    block."""
    params = {k: v for k, v in sde_cfg.items() if k != "kind"}
    return _module(path_of(sde_cfg["kind"])).make(params, tuple(data_shape))
