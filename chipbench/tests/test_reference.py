"""The reference's family seam on the tiny configs: VPSDE and CLD give what
the single-family chain gave, and a family file that the shipped ones do
not include is found by its `sde.kind` alone."""
from pathlib import Path

import numpy as np
import pytest

from chipbench import families
from chipbench.reference import Reference
from chipbench.tests import tiny
from chipbench.tests.single_family_reference import SingleFamilyReference
from chipbench.weights import make_weights

FIXTURES = Path(__file__).resolve().parent / "families"


def requests(mix, seeds=(3, 2**31 + 17)):
    return [dict({k: v for k, v in m.items() if k != "weight"}, seed=s)
            for m in mix for s in seeds]


def worst_gap(xs, refs):
    return max(float(np.linalg.norm(x - r) / np.linalg.norm(r))
               for x, r in zip(xs, refs))


@pytest.mark.parametrize("family", ["vpsde", "cld"])
def test_families_reproduce_the_single_family_chain(family):
    """Every entry of the family's tiny mix (lam > 0, gmm, the corrector,
    q = 2) on two seeds, against the chain as it was before the seam."""
    cfg = tiny.config(family)
    params = make_weights(cfg, 5)
    reqs = requests(tiny.MIX_CLD if family == "cld" else tiny.MIX)
    got = Reference(cfg, params).samples(reqs)
    want = SingleFamilyReference(cfg, params).samples(reqs)
    gap = worst_gap(got, want)
    print(f"{family}: worst relative gap {gap:.3e}")
    assert gap <= 1e-12


@pytest.fixture
def fixture_families(monkeypatch):
    monkeypatch.setattr(families, "PATHS", [*families.PATHS, FIXTURES])


def _against_vpsde(kind):
    cfg = tiny.config("vpsde")
    params = make_weights(cfg, 6)
    reqs = requests(tiny.MIX)
    other = dict(cfg, sde=dict(cfg["sde"], kind=kind))
    return worst_gap(Reference(other, params).samples(reqs),
                     Reference(cfg, params).samples(reqs))


def test_a_family_file_is_found_by_its_kind(fixture_families):
    """VPSDE written per coordinate (one 1x1 block for each of the D
    coordinates), in a file of its own."""
    gap = _against_vpsde("vpsde_per_coordinate")
    print(f"per coordinate: worst relative gap {gap:.3e}")
    assert gap <= 1e-12


def test_a_family_in_another_basis(fixture_families):
    """VPSDE in a random orthonormal basis gives VPSDE's samples.  The DiT
    takes float32 inputs, and V V^T u differs from u in float64's last
    bits, so the readings may differ at float32 resolution; a basis left
    out of the prior, the noise or the net's input or output gives
    another sample altogether."""
    gap = _against_vpsde("vpsde_rotated")
    print(f"rotated basis: worst relative gap {gap:.3e}")
    assert gap <= 1e-5


def test_an_unknown_kind_names_the_file_it_looked_for():
    with pytest.raises(FileNotFoundError, match="bdm_blur.py"):
        families.make({"kind": "bdm_blur"}, (8, 8, 3))
