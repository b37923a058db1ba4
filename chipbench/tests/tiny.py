"""Tiny cells for the CPU tests: the program's reduced DiT (8x8x3,
d_model 64, 2 layers, float32) under small backlog and mixed traffic, of
one family or of two co-resident ones."""
from __future__ import annotations

import json
from pathlib import Path

from chipbench.harness import Cell

HERE = Path(__file__).resolve().parent
ROOT = HERE.parents[1]


def config(family: str) -> dict:
    name = {"vpsde": "cifar10-ddpm", "cld": "cifar10-cld"}[family]
    full = json.loads((ROOT / "chipbench" / "configs" / f"{name}.json")
                      .read_text())
    return dict(full, program={"diffusion": name, "reduced": True},
                hidden_size=64, depth=2, num_heads=2, patch_size=4,
                input_size=8, data_shape=[8, 8, 3], dtype="float32",
                limits={"sample_rel_l2_max": 1e-3})


MIX = [
    {"weight": 2, "nfe": 4, "q": 1, "corrector": False, "lam": 0.0,
     "algorithm": "gddim"},
    {"weight": 1, "nfe": 4, "q": 1, "corrector": False, "lam": 0.5,
     "algorithm": "gmm"},
    {"weight": 1, "nfe": 5, "q": 2, "corrector": True, "lam": 0.0,
     "algorithm": "gddim"},
]


# CLD with lam > 0 at NFE 4 stops in the program's Stage-I (its injected
# covariance is not positive definite there; see PERF.md), so the CLD mix
# draws its stochastic rows at NFE 5
MIX_CLD = [
    {"weight": 2, "nfe": 4, "q": 1, "corrector": False, "lam": 0.0,
     "algorithm": "gddim"},
    {"weight": 1, "nfe": 5, "q": 1, "corrector": False, "lam": 0.5,
     "algorithm": "gddim"},
    {"weight": 1, "nfe": 6, "q": 2, "corrector": True, "lam": 0.0,
     "algorithm": "gddim"},
]


# the keys each family of a config gives itself; the rest are shared
OWN_KEYS = ("program", "in_channels", "sde", "limits")


def co_resident(*configs: dict) -> dict:
    """One config holding the families of single-family configs that
    share their DiT widths, in the order given."""
    shared = {k: v for k, v in configs[0].items() if k not in OWN_KEYS}
    return dict(shared, families=[{k: c[k] for k in OWN_KEYS}
                                  for c in configs])


# VPSDE and CLD co-resident, each family's mix entries naming it
MIX_TWO = [dict(m, family="vpsde") for m in MIX] \
    + [dict(m, family="cld") for m in MIX_CLD]


def cell(family: str = "vpsde", kind: str = "poisson",
         mix=None) -> Cell:
    """A tiny cell of one family, or, with family "vpsde+cld", of VPSDE and
    CLD co-resident under MIX_TWO."""
    if family == "vpsde+cld":
        cfg = co_resident(config("vpsde"), config("cld"))
        mix = mix or MIX_TWO
    else:
        cfg = config(family)
        mix = mix or (MIX_CLD if family == "cld" else MIX)
    traffic = {"kind": kind, "slots": 4, "rate_per_s": 6.0, "mix": mix,
               "check_samples": 6}
    return Cell(name=f"tiny-{family}-{kind}", chips=1,
                config=cfg, traffic=traffic,
                end_to_end=[{"name": n, "unit": u} for n, u in (
                    ("samples_per_s", "samples/s"), ("latency_p50_s", "s"),
                    ("latency_p95_s", "s"), ("setup_s", "s"))],
                per_layer=[])
