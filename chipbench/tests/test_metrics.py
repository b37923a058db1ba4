"""The per-layer readers on hand-made runs."""
import json
from pathlib import Path

import pytest

from chipbench import counts, traffic
from chipbench.counts import dit_eval_flops
from chipbench.harness import Cell, RunData, load_reader
from chipbench.peaks import peaks_for
from chipbench.tests.tiny import co_resident
from chipbench.trace import Reduction

CONFIGS = Path(__file__).resolve().parents[1] / "configs"


def load(name):
    return json.loads((CONFIGS / f"{name}.json").read_text())


def run_data(records, busy_s, traced=(1.0, 3.0), cfg=None, mix=(),
             kernels=None):
    cell = Cell(name="hand", chips=1, config=cfg or load("cifar10-ddpm"),
                traffic={"kind": "backlog", "slots": 2, "mix": list(mix)},
                end_to_end=[], per_layer=[])
    s, n = kernels or ({}, {})
    red = Reduction(window_s=traced[1] - traced[0], busy_s=busy_s,
                    n_devices=1, kernel_s=s, kernel_n=n, gaps=[])
    return RunData(cell=cell, records=records, seconds=4.0,
                   peaks=peaks_for("TPU v5 lite"), trace=red, traced=traced)


def record(rid, admit, done, nfe=10, corrector=False, family="vpsde"):
    r = traffic.Record(rid, 0.0, {"nfe": nfe, "corrector": corrector,
                                  "family": family})
    r.admit, r.done = admit, done
    return r


def test_step_mfu_counts_the_needed_evals_inside_the_traced_span():
    # two waves tile 0-2 s and 2-4 s; the trace covers 1-3 s, half of
    # each; the corrector request needs 2 nfe - 1 evals
    recs = [record(0, 0.0, 2.0), record(1, 0.0, 2.0, corrector=True),
            record(2, 2.0, 4.0), record(3, 2.0, 4.0),
            record(4, 3.5, None)]            # never done: not counted
    evals = 0.5 * (10 + 19) + 0.5 * (10 + 10)
    got = load_reader("step_mfu")(run_data(recs, busy_s=1.5))
    want = 100 * evals * dit_eval_flops(run_data([], 1.0).cell.config)[
        "total"] / (1.5 * 197e12)
    assert got == pytest.approx(want, rel=1e-12)


def test_step_mfu_counts_each_request_at_its_own_familys_net():
    """VPSDE's net takes 3 channels and CLD's 6: their patch projections
    differ, so an eval costs a different number of FLOPs."""
    cfg = co_resident(load("cifar10-ddpm"), load("cifar10-cld"))
    recs = [record(0, 1.0, 3.0), record(1, 1.0, 3.0, nfe=50, family="cld"),
            record(2, 1.0, 3.0, nfe=20, corrector=True, family="cld")]
    got = load_reader("step_mfu")(run_data(recs, busy_s=2.0, cfg=cfg))
    fv = dit_eval_flops(load("cifar10-ddpm"))["total"]
    fc = dit_eval_flops(load("cifar10-cld"))["total"]
    assert fv < fc
    want = 100 * (10 * fv + (50 + 39) * fc) / (2.0 * 197e12)
    assert got == pytest.approx(want, rel=1e-12)


def _round_fused(cfg, q):
    mix = [{"weight": 1, "nfe": 50, "q": q, "corrector": False, "lam": 0.0,
            "algorithm": "gddim"}]
    data = run_data([], busy_s=1.0, cfg=cfg, mix=mix,
                    kernels=({"round_fused": 1e-3}, {"round_fused": 10}))
    return load_reader("round_fused_roofline")(data)


@pytest.mark.parametrize("name,kf", [("cifar10-ddpm", 1), ("cifar10-cld", 2)])
def test_round_fused_roofline_takes_its_width_from_the_family(name, kf):
    c = counts.round_fused_cost(batch=2, K=kf, Qb=2, kf=kf, D=3072,
                                pool_rows=1)
    least, _ = counts.roofline_seconds(c["flops"], c["bytes"],
                                       peaks_for("TPU v5 lite"))
    assert _round_fused(load(name), q=2) == pytest.approx(
        100 * 10 * least / 1e-3, rel=1e-12)


def test_round_fused_roofline_reads_nothing_across_widths():
    """VPSDE's and CLD's calls have different shapes under one op name."""
    cfg = co_resident(load("cifar10-ddpm"), load("cifar10-cld"))
    assert _round_fused(cfg, q=1) is None


def test_readers_find_nothing_without_a_trace():
    data = run_data([record(0, 0.0, 2.0)], busy_s=1.0)
    data.trace = None
    for name in ("step_mfu", "flash_attention_roofline",
                 "round_fused_roofline", "device_idle_share.backlog"):
        assert load_reader(name)(data) is None
