"""The per-layer readers on hand-made runs."""
import json
from pathlib import Path

import pytest

from chipbench import traffic
from chipbench.counts import dit_eval_flops
from chipbench.harness import Cell, RunData, load_reader
from chipbench.peaks import peaks_for
from chipbench.trace import Reduction

CONFIGS = Path(__file__).resolve().parents[1] / "configs"


def run_data(records, busy_s, traced=(1.0, 3.0)):
    cfg = json.loads((CONFIGS / "cifar10-ddpm.json").read_text())
    cell = Cell(name="hand", chips=1, config=cfg,
                traffic={"kind": "backlog", "slots": 2, "mix": []},
                end_to_end=[], per_layer=[])
    red = Reduction(window_s=traced[1] - traced[0], busy_s=busy_s,
                    n_devices=1, kernel_s={}, kernel_n={}, gaps=[])
    return RunData(cell=cell, records=records, seconds=4.0,
                   peaks=peaks_for("TPU v5 lite"), trace=red, traced=traced)


def record(rid, admit, done, nfe=10, corrector=False):
    r = traffic.Record(rid, 0.0, {"nfe": nfe, "corrector": corrector})
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


def test_readers_find_nothing_without_a_trace():
    data = run_data([record(0, 0.0, 2.0)], busy_s=1.0)
    data.trace = None
    for name in ("step_mfu", "flash_attention_roofline",
                 "round_fused_roofline", "device_idle_share.backlog"):
        assert load_reader(name)(data) is None
