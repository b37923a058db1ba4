"""The generator gives every seed the same work in the same order; the
seed draws only each request's own seed."""
import json
from pathlib import Path

import pytest

from chipbench import traffic

TRAFFIC = Path(__file__).resolve().parents[1] / "traffic"


def load(name):
    return traffic.load_traffic(TRAFFIC / f"{name}.json")


def entry(nfe, weight, **kw):
    return dict({"weight": weight, "nfe": nfe, "q": 1, "corrector": False,
                 "lam": 0.0, "algorithm": "gddim"}, **kw)


# an open-loop mix in the shape a traffic file gives it
POISSON = {"kind": "poisson", "slots": 32, "rate_per_s": 12.0,
           "mix": [entry(10, 0.375), entry(10, 0.0625, lam=0.5),
                   entry(10, 0.0625, lam=0.5, algorithm="gmm"),
                   entry(20, 0.225, q=2), entry(20, 0.075, q=2,
                                                corrector=True),
                   entry(50, 0.15, q=2), entry(50, 0.05, q=2,
                                               corrector=True)],
           "check_samples": 24}


def test_poisson_seeds_share_counts_and_gaps():
    t = POISSON
    a, b = traffic.Plan(t, 1, 30.0), traffic.Plan(t, 2**31 + 5, 30.0)
    assert len(a.arrivals) == len(b.arrivals) == round(30 * t["rate_per_s"])
    assert a.order == b.order and len(set(a.order)) == len(t["mix"])
    assert a.arrivals == b.arrivals and 0 < a.arrivals[-1] < 30.0
    gaps = sorted(x - y for x, y in zip(a.arrivals, [0.0] + a.arrivals[:-1]))
    assert gaps[0] < gaps[-1]
    ea, eb = ([p.entry(i) for i in range(20)] for p in (a, b))
    assert [dict(e, seed=0) for e in ea] == [dict(e, seed=0) for e in eb]
    assert [e["seed"] for e in ea] != [e["seed"] for e in eb]
    assert ea == [traffic.Plan(t, 1, 30.0).entry(i) for i in range(20)]


def test_mix_counts_follow_the_weights():
    counts = traffic.allocate([m["weight"] for m in POISSON["mix"]], 240)
    assert sum(counts) == 240
    assert counts == [90, 15, 15, 54, 18, 36, 12]


def test_backlog_waves_are_whole_batches():
    t = load("backlog-cld-nfe50-q2")
    p = traffic.Plan(t, 7, 30.0)
    assert p.arrivals is None
    fields = [p.entry(i) for i in range(3 * t["slots"])]
    assert {f["nfe"] for f in fields} == {50}
    assert len({f["seed"] for f in fields}) == len(fields)


def test_percentile_interpolates_like_numpy():
    import numpy as np
    xs = [5.0, 1.0, 4.0, 2.0, 3.0, 10.0]
    for p in (50.0, 95.0):
        assert traffic.percentile(xs, p) == np.percentile(xs, p)


def test_mix_entries_may_name_a_family(tmp_path):
    t = {"kind": "backlog", "slots": 4, "check_samples": 2,
         "mix": [entry(10, 1, family="cld"), entry(10, 1)]}
    path = tmp_path / "two.json"
    path.write_text(json.dumps(t))
    assert [m.get("family") for m in traffic.load_traffic(path)["mix"]] \
        == ["cld", None]
    assert traffic.menu(t)[0]["family"] == "cld"
    path.write_text(json.dumps(dict(t, mix=[entry(10, 1, model="dit")])))
    with pytest.raises(ValueError, match="mix entry keys"):
        traffic.load_traffic(path)


def test_traffic_files_parse():
    for path in TRAFFIC.glob("*.json"):
        t = traffic.load_traffic(path)
        assert t["slots"] > 0 and t["check_samples"] > 0
        json.dumps(t)
