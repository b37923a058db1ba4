"""The benchmark's one traffic generator, its wall clock, and the traffic
objects that feed `ServeLoop.serve_stream`.

A traffic mix is a data file (`traffic/<name>.json`) read by
`load_traffic`; nothing here knows a mix by name.  Keys:

  kind          "backlog": waves of `slots` requests are released whenever
                the engine's queue is empty, so the queue stays at most one
                batch ahead of the slots while the window is open;
                "poisson": open-loop arrivals at `rate_per_s`
  slots         the engine's batch size B
  rate_per_s    poisson only: the offered rate
  mix           list of {weight, nfe, q, corrector, lam, algorithm} and,
                optionally, `family`: the SDE family of a config that
                holds several (the config's first where it is left out)
  check_samples how many served requests the correctness check compares

Every seed gives the same work in the same order: which mix entry each
request takes and the inter-arrival gaps are fixed by the file and the
window length, and the seed draws only each request's own seed (its
prior and noise).  So runs with different seeds measure the same load.
"""
from __future__ import annotations

import json
import math
import time
from pathlib import Path
from typing import Any, Callable, Dict, List, Optional

import numpy as np

KINDS = ("backlog", "poisson")
ORDER_SEED = 0          # orders the mix entries and the gaps of every run
MIX_KEYS = ("weight", "nfe", "q", "corrector", "lam", "algorithm")
OPTIONAL_MIX_KEYS = ("family",)


def load_traffic(path: Path) -> dict:
    t = json.loads(Path(path).read_text())
    if t.get("kind") not in KINDS:
        raise ValueError(f"{path}: kind must be one of {KINDS}")
    if t["kind"] == "poisson" and not t.get("rate_per_s", 0) > 0:
        raise ValueError(f"{path}: a poisson mix needs rate_per_s > 0")
    for m in t["mix"]:
        if not set(MIX_KEYS) <= set(m) <= set(MIX_KEYS + OPTIONAL_MIX_KEYS):
            raise ValueError(f"{path}: mix entry keys must be {MIX_KEYS}, "
                             f"optionally with {OPTIONAL_MIX_KEYS}")
    return t


def allocate(weights: List[float], n: int) -> List[int]:
    """Largest-remainder split of n items over weights (ties to the
    earlier entry): the same counts for every seed."""
    w = np.asarray(weights, np.float64)
    exact = n * w / w.sum()
    counts = np.floor(exact).astype(int)
    order = sorted(range(len(w)), key=lambda i: (-(exact[i] - counts[i]), i))
    for i in order[:n - int(counts.sum())]:
        counts[i] += 1
    return counts.tolist()


class Plan:
    """The request stream of one run: `entry(i)` is request i's sampler
    fields and seed; `arrivals` (poisson) the due times.  Only the seeds
    come from the run's seed."""

    def __init__(self, traffic: dict, seed: int, seconds: float):
        self.traffic = traffic
        self.rng = np.random.default_rng(seed)
        self.fixed = np.random.default_rng(ORDER_SEED)
        self.mix = traffic["mix"]
        self.arrivals: Optional[List[float]] = None
        if traffic["kind"] == "poisson":
            n = max(1, int(round(traffic["rate_per_s"] * seconds)))
            self.order = self._shuffled(n)
            # exponential quantiles at (j + 1/2)/n in a fixed order,
            # scaled so the last arrival falls inside the window at the
            # offered mean rate
            u = (np.arange(n) + 0.5) / n
            gaps = -np.log1p(-u) / traffic["rate_per_s"]
            gaps = gaps[self.fixed.permutation(n)]
            gaps *= (seconds * n / (n + 1)) / gaps.sum()
            self.arrivals = np.cumsum(gaps).tolist()
        else:
            self.order = []
        self.seeds: List[int] = []

    def _shuffled(self, n: int) -> List[int]:
        counts = allocate([m["weight"] for m in self.mix], n)
        idx = np.repeat(np.arange(len(self.mix)), counts)
        return idx[self.fixed.permutation(n)].tolist()

    def entry(self, i: int) -> dict:
        while i >= len(self.order):                 # backlog: one wave more
            self.order += self._shuffled(self.traffic["slots"])
        while i >= len(self.seeds):
            self.seeds.append(int(self.rng.integers(0, 2**31 - 1)))
        m = dict(self.mix[self.order[i]])
        m.pop("weight")
        m["seed"] = self.seeds[i]
        return m


def menu(traffic: dict) -> List[dict]:
    """The distinct sampler configs of a mix (warmed before the window)."""
    return [{k: v for k, v in m.items() if k != "weight"}
            for m in traffic["mix"]]


# ---------------------------------------------------------------------------
# clock and traffic objects driven by serve_stream
# ---------------------------------------------------------------------------
class WallClock:
    """Seconds since the window opened.  The loop's `advance` (one round
    dispatched) is a no-op: wall time moves by itself; `advance_to`
    sleeps until its time."""

    def __init__(self):
        self.t0 = time.perf_counter()

    def now(self) -> float:
        return time.perf_counter() - self.t0

    def advance(self, dt: float) -> None:
        pass

    def advance_to(self, t: float) -> None:
        wait = t - self.now()
        if wait > 0:
            time.sleep(wait)


class Record:
    """One request's life on the benchmark's clock: due, admitted (the
    loop's own stamp, taken on this clock) and done (stamped by the
    benchmark once the loop's poll has returned the sample)."""
    __slots__ = ("rid", "due", "fields", "admit", "done")

    def __init__(self, rid: int, due: float, fields: dict):
        self.rid, self.due, self.fields = rid, due, fields
        self.admit: Optional[float] = None
        self.done: Optional[float] = None


class BenchTraffic:
    """Feeds serve_stream (`due`, `next_time`) and watches it: every loop
    iteration calls `due`, which stamps the completion of each request the
    previous poll returned and runs the per-iteration hooks (the trace
    window).  The window closes at `seconds`; after that nothing more is
    due and the loop drains."""

    def __init__(self, plan: Plan, engine, clock: WallClock, seconds: float,
                 make_request: Callable[[int, dict], Any],
                 hooks: Optional[List[Callable[[float], None]]] = None):
        self.plan, self.engine, self.clock = plan, engine, clock
        self.seconds = seconds
        self.make_request = make_request
        self.hooks = hooks or []
        self.records: Dict[int, Record] = {}
        self._open: Dict[int, Record] = {}      # not yet stamped done
        self._next = 0
        self.t_close: Optional[float] = None

    # -- serve_stream interface ---------------------------------------------
    def due(self, now: float):
        self._stamp(now)
        for h in self.hooks:
            h(now)
        if self.t_close is not None:
            return []
        if now >= self.seconds:
            self.t_close = now
            # whatever fell due inside the window, even if the loop was
            # busy past the close
            return self._release(self.seconds) \
                if self.plan.arrivals is not None else []
        return self._release(now)

    def next_time(self) -> Optional[float]:
        if self.t_close is not None:
            return None
        if self.plan.arrivals is None:           # backlog: nothing is due
            return self.seconds                  # by time before the close
        if self._next < len(self.plan.arrivals):
            return min(self.plan.arrivals[self._next], self.seconds)
        return self.seconds

    # -- internals -------------------------------------------------------------
    def _release(self, now: float):
        from repro.serve.traffic import Arrival
        out = []
        if self.plan.arrivals is None:
            if self.engine.scheduler.n_pending == 0:
                for _ in range(self.plan.traffic["slots"]):
                    out.append(self._arrival(now, Arrival))
        else:
            arr = self.plan.arrivals
            while self._next < len(arr) and arr[self._next] <= now:
                out.append(self._arrival(arr[self._next], Arrival))
        return out

    def _arrival(self, t: float, arrival_cls):
        i = self._next
        self._next += 1
        fields = self.plan.entry(i)
        rec = Record(i, t, fields)
        self.records[i] = rec
        self._open[i] = rec
        return arrival_cls(t=t, request=self.make_request(i, fields))

    def _stamp(self, now: float) -> None:
        log = self.engine.request_log
        for rid in [r for r in self._open if log[r].t_done is not None]:
            rec = self._open.pop(rid)
            rec.done = now
            rec.admit = log[rid].t_admit

    def finish(self) -> None:
        """Stamp what the loop's last poll returned (serve_stream has
        returned, so the samples are host arrays now)."""
        self._stamp(self.clock.now())


# ---------------------------------------------------------------------------
# a copy of the program's percentile (repro/serve/traffic.py), so the
# yardstick does not move with it
# ---------------------------------------------------------------------------
def percentile(xs: List[float], p: float) -> float:
    """Linear-interpolation percentile (numpy's default method)."""
    if not xs:
        raise ValueError("percentile of an empty sample")
    xs = sorted(xs)
    rank = (len(xs) - 1) * (p / 100.0)
    lo = int(math.floor(rank))
    hi = min(lo + 1, len(xs) - 1)
    frac = rank - lo
    return xs[lo] * (1.0 - frac) + xs[hi] * frac
