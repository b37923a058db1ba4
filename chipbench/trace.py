"""Reduction of a profiler trace to device busy time, per-kernel device
time and the host frames behind the device's idle gaps.

`load_events` turns an `.xplane.pb` into plain `Event`s (plane, line,
name, start, end in ns, on the trace's one clock); everything else works
on those, so it can be checked on hand-built events.

  window    from the `chipbench.trace_open` marker (else the first event)
            to the `chipbench.window_close` marker (else the last device
            event): the traced part of the measured window
  busy      the union of the device's op intervals inside the window,
            averaged over the device planes
  ops       each device op's self time (its duration less that of the ops
            nested in it, as a loop's body is in the loop), summed by the
            op's base name: '%flash_attention.5 = bf16[...] custom-call(..)'
            counts as 'flash_attention' (a Pallas kernel keeps its jitted
            function's name)
  gaps      each stretch of the window in which no op ran on the first
            device, labelled by the innermost serve-loop frame and the
            innermost frame of the host's Python stack at its midpoint
"""
from __future__ import annotations

import dataclasses
from collections import Counter, defaultdict
from pathlib import Path
from typing import Dict, Iterable, List, Tuple

OPEN, CLOSE = "chipbench.trace_open", "chipbench.window_close"
# the device's op line; the module and step lines repeat the same time
DEVICE_PREFIX, OPS_LINE = "/device:TPU:", "XLA Ops"
# frames of the serve loop, outermost first, by the function's name
LOOP_FRAMES = ("serve_stream", "_admit_stream", "_place_group",
               "_admit_wave", "_round", "_poll", "due", "advance_to")
MIN_GAP_NS = 1_000


@dataclasses.dataclass(frozen=True)
class Event:
    plane: str
    line: str
    name: str
    start: float
    end: float


def load_events(path: Path) -> List[Event]:
    import jax
    pd = jax.profiler.ProfileData.from_file(str(path))
    out = []
    for plane in pd.planes:
        for line in plane.lines:
            for e in line.events:
                out.append(Event(plane.name, line.name, e.name,
                                 float(e.start_ns),
                                 float(e.start_ns + e.duration_ns)))
    return out


def union(intervals: Iterable[Tuple[float, float]]) -> List[Tuple[float, float]]:
    merged: List[List[float]] = []
    for s, e in sorted(intervals):
        if merged and s <= merged[-1][1]:
            merged[-1][1] = max(merged[-1][1], e)
        else:
            merged.append([s, e])
    return [(s, e) for s, e in merged]


def clip(iv, lo: float, hi: float):
    return [(max(s, lo), min(e, hi)) for s, e in iv if e > lo and s < hi]


def op_name(name: str) -> str:
    """'%while.2 = (s32[], ...) while(...)' -> 'while'."""
    base = name.split(" = ", 1)[0].lstrip("%")
    head, _, tail = base.rpartition(".")
    return head if head and tail.isdigit() else base


def self_times(ops: List[Event]) -> List[Tuple[Event, float]]:
    """(op, self time in ns) for the ops of one device line, where an op
    that lies inside another is that one's child."""
    out: List[list] = []
    stack: List[list] = []
    for e in sorted(ops, key=lambda e: (e.start, -e.end)):
        while stack and stack[-1][0].end <= e.start:
            stack.pop()
        item = [e, e.end - e.start]
        if stack:
            stack[-1][1] -= e.end - e.start
        stack.append(item)
        out.append(item)
    return [(e, t) for e, t in out]


def frame_name(name: str) -> str:
    """'$engine.py:777 _round' -> '_round'; other names unchanged."""
    return name.rsplit(" ", 1)[-1] if name.startswith("$") else name


@dataclasses.dataclass
class Reduction:
    window_s: float
    busy_s: float                       # mean over device planes
    n_devices: int
    kernel_s: Dict[str, float]          # self seconds by op base name
    kernel_n: Dict[str, int]
    gaps: List[Tuple[str, float]]       # (host label, seconds), each gap

    @property
    def idle_share(self) -> float:
        return 1.0 - self.busy_s / self.window_s

    def kernel(self, name: str) -> Tuple[float, int]:
        """(seconds, calls) of the ops named `name`, per device."""
        n = self.n_devices
        return self.kernel_s.get(name, 0.0) / n, \
            self.kernel_n.get(name, 0) // n

    def breakdown(self) -> dict:
        ops = sorted(self.kernel_s.items(), key=lambda kv: -kv[1])[:10]
        by_label: Dict[str, List[float]] = defaultdict(list)
        for label, s in self.gaps:
            by_label[label].append(s)
        idle = sorted(((f"{k} (x{len(v)})", sum(v))
                       for k, v in by_label.items()), key=lambda kv: -kv[1])
        return {"device_ops": [[k, v] for k, v in ops],
                "idle_gaps": [[k, v] for k, v in idle[:10]]}


def reduce_events(events: List[Event]) -> Reduction:
    dev = [e for e in events
           if e.plane.startswith(DEVICE_PREFIX) and e.line == OPS_LINE]
    if not dev:
        raise ValueError("the trace holds no device op")
    marks = {e.name: e.start for e in events if e.name in (OPEN, CLOSE)}
    lo = marks.get(OPEN, min(e.start for e in events))
    hi = marks.get(CLOSE, max(e.end for e in dev))
    window = hi - lo
    planes = sorted({e.plane for e in dev})
    busy = 0.0
    for p in planes:
        iv = clip(union((e.start, e.end) for e in dev if e.plane == p), lo, hi)
        busy += sum(e - s for s, e in iv)
    kernel_s: Counter = Counter()
    kernel_n: Counter = Counter()
    for p in planes:
        for e, own in self_times([e for e in dev if e.plane == p]):
            if lo <= e.start < hi:
                kernel_s[op_name(e.name)] += own * 1e-9
                kernel_n[op_name(e.name)] += 1
    first = [(s, e) for s, e in clip(union(
        (e.start, e.end) for e in dev if e.plane == planes[0]), lo, hi)]
    gaps, cur = [], lo
    for s, e in first + [(hi, hi)]:
        if s - cur >= MIN_GAP_NS:
            gaps.append((cur, s))
        cur = max(cur, e)
    labels = labels_at(host_line(events), [0.5 * (a + b) for a, b in gaps])
    labelled = [(lab, (b - a) * 1e-9) for lab, (a, b) in zip(labels, gaps)]
    return Reduction(window_s=window * 1e-9,
                     busy_s=busy / len(planes) * 1e-9,
                     n_devices=len(planes), kernel_s=dict(kernel_s),
                     kernel_n=dict(kernel_n), gaps=labelled)


def host_line(events: List[Event]) -> List[Event]:
    """The events of the host thread that ran the loop: the line holding
    the trace-open marker, else every host line with Python frames."""
    host = [e for e in events if not e.plane.startswith(DEVICE_PREFIX)
            and e.plane != "/host:metadata"]
    mark = [(e.plane, e.line) for e in host if e.name == OPEN]
    if mark:
        return [e for e in host if (e.plane, e.line) == mark[0]]
    lines = {(e.plane, e.line) for e in host if e.name.startswith("$")}
    return [e for e in host if (e.plane, e.line) in lines]


def labels_at(host: List[Event], times: List[float]) -> List[str]:
    """What the host was doing at each time: the innermost serve-loop
    frame and the innermost frame that contain it (one sweep)."""
    order = sorted(range(len(times)), key=lambda i: times[i])
    evs = sorted(host, key=lambda e: (e.start, -e.end))
    out = [""] * len(times)
    active: List[Event] = []
    j = 0
    for i in order:
        t = times[i]
        while j < len(evs) and evs[j].start <= t:
            active.append(evs[j])
            j += 1
        active = [e for e in active if e.end > t]
        if not active:
            out[i] = "no host event"
            continue
        names = [frame_name(e.name) for e in active]
        loop = [n for n in names if n in LOOP_FRAMES]
        inner = names[-1]
        out[i] = f"{loop[-1]} > {inner}" if loop and loop[-1] != inner \
            else inner
    return out


def reduce_file(path: Path) -> Reduction:
    return reduce_events(load_events(path))
