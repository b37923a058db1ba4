"""One run of one cell: set-up, the measured window, the correctness check
and the metrics.  Everything cell-specific comes from files found by name
through BENCHMARK.json: `configs/<config>.json` (`file` of the config
entry), `traffic/<traffic>.json`, `metrics/<metric>.py`, and the
reference's `families/<kind>.py` of each SDE family the config holds.

A config file holds one family (its `program`, `in_channels`, `sde` and
`limits` among the DiT's widths) or several co-resident ones: a
`families` list whose entries each give the keys of their own, over the
keys the file shares.  A mix entry names its family under `family`, or
takes the file's first.  Each family gets its own net, its own reference
and its own limit; the engine holds them all.
"""
from __future__ import annotations

import dataclasses
import gc
import importlib.util
import json
import math
import shutil
import sys
import time
from pathlib import Path
from typing import Any, Callable, Dict, List, Optional, Tuple

import numpy as np

from . import traffic as traffic_lib

HERE = Path(__file__).resolve().parent
ROOT = HERE.parent
TRACE_SECONDS = 8.0          # the traced tail of the window (--trace 1)


class Abort(Exception):
    """The run cannot measure: no result is printed, the exit code is 2."""


def log(msg: str) -> None:
    print(msg, file=sys.stderr, flush=True)


# ---------------------------------------------------------------------------
# finding a cell's files
# ---------------------------------------------------------------------------
def family_views(config: dict) -> Dict[str, dict]:
    """{sde kind: the config as that family sees it}, in the file's order.
    A file without `families` is its own single family."""
    entries = config.get("families")
    if entries is None:
        return {config["sde"]["kind"]: config}
    shared = {k: v for k, v in config.items() if k != "families"}
    views: Dict[str, dict] = {}
    for e in entries:
        view = dict(shared, **e)
        kind = view["sde"]["kind"]
        if kind in views:
            raise Abort(f"family {kind!r} is listed twice")
        views[kind] = view
    return views


@dataclasses.dataclass
class Cell:
    """A cell as the run sees it: `families` is `family_views(config)`,
    and every mix entry of `traffic` names its family."""
    name: str
    chips: int
    config: dict
    traffic: dict
    end_to_end: List[dict]
    per_layer: List[dict]
    families: Dict[str, dict] = dataclasses.field(init=False)

    def __post_init__(self):
        from . import families as families_lib
        self.families = family_views(self.config)
        first = next(iter(self.families))
        mix = [dict({"family": first}, **m) for m in self.traffic["mix"]]
        unknown = {m["family"] for m in mix} - set(self.families)
        if unknown:
            raise Abort(f"mix entries of family {sorted(unknown)}; the "
                        f"config holds {list(self.families)}")
        for view in self.families.values():
            try:
                families_lib.path_of(view["sde"]["kind"])
            except FileNotFoundError as e:
                raise Abort(str(e)) from None
        self.traffic = dict(self.traffic, mix=mix)


def _applies(metric: dict, cell: str) -> bool:
    return "workloads" not in metric or cell in metric["workloads"]


def load_cell(name: str) -> Cell:
    bench_path = ROOT / "BENCHMARK.json"
    if not bench_path.is_file():
        raise Abort(f"{bench_path} not found")
    bench = json.loads(bench_path.read_text())
    cells = {w["name"]: w for w in bench["workloads"]}
    if name not in cells:
        raise Abort(f"unknown workload {name!r}; known: {sorted(cells)}")
    w = cells[name]
    cfg_entry = {c["name"]: c for c in bench["configs"]}[w["config"]]
    config = json.loads((ROOT / cfg_entry["file"]).read_text())
    traffic = traffic_lib.load_traffic(HERE / "traffic" / f"{w['traffic']}.json")
    return Cell(name=name, chips=w["chips"], config=config, traffic=traffic,
                end_to_end=[m for m in bench["end_to_end"]
                            if _applies(m, name)],
                per_layer=[m for m in bench["per_layer"] if _applies(m, name)])


def load_reader(metric: str) -> Callable:
    path = HERE / "metrics" / f"{metric}.py"
    spec = importlib.util.spec_from_file_location(
        f"chipbench_metric_{metric.replace('.', '_')}", path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod.read


# ---------------------------------------------------------------------------
# the system under test
# ---------------------------------------------------------------------------
def build_spec(config: dict):
    """The program's diffusion spec for this configuration at the file's
    depth (the program's own full-size spec stacks 24 layers; DiT-B
    publishes 12), checked against every size the file states."""
    from repro.configs import get_diffusion
    prog = config["program"]
    spec = get_diffusion(prog["diffusion"], reduced=prog["reduced"])
    spec = dataclasses.replace(spec, score_cfg=dataclasses.replace(
        spec.score_cfg, n_layers=config["depth"]))
    sc, sde = spec.score_cfg, spec.sde
    want = {
        "img_size": config["input_size"], "patch": config["patch_size"],
        "channels": config["in_channels"] // sc.state_mult,
        "d_model": config["hidden_size"], "n_layers": config["depth"],
        "n_heads": config["num_heads"], "dtype": config["dtype"],
    }
    have = {k: getattr(sc, k) for k in want}
    have["dtype"] = np.dtype(sc.dtype).name
    for k, v in config["sde"].items():
        if k not in ("kind", "r_anchor"):        # r_anchor: reference only
            want[f"sde.{k}"], have[f"sde.{k}"] = v, getattr(sde, k)
    want["family"], have["family"] = config["sde"]["kind"], \
        type(sde).__name__.lower()
    if (have != want or tuple(spec.data_shape) != tuple(config["data_shape"])
            or config["mlp_ratio"] != 4
            or config["frequency_embedding_size"] != 256):
        raise Abort(f"the program's {prog['diffusion']} spec does not match "
                    f"the configuration file: {have} vs {want}")
    return spec


class CompileCounter:
    """Backend compiles and persistent-cache loads, as JAX reports them."""

    def __init__(self):
        import jax
        self.n = 0
        jax.monitoring.register_event_listener(self._event)
        jax.monitoring.register_event_duration_secs_listener(self._duration)

    def _event(self, name, **kw):
        if name == "/jax/compilation_cache/cache_hits":
            self.n += 1

    def _duration(self, name, secs, **kw):
        if name == "/jax/core/compile/backend_compile_duration":
            self.n += 1


class TraceWindow:
    """Profiles the last `span` seconds of the window (--trace 1): starts
    the profiler from the loop's `due` hook, marks where the traced part
    opens and where the window closes (`span`, both on the benchmark's
    clock), and is stopped after the drain."""

    def __init__(self, logdir: Path, seconds: float, span: float):
        self.logdir, self.seconds = logdir, seconds
        self.t_start = max(0.0, seconds - span)
        self.started = self.closed = False
        self.span: Optional[Tuple[float, float]] = None

    def __call__(self, now: float) -> None:
        import jax
        if not self.started and now >= self.t_start:
            opts = jax.profiler.ProfileOptions()
            opts.python_tracer_level = 1
            opts.host_tracer_level = 2
            jax.profiler.start_trace(str(self.logdir),
                                     profiler_options=opts)
            with jax.profiler.TraceAnnotation("chipbench.trace_open"):
                pass
            self.started, self.span = True, (now, now)
        if self.started and not self.closed and now >= self.seconds:
            with jax.profiler.TraceAnnotation("chipbench.window_close"):
                pass
            self.closed, self.span = True, (self.span[0], now)

    def stop(self) -> Optional[Path]:
        import jax
        if not self.started:
            return None
        jax.profiler.stop_trace()
        found = sorted(self.logdir.glob("**/*.xplane.pb"))
        return found[-1] if found else None


@dataclasses.dataclass
class RunData:
    """What the metric readers see."""
    cell: Cell
    records: List[traffic_lib.Record]
    seconds: float
    peaks: Any
    trace: Any = None                   # trace.Reduction or None
    traced: Optional[Tuple[float, float]] = None   # its span, our clock
    notes: Dict[str, str] = dataclasses.field(default_factory=dict)


def make_request(i: int, f: dict):
    from repro.serve import ServeRequest
    return ServeRequest(rid=i, workload="diffusion", seed=f["seed"],
                        nfe=f["nfe"], q=f["q"], corrector=f["corrector"],
                        lam=f["lam"], algorithm=f["algorithm"],
                        family=f["family"])


def end_to_end(cell: Cell, records: List[traffic_lib.Record],
               seconds: float, t_end: float) -> Dict[str, float]:
    """samples_per_s: completions inside the window over the span; in a
    backlog the span runs from the first admission to the last completion
    inside the window (whole waves), in an open loop it is the window.
    Latencies: due -> sample on the host, over every request due in the
    window; one that never completed counts with the time it has waited."""
    done_in = [r.done for r in records
               if r.done is not None and r.done <= seconds]
    if cell.traffic["kind"] == "backlog":
        t0 = min(r.admit for r in records if r.admit is not None)
        span = (max(done_in) - t0) if done_in else 0.0
    else:
        span = seconds
    out = {"samples_per_s": len(done_in) / span if span > 0 else 0.0}
    lat = [(r.done if r.done is not None else t_end) - r.due
           for r in records]
    out["latency_p50_s"] = traffic_lib.percentile(lat, 50.0)
    out["latency_p95_s"] = traffic_lib.percentile(lat, 95.0)
    return out


def pick_checked(records: List[traffic_lib.Record], n: int,
                 seed: int) -> List[traffic_lib.Record]:
    """A seeded sample of the completed requests: one of every sampler
    config present (so the longest and every kernel path is in it), the
    rest drawn at random."""
    rng = np.random.default_rng(seed ^ 0x5A5A)
    done = [r for r in records if r.done is not None]
    by_cfg: Dict[tuple, List] = {}
    for r in done:
        by_cfg.setdefault(tuple(sorted((k, v) for k, v in r.fields.items()
                                       if k != "seed")), []).append(r)
    chosen = [grp[int(rng.integers(len(grp)))]
              for _, grp in sorted(by_cfg.items())]
    rest = [r for r in done if r not in chosen]
    k = max(0, min(n - len(chosen), len(rest)))
    chosen += [rest[i] for i in rng.choice(len(rest), k, replace=False)]
    return sorted(chosen, key=lambda r: r.rid)


def rel_l2(x: np.ndarray, ref: np.ndarray) -> float:
    x = np.asarray(x, np.float64)
    if not np.isfinite(x).all():
        return math.inf
    return float(np.linalg.norm(x - ref) / np.linalg.norm(ref))


# ---------------------------------------------------------------------------
# one run
# ---------------------------------------------------------------------------
def run_cell(cell: Cell, seed: int, seconds: float, trace: bool, *,
             t_process: float, require_tpu: bool = True,
             control: bool = False,
             engine_hook: Optional[Callable] = None) -> dict:
    """Run one cell once and return the result object.  The trace goes to
    `<checkout>/.chipbench_trace` and is deleted once read.  For
    chipbench's own checks, never in a run of the benchmark: `control`
    puts the check's control (the reference with fp8 matmul inputs) in
    the program's place for the compared samples; `engine_hook(engine)`
    may alter the engine after warm-up (the fault tests)."""
    import jax
    from . import peaks as peaks_lib
    devices = jax.devices()
    dev = devices[0]
    if require_tpu and (dev.platform != "tpu" or len(devices) < cell.chips):
        raise Abort(f"cell {cell.name} needs {cell.chips} TPU chip(s); JAX sees "
                    f"{len(devices)} {dev.platform} device(s)")
    peaks = peaks_lib.peaks_for(dev.device_kind) if require_tpu else None
    log(f"device: {len(devices)} x {dev.device_kind} ({dev.platform}), "
        f"jax {jax.__version__}")
    from repro.core import SamplerConfig
    from repro.serve import DiffusionEngine

    tr = cell.traffic
    compiles = CompileCounter()
    t = time.perf_counter()
    specs = {fam: build_spec(view) for fam, view in cell.families.items()}
    from .weights import make_weights
    params = {fam: make_weights(view, seed, family_index=j)
              for j, (fam, view) in enumerate(cell.families.items())}
    menu = traffic_lib.menu(tr)
    # the default config is a served one, so the engine's Stage-I
    # coefficients at start are ones that the traffic uses
    engine = DiffusionEngine(
        specs, params, batch_size=tr["slots"],
        default_config=SamplerConfig(**menu[0]))
    log(f"setup: weights and engine {time.perf_counter() - t:.3f} s")

    # warm-up: the whole menu registered and every class compiled, then a
    # second pass on warm programs gives the seconds per round
    t = time.perf_counter()
    warm = [make_request(-1 - j, dict(m, seed=j)) for j, m in enumerate(menu)]
    engine.serve(warm)
    stats = engine.compile_stats()
    log(f"setup: compile serve {time.perf_counter() - t:.3f} s {stats}")
    t, r0 = time.perf_counter(), engine.n_rounds
    engine.serve([dataclasses.replace(r, rid=r.rid - len(menu))
                  for r in warm])
    round_cost = (time.perf_counter() - t) / max(1, engine.n_rounds - r0)
    log(f"setup: warm serve {time.perf_counter() - t:.3f} s, "
        f"{round_cost * 1e3:.3f} ms per round")
    if engine_hook is not None:
        engine_hook(engine)

    plan = traffic_lib.Plan(tr, seed, seconds)
    tracer = None
    hooks = []
    if trace:
        tracer = TraceWindow(ROOT / ".chipbench_trace",
                             seconds, min(TRACE_SECONDS, seconds))
        hooks.append(tracer)
    # set-up's garbage (a cold run's compiles leave far more) is collected
    # and kept out of the window's collections, so every run's window
    # pays the same collector
    gc.collect()
    gc.freeze()
    clock = traffic_lib.WallClock()
    feed = traffic_lib.BenchTraffic(plan, engine, clock, seconds,
                                    make_request, hooks)
    setup_s = time.perf_counter() - t_process
    n_compiles = compiles.n
    served = engine.serve_stream(feed, clock=clock, round_cost=round_cost)
    feed.finish()
    gc.unfreeze()
    t_end = clock.now()
    in_window = compiles.n - n_compiles
    grown = engine.compile_stats() != stats
    log(f"window: {seconds} s, {len(feed.records)} requests due, drained "
        f"at {t_end:.3f} s; compiles in the window: {in_window} "
        f"(compile_stats {'grew' if grown else 'unchanged'})")
    mem = (dev.memory_stats() or {}).get("peak_bytes_in_use", 0) \
        if dev.platform == "tpu" else 0

    records = [feed.records[i] for i in sorted(feed.records)]
    e2e = end_to_end(cell, records, seconds, t_end)
    e2e["setup_s"] = setup_s

    trace_red = None
    if tracer is not None:
        path = tracer.stop()
        if path is not None:
            from . import trace as trace_lib
            trace_red = trace_lib.reduce_file(path)
        shutil.rmtree(tracer.logdir, ignore_errors=True)

    # free the program before the reference runs
    del engine, feed
    gc.collect()

    # each checked sample against its own family's reference and limit
    checked = pick_checked(records, tr["check_samples"], seed)
    t = time.perf_counter()
    from .reference import Reference, fp8
    checks: Dict[str, dict] = {}
    wrong, compared = 0, True
    for fam, view in cell.families.items():
        mine = [r for r in checked if r.fields["family"] == fam]
        fields = [r.fields for r in mine]
        want = Reference(view, params[fam]).samples(fields)
        got = Reference(view, params[fam], low=fp8).samples(fields) \
            if control else [served[r.rid] for r in mine]
        gaps = [rel_l2(x, w) for x, w in zip(got, want)]
        limit = view["limits"]["sample_rel_l2_max"]
        wrong += sum(1 for g in gaps if not g <= limit)
        compared = compared and bool(gaps)
        name = "sample_rel_l2_max" if len(cell.families) == 1 \
            else f"sample_rel_l2_max.{fam}"
        checks[name] = {"value": max(gaps) if gaps else math.inf,
                        "limit": limit}
    del params
    log(f"reference: {len(checked)} samples in "
        f"{time.perf_counter() - t:.3f} s")
    unfinished = sum(1 for r in records if r.done is None)
    correct = unfinished == 0 and wrong == 0 and compared
    checks["unfinished"] = {"value": unfinished, "limit": 0}

    result = {"correct": correct, "attempted": len(records),
              "failed": unfinished + wrong}
    data = RunData(cell=cell, records=records, seconds=seconds, peaks=peaks,
                   trace=trace_red,
                   traced=tracer.span if tracer is not None else None)
    metrics = {}
    if trace:
        for m in cell.per_layer:
            v = load_reader(m["name"])(data)
            if v is not None:
                metrics[m["name"]] = {"value": v, "unit": m["unit"]}
    else:
        for m in cell.end_to_end:
            metrics[m["name"]] = {"value": e2e[m["name"]], "unit": m["unit"]}
    for k, v in data.notes.items():
        log(f"metric {k}: {v}")
    result["metrics"] = metrics
    result["device"] = {"platform": dev.platform, "kind": dev.device_kind,
                        "count": len(devices), "memory_peak_bytes": int(mem)}
    if trace_red is not None:
        result["device"]["busy_s"] = trace_red.busy_s
        result["device"]["window_s"] = trace_red.window_s
        result["breakdown"] = trace_red.breakdown()
    for k, c in checks.items():
        log(f"check {k}: {c['value']} (limit {c['limit']})")
    result["checks"] = checks
    return result
