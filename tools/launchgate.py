#!/usr/bin/env python
"""Launch-wait-harvest-gate harness for the multi-replica serving tier.

    PYTHONPATH=src python tools/launchgate.py --replicas 2 --check-solo

The reframe pattern (ROADMAP open item 1), applied to the router fleet:

  1. **Launch** — the parent computes the deterministic route plan
     (serve/router.py) for the canonical router-benchmark trace, writes
     each replica's wire-form sub-trace to the workdir, and spawns N
     replica processes.  Each replica joins a real `jax.distributed`
     fleet (repro.distributed.multihost — process 0 hosts the
     coordinator), builds + warms its engine, and clears the shared
     readiness barrier.
  2. **Wait** — the parent polls per-replica readiness sentinels (each
     written only after the fleet-wide barrier clears, i.e. after every
     replica is warmed), then waits for the serves to finish, with a
     hard timeout so a wedged replica fails the job instead of hanging
     it.
  3. **Harvest** — every replica writes `replica_<i>.json`: its engine's
     deterministic BENCH counters (rounds / dispatches / polls /
     recompiles-after-warmup) plus a sha256 digest of every served
     sample.  The parent merges them with the route-plan counters into
     the `gddim_router_R2` record.
  4. **Gate** — nonzero exit if any replica fails, any replica
     recompiled after warmup, the merged counters disagree with the
     route plan, a routed sample's digest differs from the single-host
     solo engine's (`--check-solo`: the bitwise acceptance), or the
     deterministic counters drift from the committed `BENCH_serving.json`
     row.  On success the record is merged into `--bench-json` (the
     in-process benchmark, `python -m benchmarks.run serving`, produces
     the identical record via `run_in_process()` below — both modes
     route the same plan and serve the same sub-traces, so the counters
     agree by construction, and tools/perf_guard.py EXACT-gates them).

In CI this runs N local processes on one machine (the `serve-router`
job).  The spawned replicas are pinned to the CPU: the harness checks the
routing and fleet protocol and measures no device (a TPU chip belongs to
one process, so on a chip the N replicas run in one process through the
in-process router: `python -m repro.launch.serve --replicas N`).  On a real cluster the same three-phase shape maps onto k8s: one
headless Service + StatefulSet of N replicas, each pod running
`tools/launchgate.py --worker --replica $POD_ORDINAL --coordinator
<pod-0-dns>:12355`, with the parent's wait/harvest/gate phases as a Job
reading the per-replica JSON from a shared volume — see
docs/serving.md#multi-host-serving-and-the-router-front-tier for the
manifest sketch.
"""
from __future__ import annotations

import argparse
import hashlib
import json
import os
import socket
import subprocess
import sys
import tempfile
import time
from typing import Any, Dict, List, Optional, Tuple

REPO_ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if os.path.join(REPO_ROOT, "src") not in sys.path:
    sys.path.insert(0, os.path.join(REPO_ROOT, "src"))

# ---------------------------------------------------------------------------
# the canonical router-benchmark scenario (shared with benchmarks/serving.py
# so the in-process record and the multi-process harvest agree EXACTLY)
# ---------------------------------------------------------------------------
N_REPLICAS = 2
BATCH = 4
NFE = 10
PREVIEW_NFE = 5
N_REQUESTS = 12
TRACE_SEED = 23
TRACE_RATE = 0.8
RECORD_CONFIG = f"gddim_router_R{N_REPLICAS}"
# replica 1 is down for a deterministic window mid-trace: probes at the
# 4.0 cadence catch it, traffic shifts to replica 0, and the backpressure
# bound forces requeues — so the gated counters exercise the whole policy
FAULT_WINDOWS_R1 = ((6.0, 14.0),)


def record_config(n_replicas: int = N_REPLICAS) -> str:
    return f"gddim_router_R{n_replicas}"


def build_router(n_replicas: int = N_REPLICAS):
    from repro.serve import ReplicaSpec, Router, RouterConfig
    specs = [ReplicaSpec(i, batch=BATCH,
                         fault_windows=FAULT_WINDOWS_R1 if i == 1 else ())
             for i in range(n_replicas)]
    return Router(specs, RouterConfig(
        max_queue_depth=3, probe_every=4.0, requeue_delay=1.0,
        max_requeues=8, default_nfe=NFE))


def build_trace():
    from repro.serve import SampleRequest, poisson_trace

    def make_request(i, rng):
        return SampleRequest(rid=i, seed=i,
                             nfe=PREVIEW_NFE if i % 3 == 0 else None)

    return poisson_trace(make_request, n=N_REQUESTS, rate=TRACE_RATE,
                         seed=TRACE_SEED)


def build_engine():
    """One warmed replica engine.  The warmup serves both NFE buckets the
    trace draws from, so the measured routed serve compiles nothing."""
    import jax
    from repro.configs import get_diffusion
    from repro.serve import DiffusionEngine, SampleRequest

    spec = get_diffusion("cifar10-ddpm", reduced=True)
    params = spec.init(jax.random.PRNGKey(0))  # staticcheck: disable=SC102 (fixed scenario seed on purpose: every replica AND the solo reference must init identical params for the bitwise gate)
    engine = DiffusionEngine(spec, params, batch_size=BATCH, nfe=NFE)
    engine.serve([SampleRequest(rid=-1, seed=0),
                  SampleRequest(rid=-2, seed=0, nfe=PREVIEW_NFE)])
    warm_stats = sum(engine.compile_stats().values())
    return engine, warm_stats


def replica_counters(engine, warm_stats: int, served: Dict[int, Any],
                     marks: Tuple[int, int, int]) -> Dict[str, Any]:
    """The per-replica BENCH counter JSON: deterministic engine counters
    for the measured (post-warmup) serve plus per-sample digests."""
    r0, s0, p0 = marks
    return {
        "rounds": engine.n_rounds - r0,
        "dispatches": engine.n_steps - s0,
        "polls": engine.n_polls - p0,
        "recompiles_after_warmup":
            sum(engine.compile_stats().values()) - warm_stats,
        "n_served": len(served),
        "digests": {str(rid): hashlib.sha256(x.tobytes()).hexdigest()
                    for rid, x in sorted(served.items())},
    }


def serve_wire_arrivals(engine, arrivals
                        ) -> Tuple[Dict[int, Any], Dict[str, Any]]:
    """Drain one replica's wire-form sub-trace — a list of
    (t, wire-request-dict) pairs, straight off a RoutePlan or a JSON file
    — through a warmed engine; returns (results, counter JSON).  The
    in-process benchmark and a spawned replica process both enter here,
    so their counters agree by construction."""
    from repro.serve import (Arrival, ServeRequest, TraceTraffic,
                             VirtualClock)
    warm_stats = sum(engine.compile_stats().values())
    marks = (engine.n_rounds, engine.n_steps, engine.n_polls)
    served: Dict[int, Any] = {}
    if arrivals:
        trace = TraceTraffic([Arrival(t, ServeRequest.from_wire(w))
                              for t, w in arrivals])
        served = engine.serve_stream(trace, clock=VirtualClock())
    return served, replica_counters(engine, warm_stats, served, marks)


def merge_record(plan, reports: List[Dict[str, Any]],
                 wall_dt: float) -> Dict[str, Any]:
    """The `gddim_router_R2` BENCH record from a route plan + per-replica
    counter reports.  Every field except the two wall-time columns is a
    pure function of (trace, router config, seeds) — EXACT/BOUNDED-gated
    by tools/perf_guard.py."""
    rounds = sum(r["rounds"] for r in reports)
    return {
        "workload": "diffusion",
        "config": record_config(len(reports)),
        "traffic": "routed-poisson",
        "n_replicas": len(reports),
        "batch": BATCH, "nfe": NFE,
        "n_requests": N_REQUESTS,
        **plan.counters,               # requests_routed / requeues /
                                       # health_probes / n_shed
        "rounds": rounds,
        "dispatches": sum(r["dispatches"] for r in reports),
        "polls": sum(r["polls"] for r in reports),
        "recompiles_after_warmup":
            sum(r["recompiles_after_warmup"] for r in reports),
        "per_replica_rounds": [r["rounds"] for r in reports],
        "us_per_round": round(1e6 * wall_dt / max(rounds, 1), 1),
        "samples_per_s": round(
            plan.counters["requests_routed"] / max(wall_dt, 1e-9), 3),
    }


def run_in_process(n_replicas: int = N_REPLICAS
                   ) -> Tuple[Dict[str, Any], Dict[int, Any], Any]:
    """The whole scenario in one process (used by benchmarks/serving.py):
    plan the routes, serve every sub-trace on its own warmed engine,
    merge.  Returns (record, merged results, plan)."""
    plan = build_router(n_replicas).plan(build_trace())
    reports, results = [], {}
    t0 = time.perf_counter()
    for i in range(n_replicas):
        engine, _ = build_engine()
        served, counters = serve_wire_arrivals(engine, plan.sub_traces[i])
        results.update(served)
        reports.append(counters)
    wall_dt = time.perf_counter() - t0
    return merge_record(plan, reports, wall_dt), results, plan


# ---------------------------------------------------------------------------
# replica worker (one process of the fleet)
# ---------------------------------------------------------------------------
def worker_main(args) -> int:
    from repro.distributed import multihost

    ctx = multihost.initialize(coordinator_address=args.coordinator,
                               num_processes=args.replicas,
                               process_id=args.replica)
    engine, _ = build_engine()                       # warm before 'ready'
    multihost.kv_set(f"launchgate/warm/{ctx.process_id}", "1")
    multihost.barrier("launchgate-ready", timeout_s=args.timeout)
    ready = os.path.join(args.workdir, f"ready_{ctx.process_id}")
    with open(ready, "w") as f:
        f.write("ready\n")

    # the sub-trace crosses the process boundary ONLY in wire form: the
    # parent wrote the plan's (t, ServeRequest.to_wire()) pairs, the
    # worker deserializes at its ingress
    with open(os.path.join(args.workdir,
                           f"subtrace_{ctx.process_id}.json")) as f:
        arrivals = json.load(f)["arrivals"]
    _, counters = serve_wire_arrivals(engine, arrivals)
    counters["replica"] = ctx.process_id
    out = os.path.join(args.workdir, f"replica_{ctx.process_id}.json")
    with open(out + ".tmp", "w") as f:
        json.dump(counters, f, indent=2, sort_keys=True)
    os.replace(out + ".tmp", out)
    multihost.barrier("launchgate-done", timeout_s=args.timeout)
    return 0


# ---------------------------------------------------------------------------
# parent: launch -> wait -> harvest -> gate
# ---------------------------------------------------------------------------
def _free_port() -> int:
    with socket.socket() as s:
        s.bind(("127.0.0.1", 0))
        return s.getsockname()[1]


def _gate(errors: List[str], ok: bool, message: str) -> None:
    print(("ok   " if ok else "FAIL ") + message)
    if not ok:
        errors.append(message)


def parent_main(args) -> int:
    workdir = args.workdir or tempfile.mkdtemp(prefix="launchgate_")
    os.makedirs(workdir, exist_ok=True)
    coordinator = f"127.0.0.1:{_free_port()}"
    plan = build_router(args.replicas).plan(build_trace())
    for i in range(args.replicas):
        with open(os.path.join(workdir, f"subtrace_{i}.json"), "w") as f:
            json.dump({"replica": i, "arrivals": plan.sub_traces[i]},
                      f, indent=2, sort_keys=True)
    print(f"route plan: {plan.counters} -> "
          f"{[len(s) for s in plan.sub_traces]} requests per replica")

    # -- launch -----------------------------------------------------------
    env = dict(os.environ)
    # the replicas check the fleet protocol, not a device: pinned to the
    # CPU, because one chip serves one process (on a chip, N replicas run
    # in one process: `repro.launch.serve --replicas N`)
    env["JAX_PLATFORMS"] = "cpu"
    env["PYTHONPATH"] = os.path.join(REPO_ROOT, "src") + (
        os.pathsep + env["PYTHONPATH"] if env.get("PYTHONPATH") else "")
    procs = []
    t0 = time.perf_counter()
    for i in range(args.replicas):
        log = open(os.path.join(workdir, f"replica_{i}.log"), "w")
        procs.append((subprocess.Popen(
            [sys.executable, os.path.abspath(__file__), "--worker",
             "--replica", str(i), "--replicas", str(args.replicas),
             "--coordinator", coordinator, "--workdir", workdir,
             "--timeout", str(args.timeout)],
            stdout=log, stderr=subprocess.STDOUT, env=env, cwd=REPO_ROOT),
            log))
    print(f"launched {args.replicas} replica processes "
          f"(coordinator {coordinator}, workdir {workdir})")

    # -- wait: readiness sentinels, then completion -----------------------
    errors: List[str] = []
    deadline = time.monotonic() + args.timeout
    ready = [os.path.join(workdir, f"ready_{i}")
             for i in range(args.replicas)]
    while time.monotonic() < deadline:
        if all(os.path.exists(p) for p in ready):
            break
        if any(p.poll() is not None and p.returncode != 0
               for p, _ in procs):
            break
        time.sleep(0.2)
    _gate(errors, all(os.path.exists(p) for p in ready),
          f"fleet ready ({sum(os.path.exists(p) for p in ready)}"
          f"/{args.replicas} replicas warmed + barrier cleared)")

    for i, (p, log) in enumerate(procs):
        try:
            code = p.wait(timeout=max(deadline - time.monotonic(), 1.0))
        except subprocess.TimeoutExpired:
            p.kill()
            code = -9
        log.close()
        _gate(errors, code == 0, f"replica {i} exited {code}")
    wall_dt = time.perf_counter() - t0

    # -- harvest ----------------------------------------------------------
    reports: List[Dict[str, Any]] = []
    for i in range(args.replicas):
        path = os.path.join(workdir, f"replica_{i}.json")
        if not os.path.exists(path):
            _gate(errors, False, f"replica {i}: no counter JSON harvested")
            with open(os.path.join(workdir, f"replica_{i}.log")) as f:
                tail = f.read().splitlines()[-12:]
            print("      " + "\n      ".join(tail))
            continue
        with open(path) as f:
            reports.append(json.load(f))
    if len(reports) != args.replicas:
        print(f"\nLAUNCHGATE FAILED: {errors}")
        return 1

    # -- gate -------------------------------------------------------------
    record = merge_record(plan, reports, wall_dt)
    for i, rep in enumerate(reports):
        _gate(errors, rep["recompiles_after_warmup"] == 0,
              f"replica {i}: recompiles_after_warmup == 0 "
              f"(got {rep['recompiles_after_warmup']})")
    _gate(errors,
          sum(r["n_served"] for r in reports) == record["requests_routed"],
          f"served {sum(r['n_served'] for r in reports)} == "
          f"routed {record['requests_routed']}")

    if args.check_solo:
        solo = _solo_digests()
        routed = {rid: d for r in reports for rid, d in r["digests"].items()}
        bad = [rid for rid, d in routed.items() if solo.get(rid) != d]
        _gate(errors, not bad,
              "routed samples bitwise == single-host solo engine "
              + (f"(mismatched rids: {bad})" if bad
                 else f"({len(routed)} digests)"))

    merged = _merge_bench_json(args.bench_json, record, errors)
    print(f"\n{record['config']}: " + json.dumps(
        {k: v for k, v in record.items()
         if k not in ("us_per_round", "samples_per_s")}, sort_keys=True))
    if errors:
        print(f"\nLAUNCHGATE FAILED ({len(errors)} gate(s)):")
        for e in errors:
            print(f"  {e}")
            if os.environ.get("GITHUB_ACTIONS") == "true":
                print(f"::error title=launchgate::{e}")
        return 1
    print(f"\nlaunchgate passed: {args.replicas} replicas, "
          f"record {'merged into ' + merged if merged else 'gated (no merge)'}")
    return 0


def _solo_digests() -> Dict[str, str]:
    """Single-host reference: ONE engine serves the whole trace; digests
    keyed by rid.  Per-request purity makes these the bitwise truth every
    routed replica must reproduce."""
    from repro.serve import VirtualClock
    engine, _ = build_engine()
    results = engine.serve_stream(build_trace(), clock=VirtualClock())
    return {str(rid): hashlib.sha256(bytes(x.data)).hexdigest()
            for rid, x in sorted(results.items())}


def _merge_bench_json(path: Optional[str], record: Dict[str, Any],
                      errors: List[str]) -> Optional[str]:
    """Gate the deterministic counters against an existing router record
    in `path` (the committed baseline in CI), then merge the fresh record
    in (replacing any previous row with the same config)."""
    if not path:
        return None
    gated = ("requests_routed", "requeues", "health_probes", "n_shed",
             "n_requests", "n_replicas", "batch", "nfe",
             "recompiles_after_warmup")
    doc = {"table": "serving", "records": []}
    if os.path.exists(path):
        with open(path) as f:
            doc = json.load(f)
        prev = next((r for r in doc.get("records", [])
                     if r.get("config") == record["config"]), None)
        if prev is not None:
            drift = {k: (prev.get(k), record.get(k)) for k in gated
                     if k in prev and prev.get(k) != record.get(k)}
            _gate(errors, not drift,
                  f"deterministic counters match committed {path}"
                  + (f" (drift: {drift})" if drift else ""))
    # replace in place (or append), preserving the benchmark writer's
    # record order so a gate-passing merge is a minimal diff
    recs = doc.get("records", [])
    idx = [i for i, r in enumerate(recs) if r.get("config")
           == record["config"]]
    if idx:
        recs[idx[0]] = record
        for i in reversed(idx[1:]):
            del recs[i]
    else:
        recs.append(record)
    doc["records"] = recs
    with open(path + ".tmp", "w") as f:
        json.dump(doc, f, indent=2, sort_keys=True)
        f.write("\n")
    os.replace(path + ".tmp", path)
    return path


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(
        description="launch-wait-harvest-gate harness for the router fleet")
    ap.add_argument("--replicas", type=int, default=N_REPLICAS)
    ap.add_argument("--workdir", default=None,
                    help="scratch dir for sub-traces / sentinels / harvested"
                         " JSON (default: a fresh tempdir)")
    ap.add_argument("--bench-json",
                    default=os.path.join(REPO_ROOT, "BENCH_serving.json"),
                    help="BENCH file to gate against and merge the "
                         f"{RECORD_CONFIG} record into ('' disables)")
    ap.add_argument("--check-solo", action="store_true",
                    help="also serve the whole trace on one single-host "
                         "engine and require bitwise-equal digests")
    ap.add_argument("--timeout", type=float, default=600.0,
                    help="readiness + completion timeout, seconds")
    # worker mode (one replica of the fleet; spawned by the parent or by a
    # k8s pod — not user-facing)
    ap.add_argument("--worker", action="store_true", help=argparse.SUPPRESS)
    ap.add_argument("--replica", type=int, default=0, help=argparse.SUPPRESS)
    ap.add_argument("--coordinator", default=None, help=argparse.SUPPRESS)
    args = ap.parse_args(argv)
    if args.replicas != N_REPLICAS:
        print(f"note: scenario counters are committed for "
              f"--replicas {N_REPLICAS}; {args.replicas} replicas will "
              "gate against the plan only", file=sys.stderr)
    if args.worker:
        return worker_main(args)
    return parent_main(args)


if __name__ == "__main__":
    raise SystemExit(main())
