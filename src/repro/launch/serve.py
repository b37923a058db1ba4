"""Serving CLI: a thin driver over the `repro.serve` continuous-batching
engines (all engine logic — slot isolation, cache scatter, admission
grouping, the sampler-coefficient cache — lives in `repro.serve` and
`repro.core.coeffs`; this module only parses flags, builds a synthetic
request stream, and reports throughput).

Two workloads share the same scheduler/slot machinery:

  * token decoding (any Arch family) — batched prefill, per-slot positions,
    retire-and-refill without recompilation:

        python -m repro.launch.serve --arch gemma3-1b --reduced --requests 12

  * gDDIM sampling as a service — slots are samples, each at its own
    sampler step index *and* its own sampler config.  Homogeneous traffic
    uses the engine defaults (--nfe/--q/--corrector/--lam); heterogeneous
    traffic cycles requests through --mix specs, one comma-separated
    key=value config per spec:

        python -m repro.launch.serve --diffusion cifar10-ddpm --reduced \\
            --requests 9 --batch 3 \\
            --mix nfe=10 nfe=50,q=2,corrector nfe=20,lam=0.5

    The sampler *algorithm* is a per-request axis too (gddim | gmm |
    accel — see docs/sampler_math.md), so one engine serves a
    mixed-algorithm batch from the same warmed programs:

        python -m repro.launch.serve --diffusion cifar10-ddpm --reduced \\
            --requests 9 --batch 3 \\
            --mix algorithm=gddim algorithm=accel algorithm=gmm,lam=0.5

    One engine serves the whole mix from one warmed set of compiled step
    programs (`compile_stats` is printed so you can see it).  Passing a
    comma-separated list to --diffusion builds a *multi-family* engine
    (first entry = default family) and --mix specs may then pick their
    SDE family per request:

        python -m repro.launch.serve --reduced --requests 9 --batch 3 \\
            --diffusion cifar10-ddpm,cifar10-cld,cifar10-bdm \\
            --mix family=vpsde,nfe=10 family=cld,nfe=8 family=bdm,nfe=8

Both workloads take `--mesh` to shard the engine over a (data, model)
device mesh (slot batch and caches over `data`, params via the repo's
TP/FSDP rules) — e.g. on a CPU host:

        XLA_FLAGS=--xla_force_host_platform_device_count=2 \\
        python -m repro.launch.serve --diffusion cifar10-ddpm --reduced \\
            --requests 8 --batch 4 --mesh data=2

and `--sync-every` to bound how many device-resident rounds run between
host polls of the retire mask (see repro.serve.ServeLoop).

Flags are grouped to mirror `repro.serve.ServeRequest` (serve/api.py):
each --mix spec parses directly into `ServeRequest` field values, so the
CLI surface and the wire surface are the same vocabulary.  `--replicas N`
routes the request stream through the front-tier (`repro.serve.Router`)
over N engine replicas instead of one engine — the routed results are
bitwise-identical to the single-engine serve (see docs/serving.md,
"Multi-host serving and the router front-tier"):

        python -m repro.launch.serve --diffusion cifar10-ddpm --reduced \\
            --requests 12 --batch 4 --replicas 2

For the multi-process / multi-host version of the same fleet (spawned
replica processes, readiness barriers, harvested counters, CI gates) see
tools/launchgate.py and repro.distributed.multihost.
"""
from __future__ import annotations

import argparse
import dataclasses
import time

import numpy as np
import jax

from ..configs import get_arch, get_diffusion, ARCH_IDS, DIFFUSION_MODULES
from ..core import SamplerConfig
from ..models.registry import Arch
from ..serve import (DiffusionEngine, ReplicaSpec, Router, RouterConfig,
                     ServeRequest, TokenEngine)
from .compile_cache import enable_compile_cache
from .mesh import make_serve_mesh


def parse_sampler_spec(spec: str) -> dict:
    """Parse one --mix item:
    'family=cld,nfe=50,q=2,corrector,lam=0.5,grid=uniform,algorithm=gmm'.

    Bare flags ('corrector') mean True; 'lambda' is accepted for 'lam'.
    Returns a kwargs dict of `ServeRequest` sampler-config fields (the
    --mix vocabulary IS the wire vocabulary — serve/api.py); `main()`
    validates the merged `SamplerConfig` (defaults + spec) before any
    device work."""
    def parse_bool(v: str) -> bool:
        v = v.strip().lower()
        if v in ("", "1", "true", "yes", "on"):
            return True
        if v in ("0", "false", "no", "off"):
            return False
        raise ValueError(v)

    convert = {"nfe": int, "q": int, "lam": float, "grid": str.strip,
               "corrector": parse_bool, "family": str.strip,
               "algorithm": str.strip}
    out: dict = {}
    for part in spec.split(","):
        part = part.strip()
        if not part:
            continue
        key, _, val = part.partition("=")
        key = key.strip().replace("-", "_")
        if key == "lambda":
            key = "lam"
        if key not in convert:
            raise ValueError(f"unknown sampler-config key {key!r} in {spec!r}")
        try:
            out[key] = convert[key](val)
        except ValueError:
            raise ValueError(
                f"bad value {val!r} for {key} in {spec!r}") from None
    return out


def _mesh_banner(engine) -> str:
    if engine.mesh is None:
        return "single-device"
    return (f"mesh {dict(engine.mesh.shape)} "
            f"({engine.n_shards} slot shard{'s' if engine.n_shards > 1 else ''})")


def _serve_tokens(args) -> int:
    spec = get_arch(args.arch, reduced=args.reduced)
    arch = Arch(spec)
    key = jax.random.PRNGKey(args.seed)
    params = arch.init(key)

    rng = np.random.default_rng(args.seed)
    requests = []
    for rid in range(args.requests):
        # requests are frozen (serve/api.py) — every field, including the
        # encdec conditioning frames, is set at construction
        frames = None
        if spec.family == "encdec":
            frames = rng.standard_normal(
                (spec.frontend_ctx, arch.cfg.d_model)).astype(np.float32)
        requests.append(ServeRequest(
            rid=rid, workload="token",
            tokens=rng.integers(2, arch.cfg.vocab,
                                size=args.prompt_len).astype(np.int32),
            max_new=args.max_new, frames=frames))

    engine = TokenEngine(arch, params, batch_size=args.batch,
                         max_len=args.max_len, mesh=make_serve_mesh(args.mesh),
                         sync_every=args.sync_every)
    t0 = time.time()
    results = engine.serve(requests)
    dt = time.time() - t0
    tps = engine.n_tokens_out / max(dt, 1e-9)
    print(f"served {len(results)} requests in {dt:.1f}s "
          f"({engine.n_decode_steps} decode rounds, "
          f"{engine.n_prefill_calls} prefill calls, {engine.n_polls} polls, "
          f"batch {args.batch}, {_mesh_banner(engine)}, "
          f"{tps:.1f} tok/s)  compile={engine.compile_stats()}")
    for rid in sorted(results)[:4]:
        print(f"  req{rid}: {results[rid][:12].tolist()}...")
    return 0


def _serve_samples(args) -> int:
    from ..sde.base import family_name

    names = [n.strip() for n in args.diffusion.split(",") if n.strip()]
    specs = {}
    for n in names:
        spec = get_diffusion(n, reduced=args.reduced)
        fam = family_name(spec.sde)
        if fam in specs:
            raise SystemExit(f"--diffusion lists family {fam!r} twice")
        specs[fam] = spec
    default, mix = args.default_config, args.mix_parsed
    # reject --mix family typos while startup is still cheap (before any
    # score-net init / device work)
    for kw in mix:
        if kw.get("family") not in (None, *specs):
            raise SystemExit(
                f"--mix family {kw['family']!r} is not served; "
                f"--diffusion provides {list(specs)}")
    params = {fam: spec.init(jax.random.PRNGKey(args.seed))
              for fam, spec in specs.items()}
    if len(specs) == 1:
        specs, params = next(iter(specs.values())), next(iter(params.values()))

    def build_engine():
        return DiffusionEngine(specs, params, batch_size=args.batch,
                               default_config=default,
                               mesh=make_serve_mesh(args.mesh),
                               sync_every=args.sync_every)

    requests = []
    for i in range(args.requests):
        kw = mix[i % len(mix)] if mix else {}
        requests.append(ServeRequest(rid=i, workload="diffusion",
                                     seed=args.seed + i, **kw))

    if args.replicas > 1:
        return _serve_routed(args, build_engine, requests, default)

    engine = build_engine()
    t0 = time.time()
    results = engine.serve(requests)
    dt = time.time() - t0
    sps = engine.n_samples_out / max(dt, 1e-9)
    kinds = ("mixed traffic, "
             f"{len(engine.cache)} sampler configs, "
             f"families {engine.families}") if mix else \
        f"homogeneous @ NFE {default.nfe}"
    print(f"sampled {len(results)} requests in {dt:.1f}s "
          f"({engine.n_rounds} gDDIM rounds / {engine.n_steps} step "
          f"dispatches, {kinds}, "
          f"batch {args.batch}, {_mesh_banner(engine)}, "
          f"{sps:.2f} samples/s)  "
          f"compile={engine.compile_stats()}")
    if mix:
        for cfg in engine.cache.configs:
            print(f"  config: family={cfg.family} nfe={cfg.nfe} q={cfg.q} "
                  f"corrector={cfg.corrector} lam={cfg.lam} grid={cfg.grid} "
                  f"algorithm={cfg.algorithm}")
    return 0


def _serve_routed(args, build_engine, requests, default) -> int:
    """--replicas N: the in-process router fleet.  Deterministic arrival
    times (request i at virtual time i), one warmed engine per replica,
    the plan fully replayable from (requests, replica config, seeds)."""
    from ..serve import Arrival, TraceTraffic

    router = Router(
        [ReplicaSpec(index=i, batch=args.batch)
         for i in range(args.replicas)],
        RouterConfig(default_nfe=default.nfe))
    trace = TraceTraffic([Arrival(float(i), r)
                          for i, r in enumerate(requests)])
    engines = [build_engine() for _ in range(args.replicas)]
    t0 = time.time()
    results, plan = router.serve(trace, engines)
    dt = time.time() - t0
    sps = len(results) / max(dt, 1e-9)
    per_replica = [len(s) for s in plan.sub_traces]
    print(f"routed {len(results)} requests over {args.replicas} replicas "
          f"in {dt:.1f}s ({per_replica} per replica, "
          f"counters {plan.counters}, batch {args.batch}, "
          f"{sps:.2f} samples/s)")
    for a in plan.assignments[:6]:
        print(f"  t={a['t']:.1f} req{a['rid']} -> replica {a['replica']}"
              + (f" after {a['n_requeues']} requeues"
                 if a["n_requeues"] else ""))
    return 0


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(
        description="Serving CLI over the repro.serve engines; request "
                    "flags mirror the fields of repro.serve.ServeRequest "
                    "(the wire-level request type, serve/api.py)")
    g_model = ap.add_argument_group(
        "model / engine", "what is being served, and the engine shape")
    g_model.add_argument("--arch", choices=ARCH_IDS)
    g_model.add_argument("--diffusion", metavar="NAME[,NAME...]",
                         help="diffusion config(s) to serve, from "
                              f"{list(DIFFUSION_MODULES)}; a comma-separated "
                              "list builds one multi-family engine (first "
                              "entry = default family)")
    g_model.add_argument("--reduced", action="store_true")
    g_model.add_argument("--batch", type=int, default=4)
    g_model.add_argument("--max-len", type=int, default=64)

    g_req = ap.add_argument_group(
        "request stream (ServeRequest fields)",
        "how many requests, and their non-sampler ServeRequest fields "
        "(rid/seed are derived: rid=i, seed=--seed+i)")
    g_req.add_argument("--requests", type=int, default=8)
    g_req.add_argument("--prompt-len", type=int, default=16,
                       help="token workload: synthetic `tokens` prompt "
                            "length")
    g_req.add_argument("--max-new", type=int, default=24,
                       help="token workload: ServeRequest.max_new")
    g_req.add_argument("--seed", type=int, default=0)

    g_cfg = ap.add_argument_group(
        "sampler config (ServeRequest sampler fields)",
        "engine defaults for nfe/q/corrector/lam/grid; --mix overrides "
        "them per request with the same key=value vocabulary")
    g_cfg.add_argument("--nfe", type=int, default=20,
                       help="default sampler NFE (grid steps)")
    g_cfg.add_argument("--q", type=int, default=1,
                       help="default multistep order (Eq. 19)")
    g_cfg.add_argument("--corrector", action="store_true",
                       help="default: run the Eq. 45 corrector")
    g_cfg.add_argument("--lam", "--lambda", type=float, default=0.0,
                       dest="lam",
                       help="default stochasticity lambda (Eq. 22)")
    g_cfg.add_argument("--grid", choices=("quadratic", "uniform"),
                       default="quadratic")
    g_cfg.add_argument("--algorithm", choices=("gddim", "gmm", "accel"),
                       default="gddim",
                       help="default sampler update rule: gddim (Eq. 19), "
                            "gmm (moment-matched 2-component mixture "
                            "reverse kernel; needs lam>0), accel "
                            "(first-moment-corrected deterministic "
                            "update; needs q=1, lam=0)")
    g_cfg.add_argument("--mix", nargs="+", metavar="SPEC",
                       help="per-request sampler configs to cycle through, "
                            "e.g. --mix nfe=10 nfe=50,q=2,corrector "
                            "nfe=20,lam=0.5 family=cld,nfe=8 — each spec "
                            "is ServeRequest sampler fields as key=value "
                            "(keys not named fall back to the defaults "
                            "above; family= needs a multi-family "
                            "--diffusion list)")

    g_place = ap.add_argument_group(
        "placement", "mesh sharding, host-poll pacing, and the router "
                     "front-tier")
    g_place.add_argument("--mesh", default=None, metavar="SPEC",
                         help="shard the engine over a (data, model) device "
                              "mesh: 'data=2', 'data=2,model=1', '2x1', or "
                              "'auto' (all devices on the data axis).  Slot "
                              "batch and caches shard over data; params "
                              "follow the repo's TP/FSDP rules.  Default: "
                              "single device")
    g_place.add_argument("--sync-every", type=int, default=8,
                         help="max rounds between host polls of the done "
                              "mask (R); the loop polls sooner when a "
                              "retirement is provably near")
    g_place.add_argument("--replicas", type=int, default=1,
                         help="route the stream over N in-process engine "
                              "replicas via repro.serve.Router (diffusion "
                              "only; bitwise-identical results to "
                              "--replicas 1 — see docs/serving.md).  For "
                              "spawned-process replicas see "
                              "tools/launchgate.py")
    args = ap.parse_args(argv)
    if (args.arch is None) == (args.diffusion is None):
        ap.error("pass exactly one of --arch / --diffusion")
    if args.mix and args.diffusion is None:
        ap.error("--mix only applies to --diffusion serving")
    if args.replicas < 1:
        ap.error("--replicas must be >= 1")
    if args.replicas > 1 and args.diffusion is None:
        ap.error("--replicas routing currently applies to --diffusion "
                 "serving")
    if args.diffusion:
        for n in args.diffusion.split(","):
            if n.strip() not in DIFFUSION_MODULES:
                ap.error(f"unknown diffusion config {n.strip()!r}; known: "
                         f"{list(DIFFUSION_MODULES)}")
        # validate the full merged configs (defaults + every --mix spec)
        # here, before any model init / device work
        try:
            args.default_config = SamplerConfig(
                nfe=args.nfe, q=args.q, corrector=args.corrector,
                lam=args.lam, grid=args.grid, algorithm=args.algorithm)
            args.mix_parsed = [parse_sampler_spec(s)
                               for s in (args.mix or [])]
            for kw in args.mix_parsed:
                SamplerConfig(**{**dataclasses.asdict(args.default_config),
                                 **kw})
        except ValueError as e:
            ap.error(str(e))
    enable_compile_cache()
    return _serve_samples(args) if args.diffusion else _serve_tokens(args)


if __name__ == "__main__":
    raise SystemExit(main())
