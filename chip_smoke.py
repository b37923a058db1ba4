#!/usr/bin/env python
"""Chip smoke test: the full-width gDDIM diffusion serve path on a TPU.

    python chip_smoke.py [--seed 0]
    python chip_smoke.py --four-chips

Default (one chip): builds the multi-family `DiffusionEngine` that
`python -m repro.launch.serve --diffusion cifar10-ddpm,cifar10-cld,
cifar10-bdm` builds, with `reduced=False` (DiT d_model 768, 24 layers,
patch 2, 32x32x3, bf16) at `BATCH` slots, serves a request mix that
takes each kernel path of the round once, and checks:

  * every sample is finite and has the data shape;
  * `compile_stats()` does not grow after the warmup serve;
  * each compiled round-step variant holds the Pallas kernels
    (`tpu_custom_call`: flash attention + the fused round commit, plus the
    predictor for the corrector and dct2 for BDM), not the ref chain;
  * the fused commit `round_update(impl="pallas")` agrees with
    `impl="ref"` at full width, for every config of the mix;
  * the served DiT eps (bf16, Pallas attention) agrees with a float32
    `attention_ref` forward under `jax.default_matmul_precision("highest")`.

`--four-chips` runs only the mesh path: the same engine on a 4-way data
mesh (`--mesh data=4`) against the same requests on one device in this
process, and reports, per family, the samples' RMS, whether they are
bitwise equal and their relative error.

Weights are random, drawn from `--seed`.  The DiT's zero-initialised
output projection and 1e-3-scaled adaLN gates would make a fresh net
return zeros, so they are redrawn at 1/sqrt(fan_in) so that every layer
contributes.  The phase times printed are of one cold run and include
compilation; they are not throughput numbers.  The last line of standard
output is the JSON result; any failed check or phase exits nonzero
without it.  A machine without a TPU exits nonzero before any work.
"""
from __future__ import annotations

import argparse
import dataclasses
import functools
import json
import re
import sys
import time
from pathlib import Path

SRC = Path(__file__).resolve().parent / "src"
NFE = 10
BATCH = 32
# one request per kernel path of the round; requests cycle through it
MIX = (
    dict(family="vpsde"),                             # kf=1
    dict(family="cld"),                               # kf=2, the headline
    dict(family="bdm", lam=0.5),                      # dct2, noise streamed
    dict(family="cld", q=2, corrector=True),          # Eq. 45 predictor
    dict(family="cld", lam=0.5),                      # noise drawn in-kernel
    dict(family="vpsde", lam=0.5, algorithm="gmm"),   # gmm mixture noise
)
# Fused commit vs ref chain, max |pallas - ref| / max(1, max |ref|): both
# evaluate the same float32 expression on the VPU; they may differ by a few
# ulp where XLA associates the kf=2 block sum differently or rounds the
# erf_inv polynomial of the noise draw differently.  1e-5 is ~80 ulp at
# unit scale, and a wrong coefficient, noise stream or retire mask moves
# the state by 1e-2 or more.
COMMIT_TOL = 1e-5
# Served DiT eps vs the float32 reference, ||eps - eps_ref|| / ||eps_ref||:
# bf16 weights and activations round each matmul input to 8 mantissa bits
# (~4e-3 relative), and 48 residual branches add such errors roughly in
# quadrature.  A dropped head or a softmax over the wrong axis moves eps by
# O(1).
EPS_TOL = 5e-2
# Mesh vs one device, per family, max over its requests of
# ||x_mesh - x_one|| / ||x_one||: relative, because random weights give
# each family samples of its own scale (the chain does not cancel a random
# eps, so BDM's deblurring and CLD's velocity coefficients amplify it; the
# RMS is printed beside the error).  Same bf16 model and kernels, but the
# partitioner may order some sharded contractions differently than one
# device does, so samples may differ by bf16 rounding carried through the
# chain, which the eps budget bounds.  Rows computed on the wrong shard or
# from another slot's state differ by O(1).
MESH_TOL = 5e-2


def fail(msg: str) -> None:
    print(f"chip_smoke: FAIL: {msg}", file=sys.stderr)
    raise SystemExit(1)


def live_weights(params, key):
    """Redraw the DiT leaves a fresh init leaves (nearly) zero —
    adaLN modulation weights and the output projection — as
    N(0, 1/fan_in) in the params' own dtype."""
    import jax
    import jax.numpy as jnp

    def redraw(k, w):
        return (jax.random.normal(k, w.shape, jnp.float32)
                / jnp.sqrt(w.shape[-2])).astype(w.dtype)

    names = ("final_ada_w", "patch_out")
    ks = jax.random.split(key, len(names) + 1)
    out = dict(params, **{n: redraw(k, params[n]) for n, k in zip(names, ks)})
    out["layers"] = dict(params["layers"],
                         ada_w=redraw(ks[-1], params["layers"]["ada_w"]))
    return out


def build_engine(seed: int, mesh=None):
    import jax
    from repro.configs import get_diffusion
    from repro.core import SamplerConfig
    from repro.serve import DiffusionEngine
    names = {"vpsde": "cifar10-ddpm", "cld": "cifar10-cld",
             "bdm": "cifar10-bdm"}
    specs = {f: get_diffusion(n, reduced=False) for f, n in names.items()}
    params = {}
    for i, (f, spec) in enumerate(specs.items()):
        def init(key, spec=spec):
            return live_weights(spec.init(key), jax.random.fold_in(key, 1))
        params[f] = jax.jit(init)(
            jax.random.fold_in(jax.random.PRNGKey(seed), i))
    return DiffusionEngine(specs, params, batch_size=BATCH,
                           default_config=SamplerConfig(nfe=NFE),
                           mesh=mesh)


def requests(n: int, first_rid: int, seed: int):
    from repro.serve import ServeRequest
    return [ServeRequest(rid=first_rid + i, workload="diffusion",
                         seed=seed * 100003 + first_rid + i, nfe=NFE,
                         **MIX[i % len(MIX)])
            for i in range(n)]


def kernel_names(hlo: str):
    """Pallas kernels in a compiled program, by the jitted function that
    launched each `tpu_custom_call`, with each call's leading result dim."""
    out = []
    for line in hlo.splitlines():
        if 'custom_call_target="tpu_custom_call"' not in line:
            continue
        name = re.search(r'op_name="[^"]*jit\((\w+)\)/pallas_call', line)
        dim = re.search(r"= \(?\w+\[(\d+)", line)
        out.append((name.group(1) if name else "?",
                    int(dim.group(1)) if dim else -1))
    return out


def round_programs(engine):
    """(family, with_corrector) -> compiled HLO text of each round-step
    variant the served mix dispatches."""
    variants = {(c["family"], bool(c.get("corrector"))) for c in MIX}
    variants |= {(f, False) for f, _ in variants}
    out = {}
    for fam, corr in sorted(variants):
        step = engine._steps[(fam, "f32")]
        with engine._ctx():
            lowered = step.lower(engine._params_for(fam, "f32"),
                                 engine.state, engine._bank,
                                 with_corrector=corr)
        out[(fam, corr)] = lowered.compile().as_text()
    return out


def check(results, name, value, tol, passed=None):
    ok = value <= tol if passed is None else passed
    print(f"check {name}: {value:.3e} (tolerance {tol:.0e}) "
          f"{'ok' if ok else 'FAIL'}")
    results.append((name, ok))


def check_programs(results, engine):
    want = {"flash_attention", "round_fused"}
    for (fam, corr), hlo in round_programs(engine).items():
        names = {n for n, _ in kernel_names(hlo)}
        need = want | ({"round_predict"} if corr else set()) \
            | ({"dct2"} if fam == "bdm" else set())
        ok = need <= names
        print(f"check kernels[{fam}{'+corr' if corr else ''}]: "
              f"{sorted(names)} {'ok' if ok else 'FAIL: missing '}"
              f"{'' if ok else sorted(need - names)}")
        results.append((f"kernels[{fam}]", ok))


def check_commit(results, engine, seed: int):
    """round_update pallas vs ref at full width, one case per mix config.
    Both run under "highest" matmul precision: the commit kernel has no
    matmul, and BDM's noise is canonicalised (dct2) outside the commit by
    the same code in both paths, which must see the same precision."""
    import numpy as np
    import jax
    import jax.numpy as jnp
    from repro.core import SamplerConfig
    from repro.kernels.round_fused import ops as rf
    cache, bank, B = engine.cache, engine._bank, engine.batch_size
    K, Qb = cache.k_max, bank.pC_blk.shape[2]
    D = int(np.prod(engine._data_shape))
    for i, kw in enumerate(MIX):
        cfg = SamplerConfig(nfe=NFE, **kw)
        fam = cfg.family
        sde, kf, fi = engine.specs[fam].sde, engine.specs[fam].sde.packed_k, \
            cache.fam_index(fam)
        rng = np.random.default_rng(seed * 7919 + i)
        normal = lambda *s: jnp.asarray(rng.standard_normal(s), jnp.float32)
        k = jnp.asarray(rng.integers(0, NFE, B), jnp.int32)
        ci = jnp.full((B,), cache.index_of(cfg), jnp.int32)
        if cache.factored_bank is not engine._bank_src:
            fail(f"config {cfg} was not among the served ones")
        kc = jnp.clip(k, 0, bank.n_steps[ci] - 1)
        # a few inactive slots and a few of another family: both frozen
        fams = jnp.asarray(np.where(rng.random(B) < 0.9, fi, (fi + 1) % 3),
                           jnp.int32)
        active = jnp.asarray(rng.random(B) < 0.9)
        keys = jnp.asarray(rng.integers(0, 2**32, (B, 2), dtype=np.uint64),
                           jnp.uint32)
        u, hist, eps = normal(B, K, D), normal(B, Qb, K, D), normal(B, kf, D)
        eps_n = normal(B, kf, D) if cfg.corrector else None
        args = (u, hist, k, kc, ci, fams, jnp.zeros((B,), jnp.int32), keys,
                active, bank, eps)
        call = functools.partial(
            rf.round_update, sde=sde,
            state_shape=sde.state_shape(engine._data_shape), kf=kf,
            fam_index=fi, with_corrector=cfg.corrector, eps_n_c=eps_n)
        with jax.default_matmul_precision("highest"):
            got = jax.jit(functools.partial(call, impl="pallas"))(*args)
            ref = jax.jit(functools.partial(call, impl="ref"))(*args)
            if cfg.corrector:
                pred = functools.partial(rf.round_predict, kf=kf)
                pargs = (u, hist, kc, ci, bank, eps)
                got += (jax.jit(functools.partial(pred, impl="pallas"))(
                    *pargs),)
                ref += (jax.jit(functools.partial(pred, impl="ref"))(*pargs),)
        err, exact = 0.0, True
        for a, b in zip(got, ref):
            a, b = np.asarray(a), np.asarray(b)
            if a.dtype.kind == "f":
                b64 = b.astype(np.float64)
                err = max(err, float(np.abs(a - b64).max()
                                     / max(1.0, np.abs(b64).max())))
            else:
                exact &= bool(np.array_equal(a, b))
        label = ",".join(f"{name}={v}" for name, v in kw.items())
        check(results, f"commit[{label}]", err, COMMIT_TOL,
              passed=exact and err <= COMMIT_TOL)


def check_eps(results, engine, seed: int):
    """Served eps_model (bf16, Pallas attention) vs a float32 attention_ref
    forward of the same weights under "highest" precision, for the VPSDE
    and CLD nets (BDM's net has the VPSDE net's shapes)."""
    import numpy as np
    import jax
    import jax.numpy as jnp
    from repro.kernels.attention import ops as attn_ops
    from repro.models.score_net import dit_apply
    B = engine.batch_size
    for i, fam in enumerate(("vpsde", "cld")):
        spec = engine.specs[fam]
        params = engine.params[fam]
        rng = np.random.default_rng(seed * 104729 + i)
        shape = (B,) + spec.sde.state_shape(tuple(spec.data_shape))
        u = jnp.asarray(rng.standard_normal(shape), jnp.float32)
        t = jnp.asarray(rng.uniform(0.05, 1.0, B), jnp.float32)
        eps = np.asarray(jax.jit(spec.eps_model)(params, u, t), np.float64)
        cfg32 = dataclasses.replace(spec.score_cfg, dtype=jnp.float32)
        p32 = jax.tree.map(lambda w: w.astype(jnp.float32), params)
        attn_ops.FORCE_IMPL = "ref"      # read while the forward traces
        try:
            with jax.default_matmul_precision("highest"):
                ref = jax.jit(lambda p, x, s: dit_apply(p, cfg32, x, s))(
                    p32, u, t)
        finally:
            attn_ops.FORCE_IMPL = None
        ref = np.asarray(ref, np.float64)
        ok = bool(np.isfinite(eps).all())
        err = float(np.linalg.norm(eps - ref) / np.linalg.norm(ref))
        print(f"  eps[{fam}] rms {np.sqrt(np.mean(ref ** 2)):.3f}")
        check(results, f"eps[{fam}]", err, EPS_TOL,
              passed=ok and err <= EPS_TOL)


def by_family(out, reqs):
    """family -> stacked float64 samples of its requests."""
    import numpy as np
    fams = {}
    for r in reqs:
        fams.setdefault(r.family, []).append(out[r.rid])
    return {f: np.stack(v).astype(np.float64)
            for f, v in sorted(fams.items())}


def check_samples(results, engine, out, reqs):
    import numpy as np
    shape = tuple(engine._data_shape)
    bad = [r.rid for r in reqs
           if out[r.rid].shape != shape or not np.isfinite(out[r.rid]).all()]
    ok = len(out) == len(reqs) and not bad
    print(f"check samples: {len(out)}/{len(reqs)} served, non-finite or "
          f"misshapen {bad} {'ok' if ok else 'FAIL'}")
    results.append(("samples", ok))
    if ok:
        for fam, x in by_family(out, reqs).items():
            print(f"  samples[{fam}]: {len(x)}, "
                  f"rms {np.sqrt(np.mean(x ** 2)):.6e}")


def one_chip(args, results):
    t0 = time.perf_counter()
    engine = build_engine(args.seed)
    warm, reqs = requests(BATCH, 0, args.seed), \
        requests(BATCH, BATCH, args.seed)
    print(f"phase init: {time.perf_counter() - t0:.3f} s")
    t0 = time.perf_counter()
    engine.serve(warm)
    stats = engine.compile_stats()
    print(f"phase compile (warmup serve of {len(warm)} requests, one cold "
          f"run): {time.perf_counter() - t0:.3f} s  compile={stats}")
    t0 = time.perf_counter()
    out = engine.serve(reqs)
    print(f"phase serve ({len(reqs)} requests, {engine.n_rounds} rounds "
          f"in all, one run): {time.perf_counter() - t0:.3f} s")
    t0 = time.perf_counter()
    check_samples(results, engine, out, reqs)
    grown = engine.compile_stats() != stats
    print(f"check compile_stats after warmup: {engine.compile_stats()} "
          f"{'FAIL: grew' if grown else 'ok'}")
    results.append(("compile_stats", not grown))
    check_programs(results, engine)
    check_commit(results, engine, args.seed)
    check_eps(results, engine, args.seed)
    print(f"phase checks: {time.perf_counter() - t0:.3f} s")


def four_chips(args, results):
    import numpy as np
    from repro.launch.mesh import make_serve_mesh
    t0 = time.perf_counter()
    mesh_engine = build_engine(args.seed, mesh=make_serve_mesh("data=4"))
    one_engine = build_engine(args.seed)
    reqs = requests(BATCH, 0, args.seed)
    print(f"phase init: {time.perf_counter() - t0:.3f} s")
    t0 = time.perf_counter()
    got = mesh_engine.serve(reqs)
    print(f"phase serve on mesh data=4 (one cold run): "
          f"{time.perf_counter() - t0:.3f} s")
    t0 = time.perf_counter()
    want = one_engine.serve(reqs)
    print(f"phase serve on one device (one cold run): "
          f"{time.perf_counter() - t0:.3f} s")
    check_samples(results, mesh_engine, got, reqs)
    want = by_family(want, reqs)
    for fam, a in by_family(got, reqs).items():
        b = want[fam]
        axes = tuple(range(1, a.ndim))
        err = float(np.max(np.sqrt(((a - b) ** 2).sum(axes))
                           / np.sqrt((b ** 2).sum(axes))))
        print(f"  mesh vs one device[{fam}]: {len(a)} samples, one-device "
              f"rms {np.sqrt(np.mean(b ** 2)):.6e}, bitwise equal: "
              f"{bool(np.array_equal(a, b))}, max abs diff "
              f"{np.abs(a - b).max():.6e}")
        check(results, f"mesh_vs_one_device[{fam}]", err, MESH_TOL)
    per_shard = BATCH // 4
    for (fam, corr), hlo in round_programs(mesh_engine).items():
        calls = kernel_names(hlo)
        ok = bool(calls) and all(d == per_shard for _, d in calls)
        print(f"check per-shard kernels[{fam}{'+corr' if corr else ''}]: "
              f"{calls} {'ok' if ok else 'FAIL'} (want leading dim "
              f"{per_shard})")
        results.append((f"per_shard[{fam}]", ok))


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--seed", type=int, default=0,
                    help="seed of the random weights and the requests")
    ap.add_argument("--four-chips", action="store_true",
                    help="run only the 4-way data-mesh engine against the "
                         "one-device engine")
    args = ap.parse_args(argv)
    if not (SRC / "repro").is_dir():
        fail(f"{SRC / 'repro'} not found: run chip_smoke.py from a "
             "checkout of the repository")
    sys.path.insert(0, str(SRC))
    from repro.launch.compile_cache import enable_compile_cache
    cache_dir = enable_compile_cache()

    import jax
    devices = jax.devices()
    if devices[0].platform != "tpu":
        fail(f"no TPU found: JAX sees {len(devices)} "
             f"{devices[0].platform} device(s)")
    if args.four_chips and len(devices) < 4:
        fail(f"--four-chips needs 4 TPU devices, found {len(devices)}")
    print(f"jax {jax.__version__}, {len(devices)} x "
          f"{devices[0].device_kind}, compile cache {cache_dir}")

    results = []
    (four_chips if args.four_chips else one_chip)(args, results)
    failed = [name for name, ok in results if not ok]
    if failed:
        fail(f"checks failed: {failed}")
    print(json.dumps({"ok": True, "device": {
        "platform": devices[0].platform, "kind": devices[0].device_kind,
        "count": len(devices)}}))
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
