"""End-to-end training driver (LM archs and paper diffusion configs).

Fault-tolerant by construction: atomic checkpoints every --ckpt-every steps
(async writer), ``--resume latest`` restarts exactly (data stream is a pure
function of step), and shardings are recomputed from the *present* device
count at startup — elastic re-meshing needs no config change.

Examples (CPU-sized):
    python -m repro.launch.train --arch gemma3-1b --reduced --steps 50
    python -m repro.launch.train --diffusion cifar10-cld --reduced --steps 200
"""
from __future__ import annotations

import argparse
import time

import numpy as np
import jax
import jax.numpy as jnp
from jax.sharding import Mesh

from ..configs import get_arch, get_diffusion, ARCH_IDS
from ..models.registry import Arch
from ..optim.adamw import AdamWCfg, adamw_init
from ..distributed.sharding import ShardCfg, param_shardings
from ..ckpt.store import CheckpointStore
from ..data.pipeline import TokenPipeline, MixturePipeline
from . import steps as steps_lib
from .compile_cache import enable_compile_cache
from .mesh import make_mesh


def make_auto_mesh() -> Mesh:
    """Largest (data, model) mesh over the devices actually present."""
    n = jax.device_count()
    model = 1
    for m in (16, 8, 4, 2, 1):
        if n % m == 0 and m <= n:
            model = m
            break
    return make_mesh((n // model, model), ("data", "model"))


def train_lm(args) -> dict:
    spec = get_arch(args.arch, reduced=args.reduced)
    arch = Arch(spec)
    mesh = make_auto_mesh()
    scfg = ShardCfg()
    opt_cfg = AdamWCfg(lr=args.lr, total_steps=args.steps,
                       warmup_steps=max(args.steps // 20, 5))

    key = jax.random.PRNGKey(args.seed)
    params = arch.init(key)
    psh = param_shardings(params, mesh, scfg)
    params = jax.device_put(params, psh)
    opt_state = adamw_init(params, opt_cfg)

    pipe = TokenPipeline(vocab=arch.cfg.vocab, seq_len=args.seq_len,
                         global_batch=args.batch, seed=args.seed)
    store = CheckpointStore(args.ckpt_dir) if args.ckpt_dir else None

    start_step = 0
    if store and args.resume:
        latest, restored = store.restore_latest((params, opt_state))
        if latest is not None:
            params, opt_state = restored
            start_step = latest
            print(f"resumed from step {start_step}")

    # donate (params, opt_state): the AdamW update is in-place at the XLA
    # level — no per-step copy of the two largest buffers in the job.
    # Safe because the loop rebinds both every step and CheckpointStore
    # copies leaves to host before the next step can donate them
    step_fn = jax.jit(steps_lib.make_train_step(arch, opt_cfg),
                      donate_argnums=(0, 1))

    losses = []
    t0 = time.time()
    it = pipe.iterator(start_step)
    for step in range(start_step, args.steps):
        batch = next(it)
        db = {"tokens": batch["tokens"], "labels": batch["labels"]}
        if spec.input_mode == "embeddings":
            emb = jax.random.normal(jax.random.fold_in(key, step),
                                    batch["tokens"].shape + (arch.cfg.d_model,),
                                    jnp.float32) * 0.02
            db = {"embeddings": emb, "labels": batch["labels"]}
        if spec.family == "encdec":
            db["frames"] = jax.random.normal(
                jax.random.fold_in(key, step),
                (args.batch, spec.frontend_ctx, arch.cfg.d_model)) * 0.02
        params, opt_state, metrics = step_fn(params, opt_state, db)
        losses.append(float(metrics["loss"]))
        if args.log_every and step % args.log_every == 0:
            print(f"step {step:5d} loss {losses[-1]:.4f} "
                  f"lr {float(metrics['lr']):.2e} "
                  f"gnorm {float(metrics['grad_norm']):.3f}", flush=True)
        if store and args.ckpt_every and (step + 1) % args.ckpt_every == 0:
            store.save(step + 1, (params, opt_state))
    if store:
        store.save(args.steps, (params, opt_state), blocking=True)
    dt = time.time() - t0
    print(f"done: {args.steps - start_step} steps in {dt:.1f}s; "
          f"loss {losses[0]:.4f} -> {losses[-1]:.4f}")
    return {"first_loss": losses[0] if losses else None,
            "last_loss": losses[-1] if losses else None}


def train_diffusion(args) -> dict:
    spec = get_diffusion(args.diffusion, reduced=args.reduced)
    opt_cfg = AdamWCfg(lr=args.lr, total_steps=args.steps,
                       warmup_steps=max(args.steps // 20, 5),
                       weight_decay=0.0)
    key = jax.random.PRNGKey(args.seed)
    params = spec.init(key)
    opt_state = adamw_init(params, opt_cfg)

    shp = spec.data_shape
    rng = np.random.default_rng(args.seed)
    means = rng.uniform(-1, 1, size=(4,) + tuple(shp))
    pipe = MixturePipeline(means=means, stds=np.full(4, 0.05),
                           weights=np.ones(4), global_batch=args.batch,
                           seed=args.seed)
    store = CheckpointStore(args.ckpt_dir) if args.ckpt_dir else None
    start_step = 0
    if store and args.resume:
        latest, restored = store.restore_latest((params, opt_state))
        if latest is not None:
            params, opt_state = restored
            start_step = latest

    step_fn = jax.jit(steps_lib.make_diffusion_train_step(spec, opt_cfg),
                      donate_argnums=(0, 1))
    losses = []
    it = pipe.iterator(start_step)
    for step in range(start_step, args.steps):
        batch = next(it)
        k = jax.random.fold_in(key, step)
        params, opt_state, metrics = step_fn(params, opt_state,
                                             {"x0": batch["x0"]}, k)
        losses.append(float(metrics["loss"]))
        if args.log_every and step % args.log_every == 0:
            print(f"step {step:5d} dsm-loss {losses[-1]:.4f}", flush=True)
        if store and args.ckpt_every and (step + 1) % args.ckpt_every == 0:
            store.save(step + 1, (params, opt_state))
    if store:
        store.save(args.steps, (params, opt_state), blocking=True)
    print(f"done: loss {losses[0]:.4f} -> {losses[-1]:.4f}")
    return {"first_loss": losses[0] if losses else None,
            "last_loss": losses[-1] if losses else None, "params": params}


def main(argv=None) -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", choices=ARCH_IDS)
    ap.add_argument("--diffusion")
    ap.add_argument("--reduced", action="store_true")
    ap.add_argument("--steps", type=int, default=100)
    ap.add_argument("--batch", type=int, default=8)
    ap.add_argument("--seq-len", type=int, default=128)
    ap.add_argument("--lr", type=float, default=3e-4)
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--ckpt-dir", default=None)
    ap.add_argument("--ckpt-every", type=int, default=0)
    ap.add_argument("--resume", action="store_true")
    ap.add_argument("--log-every", type=int, default=10)
    args = ap.parse_args(argv)
    enable_compile_cache()
    if args.diffusion:
        train_diffusion(args)
    elif args.arch:
        train_lm(args)
    else:
        ap.error("--arch or --diffusion required")
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
