"""GPipe-style pipeline parallelism as a shard_map stage-scan.

For 1000+-node scale-out, depth must shard across pods; this module maps the
classic GPipe schedule onto jax-native constructs (DESIGN.md §4): the layer
stack is sharded over a `stage` mesh axis, microbatches stream through
stages via `lax.ppermute`, and the whole schedule is one `lax.scan` of
length n_micro + n_stages - 1 (the pipeline fill/drain bubble is explicit).

Every device executes the same program (SPMD); stage s works on real data
from tick s onward.  Outputs of non-final ticks are masked garbage that the
caller discards, matching the standard bubble accounting:

    efficiency = n_micro / (n_micro + n_stages - 1).

The dry-run cells use FSDP+TP only (single pod fits every cell — see
EXPERIMENTS.md memory math); this module is exercised by a unit test on a
CPU mesh and is the documented scale-out path for llama3-405b beyond 2 pods.
"""
from __future__ import annotations

from typing import Any, Callable

import jax
import jax.numpy as jnp
from jax.sharding import Mesh, PartitionSpec as P

Array = jax.Array


def pipeline_apply(
    stage_fn: Callable[[Any, Array], Array],
    stage_params: Any,
    x_micro: Array,
    *,
    mesh: Mesh,
    stage_axis: str = "stage",
) -> Array:
    """Run `stage_fn` over `n_stages` pipeline stages.

    stage_params: pytree with leading axis n_stages (sharded over stage_axis).
    x_micro: (n_micro, micro_batch, ...) microbatched input, replicated.
    Returns (n_micro, micro_batch, ...) outputs after the final stage.
    """
    n_stages = mesh.shape[stage_axis]
    n_micro = x_micro.shape[0]
    total = n_micro + n_stages - 1

    def per_stage(params_s, x_all):
        # params_s: this stage's slice (leading axis 1); x_all: all microbatches
        params_s = jax.tree.map(lambda t: t[0], params_s)
        stage_id = jax.lax.axis_index(stage_axis)
        buf = jnp.zeros_like(x_all[0])
        outs0 = jnp.zeros_like(x_all)

        def tick(carry, t):
            buf, outs = carry
            # stage 0 injects microbatch t (when valid); others use the
            # activation received on the previous tick.
            inject = jnp.where(t < n_micro, t, 0)
            x_in = jnp.where(stage_id == 0, x_all[inject], buf)
            y = stage_fn(params_s, x_in)
            # the final stage retires microbatch (t - n_stages + 1)
            out_idx = t - (n_stages - 1)
            valid = (out_idx >= 0) & (out_idx < n_micro)
            idx = jnp.clip(out_idx, 0, n_micro - 1)
            upd = jnp.where(valid & (stage_id == n_stages - 1),
                            y, outs[idx])
            outs = jax.lax.dynamic_update_index_in_dim(outs, upd, idx, 0)
            # shift activations one stage forward (ring permute)
            buf = jax.lax.ppermute(
                y, stage_axis,
                [(i, (i + 1) % n_stages) for i in range(n_stages)])
            return (buf, outs), None

        (_, outs), _ = jax.lax.scan(tick, (buf, outs0), jnp.arange(total))
        # every stage holds the same `outs` garbage except the last; broadcast
        # the last stage's buffer to all (psum of masked contributions).
        mine = jnp.where(stage_id == n_stages - 1, 1.0, 0.0)
        outs = jax.lax.psum(outs * mine.astype(outs.dtype), stage_axis)
        return outs

    spec_params = jax.tree.map(lambda _: P(stage_axis), stage_params)
    fn = jax.shard_map(per_stage, mesh=mesh,
                   in_specs=(spec_params, P()), out_specs=P(),
                   check_vma=False)
    return fn(stage_params, x_micro)


def make_stage_mesh(n_stages: int) -> Mesh:
    devs = jax.devices()[:n_stages]
    import numpy as np
    return Mesh(np.array(devs).reshape(n_stages), ("stage",))
