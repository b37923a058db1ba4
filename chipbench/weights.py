"""Random score-net weights from the run's seed, made on the device in one
jitted call, in the dtype the configuration serves them in.

The tree is laid out as the program's DiT consumes it (per-layer leaves
stacked on a leading depth axis).  Every matrix is drawn N(0, 1/fan_in),
the positional table N(0, 0.02^2) and the biases zero, so that every
layer contributes: a DiT's usual zero-initialised output projection and
adaLN gates would make a fresh net return zeros.  The reference
(reference.py) reads these same arrays, upcast to float32.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

DTYPES = {"bfloat16": jnp.bfloat16, "float32": jnp.float32}


def _normal(key, shape, fan_in, dtype, scale=None):
    s = (1.0 / fan_in ** 0.5) if scale is None else scale
    return (jax.random.normal(key, shape, jnp.float32) * s).astype(dtype)


def dit_tree(key, cfg: dict):
    d, L = cfg["hidden_size"], cfg["depth"]
    ff = int(cfg["mlp_ratio"] * d)
    pd = cfg["patch_size"] ** 2 * cfg["in_channels"]
    side = cfg["input_size"] // cfg["patch_size"]
    td = cfg["frequency_embedding_size"]
    dt = DTYPES[cfg["dtype"]]
    k = iter(jax.random.split(key, 13))
    layers = {
        "attn": {n: _normal(next(k), (L, d, d), d, dt)
                 for n in ("wq", "wk", "wv", "wo")},
        "mlp": {"w_up": _normal(next(k), (L, d, ff), d, dt),
                "w_down": _normal(next(k), (L, ff, d), ff, dt)},
        "ada_w": _normal(next(k), (L, d, 6 * d), d, dt),
        "ada_b": jnp.zeros((L, 6 * d), dt),
    }
    return {
        "patch_in": _normal(next(k), (pd, d), pd, dt),
        "pos": _normal(next(k), (side * side, d), 1, dt, scale=0.02),
        "t_mlp1": _normal(next(k), (td, d), td, dt),
        "t_mlp2": _normal(next(k), (d, d), d, dt),
        "layers": layers,
        "final_ada_w": _normal(next(k), (d, 2 * d), d, dt),
        "final_ada_b": jnp.zeros((2 * d,), dt),
        "patch_out": _normal(next(k), (d, pd), d, dt),
    }


def make_weights(cfg: dict, seed: int, family_index: int = 0):
    """One jitted draw on the default device; the seed may exceed 32 bits.
    Each family of a config (`family_index`, in the file's order) draws
    its own net; the first draws from the seed's own key."""
    key = jax.random.fold_in(jax.random.PRNGKey(seed & 0x7FFFFFFF),
                             seed >> 31)
    if family_index:
        key = jax.random.fold_in(key, family_index)
    init = jax.jit(functools.partial(dit_tree, cfg=cfg))
    return jax.block_until_ready(init(key))
