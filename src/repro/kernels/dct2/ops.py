"""Dispatch wrappers for the dct2 / fused-BDM kernels."""
from __future__ import annotations

import functools

import jax

from ...distributed.sharding import per_batch_shard
from . import ref as _ref
from . import kernel as _kernel

Array = jax.Array


def _resolve(impl: str) -> str:
    if impl == "auto":
        return "pallas" if jax.default_backend() == "tpu" else "ref"
    return impl


def dct2(x: Array, inverse: bool = False, impl: str = "auto") -> Array:
    impl = _resolve(impl)
    if impl in ("pallas", "pallas_interpret"):
        return per_batch_shard(functools.partial(
            _kernel.dct2, inverse=inverse,
            interpret=impl == "pallas_interpret"), x)
    return _ref.idct2_ref(x) if inverse else _ref.dct2_ref(x)


def staticcheck_entries():
    """Named Pallas traces at representative serve shapes for
    tools/staticcheck's kernel checks.  Trace-only (jax.make_jaxpr of the
    pallas impl): runs on any backend, nothing is lowered or executed."""
    import jax.numpy as jnp
    B, H, W, Ch, q = 4, 32, 32, 3, 2    # CIFAR frame, q=2 multistep
    x = jnp.zeros((B, Ch, H, W), jnp.float32)
    u = jnp.zeros((B, H, W, Ch), jnp.float32)
    eps = jnp.zeros((q, B, H, W, Ch), jnp.float32)
    psi = jnp.zeros((H, W, 1), jnp.float32)
    C = jnp.zeros((q, H, W, 1), jnp.float32)
    return [
        ("kernels/dct2/dct2[B4,32x32x3]",
         jax.make_jaxpr(lambda a: dct2(a, impl="pallas"))(x)),
        ("kernels/dct2/bdm_ei_update[B4,q2,32x32x3]",
         jax.make_jaxpr(lambda a, e, p, c: bdm_ei_update(
             a, e, p, c, impl="pallas"))(u, eps, psi, C)),
    ]


def bdm_ei_update(u: Array, eps_hist: Array, psi: Array, C: Array,
                  impl: str = "auto") -> Array:
    impl = _resolve(impl)
    if impl == "pallas":
        return _kernel.bdm_ei_update(u, eps_hist, psi, C)
    if impl == "pallas_interpret":
        return _kernel.bdm_ei_update(u, eps_hist, psi, C, interpret=True)
    return _ref.bdm_ei_update_ref(u, eps_hist, psi, C)
