"""Dispatch layer for attention: Pallas TPU kernel / blocked-jnp / reference.

`impl` resolution:
  * "pallas"     — the Pallas flash kernel (TPU; `interpret=True` on CPU)
  * "blocked"    — jnp online-softmax over KV chunks via lax.scan.  Same
                   memory profile as flash (never materializes S x S), lowers
                   to plain XLA ops — this is what the multi-pod dry-run
                   compiles, so cost_analysis/memory_analysis reflect the
                   flash-style dataflow rather than a naive S^2 buffer.
  * "ref"        — the dense oracle (small shapes, tests)
  * "auto"       — TPU -> pallas; otherwise blocked for long sequences,
                   ref for short ones.
"""
from __future__ import annotations

from typing import Optional, Union

import jax
import jax.numpy as jnp

from ...distributed.sharding import per_batch_shard
from .ref import attention_ref
from .kernel import flash_attention

Array = jax.Array

_BLOCKED_THRESHOLD = 1024

# Dry-run override (repro.launch.dryrun --opt flash_stub): lower attention
# as `traffic_stub`, whose HLO HBM traffic equals the Pallas flash kernel's
# true dataflow (q,k,v read once; o written once; online-softmax stats live
# in VMEM).  The blocked-jnp lowering otherwise materializes per-chunk score
# tiles and scan carries into HBM, inflating the roofline memory term by
# ~10-20x (EXPERIMENTS.md §Perf iter A3).  NUMERICS ARE WRONG by design —
# the stub exists only to measure the kernel's memory/collective profile
# from the compiled artifact; real execution always uses pallas/blocked/ref.
FORCE_IMPL: str | None = None


def _platform() -> str:
    return jax.default_backend()


def blocked_attention(q: Array, k: Array, v: Array, *, causal: bool,
                      window: Optional[int], q_offset: Union[int, Array],
                      block_k: int = 512) -> Array:
    """Online-softmax attention, scanning KV in chunks (flash dataflow in jnp)."""
    B, Sq, Hq, Dh = q.shape
    _, Sk, Hkv, _ = k.shape
    rep = Hq // Hkv
    if Sk % block_k:
        pad = block_k - Sk % block_k
        k = jnp.pad(k, ((0, 0), (0, pad), (0, 0), (0, 0)))
        v = jnp.pad(v, ((0, 0), (0, pad), (0, 0), (0, 0)))
        # padded keys are masked because their positions exceed every q_pos
        Sk_p = Sk + pad
    else:
        Sk_p = Sk
    nk = Sk_p // block_k
    qg = q.reshape(B, Sq, Hkv, rep, Dh).astype(jnp.float32)
    kb = k.reshape(B, nk, block_k, Hkv, Dh).transpose(1, 0, 2, 3, 4)
    vb = v.reshape(B, nk, block_k, Hkv, Dh).transpose(1, 0, 2, 3, 4)
    q_pos = (jnp.asarray(q_offset) + jnp.arange(Sq)).astype(jnp.int32)
    scale = 1.0 / (Dh ** 0.5)

    def step(carry, blk):
        m, l, acc = carry
        kc, vc, j = blk
        s = jnp.einsum("bqhrd,bkhd->bhrqk", qg, kc.astype(jnp.float32)) * scale
        k_pos = j * block_k + jnp.arange(block_k)
        mask = jnp.ones((Sq, block_k), bool)
        mask = mask & (k_pos[None, :] < Sk)
        if causal:
            mask = mask & (k_pos[None, :] <= q_pos[:, None])
        if window is not None:
            mask = mask & (k_pos[None, :] > q_pos[:, None] - window)
        s = jnp.where(mask[None, None, None], s, -1e30)
        m_new = jnp.maximum(m, jnp.max(s, axis=-1))
        p = jnp.exp(s - m_new[..., None])
        alpha = jnp.exp(m - m_new)
        l_new = alpha * l + p.sum(-1)
        pv = jnp.einsum("bhrqk,bkhd->bhrqd", p, vc.astype(jnp.float32))
        acc_new = acc * alpha[..., None] + pv
        return (m_new, l_new, acc_new), None

    m0 = jnp.full((B, Hkv, rep, Sq), -1e30, jnp.float32)
    l0 = jnp.zeros((B, Hkv, rep, Sq), jnp.float32)
    a0 = jnp.zeros((B, Hkv, rep, Sq, Dh), jnp.float32)
    (m, l, acc), _ = jax.lax.scan(step, (m0, l0, a0),
                                  (kb, vb, jnp.arange(nk)))
    l = jnp.where(l == 0.0, 1.0, l)
    out = (acc / l[..., None]).transpose(0, 3, 1, 2, 4).reshape(B, Sq, Hq, Dh)
    return out.astype(q.dtype)


def traffic_stub(q: Array, k: Array, v: Array) -> Array:
    """Flash-kernel HBM-traffic stand-in: reads q/k/v once, writes o once
    (reductions over S fuse into a single pass); ~zero flops.  See
    FORCE_IMPL above — measurement artifact for the dry-run only."""
    B, Sq, Hq, Dh = q.shape
    _, Sk, Hkv, _ = k.shape
    rep = Hq // Hkv
    km = jnp.mean(k.astype(jnp.float32), axis=1)           # (B, Hkv, Dh)
    vm = jnp.max(v.astype(jnp.float32), axis=1)            # (B, Hkv, Dh)
    s = jnp.tanh(km + vm)                                  # (B, Hkv, Dh)
    s = jnp.repeat(s, rep, axis=1)[:, None]                # (B, 1, Hq, Dh)
    return (q.astype(jnp.float32) * s).astype(q.dtype)


def attention(q: Array, k: Array, v: Array, *, causal: bool = True,
              window: Optional[int] = None, q_offset: Union[int, Array] = 0,
              impl: str = "auto") -> Array:
    if FORCE_IMPL is not None:
        impl = FORCE_IMPL
    if impl == "traffic_stub":
        return traffic_stub(q, k, v)
    if impl == "auto":
        if _platform() == "tpu":
            impl = "pallas"
        elif k.shape[1] >= _BLOCKED_THRESHOLD:
            impl = "blocked"
        else:
            impl = "ref"
    if impl in ("pallas", "pallas_interpret"):
        launch = lambda q, k, v, off: flash_attention(
            q, k, v, q_offset=off, causal=causal, window=window,
            interpret=impl == "pallas_interpret")
        return per_batch_shard(launch, q, k, v,
                               jnp.asarray(q_offset, jnp.int32),
                               replicated=(3,), head_dim=2)
    if impl == "blocked":
        return blocked_attention(q, k, v, causal=causal, window=window,
                                 q_offset=q_offset)
    if impl == "ref":
        return attention_ref(q, k, v, causal=causal, window=window,
                             q_offset=q_offset)
    raise ValueError(impl)
