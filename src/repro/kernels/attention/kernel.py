"""Pallas TPU flash attention (forward) with GQA, causal & sliding-window.

One jitted entry point, `flash_attention`, with two tilings.  Which one a
call takes is a static choice made at trace time by `attention_plan`, from
the call's shapes, `causal`, `window` and a VMEM budget; both lower to one
Pallas call, so a profile shows one `flash_attention` op per call.

Whole-sequence path: no mask (`causal` false, `window` None) and the whole
key sequence fits VMEM beside `bt` batch rows of q, k, v and o.  It reads
q, k, v and writes o in the layout the projections produce, (B, S, H*Dh)
(a free reshape of (B, S, H, Dh)), so no transpose runs around it and the
lane dimension is H*Dh wide, not Dh padded to 128.  The grid is (B / bt,):
each step loops over its rows and over 128-lane windows of q, k and v,
each holding 128 // Dh heads; a head's scores contract its own q lanes
(the window's others zeroed) with k's window, so every operand is read
at a lane offset the vector unit needs no shift for.  GQA calls, and
heads of 128 lanes or more, take one head per window (q-head h reads kv
lanes (h // rep) * Dh).  Each head runs a plain softmax over the whole key
sequence.  Without a mask `q_offset` plays no part.  A DiT eval (256
tokens, 12 heads of 64) at B = 64 is 16 grid steps of 4 rows, 6 windows
of 2 heads each, against 3072 steps of one 128x128 tile in the tiled
path.  whisper-base's decoder cross-attention (one query at decode, or a
prompt's, against 1500 encoder frames) takes this path too: the tiled
one needs Sk to be a multiple of its k block.

Tiled path: everything else (causal or windowed attention, chunked calls
with `q_offset`, sequences too long for one batch row to fit VMEM).  TPU
adaptation of FlashAttention (Dao et al.): instead of GPU SM/warp
scheduling, we exploit the TPU Pallas guarantee that grid iterations
execute *sequentially* with the last grid axis fastest.  The grid is

    (batch, q_heads, num_q_blocks, num_k_blocks)

and the online-softmax running statistics (m, l) plus the f32 accumulator
live in VMEM scratch that persists across the k-block axis; the output tile
is written once, on the final k block.  GQA is handled with a BlockSpec
index_map that maps q-head h to kv-head h // (Hq // Hkv) — the repeated KV
is never materialized in HBM.  Blocks default to (128, 128), the MXU's
size; the heads are transposed to a grid axis around the kernel.

Causal + sliding-window masking is computed from absolute positions, so the
same kernel serves prefill (q_offset=0) and chunked/decode attention
(q_offset = cache length).  K-blocks that are entirely outside the causal /
window band are skipped via pl.when (no MXU work, no VMEM traffic beyond the
prefetch), which makes causal attention ~2x and sliding-window O(S*W).

Both paths take q and k at the input's precision with float32
accumulation, and compute the softmax and p in float32; p v is a float32
dot.  At default precision the TPU's MXU rounds a float32 dot's operands
to bf16, in both paths alike.
"""
from __future__ import annotations

import functools
from typing import Optional, Tuple

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

Array = jax.Array

NEG_INF = -1e30
# VMEM a whole-sequence step may fill with its blocks and working tiles:
# the default scoped VMEM limit of a TPU v5e kernel.  The v5e compile
# refuses a kernel only past the core's 128 MiB, so an estimate below that
# errs low by 8x before a call fails to compile
VMEM_BUDGET = 16 * 2**20
LANES = 128


def attention_plan(B: int, Sq: int, Sk: int, Hq: int, Hkv: int, Dh: int,
                   itemsize: int, *, causal: bool, window: Optional[int],
                   vmem_budget: int = VMEM_BUDGET) -> Tuple[str, int]:
    """("whole", bt) for the whole-sequence path at `bt` batch rows per
    grid step, or ("tiled", 0).

    `itemsize` is that of the widest of q, k and v (whisper's cross-
    attention reads bf16 q against float32 k and v).  A step holds q, k,
    v and o for its rows, each double-buffered, and one head's working
    tiles at a time: eight float32 (Sq, Sk) tiles (the
    scores, their exponentials and the compiler's copies; the v5e compile
    of the DiT's call takes 2.2 MB of scratch) and two float32 (Sq,
    max(Dh, 128)) ones (its p v and the window's output).
    `bt` is the largest divisor of B whose rows the budget holds, so no
    step is padded.  Where not even one row fits, the call takes the
    tiled path."""
    if causal or window is not None:
        return "tiled", 0
    row = 2 * (2 * Sq * Hq + 2 * Sk * Hkv) * Dh * itemsize
    head = (8 * Sq * Sk + 2 * Sq * max(Dh, LANES)) * 4
    fit = min(B, (vmem_budget - head) // row)
    if fit < 1:
        return "tiled", 0
    return "whole", max(d for d in range(1, fit + 1) if B % d == 0)


def _flash_kernel(q_off_ref, q_ref, k_ref, v_ref, o_ref,
                  m_scr, l_scr, acc_scr, *,
                  causal: bool, window: Optional[int],
                  block_q: int, block_k: int, sm_scale: float,
                  num_k_blocks: int):
    qb = pl.program_id(2)
    kb = pl.program_id(3)

    q_off = q_off_ref[0]
    q_pos = q_off + qb * block_q + jax.lax.broadcasted_iota(
        jnp.int32, (block_q, block_k), 0)
    k_pos = kb * block_k + jax.lax.broadcasted_iota(
        jnp.int32, (block_q, block_k), 1)

    @pl.when(kb == 0)
    def _init():
        m_scr[...] = jnp.full_like(m_scr, NEG_INF)
        l_scr[...] = jnp.zeros_like(l_scr)
        acc_scr[...] = jnp.zeros_like(acc_scr)

    # block-level skip: is any (q, k) pair in this tile visible?
    lo_q = q_off + qb * block_q            # first q position in tile
    hi_q = lo_q + block_q - 1              # last q position
    lo_k = kb * block_k
    hi_k = lo_k + block_k - 1
    visible = jnp.bool_(True)
    if causal:
        visible = visible & (lo_k <= hi_q)
    if window is not None:
        visible = visible & (hi_k > lo_q - window)

    @pl.when(visible)
    def _compute():
        q = q_ref[0, 0].astype(jnp.float32)                 # (bq, Dh)
        k = k_ref[0, 0].astype(jnp.float32)                 # (bk, Dh)
        s = jax.lax.dot_general(q, k, (((1,), (1,)), ((), ())),
                                preferred_element_type=jnp.float32)
        s = s * sm_scale                                  # (bq, bk)
        mask = jnp.ones_like(s, dtype=jnp.bool_)
        if causal:
            mask = mask & (k_pos <= q_pos)
        if window is not None:
            mask = mask & (k_pos > q_pos - window)
        s = jnp.where(mask, s, NEG_INF)

        m_prev = m_scr[...]                               # (bq, 1)
        l_prev = l_scr[...]
        m_cur = jnp.max(s, axis=-1, keepdims=True)
        m_new = jnp.maximum(m_prev, m_cur)
        p = jnp.exp(s - m_new)                            # (bq, bk)
        alpha = jnp.exp(m_prev - m_new)                   # (bq, 1)
        l_new = alpha * l_prev + jnp.sum(p, axis=-1, keepdims=True)
        v = v_ref[0, 0].astype(jnp.float32)                  # (bk, Dh)
        pv = jax.lax.dot_general(p, v, (((1,), (0,)), ((), ())),
                                 preferred_element_type=jnp.float32)
        acc_scr[...] = acc_scr[...] * alpha + pv
        m_scr[...] = m_new
        l_scr[...] = l_new

    @pl.when(kb == num_k_blocks - 1)
    def _finalize():
        l = l_scr[...]
        safe_l = jnp.where(l == 0.0, 1.0, l)              # fully-masked rows -> 0
        o_ref[0, 0] = (acc_scr[...] / safe_l).astype(o_ref.dtype)


def _head_group(Hq: int, Hkv: int, Dh: int) -> int:
    """Heads per 128-lane window of q, k and v: 128 // Dh where heads are
    narrower than the lanes and each q-head has its own kv-head, else 1
    (a window is then one head's Dh lanes)."""
    g = LANES // Dh
    if Hq == Hkv and Dh < LANES and LANES % Dh == 0 and Hq % g == 0:
        return g
    return 1


def _whole_kernel(q_ref, k_ref, v_ref, o_ref, *, heads: int, rep: int,
                  group: int, d_head: int, sm_scale: float):
    width = group * d_head
    nt = (((1,), (1,)), ((), ()))
    nn = (((1,), (0,)), ((), ()))

    def row(b, carry):
        for j in range(heads // group):
            win = pl.ds(j * width, width)
            kvw = pl.ds((j * group // rep) * d_head, width)
            q = q_ref[b, :, win]                          # (Sq, width)
            k = k_ref[b, :, kvw]                          # (Sk, width)
            v = v_ref[b, :, kvw].astype(jnp.float32)
            # head i of the window: its q lanes alone (the others zeroed)
            # contract with k's window; its o lanes are kept from p v
            lane_head = jax.lax.broadcasted_iota(jnp.int32, q.shape, 1) \
                // d_head
            out = None
            for i in range(group):
                mine = lane_head == i
                qi = q if group == 1 else jnp.where(mine, q, jnp.zeros_like(q))
                s = jax.lax.dot_general(qi, k, nt,
                                        preferred_element_type=jnp.float32)
                s = s * sm_scale                          # (Sq, Sk)
                p = jnp.exp(s - jnp.max(s, axis=-1, keepdims=True))
                l = jnp.sum(p, axis=-1, keepdims=True)
                o = jax.lax.dot_general(p, v, nn,
                                        preferred_element_type=jnp.float32) / l
                out = o if out is None else jnp.where(mine, o, out)
            o_ref[b, :, win] = out.astype(o_ref.dtype)
        return carry

    jax.lax.fori_loop(0, o_ref.shape[0], row, 0)


def _whole_sequence(q: Array, k: Array, v: Array, *, bt: int,
                    interpret: bool) -> Array:
    B, Sq, Hq, Dh = q.shape
    _, Sk, Hkv, _ = k.shape
    kernel = functools.partial(_whole_kernel, heads=Hq, rep=Hq // Hkv,
                               group=_head_group(Hq, Hkv, Dh), d_head=Dh,
                               sm_scale=1.0 / (Dh ** 0.5))
    q_spec = pl.BlockSpec((bt, Sq, Hq * Dh), lambda i: (i, 0, 0))
    kv_spec = pl.BlockSpec((bt, Sk, Hkv * Dh), lambda i: (i, 0, 0))
    out = pl.pallas_call(
        kernel,
        grid=(B // bt,),
        in_specs=[q_spec, kv_spec, kv_spec],
        out_specs=q_spec,
        out_shape=jax.ShapeDtypeStruct((B, Sq, Hq * Dh), q.dtype),
        interpret=interpret,
    )(q.reshape(B, Sq, Hq * Dh), k.reshape(B, Sk, Hkv * Dh),
      v.reshape(B, Sk, Hkv * Dh))
    return out.reshape(B, Sq, Hq, Dh)


def flash_attention_tiled(q: Array, k: Array, v: Array, *,
                          q_offset: Array | int = 0,
                          causal: bool = True,
                          window: Optional[int] = None,
                          block_q: int = 128,
                          block_k: int = 128,
                          interpret: bool = False) -> Array:
    """The tiled path alone (no jit of its own, so that its Pallas call
    keeps the name of the jitted function it is traced in)."""
    B, Sq, Hq, Dh = q.shape
    _, Sk, Hkv, _ = k.shape
    rep = Hq // Hkv

    block_q = min(block_q, Sq)
    block_k = min(block_k, Sk)
    assert Sq % block_q == 0 and Sk % block_k == 0, (Sq, block_q, Sk, block_k)
    nq, nk = Sq // block_q, Sk // block_k

    # layouts: (B, H, S, Dh) so the head axis is a pure grid axis
    qt = q.transpose(0, 2, 1, 3)
    kt = k.transpose(0, 2, 1, 3)
    vt = v.transpose(0, 2, 1, 3)
    q_off = jnp.asarray(q_offset, jnp.int32).reshape(1)

    grid = (B, Hq, nq, nk)
    kernel = functools.partial(
        _flash_kernel, causal=causal, window=window, block_q=block_q,
        block_k=block_k, sm_scale=1.0 / (Dh ** 0.5), num_k_blocks=nk)

    out = pl.pallas_call(
        kernel,
        grid=grid,
        in_specs=[
            pl.BlockSpec(memory_space=pltpu.SMEM),  # q_offset scalar
            pl.BlockSpec((1, 1, block_q, Dh), lambda b, h, i, j: (b, h, i, 0)),
            pl.BlockSpec((1, 1, block_k, Dh), lambda b, h, i, j: (b, h // rep, j, 0)),
            pl.BlockSpec((1, 1, block_k, Dh), lambda b, h, i, j: (b, h // rep, j, 0)),
        ],
        out_specs=pl.BlockSpec((1, 1, block_q, Dh), lambda b, h, i, j: (b, h, i, 0)),
        out_shape=jax.ShapeDtypeStruct((B, Hq, Sq, Dh), q.dtype),
        scratch_shapes=[
            pltpu.VMEM((block_q, 1), jnp.float32),
            pltpu.VMEM((block_q, 1), jnp.float32),
            pltpu.VMEM((block_q, Dh), jnp.float32),
        ],
        interpret=interpret,
    )(q_off, qt, kt, vt)
    return out.transpose(0, 2, 1, 3)


@functools.partial(
    jax.jit,
    static_argnames=("causal", "window", "block_q", "block_k", "interpret"))
def flash_attention(q: Array, k: Array, v: Array, *,
                    q_offset: Array | int = 0,
                    causal: bool = True,
                    window: Optional[int] = None,
                    block_q: int = 128,
                    block_k: int = 128,
                    interpret: bool = False) -> Array:
    """q: (B, Sq, Hq, Dh); k/v: (B, Sk, Hkv, Dh) -> (B, Sq, Hq, Dh).
    `block_q`/`block_k` tile the tiled path; the whole-sequence path has
    no q or k blocks."""
    B, Sq, Hq, Dh = q.shape
    _, Sk, Hkv, _ = k.shape
    assert Hq % Hkv == 0
    itemsize = max(x.dtype.itemsize for x in (q, k, v))
    path, bt = attention_plan(B, Sq, Sk, Hq, Hkv, Dh, itemsize,
                              causal=causal, window=window)
    if path == "whole":
        return _whole_sequence(q, k, v, bt=bt, interpret=interpret)
    return flash_attention_tiled(q, k, v, q_offset=q_offset, causal=causal,
                                 window=window, block_q=block_q,
                                 block_k=block_k, interpret=interpret)
