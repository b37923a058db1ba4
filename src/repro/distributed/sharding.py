"""Sharding rules: parameter/activation PartitionSpecs with divisibility fallback.

Mesh contract (launch/mesh.py):
    single-pod : (data=16, model=16)            axes ("data", "model")
    multi-pod  : (pod=2, data=16, model=16)     axes ("pod", "data", "model")

Policy (DESIGN.md §4):
  * batch / activations  -> sharded over BATCH_AXES = ("pod", "data")
  * params               -> TP over "model" on a rule-chosen dim (Megatron
                            column/row split, expert axis for MoE, vocab for
                            embeddings), then FSDP (ZeRO-3) over "data" on the
                            largest remaining dim.  Cross-pod stays pure DP
                            (params replicated over "pod"; gradients
                            all-reduce over it) so per-layer FSDP gathers
                            never cross the DCI.
  * every axis assignment is divisibility-checked; a dim that does not
    divide the axis size falls back to replication on that axis (e.g.
    gemma3-1b's 4-head wq cannot split 16 ways -> FFN-only TP).

Rules are *name-pattern based* over the params pytree paths, so any model in
the zoo (transformer / rwkv / mamba / enc-dec / DiT) shards without
per-model code.  Leading scan-stack axes (layer groups) are never sharded —
XLA then performs the FSDP all-gather on the per-iteration slice inside the
scanned layer body, which is what overlaps gather with the previous layer's
compute on real hardware.
"""
from __future__ import annotations

import dataclasses
import re
from typing import Any, Dict, Optional, Tuple

import jax
from jax._src import mesh as _mesh_lib
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

Array = jax.Array


@dataclasses.dataclass(frozen=True)
class ShardCfg:
    tp_axis: str = "model"
    fsdp_axes: Tuple[str, ...] = ("data",)
    batch_axes: Tuple[str, ...] = ("pod", "data")
    # sequence-parallel axis for long-context activations / KV caches
    seq_axis: str = "model"
    fsdp_params: bool = True
    tp_params: bool = True
    # head-aligned attention TP: splitting the flat (H*Dh) projection dim
    # when H % tp != 0 makes GSPMD partition the QK^T einsum on its
    # CONTRACTING dim and all-reduce every score tile (measured 119 TB on
    # deepseek prefill_32k — EXPERIMENTS.md §Perf iter A1).  Attention
    # projections therefore only TP-shard when the head count divides.
    n_heads: int = 0
    n_kv_heads: int = 0
    # context parallelism: shard the sequence dim of train/prefill
    # activations over the model axis (§Perf iter A2)
    seq_shard_activations: bool = False

    def present(self, mesh: Mesh, axes) -> Tuple[str, ...]:
        if isinstance(axes, str):
            axes = (axes,)
        return tuple(a for a in axes if a in mesh.axis_names)


def axis_size(mesh: Mesh, axes) -> int:
    if isinstance(axes, str):
        axes = (axes,)
    n = 1
    for a in axes:
        n *= mesh.shape[a]
    return n


# ---------------------------------------------------------------------------
# TP dim selection rules: (regex over joined path) -> dim index from the END
# of the shape (negative), or a callable(shape)->dim.  First match wins.
# ---------------------------------------------------------------------------
def _moe_expert_dim(shape):
    # (..., E, D, F) expert-stacked weights: TP over the expert axis
    return len(shape) - 3


_TP_RULES = [
    (re.compile(r"moe/(w_gate|w_up|w_down)$"), _moe_expert_dim),
    (re.compile(r"moe/router$"), lambda s: len(s) - 1),        # (D, E): split experts
    (re.compile(r"(^|/)embed$"), lambda s: len(s) - 2),        # (V, D): split vocab
    (re.compile(r"(^|/)unembed$"), lambda s: len(s) - 1),      # (D, V): split vocab
    # rwkv channel-mix: wk (D, F) col, wv (F, D) row — disambiguated by parent
    # (must precede the generic wk/wv rule)
    (re.compile(r"cmix/wk$"), lambda s: len(s) - 1),
    (re.compile(r"cmix/wv$"), lambda s: len(s) - 2),
    (re.compile(r"(wq|wk|wv|w_up|w_gate|in_proj|patch_in|wr|wg|ada_w)$"),
     lambda s: len(s) - 1),                                    # column parallel
    (re.compile(r"(wo|w_down|out_proj|patch_out)$"), lambda s: len(s) - 2),
]

_REPLICATE = re.compile(
    r"(ln|norm|bias|mu$|decay_base|dt_bias|A_log|(^|/)D$|(^|/)u$|ada_b|b_in|b_out|b1$|b2$|pos|conv_b)")


def _path_str(path) -> str:
    parts = []
    for p in path:
        if hasattr(p, "key"):
            parts.append(str(p.key))
        elif hasattr(p, "idx"):
            parts.append(str(p.idx))
        else:
            parts.append(str(p))
    return "/".join(parts)


def _n_stack_axes(path_s: str) -> int:
    """Leading scan-stack axes for stacked layer params (never sharded)."""
    return 1 if re.search(r"(layer_stacks|layers|enc_layers|dec_layers|blocks)/", path_s) else 0


def param_spec(path_s: str, shape: Tuple[int, ...], mesh: Mesh,
               cfg: ShardCfg) -> P:
    """PartitionSpec for one parameter tensor."""
    ndim = len(shape)
    spec: list = [None] * ndim
    if _REPLICATE.search(path_s) or ndim <= 1:
        return P(*spec)
    n_stack = _n_stack_axes(path_s)

    tp_dim: Optional[int] = None
    if cfg.tp_params and cfg.tp_axis in mesh.axis_names:
        tp_size = mesh.shape[cfg.tp_axis]
        for pat, dim_fn in _TP_RULES:
            if pat.search(path_s):
                d = dim_fn(shape)
                ok = d is not None and n_stack <= d < ndim and shape[d] % tp_size == 0
                # head-aligned gating for attention projections
                if ok and re.search(r"attn/(wq|wo)$|xattn/(wq|wo)$", path_s) \
                        and cfg.n_heads and cfg.n_heads % tp_size != 0:
                    ok = False
                if ok and re.search(r"attn/(wk|wv)$|xattn/(wk|wv)$", path_s) \
                        and cfg.n_kv_heads and cfg.n_kv_heads % tp_size != 0:
                    ok = False
                if ok:
                    spec[d] = cfg.tp_axis
                    tp_dim = d
                break

    if cfg.fsdp_params:
        fsdp = cfg.present(mesh, cfg.fsdp_axes)
        if fsdp:
            fs = axis_size(mesh, fsdp)
            # largest remaining dim divisible by the fsdp size
            cands = [(shape[d], d) for d in range(n_stack, ndim)
                     if d != tp_dim and shape[d] % fs == 0]
            if cands:
                _, d = max(cands)
                spec[d] = fsdp if len(fsdp) > 1 else fsdp[0]
    return P(*spec)


def param_shardings(params: Any, mesh: Mesh, cfg: ShardCfg = ShardCfg()) -> Any:
    """Pytree of NamedShardings matching `params` (works on ShapeDtypeStructs)."""
    def one(path, leaf):
        spec = param_spec(_path_str(path), tuple(leaf.shape), mesh, cfg)
        return NamedSharding(mesh, spec)

    return jax.tree_util.tree_map_with_path(one, params)


# ---------------------------------------------------------------------------
# Activation / batch / cache specs
# ---------------------------------------------------------------------------
def batch_axes_entry(mesh: Mesh, cfg: ShardCfg, batch_size: int):
    """The PartitionSpec entry for a batch dim of `batch_size`: the largest
    prefix of the batch axes (pods first) whose product divides it."""
    axes = [a for a in cfg.batch_axes if a in mesh.axis_names]
    use, prod = [], 1
    for a in axes:
        if batch_size % (prod * mesh.shape[a]) == 0:
            use.append(a)
            prod *= mesh.shape[a]
    return tuple(use) if len(use) > 1 else (use[0] if use else None)


def batch_spec(mesh: Mesh, cfg: ShardCfg, ndim: int, batch_size: int,
               extra: Optional[Dict[int, Any]] = None) -> P:
    """Batch-leading activation spec; batch sharded over the batch axes that
    divide it (pods first), remaining dims per `extra` {dim: axis}."""
    spec: list = [None] * ndim
    spec[0] = batch_axes_entry(mesh, cfg, batch_size)
    for d, ax in (extra or {}).items():
        if ax in mesh.axis_names:
            spec[d] = ax
    return P(*spec)


def kv_cache_spec(mesh: Mesh, cfg: ShardCfg, cache_shape: Tuple[int, ...],
                  batch_size: int, n_kv_heads: int,
                  seq_fallback: bool = False) -> P:
    """KV cache (.., B, S, Hkv, Dh), possibly with a leading layer-stack axis.

    Heads shard over `model` when divisible; otherwise the cache replicates
    over `model` (batch sharding still applies).  Sequence-sharding the
    cache (`seq_fallback=True`) is NOT the baseline: the per-token
    dynamic-update-slice at a dynamic index forces GSPMD into involuntary
    full rematerialization (measured: ~50x collective blow-up on the
    decode_32k cells) — the production SP-cache path needs the shard_map
    flash-decode with partial-softmax merge and is tracked as a §Perf
    optimization, not a default.
    """
    ndim = len(cache_shape)
    lead = ndim - 4
    spec: list = [None] * ndim
    spec[lead] = batch_axes_entry(mesh, cfg, batch_size)
    tp = cfg.tp_axis
    if tp in mesh.axis_names:
        if n_kv_heads % mesh.shape[tp] == 0:
            spec[lead + 2] = tp
        elif seq_fallback and cache_shape[lead + 1] % mesh.shape[tp] == 0:
            spec[lead + 1] = cfg.seq_axis        # SP over cache length
    return P(*spec)


# ---------------------------------------------------------------------------
# Serving-engine state: the mesh rules for `repro.serve` (EngineState pytrees
# + engine caches).  Every EngineState leaf is slot-batch-leading, so one
# rule shards the whole engine over the data axes; cache leaves carry their
# batch/slot dim wherever the arch family put it (probed by
# `Arch.cache_batch_axes`), with KV-shaped leaves additionally head-sharded
# via `kv_cache_spec`.
# ---------------------------------------------------------------------------
def serve_state_spec(mesh: Mesh, cfg: ShardCfg, ndim: int,
                     batch_size: int) -> P:
    """Spec for one slot-batch-leading EngineState leaf: dim 0 over the
    batch axes that divide the slot count, everything else replicated."""
    return batch_spec(mesh, cfg, ndim, batch_size)


def serve_state_shardings(state: Any, mesh: Mesh,
                          cfg: ShardCfg = ShardCfg()) -> Any:
    """NamedShardings for an EngineState pytree (all leaves batch-leading)."""
    return jax.tree.map(
        lambda l: NamedSharding(
            mesh, serve_state_spec(mesh, cfg, l.ndim, l.shape[0])), state)


def cache_leaf_spec(mesh: Mesh, cfg: ShardCfg, shape: Tuple[int, ...],
                    batch_axis: Optional[int], batch_size: int,
                    n_kv_heads: int = 0, d_head: int = -1) -> P:
    """Spec for one engine-cache leaf.  KV-shaped leaves ((.., B, S, Hkv, Dh))
    go through `kv_cache_spec` (batch + head sharding); every other state
    leaf (ssm/conv/recurrent aux) shards its probed batch axis only."""
    if len(shape) >= 4 and n_kv_heads and shape[-2] == n_kv_heads \
            and shape[-1] == d_head:
        return kv_cache_spec(mesh, cfg, shape, batch_size, n_kv_heads)
    spec: list = [None] * len(shape)
    if batch_axis is not None:
        spec[batch_axis] = batch_axes_entry(mesh, cfg, batch_size)
    return P(*spec)


def cache_shardings(cache_like: Any, batch_axes: Any, mesh: Mesh,
                    cfg: ShardCfg, batch_size: int, n_kv_heads: int = 0,
                    d_head: int = -1) -> Any:
    """NamedShardings for an engine cache pytree; `batch_axes` is the
    same-structure pytree of batch-axis indices from
    `Arch.cache_batch_axes`."""
    def one(leaf, ax):
        return NamedSharding(mesh, cache_leaf_spec(
            mesh, cfg, tuple(leaf.shape), int(ax), batch_size,
            n_kv_heads, d_head))
    return jax.tree.map(one, cache_like, batch_axes)


def bank_shardings(mesh: Mesh, cfg: ShardCfg, bank: Any,
                   shard_diag: bool = False) -> Any:
    """Mesh placement for a serve coefficient bank (`FactoredBank`).

    Every block-factor / index / time / flag leaf is tiny (O(K^2) or O(1)
    per row) and replicates.  The (P, D) diagonal pool — the only
    D-scaled leaf left after the factored refactor — replicates by
    default too; `shard_diag=True` shards its D axis over the tp axis
    when divisible (pool-row gathers are along P, so each shard keeps its
    D-slice local), which only pays once D is large enough for pool
    residency to matter and costs re-gathering the rows against the
    replicated slot state.
    """
    named = {}
    for f in bank._fields:
        spec = P()
        if f == "diag" and shard_diag and cfg.tp_axis in mesh.axis_names \
                and getattr(bank, f).shape[-1] % mesh.shape[cfg.tp_axis] == 0:
            spec = P(None, cfg.tp_axis)
        named[f] = NamedSharding(mesh, spec)
    return type(bank)(**named)


# ---------------------------------------------------------------------------
# in-model activation constraints (Megatron-style SP residual stream)
# ---------------------------------------------------------------------------
# GSPMD left to itself re-replicates the sequence dim inside transformer
# blocks and contraction-partitions the FFN matmuls (measured: 20 GB/layer
# f32 all-reduce on deepseek prefill — EXPERIMENTS.md §Perf iter A4).
# Model code calls `constrain_acts` on the (B, S, D) residual stream at
# block boundaries; the launcher installs a spec via `set_activation_spec`
# (None = no-op, the default for tests/small runs).
_ACT_SPEC: Optional[P] = None


def set_activation_spec(spec: Optional[P]) -> None:
    global _ACT_SPEC
    _ACT_SPEC = spec


def constrain_acts(x: Array) -> Array:
    if _ACT_SPEC is None or x.ndim != len(_ACT_SPEC):
        return x
    return jax.lax.with_sharding_constraint(x, _ACT_SPEC)


def _ambient_mesh():
    """The mesh installed by `with mesh:` (None outside a mesh context).

    `with mesh:` records the mesh in `jax._src.mesh.thread_resources`, the
    one place the installed JAX keeps it (the public `jax.sharding.get_mesh`
    sees only `jax.set_mesh`).  A JAX that moves it fails here loudly
    rather than turning constrain_batch into a silent no-op."""
    m = _mesh_lib.thread_resources.env.physical_mesh
    return None if m.empty else m


def per_batch_shard(fn, *args, replicated: Tuple[int, ...] = (),
                    head_dim: Optional[int] = None):
    """`fn(*args)`, run once per batch shard of the ambient mesh.

    A Pallas kernel is an opaque custom call to XLA's SPMD partitioner:
    handed batch-sharded operands it gathers them and runs the whole
    batch on every device.  When a `with mesh:` context splits the
    batch (dim 0 of every arg not listed in `replicated`), this maps `fn`
    over the shards with `jax.shard_map` instead, so each device runs the
    kernel on its own rows; `fn` must be row-independent and return
    batch-leading arrays.  A batch that does not split (a single admitted
    or retiring row) runs whole on every device: the TPU compiler refuses
    a kernel outside `shard_map` in a mesh program either way.  Outside a
    mesh it is `fn(*args)`.

    `head_dim` names a dim that `fn` also treats independently (attention
    heads, in the sharded args and the output alike).  It is split over
    the tensor-parallel axis when the mesh has one of size > 1 and it
    divides every such dim, the layout the head-aligned TP projections
    produce; otherwise each tensor-parallel device runs every head, as
    the replicated projections of a non-dividing head count do."""
    mesh = _ambient_mesh()
    if mesh is None:
        return fn(*args)
    cfg = ShardCfg()
    sharded = [a for i, a in enumerate(args) if i not in replicated]
    entries = [batch_axes_entry(mesh, cfg, sharded[0].shape[0])]
    if head_dim is not None:
        tp = mesh.shape.get(cfg.tp_axis, 1)
        split = tp > 1 and all(a.shape[head_dim] % tp == 0 for a in sharded)
        entries += [None] * (head_dim - 1) \
            + [cfg.tp_axis if split else None]
    spec = P(*entries)
    in_specs = tuple(P() if i in replicated else spec
                     for i in range(len(args)))
    return jax.shard_map(fn, mesh=mesh, in_specs=in_specs,
                         out_specs=spec, check_vma=False)(*args)


def constrain_batch(x: Array) -> Array:
    """Pin a batch-leading activation to the data-parallel layout (dim 0
    over the batch axes, everything else replicated).

    Model code calls this right after the embedding lookup.  Left to
    itself, GSPMD propagates the vocab-sharded embedding table's gather
    sharding into the layer scan, and the CPU SPMD partitioner miscompiles
    that composition — the sharded forward diverged from the single-device
    result by O(1) logits error while each block in isolation agreed to
    1e-6 (caught by tests/test_distributed.py::test_fsdp_train_step_*).
    An explicit constraint at the lookup restores agreement up to
    reduction order.  Honors an installed activation spec first; derives
    the spec from the ambient mesh otherwise; no-op outside a mesh
    context (single-device tests, the serving engine on CPU)."""
    if _ACT_SPEC is not None and x.ndim == len(_ACT_SPEC):
        return jax.lax.with_sharding_constraint(x, _ACT_SPEC)
    mesh = _ambient_mesh()
    if mesh is None:
        return x
    spec = batch_spec(mesh, ShardCfg(), x.ndim, x.shape[0])
    if all(a is None for a in spec):
        return x
    return jax.lax.with_sharding_constraint(x, spec)


def logical_to_sharding(mesh: Mesh, spec: P) -> NamedSharding:
    return NamedSharding(mesh, spec)


def replicated(mesh: Mesh) -> NamedSharding:
    return NamedSharding(mesh, P())
