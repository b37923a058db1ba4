"""Layer 2: static checks over Pallas kernel launches.

Works on the `pallas_call` equations found in a traced jaxpr (tracing —
not lowering — so it runs on any backend, including the CPU CI runner):

  PL201  BlockSpec divisibility: every blocked dimension must divide the
         array dimension it tiles.  A ragged tile means the kernel reads
         or writes out-of-bounds lanes on the last grid step (masked on
         TPU, garbage in interpret mode — either way not the contract the
         kernels document).
  PL202  index-map bounds: evaluating each BlockSpec's index map at every
         corner of the grid must keep `block_index * block_shape` inside
         the array for every dimension.
  PL203  memory budget: the per-grid-step working set — all VMEM blocks
         double-buffered, plus scratch — must fit the per-core VMEM
         budget, and SMEM operands the SMEM budget (conservative TPU
         figures; see /opt/skills/guides/pallas_guide.md).

Entry points are discovered via each kernel package's
`staticcheck_entries()` (ops.py), which returns named example traces at
representative serve shapes.
"""
from __future__ import annotations

import itertools
from typing import List

from .findings import Finding
from .jaxprcheck import iter_eqns

VMEM_BUDGET = 16 * 2 ** 20        # ~16 MiB/core (v4/v5 class)
SMEM_BUDGET = 1 * 2 ** 20         # conservative scalar-memory ceiling


def find_pallas_eqns(jaxpr) -> List:
    return [e for e in iter_eqns(jaxpr) if e.primitive.name == "pallas_call"]


def _dtype_bytes(aval) -> int:
    import numpy as np
    return int(np.dtype(aval.dtype).itemsize)


def _block_dim(d):
    """One block dim as an int: the installed Pallas wraps each dim of a
    BlockSpec in a `Blocked(block_size=...)` record."""
    return getattr(d, "block_size", d)


def _eval_index_map(bm, idx) -> List[int]:
    import jax.core as jcore
    imj = bm.index_map_jaxpr
    out = jcore.eval_jaxpr(imj.jaxpr, imj.consts, *idx)
    return [int(v) for v in out]


def _grid_corners(grid):
    axes = [sorted({0, max(int(g) - 1, 0)}) for g in grid]
    return itertools.product(*axes)


def check_pallas_eqn(eqn, label: str) -> List[Finding]:
    out: List[Finding] = []
    gm = eqn.params["grid_mapping"]
    grid = tuple(int(g) for g in gm.grid)
    shapes = [tuple(s.shape) for s in gm.in_shapes] + \
             [tuple(s.shape) for s in gm.out_shapes]
    bms = list(gm.block_mappings)
    if len(bms) != len(shapes):          # index operands offset the zip
        shapes = shapes[len(bms) - len(shapes):] if len(shapes) > len(bms) \
            else shapes

    vmem_bytes = 0
    smem_bytes = 0
    for op, (bm, ashape) in enumerate(zip(bms, shapes)):
        bshape = tuple(_block_dim(d) for d in bm.block_shape)
        is_smem = "smem" in str(bm.block_aval).lower()
        nbytes = _dtype_bytes(bm.block_aval.inner_aval
                              if hasattr(bm.block_aval, "inner_aval")
                              else bm.block_aval)
        for d in bshape:
            nbytes *= int(d) if isinstance(d, int) else 1
        if is_smem:
            smem_bytes += nbytes
        else:
            vmem_bytes += 2 * nbytes          # double-buffered pipeline

        if is_smem or len(bshape) != len(ashape):
            continue                           # unblocked operand
        # PL201: divisibility
        for dim, (bd, ad) in enumerate(zip(bshape, ashape)):
            if isinstance(bd, int) and bd > 0 and ad % bd:
                out.append(Finding(
                    "PL201", "", 0,
                    f"[{label}] operand {op}: block shape {bshape} does "
                    f"not divide array shape {ashape} at dim {dim} "
                    f"({ad} % {bd} != 0) — the last grid step tiles out "
                    "of bounds"))
        # PL202: index-map bounds at the grid corners
        for idx in _grid_corners(grid):
            try:
                bidx = _eval_index_map(bm, idx)
            except Exception as e:           # index map not evaluable
                out.append(Finding(
                    "PL202", "", 0,
                    f"[{label}] operand {op}: index map failed to "
                    f"evaluate at grid index {idx}: {e}"))
                break
            for dim, (bi, bd, ad) in enumerate(zip(bidx, bshape, ashape)):
                if not isinstance(bd, int):
                    continue
                start = bi * bd
                if start < 0 or start + bd > ad:
                    out.append(Finding(
                        "PL202", "", 0,
                        f"[{label}] operand {op}: index map at grid "
                        f"{idx} selects block {bidx} -> elements "
                        f"[{start}, {start + bd}) outside dim {dim} of "
                        f"{ashape}"))
                    break

    # scratch operands live in VMEM for the whole call (not double-buffered)
    body = eqn.params.get("jaxpr")
    if body is not None and getattr(gm, "num_scratch_operands", 0):
        inner = getattr(body, "jaxpr", body)
        for var in inner.invars[-gm.num_scratch_operands:]:
            aval = getattr(var.aval, "inner_aval", var.aval)
            n = _dtype_bytes(aval)
            for d in getattr(aval, "shape", ()):
                n *= int(d)
            vmem_bytes += n

    if vmem_bytes > VMEM_BUDGET:
        out.append(Finding(
            "PL203", "", 0,
            f"[{label}] per-step VMEM working set ~{vmem_bytes} B "
            f"(double-buffered blocks + scratch) exceeds the "
            f"{VMEM_BUDGET} B budget"))
    if smem_bytes > SMEM_BUDGET:
        out.append(Finding(
            "PL203", "", 0,
            f"[{label}] SMEM operands ~{smem_bytes} B exceed the "
            f"{SMEM_BUDGET} B budget"))
    return out


def check_if_present(jaxpr, label: str) -> List[Finding]:
    """Pallas checks over any pallas_call the trace happens to contain
    (serve variants on the CPU ref path legitimately contain none)."""
    out: List[Finding] = []
    for eqn in find_pallas_eqns(jaxpr):
        out += check_pallas_eqn(eqn, label)
    return out


def check_traced(jaxpr, label: str) -> List[Finding]:
    """All Pallas checks over every pallas_call in a traced program."""
    out: List[Finding] = []
    eqns = find_pallas_eqns(jaxpr)
    if not eqns:
        out.append(Finding(
            "PL200", "", 0,
            f"[{label}] expected a pallas_call in this entry's trace but "
            "found none — the staticcheck entry no longer exercises the "
            "kernel"))
    for eqn in eqns:
        out += check_pallas_eqn(eqn, label)
    return out
