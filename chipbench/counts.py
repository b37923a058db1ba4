"""Analytic operation and byte counts of the work a cell needs, from its
shapes alone.  These are the yardstick's own copies: the program's
`fused_round_cost` may change, these may not.

Conventions: a multiply-add is 2 FLOPs; only matmul FLOPs count for the
score net (norms, softmax and activations are a fraction of a percent at
these widths); bytes are HBM traffic with every stream read or written
exactly once, whatever the kernel's tiling re-reads.
"""
from __future__ import annotations

from typing import Mapping


def dit_shapes(dit: Mapping) -> dict:
    d = dit["hidden_size"]
    side = dit["input_size"] // dit["patch_size"]
    return {"d": d, "tokens": side * side, "heads": dit["num_heads"],
            "d_head": d // dit["num_heads"], "depth": dit["depth"],
            "ff": int(dit["mlp_ratio"] * d),
            "patch_dim": dit["patch_size"] ** 2 * dit["in_channels"],
            "t_dim": dit["frequency_embedding_size"]}


def state_rows(dit: Mapping) -> int:
    """Rows of a family's packed state: the net's input channels over the
    data's (2 for CLD's (x, v), 1 for pixels)."""
    return dit["in_channels"] // dit["data_shape"][-1]


def dit_eval_flops(dit: Mapping) -> dict:
    """FLOPs of one score-net evaluation of ONE sample, by part."""
    s = dit_shapes(dit)
    d, S, L = s["d"], s["tokens"], s["depth"]
    proj = 2 * S * (4 * d * d) * L                 # q, k, v, o
    mlp = 2 * S * (2 * d * s["ff"]) * L            # up, down
    attn = 4 * S * S * d * L                       # QK^T and PV
    ada = 2 * d * 6 * d * L + 2 * d * 2 * d        # per-layer + final adaLN
    embed = 2 * S * s["patch_dim"] * d * 2 \
        + 2 * (s["t_dim"] * d + d * d)             # patch in/out, t-MLP
    total = proj + mlp + attn + ada + embed
    return {"proj_mlp": proj + mlp, "attention": attn, "adaln": ada,
            "embed": embed, "total": total}


def flash_attention_cost(batch: int, seq: int, heads: int, d_head: int,
                         itemsize: int = 2) -> dict:
    """One non-causal flash-attention call: q, k, v read once, o written
    once; FLOPs of QK^T and PV."""
    elems = batch * seq * heads * d_head
    return {"flops": 4 * batch * heads * seq * seq * d_head,
            "bytes": 4 * elems * itemsize}


def round_fused_cost(*, batch: int, K: int, Qb: int, kf: int, D: int,
                     pool_rows: int, with_corrector: bool = False,
                     gen_noise: bool = True, itemsize: int = 4,
                     n_ints: int = 5) -> dict:
    """One fused round commit: state u and eps history read and written
    once, this round's eps read once (and the corrector's, and streamed
    noise where the family cannot draw it in-kernel), each diagonal-pool
    row read once, and the per-slot coefficient tables.  FLOPs are the
    factor applies on the vector unit: per element of D, 2 kf^2 for a
    kf x kf block, kf for its diagonal and kf for adding the term."""
    state = batch * K * D
    hist = batch * Qb * K * D
    eps = batch * kf * D
    streams = 2 * state + 2 * hist + eps + pool_rows * D
    if with_corrector:
        streams += eps
    if not gen_noise:
        streams += eps
    n_coef = 3 + Qb + (Qb if with_corrector else 0)
    tables = batch * (n_ints + 2 + n_coef * (kf * kf + 1))
    applies = (1 + Qb) + 2 + (Qb if with_corrector else 0)
    flops = batch * D * (2 * kf * kf + 2 * kf) * applies
    return {"flops": int(flops), "bytes": int(itemsize * (streams + tables))}


def roofline_seconds(flops: float, nbytes: float, peaks) -> tuple:
    """(least time on the chip, 'compute' | 'memory' bound)."""
    tc, tm = flops / peaks.bf16_flops, nbytes / peaks.hbm_bytes
    return (tc, "compute") if tc >= tm else (tm, "memory")
