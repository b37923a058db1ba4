"""Pallas flash attention's share of its roofline, in %: for every call
in the traced window, the least time the chip needs for the call's FLOPs
(4 B H S^2 d_h) or its bytes (q, k, v read and o written once, bf16),
whichever is larger, over the calls' summed device time.  Kernels layer;
moves samples_per_s."""
from chipbench.counts import dit_shapes, flash_attention_cost, \
    roofline_seconds

KERNEL = "flash_attention"


def read(run):
    if run.trace is None or run.peaks is None:
        return None
    seconds, calls = run.trace.kernel(KERNEL)
    if calls == 0 or seconds <= 0:
        return None
    # the families of a config share the DiT's widths
    s = dit_shapes(next(iter(run.cell.families.values())))
    c = flash_attention_cost(run.cell.traffic["slots"], s["tokens"],
                             s["heads"], s["d_head"])
    least, bound = roofline_seconds(c["flops"], c["bytes"], run.peaks)
    run.notes[KERNEL + "_roofline"] = f"{bound} bound, {calls} calls"
    return 100.0 * calls * least / seconds
