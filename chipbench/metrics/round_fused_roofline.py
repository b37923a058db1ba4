"""The fused round commit's share of its roofline, in %: for every call in
the traced window, the least time the chip needs for the bytes it must
move (state and eps history read and written once, this round's eps read
once, one diagonal-pool row, the per-slot tables) or its vector FLOPs,
over the calls' summed device time.  Kernels layer, predictor-only cells
whose families share one state width (one call shape); moves
samples_per_s."""
from chipbench.counts import round_fused_cost, roofline_seconds, state_rows

KERNEL = "round_fused"


def read(run):
    if run.trace is None or run.peaks is None:
        return None
    seconds, calls = run.trace.kernel(KERNEL)
    if calls == 0 or seconds <= 0:
        return None
    tr = run.cell.traffic
    if any(m["corrector"] for m in tr["mix"]):
        return None
    widths = {state_rows(view) for view in run.cell.families.values()}
    if len(widths) > 1:
        return None                 # one call shape per width, one name
    kf = K = widths.pop()
    cfg = next(iter(run.cell.families.values()))
    q = max(m["q"] for m in tr["mix"])
    Qb = 2
    while Qb < q:
        Qb *= 2
    D = 1
    for n in cfg["data_shape"]:
        D *= n
    c = round_fused_cost(batch=tr["slots"], K=K, Qb=Qb, kf=kf, D=D,
                         pool_rows=1)
    least, bound = roofline_seconds(c["flops"], c["bytes"], run.peaks)
    run.notes[KERNEL + "_roofline"] = f"{bound} bound, {calls} calls"
    return 100.0 * calls * least / seconds
