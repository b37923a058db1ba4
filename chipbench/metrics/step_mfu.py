"""The round step's share of the chip's bf16 peak while the device works,
in %: the score-net FLOPs that served requests needed in the traced part
of the window, over the device's busy time there times the peak.  A
request needs NFE evals, plus NFE-1 corrector evals if it asked for the
corrector; padded, idle and other-class rows and corrector evals of slots
that did not ask for one do not count.  Each request's evals are counted
at its own family's net (the families of one config differ in their
patch projections).  Its evals are spread evenly over
its life in a slot (admission to the sample on the host), so the part
that falls in the traced span counts.  Round step + score net; moves
samples_per_s."""
from chipbench.counts import dit_eval_flops


def read(run):
    if run.trace is None or run.peaks is None or run.traced is None:
        return None
    lo, hi = run.traced
    evals = {}                                  # family -> evals
    for r in run.records:
        if r.admit is None or r.done is None or r.done <= r.admit:
            continue
        nfe = r.fields["nfe"]
        need = nfe + (nfe - 1 if r.fields["corrector"] else 0)
        inside = min(r.done, hi) - max(r.admit, lo)
        if inside > 0:
            fam = r.fields["family"]
            evals[fam] = evals.get(fam, 0.0) + need * inside / (r.done
                                                               - r.admit)
    if not evals or run.trace.busy_s <= 0:
        return None
    flops = sum(n * dit_eval_flops(run.cell.families[fam])["total"]
                for fam, n in evals.items())
    return 100.0 * flops / (run.trace.busy_s * run.peaks.bf16_flops)
