"""The correctness check's control, on the chip at the cell's own size:

    python3 chipbench/control.py --workload <cell> --seeds 1,2,3 \
        --seconds 10 [--kinds control,sound]

runs the cell once per seed and kind in one process and prints each
family's compared number beside its limit, as [value, limit].  `control`
puts the reference with every matmul input in fp8 (per-tensor scale; the
precision below the configuration's bf16) in the program's place for the
compared samples and must read above the limit on every seed; `sound` is
the program as configured.  The benchmark's own runs never run this.
"""
import argparse
import json
import sys
import time
from pathlib import Path

ROOT = Path(__file__).resolve().parents[1]


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--kinds", default="control")
    args = ap.parse_args(argv)
    sys.path.insert(0, str(ROOT))
    from chipbench.run import start_jax
    start_jax()
    from chipbench.harness import Abort, load_cell, run_cell
    cell = load_cell(args.workload)
    kinds = {"control": {"control": True}, "sound": {}}
    for seed in (int(s) for s in args.seeds.split(",")):
        for kind in args.kinds.split(","):
            try:
                res = run_cell(cell, seed, args.seconds, False,
                               t_process=time.perf_counter(), **kinds[kind])
            except Abort as e:
                print(f"chipbench: {e}", file=sys.stderr)
                return 2
            # one sample_rel_l2_max check per family of the config
            gaps = {k: [c["value"], c["limit"]]
                    for k, c in res["checks"].items()
                    if k.startswith("sample_rel_l2_max")}
            print(json.dumps({"kind": kind, "seed": seed, **gaps,
                              "correct": res["correct"],
                              "attempted": res["attempted"]}), flush=True)
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
