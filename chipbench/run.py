"""Run one benchmark cell once on the chip this process finds:

    python3 chipbench/run.py --workload <cell> --seed <n> --seconds <s> \
        --trace <0|1>

The last line of standard output is the run's JSON result; the compared
numbers and their limits are the last lines of standard error.  A run that
cannot measure (no TPU, too few chips, no checkout around it) prints no
result and exits 2.  The compile cache is `<checkout>/.jax_cache`.
"""
import time

T_PROCESS = time.perf_counter()

import argparse  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import sys  # noqa: E402
from pathlib import Path  # noqa: E402

ROOT = Path(__file__).resolve().parents[1]


def start_jax() -> None:
    """Put the checkout's program on the path and JAX's persistent compile
    cache in the checkout, caching every program, however quick."""
    for p in (str(ROOT / "src"), str(ROOT)):
        if p not in sys.path:
            sys.path.insert(0, p)
    # the program keeps its compile cache where this variable points
    os.environ["JAX_COMPILATION_CACHE_DIR"] = str(ROOT / ".jax_cache")
    from repro.launch.compile_cache import enable_compile_cache
    enable_compile_cache()
    import jax
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description="run one chipbench cell once")
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)
    if not (ROOT / "src" / "repro").is_dir():
        print(f"chipbench: no program under {ROOT / 'src'}: run from a "
              "checkout of the repository", file=sys.stderr)
        return 2
    start_jax()
    from chipbench.harness import Abort, load_cell, run_cell
    try:
        result = run_cell(load_cell(args.workload), args.seed, args.seconds,
                          bool(args.trace), t_process=T_PROCESS)
    except Abort as e:
        print(f"chipbench: {e}", file=sys.stderr)
        return 2
    print(json.dumps(result), flush=True)
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
