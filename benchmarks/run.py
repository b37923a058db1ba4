"""Benchmark aggregator: one function per paper table (see tables.py).

    PYTHONPATH=src python -m benchmarks.run            # all tables
    PYTHONPATH=src python -m benchmarks.run tab3 fig1  # subset

Output CSV: table,config,nfe,us_per_call,sw2,mode_recovery
(sw2 = sliced Wasserstein-2 to ground truth; the FID stand-in, lower=better)
"""
import sys

from repro.launch.compile_cache import enable_compile_cache

from . import quality
from . import tables
from . import serving


ALL = {
    "tab1": tables.table1_Lt_vs_Rt,
    "tab2": tables.table2_lambda,
    "tab3": tables.table3_accelerate,
    "tab8": tables.table8_pc,
    "fig1": tables.fig1_eps_constancy,
    "kernels": tables.kernel_micro,
    "serving": serving.serving_throughput,
    "quality": quality.quality_table,
}


def main(argv=None) -> int:
    argv = argv if argv is not None else sys.argv[1:]
    names = argv or list(ALL)
    enable_compile_cache()
    print("table,config,nfe,us_per_call,sw2,mode_recovery")
    for n in names:
        for row in ALL[n]():
            print(row, flush=True)
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
