"""chipbench's own tests run on the CPU: keep JAX off the TPU library, hold
each test process and its subprocesses to one BLAS thread (the reference's
and the program's host solvers loop over tiny matrices, and BLAS threads
spinning around each call starve several test processes of a host), and
fail a test that runs past LIMIT_S of wall time on its own clock."""
import os
import signal
import sys
from pathlib import Path

os.environ.setdefault("JAX_PLATFORMS", "cpu")
for _var in ("OPENBLAS_NUM_THREADS", "OMP_NUM_THREADS", "MKL_NUM_THREADS"):
    os.environ.setdefault(_var, "1")

import pytest  # noqa: E402
import scipy.linalg  # noqa: E402,F401  (loads scipy's BLAS before the limit)
from threadpoolctl import threadpool_limits  # noqa: E402

threadpool_limits(1)

ROOT = Path(__file__).resolve().parents[2]
for p in (str(ROOT / "src"), str(ROOT)):
    if p not in sys.path:
        sys.path.insert(0, p)

# about 3x the slowest test here on a loaded 8-core host
LIMIT_S = 210


@pytest.hookimpl(hookwrapper=True)
def pytest_runtest_call(item):
    def expire(signum, frame):
        pytest.fail(f"{item.nodeid} ran past the per-test time limit of "
                    f"{LIMIT_S} s")

    outer = signal.signal(signal.SIGALRM, expire)
    signal.setitimer(signal.ITIMER_REAL, LIMIT_S)
    try:
        yield
    finally:
        signal.setitimer(signal.ITIMER_REAL, 0)
        signal.signal(signal.SIGALRM, outer)
