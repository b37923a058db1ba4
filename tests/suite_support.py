"""What every test process shares: the default grid-solved SDEs and the
per-test time limit (wired up in tests/conftest.py).

`CLD()` and `GeneralSDE()` solve their R_t grid (Eq. 17) on the host the
first time it is read: ~26k RK4 steps of 4x4 matrix exponentials, seconds
per instance.  An instance is a pure function of its dataclass fields and
fills its `cached_property`s the same way whoever reads first, so tests
that only read a default instance share one per process.  A test that
changes an SDE builds its own.
"""
import functools
import signal
import time

import pytest

# Wall-time limit of one test, in seconds: about 3x the slowest test of
# the tier-1 run, so a hang fails on its own clock instead of the whole
# run's.  A test's subprocesses get the same limit.
LIMIT_S = 210


@functools.cache
def default_cld():
    from repro.sde import CLD
    return CLD()


@functools.cache
def default_general_sde():
    from repro.sde.general import GeneralSDE
    return GeneralSDE()


def time_limit_hook(limit_s):
    """A `pytest_runtest_call` hook that fails a test still running after
    `limit_s` seconds of wall time, naming the test and the limit.

    It arms `ITIMER_REAL` on the main thread, where pytest (and each
    xdist worker) runs tests.  A timer already armed around the call (a
    pytester run inside a timed test) is put back with what is left of
    it.  `subprocess.run` kills its child when the failure is raised
    inside `communicate`."""
    @pytest.hookimpl(hookwrapper=True)
    def pytest_runtest_call(item):
        def expire(signum, frame):
            pytest.fail(f"{item.nodeid} ran past the per-test time limit "
                        f"of {limit_s} s")

        outer_handler = signal.signal(signal.SIGALRM, expire)
        outer_left, _ = signal.setitimer(signal.ITIMER_REAL, limit_s)
        start = time.monotonic()
        try:
            yield
        finally:
            signal.setitimer(signal.ITIMER_REAL, 0)
            signal.signal(signal.SIGALRM, outer_handler)
            if outer_left:
                left = outer_left - (time.monotonic() - start)
                signal.setitimer(signal.ITIMER_REAL, max(left, 1e-3))
    return pytest_runtest_call
