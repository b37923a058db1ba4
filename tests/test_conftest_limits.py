"""The suite's own set-up (tests/conftest.py): the per-test time limit,
one BLAS thread in the test process, and the shared default CLD."""
import signal

import numpy as np
from threadpoolctl import threadpool_info

from repro.sde import CLD


def test_time_limit_fails_the_test_by_name(pytester):
    """A test that sleeps past the limit fails on the limit's clock, and
    the failure names the test and the limit.  The suite's own limit on
    this test is armed again afterwards."""
    pytester.makeconftest("""
        from suite_support import time_limit_hook
        pytest_runtest_call = time_limit_hook(2)
    """)
    pytester.makepyfile(test_sleeper="""
        import time

        def test_sleeps_past_the_limit():
            time.sleep(60)

        def test_ends_inside_the_limit():
            pass
    """)
    result = pytester.runpytest_inprocess("-p", "no:cacheprovider")
    result.assert_outcomes(passed=1, failed=1)
    result.stdout.fnmatch_lines([
        "*test_sleeper.py::test_sleeps_past_the_limit ran past the per-test "
        "time limit of 2 s*"])
    assert result.duration < 30
    assert signal.getitimer(signal.ITIMER_REAL)[0] > 0


def test_every_thread_pool_has_one_thread():
    pools = threadpool_info()
    assert {p["user_api"] for p in pools} >= {"blas"}
    assert [p for p in pools if p["num_threads"] != 1] == []


def test_shared_cld_equals_a_fresh_one(cld):
    np.testing.assert_array_equal(cld.R_np(0.5), CLD().R_np(0.5))
