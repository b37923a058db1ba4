"""Shared test configuration: one BLAS thread, a per-test time limit, the
default grid-solved SDEs, and hypothesis example-budget profiles.

One BLAS thread per process.  The host Stage-I solvers (sde/solve.py,
sde/cld.py, sde/general.py) run float64 Python loops over 2x2 and 4x4
matrices.  numpy and scipy each load their own OpenBLAS; at one worker
thread per core, those threads spin around every tiny call, and several
test processes on one host then leave the Python loop a small share of a
core.  The environment reaches libraries loaded later and every
subprocess a test starts; numpy is already loaded when this module runs,
so `threadpool_limits` sets the pools already in the process.

The property/differential suites (test_properties.py,
test_factored_bank.py) leave `max_examples` unset in their per-test
`@settings(...)` so the *active profile* governs the budget:

  * ``dev`` (default) — small budget, random seeds: the local tier-1 run.
  * ``ci``            — pinned larger budget, **derandomized** (fixed
                        example sequence, reproducible across runs): the
                        CI hypothesis job selects it via
                        ``HYPOTHESIS_PROFILE=ci``.

hypothesis is an optional dev dependency (requirements-dev.txt); without
it the profiles are not registered and the suites skip themselves.
"""
import os

for _var in ("OPENBLAS_NUM_THREADS", "OMP_NUM_THREADS", "MKL_NUM_THREADS"):
    os.environ.setdefault(_var, "1")

import pytest
import scipy.linalg  # noqa: F401  (loads scipy's OpenBLAS before the limit)
from threadpoolctl import threadpool_limits

from suite_support import (LIMIT_S, default_cld, default_general_sde,
                           time_limit_hook)

threadpool_limits(1)

pytest_plugins = ["pytester"]

pytest_runtest_call = time_limit_hook(LIMIT_S)


@pytest.fixture(scope="session")
def cld():
    """The default `CLD()`, its R grid solved once per process."""
    return default_cld()


@pytest.fixture(scope="session")
def gsde():
    """The default `GeneralSDE()`, its R grid solved once per process."""
    return default_general_sde()


try:
    from hypothesis import HealthCheck, settings
except ImportError:                                   # pragma: no cover
    pass
else:
    _common = dict(deadline=None,
                   suppress_health_check=[HealthCheck.too_slow])
    settings.register_profile("dev", max_examples=12, **_common)
    settings.register_profile("ci", max_examples=40, derandomize=True,
                              print_blob=True, **_common)
    settings.load_profile(os.environ.get("HYPOTHESIS_PROFILE", "dev"))
