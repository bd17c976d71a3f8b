"""Test configuration.

JAX runs on a virtual 8-device CPU mesh in tests (multi-chip sharding is validated
without TPU hardware, mirroring how the reference simulates multi-node sharding
in-process - petastorm/tests/test_end_to_end.py:454).  The env vars must be set
before the first ``import jax`` anywhere in the test process.
"""

import os
import sys

os.environ["JAX_PLATFORMS"] = "cpu"
_flags = os.environ.get("XLA_FLAGS", "")
if "xla_force_host_platform_device_count" not in _flags:
    os.environ["XLA_FLAGS"] = (_flags + " --xla_force_host_platform_device_count=8").strip()
sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

import numpy as np  # noqa: E402
import pytest  # noqa: E402

# Hang watchdog (a full-suite run once wedged inside
# tests/test_concurrency_stress.py with every thread in futex wait and the
# per-thread stacks lost to the output pipe).  Any single test exceeding the
# budget dumps ALL thread stacks to tests/.hang_dump.txt and kills the run -
# a wedge becomes an attributable failure with evidence instead of a silent
# stall.  faulthandler's watchdog is one C thread; re-arming per test is
# cheap.  Generous budget: the multi-process selfcheck phases legitimately
# take minutes.
_HANG_DUMP_PATH = os.path.join(os.path.dirname(os.path.abspath(__file__)),
                               ".hang_dump.txt")
_HANG_BUDGET_S = float(os.environ.get("PETASTORM_TPU_TEST_HANG_S", "600"))
_hang_dump_file = None


@pytest.hookimpl(hookwrapper=True)
def pytest_runtest_protocol(item, nextitem):
    global _hang_dump_file
    import faulthandler

    if _HANG_BUDGET_S <= 0:
        # PETASTORM_TPU_TEST_HANG_S=0 disables the watchdog entirely (e.g.
        # when running under a debugger); arming faulthandler with a
        # non-positive timeout would instead ValueError on every test
        yield
        return
    if _hang_dump_file is None:
        _hang_dump_file = open(_HANG_DUMP_PATH, "w")
    _hang_dump_file.seek(0)
    _hang_dump_file.truncate()
    _hang_dump_file.write(f"watchdog armed for: {item.nodeid}\n")
    _hang_dump_file.flush()
    faulthandler.dump_traceback_later(_HANG_BUDGET_S, exit=True,
                                      file=_hang_dump_file)
    yield
    faulthandler.cancel_dump_traceback_later()


def pytest_sessionfinish(session, exitstatus):
    # a clean finish leaves no stale evidence behind
    if _hang_dump_file is not None and os.path.exists(_HANG_DUMP_PATH):
        try:
            os.unlink(_HANG_DUMP_PATH)
        except OSError:
            pass


@pytest.fixture(scope="session")
def rng():
    return np.random.default_rng(1234)
