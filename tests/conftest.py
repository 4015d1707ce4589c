"""Test harness configuration.

Forces JAX onto the host CPU platform with 8 virtual devices, so every
sharding/collective test runs the same way the driver's multi-chip dry-run
does (SURVEY.md §4 "Distributed-without-a-cluster") and a TPU on the host is
never taken by the test suite. Nothing here can tell whether a program
compiles for the TPU: that is `python chip_smoke.py`, on the chip.

jax may already be imported when this runs, so JAX_PLATFORMS via os.environ
could be too late. Backends initialize lazily, so `jax.config.update` still
redirects, and XLA_FLAGS is read at first backend init — set both before any
test touches a device.
"""

import os
import time

import jax
import pytest

_flags = os.environ.get("XLA_FLAGS", "")
if "xla_force_host_platform_device_count" not in _flags:
    os.environ["XLA_FLAGS"] = (
        _flags + " --xla_force_host_platform_device_count=8"
    ).strip()
jax.config.update("jax_platforms", "cpu")


# -- tier-1 duration guard ---------------------------------------------------
# The tier-1 budget is one 870s pytest run for the WHOLE suite; a single
# slow unmarked test eats everyone else's budget. Any test whose call phase
# exceeds TIER1_TEST_BUDGET_S (default 5s) without a @pytest.mark.slow is
# reported in a terminal summary section; TIER1_DURATION_STRICT=1 turns the
# report into a failing exit status (opt-in — this container's wall clock
# swings with neighbor load, so the default guard names offenders without
# flaking the suite).

_DURATION_BUDGET_S = float(os.environ.get("TIER1_TEST_BUDGET_S", "5"))
_duration_offenders = []


def pytest_configure(config):
    config.addinivalue_line(
        "markers",
        "slow: excluded from tier-1 (-m 'not slow'); required on any test "
        f"whose call phase exceeds the {_DURATION_BUDGET_S:.0f}s duration "
        "budget (tests/conftest.py tier-1 duration guard)",
    )


@pytest.hookimpl(hookwrapper=True)
def pytest_runtest_call(item):
    t0 = time.perf_counter()
    yield
    elapsed = time.perf_counter() - t0
    if (
        elapsed > _DURATION_BUDGET_S
        and item.get_closest_marker("slow") is None
    ):
        _duration_offenders.append((item.nodeid, elapsed))


def pytest_terminal_summary(terminalreporter):
    if not _duration_offenders:
        return
    terminalreporter.section("tier-1 duration guard")
    terminalreporter.write_line(
        f"{len(_duration_offenders)} test(s) exceeded the "
        f"{_DURATION_BUDGET_S:.0f}s budget without @pytest.mark.slow "
        f"(the 870s tier-1 budget must cover the whole suite):"
    )
    for nodeid, elapsed in sorted(
        _duration_offenders, key=lambda kv: -kv[1]
    ):
        terminalreporter.write_line(f"  {elapsed:7.1f}s  {nodeid}")


def pytest_sessionfinish(session, exitstatus):
    if _duration_offenders and os.environ.get("TIER1_DURATION_STRICT"):
        session.exitstatus = 1
