"""Test harness config: an 8-device virtual CPU mesh.

Tests run on the CPU (the driver's command sets ``JAX_PLATFORMS=cpu``;
this file pins ``jax_platforms`` too) with Pallas kernels in interpret
mode, and validate multi-chip sharding on 8 virtual devices, the way
``__graft_entry__.dryrun_multichip`` does.  ``XLA_FLAGS`` is read when
the CPU client is first created, so it is set here before anything
touches a backend.  The chip path is ``chip_smoke.py``, run on the chip.
"""
import glob
import os
import sys
import time

import pytest

_flags = os.environ.get("XLA_FLAGS", "")
if "xla_force_host_platform_device_count" not in _flags:
    _flags = (_flags + " --xla_force_host_platform_device_count=8").strip()
# Tests are compile-dominated on the 1-core CI box (hundreds of distinct
# jitted programs, each compiled serially); backend optimization buys
# nothing for correctness — the kernels are exact integer ops and every
# suite pins bit-identity against the host oracle — so run the XLA
# backend at optimization level 0 here.  Measured ~27% off the tier-1
# wall (the 870s gate timeout had < 2% headroom).  Perf probes and
# bench.py do NOT inherit this: it is test-harness-only by construction
# (conftest), so recorded walls stay honest.
if "xla_backend_optimization_level" not in _flags:
    _flags = (_flags + " --xla_backend_optimization_level=0").strip()
os.environ["XLA_FLAGS"] = _flags

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

import jax  # noqa: E402

jax.config.update("jax_platforms", "cpu")


# --- tier-1 wall-time budget guard (ISSUE 3 satellite) -----------------------
# The tier-1 command runs under a 870s timeout (ROADMAP); when the suite
# creeps past ~720s the gate starts flaking on slow boxes before anyone
# notices a test belongs in `slow`.  The guard measures every `-m "not
# slow"` run and either warns LOUDLY (default) or fails the session
# (TCR_TIER1_BUDGET_FAIL=1).  Budget override: TCR_TIER1_BUDGET_S.

_TIER1_BUDGET_S = float(os.environ.get("TCR_TIER1_BUDGET_S", "720"))
_SESSION_T0 = time.time()


def _is_tier1(config) -> bool:
    return "not slow" in (config.getoption("-m") or "")


def _slowest_calls(terminalreporter, n: int = 15):
    """The session's ``n`` slowest test call phases, from the reports
    the terminal reporter already holds — so the budget warning can
    NAME the tests to demote instead of sending someone off to re-run
    with ``--durations``."""
    calls = []
    for reports in terminalreporter.stats.values():
        for rep in reports:
            if (getattr(rep, "when", None) == "call"
                    and hasattr(rep, "duration")):
                calls.append((rep.duration, rep.nodeid))
    calls.sort(reverse=True)
    return calls[:n]


def pytest_terminal_summary(terminalreporter, exitstatus, config):
    wall = time.time() - _SESSION_T0
    if not _is_tier1(config):
        return
    tr = terminalreporter
    if wall <= _TIER1_BUDGET_S:
        tr.write_line(
            f"tier-1 wall time {wall:.0f}s (budget {_TIER1_BUDGET_S:.0f}s)")
        return
    tr.write_sep("=", "TIER-1 WALL-TIME BUDGET EXCEEDED")
    tr.write_line(
        f"tier-1 ('-m \"not slow\"') took {wall:.0f}s — over the "
        f"{_TIER1_BUDGET_S:.0f}s budget of the 870s gate timeout.\n"
        f"Move the heaviest new tests to the `slow` tier (pytest.ini) "
        f"before the tier-1 command starts flaking.  Set "
        f"TCR_TIER1_BUDGET_FAIL=1 to make this a hard failure, "
        f"TCR_TIER1_BUDGET_S to adjust the budget.", red=True, bold=True)
    slowest = _slowest_calls(terminalreporter)
    if slowest:
        tr.write_line("slowest 15 call phases (demotion candidates):",
                      bold=True)
        for dur, nodeid in slowest:
            tr.write_line(f"  {dur:7.2f}s  {nodeid}")


def pytest_sessionfinish(session, exitstatus):
    wall = time.time() - _SESSION_T0
    if (_is_tier1(session.config) and wall > _TIER1_BUDGET_S
            and os.environ.get("TCR_TIER1_BUDGET_FAIL")):
        session.exitstatus = 3  # pytest's "internal error"-class exit:
        #                         loud and unambiguous in CI logs


# --- flight-recorder attach on serve-test failures (ISSUE 8 satellite) ------
# With TCR_TRACE_DIR set, every DocServer built during the run writes
# its post-mortem bundles there (serve/server.py reads the env as the
# obs_dir default).  Any failing tests/test_serve_* test then gets the
# bundle paths attached to its pytest report section, so a tier-1
# failure ships its own post-mortem instead of just an assert message:
#
#     TCR_TRACE_DIR=/tmp/tcr_obs pytest tests/ -m 'not slow'


@pytest.hookimpl(hookwrapper=True)
def pytest_runtest_makereport(item, call):
    outcome = yield
    rep = outcome.get_result()
    tdir = os.environ.get("TCR_TRACE_DIR")
    if not (tdir and rep.failed
            and os.path.basename(str(item.fspath)).startswith(
                ("test_serve_", "test_obs_"))):
        return
    # Only bundles written DURING this session: the dir is long-lived
    # and stale bundles from a previous run would mislead the triage.
    bundles = sorted(
        p for p in glob.glob(os.path.join(tdir, "**", "bundle_*.json"),
                             recursive=True)
        if os.path.getmtime(p) >= _SESSION_T0)
    rep.sections.append((
        "flight-recorder (TCR_TRACE_DIR)",
        "\n".join(bundles) if bundles
        else f"no post-mortem bundles under {tdir} from this session"))
