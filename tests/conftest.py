"""Test fixture: 8 virtual CPU devices standing in for an 8-chip TPU slice.

The reference runs its distributed tests under ``mpirun -np 4 pytest``
(SURVEY.md §4); the SPMD equivalent is a host-platform device mesh — plain
pytest, no launcher.  Env vars must be set before jax initializes a backend,
hence at module import time here.
"""

import functools
import os
import tempfile

os.environ["JAX_PLATFORMS"] = "cpu"  # force: the outer env may point at a TPU
# blackbox host-path recording is on by default, and failure-path tests
# legitimately trigger dumps — route them to a scratch dir instead of
# littering ./blackbox in the repo (tests that care set their own dir)
if "BLUEFOG_TPU_BLACKBOX_DIR" not in os.environ:
    os.environ["BLUEFOG_TPU_BLACKBOX_DIR"] = tempfile.mkdtemp(
        prefix="bf-blackbox-test-")
flags = os.environ.get("XLA_FLAGS", "")
if "host_platform_device_count" not in flags:
    os.environ["XLA_FLAGS"] = (
        flags + " --xla_force_host_platform_device_count=8"
    ).strip()

import jax  # noqa: E402
import pytest  # noqa: E402

# ---------------------------------------------------------------------------
# Tier-1 duration guard: the -m 'not slow' suite runs inside a hard wall
# (1,470 s under six xdist workers: the driver's command, `commands` of
# /root/TESTS_LAST_RUN.json) — a single new test that quietly burns half a
# minute eats the budget's headroom.  Any non-`slow` test exceeding the
# budget FAILS with instructions: mark it `slow`, or shrink it.
# Pre-existing heavyweights that must stay in tier-1 (their coverage is
# load-bearing) carry an explicit `@pytest.mark.duration_budget(<seconds>)`
# override — a visible, reviewed exemption, not a silent one.
#
# A test is judged by what its own work determines: it fails only when BOTH
# its wall time and its own CPU seconds (this process and the children it
# reaped, `os.times()`) pass the budget.  Under six workers the wall of a
# jitted case doubles or triples with the machine's load (7.6 s alone, 18.7
# beside five busy workers) while its CPU seconds stand still (14.8 / 16.7):
# cases of 8-11 s alone used to fail for load they did not cause.  CPU
# seconds alone would not do as the measure: eight virtual devices and
# XLA's thread pool put them at 1.2-2 x the wall of a test run alone (and
# BLAS spinning under contention at far more), so a test within the wall
# budget could fail on them; the smaller of the two never fails a test the
# wall alone would pass.  A test that only sleeps is not caught by this.
# ---------------------------------------------------------------------------
_TEST_DURATION_BUDGET_S = 20.0
_TIER1_WALL_S = 1470

# (nodeid, seconds) for every non-slow call phase this run — the
# terminal summary prints the 10 slowest so budget pressure is visible
# on EVERY run, not only when a test breaches the per-test guard
_durations = []


def pytest_configure(config):
    config.addinivalue_line(
        "markers",
        "duration_budget(seconds): override the tier-1 per-test duration "
        "guard for a reviewed pre-existing heavyweight (default "
        f"{_TEST_DURATION_BUDGET_S:.0f}s; new long tests should be "
        "marked slow instead)")


_cpu_key = pytest.StashKey[float]()


def _cpu_seconds():
    t = os.times()
    return t.user + t.system + t.children_user + t.children_system


@pytest.hookimpl(hookwrapper=True)
def pytest_runtest_call(item):
    before = _cpu_seconds()
    yield
    item.stash[_cpu_key] = _cpu_seconds() - before


@pytest.hookimpl(hookwrapper=True)
def pytest_runtest_makereport(item, call):
    outcome = yield
    rep = outcome.get_result()
    if rep.when != "call":
        return
    if "slow" in item.keywords:
        return  # slow-marked tests are outside the tier-1 wall
    _durations.append((item.nodeid, call.duration))
    if not rep.passed:
        return  # failures/skips already tell their own story
    budget = _TEST_DURATION_BUDGET_S
    marker = item.get_closest_marker("duration_budget")
    if marker is not None and marker.args:
        budget = float(marker.args[0])
    cpu = item.stash.get(_cpu_key, call.duration)
    if min(call.duration, cpu) > budget:
        rep.outcome = "failed"
        rep.longrepr = (
            f"{item.nodeid} took {call.duration:.1f}s of wall and "
            f"{cpu:.1f}s of its own CPU time — both over the "
            f"{budget:g}s tier-1 per-test budget.  Mark it "
            "@pytest.mark.slow (soak/MP scenarios belong outside the "
            "tier-1 wall), shrink it, or — for a reviewed pre-existing "
            "heavyweight whose tier-1 coverage is load-bearing — add an "
            "explicit @pytest.mark.duration_budget(<seconds>) override.")


def pytest_terminal_summary(terminalreporter):
    """The tier-1 budget dashboard: the 10 slowest non-`slow` tests of
    this run, every run.  The suite lives inside a 1,470 s wall under six
    workers — the guard above catches a single runaway test, this
    summary is how creeping aggregate growth gets noticed while it is
    still one `slow` mark away from fixed."""
    if not _durations:
        return
    top = sorted(_durations, key=lambda kv: -kv[1])[:10]
    terminalreporter.write_sep(
        "-", "10 slowest non-slow tests (tier-1 budget watch)")
    for nodeid, dur in top:
        terminalreporter.write_line(f"{dur:7.2f}s  {nodeid}")
    total = sum(d for _, d in _durations)
    terminalreporter.write_line(
        f"{total:7.1f}s  total across {len(_durations)} non-slow "
        f"call phases (tier-1 wall: {_TIER1_WALL_S}s under six workers)")


@pytest.fixture(autouse=True)
def _fresh_context():
    """Each test starts without a live bluefog context."""
    import bluefog_tpu as bf

    yield
    bf.shutdown()


@pytest.fixture
def devices8():
    d = jax.devices()
    assert len(d) == 8, f"expected 8 virtual devices, got {len(d)}"
    return d


AOT_TOPO_NAME = "v5e:2x4"


@functools.lru_cache(maxsize=None)
def aot_topology(name: str):
    """AOT TPU topology for compile-only tests (overlap report, Pallas
    kernel schedulability).  ONE skip policy for every AOT test: skips when
    the topologies API or libtpu is missing; anything else (e.g. a
    ValueError from a typo'd topology name) must FAIL, not skip — PARITY.md
    advertises these tests as enforced where libtpu exists.  lru_cached:
    get_topology_desc loads the TPU compiler, worth doing once per name."""
    try:
        from jax.experimental import topologies
    except ImportError as e:  # API moved/removed in a jax upgrade
        pytest.skip(f"jax topologies API unavailable: {e}")
    try:
        return topologies.get_topology_desc(platform="tpu",
                                            topology_name=name)
    except RuntimeError as e:  # no libtpu on this machine
        pytest.skip(f"TPU AOT topology unavailable: {e}")


@pytest.fixture(scope="session")
def tpu_aot_topology():
    return aot_topology(AOT_TOPO_NAME)
