"""The trace reduction on hand-made lanes, and on a small trace recorded on
a v5e chip by ``record_trace.py`` (three steps, a 50 ms host sleep under the
span ``chipbench.sleep`` before the last)."""

import os

import pytest

from chipbench import xplane
from chipbench.xplane import Event

RECORDED = os.path.join(os.path.dirname(__file__), "data", "small.xplane.pb")

# one lane: a loop op covering two nested ops, a gap, then two ops that touch
LANE = [Event("while.1", 0, 100), Event("fusion.1", 10, 40),
        Event("fusion.2", 50, 90), Event("copy.3", 150, 170),
        Event("fusion.7", 170, 200)]


def test_union_counts_nested_and_touching_ops_once():
    assert xplane.busy_ns(LANE) == 100 + 50
    assert xplane.window_ns(LANE) == 200
    assert xplane.merged(LANE) == [(0, 100, "while.1"),
                                   (150, 200, "fusion.7")]


def test_gaps_name_the_op_that_ran_before():
    assert xplane.gaps(LANE) == [(100, 150, "while.1")]


def test_self_times_add_up_to_the_busy_time():
    times = xplane.self_times(LANE)
    assert dict(times) == {"while.1": 30, "fusion.1": 30, "fusion.2": 40,
                           "copy.3": 20, "fusion.7": 30}
    assert sum(ns for _, ns in times) == xplane.busy_ns(LANE)


def test_base_name_drops_the_uniquifier():
    assert xplane.base_name("fusion.123") == "fusion"
    assert xplane.base_name("copy-done") == "copy-done"
    assert xplane.base_name("flash_attention.12") == "flash_attention"


def test_a_gap_goes_to_the_innermost_span_covering_its_start():
    spans = [Event("chipbench.wait", 0, 120), Event("chipbench.inner", 90, 110),
             Event("chipbench.dispatch", 130, 160)]
    assert xplane.span_at(spans, 100) == "chipbench.inner"
    assert xplane.span_at(spans, 115) == "chipbench.wait"
    assert xplane.span_at(spans, 125) == "(no span)"
    assert xplane.span_at(spans, 140) == "chipbench.dispatch"


@pytest.fixture(scope="module")
def recorded():
    return xplane.read(RECORDED)


def test_recorded_trace_has_one_op_lane_and_the_spans(recorded):
    assert list(recorded.lanes) == ["/device:TPU:0"]
    names = [s.name for s in recorded.spans]
    assert names.count("chipbench.dispatch") == 3
    assert names.count("chipbench.wait") == 3
    assert names.count("chipbench.sleep") == 1


def test_recorded_trace_busy_idle_and_sums(recorded):
    lane, = recorded.lanes.values()
    busy, window = xplane.busy_ns(lane), xplane.window_ns(lane)
    assert 0 < busy < window
    assert sum(ns for _, ns in xplane.self_times(lane)) == pytest.approx(busy)
    # the sleep keeps the chip idle for most of the window
    assert 1 - busy / window > 0.5
    assert window > 50e6


def test_recorded_trace_longest_gap_is_the_sleep(recorded):
    lane, = recorded.lanes.values()
    start, end, _ = max(xplane.gaps(lane), key=lambda g: g[1] - g[0])
    assert end - start > 45e6
    assert xplane.span_at(recorded.spans, start + (end - start) / 2) == (
        "chipbench.sleep")
