"""``benchmarks/conv_roofline.py`` reads the device time of its traced window
through the benchmark's trace reader (``chipbench/xplane.py``): checked on the
trace ``chipbench/tests/record_scoped_trace.py`` recorded on a v5e chip
(three steps, one chip)."""

import os
import shutil

import pytest

from tests._util import REPO, load_script

RECORDED = os.path.join(REPO, "chipbench", "tests", "data", "scoped.xplane.pb")
RECORDED_STEPS = 3


@pytest.fixture(scope="module")
def roofline():
    return load_script(os.path.join("benchmarks", "conv_roofline.py"))


def test_trace_step_ms_is_the_device_busy_time_per_step(roofline, tmp_path):
    run = tmp_path / "plugins" / "profile" / "2026_09_28_00_00_00"
    run.mkdir(parents=True)
    shutil.copy(RECORDED, run / "host.xplane.pb")
    got = roofline.trace_step_ms(str(tmp_path), RECORDED_STEPS)
    # the union of the one lane's op intervals is 77,577 ns (of a window of
    # 2.39 ms: busy time, not the window)
    assert got == pytest.approx(77_577 / 1e6 / RECORDED_STEPS, rel=1e-9)


def test_trace_step_ms_is_none_without_a_trace(roofline, tmp_path):
    assert roofline.trace_step_ms(str(tmp_path), RECORDED_STEPS) is None
