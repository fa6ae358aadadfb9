"""Median step time of the cell's step minus that of an arm: the same step
with some traffic keys overridden, timed untraced in the same process."""

import statistics

from chipbench.cell import arm_key


def reduce(measured, params):
    arm = measured.arm_step_ms.get(arm_key(params["arm"]))
    if arm is None or not measured.step_ms:
        return None
    return statistics.median(measured.step_ms) - arm
