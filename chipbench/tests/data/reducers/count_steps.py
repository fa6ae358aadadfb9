"""Test data: steady steps the untraced stretch completed."""


def reduce(measured, params):
    return len(measured.step_ms) + 1
