"""Median host time inside the call into the compiled step (the span
``chipbench.dispatch``), from the steady untraced stretch."""

import statistics


def reduce(measured, params):
    values = measured.dispatch_ms
    return statistics.median(values) if values else None
