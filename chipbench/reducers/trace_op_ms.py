"""Device time, per step and chip, of the operations whose trace name matches
any of ``patterns``: summed self times on each chip's op lane."""

import re

from chipbench import xplane


def matched_ns(trace, patterns):
    patterns = [re.compile(p) for p in patterns]
    return sum(ns for events in trace.lanes.values()
               for name, ns in xplane.self_times(events)
               if any(p.search(name) for p in patterns))


def reduce(measured, params):
    if measured.trace is None:
        return None
    ns = matched_ns(measured.trace, params["patterns"])
    return ns / 1e6 / measured.traced_steps / len(measured.trace.lanes)
