"""The device's busy time from the trace: the union of the op intervals on
each chip's op lane over the lane's window (first op start to last op end),
averaged over the chips.  ``report``: ``idle_share`` (percent of the window
in which no operation ran) or ``ms_per_step``."""

from chipbench import xplane


def reduce(measured, params):
    trace = measured.trace
    if trace is None:
        return None
    busy = xplane.mean_over_lanes(trace, xplane.busy_ns)
    if params["report"] == "ms_per_step":
        return busy / 1e6 / measured.traced_steps
    return 100.0 * (1.0 - busy / xplane.mean_over_lanes(trace,
                                                        xplane.window_ns))
