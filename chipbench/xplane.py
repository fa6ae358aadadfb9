"""From a profiler trace (``.xplane.pb``) to intervals: the reduction every
device metric of the benchmark shares.

A trace holds planes; a TPU chip is a plane ``/device:TPU:<n>`` whose line
``XLA Ops`` carries one event per executed HLO instruction (an enclosing
``while`` or ``conditional`` covers its body's events; asynchronous copies and
collectives have a line of their own, ``Async XLA Ops``, which is not busy
time of the core).  An event is named by the instruction's whole text,
``%fusion.168 = (f32[...]) fusion(...)``; here it keeps the instruction's
name, ``fusion.168``.  The benchmark's own
host spans (``chipbench.*``, written with ``jax.profiler.TraceAnnotation``)
are events on the host plane's thread lines and share the device lines' clock.
Times are nanoseconds since the start of the trace.
"""

import dataclasses
import glob
import os
import re

DEVICE_PLANE = re.compile(r"^/device:(TPU|GPU):\d+")
OP_LINE = "XLA Ops"
SPAN_PREFIX = "chipbench."


@dataclasses.dataclass(frozen=True)
class Event:
    name: str
    start: float
    end: float

    @property
    def duration(self):
        return self.end - self.start


@dataclasses.dataclass(frozen=True)
class Trace:
    lanes: dict    # device plane name -> its op events, sorted by start
    spans: list    # the benchmark's host spans, sorted by start


def newest(trace_dir):
    """The ``.xplane.pb`` the profiler wrote last under ``trace_dir``."""
    hits = sorted(glob.glob(os.path.join(
        trace_dir, "plugins", "profile", "*", "*.xplane.pb")))
    return hits[-1] if hits else None


def instruction_name(text: str) -> str:
    """``%fusion.168 = (f32[...]) fusion(...)`` -> ``fusion.168``."""
    return text.split(" = ", 1)[0].lstrip("%")


def read(path) -> Trace:
    from jax.profiler import ProfileData

    lanes, spans = {}, []
    for plane in ProfileData.from_file(path).planes:
        if DEVICE_PLANE.match(plane.name):
            for line in plane.lines:
                if line.name == OP_LINE:
                    lanes[plane.name] = sorted(
                        (Event(instruction_name(e.name), e.start_ns,
                               e.start_ns + e.duration_ns)
                         for e in line.events), key=lambda e: e.start)
        else:
            for line in plane.lines:
                spans.extend(
                    Event(e.name, e.start_ns, e.start_ns + e.duration_ns)
                    for e in line.events if e.name.startswith(SPAN_PREFIX))
    return Trace(lanes, sorted(spans, key=lambda e: e.start))


def merged(events):
    """The union of the events' intervals as disjoint ``(start, end, name)``,
    ``name`` being the event that ends each stretch."""
    out = []
    for e in sorted(events, key=lambda e: e.start):
        if out and e.start <= out[-1][1]:
            if e.end > out[-1][1]:
                out[-1] = (out[-1][0], e.end, e.name)
        else:
            out.append((e.start, e.end, e.name))
    return out


def busy_ns(events) -> float:
    return sum(end - start for start, end, _ in merged(events))


def window_ns(events) -> float:
    """First start to last end: the steady window of one lane."""
    if not events:
        return 0.0
    return max(e.end for e in events) - min(e.start for e in events)


def mean_over_lanes(trace, measure) -> float:
    """``measure`` (``busy_ns``, ``window_ns``) averaged over the chips."""
    return sum(map(measure, trace.lanes.values())) / len(trace.lanes)


def gaps(events):
    """Idle stretches inside the lane's window: ``(start, end, after)`` with
    ``after`` the name of the op that ran last before the gap."""
    m = merged(events)
    return [(a[1], b[0], a[2]) for a, b in zip(m, m[1:]) if b[0] > a[1]]


def self_times(events):
    """``(name, ns)`` per event with the time its nested events cover taken
    out, so the figures of one lane add up to its busy time."""
    out, stack = [], []   # stack of [event, self_ns]
    for e in sorted(events, key=lambda e: (e.start, -e.end)):
        while stack and stack[-1][0].end <= e.start:
            done = stack.pop()
            out.append((done[0].name, max(done[1], 0.0)))
        if stack:
            stack[-1][1] -= min(e.end, stack[-1][0].end) - e.start
        stack.append([e, e.duration])
    out.extend((ev.name, max(ns, 0.0)) for ev, ns in stack)
    return out


def base_name(name: str) -> str:
    """``fusion.123`` and ``fusion.7.remat2`` -> ``fusion``: the suffixes
    XLA adds to keep instruction names apart dropped."""
    return re.sub(r"(\.(\d+|remat\d*|clone))+$", "", name)


def span_at(spans, t) -> str:
    """The innermost host span covering time ``t``, or ``"(no span)"``."""
    best = None
    for s in spans:
        if s.start > t:
            break
        if s.end > t and (best is None or s.start >= best.start):
            best = s
    return best.name if best else "(no span)"
