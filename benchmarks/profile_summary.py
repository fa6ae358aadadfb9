"""Summarize a jax.profiler trace: top device-time sinks per op category.

``bench.py --profile DIR`` captures a TensorBoard-format trace
(``DIR/plugins/profile/<run>/<host>.trace.json.gz`` — Chrome trace events).
This digests it into the top-N device ops by total duration — the data behind
PROFILE.md's sink table — without needing TensorBoard.

Run:  python benchmarks/profile_summary.py /tmp/bench_profile [--top 15]
"""

import argparse
import glob
import gzip
import json
import os
import re
import sys
from collections import defaultdict


def find_trace(root):
    pats = [os.path.join(root, "plugins", "profile", "*", "*.trace.json.gz"),
            os.path.join(root, "**", "*.trace.json.gz")]
    for p in pats:
        hits = sorted(glob.glob(p, recursive=True))
        if hits:
            return hits[-1]  # latest run
    raise SystemExit(f"no *.trace.json.gz under {root}")


def device_op_totals(trace_dir):
    """Per-op device time from the latest trace under ``trace_dir``.

    Returns ``(path, by_op, total_us, n_lanes, device_events)``: the trace
    file used, total duration (µs) per base op name, their sum across ALL
    contributing lanes, the number of distinct event lanes (one "XLA Ops"
    thread per local device — a per-chip figure must divide by this), and
    whether the events actually came from a device-side lane rather than
    host threads.  ``bench.py`` prints the total beside its wall-clock
    timing as ``trace_device_step_ms``; this CLI uses ``by_op`` for the sink
    table.
    """
    path = find_trace(trace_dir)
    with gzip.open(path, "rt") as f:
        data = json.load(f)
    events = data.get("traceEvents", data if isinstance(data, list) else [])

    # Select per-op device events WITHOUT double counting their enclosing
    # spans: TensorBoard traces put one "XLA Ops" thread (per-instruction
    # events) next to "XLA Modules"/"Steps" threads whose events span whole
    # compiled steps — summing a pid wholesale counts every op twice.
    pid_names, tid_names = {}, {}
    for e in events:
        if e.get("ph") != "M":
            continue
        if e.get("name") == "process_name":
            pid_names[e["pid"]] = e.get("args", {}).get("name", "")
        elif e.get("name") == "thread_name":
            tid_names[(e.get("pid"), e.get("tid"))] = (
                e.get("args", {}).get("name", ""))
    op_tids = {k for k, v in tid_names.items() if re.search(r"XLA Ops", v)}
    device_pids = {pid for pid, name in pid_names.items()
                   if re.search(r"TPU|device|/device", name, re.I)}

    def selected(e):
        if op_tids:
            return (e.get("pid"), e.get("tid")) in op_tids
        tname = tid_names.get((e.get("pid"), e.get("tid")), "")
        if re.search(r"Modules|Steps", tname):
            return False  # step/module envelopes, not per-op time
        return not device_pids or e.get("pid") in device_pids

    by_op = defaultdict(float)
    total = 0.0
    lanes = set()
    for e in events:
        if e.get("ph") != "X" or "dur" not in e or not selected(e):
            continue
        name = e.get("name", "?")
        # collapse XLA's uniquifier suffixes: fusion.123 -> fusion
        base = re.sub(r"[.\d]+$", "", name) or name
        by_op[base] += e["dur"]
        total += e["dur"]
        lanes.add((e.get("pid"), e.get("tid")))

    # A lane count is only a chip count when the lanes are the labeled
    # per-device "XLA Ops" threads; in the device-pid fallback a pid's
    # extra streams (DMA etc.) would masquerade as chips and understate
    # the per-chip time — report 0 so callers refuse to divide by it.
    n_lanes = len(lanes) if op_tids else 0
    return path, by_op, total, n_lanes, bool(op_tids or device_pids)


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("trace_dir")
    ap.add_argument("--top", type=int, default=15)
    args = ap.parse_args()

    path, by_op, total, _lanes, device_events = device_op_totals(
        args.trace_dir)
    if not by_op:
        raise SystemExit("no device op events found in trace")
    if not device_events:
        print("WARNING: no 'XLA Ops' thread or device pid in this trace — "
              "host-side events are being summed (CPU-only capture?); "
              "capture on a TPU for a meaningful sink table", file=sys.stderr)
    print(f"trace: {path}")
    print(f"total device op time: {total / 1e3:.2f} ms "
          f"(over the captured steps)")
    print(f"{'op':40s} {'ms':>10s} {'share':>7s}")
    for op, dur in sorted(by_op.items(), key=lambda kv: -kv[1])[:args.top]:
        print(f"{op:40s} {dur / 1e3:10.2f} {dur / total:7.1%}")


if __name__ == "__main__":
    main()
