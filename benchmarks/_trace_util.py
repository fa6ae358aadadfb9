"""Shared device-trace timing for the single-chip micro-benchmarks.

At microbenchmark scale dispatch overhead is a large share of a host wall
clock, so every benchmark also times a ``jax.profiler`` trace window and
reports the device's own op-time total beside it
(`profile_summary.device_op_totals`, the same parser bench.py uses for its
``trace_device_step_ms`` field).
"""

import importlib.util
import os
import tempfile
import time

import jax


def trace_step_ms(trace_dir, steps):
    """Per-step per-chip device op time (ms), or None when the trace is
    missing/host-only (CPU runs)."""
    summary_py = os.path.join(os.path.dirname(os.path.abspath(__file__)),
                              "profile_summary.py")
    try:
        spec = importlib.util.spec_from_file_location(
            "bftpu_profile_summary", summary_py)
        mod = importlib.util.module_from_spec(spec)
        spec.loader.exec_module(mod)
        (_path, by_op, total_us, n_lanes,
         device_events) = mod.device_op_totals(trace_dir)
    except (Exception, SystemExit):
        return None
    if not by_op or not device_events or n_lanes <= 0:
        return None
    return total_us / 1e3 / steps / n_lanes


def timed_trace(fn, args_, steps, trace_steps: int = 3):
    """Time ``steps`` untraced calls, then trace ``trace_steps`` more.

    bench.py's discipline: the wall clock is measured WITHOUT the profiler
    running (host-side tracing overhead would land in it), and a separate
    short traced window supplies the device op time.  Returns
    ``(wall_ms_per_step, trace_ms_per_step | None)``.  Compile happens
    outside both clocks.
    """
    jax.tree_util.tree_leaves(fn(*args_))[0].block_until_ready()
    t0 = time.perf_counter()
    out = None
    for _ in range(steps):
        out = fn(*args_)
    jax.tree_util.tree_leaves(out)[0].block_until_ready()
    wall_ms = (time.perf_counter() - t0) / steps * 1e3
    with tempfile.TemporaryDirectory(prefix="bftpu_trace_") as trace_dir:
        with jax.profiler.trace(trace_dir):
            for _ in range(trace_steps):
                out = fn(*args_)
            jax.tree_util.tree_leaves(out)[0].block_until_ready()
        return wall_ms, trace_step_ms(trace_dir, trace_steps)
