"""Per-op conv roofline: is each dominant ResNet-50 convolution at ITS bound?

Round-4 verdict weak #1: the ResNet-50 step measures 31 % MFU while the
whole-model roofline (all FLOPs at nominal matmul peak) says ~3x headroom —
a claim that needs per-op evidence, because "all conv FLOPs at matmul peak"
is not attainable for real conv shapes (low channel counts under-fill the
128-lane MXU; strided/spatial tiling costs the systolic array turns a pure
GEMM never pays).

Method, per dominant conv shape of ResNet-50/224 (each unique (HxW, Cin,
Cout, k, stride) with its per-network multiplicity):

- time the convolution standalone (jitted scan loop, timed from the device
  trace — dispatch overhead dominates a host wall clock at this scale);
- time its **im2col GEMM twin** — a single ``(M, K) @ (K, N)`` with
  ``M = B*Ho*Wo, K = kh*kw*Cin, N = Cout``, i.e. the same MAC count on the
  same chip.  The twin's rate is the *empirically attainable* ceiling for
  that shape: if conv time ~= twin time, the conv is at its shape's bound
  and no layout/scheduling fix can buy more without changing the model;
- compute the analytic bounds: flops / nominal-peak and min-bytes / HBM-BW.

Aggregate: sum over shapes of (multiplicity x twin time) = the best step
time any scheduler could reach if every conv hit its GEMM-twin rate; the
implied "attainable MFU" is the honest ceiling to compare 31 % against.
Forward convs only (the backward convs are GEMM-twins of the same K/M/N up
to transposition — stated, not measured).

Run (real chip):  python benchmarks/conv_roofline.py [--batch 128]
Prints one JSON line; rows carry wall+trace ms and a bound verdict.
"""

import argparse
import json
import os
import sys
import tempfile
import time

sys.path.insert(0, os.path.join(os.path.dirname(__file__), ".."))

import jax
import jax.numpy as jnp
from jax import lax

from chipbench import xplane
from chipbench.peaks import peaks_for
from chipbench.run import read_trace

# ResNet-50/224 conv inventory: (label, H, W, Cin, Cout, k, stride, count).
# Counts are per forward pass (bottleneck expansions included; projection
# convs folded into their stage rows).
RESNET50_CONVS = [
    ("stem 7x7/2", 224, 224, 3, 64, 7, 2, 1),
    ("s1 1x1 64>64", 56, 56, 64, 64, 1, 1, 1),
    ("s1 3x3 64", 56, 56, 64, 64, 3, 1, 3),
    ("s1 1x1 64>256", 56, 56, 64, 256, 1, 1, 3),
    ("s1 1x1 256>64", 56, 56, 256, 64, 1, 1, 2),
    ("s1 proj 256", 56, 56, 64, 256, 1, 1, 1),
    ("s2 1x1 256>128", 56, 56, 256, 128, 1, 1, 1),
    ("s2 3x3/2 128", 56, 56, 128, 128, 3, 2, 1),
    ("s2 3x3 128", 28, 28, 128, 128, 3, 1, 3),
    ("s2 1x1 128>512", 28, 28, 128, 512, 1, 1, 4),
    ("s2 1x1 512>128", 28, 28, 512, 128, 1, 1, 3),
    ("s2 proj 512/2", 56, 56, 256, 512, 1, 2, 1),
    ("s3 1x1 512>256", 28, 28, 512, 256, 1, 1, 1),
    ("s3 3x3/2 256", 28, 28, 256, 256, 3, 2, 1),
    ("s3 3x3 256", 14, 14, 256, 256, 3, 1, 5),
    ("s3 1x1 256>1024", 14, 14, 256, 1024, 1, 1, 6),
    ("s3 1x1 1024>256", 14, 14, 1024, 256, 1, 1, 5),
    ("s3 proj 1024/2", 28, 28, 512, 1024, 1, 2, 1),
    ("s4 1x1 1024>512", 14, 14, 1024, 512, 1, 1, 1),
    ("s4 3x3/2 512", 14, 14, 512, 512, 3, 2, 1),
    ("s4 3x3 512", 7, 7, 512, 512, 3, 1, 2),
    ("s4 1x1 512>2048", 7, 7, 512, 2048, 1, 1, 3),
    ("s4 1x1 2048>512", 7, 7, 512, 2048, 1, 1, 0),  # transpose of above
    ("s4 1x1 2048>512b", 7, 7, 2048, 512, 1, 1, 2),
    ("s4 proj 2048/2", 14, 14, 1024, 2048, 1, 2, 1),
]


def trace_step_ms(trace_dir, steps):
    """Device-busy ms per step and chip from the trace under ``trace_dir``,
    or ``None`` where it holds no device lane (a CPU run) or no trace."""
    trace = read_trace(trace_dir)
    if trace is None:
        return None
    return xplane.mean_over_lanes(trace, xplane.busy_ns) / 1e6 / steps


def timed_trace(fn, args_, steps, trace_steps: int = 3):
    """Time ``steps`` untraced calls, then trace ``trace_steps`` more.

    The wall clock is measured WITHOUT the profiler running (host-side
    tracing overhead would land in it), and a separate short traced window
    supplies the device time.  Returns ``(wall_ms_per_step,
    trace_ms_per_step | None)``.  Compile happens outside both clocks.
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


def conv_fn(B, H, W, Cin, Cout, k, s):
    pad = "SAME" if k > 1 else "VALID"

    def f(x, w):
        def body(acc, _):
            # the carry perturbs the WEIGHTS so the conv is NOT
            # loop-invariant (XLA would hoist an invariant conv out of the
            # while loop and the 8 "repeats" would time one execution);
            # weights are the smallest operand, and the GEMM twin perturbs
            # its same-sized B matrix — symmetric overhead
            ww = (w.astype(jnp.float32) * (1.0 + acc * 1e-30)).astype(w.dtype)
            y = lax.conv_general_dilated(
                x, ww, (s, s), pad,
                dimension_numbers=("NHWC", "HWIO", "NHWC"),
                preferred_element_type=jnp.float32)
            # consume EVERY output element: slicing one element would let
            # XLA push the slice through the conv and compute a single
            # output position (measured: 70x non-physical rates)
            return acc + jnp.sum(y), None

        acc, _ = lax.scan(body, jnp.float32(0), None, length=REPEATS)
        return acc

    return f


def gemm_fn(M, K, N):
    def f(a, b):
        def body(acc, _):
            bb = (b.astype(jnp.float32) * (1.0 + acc * 1e-30)).astype(b.dtype)
            y = jnp.dot(a, bb, preferred_element_type=jnp.float32)
            return acc + jnp.sum(y), None  # full consumption — see conv_fn

        acc, _ = lax.scan(body, jnp.float32(0), None, length=REPEATS)
        return acc

    return f


REPEATS = 8  # convs per jitted call: amortizes per-call dispatch


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--batch", type=int, default=128)
    ap.add_argument("--steps", type=int, default=5)
    ap.add_argument("--top", type=int, default=0,
                    help="only the N most FLOP-heavy shapes (0 = all)")
    args = ap.parse_args()
    B = args.batch
    peak_flops, peak_bytes = peaks_for(jax.devices()[0].device_kind)

    shapes = [r for r in RESNET50_CONVS if r[7] > 0]
    if args.top:
        shapes = sorted(
            shapes, key=lambda r: -(r[1] * r[2] * r[3] * r[4] * r[5] ** 2
                                    / r[6] ** 2 * r[7]))[:args.top]

    key = jax.random.PRNGKey(0)
    rows, twin_total_ms, conv_total_ms = [], 0.0, 0.0
    for (label, H, W, Cin, Cout, k, s, count) in shapes:
        Ho, Wo = H // s, W // s
        M, K, N = B * Ho * Wo, k * k * Cin, Cout
        flops = 2.0 * M * K * N
        # TRUE lower bound on HBM traffic: bf16 input + weights only.  The
        # output is deliberately excluded — the timed kernel's jnp.sum
        # consumer fuses into the conv epilogue, so the f32 output need
        # never materialize in HBM; counting it would overstate the floor
        # (and in-model the next layer often fuses the same way).
        bytes_min = 2.0 * (B * H * W * Cin + k * k * Cin * Cout)

        x = jax.random.normal(key, (B, H, W, Cin), jnp.bfloat16)
        w = jax.random.normal(key, (k, k, Cin, Cout), jnp.bfloat16)
        cfn = jax.jit(conv_fn(B, H, W, Cin, Cout, k, s))
        c_wall, c_trace = timed_trace(cfn, (x, w), args.steps)

        a = jax.random.normal(key, (M, K), jnp.bfloat16)
        b = jax.random.normal(key, (K, N), jnp.bfloat16)
        gfn = jax.jit(gemm_fn(M, K, N))
        g_wall, g_trace = timed_trace(gfn, (a, b), args.steps)

        # the conv/twin ratio is only meaningful same-source: a device trace
        # against a host wall clock compares two different quantities, so
        # fall back to wall for BOTH when either trace is missing (the row
        # is then flagged uncorroborated)
        both_traced = c_trace is not None and g_trace is not None
        c_ms = (c_trace if both_traced else c_wall) / REPEATS
        g_ms = (g_trace if both_traced else g_wall) / REPEATS

        t_peak_ms = flops / peak_flops * 1e3
        t_bw_ms = bytes_min / peak_bytes * 1e3
        ratio = c_ms / g_ms if g_ms > 0 else float("inf")
        bound = ("matmul_equivalent" if ratio <= 1.15 else
                 "bandwidth" if c_ms <= 1.25 * t_bw_ms else
                 "headroom")
        rows.append({
            "label": label, "count": count,
            "conv_ms": round(c_ms, 4), "gemm_twin_ms": round(g_ms, 4),
            "conv_vs_twin": round(ratio, 3),
            "tflops_conv": round(flops / (c_ms * 1e-3) / 1e12, 1),
            "tflops_twin": round(flops / (g_ms * 1e-3) / 1e12, 1),
            "t_nominal_peak_ms": round(t_peak_ms, 4),
            "t_bandwidth_ms": round(t_bw_ms, 4),
            "bound": bound,
            "timing_source": ("profiler_trace" if both_traced
                              else "wall_clock_uncorroborated"),
        })
        conv_total_ms += count * c_ms
        twin_total_ms += count * g_ms
        print(f"{label:>18s}: conv {c_ms:7.3f} ms vs twin {g_ms:7.3f} ms "
              f"({rows[-1]['tflops_conv']:6.1f} vs "
              f"{rows[-1]['tflops_twin']:6.1f} TF/s) -> {bound}",
            file=sys.stderr)

    fwd_flops = sum(2.0 * B * (H // s) * (W // s) * k * k * Cin * Cout * c
                    for (_, H, W, Cin, Cout, k, s, c) in shapes)
    out = {
        "metric": "resnet50_conv_roofline",
        "batch": B,
        "rows": rows,
        "fwd_conv_ms_measured": round(conv_total_ms, 2),
        "fwd_conv_ms_twin_bound": round(twin_total_ms, 2),
        "fwd_conv_tflops_measured": round(
            fwd_flops / (conv_total_ms * 1e-3) / 1e12, 1),
        "fwd_conv_tflops_twin_bound": round(
            fwd_flops / (twin_total_ms * 1e-3) / 1e12, 1),
        "attainable_mfu_vs_nominal": round(
            fwd_flops / (twin_total_ms * 1e-3) / peak_flops, 4),
        "note": ("twin = im2col GEMM with identical MAC count; its rate is "
                 "the empirically attainable per-shape ceiling.  Forward "
                 "convs only; backward convs are GEMM-twins of the same "
                 "M/K/N up to transposition."),
    }
    print(json.dumps(out))


if __name__ == "__main__":
    main()
