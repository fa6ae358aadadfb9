"""Flash-attention micro-benchmark on a single chip: Pallas fused kernel
vs the dense softmax path, over sequence length.

This is the single-chip half of the long-context story (the multi-chip half
— ring/zigzag sequence parallelism — is `ring_attention_bench.py`, which
needs a mesh).  It measures the kernel the model layer's ``backend='auto'``
opts into (``ops/ring_attention.py::local_attention``): forward + backward
through a jitted loss, bf16, causal, shapes eligible for the fused kernel.

Timing discipline: at microbenchmark scale dispatch overhead is a large
share of a host wall clock, so each (T, backend) variant captures its own
``jax.profiler`` trace and the per-step time is the device's own op-time
total divided by the traced step count.  Wall clock is reported alongside
with a ``wall_plausible`` flag (wall >= 0.9 x trace).

Run (real chip):      python benchmarks/flash_attention_bench.py
Run (CPU, dense only): JAX_PLATFORMS=cpu \
                       python benchmarks/flash_attention_bench.py --dense-only

Prints one JSON line: per-seq-len step times and ``flash_speedup``.
"""

import argparse
import json
import os
import sys
import time

sys.path.insert(0, os.path.join(os.path.dirname(__file__), ".."))

import jax
import jax.numpy as jnp

from bluefog_tpu.ops.ring_attention import local_attention


from benchmarks._trace_util import timed_trace as step_time  # noqa: E402


def make_step(backend, causal=True, flash_block=None):
    @jax.jit
    def step(q, k, v):
        def loss(q, k, v):
            o = local_attention(q, k, v, causal=causal, backend=backend,
                                flash_block=flash_block)
            return jnp.sum(o.astype(jnp.float32) ** 2)

        l, grads = jax.value_and_grad(loss, argnums=(0, 1, 2))(q, k, v)
        return l, grads

    return step


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--batch", type=int, default=4)
    ap.add_argument("--heads", type=int, default=8)
    ap.add_argument("--head-dim", type=int, default=128)
    ap.add_argument("--seq-lens", type=int, nargs="+",
                    default=[1024, 2048, 4096, 8192])
    ap.add_argument("--steps", type=int, default=10)
    ap.add_argument("--dense-only", action="store_true")
    ap.add_argument("--tune", action="store_true",
                    help="sweep flash kernel tile edges (128..1024) per seq "
                         "len instead of the dense/flash comparison")
    args = ap.parse_args()

    if args.tune:
        rows = []
        for t in args.seq_lens:
            shape = (args.batch, t, args.heads, args.head_dim)
            ks = jax.random.split(jax.random.PRNGKey(0), 3)
            q, k, v = (jax.random.normal(kk, shape, jnp.bfloat16)
                       for kk in ks)
            row = {"seq_len": t}
            for blk in (128, 256, 512, 1024):
                if blk > t:
                    continue
                try:
                    wall_ms, trace_ms = step_time(
                        make_step("flash", flash_block=blk), (q, k, v),
                        args.steps)
                    row[f"block{blk}_ms"] = round(trace_ms or wall_ms, 3)
                    if trace_ms is None:
                        # same contract as the main path: say so when no
                        # device trace stands behind the wall clock
                        row[f"block{blk}_timing_source"] = (
                            "wall_clock_uncorroborated")
                except Exception as e:  # noqa: BLE001
                    row[f"block{blk}_error"] = (
                        f"{type(e).__name__}: {str(e)[:100]}")
            rows.append(row)
            print(f"tune: T={t}: {row}", file=sys.stderr)
        print(json.dumps({"metric": "flash_block_tune", "rows": rows}))
        return

    dev = jax.devices()[0]
    rows = []
    for t in args.seq_lens:
        shape = (args.batch, t, args.heads, args.head_dim)
        ks = jax.random.split(jax.random.PRNGKey(0), 3)
        q, k, v = (jax.random.normal(kk, shape, jnp.bfloat16) for kk in ks)
        row = {"seq_len": t}
        # dense first: at long T it OOMs before flash does — record that
        # honestly instead of dying
        for name, backend in [("dense", "dense")] + (
                [] if args.dense_only else [("flash", "flash")]):
            try:
                wall_ms, trace_ms = step_time(
                    make_step(backend), (q, k, v), args.steps)
            except Exception as e:  # noqa: BLE001 — expected O(T^2) OOM path
                row[f"{name}_error"] = f"{type(e).__name__}: {str(e)[:120]}"
                continue
            row[f"{name}_wall_ms"] = round(wall_ms, 3)
            if trace_ms:
                # a step cannot end before the device finished its ops
                row[f"{name}_ms"] = round(trace_ms, 3)
                row[f"{name}_wall_plausible"] = wall_ms >= 0.9 * trace_ms
            else:
                row[f"{name}_ms"] = round(wall_ms, 3)
                row[f"{name}_timing_source"] = "wall_clock_uncorroborated"
        if "dense_ms" in row and "flash_ms" in row and row["flash_ms"] > 0:
            row["flash_speedup"] = round(row["dense_ms"] / row["flash_ms"], 3)
        rows.append(row)
        print(f"bench: T={t}: {row}", file=sys.stderr)

    speedups = [r["flash_speedup"] for r in rows if "flash_speedup" in r]
    out = {
        "metric": "flash_attention_fwd_bwd",
        "device_kind": getattr(dev, "device_kind", str(dev)),
        "batch": args.batch, "heads": args.heads, "head_dim": args.head_dim,
        "causal": True, "dtype": "bfloat16",
        "rows": rows,
        "flash_speedup_max": max(speedups) if speedups else None,
    }
    print(json.dumps(out))


if __name__ == "__main__":
    main()
