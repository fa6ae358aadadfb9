"""Ring-attention micro-benchmark: causal block skipping vs full work.

The causal ring dispatches each arriving KV block through a ``lax.switch``
(skip / unmasked / diagonal-masked) so strictly-future blocks execute nothing
— at n shards that is ~(n-1)/2n of the block work skipped (≈ half for large
n).  This script measures it: wall-clock per ring-attention forward, causal
vs non-causal, on whatever devices are visible (8-virtual-CPU mesh or a TPU
slice).

Run (CPU mesh):
  JAX_PLATFORMS=cpu XLA_FLAGS=--xla_force_host_platform_device_count=8 \
  python benchmarks/ring_attention_bench.py

Prints one JSON line; `causal_speedup` is the headline (→ ~2x as n grows).
"""

import argparse
import functools
import json
import os
import sys
import time

sys.path.insert(0, os.path.join(os.path.dirname(__file__), ".."))

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import Mesh, PartitionSpec as P

from bluefog_tpu.ops.ring_attention import ring_attention
from bluefog_tpu.parallel.api import shard_map


def bench_one(mesh, causal, args, layout="contiguous"):
    n = len(mesh.devices.flat)
    fn = jax.jit(shard_map(
        functools.partial(ring_attention, axis_name="sp", causal=causal,
                          kv_tile=args.kv_tile, layout=layout),
        mesh=mesh, in_specs=(P(None, "sp"),) * 3, out_specs=P(None, "sp"),
        check_vma=False,
    ))
    ks = jax.random.split(jax.random.PRNGKey(0), 3)
    shape = (args.batch, n * args.t_local, args.heads, args.head_dim)
    q, k, v = (jax.random.normal(kk, shape, jnp.float32) for kk in ks)
    fn(q, k, v).block_until_ready()  # compile
    t0 = time.perf_counter()
    for _ in range(args.steps):
        out = fn(q, k, v)
    out.block_until_ready()
    return (time.perf_counter() - t0) / args.steps


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--t-local", type=int, default=512)
    ap.add_argument("--batch", type=int, default=1)
    ap.add_argument("--heads", type=int, default=4)
    ap.add_argument("--head-dim", type=int, default=64)
    ap.add_argument("--kv-tile", type=int, default=512)
    ap.add_argument("--steps", type=int, default=10)
    args = ap.parse_args()

    devs = jax.devices()
    mesh = Mesh(np.array(devs), ("sp",))
    n = len(devs)

    dt_full = bench_one(mesh, False, args)
    dt_causal = bench_one(mesh, True, args)
    # zigzag: the load-balanced causal layout — every rank folds exactly 2
    # half-chunks/step, so on a lock-stepped slice the FLOP saving is
    # wall-clock; input layout conversion is outside the timed region (it is
    # a one-time data layout choice, not per-step work)
    dt_zigzag = bench_one(mesh, True, args, layout="zigzag")
    print(json.dumps({
        "metric": "ring_attention_step_ms",
        "n_shards": n,
        "t_global": n * args.t_local,
        "full_ms": round(dt_full * 1e3, 2),
        "causal_ms": round(dt_causal * 1e3, 2),
        "causal_zigzag_ms": round(dt_zigzag * 1e3, 2),
        "causal_speedup": round(dt_full / dt_causal, 3),
        "zigzag_speedup": round(dt_full / dt_zigzag, 3),
        "expected_flop_ratio": round(2 * n / (n + 1), 3),
    }))


if __name__ == "__main__":
    main()
