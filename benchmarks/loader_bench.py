"""Input-pipeline overlap benchmark: does prefetch hide host data work?

Builds real on-disk TFRecord shards, then runs a loader+compute loop twice —
``prefetch=0`` (host gather/decode serializes with device compute) and
``prefetch=2`` (a background thread keeps batches ahead) — and reports the
overlap factor.  The compute is a jitted matmul loop sized to take roughly as
long as one batch's host work, the worst case for a non-overlapped pipeline.

``--io-ms`` adds per-batch source latency (sleep), modelling a disk/network-
bound source.  On a CPU-only host that is also the *honest* configuration:
decode and "device" compute share the same cores, so pure-CPU overlap cannot
exceed 1.0x — the prefetch win is hiding IO latency (and, on a real TPU,
hiding all host work under device compute).

Run (8-virtual-device CPU mesh):
  JAX_PLATFORMS=cpu XLA_FLAGS=--xla_force_host_platform_device_count=8 \
  python benchmarks/loader_bench.py
Prints one JSON line.
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
import numpy as np

import bluefog_tpu as bf
from bluefog_tpu.data import (
    DistributedLoader,
    TFRecordSource,
    write_image_classification_shards,
)


def run_epochs(loader, compute, epochs):
    # Block on each step's result, as a real train loop effectively does
    # (the next step depends on donated params) — otherwise jax async
    # dispatch pipelines the compute regardless of the loader and the
    # measurement only sees the source.
    t0 = time.perf_counter()
    for e in range(epochs):
        for imgs, labels in loader.epoch(e):
            jax.block_until_ready(compute(imgs))
    return time.perf_counter() - t0


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--examples", type=int, default=512)
    ap.add_argument("--hw", type=int, default=48)
    ap.add_argument("--batch", type=int, default=8)
    ap.add_argument("--epochs", type=int, default=3)
    ap.add_argument("--matmul", type=int, default=384,
                    help="device work per step (matmul side)")
    ap.add_argument("--io-ms", type=float, default=10.0,
                    help="simulated per-batch source IO latency")
    args = ap.parse_args()

    n = len(jax.devices())
    bf.init()

    with tempfile.TemporaryDirectory() as d:
        rng = np.random.default_rng(0)
        images = rng.integers(0, 256, size=(args.examples, args.hw, args.hw, 3),
                              dtype=np.uint8)
        labels = rng.integers(0, 10, size=args.examples).astype(np.int64)
        write_image_classification_shards(d, images, labels, shard_size=128,
                                          prefix="train")
        src = TFRecordSource(os.path.join(d, "train-*.tfrecord"))

        if args.io_ms > 0:
            class IOBoundSource:
                """Real source + per-gather IO latency (disk/network model)."""

                def __init__(self, inner, delay_s):
                    self.inner, self.delay = inner, delay_s

                def __len__(self):
                    return len(self.inner)

                def __getitem__(self, idx):
                    time.sleep(self.delay)
                    return self.inner[idx]

            src = IOBoundSource(src, args.io_ms / 1e3)

        m = args.matmul
        w = jnp.ones((m, m), jnp.float32)

        @jax.jit
        def compute(imgs):
            z = w
            for _ in range(8):
                z = jnp.tanh(z @ w)
            return z.sum() + imgs.sum()

        def loader(prefetch):
            return DistributedLoader(src, args.batch, prefetch=prefetch)

        # warm caches/compiles
        run_epochs(loader(0), compute, 1)
        t_serial = run_epochs(loader(0), compute, args.epochs)
        t_overlap = run_epochs(loader(2), compute, args.epochs)

    print(json.dumps({
        "metric": "loader_prefetch_overlap",
        "ranks": n,
        "steps": args.epochs * (args.examples // (n * args.batch)),
        "serial_s": round(t_serial, 3),
        "prefetch2_s": round(t_overlap, 3),
        "overlap_speedup": round(t_serial / t_overlap, 3),
    }))


if __name__ == "__main__":
    main()
