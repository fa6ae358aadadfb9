"""Average consensus via gossip — the reference's
``examples/pytorch_average_consensus.py`` (upstream-relative), TPU-native.

Each rank starts with a random vector; repeated ``neighbor_allreduce`` steps
drive every rank to the global average.  Demonstrates the stacked-array API
and topology switching.

Run (any host, no launcher needed — SPMD replaces mpirun/bfrun):

    python examples/average_consensus.py [--size 8] [--steps 50] \
        [--topology exp2|ring|grid|star|full]

On a CPU-only host, set
``JAX_PLATFORMS=cpu XLA_FLAGS=--xla_force_host_platform_device_count=8``
to simulate an 8-chip slice.
"""

import argparse
import os
import sys

sys.path.insert(0, os.path.join(os.path.dirname(__file__), ".."))  # repo-root run

import jax
import jax.numpy as jnp
import numpy as np

import bluefog_tpu as bf
from bluefog_tpu.topology import (
    ExponentialTwoGraph,
    FullyConnectedGraph,
    MeshGrid2DGraph,
    RingGraph,
    StarGraph,
)

TOPOLOGIES = {
    "exp2": ExponentialTwoGraph,
    "ring": RingGraph,
    "grid": MeshGrid2DGraph,
    "star": StarGraph,
    "full": FullyConnectedGraph,
}


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--size", type=int, default=None, help="ranks (default: all devices)")
    ap.add_argument("--steps", type=int, default=50)
    ap.add_argument("--dim", type=int, default=1000)
    ap.add_argument("--topology", choices=sorted(TOPOLOGIES), default="exp2")
    args = ap.parse_args()
    bf.configure_compile_cache()

    n = args.size or len(jax.devices())
    bf.init(topology=TOPOLOGIES[args.topology](n), size=n)
    print(f"ranks={bf.size()} topology={bf.load_topology().name}")

    key = jax.random.PRNGKey(0)
    x = jax.random.normal(key, (n, args.dim))  # stacked: row r = rank r's vector
    x = bf.rank_shard(x)
    target = np.asarray(x).mean(axis=0)

    for step in range(args.steps):
        x = bf.neighbor_allreduce(x)
        if step % 10 == 0 or step == args.steps - 1:
            err = float(np.max(np.abs(np.asarray(x) - target)))
            print(f"step {step:4d}  max|x - avg| = {err:.3e}")

    err = float(np.max(np.abs(np.asarray(x) - target)))
    print(f"final consensus error: {err:.3e}")
    assert err < 1e-3, "consensus failed to converge"
    print("OK")


if __name__ == "__main__":
    main()
