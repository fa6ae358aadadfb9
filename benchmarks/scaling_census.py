"""Pod-scale wire census: gossip vs allreduce, from compiled programs.

No multi-chip hardware is needed for the SCALING story: lower the real
gossip step on abstract meshes of growing size, read the collective-permute
op count from the StableHLO, and put it next to the analytic byte model for
each strategy (ring allreduce uses the standard cost model throughout).  This is the
reference's core claim made concrete (neighbor_allreduce scales better at
high node counts because its per-step wire cost and dependency depth do
not grow with the mesh):

- ring allreduce moves ``2P(n-1)/n`` bytes/chip in ``2(n-1)`` serial hops
  — DEPTH grows linearly with the mesh (and any straggler stalls all);
- static exp2 gossip moves ``P*log2(n)`` bytes/chip in ``log2(n)`` hops;
- one-peer dynamic gossip moves ``P`` bytes/chip in ONE hop, step after
  step, independent of mesh size.

Run:  python benchmarks/scaling_census.py [--param-mib 97.6]
Prints one JSON line per mesh size (plus a table to stderr).
"""

import argparse
import json
import os
import sys

sys.path.insert(0, os.path.join(os.path.dirname(__file__), ".."))

import jax

jax.config.update("jax_platforms", "cpu")  # compile-only analysis: never
# claim an accelerator

import jax.numpy as jnp
from jax.sharding import AbstractMesh, PartitionSpec as P

from bluefog_tpu.ops import collectives as C
from bluefog_tpu.parallel.api import shard_map
from bluefog_tpu.topology import ExponentialTwoGraph
from bluefog_tpu.topology.schedule import build_schedule


def census(n: int, param_bytes: int):
    mesh = AbstractMesh((n,), ("bf",))
    leaf = jax.ShapeDtypeStruct((n, param_bytes // 4), jnp.float32)
    sched = build_schedule(ExponentialTwoGraph(n))

    fn = jax.jit(shard_map(
        lambda v: C.neighbor_allreduce(v, sched, "bf"),
        mesh=mesh, in_specs=(P("bf"),), out_specs=P("bf"), check_vma=False))
    hlo = fn.lower(leaf).as_text()
    k = hlo.count("collective_permute") or hlo.count("collective-permute")
    # lowering text is StableHLO; count ops there, model bytes analytically
    # (each slot ships the full payload once)
    num_slots = sched.num_slots
    return {
        "mesh": n,
        "param_mib": round(param_bytes / 2**20, 1),
        "exp2_gossip": {
            "hops": num_slots,
            "bytes_per_chip": num_slots * param_bytes,
            "ops_in_program": k,
        },
        "one_peer_gossip": {"hops": 1, "bytes_per_chip": param_bytes},
        "ring_allreduce_model": {
            "hops": 2 * (n - 1),
            "bytes_per_chip": int(2 * param_bytes * (n - 1) / n),
        },
    }


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--param-mib", type=float, default=97.66,
                    help="parameter payload per chip (default ResNet-50 f32)")
    ap.add_argument("--sizes", type=int, nargs="*",
                    default=[8, 16, 32, 64, 128])
    args = ap.parse_args()
    pbytes = int(args.param_mib * 2**20)

    print(f"{'n':>4} {'exp2 hops':>10} {'exp2 MiB':>9} {'1peer MiB':>10} "
          f"{'ring hops':>10} {'ring MiB':>9}", file=sys.stderr)
    for n in args.sizes:
        row = census(n, pbytes)
        g, o, r = (row["exp2_gossip"], row["one_peer_gossip"],
                   row["ring_allreduce_model"])
        print(f"{n:>4} {g['hops']:>10} {g['bytes_per_chip']/2**20:>9.0f} "
              f"{o['bytes_per_chip']/2**20:>10.0f} {r['hops']:>10} "
              f"{r['bytes_per_chip']/2**20:>9.0f}", file=sys.stderr)
        print(json.dumps(row), flush=True)


if __name__ == "__main__":
    main()
