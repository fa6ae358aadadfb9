"""Gossip transport microbenchmark: Pallas RDMA kernels vs XLA ppermute.

On a real multi-chip TPU slice, times one fused-RDMA gossip step vs the XLA
lowering across payload sizes.  Per size it reports the gossip chunk plan
(auto always picks pallas there, splitting oversized payloads into
VMEM-cap-sized kernels) and where the non-chunkable WINDOW transport's
size cutoff flips its routing.  On a single chip only the XLA path
is timed (the RDMA kernel needs a peer); on a CPU mesh (no real kernel execution possible) it instead validates the
kernel under TPU-interpret emulation against the XLA path and times only the
XLA side, saying so in the output.

Run:  python benchmarks/pallas_gossip_bench.py [--sizes-kib 64 1024 4096]
Prints one JSON line.
"""

import argparse
import json
import os
import sys
import time

sys.path.insert(0, os.path.join(os.path.dirname(__file__), ".."))

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import Mesh, PartitionSpec as P

from bluefog_tpu.ops import collectives as C
from bluefog_tpu.ops import pallas_gossip
from bluefog_tpu.parallel.api import shard_map
from bluefog_tpu.topology import ExponentialTwoGraph, RingGraph
from bluefog_tpu.topology.schedule import build_schedule


def _time(fn, x, steps):
    fn(x).block_until_ready()  # compile
    t0 = time.perf_counter()
    out = x
    for _ in range(steps):
        out = fn(out)
    out.block_until_ready()
    return (time.perf_counter() - t0) / steps * 1e3  # ms


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--sizes-kib", type=int, nargs="+",
                    default=[64, 512, 1024, 4096, 16384])
    ap.add_argument("--steps", type=int, default=20)
    args = ap.parse_args()

    devs = jax.devices()
    n = len(devs)
    on_tpu = devs[0].platform == "tpu"
    mesh = Mesh(np.array(devs), ("bf",))
    if n > 1:
        sched = build_schedule(ExponentialTwoGraph(n))
    else:
        # Single chip: the RDMA kernel needs a peer, so it is only timed on
        # real multi-chip slices; on one chip we time the XLA path.
        from bluefog_tpu.topology.graphs import Topology

        sched = build_schedule(Topology(weights=np.ones((1, 1)),
                                        name="SelfLoop"))

    rows = []
    auto_choice = {}
    for kib in args.sizes_kib:
        elems = kib * 1024 // 4
        x = jnp.ones((n, elems), jnp.float32)
        x = jax.device_put(
            x, jax.sharding.NamedSharding(mesh, P("bf")))

        xla_fn = jax.jit(shard_map(
            lambda v: C.neighbor_allreduce(v, sched, "bf", backend="xla"),
            mesh=mesh, in_specs=(P("bf"),), out_specs=P("bf"),
            check_vma=False))
        row = {"kib": kib, "xla_ms": round(_time(xla_fn, x, args.steps), 3)}
        probe = jnp.zeros((elems,), jnp.float32)
        auto_choice[kib] = {
            "gossip": pallas_gossip.auto_gossip_backend(sched, probe),
            # chunk plan is undefined under a non-positive cap (the
            # "never use the kernels" override; leaf_chunk_count raises)
            "gossip_chunks": (pallas_gossip.leaf_chunk_count(probe)
                              if pallas_gossip.auto_max_bytes() > 0
                              else None),
            "window": pallas_gossip.auto_gossip_backend(
                sched, probe, chunkable=False),
        }

        if on_tpu and n > 1 and pallas_gossip.circulant_shifts(sched):
            pl_fn = jax.jit(shard_map(
                lambda v: C.neighbor_allreduce(v, sched, "bf",
                                               backend="pallas"),
                mesh=mesh, in_specs=(P("bf"),), out_specs=P("bf"),
                check_vma=False))
            row["pallas_ms"] = round(_time(pl_fn, x, args.steps), 3)
            row["pallas_speedup"] = round(row["xla_ms"] / row["pallas_ms"], 3)
        rows.append(row)

    interpret_parity = None
    if n > 1 and not on_tpu:
        # no hardware: prove the kernel's semantics instead (interpret mode)
        elems = 512
        xs = jnp.arange(n * elems, dtype=jnp.float32).reshape(n, elems)
        xs = jax.device_put(xs, jax.sharding.NamedSharding(mesh, P("bf")))
        want = jax.jit(shard_map(
            lambda v: C.neighbor_allreduce(v, sched, "bf", backend="xla"),
            mesh=mesh, in_specs=(P("bf"),), out_specs=P("bf"),
            check_vma=False))(xs)
        got = jax.jit(shard_map(
            lambda v: pallas_gossip.neighbor_allreduce_pallas(
                v[0], sched, "bf", interpret=True)[None],
            mesh=mesh, in_specs=(P("bf"),), out_specs=P("bf"),
            check_vma=False))(xs)
        interpret_parity = bool(np.allclose(np.asarray(got), np.asarray(want),
                                            rtol=1e-6))

    print(json.dumps({
        "metric": "pallas_gossip_vs_xla_ms",
        "platform": devs[0].platform,
        "n_devices": n,
        "rows": rows,
        "auto_backend_by_size": auto_choice,
        "interpret_parity_vs_xla": interpret_parity,
        "note": (None if on_tpu else
                 "no TPU attached: pallas timings require hardware; "
                 "interpret-mode parity validated instead"),
    }))


if __name__ == "__main__":
    main()
