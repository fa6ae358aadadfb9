"""CHOCO-SGD: decentralized training over a 10x-compressed wire.

Beyond-reference example (upstream has no communication compression):
least-squares regression with per-rank data on a ring, gossiping only a
compressed innovation each round (CHOCO-SGD, Koloskova et al., ICML 2019 —
see ops/compression.py).  Self-asserting: every rank must reach the SHARED
least-squares optimum, which plain compressed gossip cannot do (compression
noise accumulates; CHOCO's mirror copies cancel it).

Run (8-rank CPU mesh):
  JAX_PLATFORMS=cpu XLA_FLAGS=--xla_force_host_platform_device_count=8 \
  python examples/choco_sgd.py
"""

import argparse
import functools
import os
import sys

sys.path.insert(0, os.path.join(os.path.dirname(__file__), ".."))

import jax
import jax.numpy as jnp
import numpy as np
import optax
from jax.sharding import Mesh, PartitionSpec as P

from bluefog_tpu.ops import compression as CP
from bluefog_tpu.optim import DistributedChocoSGDOptimizer
from bluefog_tpu.parallel.api import shard_map
from bluefog_tpu.topology.graphs import RingGraph
from bluefog_tpu.topology.schedule import build_schedule


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--ranks", type=int, default=8)
    ap.add_argument("--dim", type=int, default=8)
    ap.add_argument("--rows", type=int, default=32, help="data rows per rank")
    ap.add_argument("--steps", type=int, default=1500)
    ap.add_argument("--ratio", type=float, default=0.1,
                    help="kept fraction of wire bytes (0.1 = 10x compression)")
    ap.add_argument("--compressor", choices=["random_block_k", "top_k"],
                    default="random_block_k")
    ap.add_argument("--lr", type=float, default=0.05)
    args = ap.parse_args()

    n = args.ranks
    if len(jax.devices()) < n:
        raise SystemExit(f"need {n} devices, have {len(jax.devices())} "
                         "(set XLA_FLAGS=--xla_force_host_platform_device_count)")
    mesh = Mesh(np.array(jax.devices()[:n]), ("g",))
    sched = build_schedule(RingGraph(n))
    comp = getattr(CP, args.compressor)(args.ratio)

    rng = np.random.default_rng(0)
    A = jnp.asarray(rng.normal(size=(n, args.rows, args.dim)))
    w_star = jnp.asarray(rng.normal(size=(args.dim,)))
    b = jnp.einsum("nij,j->ni", A, w_star)

    opt = DistributedChocoSGDOptimizer(
        optax.sgd(args.lr), sched, "g", compressor=comp)  # gamma = delta

    @jax.jit
    @functools.partial(shard_map, mesh=mesh, in_specs=(P("g"), P("g")),
                       out_specs=P("g"), check_vma=False)
    def train(A_blk, b_blk):
        Ai, bi = A_blk[0], b_blk[0]
        params = jnp.zeros((args.dim,))
        state = opt.init(params)

        def body(carry, _):
            params, state = carry
            g = jax.grad(lambda w: jnp.mean((Ai @ w - bi) ** 2))(params)
            upd, state = opt.update(g, state, params)
            return (optax.apply_updates(params, upd), state), None

        (params, _), _ = jax.lax.scan(body, (params, state), None,
                                      length=args.steps)
        return params[None]

    out = np.asarray(train(A, b))
    err = np.abs(out - np.asarray(w_star)).max()
    spread = np.abs(out - out.mean(axis=0)).max()
    wire = comp.wire_ratio(np.zeros(args.dim, np.float32))
    print(f"ranks={n} compressor={comp.name} ratio={args.ratio} "
          f"(wire = {wire:.0%} of dense bytes)")
    print(f"max|w_i - w*|      = {err:.2e}")
    print(f"max rank spread    = {spread:.2e}")
    assert err < 0.05, f"did not reach the shared optimum: {err}"
    assert spread < 0.01, f"ranks did not agree: {spread}"
    print("OK")


if __name__ == "__main__":
    main()
