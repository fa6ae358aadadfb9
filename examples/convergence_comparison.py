"""Convergence parity: decentralized gossip vs centralized allreduce.

The reference's core claim (Bluefog paper, arXiv:2111.04287; BASELINE.md
north star) is that decentralized SGD over a well-chosen topology matches
centralized allreduce SGD in final accuracy while communicating less.  This
script reproduces that comparison end-to-end on the simulated slice: the same
LeNet, same per-rank data shards, same seeds — trained under each
communication flavor — then evaluated on one shared held-out set.

Expected shape of the results (and asserted): exp2/ring gossip land within a
small gap of allreduce, while no-communication ranks (each stuck on its own
shard) trail behind and disagree with each other.

Run:  JAX_PLATFORMS=cpu XLA_FLAGS=--xla_force_host_platform_device_count=8 \
      python examples/convergence_comparison.py
"""

import argparse
import os
import sys

sys.path.insert(0, os.path.join(os.path.dirname(__file__), ".."))  # repo-root run

import jax
import jax.numpy as jnp
import numpy as np
import optax
from jax.sharding import PartitionSpec as P

import bluefog_tpu as bf
from bluefog_tpu.models import LeNet5
from bluefog_tpu.optim import CommunicationType, decentralized_optimizer
from bluefog_tpu.parallel.api import shard_map
from bluefog_tpu.topology import ExponentialTwoGraph, RingGraph


def make_dataset(n_per_rank, n_ranks, key, noise=0.6):
    """Prototype MNIST stand-in, heterogeneous shards: each rank's label
    distribution is skewed (decentralized training's hard case).  Returns
    ``(imgs, labels, protos)`` — protos so callers build eval sets from the
    same distribution."""
    kp, kx, ky = jax.random.split(key, 3)
    protos = jax.random.normal(kp, (10, 28, 28, 1)) * 0.8
    # rank r over-samples classes around r: sharpness controls heterogeneity
    logits = -0.5 * ((jnp.arange(10)[None, :] -
                      jnp.linspace(0, 9, n_ranks)[:, None]) ** 2)
    labels = jax.vmap(
        lambda k, lg: jax.random.categorical(k, lg, shape=(n_per_rank,))
    )(jax.random.split(ky, n_ranks), logits)
    imgs = protos[labels] + noise * jax.random.normal(
        kx, (n_ranks, n_per_rank, 28, 28, 1))
    return imgs, labels.astype(jnp.int32), protos


def train_flavor(comm_type, topology, ctx, data, eval_data, args):
    model = LeNet5()
    opt = decentralized_optimizer(
        optax.sgd(args.lr, momentum=0.9), topology, ctx.axis_name,
        communication_type=comm_type)

    init = model.init(jax.random.PRNGKey(0), jnp.zeros((1, 28, 28, 1)))
    params = bf.rank_shard(bf.rank_stack(init))
    imgs, labels = data

    def init_opt(p_blk):
        p = jax.tree_util.tree_map(lambda t: t[0], p_blk)
        return jax.tree_util.tree_map(lambda t: jnp.asarray(t)[None],
                                      opt.init(p))

    opt_state = jax.jit(shard_map(
        init_opt, mesh=ctx.mesh, in_specs=(P(ctx.axis_name),),
        out_specs=P(ctx.axis_name), check_vma=False))(params)

    def epoch_fn(p_blk, opt_blk, x_blk, y_blk, perm):
        p, st = jax.tree_util.tree_map(lambda t: t[0], (p_blk, opt_blk))
        x, y = x_blk[0][perm], y_blk[0][perm]
        nb = x.shape[0] // args.batch
        if nb < 1:
            raise ValueError(
                f"--batch {args.batch} > examples per rank {x.shape[0]}")

        def body(carry, i):
            p, st = carry
            xb = jax.lax.dynamic_slice_in_dim(x, i * args.batch, args.batch)
            yb = jax.lax.dynamic_slice_in_dim(y, i * args.batch, args.batch)

            def loss_fn(p):
                logits = model.apply(p, xb)
                return optax.softmax_cross_entropy_with_integer_labels(
                    logits, yb).mean()

            loss, g = jax.value_and_grad(loss_fn)(p)
            upd, st = opt.update(g, st, p)
            return (optax.apply_updates(p, upd), st), loss

        (p, st), losses = jax.lax.scan(body, (p, st), jnp.arange(nb))
        out = jax.tree_util.tree_map(lambda t: t[None], (p, st))
        return out + (losses.mean()[None],)

    step = jax.jit(shard_map(
        epoch_fn, mesh=ctx.mesh, in_specs=(P(ctx.axis_name),) * 4 + (P(),),
        out_specs=(P(ctx.axis_name),) * 3, check_vma=False,
    ), donate_argnums=(0, 1))

    loss = None
    for e in range(args.epochs):
        perm = jax.random.permutation(jax.random.fold_in(
            jax.random.PRNGKey(13), e), imgs.shape[1])
        params, opt_state, loss = step(params, opt_state, imgs, labels, perm)

    # evaluate every rank's model on the SHARED eval set
    ex, ey = eval_data

    def eval_fn(p_blk):
        p = jax.tree_util.tree_map(lambda t: t[0], p_blk)
        logits = model.apply(p, ex)
        return ((jnp.argmax(logits, -1) == ey).mean())[None]

    accs = np.asarray(jax.jit(shard_map(
        eval_fn, mesh=ctx.mesh, in_specs=(P(ctx.axis_name),),
        out_specs=P(ctx.axis_name), check_vma=False))(params))
    return float(np.mean(accs)), float(np.min(accs)), float(np.max(accs)), \
        float(np.mean(loss))


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--epochs", type=int, default=6)
    ap.add_argument("--batch", type=int, default=32)
    ap.add_argument("--lr", type=float, default=0.02)
    ap.add_argument("--n-per-rank", type=int, default=512)
    args = ap.parse_args()
    bf.configure_compile_cache()

    n = len(jax.devices())
    bf.init(topology=ExponentialTwoGraph(n))
    ctx = bf.get_context()

    imgs, labels, protos = make_dataset(args.n_per_rank, n,
                                        jax.random.PRNGKey(1))
    data = (bf.rank_shard(imgs), bf.rank_shard(labels))  # place once
    # shared balanced eval set drawn from the SAME prototypes
    ey = jnp.tile(jnp.arange(10), 40).astype(jnp.int32)
    ex = protos[ey] + 0.6 * jax.random.normal(
        jax.random.PRNGKey(99), (ey.shape[0], 28, 28, 1))

    flavors = [
        ("allreduce", CommunicationType.allreduce, None),
        ("exp2 gossip", CommunicationType.neighbor_allreduce,
         ExponentialTwoGraph(n)),
        ("ring gossip", CommunicationType.neighbor_allreduce, RingGraph(n)),
        ("no comm", CommunicationType.empty, None),
    ]
    print(f"ranks={n} epochs={args.epochs} per-rank={args.n_per_rank} "
          f"(heterogeneous shards)\n")
    print(f"{'flavor':<14} {'eval acc':>9} {'min rank':>9} {'max rank':>9} "
          f"{'train loss':>11}")
    results = {}
    for name, ct, topo in flavors:
        acc, lo, hi, loss = train_flavor(ct, topo, ctx, data, (ex, ey), args)
        results[name] = acc
        print(f"{name:<14} {acc:>9.4f} {lo:>9.4f} {hi:>9.4f} {loss:>11.4f}")

    gap_exp2 = results["allreduce"] - results["exp2 gossip"]
    gap_ring = results["allreduce"] - results["ring gossip"]
    print(f"\ngossip-vs-allreduce gap: exp2 {gap_exp2:+.4f}, "
          f"ring {gap_ring:+.4f}")
    if gap_exp2 > 0.05 or gap_ring > 0.08:
        print("FAIL: gossip trails allreduce beyond tolerance "
              "(short run? try more --epochs)")
        sys.exit(1)
    print("OK — decentralized matches centralized (reference's claim)")


if __name__ == "__main__":
    main()
