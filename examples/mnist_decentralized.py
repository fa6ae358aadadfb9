"""Decentralized LeNet training — the reference's ``examples/pytorch_mnist.py``
(BASELINE.json config[0]: LeNet on ring topology, neighbor_allreduce),
TPU-native.

Each rank holds its own LeNet replica and a disjoint data shard; every step
runs local forward/backward and gossips parameters with ring neighbors via
``DistributedNeighborAllreduceOptimizer``.  The whole per-rank step (compute +
gossip) is one jitted ``shard_map`` program, so XLA overlaps the ppermute
traffic with backprop — the TPU equivalent of the reference's
hook-based comm/compute overlap (SURVEY.md §3.3).

This environment has no network, so MNIST is synthesized: 10 fixed random
class prototypes + noise.  Real MNIST drops in by replacing ``make_dataset``.

Run:  JAX_PLATFORMS=cpu XLA_FLAGS=--xla_force_host_platform_device_count=8 \
      python examples/mnist_decentralized.py
"""

import argparse
import os
import sys

sys.path.insert(0, os.path.join(os.path.dirname(__file__), ".."))  # repo-root run

import jax
import jax.numpy as jnp
import numpy as np
import optax
from jax import lax
from jax.sharding import PartitionSpec as P

import bluefog_tpu as bf
from bluefog_tpu.metrics import health as bf_health
from bluefog_tpu.models import LeNet5
from bluefog_tpu.optim import DistributedNeighborAllreduceOptimizer
from bluefog_tpu.parallel.api import shard_map
from bluefog_tpu.topology import RingGraph


def make_dataset(n_per_rank, n_ranks, key, noise=0.35):
    """Synthetic MNIST: 10 random 28x28 prototypes + Gaussian noise."""
    kp, kx, ky = jax.random.split(key, 3)
    protos = jax.random.normal(kp, (10, 28, 28, 1)) * 0.8
    labels = jax.random.randint(ky, (n_ranks, n_per_rank), 0, 10)
    imgs = protos[labels] + noise * jax.random.normal(
        kx, (n_ranks, n_per_rank, 28, 28, 1)
    )
    return imgs, labels


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--epochs", type=int, default=3)
    ap.add_argument("--batch-size", type=int, default=32, help="per-rank batch")
    ap.add_argument("--lr", type=float, default=0.01)
    ap.add_argument("--n-per-rank", type=int, default=512)
    ap.add_argument("--atc", action="store_true", help="adapt-then-combine")
    args = ap.parse_args()
    bf.configure_compile_cache()

    n = len(jax.devices())
    bf.init(topology=RingGraph(n))
    ctx = bf.get_context()
    print(f"ranks={n} topology={bf.load_topology().name}")

    model = LeNet5()
    opt = DistributedNeighborAllreduceOptimizer(
        optax.sgd(args.lr, momentum=0.9),
        topology=bf.get_context().schedule,
        axis_name=ctx.axis_name,
        atc=args.atc,
    )

    key = jax.random.PRNGKey(42)
    imgs, labels = make_dataset(args.n_per_rank, n, key)
    init_params = model.init(jax.random.PRNGKey(0), jnp.zeros((1, 28, 28, 1)))

    # all ranks start from identical params (reference: broadcast_parameters)
    params = bf.rank_shard(bf.rank_stack(init_params))
    imgs = bf.rank_shard(imgs)
    labels = bf.rank_shard(labels)

    steps_per_epoch = args.n_per_rank // args.batch_size

    def init_opt(params_blk):
        st = opt.init(jax.tree_util.tree_map(lambda t: t[0], params_blk))
        return jax.tree_util.tree_map(lambda t: jnp.asarray(t)[None], st)

    opt_state = jax.jit(shard_map(
        init_opt, mesh=ctx.mesh, in_specs=(P(ctx.axis_name),),
        out_specs=P(ctx.axis_name), check_vma=False,
    ))(params)

    def epoch_body(params_blk, opt_blk, imgs_blk, labels_blk):
        """One epoch for this rank (block leading dim 1); optimizer state
        (momentum, gossip counters) persists across epochs."""
        p, st = jax.tree_util.tree_map(lambda t: t[0], (params_blk, opt_blk))
        x, y = imgs_blk[0], labels_blk[0]

        def loss_fn(p, xb, yb):
            logits = model.apply(p, xb)
            return optax.softmax_cross_entropy_with_integer_labels(logits, yb).mean()

        def step(carry, i):
            p, st = carry
            xb = lax.dynamic_slice_in_dim(x, i * args.batch_size, args.batch_size)
            yb = lax.dynamic_slice_in_dim(y, i * args.batch_size, args.batch_size)
            loss, g = jax.value_and_grad(loss_fn)(p, xb, yb)
            upd, st = opt.update(g, st, p)
            return (optax.apply_updates(p, upd), st), loss

        (p, st), losses = lax.scan(step, (p, st), jnp.arange(steps_per_epoch))
        acc = (model.apply(p, x).argmax(-1) == y).mean()
        return (jax.tree_util.tree_map(lambda t: t[None], (p, st))
                + (losses.mean()[None], acc[None]))

    train_epoch = jax.jit(shard_map(
        epoch_body, mesh=ctx.mesh,
        in_specs=(P(ctx.axis_name),) * 4,
        out_specs=(P(ctx.axis_name),) * 4,
        check_vma=False,
    ))

    # observability (active only under BLUEFOG_TPU_METRICS=<file.jsonl> or
    # bf.metrics_start()): the instrumented collectives count gossip bytes
    # from inside the jitted epoch; the health gauges below add consensus
    # distance and measured-vs-predicted mixing contraction per epoch
    # fed once per EPOCH while each jitted epoch runs steps_per_epoch
    # gossip rounds — rounds_per_update scales the spectral-gap
    # prediction to the same cadence (|lambda_2|^R)
    mixing = bf_health.MixingTracker(ctx.schedule,
                                     rounds_per_update=steps_per_epoch)
    for epoch in range(args.epochs):
        params, opt_state, losses, accs = train_epoch(params, opt_state, imgs, labels)
        if bf.metrics_active():
            mixing.update(bf_health.consensus_distance_stacked(
                jax.device_get(params)))
            bf.metrics.step(epoch)
        print(f"epoch {epoch}: mean loss {np.asarray(losses).mean():.4f}  "
              f"mean local acc {np.asarray(accs).mean():.3f}")

    # post-training consensus average (reference: bf.allreduce_parameters)
    params = bf.allreduce_parameters(params)
    final_acc = float(np.asarray(accs).mean())
    total_steps = steps_per_epoch * args.epochs
    if total_steps >= 30:
        assert final_acc > 0.9, f"training failed to learn (acc={final_acc})"
        print("OK")
    else:
        print(f"OK (only {total_steps} steps run; acc={final_acc:.3f} — "
              "too few steps for the convergence check)")


if __name__ == "__main__":
    main()
