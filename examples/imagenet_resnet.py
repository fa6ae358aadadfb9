"""Decentralized ResNet-50 ImageNet training — BASELINE.json config[1]
(ResNet-50/ImageNet, ExponentialTwoGraph, DistributedNeighborAllreduceOptimizer),
the reference's ImageNet example (upstream ``examples/pytorch_imagenet_resnet50.py``;
SURVEY.md §2.2 "Examples") rebuilt TPU-native.

Each rank trains its own ResNet replica on a disjoint shard and gossips
parameters with its exp2 neighbors every step; compute + gossip is one jitted
``shard_map`` program so XLA overlaps the permutes with backprop (the TPU
equivalent of the reference's hook overlap, SURVEY.md §3.3).  The standard
90-epoch recipe pieces are here: per-rank batch, 5-epoch linear warmup →
cosine decay, label smoothing, SGD momentum + weight decay, top-1 eval, and
periodic (optionally consensus-mode) checkpoints.

Data: ``--data-dir`` pointing at ``train-*.tfrecord / val-*.tfrecord`` shards
(tf.Example with raw uint8 image/shape/label — see
``bluefog_tpu.data.write_image_classification_shards``) or at
``{train,val}_{images,labels}.npy`` pairs (memory-mapped) trains real
ImageNet; without it a deterministic synthetic stand-in of the same shapes
keeps the example runnable in this offline environment.

Run (8 virtual devices):
  JAX_PLATFORMS=cpu XLA_FLAGS=--xla_force_host_platform_device_count=8 \
  python examples/imagenet_resnet.py \
      --image-size 64 --batch-size 8 --steps-per-epoch 4 --epochs 2

``main(argv)`` returns the final state and per-rank losses so a caller
(``chip_smoke.py``) can assert on them.
"""

import argparse
import os
import sys
import time

sys.path.insert(0, os.path.join(os.path.dirname(__file__), ".."))  # repo-root run

import jax
import jax.numpy as jnp
import numpy as np
import optax
from jax.sharding import PartitionSpec as P

import bluefog_tpu as bf
from bluefog_tpu.data import (
    ArraySource,
    DistributedLoader,
    SyntheticClassificationSource,
)
from bluefog_tpu.models import ResNet50
from bluefog_tpu.optim import (
    DistributedGradientAllreduceOptimizer,
    DistributedNeighborAllreduceOptimizer,
)
from bluefog_tpu.parallel.api import shard_map
from bluefog_tpu.topology import ExponentialTwoGraph, MeshGrid2DGraph, RingGraph
from bluefog_tpu.utils.checkpoint import CheckpointManager

TOPOLOGIES = {
    "exp2": ExponentialTwoGraph,
    "ring": RingGraph,
    "grid": MeshGrid2DGraph,
}


def make_sources(args, n_ranks):
    if args.data_dir:
        import glob

        from bluefog_tpu.data import TFRecordSource

        # TFRecord shards take precedence (train-*.tfrecord / val-*.tfrecord,
        # e.g. from bluefog_tpu.data.write_image_classification_shards);
        # otherwise fall back to memory-mapped .npy pairs.
        if glob.glob(os.path.join(args.data_dir, "train-*.tfrecord")):
            train = TFRecordSource(
                os.path.join(args.data_dir, "train-*.tfrecord"))
            val = TFRecordSource(os.path.join(args.data_dir, "val-*.tfrecord"))
            return train, val

        def load(name):
            return np.load(os.path.join(args.data_dir, name), mmap_mode="r")

        train = ArraySource(load("train_images.npy"), load("train_labels.npy"))
        val = ArraySource(load("val_images.npy"), load("val_labels.npy"))
        return train, val
    shape = (args.image_size, args.image_size, 3)
    n_train = args.steps_per_epoch * args.batch_size * n_ranks
    train = SyntheticClassificationSource(
        n_train, shape=shape, num_classes=args.num_classes, seed=0)
    val = SyntheticClassificationSource(
        max(n_train // 8, args.batch_size * n_ranks), shape=shape,
        num_classes=args.num_classes, seed=1)
    return train, val


def lr_schedule(args, steps_per_epoch):
    base = args.lr * args.batch_size / 256.0  # linear scaling rule
    warmup = optax.linear_schedule(0.0, base, args.warmup_epochs * steps_per_epoch)
    cosine = optax.cosine_decay_schedule(
        base, max((args.epochs - args.warmup_epochs), 1) * steps_per_epoch)
    return optax.join_schedules([warmup, cosine],
                                [args.warmup_epochs * steps_per_epoch])


def main(argv=None):
    ap = argparse.ArgumentParser()
    ap.add_argument("--data-dir", default=None,
                    help="dir with {train,val}-*.tfrecord shards or "
                         "{train,val}_{images,labels}.npy; synthetic if unset")
    ap.add_argument("--epochs", type=int, default=90)
    ap.add_argument("--steps-per-epoch", type=int, default=32,
                    help="synthetic epoch length (ignored with --data-dir)")
    ap.add_argument("--batch-size", type=int, default=128, help="per-rank batch")
    ap.add_argument("--image-size", type=int, default=224)
    ap.add_argument("--num-classes", type=int, default=1000)
    ap.add_argument("--lr", type=float, default=0.1, help="base lr at batch 256")
    ap.add_argument("--warmup-epochs", type=int, default=5)
    ap.add_argument("--weight-decay", type=float, default=1e-4)
    ap.add_argument("--label-smoothing", type=float, default=0.1)
    ap.add_argument("--topology", choices=sorted(TOPOLOGIES), default="exp2")
    ap.add_argument("--optimizer", choices=["neighbor", "allreduce"],
                    default="neighbor",
                    help="decentralized gossip vs centralized baseline")
    ap.add_argument("--atc", action="store_true", help="adapt-then-combine")
    ap.add_argument("--checkpoint-dir", default=None)
    ap.add_argument("--checkpoint-every", type=int, default=1, metavar="EPOCHS")
    ap.add_argument("--resume", action="store_true")
    ap.add_argument("--eval-every", type=int, default=1, metavar="EPOCHS")
    ap.add_argument("--stem", choices=["conv", "s2d"], default="conv",
                    help="s2d = space-to-depth stem (same function class, "
                         "4x MXU input-lane occupancy on the stem conv)")
    ap.add_argument("--fp32", action="store_true",
                    help="train in float32 (default bfloat16)")
    args = ap.parse_args(argv)
    bf.configure_compile_cache()

    n = len(jax.devices())
    bf.init(topology=TOPOLOGIES[args.topology](n))
    ctx = bf.get_context()
    dtype = jnp.float32 if args.fp32 else jnp.bfloat16
    print(f"platform={jax.devices()[0].platform} ranks={n} "
          f"topology={args.topology} optimizer={args.optimizer} "
          f"dtype={dtype.__name__}")

    train_src, val_src = make_sources(args, n)
    loader = DistributedLoader(train_src, args.batch_size)
    val_loader = DistributedLoader(val_src, args.batch_size, shuffle=False)
    steps_per_epoch = loader.steps_per_epoch

    model = ResNet50(num_classes=args.num_classes, dtype=dtype, stem=args.stem)
    sched = lr_schedule(args, steps_per_epoch)
    base_opt = optax.chain(
        optax.add_decayed_weights(args.weight_decay),
        optax.sgd(sched, momentum=0.9, nesterov=True),
    )
    if args.optimizer == "neighbor":
        opt = DistributedNeighborAllreduceOptimizer(
            base_opt, topology=ctx.schedule, axis_name=ctx.axis_name,
            atc=args.atc)
    else:
        opt = DistributedGradientAllreduceOptimizer(
            base_opt, axis_name=ctx.axis_name)

    x0 = jnp.zeros((1, args.image_size, args.image_size, 3), dtype)
    variables = model.init(jax.random.PRNGKey(0), x0, train=True)
    # identical start on every rank — the reference's broadcast_parameters
    params = bf.rank_shard(bf.rank_stack(variables["params"]))
    batch_stats = bf.rank_shard(bf.rank_stack(variables["batch_stats"]))

    def init_opt(p_blk):
        p = jax.tree_util.tree_map(lambda t: t[0], p_blk)
        st = opt.init(p)
        return jax.tree_util.tree_map(lambda t: jnp.asarray(t)[None], st)

    opt_state = jax.jit(shard_map(
        init_opt, mesh=ctx.mesh, in_specs=(P(ctx.axis_name),),
        out_specs=P(ctx.axis_name), check_vma=False))(params)

    def prep(x):
        if x.dtype == jnp.uint8:  # raw images: map [0,255] → [-1,1]
            x = x.astype(dtype) / 127.5 - 1.0
        return x.astype(dtype)

    def train_step(p_blk, bs_blk, opt_blk, x_blk, y_blk):
        p, bs, st = jax.tree_util.tree_map(
            lambda t: t[0], (p_blk, bs_blk, opt_blk))
        x, y = prep(x_blk[0]), y_blk[0]

        def loss_fn(p):
            logits, mut = model.apply(
                {"params": p, "batch_stats": bs}, x, train=True,
                mutable=["batch_stats"])
            loss = optax.softmax_cross_entropy(
                logits,
                optax.smooth_labels(
                    jax.nn.one_hot(y, args.num_classes),
                    args.label_smoothing)).mean()
            return loss, (mut["batch_stats"], logits)

        (loss, (new_bs, logits)), g = jax.value_and_grad(
            loss_fn, has_aux=True)(p)
        upd, st = opt.update(g, st, p)
        p = optax.apply_updates(p, upd)
        acc = (jnp.argmax(logits, -1) == y).mean()
        out = jax.tree_util.tree_map(lambda t: t[None], (p, new_bs, st))
        return out + (loss[None], acc[None])

    step_fn = jax.jit(shard_map(
        train_step, mesh=ctx.mesh, in_specs=(P(ctx.axis_name),) * 5,
        out_specs=(P(ctx.axis_name),) * 5, check_vma=False,
    ), donate_argnums=(0, 1, 2))

    def eval_step(p_blk, bs_blk, x_blk, y_blk):
        p, bs = jax.tree_util.tree_map(lambda t: t[0], (p_blk, bs_blk))
        logits = model.apply(
            {"params": p, "batch_stats": bs}, prep(x_blk[0]), train=False)
        hits = (jnp.argmax(logits, -1) == y_blk[0]).sum()
        return hits[None]

    eval_fn = jax.jit(shard_map(
        eval_step, mesh=ctx.mesh, in_specs=(P(ctx.axis_name),) * 4,
        out_specs=P(ctx.axis_name), check_vma=False))

    mgr = None
    start_epoch = 0
    loss = val_top1 = saved = None
    if args.checkpoint_dir:
        mgr = CheckpointManager(args.checkpoint_dir)
        if args.resume and mgr.latest_step() is not None:
            state = mgr.restore(template={
                "params": params, "batch_stats": batch_stats,
                "opt_state": opt_state,
            })
            params, batch_stats, opt_state = (
                bf.rank_shard(state["params"]),
                bf.rank_shard(state["batch_stats"]),
                bf.rank_shard(state["opt_state"]),
            )
            start_epoch = mgr.latest_step()
            print(f"resumed from epoch {start_epoch}")

    for epoch in range(start_epoch, args.epochs):
        t0 = time.perf_counter()
        acc = None
        for x, y in loader.epoch(epoch):
            params, batch_stats, opt_state, loss, acc = step_fn(
                params, batch_stats, opt_state, x, y)
        jax.block_until_ready(loss)
        dt = time.perf_counter() - t0
        ips = steps_per_epoch * args.batch_size * n / dt
        print(f"epoch {epoch:3d}  loss {np.mean(loss):.4f}  "
              f"train-acc {np.mean(acc):.3f}  "
              f"{ips:,.0f} img/s ({ips / n:,.0f}/chip)  "
              f"lr {sched(epoch * steps_per_epoch + steps_per_epoch - 1):.4f}")

        # BN running stats never appear in the gossip (only params do), so
        # each rank's batch_stats drift apart on disjoint shards.  Average
        # them across ranks before they are consumed (eval / checkpoint) —
        # the analog of the reference re-synchronizing buffers with
        # broadcast_parameters before evaluation.
        synced_bs = batch_stats
        if (args.eval_every and (epoch + 1) % args.eval_every == 0) or (
                mgr and (epoch + 1) % args.checkpoint_every == 0):
            synced_bs = bf.allreduce(batch_stats)

        if args.eval_every and (epoch + 1) % args.eval_every == 0:
            hits = 0
            for x, y in val_loader.epoch(0):
                hits += int(np.sum(eval_fn(params, synced_bs, x, y)))
            total = val_loader.steps_per_epoch * args.batch_size * n
            val_top1 = hits / total
            print(f"          val top-1 {val_top1:.4f}  ({hits}/{total})")

        if mgr and (epoch + 1) % args.checkpoint_every == 0:
            saved = {"params": params, "batch_stats": synced_bs,
                     "opt_state": opt_state}
            mgr.save(epoch + 1, saved)
    if mgr:
        mgr.wait()
        mgr.close()
    if loss is not None and not np.isfinite(np.asarray(loss)).all():
        raise SystemExit(f"non-finite training loss: {np.asarray(loss)}")
    print("OK")
    return {
        "start_epoch": start_epoch,
        "loss": None if loss is None else np.asarray(loss),
        "val_top1": val_top1,
        "params": params,
        "batch_stats": batch_stats,
        "opt_state": opt_state,
        "saved": saved,  # the last tree handed to the checkpoint manager
    }


if __name__ == "__main__":
    main()
