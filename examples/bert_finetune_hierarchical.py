"""Decentralized BERT fine-tuning with hierarchical gossip — BASELINE.json
config[4] (BERT-large decentralized fine-tune, hierarchical_neighbor_allreduce:
intra-host allreduce + inter-host gossip), the TPU rebuild of the reference's
hierarchical mode (SURVEY.md §0, §2.1 "MPI controller" local/cross
communicators).

The device mesh is split into "machines" of ``--local-size`` chips (a TPU
host / ICI island).  Every step: exact ``psum`` average within each machine
(cheap, rides ICI), then one gossip round between machine leaders on a
machine-level ring (the DCN hop on a real multi-host pod) — all fused into the
single jitted ``shard_map`` train step via
``DistributedHierarchicalNeighborAllreduceOptimizer``.

Task: synthetic sequence classification (GLUE-style shape).  Each example is
a token sequence carrying a class-marker token at random positions; BERT
fine-tunes to detect it.  Real data drops in via ``ArraySource`` over
tokenized ``.npy`` files exactly as in examples/imagenet_resnet.py.

Run (8 virtual devices = 4 machines x 2 chips):
  JAX_PLATFORMS=cpu XLA_FLAGS=--xla_force_host_platform_device_count=8 \
  python examples/bert_finetune_hierarchical.py \
      --local-size 2 --epochs 3
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
from bluefog_tpu.data import ArraySource, DistributedLoader
from bluefog_tpu.models import BertConfig, BertEncoder
from bluefog_tpu.optim import DistributedHierarchicalNeighborAllreduceOptimizer
from bluefog_tpu.parallel.api import shard_map
from bluefog_tpu.topology import ExponentialTwoGraph, RingGraph


def make_task(n_examples, seq_len, vocab, num_classes, seed):
    """Marker-token classification: class c plants token ``vocab-1-c`` at
    3 random positions; everything else is uniform noise."""
    rng = np.random.default_rng(seed)
    ids = rng.integers(1, vocab - num_classes - 1,
                       (n_examples, seq_len)).astype(np.int32)
    labels = rng.integers(0, num_classes, n_examples).astype(np.int32)
    for i in range(n_examples):
        pos = rng.choice(seq_len, 3, replace=False)
        ids[i, pos] = vocab - 1 - labels[i]
    return ArraySource(ids, labels)


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--model", choices=["tiny", "base", "large"],
                    default="tiny")
    ap.add_argument("--epochs", type=int, default=3)
    ap.add_argument("--batch-size", type=int, default=8, help="per-rank")
    ap.add_argument("--seq-len", type=int, default=64)
    ap.add_argument("--num-classes", type=int, default=4)
    ap.add_argument("--n-per-rank", type=int, default=128)
    ap.add_argument("--lr", type=float, default=5e-4)
    ap.add_argument("--local-size", type=int, default=2,
                    help="chips per machine (intra-machine exact average)")
    ap.add_argument("--atc", action="store_true")
    ap.add_argument("--two-level-mesh", action="store_true",
                    help="run over the explicit (machine, local) mesh — the "
                         "multi-slice/DCN deployment form (machine hops on "
                         "the outer axis)")
    args = ap.parse_args()
    bf.configure_compile_cache()

    n = len(jax.devices())
    if n % args.local_size:
        raise SystemExit(f"--local-size {args.local_size} must divide {n}")
    n_machines = n // args.local_size
    bf.init(
        topology=ExponentialTwoGraph(n),
        machine_topology=(RingGraph(n_machines) if n_machines > 1 else None),
        local_size=args.local_size,
    )
    ctx = bf.get_context()
    print(f"ranks={n} machines={n_machines} local_size={args.local_size}")

    cfg = {"tiny": BertConfig.tiny, "base": BertConfig.base,
           "large": BertConfig.large}[args.model]()
    seq_len = min(args.seq_len, cfg.max_position)
    model = BertEncoder(cfg, num_classes=args.num_classes)

    src = make_task(args.n_per_rank * n, seq_len, cfg.vocab_size,
                    args.num_classes, seed=0)
    loader = DistributedLoader(src, args.batch_size)

    two_level = args.two_level_mesh and ctx.machine_schedule is not None
    if args.two_level_mesh and ctx.machine_schedule is None:
        print("WARNING: --two-level-mesh ignored: only one machine "
              "(raise the device count or lower --local-size)")
    # the step's mesh/specs are the only thing the two-level form changes:
    # same model, same optimizer API — axis_name becomes the axis pair
    axis = ((ctx.machine_axis_name, ctx.local_axis_name) if two_level
            else ctx.axis_name)
    mesh = ctx.hier_mesh if two_level else ctx.mesh
    spec = P(axis)
    if ctx.machine_schedule is not None:
        opt = DistributedHierarchicalNeighborAllreduceOptimizer(
            optax.adamw(args.lr), machine_topology=ctx.machine_schedule,
            local_size=args.local_size, axis_name=axis, atc=args.atc)
    else:  # single machine: degenerate to plain gossip
        from bluefog_tpu.optim import DistributedNeighborAllreduceOptimizer
        opt = DistributedNeighborAllreduceOptimizer(
            optax.adamw(args.lr), topology=ctx.schedule,
            axis_name=ctx.axis_name, atc=args.atc)

    x0 = jnp.zeros((1, seq_len), jnp.int32)
    params = model.init(jax.random.PRNGKey(0), x0)["params"]
    params = bf.rank_shard(bf.rank_stack(params))

    def init_opt(p_blk):
        p = jax.tree_util.tree_map(lambda t: t[0], p_blk)
        return jax.tree_util.tree_map(lambda t: jnp.asarray(t)[None],
                                      opt.init(p))

    opt_state = jax.jit(shard_map(
        init_opt, mesh=mesh, in_specs=(spec,),
        out_specs=spec, check_vma=False))(params)

    def train_step(p_blk, opt_blk, ids_blk, y_blk):
        p, st = jax.tree_util.tree_map(lambda t: t[0], (p_blk, opt_blk))
        ids, y = ids_blk[0], y_blk[0]

        def loss_fn(p):
            logits = model.apply({"params": p}, ids)
            loss = optax.softmax_cross_entropy_with_integer_labels(
                logits, y).mean()
            return loss, logits

        (loss, logits), g = jax.value_and_grad(loss_fn, has_aux=True)(p)
        upd, st = opt.update(g, st, p)
        p = optax.apply_updates(p, upd)
        acc = (jnp.argmax(logits, -1) == y).mean()
        out = jax.tree_util.tree_map(lambda t: t[None], (p, st))
        return out + (loss[None], acc[None])

    step_fn = jax.jit(shard_map(
        train_step, mesh=mesh, in_specs=(spec,) * 4,
        out_specs=(spec,) * 4, check_vma=False,
    ), donate_argnums=(0, 1))

    for epoch in range(args.epochs):
        t0 = time.perf_counter()
        loss = acc = None
        for ids, y in loader.epoch(epoch):
            params, opt_state, loss, acc = step_fn(params, opt_state, ids, y)
        jax.block_until_ready(loss)
        dt = time.perf_counter() - t0
        sps = loader.steps_per_epoch * args.batch_size * n / dt
        print(f"epoch {epoch}  loss {np.mean(loss):.4f}  "
              f"acc {np.mean(acc):.3f}  {sps:,.0f} seq/s")

    # consensus check: ranks should stay close (gossip contracts disagreement)
    spread = jax.tree_util.tree_reduce(
        max, jax.tree_util.tree_map(
            lambda t: float(np.max(np.abs(
                np.asarray(t, np.float32) -
                np.asarray(t, np.float32).mean(0, keepdims=True)))), params))
    print(f"max param spread across ranks: {spread:.3e}")
    final_acc = float(np.mean(acc))
    if final_acc <= 0.5:
        # short runs legitimately stop before convergence — report, don't die
        print(f"WARNING: accuracy {final_acc:.3f} <= 0.5 "
              f"(train longer: --epochs/--n-per-rank)")
    print("OK")


if __name__ == "__main__":
    main()
