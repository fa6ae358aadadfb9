"""Synthetic throughput benchmark — the reference's
``examples/pytorch_benchmark.py`` (Horovod-style: fixed model, synthetic data,
report images/sec mean ± stddev; SURVEY.md §2.2 "Examples") rebuilt TPU-native.

Any model from the zoo x any communication flavor, so gossip overhead can be
compared against the centralized baseline and against no communication at
all — the experiment the reference's benchmark exists for:

  models:  lenet | resnet18 | resnet50 | bert-base | bert-large | gpt-small
  comm:    none | allreduce | neighbor | hierarchical | winput
  topology: exp2 | ring | grid   (for the gossip flavors)

Each timed iteration runs ``--inner`` jitted decentralized train steps; we
report per-chip examples/sec over ``--iters`` iterations, mean ± stddev,
mirroring the reference benchmark's output format.

Run (8 virtual devices):
  JAX_PLATFORMS=cpu XLA_FLAGS=--xla_force_host_platform_device_count=8 \
  python examples/synthetic_benchmark.py \
      --model lenet --comm neighbor --iters 3 --inner 2

``main(argv)`` returns the per-iteration rates and the last per-rank loss so
a caller (``chip_smoke.py``) can assert on them.
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
from bluefog_tpu.optim import (
    CommunicationType,
    DistributedHierarchicalNeighborAllreduceOptimizer,
    DistributedNeighborAllreduceOptimizer,
    DistributedWinPutOptimizer,
    decentralized_optimizer,
)
from bluefog_tpu.parallel.api import shard_map
from bluefog_tpu.topology import ExponentialTwoGraph, MeshGrid2DGraph, RingGraph

TOPOLOGIES = {"exp2": ExponentialTwoGraph, "ring": RingGraph,
              "grid": MeshGrid2DGraph}


def build_model(name, image_size, seq_len, dtype):
    """Returns (apply_fn(params, batch) -> loss, init_params, batch_maker)."""
    from bluefog_tpu.models import (
        BertConfig, BertEncoder, GPTConfig, LeNet5, ResNet18, ResNet50,
        TransformerLM)

    rng = jax.random.PRNGKey(0)
    if name in ("lenet", "resnet18", "resnet50"):
        if name == "lenet":
            model, hw, ch, classes = LeNet5(), 28, 1, 10
        else:
            cls = ResNet18 if name == "resnet18" else ResNet50
            model, hw, ch, classes = (cls(num_classes=1000, dtype=dtype),
                                      image_size, 3, 1000)

        def make_batch(key, n, b):
            return (jax.random.normal(key, (n, b, hw, hw, ch), dtype),
                    jax.random.randint(key, (n, b), 0, classes))

        x0 = jnp.zeros((1, hw, hw, ch), dtype)
        if name == "lenet":
            params = model.init(rng, x0)

            def loss_fn(p, batch):
                x, y = batch
                logits = model.apply(p, x)
                return optax.softmax_cross_entropy_with_integer_labels(
                    logits, y).mean()
        else:
            variables = model.init(rng, x0, train=False)
            params = variables  # fold batch_stats in; frozen for benchmarking

            def loss_fn(p, batch):
                x, y = batch
                logits = model.apply(p, x, train=False)
                return optax.softmax_cross_entropy_with_integer_labels(
                    logits, y).mean()

        return loss_fn, params, make_batch

    if name.startswith("vit"):
        import dataclasses

        from bluefog_tpu.models import ViT, ViTConfig

        cfg = (ViTConfig.base() if name == "vit-base"
               else ViTConfig.tiny())
        # honor --image-size like the resnet branch (must stay a multiple of
        # the patch size for the patchify conv to tile exactly)
        image_size = image_size - (image_size % cfg.patch_size)
        cfg = dataclasses.replace(cfg, dtype=dtype, image_size=image_size)
        model = ViT(cfg)
        hw, classes = cfg.image_size, cfg.num_classes
        params = model.init(rng, jnp.zeros((1, hw, hw, 3), dtype))

        def make_batch(key, n, b):
            return (jax.random.normal(key, (n, b, hw, hw, 3), dtype),
                    jax.random.randint(key, (n, b), 0, classes))

        def loss_fn(p, batch):
            x, y = batch
            logits = model.apply(p, x)
            return optax.softmax_cross_entropy_with_integer_labels(
                logits, y).mean()

        return loss_fn, params, make_batch

    if name.startswith("bert"):
        cfg = BertConfig.large() if name == "bert-large" else BertConfig.base()
        model = BertEncoder(cfg, num_classes=2)
        seq = min(seq_len, cfg.max_position)
        params = model.init(rng, jnp.zeros((1, seq), jnp.int32))

        def make_batch(key, n, b):
            return (jax.random.randint(key, (n, b, seq), 0, cfg.vocab_size),
                    jax.random.randint(key, (n, b), 0, 2))

        def loss_fn(p, batch):
            ids, y = batch
            logits = model.apply(p, ids)
            return optax.softmax_cross_entropy_with_integer_labels(
                logits, y).mean()

        return loss_fn, params, make_batch

    if name == "gpt-small":
        cfg = GPTConfig.small()
        model = TransformerLM(cfg)
        seq = min(seq_len, cfg.max_position)
        params = model.init(rng, jnp.zeros((1, seq), jnp.int32))

        def make_batch(key, n, b):
            return (jax.random.randint(key, (n, b, seq), 0, cfg.vocab_size),)

        def loss_fn(p, batch):
            (ids,) = batch
            logits = model.apply(p, ids)
            tgt = jnp.roll(ids, -1, axis=-1)
            return optax.softmax_cross_entropy_with_integer_labels(
                logits, tgt).mean()

        return loss_fn, params, make_batch

    raise SystemExit(f"unknown model {name}")


def build_optimizer(args, ctx):
    base = optax.sgd(0.01, momentum=0.9)
    if args.comm == "none":
        return decentralized_optimizer(
            base, None, ctx.axis_name,
            communication_type=CommunicationType.empty)
    if args.comm == "allreduce":
        return decentralized_optimizer(
            base, None, ctx.axis_name,
            communication_type=CommunicationType.allreduce)
    if args.comm == "neighbor":
        return DistributedNeighborAllreduceOptimizer(
            base, topology=ctx.schedule, axis_name=ctx.axis_name)
    if args.comm == "winput":
        return DistributedWinPutOptimizer(
            base, topology=ctx.schedule, axis_name=ctx.axis_name)
    if args.comm == "hierarchical":
        if ctx.machine_schedule is None:
            raise SystemExit("--comm hierarchical needs --local-size > 1 "
                             "dividing the device count")
        return DistributedHierarchicalNeighborAllreduceOptimizer(
            base, machine_topology=ctx.machine_schedule,
            local_size=ctx.local_size, axis_name=ctx.axis_name)
    raise SystemExit(f"unknown comm {args.comm}")


def main(argv=None):
    ap = argparse.ArgumentParser()
    ap.add_argument("--model", default="resnet50",
                    choices=["lenet", "resnet18", "resnet50", "bert-base",
                             "bert-large", "gpt-small", "vit-tiny",
                             "vit-base"])
    ap.add_argument("--comm", default="neighbor",
                    choices=["none", "allreduce", "neighbor", "hierarchical",
                             "winput"])
    ap.add_argument("--topology", choices=sorted(TOPOLOGIES), default="exp2")
    ap.add_argument("--batch-size", type=int, default=32, help="per rank")
    ap.add_argument("--image-size", type=int, default=224)
    ap.add_argument("--seq-len", type=int, default=128)
    ap.add_argument("--iters", type=int, default=10)
    ap.add_argument("--inner", type=int, default=10,
                    help="train steps per timed iteration")
    ap.add_argument("--warmup", type=int, default=2)
    ap.add_argument("--local-size", type=int, default=1)
    ap.add_argument("--fp32", action="store_true")
    args = ap.parse_args(argv)
    bf.configure_compile_cache()

    n = len(jax.devices())
    n_machines = n // args.local_size if args.local_size > 1 else n
    bf.init(
        topology=TOPOLOGIES[args.topology](n),
        machine_topology=(RingGraph(n_machines)
                          if args.local_size > 1 and n_machines > 1 else None),
        local_size=args.local_size if args.local_size > 1 else None,
    )
    ctx = bf.get_context()
    dtype = jnp.float32 if args.fp32 else jnp.bfloat16

    loss_fn, params, make_batch = build_model(
        args.model, args.image_size, args.seq_len, dtype)
    opt = build_optimizer(args, ctx)

    params = bf.rank_shard(bf.rank_stack(params))
    batch = bf.rank_shard(make_batch(jax.random.PRNGKey(1), n,
                                     args.batch_size))

    def init_opt(p_blk):
        p = jax.tree_util.tree_map(lambda t: t[0], p_blk)
        return jax.tree_util.tree_map(lambda t: jnp.asarray(t)[None],
                                      opt.init(p))

    opt_state = jax.jit(shard_map(
        init_opt, mesh=ctx.mesh, in_specs=(P(ctx.axis_name),),
        out_specs=P(ctx.axis_name), check_vma=False))(params)

    def train_step(p_blk, opt_blk, *batch_blk):
        p, st = jax.tree_util.tree_map(lambda t: t[0], (p_blk, opt_blk))
        local = tuple(b[0] for b in batch_blk)
        loss, g = jax.value_and_grad(loss_fn)(p, local)
        upd, st = opt.update(g, st, p)
        p = optax.apply_updates(p, upd)
        out = jax.tree_util.tree_map(lambda t: t[None], (p, st))
        return out + (loss[None],)

    nb = len(batch)
    step_fn = jax.jit(shard_map(
        train_step, mesh=ctx.mesh, in_specs=(P(ctx.axis_name),) * (2 + nb),
        out_specs=(P(ctx.axis_name),) * 3, check_vma=False,
    ), donate_argnums=(0, 1))

    loss = None

    def run_inner():
        nonlocal params, opt_state, loss
        for _ in range(args.inner):
            params, opt_state, loss = step_fn(params, opt_state, *batch)
        jax.block_until_ready(loss)

    for _ in range(args.warmup):
        run_inner()

    rates = []
    for it in range(args.iters):
        t0 = time.perf_counter()
        run_inner()
        dt = time.perf_counter() - t0
        rate = args.inner * args.batch_size * n / dt / n  # per chip
        rates.append(rate)
        if bf.metrics_active():
            # one JSONL snapshot per timed iteration: gossip byte counters
            # (from the instrumented collectives) plus throughput
            bf.metrics.comm.set("bf_bench_examples_per_sec_per_chip", rate,
                                model=args.model, comm=args.comm)
            bf.metrics.step(it)
        print(f"iter {it:3d}: {rate:,.1f} ex/s/chip")

    unit = "img" if args.model in ("lenet", "resnet18", "resnet50") else "seq"
    print(f"\nmodel={args.model} comm={args.comm} topology={args.topology} "
          f"ranks={n} batch={args.batch_size}")
    print(f"{unit}/sec/chip: {np.mean(rates):,.1f} ± {np.std(rates):,.1f}   "
          f"total: {np.mean(rates) * n:,.1f}")
    if not np.isfinite(np.asarray(loss)).all():
        raise SystemExit(f"non-finite training loss: {np.asarray(loss)}")
    return {"rates": rates, "loss": np.asarray(loss)}


if __name__ == "__main__":
    main()
