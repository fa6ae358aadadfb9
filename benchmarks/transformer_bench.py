"""Transformer-LM training throughput on a single chip (tokens/sec/chip).

The second model-family perf number next to bench.py's ResNet-50 headline:
a GPT-style decoder (``models/transformer.py``) under the SAME decentralized
training step the examples use — ``DistributedNeighborAllreduceOptimizer``
over the exp2 schedule (identity gossip on one chip, real gossip on a mesh)
— with the model layer's ``backend='auto'`` attention, i.e. the tuned-tile
flash kernel on TPU (PROFILE.md §4a).

Timing discipline: wall clock with the profiler off, and the device's own
op time from a short traced window beside it (``benchmarks/_trace_util``).
MFU uses XLA's own flop count for the compiled step when available, else
the analytic 6·N·T approximation.

Run (real chip):  python benchmarks/transformer_bench.py --seq-len 2048
Run (CPU smoke):  JAX_PLATFORMS=cpu \
    python benchmarks/transformer_bench.py --config tiny --batch 2 \
    --seq-len 256 --steps 2

Prints one JSON line: tokens/sec/chip, per-step times, MFU.
"""

import argparse
import json
import os
import sys

sys.path.insert(0, os.path.join(os.path.dirname(__file__), ".."))

import jax
import jax.numpy as jnp
import numpy as np
import optax

from jax.sharding import PartitionSpec as P

import bluefog_tpu as bf
from benchmarks._trace_util import timed_trace
from bluefog_tpu.models import GPTConfig, TransformerLM
from bluefog_tpu.optim import DistributedNeighborAllreduceOptimizer
from bluefog_tpu.parallel.api import shard_map
from bluefog_tpu.topology import ExponentialTwoGraph

NOMINAL_TFLOPS = {"TPU v5 lite": 197.0, "TPU v5p": 459.0, "TPU v4": 275.0,
                  "TPU v6 lite": 918.0}


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--model", choices=["lm", "vit", "bert", "moe", "moe2"],
                    default="lm",
                    help="lm = GPT decoder (tokens/s); vit = ViT classifier "
                         "(images/s); bert = encoder fine-tune step "
                         "(BASELINE config[4] flavor); moe = Switch-MoE "
                         "decoder (top-1 routing); moe2 = GShard top-2 "
                         "routing under the same step")
    ap.add_argument("--config", choices=["tiny", "small", "large", "base"],
                    default="small",
                    help="GPTConfig preset for lm/moe; ViTConfig for vit "
                         "(tiny/base); BertConfig for bert (tiny/base/large)")
    ap.add_argument("--num-experts", type=int, default=None,
                    help="moe only (default: 8, or tiny preset's 4)")
    ap.add_argument("--batch", type=int, default=8, help="per-chip batch")
    ap.add_argument("--seq-len", type=int, default=2048,
                    help="lm only; vit token count is set by image/patch")
    ap.add_argument("--steps", type=int, default=10)
    ap.add_argument("--remat", action="store_true",
                    help="rematerialize blocks (long sequences)")
    args = ap.parse_args()

    valid_configs = {"lm": ("tiny", "small", "large"),
                     "vit": ("tiny", "base"),
                     "bert": ("tiny", "base", "large"),
                     "moe": ("tiny", "small", "large"),
                     "moe2": ("tiny", "small", "large")}[args.model]
    if args.config not in valid_configs:
        raise SystemExit(
            f"--model {args.model} has no '{args.config}' preset; "
            f"choose from {valid_configs}")

    devices = jax.devices()
    n = len(devices)
    bf.init(topology=ExponentialTwoGraph(n))
    ctx = bf.get_context()

    import dataclasses

    if args.model == "vit":
        from bluefog_tpu.models import ViT, ViTConfig

        vcfg = getattr(ViTConfig, args.config)()
        if args.remat:
            vcfg = dataclasses.replace(vcfg, remat=True)
        cfg = vcfg.trunk()  # dtype/report fields
        model = ViT(vcfg)
        rng_in = jnp.zeros((args.batch, vcfg.image_size, vcfg.image_size, 3),
                           jnp.bfloat16)
        data = (
            jax.random.normal(jax.random.PRNGKey(1),
                              (n, args.batch, vcfg.image_size,
                               vcfg.image_size, 3)).astype(jnp.bfloat16),
            jax.random.randint(jax.random.PRNGKey(2), (n, args.batch), 0,
                               vcfg.num_classes, dtype=jnp.int32))
        unit, per_step_items = "images/sec/chip", args.batch
        # transformer token positions per step, for the analytic fallback
        fallback_tokens = args.batch * (
            (vcfg.image_size // vcfg.patch_size) ** 2 + 1)
        metric = "vit_images_per_sec_per_chip"
    elif args.model == "bert":
        from bluefog_tpu.models import BertConfig, BertEncoder

        bcfg = getattr(BertConfig, args.config)()
        if args.remat:
            bcfg = dataclasses.replace(bcfg, remat=True)
        cfg = bcfg  # report fields (dtype)
        seq = min(args.seq_len, bcfg.max_position)
        model = BertEncoder(bcfg, num_classes=2)  # fine-tune head
        rng_in = jnp.zeros((args.batch, seq), jnp.int32)
        data = (
            jax.random.randint(jax.random.PRNGKey(1), (n, args.batch, seq),
                               0, bcfg.vocab_size, dtype=jnp.int32),
            jax.random.randint(jax.random.PRNGKey(2), (n, args.batch), 0, 2,
                               dtype=jnp.int32))
        unit, per_step_items = "tokens/sec/chip", args.batch * seq
        fallback_tokens = args.batch * seq  # the CAPPED seq, not --seq-len
        metric = "bert_finetune_tokens_per_sec_per_chip"
    elif args.model in ("moe", "moe2"):
        from bluefog_tpu.models import MoEConfig, MoETransformerLM

        if args.config == "tiny":
            mcfg = MoEConfig.tiny()
        else:
            gpt = getattr(GPTConfig, args.config)()
            mcfg = MoEConfig(gpt=gpt)
        # every flag applies in every branch — the report must never claim
        # a remat'd / N-expert run that did not happen
        if args.remat:
            mcfg = dataclasses.replace(
                mcfg, gpt=dataclasses.replace(mcfg.gpt, remat=True))
        if args.num_experts is not None:
            mcfg = dataclasses.replace(mcfg, num_experts=args.num_experts)
        elif args.config != "tiny":
            mcfg = dataclasses.replace(mcfg, num_experts=8)
        if args.model == "moe2":
            mcfg = dataclasses.replace(mcfg, router="top2")
        cfg = mcfg.gpt
        model = MoETransformerLM(mcfg)
        moe_aux_weight = mcfg.aux_loss_weight
        rng_in = jnp.zeros((args.batch, args.seq_len), jnp.int32)
        data = (jax.random.randint(
            jax.random.PRNGKey(1), (n, args.batch, args.seq_len + 1), 0,
            cfg.vocab_size, dtype=jnp.int32),)
        unit, per_step_items = "tokens/sec/chip", args.batch * args.seq_len
        # 6*N*T over ALL params would count every expert as active though
        # top-1 routing executes one -- no honest analytic fallback exists
        fallback_tokens = None
        metric = f"{args.model}_lm_tokens_per_sec_per_chip"
    else:
        cfg = getattr(GPTConfig, args.config)()
        if args.remat:
            cfg = dataclasses.replace(cfg, remat=True)
        model = TransformerLM(cfg)
        rng_in = jnp.zeros((args.batch, args.seq_len), jnp.int32)
        data = (jax.random.randint(
            jax.random.PRNGKey(1), (n, args.batch, args.seq_len + 1), 0,
            cfg.vocab_size, dtype=jnp.int32),)
        unit, per_step_items = "tokens/sec/chip", args.batch * args.seq_len
        fallback_tokens = args.batch * args.seq_len
        metric = "transformer_lm_tokens_per_sec_per_chip"

    opt = DistributedNeighborAllreduceOptimizer(
        optax.adamw(3e-4, weight_decay=0.01), topology=ctx.schedule,
        axis_name=ctx.axis_name)

    rng = jax.random.PRNGKey(0)
    params = model.init(rng, rng_in)["params"]
    params = bf.rank_shard(bf.rank_stack(params))
    data = tuple(bf.rank_shard(d) for d in data)

    def init_opt(params_blk):
        p = jax.tree_util.tree_map(lambda t: t[0], params_blk)
        st = opt.init(p)
        return jax.tree_util.tree_map(lambda t: jnp.asarray(t)[None], st)

    opt_state = jax.jit(shard_map(
        init_opt, mesh=ctx.mesh, in_specs=(P(ctx.axis_name),),
        out_specs=P(ctx.axis_name), check_vma=False))(params)

    def train_step(params_blk, opt_blk, *data_blks):
        p, st = jax.tree_util.tree_map(lambda t: t[0], (params_blk, opt_blk))
        vals = [d[0] for d in data_blks]

        def loss_fn(p):
            if args.model == "vit":
                imgs, labels = vals
                logits = model.apply({"params": p}, imgs, train=True)
                return optax.softmax_cross_entropy_with_integer_labels(
                    logits.astype(jnp.float32), labels).mean()
            if args.model == "bert":
                tok, labels = vals
                logits = model.apply({"params": p}, tok, deterministic=True)
                return optax.softmax_cross_entropy_with_integer_labels(
                    logits.astype(jnp.float32), labels).mean()
            (tok,) = vals
            inp, tgt = tok[:, :-1], tok[:, 1:]
            if args.model in ("moe", "moe2"):
                logits, st_aux = model.apply({"params": p}, inp,
                                             mutable=["aux_loss"])
                ce = optax.softmax_cross_entropy_with_integer_labels(
                    logits.astype(jnp.float32), tgt).mean()
                aux = sum(jnp.sum(a) for a in
                          jax.tree_util.tree_leaves(st_aux["aux_loss"]))
                return ce + moe_aux_weight * aux
            logits = model.apply({"params": p}, inp)
            return optax.softmax_cross_entropy_with_integer_labels(
                logits.astype(jnp.float32), tgt).mean()

        loss, grads = jax.value_and_grad(loss_fn)(p)
        updates, st = opt.update(grads, st, p)
        p = optax.apply_updates(p, updates)
        return (jax.tree_util.tree_map(lambda t: t[None], (p, st))
                + (loss[None],))

    # AOT-compile once; the executable serves cost analysis + the timed loop
    step_fn = jax.jit(shard_map(
        train_step, mesh=ctx.mesh,
        in_specs=(P(ctx.axis_name),) * (2 + len(data)),
        out_specs=(P(ctx.axis_name),) * 3, check_vma=False,
    ), donate_argnums=(0, 1)).lower(params, opt_state, *data).compile()

    try:
        flops_per_step = float(step_fn.cost_analysis()["flops"])
        flops_source = "xla_cost_analysis"
    except Exception:  # noqa: BLE001 — platform-dependent availability
        if fallback_tokens is None:
            flops_per_step, flops_source = 0.0, "unavailable"
        else:
            n_params = sum(int(np.prod(x.shape))
                           for x in jax.tree_util.tree_leaves(params)) / n
            flops_per_step = 6.0 * n_params * fallback_tokens
            flops_source = "analytic_6NT"

    state = {"p": params, "o": opt_state}

    def step(*data_):
        state["p"], state["o"], loss = step_fn(state["p"], state["o"],
                                               *data_)
        return loss

    wall_ms, trace_ms = timed_trace(step, data, args.steps)
    headline_ms = trace_ms or wall_ms
    tps = per_step_items / (headline_ms / 1e3)
    achieved = flops_per_step / (headline_ms / 1e3)
    kind = getattr(devices[0], "device_kind", str(devices[0]))
    spec = NOMINAL_TFLOPS.get(kind)

    # dropped-token accounting (moe/moe2): one untimed forward with the
    # metrics collection mutable; reported so a capacity_factor that
    # silently drops tokens is visible in every bench row
    moe_metrics = None
    if args.model in ("moe", "moe2"):
        p0 = jax.tree_util.tree_map(lambda t: t[0], state["p"])
        tok0 = np.asarray(data[0])[0, 0][None]
        _, mstate = model.apply({"params": p0}, jnp.asarray(tok0[:, :-1]),
                                mutable=["aux_loss", "moe_metrics"])
        flat = jax.tree_util.tree_flatten_with_path(mstate["moe_metrics"])[0]
        # exact key segment: 'dropped_frac' is a substring of
        # 'fully_dropped_frac', so match the quoted dict key
        pick = lambda key: [float(jnp.mean(v)) for path, v in flat
                            if f"'{key}'" in jax.tree_util.keystr(path)]
        moe_metrics = {
            "router": mcfg.router,
            "dropped_frac": round(float(np.mean(pick("dropped_frac"))), 4),
            "fully_dropped_frac": round(
                float(np.mean(pick("fully_dropped_frac"))), 4),
            "capacity_factor": mcfg.capacity_factor,
        }

    out = {
        "metric": metric,
        "value": round(tps, 1),
        "unit": unit,
        "model": args.model,
        "config": args.config, "batch": args.batch,
        "seq_len": (None if args.model == "vit"
                    else min(args.seq_len, cfg.max_position)
                    if args.model == "bert" else args.seq_len),
        "remat": bool(args.remat), "dtype": str(cfg.dtype.__name__ if
                                                hasattr(cfg.dtype, "__name__")
                                                else cfg.dtype),
        "wall_ms_per_step": round(wall_ms, 3),
        "trace_ms_per_step": round(trace_ms, 3) if trace_ms else None,
        "timing_source": "profiler_trace" if trace_ms else
                         "wall_clock_uncorroborated",
        "wall_plausible": (wall_ms >= 0.9 * trace_ms) if trace_ms else None,
        "model_tflops_per_sec_per_chip": (round(achieved / 1e12, 2)
                                          if flops_per_step > 0 else None),
        "flops_source": flops_source,
        "device_kind": kind,
        "mfu_vs_nominal": (round(achieved / 1e12 / spec, 4)
                           if spec and flops_per_step > 0 else None),
        "moe": moe_metrics,
    }
    print(json.dumps(out))


if __name__ == "__main__":
    main()
