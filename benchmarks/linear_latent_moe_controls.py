"""The controls a ``linear_latent_moe`` cell's tolerances are held against,
in one process (the sound runs' readings come from ``chipbench/run.py``'s own
agreement reports, a seed a run): each control goes through the harness's
agreement check from the state ``--preroll`` steps leave, and prints what
``benchmarks/gqa_moe_controls.py`` prints (its helpers are used as they are).

Step controls, which the leaves and the losses must catch: ``bf16_params``
(parameters rounded to bf16 after every step where f32 is stated) and
``lr_1.25`` (the reference at 1.25 x the rate).  Model controls, against the
plain model's loss (``model_loss_rtol``): ``none`` (the pair as it is);
lower precision in the system, ``decay_bf16`` (the KDA log-decay rounded to
bf16 before its running sum); a changed plain model, ``scalar_decay`` (one
decay a head, the mean of its channels'), ``another_lower_bound`` (-1 for
-5), ``ungrouped_routing`` (a plain top-8 of 512) and ``no_short_conv``
(the current token's tap alone).
PERF.md section 6 (PR 41) has the readings.

  chiprun --timeout 1800 -- python3 benchmarks/linear_latent_moe_controls.py \\
      --seed 2147489001 --controls all
"""

import argparse
import os
import sys
import time

sys.path.insert(0, os.path.join(os.path.dirname(__file__), ".."))
sys.path.insert(0, os.path.dirname(__file__))

import jax
import jax.numpy as jnp

import bluefog_tpu as bf
from bluefog_tpu.models import transformer
from chipbench import cell as cells
from chipbench import linear_latent_moe_reference as ref
from chipbench import reference
from gqa_moe_controls import check, left_by_the_window, rounded_to_bf16, say

STEP_CONTROLS = ("bf16_params", "lr_1.25")
MODEL_CONTROLS = ("none", "decay_bf16", "scalar_decay", "another_lower_bound",
                  "ungrouped_routing", "no_short_conv")


def altered(name):
    """Change the system (``decay_bf16``) or the plain model (the others)
    in one place; returns what undoes it."""
    saved = (transformer.kda, ref.delta_rule, ref.chosen_experts, ref.kda,
             ref.causal_conv)
    if name == "decay_bf16":
        transformer.kda = lambda q, k, v, g, beta: saved[0](
            q, k, v, jax.lax.reduce_precision(g, exponent_bits=8,
                                              mantissa_bits=7), beta)
    elif name == "scalar_decay":
        ref.delta_rule = lambda q, k, v, g, beta: saved[1](
            q, k, v, jnp.broadcast_to(g.mean(-1, keepdims=True), g.shape),
            beta)
    elif name == "ungrouped_routing":
        ref.chosen_experts = lambda steer, top_k, n_group, topk_group: (
            saved[2](steer, top_k, 1, 1))
    elif name == "another_lower_bound":
        ref.kda = lambda p, x, sizes: saved[3](
            p, x, {**sizes, "lower_bound": -1.0})
    elif name == "no_short_conv":
        ref.causal_conv = lambda x, kernel: kernel[-1] * x

    def undo():
        (transformer.kda, ref.delta_rule, ref.chosen_experts, ref.kda,
         ref.causal_conv) = saved
    return undo


def main(argv=None):
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", default="ling3flash.t8192.solo")
    ap.add_argument("--manifest", default=os.path.join(
        os.path.dirname(__file__), "..", "BENCHMARK.json"))
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--controls", default="all",
                    help="comma-separated, or 'all'")
    ap.add_argument("--preroll", type=int, default=50,
                    help="steps before the check: what the window completes")
    ap.add_argument("--out", help="directory for every leaf's difference")
    args = ap.parse_args(argv)
    controls = (STEP_CONTROLS + MODEL_CONTROLS if args.controls == "all"
                else tuple(c for c in args.controls.split(",") if c))
    unknown = set(controls) - set(STEP_CONTROLS + MODEL_CONTROLS)
    if unknown:
        raise SystemExit(f"unknown controls {sorted(unknown)}")

    bf.configure_compile_cache()
    manifest = cells.Manifest.load(args.manifest)
    cell = cells.build_cell(manifest, args.workload, args.seed)
    tolerance = cell.config["tolerance"]
    opt, _ = cells.build_step(cell.family, cell.config, cell.traffic,
                              cell.ctx)
    init = cells.build_init(cell.family, opt, cell.ctx)
    key = jax.device_put(jnp.uint32(args.seed), jax.sharding.NamedSharding(
        cell.ctx.mesh, jax.sharding.PartitionSpec()))
    sound_step = cell.step
    first = True
    for name in (c for c in controls if c in STEP_CONTROLS):
        started = time.time()
        if not first:
            cell.state, _ = init(key)
        first = False
        state = left_by_the_window(cell, args.preroll)
        if name == "bf16_params":
            cell.step = rounded_to_bf16(sound_step)
            check(name, cell, state, args.preroll, started, args.seed,
                  args.out)
            cell.step = sound_step
        else:
            real = cells.base_optimizer
            cells.base_optimizer = lambda c: real({**c, "optimizer": {
                **c["optimizer"],
                "learning_rate": 1.25 * c["optimizer"]["learning_rate"]}})
            check(name, cell, state, args.preroll, started, args.seed,
                  args.out)
            cells.base_optimizer = real
    model_controls = [c for c in controls if c in MODEL_CONTROLS]
    if not model_controls:
        return
    if not first:
        cell.state, _ = init(key)
    state = left_by_the_window(cell, args.preroll)
    params, model_state = reference.from_host(
        reference.to_host(state[:2], cell.devices), cell.devices)[0]
    del state
    batch, = reference.per_rank(cell.ring[0], cell.devices[:1])
    for name in model_controls:
        started = time.time()
        undo = altered(name)
        err, want, got = reference.model_loss_error(
            cell.family, params, model_state, batch)
        undo()
        say("MODEL_LOSS", control=name, seed=args.seed,
            ok=bool(err <= tolerance["model_loss_rtol"]), rel_err=err,
            reference=want, system=got,
            seconds=round(time.time() - started, 1))


if __name__ == "__main__":
    main()
