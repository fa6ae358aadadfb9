"""The readings a ``gqa_moe`` cell's tolerances are set from, in one process:
sound runs (``--seeds``) and controls (``--controls``, on the first seed)
through the harness's own agreement check, each from the state the measured
window leaves (``--preroll`` steps from the cell's initial state).

A sound run prints, by group of leaves (attention, router, experts, head,
embedding, norm scales), the largest difference after the check's three
steps relative to the leaf's largest magnitude and in absolute terms; a
control prints the same and whether the cell's limits catch it, which they
must.  Controls: ``bf16_params`` (parameters rounded to bf16 after every
step where f32 is stated), ``lr_1.25`` (the reference at 1.25 x the rate),
and against the plain model's loss ``rotary_in_global``, ``no_window``,
``silu_for_relu``, ``router_reads_ln2``, ``interleaved_rotary``
(``none``: the plain model as it is).  PERF.md section 6 (PR 34) has the
readings.

  chiprun --timeout 1500 -- python3 benchmarks/gqa_moe_controls.py \\
      --seeds 2147484001,2147484101 --controls all
"""

import argparse
import functools
import json
import os
import sys
import time

sys.path.insert(0, os.path.join(os.path.dirname(__file__), ".."))

import jax
import jax.numpy as jnp

import bluefog_tpu as bf
from chipbench import cell as cells
from chipbench import gqa_moe_reference as ref
from chipbench import reference, run

STEP_CONTROLS = ("bf16_params", "lr_1.25")
MODEL_CONTROLS = ("none", "rotary_in_global", "no_window", "silu_for_relu",
                  "router_reads_ln2", "interleaved_rotary")
GROUPS = {"embedding": "['embedding']", "lm_head": "['lm_head']",
          "scale": "['scale']", "router": "['router']",
          "experts": "['moe']['w_", "attn": "['attn']"}


def say(kind, **fields):
    print(kind + " " + json.dumps(fields), flush=True)


def by_group(leaves, groups=None):
    """Per group the worst leaf by ``difference / largest magnitude`` and by
    difference; ``leaves`` as ``reference.compare`` returns them, ``groups``
    another family's in place of :data:`GROUPS`."""
    out = {}
    for group, pattern in (groups or GROUPS).items():
        chosen = [leaf for leaf in leaves if pattern in leaf[1]]
        if chosen:
            rel = max(chosen, key=lambda leaf: leaf[2] / max(leaf[3], 1e-30))
            out[group] = {"rel": rel[2] / max(rel[3], 1e-30),
                          "abs": max(leaf[2] for leaf in chosen),
                          "over_limit": max(leaf[0] for leaf in chosen)}
    return out


def left_by_the_window(cell, steps):
    state, cell.state = cell.state, None
    for k in range(steps):
        state, loss = cell.step(state, cell.ring[k % len(cell.ring)])
    jax.block_until_ready(loss)
    return state


def check(name, cell, state, preroll, started, seed, out=None, groups=None):
    report = {}
    ok, leaves, loss_err = run.agreement(cell, state, preroll, report)
    if out:       # every leaf, to hold the readings against other limits
        os.makedirs(out, exist_ok=True)
        with open(os.path.join(out, f"{name}.seed{seed}.json"), "w") as f:
            json.dump({"control": name, "seed": seed, "loss_rel_err": loss_err,
                       "model_loss": report.get("model_loss"),
                       "leaves": [list(leaf[1:]) for leaf in leaves]}, f)
    say("AGREEMENT", control=name, seed=seed, ok=bool(ok),
        loss_rel_err=loss_err, model_loss=report.get("model_loss"),
        worst=[list(leaf) for leaf in leaves[:4]],
        groups=by_group(leaves, groups),
        seconds=round(time.time() - started, 1))


def rounded_to_bf16(step):
    """``step`` followed by rounding every parameter to bf16's mantissa
    (``reduce_precision``: a cast there and back is removed on the TPU),
    in place: the check's steps are dispatched ahead of the device, and a
    fresh copy of the parameters for each of them did not fit beside
    ``ling-3.0-flash``'s state (PR 41)."""
    @functools.partial(jax.jit, donate_argnums=0)
    def round_params(state):
        return (jax.tree_util.tree_map(
            lambda a: jax.lax.reduce_precision(a, exponent_bits=8,
                                               mantissa_bits=7),
            state[0]),) + tuple(state[1:])

    def wrapped(state, batch):
        state, loss = step(state, batch)
        return round_params(state), loss
    return wrapped


def run_one_seed(argv, *, description, workload, preroll, model_controls,
                 altered, groups=None, reference_controls=()):
    """The command line of the later families' control scripts
    (``linear_latent_moe_controls.py``, ``conv_gqa_moe_controls.py``): one
    seed; each of ``bf16_params`` and ``lr_1.25`` through :func:`check`
    from the state ``--preroll`` steps leave, then each of
    ``reference_controls`` the same way with ``altered(name)`` in force
    while the reference takes its steps (the system's step is compiled by
    then and keeps its program), then each of ``model_controls`` as the
    plain model's loss beside the system's with ``altered(name)`` in force
    (it returns what undoes it).  ``groups`` replaces :data:`GROUPS` for
    the family's leaves."""
    step_controls = STEP_CONTROLS + tuple(reference_controls)
    ap = argparse.ArgumentParser(description=description)
    ap.add_argument("--workload", default=workload)
    ap.add_argument("--manifest", default=os.path.join(
        os.path.dirname(__file__), "..", "BENCHMARK.json"))
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--controls", default="all",
                    help="comma-separated, or 'all'")
    ap.add_argument("--preroll", type=int, default=preroll,
                    help="steps before the check: what the window completes")
    ap.add_argument("--out", help="directory for every leaf's difference")
    args = ap.parse_args(argv)
    controls = (step_controls + tuple(model_controls)
                if args.controls == "all"
                else tuple(c for c in args.controls.split(",") if c))
    unknown = set(controls) - set(step_controls) - set(model_controls)
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
    for name in (c for c in controls if c in step_controls):
        started = time.time()
        if not first:
            cell.state, _ = init(key)
        first = False
        state = left_by_the_window(cell, args.preroll)
        if name == "bf16_params":
            cell.step = rounded_to_bf16(sound_step)
            check(name, cell, state, args.preroll, started, args.seed,
                  args.out, groups)
            cell.step = sound_step
        elif name in reference_controls:
            undo = altered(name)
            check(name, cell, state, args.preroll, started, args.seed,
                  args.out, groups)
            undo()
        else:
            real = cells.base_optimizer
            cells.base_optimizer = lambda c: real({**c, "optimizer": {
                **c["optimizer"],
                "learning_rate": 1.25 * c["optimizer"]["learning_rate"]}})
            check(name, cell, state, args.preroll, started, args.seed,
                  args.out, groups)
            cells.base_optimizer = real
    chosen = [c for c in controls if c in model_controls]
    if not chosen:
        return
    if not first:
        cell.state, _ = init(key)
    state = left_by_the_window(cell, args.preroll)
    params, model_state = reference.from_host(
        reference.to_host(state[:2], cell.devices), cell.devices)[0]
    del state
    batch, = reference.per_rank(cell.ring[0], cell.devices[:1])
    for name in chosen:
        started = time.time()
        undo = altered(name)
        err, want, got = reference.model_loss_error(
            cell.family, params, model_state, batch)
        undo()
        say("MODEL_LOSS", control=name, seed=args.seed,
            ok=bool(err <= tolerance["model_loss_rtol"]), rel_err=err,
            reference=want, system=got,
            seconds=round(time.time() - started, 1))


def interleaved_rotary(x, positions, theta):
    """Pair ``i`` = elements ``2i`` and ``2i + 1`` (the other convention)."""
    r = x.shape[-1]
    freq = theta ** (-jnp.arange(r // 2, dtype=jnp.float32) * 2.0 / r)
    angle = positions[:, None, None].astype(jnp.float32) * freq
    z = jax.lax.complex(x[..., 0::2], x[..., 1::2]) * jnp.exp(1j * angle)
    return jnp.stack([z.real, z.imag], axis=-1).reshape(x.shape)


def router_reads_ln2(p, x, positions, kind, sizes):
    h = x + ref.gqa(p["attn"], ref.rms(x, p["ln1"]["scale"], sizes["eps"]),
                    positions, kind, sizes)
    z = ref.rms(h, p["ln2"]["scale"], sizes["eps"])
    weights = ref.route(p["moe"]["router"], z, sizes["top_k"])
    return h + ref.held_experts(p["moe"], z, weights, sizes["held_first"])


def altered_reference(name):
    """Change the plain model in one place; returns what undoes it."""
    saved = (dict(ref.LAYERS), ref.reglu, ref.block, ref.rotary)
    if name == "rotary_in_global":
        ref.LAYERS["full_attention"] = (True, False)
    elif name == "no_window":
        ref.LAYERS["window_rotary_attention"] = (True, False)
    elif name == "silu_for_relu":
        ref.reglu = lambda gate, up: jax.nn.silu(gate) * up
    elif name == "router_reads_ln2":
        ref.block = router_reads_ln2
    elif name == "interleaved_rotary":
        ref.rotary = interleaved_rotary

    def undo():
        ref.LAYERS.clear()
        ref.LAYERS.update(saved[0])
        ref.reglu, ref.block, ref.rotary = saved[1:]
    return undo


def main(argv=None):
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", default="smallthinker.t16384.solo")
    ap.add_argument("--manifest", default=os.path.join(
        os.path.dirname(__file__), "..", "BENCHMARK.json"))
    ap.add_argument("--seeds", required=True,
                    help="comma-separated; a sound run each")
    ap.add_argument("--controls", default="",
                    help="comma-separated, or 'all'; on the first seed")
    ap.add_argument("--preroll", type=int, default=38,
                    help="steps before the check: what the window completes")
    ap.add_argument("--learning-rate", type=float)
    ap.add_argument("--out", help="directory for every leaf's difference")
    args = ap.parse_args(argv)
    seeds = [int(s) for s in args.seeds.split(",")]
    controls = (STEP_CONTROLS + MODEL_CONTROLS if args.controls == "all"
                else tuple(c for c in args.controls.split(",") if c))
    unknown = set(controls) - set(STEP_CONTROLS + MODEL_CONTROLS)
    if unknown:
        raise SystemExit(f"unknown controls {sorted(unknown)}")

    bf.configure_compile_cache()
    if args.learning_rate is not None:
        real_open = cells.open_cell

        def open_cell(manifest, workload):
            config, traffic = real_open(manifest, workload)
            return {**config, "optimizer": {
                **config["optimizer"],
                "learning_rate": args.learning_rate}}, traffic
        cells.open_cell = open_cell
    manifest = cells.Manifest.load(args.manifest)

    for n, seed in enumerate(seeds):
        cell = cells.build_cell(manifest, args.workload, seed)
        tolerance = cell.config["tolerance"]
        started = time.time()
        check("sound", cell, left_by_the_window(cell, args.preroll),
              args.preroll, started, seed, args.out)
        if n:
            del cell
            continue
        opt, _ = cells.build_step(cell.family, cell.config, cell.traffic,
                                  cell.ctx)
        init = cells.build_init(cell.family, opt, cell.ctx)
        key = jax.device_put(
            jnp.uint32(seed), jax.sharding.NamedSharding(
                cell.ctx.mesh, jax.sharding.PartitionSpec()))
        sound_step = cell.step
        for name in (c for c in controls if c in STEP_CONTROLS):
            started = time.time()
            cell.state, _ = init(key)
            state = left_by_the_window(cell, args.preroll)
            if name == "bf16_params":
                cell.step = rounded_to_bf16(sound_step)
                check(name, cell, state, args.preroll, started, seed,
                      args.out)
                cell.step = sound_step
            else:
                real = cells.base_optimizer
                cells.base_optimizer = lambda c: real({**c, "optimizer": {
                    **c["optimizer"],
                    "learning_rate": 1.25 * c["optimizer"]["learning_rate"]}})
                check(name, cell, state, args.preroll, started, seed,
                      args.out)
                cells.base_optimizer = real
        model_controls = [c for c in controls if c in MODEL_CONTROLS]
        if model_controls:
            cell.state, _ = init(key)
            state = left_by_the_window(cell, args.preroll)
            params, model_state = reference.from_host(
                reference.to_host(state[:2], cell.devices), cell.devices)[0]
            del state
            batch, = reference.per_rank(cell.ring[0], cell.devices[:1])
        for name in model_controls:
            started = time.time()
            undo = altered_reference(name)
            err, want, got = reference.model_loss_error(
                cell.family, params, model_state, batch)
            undo()
            say("MODEL_LOSS", control=name, seed=seed,
                ok=bool(err <= tolerance["model_loss_rtol"]), rel_err=err,
                reference=want, system=got,
                seconds=round(time.time() - started, 1))
        if model_controls:
            del params, model_state, batch
        del cell


if __name__ == "__main__":
    main()
