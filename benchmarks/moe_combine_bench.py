"""Chip microbenchmark of one routed expert layer, forward + backward, at the
shapes of the two cells that run ``ops/moe.py::routed_experts``: the sums by
token (``y`` forward, ``d_x`` backward) as XLA's **scatter-adds** of the
pass's ``C`` sorted rows against the **Pallas kernel** that keeps the sums in
VMEM a chunk of columns at a time and adds the live rows as they stream
through (``ops/row_sums.py::add_rows_at``).

``smallthinker``: 16,384 tokens of 2,560, 6 of 64 experts, 16 held of width
768, ReGLU, routing weights constant in the backward pass: ``C`` = 49,152 of
98,304 assignments.  ``joyai``: 8,192 tokens of 2,048, 8 of 256, 16 held of
width 768, SiLU, weights trained: ``C`` = 8,192 of 65,536.  That
``ops/moe.py::_sums_in_vmem`` takes the kernel at both shapes rests on this
script's output (PERF.md section 6, PR 35).

For each shape and form: the three fastest of six wall times of the jitted
value-and-gradient, then, from a profiler trace of four more calls joined with
the compiled program's ``op_name``s (the benchmark's own reader,
``chipbench/xplane.py`` and ``chipbench/reducers/scope_ms.py``), the device
time a call by scope (``bf.moe.dispatch`` / ``.combine`` / ``.experts``) and
its heaviest instructions.  One JSON line at the end.  On a CPU (``--shapes
tiny``) the products and the kernel run in the Pallas interpreter and only
wall times are reported.

  chiprun -- python3 benchmarks/moe_combine_bench.py
"""

import argparse
import collections
import json
import os
import re
import shutil
import sys
import tempfile
import time

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, REPO)

# tokens, width, k, experts, held, expert width, gate, weights trained
SHAPES = {
    "smallthinker": (16384, 2560, 6, 64, 16, 768, "relu", False),
    "joyai": (8192, 2048, 8, 256, 16, 768, "silu", True),
    "tiny": (64, 128, 2, 8, 4, 32, "relu", True),
}
SCOPE = re.compile(r"bf\.moe\.\w+")
TRACED_CALLS = 4


def layer(name, backend):
    """The jitted value-and-gradient of one layer, its operands and the row
    buffer's height."""
    import jax
    import jax.numpy as jnp

    from bluefog_tpu.ops import moe

    t, d, k, e, count, f, gate, trains = SHAPES[name]
    keys = jax.random.split(jax.random.PRNGKey(0), 6)
    x = jax.random.normal(keys[0], (t, d), jnp.bfloat16)
    scores, idx = jax.lax.top_k(jax.random.normal(keys[1], (t, e)), k)
    weights = jax.nn.softmax(scores, axis=-1)
    experts = tuple(
        jax.random.normal(key, shape, jnp.float32) * shape[1] ** -0.5
        for key, shape in zip(keys[2:5], [(count, d, f), (count, d, f),
                                          (count, f, d)]))
    probe = jax.random.normal(keys[5], (t, d), jnp.bfloat16)

    def total(x, weights, *experts):
        if not trains:
            weights = jax.lax.stop_gradient(weights)
        y, record = moe.routed_experts(
            x, idx.astype(jnp.int32), weights, *experts, num_experts=e,
            held=(0, count), activation=gate, backend=backend)
        return jnp.sum(y.astype(jnp.float32) * probe), record

    step = jax.jit(jax.value_and_grad(total, argnums=(0, 1, 2, 3, 4),
                                      has_aux=True))
    return step, (x, weights, *experts), moe._row_buffer(t * k, count, e)


def wall_times(step, args):
    """The three fastest of six wall times of a call, ms."""
    import jax

    wall = []
    for _ in range(6):
        start = time.perf_counter()
        jax.block_until_ready(step(*args))
        wall.append((time.perf_counter() - start) * 1e3)
    return sorted(wall)[:3]


def device_times(step, args, trace_dir, scope_of=SCOPE):
    """Device ms a call by scope (the last match of ``scope_of`` in an
    instruction's ``op_name``) and by instruction, or ``None`` where the
    trace holds no device lane (a CPU)."""
    import jax

    from chipbench import xplane
    from chipbench.reducers.scope_ms import Program

    shutil.rmtree(trace_dir, ignore_errors=True)
    options = jax.profiler.ProfileOptions()
    options.python_tracer_level = 0
    jax.profiler.start_trace(trace_dir, profiler_options=options)
    for _ in range(TRACED_CALLS):
        jax.block_until_ready(step(*args))
    jax.profiler.stop_trace()
    path = xplane.newest(trace_dir)
    trace = xplane.read(path) if path else None
    if trace is None or not trace.lanes:
        return None
    program = Program(step.lower(*args).compile().as_text(), [])
    scopes, instructions = collections.Counter(), collections.Counter()
    for events in trace.lanes.values():
        for name, ns in xplane.self_times(events):
            found = scope_of.findall(program.op_name(name) or "")
            scope = found[-1] if found else "(none)"
            ms = ns / 1e6 / TRACED_CALLS
            scopes[scope] += ms
            instructions[f"{scope} {xplane.base_name(name)}"] += ms
    return {"total": sum(scopes.values()), "scopes": dict(scopes),
            "instructions": instructions.most_common(14)}


def main(argv=None):
    ap = argparse.ArgumentParser()
    ap.add_argument("--shapes", default="smallthinker,joyai")
    ap.add_argument("--out", default=None,
                    help="also write the JSON line to this file")
    args = ap.parse_args(argv)
    import jax

    from bluefog_tpu.ops import moe, row_sums

    out = {"platform": jax.devices()[0].platform,
           "device_kind": jax.devices()[0].device_kind}
    backend = "gmm" if out["platform"] == "tpu" else "gmm_interpret"
    trace_dir = tempfile.mkdtemp(prefix="moe_combine_bench.")
    try:
        for name in args.shapes.split(","):
            for form in ("scatter", "kernel"):
                # the program chooses the form from the backend and the
                # shapes as a layer is traced, forward and backward; here
                # each is asked for in turn
                chosen = moe._sums_in_vmem
                if form == "scatter":
                    moe._sums_in_vmem = lambda t, d, backend: False
                try:
                    step, operands, c = layer(name, backend)
                    (_, record), _ = jax.block_until_ready(step(*operands))
                    wall = wall_times(step, operands)
                    device_ms = device_times(step, operands, trace_dir)
                finally:
                    moe._sums_in_vmem = chosen
                assert int(record["vmem_passes"]) == (
                    0 if form == "scatter" else int(record["row_passes"]))
                entry = {"row_buffer": c,
                         "sums_tile": (None if form == "scatter" else
                                       row_sums.sums_tile(*operands[0].shape)),
                         "held_rows": int(record["rows_per_expert"].sum()),
                         "row_passes": int(record["row_passes"]),
                         "wall_ms": wall, "device_ms": device_ms}
                out[f"{name}.{form}"] = entry
                print(name, form, json.dumps(entry), flush=True)
    finally:
        shutil.rmtree(trace_dir, ignore_errors=True)
    line = json.dumps(out)
    if args.out:
        os.makedirs(os.path.dirname(os.path.abspath(args.out)), exist_ok=True)
        with open(args.out, "w") as f:
            f.write(line + "\n")
    print(line)
    return out


if __name__ == "__main__":
    main()
