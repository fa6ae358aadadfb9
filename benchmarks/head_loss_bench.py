"""Chip microbenchmark of the language-model head and its loss alone, value
and both gradients, at the shapes of the five cells that call
``next_token_loss``: **whole** f32 ``(rows, V)`` logits handed to ``optax``'s
cross entropy and differentiated by JAX (what the step ran before PR 46)
against **chunked** (``ops/head_loss.py::head_loss``: the matmul, the cross
entropy and both gradients a chunk of rows at a time under one rule).

``lfm2moe``: 32,768 rows of 2,048 against the tied table of 16,384 rows.
``smallthinker``: 16,384 of 2,560, an untied head of 18,992.  ``phi4flash``:
8,192 of 2,560, tied, 25,008.  ``ling3flash``: 8,192 of 2,560, untied,
19,648.  ``joyai``: 8,192 of 2,048, untied, 16,160 (one of its two heads).
f32 hidden states (the final norm's), the f32 leaf, uniform targets: what the
cells' steps hand the head.

For each shape and form: the compile's seconds and the program's
temporaries (``memory_analysis().temp_size_in_bytes``), the three fastest of
six wall times of the jitted value-and-gradients, then, from a profiler
trace of four more calls joined with the compiled program's ``op_name``s
(``moe_combine_bench.device_times``), the device time a call by scope and its
heaviest instructions.  ``forms_apart`` is the largest difference of the
loss and of each gradient between the forms.  One JSON line at the end
(``benchmarks/head_loss_v5e.json`` is a chip run's).  On a CPU
(``--shapes tiny``) only wall times and temporaries are reported.

  chiprun -- python3 benchmarks/head_loss_bench.py --out chiprun_out/head_loss_v5e.json
"""

import argparse
import json
import os
import re
import shutil
import sys
import tempfile
import time

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, REPO)
sys.path.insert(0, os.path.join(REPO, "benchmarks"))

# token rows, hidden width, vocabulary rows, whether the head is the table
SHAPES = {
    "lfm2moe": (32768, 2048, 16384, True),
    "smallthinker": (16384, 2560, 18992, False),
    "phi4flash": (8192, 2560, 25008, True),
    "ling3flash": (8192, 2560, 19648, False),
    "joyai": (8192, 2048, 16160, False),
    "tiny": (3072, 64, 24000, True),
}
SCOPE = re.compile(r"bf\.head\.\w+")


def whole(h, w, targets, tied):
    """The head and the loss as ``TransformerLM`` and ``next_token_loss``
    made them before the chunks: whole f32 logits, ``optax``'s loss."""
    import jax
    import jax.numpy as jnp
    import optax

    with jax.named_scope("bf.head.logits"):
        logits = (jnp.einsum("...d,vd->...v", h, w) if tied
                  else jnp.dot(h, w))
    with jax.named_scope("bf.head.loss"):
        return optax.softmax_cross_entropy_with_integer_labels(
            logits, targets).mean()


def head(name, form):
    """The jitted loss and gradients (hidden states, leaf) of one head, and
    its operands."""
    import jax
    import jax.numpy as jnp

    from bluefog_tpu.ops.head_loss import head_loss

    rows, d, v, tied = SHAPES[name]
    keys = jax.random.split(jax.random.PRNGKey(0), 3)
    h = jax.random.normal(keys[0], (rows, d), jnp.float32)
    w = 0.02 * jax.random.normal(keys[1], (v, d) if tied else (d, v),
                                 jnp.float32)
    targets = jax.random.randint(keys[2], (rows,), 0, v)
    loss = {"whole": lambda h, w, t: whole(h, w, t, tied),
            "chunked": lambda h, w, t: head_loss(h, w, t, tied=tied)}[form]
    return jax.jit(jax.value_and_grad(loss, argnums=(0, 1))), (h, w, targets)


def main(argv=None):
    ap = argparse.ArgumentParser()
    ap.add_argument("--shapes",
                    default="lfm2moe,smallthinker,phi4flash,ling3flash,joyai")
    ap.add_argument("--out", default=None,
                    help="also write the JSON line to this file")
    args = ap.parse_args(argv)
    import jax
    import jax.numpy as jnp

    from bluefog_tpu.ops.head_loss import chunk_rows
    from moe_combine_bench import device_times, wall_times

    out = {"platform": jax.devices()[0].platform,
           "device_kind": jax.devices()[0].device_kind}
    trace_dir = tempfile.mkdtemp(prefix="head_loss_bench.")
    try:
        for name in args.shapes.split(","):
            rows, d, v, tied = SHAPES[name]
            results = {}
            for form in ("whole", "chunked"):
                step, operands = head(name, form)
                start = time.perf_counter()
                compiled = step.lower(*operands).compile()
                compile_s = time.perf_counter() - start
                results[form] = jax.block_until_ready(step(*operands))
                entry = {
                    "rows": rows, "hidden": d, "vocab": v, "tied": tied,
                    "chunk_rows": chunk_rows(rows, v), "compile_s": compile_s,
                    "temp_bytes": compiled.memory_analysis()
                    .temp_size_in_bytes,
                    "wall_ms": wall_times(step, operands),
                    "device_ms": device_times(step, operands, trace_dir,
                                              SCOPE)}
                out[f"{name}.{form}"] = entry
                print(name, form, json.dumps(entry), flush=True)
            apart = jax.tree_util.tree_map(
                lambda a, b: float(jnp.abs(a - b).max()),
                results["whole"], results["chunked"])
            out[f"{name}.forms_apart"] = {
                "loss": apart[0], "d_hidden": apart[1][0],
                "d_leaf": apart[1][1]}
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
