"""Chip microbenchmark of one token-embedding lookup's gradient at the
shapes of the decoder cells (the lookup itself is ``jnp.take`` in both forms,
and XLA drops it from a program that asks for the gradient alone): the
gradient of the table as XLA's **scatter-add** of the cotangent's rows
(``jnp.take``'s own transpose) against the **Pallas kernel** that sums the rows at their ids a tile of the
table in VMEM at a time (``ops/row_sums.py::take_rows`` ->
``add_rows_at``, after a sort of the ids and a gather of the rows in that
order).

``gpt2s``: 16,384 ids into 50,304 rows of 768.  ``joyai``: 8,192 into
16,160 of 2,048.  ``phi4flash``: 8,192 into 25,008 of 2,560.
``smallthinker``: 16,384 into 18,992 of 2,560.  Ids uniform, bf16 lookup of
an f32 table, bf16 cotangent: what the cells' steps hand the rule.  The rule
by which ``ops/row_sums.py::_lookup_form`` takes the kernel rests on this
script's output (PERF.md section 6, PR 37).

For each shape and form: the three fastest of six wall times of the jitted
gradient, then, from a profiler trace of four more calls joined with the
compiled program's ``op_name``s (``moe_combine_bench.device_times``), the
device time a call and its heaviest instructions; ``backward_ns_per_row`` is
that time a looked-up row, the cast of the summed table to f32 included.
One JSON line at the end.  On a CPU (``--shapes tiny``) the kernel runs in
the Pallas interpreter and only wall times are reported.

  chiprun -- python3 benchmarks/embed_grad_bench.py
"""

import argparse
import json
import os
import re
import shutil
import sys
import tempfile

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, REPO)
sys.path.insert(0, os.path.join(REPO, "benchmarks"))

# ids looked up, rows of the table, width
SHAPES = {
    "gpt2s": (16384, 50304, 768),
    "joyai": (8192, 16160, 2048),
    "phi4flash": (8192, 25008, 2560),
    "smallthinker": (16384, 18992, 2560),
    "tiny": (256, 200, 128),
}
SCOPE = re.compile(r"bf\.embed\.\w+")


def lookup(name):
    """The jitted gradient of one lookup's probe-weighted sum, and its
    operands."""
    import jax
    import jax.numpy as jnp

    from bluefog_tpu.ops import row_sums

    n, v, d = SHAPES[name]
    keys = jax.random.split(jax.random.PRNGKey(0), 3)
    table = jax.random.normal(keys[0], (v, d), jnp.float32)
    ids = jax.random.randint(keys[1], (1, n), 0, v)
    probe = jax.random.normal(keys[2], (1, n, d), jnp.bfloat16)

    def total(table, ids, probe):
        with jax.named_scope("bf.embed.lookup"):
            x = row_sums.take_rows(table, ids, jnp.bfloat16)
        return (x * probe).astype(jnp.float32).sum()

    return jax.jit(jax.grad(total)), (table, ids, probe)


def main(argv=None):
    ap = argparse.ArgumentParser()
    ap.add_argument("--shapes", default="gpt2s,joyai,phi4flash,smallthinker")
    ap.add_argument("--out", default=None,
                    help="also write the JSON line to this file")
    args = ap.parse_args(argv)
    import jax
    import jax.numpy as jnp

    from bluefog_tpu.ops import row_sums
    from moe_combine_bench import device_times, wall_times

    out = {"platform": jax.devices()[0].platform,
           "device_kind": jax.devices()[0].device_kind}
    kernel = "vmem" if out["platform"] == "tpu" else "vmem_interpret"
    trace_dir = tempfile.mkdtemp(prefix="embed_grad_bench.")
    chosen = row_sums._lookup_form
    try:
        for name in args.shapes.split(","):
            grads = {}
            for form in ("scatter", kernel):
                # the program chooses the form from the backend and the
                # shapes as the rule is traced; here each is asked for
                row_sums._lookup_form = lambda v, d, form=form: form
                step, operands = lookup(name)
                grads[form] = jax.block_until_ready(step(*operands))
                wall = wall_times(step, operands)
                device_ms = device_times(step, operands, trace_dir, SCOPE)
                n, v, d = SHAPES[name]
                entry = {"ids": n, "table": [v, d],
                         "sums_tile": row_sums.sums_tile(v, d),
                         "wall_ms": wall, "device_ms": device_ms}
                if device_ms is not None:
                    entry["backward_ns_per_row"] = (
                        device_ms["total"] * 1e6 / n)
                out[f"{name}.{form.split('_')[0]}"] = entry
                print(name, form, json.dumps(entry), flush=True)
            # bf16 sums against f32 sums of the same bf16 rows
            out[f"{name}.forms_apart"] = float(
                jnp.abs(grads["scatter"] - grads[kernel]).max())
    finally:
        row_sums._lookup_form = chosen
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
