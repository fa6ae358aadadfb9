"""Chip microbenchmark of one router call at the shapes of the four cells
with routed experts, forward and with its gradient, in three forms of the
selection (``ops/moe.py::_top_k``):

- ``sorted``: ``lax.top_k`` over whole rows and ``take_along_axis`` (the
  portable form; a row sort, a gather, and a scatter-add in the gradient);
- ``rounds``: ``k`` unrolled rounds of ``argmax`` as XLA fusions (what PR 44
  shipped and was refused for: each round is a fusion of its own in the
  executable, 87 kB apiece at 512 columns; it lives here alone);
- ``kernel``: the Pallas kernel ``bf_moe_select``, one call a router call.

``ling3flash``: 8,192 tokens of 2,560 against 512 experts in 8 groups, 4
kept, top 8, sigmoid.  ``joyai``: 8,192 of 2,048 against 256, top 8,
sigmoid; the only router that trains.  ``lfm2moe``: 32,768 of 2,048 against
32, top 4, sigmoid with the normaliser's 1e-6.  ``smallthinker``: 16,384 of
2,560 against 64, top 6, softmax.

For each shape, pass and form: ``lower`` and ``compile`` seconds of the
jitted call (the persistent cache off, so a compile is a compile), the bytes
of its serialized executable (what a warm start reads and loads instead),
the three fastest of six wall times and, from a profiler trace of four more
calls joined with the compiled program's ``op_name``s
(``moe_combine_bench.device_times``), the device time a call and its
heaviest instructions.  Ids, weights and gradients of every form are
compared with the sorted form's to the bit: ``apart_from_sorted`` names the
outputs that differ.  One JSON line at the end.  On a CPU (``--shapes
tiny``) the kernel runs in the Pallas interpreter and no device time is
reported.

  chiprun -- python3 benchmarks/router_select_bench.py --out benchmarks/router_select_v5e.json
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

# tokens, model width, experts, top_k, groups, groups kept, router, eps
SHAPES = {
    "ling3flash": (8192, 2560, 512, 8, 8, 4, "sigmoid", 0.0),
    "joyai": (8192, 2048, 256, 8, 1, 1, "sigmoid", 0.0),
    "lfm2moe": (32768, 2048, 32, 4, 1, 1, "sigmoid", 1e-6),
    "smallthinker": (16384, 2560, 64, 6, 1, 1, "softmax", 0.0),
    "tiny": (256, 64, 64, 4, 4, 2, "sigmoid", 0.0),
}
FORMS = ("sorted", "rounds", "kernel")
OUTPUTS = ("idx", "weights", "d_x", "d_router")
SCOPE = re.compile(r"bf\.moe\.\w+")


def rounds_top_k(scores, k, values=None, *, n_group=1, topk_group=1):
    """``_top_k`` as ``k`` unrolled rounds of ``argmax`` in ``jax.numpy``:
    PR 44's form, a fusion a round on a TPU."""
    import jax.numpy as jnp

    def rounds(x, k, v=None):
        cols = jnp.arange(x.shape[-1])
        ids, out = [], []
        for _ in range(k):
            at = jnp.argmax(x, axis=-1)         # the lowest index on a tie
            mine = cols == at[..., None]
            ids.append(at.astype(jnp.int32))
            out.append(jnp.sum(jnp.where(mine, x if v is None else v, 0.0),
                               axis=-1))
            x = jnp.where(mine, -jnp.inf, x)
        return jnp.stack(ids, axis=-1), jnp.stack(out, axis=-1)

    if n_group > 1:
        t, e = scores.shape
        grouped = scores.reshape(t, n_group, e // n_group)
        kept, _ = rounds(rounds(grouped, 2)[1].sum(-1), topk_group)
        open_groups = jnp.any(kept[..., None] == jnp.arange(n_group), axis=1)
        scores = jnp.where(open_groups[..., None], grouped,
                           -jnp.inf).reshape(t, e)
    return rounds(scores, k, values)


def router(name, backward):
    """One router call at a cell's shape, jitted: ``(idx, weights)``, and
    with ``backward`` the gradients of a probe-weighted sum of the weights
    by ``x`` and the router's kernel beside them; and its operands."""
    import jax
    import jax.numpy as jnp

    from bluefog_tpu.ops import moe

    t, d, e, k, n_group, topk_group, kind, eps = SHAPES[name]
    keys = jax.random.split(jax.random.PRNGKey(0), 3)
    x = jax.random.normal(keys[0], (t, d), jnp.bfloat16)
    w = jax.random.normal(keys[1], (d, e), jnp.float32) * d ** -0.5
    probe = jax.random.normal(keys[2], (t, k), jnp.float32)
    # the cells' selection bias: zero, and an operand, not a constant
    bias = jnp.zeros((e,), jnp.float32)

    def route(x, w, bias):
        if kind == "softmax":
            return moe.softmax_topk_router(x, w, top_k=k)
        return moe.sigmoid_topk_router(
            x, w, bias, top_k=k, scale=2.5, n_group=n_group,
            topk_group=topk_group, eps=eps)

    def total(x, w, bias, probe):
        idx, weights = route(x, w, bias)
        return jnp.sum(weights * probe), (idx, weights)

    if not backward:
        return (jax.jit(lambda x, w, bias, probe: route(x, w, bias)),
                (x, w, bias, probe))

    def with_gradient(x, w, bias, probe):
        (_, routing), grads = jax.value_and_grad(
            total, argnums=(0, 1), has_aux=True)(x, w, bias, probe)
        return routing + grads

    return jax.jit(with_gradient), (x, w, bias, probe)


def ask_for(form, moe, kernel):
    """Patch ``ops/moe.py`` to take ``form``; the program itself chooses
    from the backend and the shape as a call is traced."""
    if form == "rounds":
        moe._top_k = rounds_top_k
        moe._select_form = lambda *a, **k: "sorted"     # counts nothing
    else:
        moe._select_form = lambda *a, **k: (
            kernel if form == "kernel" else "sorted")


def build(step, operands):
    """``lower`` and ``compile`` seconds and the serialized executable's
    bytes, and the compiled call."""
    from jax.experimental import serialize_executable

    start = time.perf_counter()
    lowered = step.lower(*operands)
    lower_s = time.perf_counter() - start
    start = time.perf_counter()
    compiled = lowered.compile()
    compile_s = time.perf_counter() - start
    return {"lower_s": lower_s, "compile_s": compile_s,
            "executable_bytes": len(
                serialize_executable.serialize(compiled)[0])}, compiled


def main(argv=None):
    ap = argparse.ArgumentParser()
    ap.add_argument("--shapes", default="ling3flash,joyai,lfm2moe,smallthinker")
    ap.add_argument("--forms", default=",".join(FORMS))
    ap.add_argument("--out", default=None,
                    help="also write the JSON line to this file")
    args = ap.parse_args(argv)
    import jax
    import numpy as np

    from bluefog_tpu.ops import moe
    from moe_combine_bench import device_times, wall_times

    cache_was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    out = {"platform": jax.devices()[0].platform,
           "device_kind": jax.devices()[0].device_kind}
    kernel = "kernel" if out["platform"] == "tpu" else "kernel_interpret"
    trace_dir = tempfile.mkdtemp(prefix="router_select_bench.")
    own = moe._top_k, moe._select_form
    try:
        for name in args.shapes.split(","):
            for backward in (False, True):
                results = {}
                for form in args.forms.split(","):
                    moe._top_k, moe._select_form = own
                    ask_for(form, moe, kernel)
                    step, operands = router(name, backward)
                    entry, _ = build(step, operands)
                    results[form] = jax.block_until_ready(step(*operands))
                    entry["wall_ms"] = wall_times(step, operands)
                    entry["device_ms"] = device_times(step, operands,
                                                      trace_dir, SCOPE)
                    if "sorted" in results:
                        # the outputs that differ from the sorted form's in
                        # a bit: none, or the program is another program
                        entry["apart_from_sorted"] = [
                            name for name, a, b in zip(
                                OUTPUTS, results["sorted"], results[form])
                            if not np.array_equal(np.asarray(a),
                                                  np.asarray(b))]
                    key = f"{name}.{'grad' if backward else 'fwd'}.{form}"
                    out[key] = entry
                    print(key, json.dumps(entry), flush=True)
    finally:
        moe._top_k, moe._select_form = own
        jax.config.update("jax_enable_compilation_cache", cache_was)
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
