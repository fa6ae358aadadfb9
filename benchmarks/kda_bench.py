"""Chip microbenchmark of one layer's delta-rule scan (``bluefog_tpu.ops.kda``)
at ``ling3flash.t8192.solo``'s shape: batch 1, T=8,192, 16 heads of 128, bf16
``q, k, v``, f32 log-decay and ``beta`` (what ``models/transformer.py::
KdaMixer`` hands it), the forward alone and the forward with the gradients of
all five operands.

For each: the three fastest of six wall times of the jitted call, then, from
a profiler trace of four more calls joined with the compiled program's
``op_name``s (``moe_combine_bench.device_times``), the device time a call,
how much of it each kernel takes (``bf_kda_fwd``, ``bf_kda_bwd_chunks``, the
names the benchmark's ``kda_scan_*`` metrics read; ``(none)`` is the rest:
the decay's running sum, the beta-scaled keys, the probe) and the heaviest
instructions.  In the cell's step the kernels
take less than here (4.08 / 11.2 ms against 5.92 / 18.80 alone at PR 41:
PERF.md section 6), so compare a before and an after from this file, not this
file with the step.  One JSON line at the end.  On a CPU (``--shape tiny``)
the kernels run in the Pallas interpreter and only wall times are reported.

  chiprun -- python3 benchmarks/kda_bench.py
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

# batch, tokens, heads, head width
SHAPES = {"cell": (1, 8192, 16, 128), "tiny": (1, 128, 2, 128)}
KERNEL = re.compile(r"bf_kda_(?:fwd|bwd_chunks)")


def layer(kda, shape, backend):
    """The jitted forward and forward + backward of one layer's scan
    through ``kda`` (the module's, or a copy's under comparison), and their
    operands: unit keys and queries as the layer normalises them, the
    log-decay spread over ``(LOWER, 0)``."""
    import jax
    import jax.numpy as jnp

    from bluefog_tpu.ops.kda import LOWER

    b, t, h, d = shape
    keys = jax.random.split(jax.random.PRNGKey(0), 6)
    q, k, v, probe = (jax.random.normal(key, (b, t, h, d), jnp.float32)
                      for key in keys[:4])
    q = q / jnp.linalg.norm(q, axis=-1, keepdims=True) * d ** -0.5
    k = k / jnp.linalg.norm(k, axis=-1, keepdims=True)
    g = LOWER * jax.nn.sigmoid(2 * jax.random.normal(keys[4], (b, t, h, d)))
    beta = jax.nn.sigmoid(jax.random.normal(keys[5], (b, t, h)))
    operands = tuple(x.astype(jnp.bfloat16) for x in (q, k, v)) + (g, beta)

    def total(*operands):
        o = kda(*operands, backend=backend)
        return jnp.sum(o.astype(jnp.float32) * probe)

    forward = jax.jit(lambda *operands: kda(*operands, backend=backend))
    both = jax.jit(jax.value_and_grad(total, argnums=(0, 1, 2, 3, 4)))
    return {"forward": forward, "forward_backward": both}, operands


def measure(kda, shape, backend):
    """``{"forward": {"wall_ms": [...], "device_ms": {"total", "scopes",
    "instructions"} | None}, "forward_backward": ...}`` of one layer's scan
    through ``kda``."""
    import jax

    from moe_combine_bench import device_times, wall_times

    steps, operands = layer(kda, shape, backend)
    trace_dir = tempfile.mkdtemp(prefix="kda_bench.")
    out = {}
    try:
        for name, step in steps.items():
            jax.block_until_ready(step(*operands))
            out[name] = {"wall_ms": wall_times(step, operands),
                         "device_ms": device_times(step, operands,
                                                   trace_dir, KERNEL)}
    finally:
        shutil.rmtree(trace_dir, ignore_errors=True)
    return out


def main(argv=None):
    ap = argparse.ArgumentParser()
    ap.add_argument("--shape", default="cell", choices=sorted(SHAPES))
    ap.add_argument("--out", default=None,
                    help="also write the JSON line to this file")
    args = ap.parse_args(argv)
    import jax

    from bluefog_tpu.ops.kda import kda

    device = jax.devices()[0]
    backend = "pallas" if device.platform == "tpu" else "pallas_interpret"
    out = {"platform": device.platform, "device_kind": device.device_kind,
           "shape": dict(zip(("batch", "tokens", "heads", "width"),
                             SHAPES[args.shape])),
           **measure(kda, SHAPES[args.shape], backend)}
    line = json.dumps(out)
    if args.out:
        os.makedirs(os.path.dirname(os.path.abspath(args.out)), exist_ok=True)
        with open(args.out, "w") as f:
            f.write(line + "\n")
    print(line)
    return out


if __name__ == "__main__":
    main()
