"""One Mamba-2 layer's state-space scan alone (``bluefog_tpu/ops/ssd.py``),
forward and forward + backward, as XLA compiles the ``'chunked'`` form (a
``lax.scan`` over chunks of 128) and as the kernels ``bf_ssd_fwd`` /
``bf_ssd_bwd``, at the shape ``nemotron3nano.t8192.solo`` gives a layer (2 x
8,192 tokens, 64 heads of 64, state 128, 8 groups, bf16 beside f32 steps)
beside the bound ``chipbench/mamba2_gqa_moe_flops.py::ssd_cost`` states (the
recurrence's operations over the matrix unit's peak, or the bytes no kernel
avoids over HBM's, whichever is more).  Wall ms a call over ``--iters``
calls, after two that warm up (the device is the only thing busy: a call is
one or two kernels and the running sum, or the scan's loop).

  chiprun -- python3 benchmarks/ssd_bench.py
  JAX_PLATFORMS=cpu python3 benchmarks/ssd_bench.py --shape tiny
"""

import argparse
import json
import os
import sys
import time

sys.path.insert(0, os.path.join(os.path.dirname(__file__), ".."))

import jax
import jax.numpy as jnp

from bluefog_tpu.ops.ssd import ssd
from chipbench.mamba2_gqa_moe_flops import ssd_cost
from chipbench.peaks import peaks_for

# batch, tokens, heads, head width, groups, state, dtype
SHAPES = {"cell": (2, 8192, 64, 64, 8, 128, jnp.bfloat16),
          "tiny": (2, 200, 4, 8, 2, 16, jnp.float32)}


def operands(shape):
    batch, t, h, p, g, n, dtype = shape
    keys = jax.random.split(jax.random.PRNGKey(0), 7)
    x = jax.random.normal(keys[0], (batch, t, h, p)).astype(dtype)
    dt = jax.nn.softplus(jax.random.normal(keys[1], (batch, t, h)) - 3.0)
    a = -jax.random.uniform(keys[2], (h,), minval=1.0, maxval=16.0)
    b = (0.3 * jax.random.normal(keys[3], (batch, t, g, n))).astype(dtype)
    c = (0.3 * jax.random.normal(keys[4], (batch, t, g, n))).astype(dtype)
    d = jnp.ones((h,))
    probe = jax.random.normal(keys[5], (batch, t, h, p)).astype(dtype)
    return (x, dt, a, b, c, d), probe


def measure(backend, shape, iters):
    args, probe = operands(shape)
    forward = jax.jit(lambda *a: ssd(*a, backend=backend))
    both = jax.jit(jax.grad(lambda *a: jnp.sum(
        (probe * ssd(*a, backend=backend)).astype(jnp.float32)),
        argnums=tuple(range(6))))
    out = {}
    for name, fn in (("forward_ms", forward), ("forward_backward_ms", both)):
        for _ in range(2):
            jax.block_until_ready(fn(*args))
        start = time.perf_counter()
        for _ in range(iters):
            result = fn(*args)
        jax.block_until_ready(result)
        out[name] = (time.perf_counter() - start) * 1e3 / iters
    return out


def main(argv=None):
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--shape", choices=sorted(SHAPES), default="cell")
    ap.add_argument("--iters", type=int, default=10)
    args = ap.parse_args(argv)
    shape = SHAPES[args.shape]
    batch, t, h, p, g, n, dtype = shape
    device = jax.devices()[0]
    on_chip = device.platform == "tpu"
    record = {"platform": device.platform, "shape": list(shape[:6])}
    if on_chip:
        peak_flops, peak_bytes = peaks_for(device.device_kind)
        once, twice = (ssd_cost(batch, t, h, p, n, g, forward_calls=calls,
                                itemsize=jnp.dtype(dtype).itemsize)
                       for calls in (1, 2))
        # a second forward call's cost is the forward pass's own
        for name, (ops, nbytes) in (
                ("forward", (twice[0] - once[0], twice[1] - once[1])),
                ("forward_backward", once)):
            record[f"{name}_bound_ms"] = max(ops / peak_flops,
                                             nbytes / peak_bytes) * 1e3
    record["xla"] = measure("chunked", shape, args.iters)
    record["kernels"] = measure("pallas" if on_chip else "pallas_interpret",
                                shape, args.iters)
    print(json.dumps(record), flush=True)
    return record


if __name__ == "__main__":
    main()
