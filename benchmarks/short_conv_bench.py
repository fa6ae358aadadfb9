"""One short-convolution layer's gate, convolution and gate alone
(``bluefog_tpu/ops/short_conv.py``), forward and forward + backward, as XLA
compiles the ``jax.numpy`` form and as the kernels ``bf_sconv_fwd`` /
``bf_sconv_bwd``, at the shape ``lfm2moe.t8192.solo`` gives a layer (4 x
8,192 tokens of 2,048 channels, bf16, 3 taps) beside the memory bound
``chipbench/conv_gqa_moe_flops.py::gate_conv_cost`` states.  Wall ms a call
over ``--iters`` calls, after two that warm up (the device is the only
thing busy: a call is one or two kernels or a handful of fusions).

  chiprun -- python3 benchmarks/short_conv_bench.py
  JAX_PLATFORMS=cpu python3 benchmarks/short_conv_bench.py --shape tiny
"""

import argparse
import json
import os
import sys
import time

sys.path.insert(0, os.path.join(os.path.dirname(__file__), ".."))

import jax
import jax.numpy as jnp

from bluefog_tpu.ops import short_conv
from chipbench.conv_gqa_moe_flops import gate_conv_cost
from chipbench.peaks import peaks_for

SHAPES = {"cell": (4, 8192, 2048, jnp.bfloat16),
          "tiny": (2, 64, 128, jnp.float32)}


def measure(backend, shape, iters, tiles=None):
    batch, t, d, dtype = shape
    keys = jax.random.split(jax.random.PRNGKey(0), 3)
    bcz = jax.random.normal(keys[0], (batch, t, 3 * d)).astype(dtype)
    kernel = jax.random.uniform(keys[1], (3, d), minval=-0.57, maxval=0.57)
    probe = jax.random.normal(keys[2], (batch, t, d)).astype(dtype)
    real = short_conv._tiles
    if tiles is not None:
        short_conv._tiles = lambda t, d: tiles
    try:
        forward = jax.jit(lambda bcz, kernel: short_conv.gated_short_conv(
            bcz, kernel, backend=backend))
        both = jax.jit(jax.grad(lambda bcz, kernel: jnp.sum(
            (probe * short_conv.gated_short_conv(
                bcz, kernel, backend=backend)).astype(jnp.float32)),
            argnums=(0, 1)))
        out = {}
        for name, fn in (("forward_ms", forward), ("forward_backward_ms",
                                                   both)):
            for _ in range(2):
                jax.block_until_ready(fn(bcz, kernel))
            start = time.perf_counter()
            for _ in range(iters):
                result = fn(bcz, kernel)
            jax.block_until_ready(result)
            out[name] = (time.perf_counter() - start) * 1e3 / iters
    finally:
        short_conv._tiles = real
    return out


def main(argv=None):
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--shape", choices=sorted(SHAPES), default="cell")
    ap.add_argument("--iters", type=int, default=20)
    ap.add_argument("--tiles", default="",
                    help="also time the kernels at these 'tokens x channels'"
                    " tiles, comma-separated (256x512,512x512)")
    args = ap.parse_args(argv)
    shape = SHAPES[args.shape]
    device = jax.devices()[0]
    on_chip = device.platform == "tpu"
    record = {"platform": device.platform, "shape": list(shape[:3])}
    if on_chip:
        _, nbytes = gate_conv_cost(shape[0] * shape[1], shape[2],
                                   forward_calls=1,
                                   itemsize=jnp.dtype(shape[3]).itemsize)
        record["forward_backward_bound_ms"] = (
            nbytes / peaks_for(device.device_kind)[1] * 1e3)
    record["xla"] = measure("xla", shape, args.iters)
    kernels = "pallas" if on_chip else "pallas_interpret"
    record["kernels"] = measure(kernels, shape, args.iters)
    for tiles in (x for x in args.tiles.split(",") if x):
        tt, dc = (int(n) for n in tiles.split("x"))
        try:
            record[f"kernels.{tiles}"] = measure(kernels, shape, args.iters,
                                                 (tt, dc))
        except jax.errors.JaxRuntimeError as e:     # tiles past the VMEM
            record[f"kernels.{tiles}"] = str(e).split(":", 1)[0]
    print(json.dumps(record), flush=True)
    return record


if __name__ == "__main__":
    main()
