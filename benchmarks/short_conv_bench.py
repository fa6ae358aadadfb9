"""One layer's short convolution alone (``bluefog_tpu/ops/short_conv.py``),
forward and forward + backward, as XLA compiles the ``jax.numpy`` form and
as the kernels, beside the memory bound.  Wall ms a call over ``--iters``
calls, after two that warm up (the device is the only thing busy: a call is
a few kernels or a handful of fusions), and on a chip the device's own ms
by instruction from a trace of the forward + backward call (value and
gradients: the gradients alone need no forward kernel, which would drop out).

- ``--shape cell``: the gate, convolution and gate (``bf_sconv_fwd`` /
  ``bf_sconv_bwd``) at the shape ``lfm2moe.t8192.solo`` gives a layer (4 x
  8,192 tokens of 2,048 channels, bf16, 3 taps); the bound is
  ``chipbench/conv_gqa_moe_flops.py::gate_conv_cost``'s.
- ``--shape nemotron``: the convolution, bias and SiLU (``bf_cconv_fwd`` /
  ``bf_cconv_bwd``) as ``nemotron3nano.t8192.solo``'s mixer calls it: the in
  projection's ``(2, 8192, 10304)`` output whole, channels 4,096 : 10,240 in
  the scan's three pieces, 4 taps, bf16.  The bounds are bf16 in and out
  forward (4 B an element) and ``x``, the cotangent in and one cotangent out
  backward (6 B): 0.49 and 1.23 ms.  The measured forward + backward also
  holds the pass that pads the three ``dx`` to the projection's width and
  adds them (in the cell's step that pass takes ``dz`` and ``d dt`` in as
  well, behind ``Mamba2Mixer``'s fence), and the operand's copy below.
- ``--shape ling3flash``: one KDA layer's three chains as
  ``ling3flash.t8192.solo``'s mixer calls them: the q, k and v projections'
  ``(1, 8192, 2048)`` outputs, 4 taps and no bias, q and k normalised over
  each head's 128 channels (q times ``128 ** -0.5``) inside the same two
  kernels, bf16.  The ``jax.numpy`` form is ``KdaMixer``'s before them: f32
  from the convolution through the norm.  The bounds a tensor: 0.067 GB
  forward and 0.10 GB backward.

  chiprun -- python3 benchmarks/short_conv_bench.py --shape cell,nemotron,ling3flash --out chiprun_out/short_conv_v5e.json
  JAX_PLATFORMS=cpu python3 benchmarks/short_conv_bench.py --shape tiny,nemotron_tiny,ling3flash_tiny

``benchmarks/short_conv_v5e.json`` is a chip run's.
"""

import argparse
import json
import os
import shutil
import sys
import tempfile
import time

sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, os.path.join(os.path.dirname(__file__), ".."))

import jax
import jax.numpy as jnp

from bluefog_tpu.ops import short_conv
from chipbench.conv_gqa_moe_flops import gate_conv_cost
from chipbench.peaks import peaks_for
from moe_combine_bench import device_times

# gated: batch, tokens, channels, dtype; convolution and SiLU: batch, tokens,
# the operand's width, dtype, the pieces' first channels and the last's end;
# a KDA layer's q, k and v: batch, tokens, channels, dtype, a head's channels
SHAPES = {
    "cell": (4, 8192, 2048, jnp.bfloat16),
    "tiny": (2, 64, 128, jnp.float32),
    "nemotron": (2, 8192, 10304, jnp.bfloat16, (4096, 8192, 9216, 10240)),
    "nemotron_tiny": (2, 64, 832, jnp.float32, (256, 512, 640, 768)),
    "ling3flash": (1, 8192, 2048, jnp.bfloat16, 128),
    "ling3flash_tiny": (2, 64, 256, jnp.float32, 128)}
SILU_TAPS = 4


def _builder(shape):
    if len(shape) == 4:
        return _gated
    return _silu if isinstance(shape[4], tuple) else _kda


def _gated(shape, backend):
    batch, t, d, dtype = shape
    keys = jax.random.split(jax.random.PRNGKey(0), 3)
    bcz = jax.random.normal(keys[0], (batch, t, 3 * d)).astype(dtype)
    kernel = jax.random.uniform(keys[1], (3, d), minval=-0.57, maxval=0.57)
    probe = jax.random.normal(keys[2], (batch, t, d)).astype(dtype)

    def forward(bcz, kernel):
        return short_conv.gated_short_conv(bcz, kernel, backend=backend)

    def total(bcz, kernel):
        return jnp.sum((probe * forward(bcz, kernel)).astype(jnp.float32))

    return forward, jax.value_and_grad(total, argnums=(0, 1)), (bcz, kernel)


def _silu(shape, backend):
    batch, t, width, dtype, edges = shape
    first, channels = edges[0], edges[-1] - edges[0]
    keys = jax.random.split(jax.random.PRNGKey(0), 4)
    x = jax.random.normal(keys[0], (batch, t, width)).astype(dtype)
    kernel = jax.random.uniform(keys[1], (SILU_TAPS, channels), minval=-0.5,
                                maxval=0.5)
    bias = jax.random.uniform(keys[2], (channels,), minval=-0.5, maxval=0.5)
    pieces = [hi - lo for lo, hi in zip(edges, edges[1:])]
    probes = [jax.random.normal(key, (batch, t, width)).astype(dtype)
              for key, width in zip(jax.random.split(keys[3], len(pieces)),
                                    pieces)]

    def forward(x, kernel, bias):       # as Mamba2Mixer calls it
        return short_conv.silu_short_conv(x, kernel, bias, offset=first,
                                          pieces=pieces, backend=backend)

    def total(x, kernel, bias):
        return sum(jnp.sum((probe * out).astype(jnp.float32)) for probe, out
                   in zip(probes, forward(x, kernel, bias)))

    return forward, jax.value_and_grad(total, argnums=(0, 1, 2)), (
        x, kernel, bias)


def _kda(shape, backend):
    batch, t, channels, dtype, head = shape
    keys = jax.random.split(jax.random.PRNGKey(0), 9)
    xs = [jax.random.normal(key, (batch, t, channels)).astype(dtype)
          for key in keys[:3]]
    kernels = [jax.random.uniform(key, (SILU_TAPS, channels), minval=-0.5,
                                  maxval=0.5) for key in keys[3:6]]
    probes = [jax.random.normal(key, (batch, t, channels)).astype(dtype)
              for key in keys[6:]]
    norms = ((head, 1e-6, head ** -0.5), (head, 1e-6, 1.0), None)
    no_bias = jnp.zeros((channels,), jnp.float32)

    def forward(xs, kernels):           # as KdaMixer calls it
        return [short_conv.silu_short_conv(x, kernel, no_bias, l2norm=norm,
                                           backend=backend)
                for x, kernel, norm in zip(xs, kernels, norms)]

    def total(xs, kernels):
        return sum(jnp.sum((probe * out).astype(jnp.float32)) for probe, out
                   in zip(probes, forward(xs, kernels)))

    return forward, jax.value_and_grad(total, argnums=(0, 1)), (xs, kernels)


def measure(backend, shape, iters, tiles=None):
    forward, both, operands = _builder(shape)(shape, backend)
    real = short_conv._tiles
    if tiles is not None:
        short_conv._tiles = lambda *shape: tiles
        jax.clear_caches()      # the jitted kernel calls keep their traces
    try:
        forward, both = jax.jit(forward), jax.jit(both)
        out = {}
        for name, fn in (("forward_ms", forward), ("forward_backward_ms",
                                                   both)):
            for _ in range(2):
                jax.block_until_ready(fn(*operands))
            start = time.perf_counter()
            for _ in range(iters):
                result = fn(*operands)
            jax.block_until_ready(result)
            out[name] = (time.perf_counter() - start) * 1e3 / iters
        # the wall holds what stands around the op here and not in a step (an
        # operand of 10,304 channels is copied into the tiled layout a
        # matmul would write it in): the device's own account, by instruction
        trace_dir = tempfile.mkdtemp(prefix="short_conv_bench.")
        try:
            device = device_times(both, operands, trace_dir)
        finally:
            shutil.rmtree(trace_dir, ignore_errors=True)
        if device is not None:
            out["forward_backward_device_ms"] = device["instructions"]
    finally:
        short_conv._tiles = real
        if tiles is not None:
            jax.clear_caches()
    return out


def bounds_ms(shape, hbm_bytes_per_s):
    """The least a forward call, and a forward and a backward call, could
    take: the bytes no kernel avoids over the chip's HBM rate."""
    if len(shape) == 5:
        batch, t, channels, dtype, edges = shape
        elements = batch * t * (edges[-1] - edges[0] if isinstance(
            edges, tuple) else 3 * channels)
        forward = 2 * elements * jnp.dtype(dtype).itemsize
        both = 5 * elements * jnp.dtype(dtype).itemsize
    else:
        batch, t, d, dtype = shape
        both, backward = (gate_conv_cost(
            batch * t, d, forward_calls=calls,
            itemsize=jnp.dtype(dtype).itemsize)[1] for calls in (1, 0))
        forward = both - backward
    return {"forward_bound_ms": forward / hbm_bytes_per_s * 1e3,
            "forward_backward_bound_ms": both / hbm_bytes_per_s * 1e3}


def main(argv=None):
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--shape", default="cell",
                    help="comma-separated, of " + ", ".join(sorted(SHAPES)))
    ap.add_argument("--iters", type=int, default=20)
    ap.add_argument("--tiles", default="",
                    help="also time the kernels at these 'tokens x channels'"
                    " tiles, comma-separated (256x512,512x512)")
    ap.add_argument("--out", default=None, help="write the record here too")
    args = ap.parse_args(argv)
    device = jax.devices()[0]
    on_chip = device.platform == "tpu"
    kernels = "pallas" if on_chip else "pallas_interpret"
    records = {"platform": device.platform, "device_kind": device.device_kind}
    for name in args.shape.split(","):
        shape = SHAPES[name]
        record = {"shape": list(shape[:3])}
        if on_chip:
            record.update(bounds_ms(shape, peaks_for(device.device_kind)[1]))
        record["xla"] = measure("xla", shape, args.iters)
        record["kernels"] = measure(kernels, shape, args.iters)
        for tiles in (x for x in args.tiles.split(",") if x):
            tt, dc = (int(n) for n in tiles.split("x"))
            try:
                record[f"kernels.{tiles}"] = measure(kernels, shape,
                                                     args.iters, (tt, dc))
            except jax.errors.JaxRuntimeError as e:     # tiles past the VMEM
                record[f"kernels.{tiles}"] = str(e).split(":", 1)[0]
        print(name, json.dumps(record), flush=True)
        records[name] = record
    if args.out:
        os.makedirs(os.path.dirname(os.path.abspath(args.out)), exist_ok=True)
        with open(args.out, "w") as f:
            f.write(json.dumps(records) + "\n")
    return records


if __name__ == "__main__":
    main()
