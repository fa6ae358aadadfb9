"""The controls a ``mamba2_gqa_moe`` cell's tolerances are held against, in
one process (the sound runs' readings come from ``chipbench/run.py``'s own
agreement reports, a seed a run): each control goes through the harness's
agreement check from the state ``--preroll`` steps leave, and prints what
``benchmarks/gqa_moe_controls.py`` prints (its ``run_one_seed`` is the command
line).

Step controls, which the leaves and the losses must catch: ``bf16_params``
(parameters rounded to bf16 after every step where f32 is stated) and
``lr_1.25`` (the reference at 1.25 x the rate).  Model controls, against the
plain model's loss (``model_loss_rtol``): ``none`` (the pair as it is);
lower precision in the system, ``decay_bf16`` (the log-decay's running sum
rounded to bf16 before the scan) and ``state_bf16`` (the state rounded to
bf16 from chunk to chunk); a changed plain model, ``no_skip`` (the ``D x``
term dropped), ``norm_all_channels`` (the gated norm's mean of squares over
all 4,096 channels, not 8 groups of 512), ``norm_before_gate`` (the norm
first, then the gate), ``relu_for_relu2`` (the experts' activation not
squared), ``no_scale`` (the routing weights not multiplied by 2.5),
``rotary_attention`` (queries and keys turned by a rotary at the config's
unread ``rope_theta``) and ``wrong_group`` (head ``h`` reading ``B`` and
``C`` of group ``h % 8``, not ``h // 8``).  PERF.md section 6 (PR 48) has
the readings.

  chiprun --timeout 1800 -- python3 benchmarks/mamba2_gqa_moe_controls.py \\
      --seed 2147489001 --controls all
"""

import os
import sys

sys.path.insert(0, os.path.join(os.path.dirname(__file__), ".."))
sys.path.insert(0, os.path.dirname(__file__))

import jax
import jax.numpy as jnp
from jax import lax

from bluefog_tpu.ops import ssd as ssd_ops
from chipbench import mamba2_gqa_moe_reference as ref
from gqa_moe_controls import STEP_CONTROLS, run_one_seed  # noqa: F401

MODEL_CONTROLS = ("none", "decay_bf16", "state_bf16", "no_skip",
                  "norm_all_channels", "norm_before_gate", "relu_for_relu2",
                  "no_scale", "rotary_attention", "wrong_group")
GROUPS = {"embedding": "['embedding']", "lm_head": "['lm_head']",
          "scale": "['scale']", "router": "['router']",
          "experts": "['moe']['w_", "shared": "['shared']",
          "attn": "['attn']", "mixer_proj": "_proj']",
          "mixer_conv": "['conv_", "A_log": "['A_log']", "D": "['D']",
          "dt_bias": "['dt_bias']", "norm_scale": "['norm_scale']"}
ROPE_THETA = 10000.0


def to_bf16(a):
    return lax.reduce_precision(a, exponent_bits=8, mantissa_bits=7)


def rotary(x, theta):
    """``x (B, T, H, R)`` turned over the whole head, half-split pairs."""
    half = x.shape[-1] // 2
    freq = theta ** (-jnp.arange(half, dtype=jnp.float32) / half)
    angle = jnp.arange(x.shape[1], dtype=jnp.float32)[:, None, None] * freq
    z = lax.complex(x[..., :half], x[..., half:]) * jnp.exp(1j * angle)
    return jnp.concatenate([z.real, z.imag], axis=-1)


def altered(name):
    """Change the system's scan (``decay_bf16``, ``state_bf16``: what is
    traced while it is in force) or the plain model (the others) in one
    place; returns what undoes it."""
    saved = (ssd_ops._scan, ssd_ops._group_chunk, ref.recurrence,
             ref.gated_norm, ref.relu2_mlp, ref.route, ref.attention)
    if name == "decay_bf16":
        ssd_ops._scan = lambda x, dt, cum, *rest: saved[0](
            x, dt, to_bf16(cum), *rest)
    elif name == "state_bf16":
        def rounded(s):     # a cast each way: the kernels take no
            return s.astype(jnp.bfloat16).astype(jnp.float32)  # reduce_precision

        def chunk(states, *operands):
            ys, after = saved[1](tuple(rounded(s) for s in states), *operands)
            return ys, tuple(rounded(s) for s in after)
        ssd_ops._group_chunk = chunk
    elif name == "no_skip":
        ref.recurrence = lambda x, delta, a, b, c, skip: saved[2](
            x, delta, a, b, c, jnp.zeros_like(skip))
    elif name == "norm_all_channels":
        ref.gated_norm = lambda y, z, scale, groups, eps: saved[3](
            y, z, scale, 1, eps)
    elif name == "norm_before_gate":
        def norm_then_gate(y, z, scale, groups, eps):
            runs = y.reshape(y.shape[:-1] + (groups, -1))
            normed = runs * lax.rsqrt(
                jnp.mean(runs * runs, axis=-1, keepdims=True) + eps)
            return normed.reshape(y.shape) * scale * jax.nn.silu(z)
        ref.gated_norm = norm_then_gate
    elif name == "relu_for_relu2":
        ref.relu2_mlp = lambda f, up, down: jax.nn.relu(f @ up) @ down
    elif name == "no_scale":
        ref.route = lambda router, bias, f, sizes: saved[5](
            router, bias, f, {**sizes, "scale": 1.0})
    elif name == "rotary_attention":
        ref.attention = lambda q, k, v: saved[6](
            rotary(q, ROPE_THETA), rotary(k, ROPE_THETA), v)
    elif name == "wrong_group":
        def regrouped(x, delta, a, b, c, skip):
            groups = b.shape[2]
            at = (jnp.arange(x.shape[2]) % groups)       # head h reads h % G
            return saved[2](x, delta, a, b[:, :, at], c[:, :, at], skip)
        ref.recurrence = regrouped

    def undo():
        (ssd_ops._scan, ssd_ops._group_chunk, ref.recurrence, ref.gated_norm,
         ref.relu2_mlp, ref.route, ref.attention) = saved
    return undo


def main(argv=None):
    run_one_seed(argv, description=__doc__.split("\n\n")[0],
                 workload="nemotron3nano.t8192.solo", preroll=40,
                 model_controls=MODEL_CONTROLS, altered=altered,
                 groups=GROUPS)


if __name__ == "__main__":
    main()
