"""The controls a ``conv_gqa_moe`` cell's tolerances are held against, in one
process (the sound runs' readings come from ``chipbench/run.py``'s own
agreement reports, a seed a run): each control goes through the harness's
agreement check from the state ``--preroll`` steps leave, and prints what
``benchmarks/gqa_moe_controls.py`` prints (its ``run_one_seed`` is the command
line).

Step controls, which the leaves and the losses must catch: ``bf16_params``
(parameters rounded to bf16 after every step where f32 is stated) and
``lr_1.25`` (the reference at 1.25 x the rate); and with the reference's
three steps taken by a changed model (the system's own, altered in one
place, so its gradients are what the leaves are held to):
``step_router_bf16`` (the router's input and weights rounded to bf16, so
the chosen sets flip), ``step_eps_1e-2`` (the normaliser's 1e-6 as 1e-2)
and ``step_no_qk_norm`` (the per-head norms of q and k dropped; their
scales then get no gradient).  Model controls, against the
plain model's loss (``model_loss_rtol``): ``none`` (the pair as it is);
lower precision in the system, ``router_bf16`` (the router's input and
weights rounded to bf16 before its matmul, as the source runs it); a
changed plain model, ``eps_1e-2`` (the normaliser's 1e-6 as 1e-2),
``no_qk_norm`` (the per-head norms of q and k dropped), ``no_rotary`` (the
attention layer turns nothing), ``tap_dropped`` (the convolution's oldest
tap at zero) and ``no_conv`` (the current token's tap alone).
PERF.md section 6 (PR 43) has the readings.

  chiprun --timeout 1800 -- python3 benchmarks/conv_gqa_moe_controls.py \\
      --seed 2147489001 --controls all
"""

import os
import sys

sys.path.insert(0, os.path.join(os.path.dirname(__file__), ".."))
sys.path.insert(0, os.path.dirname(__file__))

import flax.linen as nn
import jax
import jax.numpy as jnp

from bluefog_tpu.models import transformer
from chipbench import conv_gqa_moe_reference as ref
from gqa_moe_controls import STEP_CONTROLS, run_one_seed  # noqa: F401

REFERENCE_CONTROLS = ("step_router_bf16", "step_eps_1e-2", "step_no_qk_norm")
MODEL_CONTROLS = ("none", "router_bf16", "eps_1e-2", "no_qk_norm",
                  "no_rotary", "tap_dropped", "no_conv")
GROUPS = {"embedding": "['embedding']", "scale": "['scale']",
          "router": "['router']", "experts": "['moe']['w_",
          "attn": "['attn']", "conv": "['conv']", "mlp": "['mlp']"}


def to_bf16(a):
    return jax.lax.reduce_precision(a, exponent_bits=8, mantissa_bits=7)


def altered(name):
    """Change the system's model (``router_bf16`` and the ``step_``
    controls: what is traced while it is in force) or the plain model (the
    others) in one place; returns what undoes it."""
    saved = (transformer.sigmoid_topk_router, ref.route, ref.rms, ref.rotary,
             ref.causal_conv, nn.RMSNorm)
    if name in ("router_bf16", "step_router_bf16"):
        transformer.sigmoid_topk_router = lambda x, kernel, *a, **kw: saved[0](
            to_bf16(x.astype(jnp.float32)), to_bf16(kernel), *a, **kw)
    elif name == "step_eps_1e-2":
        transformer.sigmoid_topk_router = lambda *a, **kw: saved[0](
            *a, **{**kw, "eps": 1e-2})
    elif name == "step_no_qk_norm":
        # the scales stay in the tree, unread: their gradient is zero
        nn.RMSNorm = lambda *a, name=None, **kw: (
            (lambda x: x.astype(jnp.float32)) if name in ("q_norm", "k_norm")
            else saved[5](*a, name=name, **kw))
    elif name == "eps_1e-2":
        ref.route = lambda router, bias, f, sizes: saved[1](
            router, bias, f, {**sizes, "weight_eps": 1e-2})
    elif name == "no_qk_norm":
        # the per-head norms are the ones over (B, T, heads, head_dim)
        ref.rms = lambda x, scale, eps: (
            x if x.ndim == 4 else saved[2](x, scale, eps))
    elif name == "no_rotary":
        ref.rotary = lambda x, positions, theta: x
    elif name == "tap_dropped":
        ref.causal_conv = lambda s, kernel: saved[4](
            s, kernel.at[0].set(0.0))
    elif name == "no_conv":
        ref.causal_conv = lambda s, kernel: kernel[-1] * s

    def undo():
        (transformer.sigmoid_topk_router, ref.route, ref.rms, ref.rotary,
         ref.causal_conv, nn.RMSNorm) = saved
    return undo


def main(argv=None):
    run_one_seed(argv, description=__doc__.split("\n\n")[0],
                 workload="lfm2moe.t8192.solo", preroll=33,
                 model_controls=MODEL_CONTROLS, altered=altered,
                 groups=GROUPS, reference_controls=REFERENCE_CONTROLS)


if __name__ == "__main__":
    main()
