"""The controls a ``linear_latent_moe`` cell's tolerances are held against,
in one process (the sound runs' readings come from ``chipbench/run.py``'s own
agreement reports, a seed a run): each control goes through the harness's
agreement check from the state ``--preroll`` steps leave, and prints what
``benchmarks/gqa_moe_controls.py`` prints (its helpers are used as they are).

Step controls, which the leaves and the losses must catch: ``bf16_params``
(parameters rounded to bf16 after every step where f32 is stated) and
``lr_1.25`` (the reference at 1.25 x the rate).  Model controls, against the
plain model's loss (``model_loss_rtol``): ``none`` (the pair as it is);
lower precision in the system, ``decay_bf16`` (the KDA log-decay rounded to
bf16 before its running sum); a changed plain model, ``scalar_decay`` (one
decay a head, the mean of its channels'), ``another_lower_bound`` (-1 for
-5), ``ungrouped_routing`` (a plain top-8 of 512) and ``no_short_conv``
(the current token's tap alone).
PERF.md section 6 (PR 41) has the readings.

  chiprun --timeout 1800 -- python3 benchmarks/linear_latent_moe_controls.py \\
      --seed 2147489001 --controls all
"""

import os
import sys

sys.path.insert(0, os.path.join(os.path.dirname(__file__), ".."))
sys.path.insert(0, os.path.dirname(__file__))

import jax
import jax.numpy as jnp

from bluefog_tpu.models import transformer
from chipbench import linear_latent_moe_reference as ref
from gqa_moe_controls import STEP_CONTROLS, run_one_seed  # noqa: F401

MODEL_CONTROLS = ("none", "decay_bf16", "scalar_decay", "another_lower_bound",
                  "ungrouped_routing", "no_short_conv")


def altered(name):
    """Change the system (``decay_bf16``) or the plain model (the others)
    in one place; returns what undoes it."""
    saved = (transformer.kda, ref.delta_rule, ref.chosen_experts, ref.kda,
             ref.causal_conv)
    if name == "decay_bf16":
        transformer.kda = lambda q, k, v, g, beta: saved[0](
            q, k, v, jax.lax.reduce_precision(g, exponent_bits=8,
                                              mantissa_bits=7), beta)
    elif name == "scalar_decay":
        ref.delta_rule = lambda q, k, v, g, beta: saved[1](
            q, k, v, jnp.broadcast_to(g.mean(-1, keepdims=True), g.shape),
            beta)
    elif name == "ungrouped_routing":
        ref.chosen_experts = lambda steer, top_k, n_group, topk_group: (
            saved[2](steer, top_k, 1, 1))
    elif name == "another_lower_bound":
        ref.kda = lambda p, x, sizes: saved[3](
            p, x, {**sizes, "lower_bound": -1.0})
    elif name == "no_short_conv":
        ref.causal_conv = lambda x, kernel: kernel[-1] * x

    def undo():
        (transformer.kda, ref.delta_rule, ref.chosen_experts, ref.kda,
         ref.causal_conv) = saved
    return undo


def main(argv=None):
    run_one_seed(argv, description=__doc__.split("\n\n")[0],
                 workload="ling3flash.t8192.solo", preroll=50,
                 model_controls=MODEL_CONTROLS, altered=altered)


if __name__ == "__main__":
    main()
