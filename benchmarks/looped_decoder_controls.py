"""The controls a ``looped_decoder`` cell's tolerances are held against, in
one process (the sound runs' readings come from ``chipbench/run.py``'s own
agreement reports, a seed a run): each control goes through the harness's
agreement check from the state ``--preroll`` steps leave, and prints what
``benchmarks/gqa_moe_controls.py`` prints (its ``run_one_seed`` is the command
line).

Step controls, which the leaves and the losses must catch: ``bf16_params``
(parameters rounded to bf16 after every step where f32 is stated) and
``lr_1.25`` (the reference at 1.25 x the rate).  Model controls, against the
plain model's loss (``model_loss_rtol``): ``none`` (the pair as it is);
``no_fourth_round`` (three rounds, the third taking what is left),
``no_post_norms`` (a sub-layer's output into the residual sum un-normed),
``beta_0`` (no entropy term), ``uniform_p`` (every exit weighted 1/4
whatever the gate says) and ``interleaved_rotary`` (pairs ``(2i, 2i + 1)``
where the source pairs the head's halves).  PERF.md section 6 (PR 51) has
the readings.

  chiprun --timeout 1800 -- python3 benchmarks/looped_decoder_controls.py \\
      --seed 2147489001 --controls all
"""

import os
import sys

sys.path.insert(0, os.path.join(os.path.dirname(__file__), ".."))
sys.path.insert(0, os.path.dirname(__file__))

import jax.numpy as jnp

from chipbench import looped_decoder_reference as ref
from gqa_moe_controls import STEP_CONTROLS, run_one_seed  # noqa: F401

MODEL_CONTROLS = ("none", "no_fourth_round", "no_post_norms", "beta_0",
                  "uniform_p", "interleaved_rotary")
GROUPS = {"embedding": "['embedding']", "lm_head": "['lm_head']",
          "scale": "['scale']", "exit_gate": "['exit_gate']",
          "attn": "['attn']", "mlp": "['mlp']"}


def altered(name):
    """Change the plain model in one place; returns what undoes it."""
    saved = (ref.loss, ref.after, ref.exit_probabilities, ref.rotary)
    if name == "no_fourth_round":
        ref.loss = lambda sizes, *a: saved[0](
            {**sizes, "rounds": sizes["rounds"] - 1}, *a)
    elif name == "beta_0":
        ref.loss = lambda sizes, *a: saved[0]({**sizes, "beta": 0.0}, *a)
    elif name == "no_post_norms":
        ref.after = lambda y, scale, eps: y
    elif name == "uniform_p":
        ref.exit_probabilities = lambda g: jnp.full_like(g, 1.0 / len(g))
    elif name == "interleaved_rotary":
        ref.rotary = lambda x, positions, theta: saved[3](
            x.reshape(x.shape[:-1] + (-1, 2)).swapaxes(-1, -2).reshape(
                x.shape), positions, theta)

    def undo():
        ref.loss, ref.after, ref.exit_probabilities, ref.rotary = saved
    return undo


def main(argv=None):
    run_one_seed(argv, description=__doc__.split("\n\n")[0],
                 workload="ouro.t4096.solo", preroll=40,
                 model_controls=MODEL_CONTROLS, altered=altered,
                 groups=GROUPS)


if __name__ == "__main__":
    main()
