"""Where a cell's step and the harness's plain reference part: one step of
each from the same state, read leaf by leaf.

``chipbench/run.py``'s agreement check holds three steps of the system
against ``chipbench/reference.py`` and reports each leaf's largest
difference.  Where the two do not end equal to the bit, this script says
whether either program differs from itself (the same state and batch through
it twice: a kernel that reads what it did not write shows here), whether the
first step's losses are equal to the bit (the forward passes), and after that
one step, leaf by leaf in the tree's order, how many elements differ and by
how much: ``LOSS`` and ``COMPARE`` lines, as ``gqa_moe_controls.py`` prints
its own.  The state is the one ``--preroll`` steps leave.  PERF.md section 6
(PR 43) has ``lfm2moe.t8192.solo``'s reading.

  chiprun --timeout 1200 -- python3 benchmarks/step_vs_reference.py \\
      --workload lfm2moe.t8192.solo --seed 2147490001 --preroll 33
"""

import argparse
import functools
import os
import sys

sys.path.insert(0, os.path.join(os.path.dirname(__file__), ".."))
sys.path.insert(0, os.path.dirname(__file__))

import jax
import numpy as np

import bluefog_tpu as bf
from chipbench import cell as cells
from chipbench import reference
from gqa_moe_controls import left_by_the_window, say


def differing(tag, got, want):
    """One ``COMPARE`` line: per leaf ``[path, elements that differ,
    elements, largest difference, largest magnitude]``."""
    rows = []
    for (path, a), b in zip(jax.tree_util.tree_leaves_with_path(got),
                            jax.tree_util.tree_leaves(want)):
        d = np.abs(a.astype(np.float64) - b.astype(np.float64))
        rows.append([jax.tree_util.keystr(path), int((d != 0).sum()),
                     int(d.size), float(d.max()), float(np.abs(b).max())])
    say("COMPARE", tag=tag, elements_differing=sum(r[1] for r in rows),
        leaves=rows)


def main(argv=None):
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--manifest", default=os.path.join(
        os.path.dirname(__file__), "..", "BENCHMARK.json"))
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--preroll", type=int, required=True,
                    help="steps before the probe: what the window completes")
    args = ap.parse_args(argv)

    bf.configure_compile_cache()
    cell = cells.build_cell(cells.Manifest.load(args.manifest),
                            args.workload, args.seed)
    if len(cell.devices) != 1:
        raise SystemExit("step_vs_reference: one-rank cells only (the "
                         "reference's mixing is not rebuilt here)")
    state = left_by_the_window(cell, args.preroll)
    batch = cell.ring[args.preroll % len(cell.ring)]
    shardings = jax.tree_util.tree_map(lambda a: a.sharding, state)
    # two copies on the host, the chip holds one state at a time
    whole = jax.tree_util.tree_map(np.array, jax.device_get(state))
    held, = reference.to_host(
        (state[0], state[1], state[2].base_state), cell.devices)
    del state

    def system_step():
        state, loss = cell.step(jax.tree_util.tree_map(
            jax.device_put, whole, shardings), batch)
        return jax.tree_util.tree_map(
            np.array, jax.device_get(state[0])), float(np.asarray(loss)[0])

    base_opt = cells.base_optimizer(cell.config)

    @functools.partial(jax.jit, donate_argnums=(1, 2))
    def local(params, model_state, opt_state, batch):     # reference.run's
        params, model_state, opt_state, batch = jax.tree_util.tree_map(
            lambda t: t[0], (params, model_state, opt_state, batch))
        (loss, model_state), grads = jax.value_and_grad(
            cell.family.loss, has_aux=True)(params, model_state, batch)
        updates, opt_state = base_opt.update(grads, opt_state, params)
        return jax.tree_util.tree_map(
            lambda t: t[None], (updates, model_state, opt_state, loss))

    rank_batch, = reference.per_rank(batch, cell.devices)

    def reference_step():
        on_chip, = reference.from_host([held], cell.devices)
        updates, _, _, loss = local(*on_chip, rank_batch)
        del on_chip, _
        updates = jax.tree_util.tree_map(np.array, jax.device_get(updates))
        # W = 1: p + update, the f32 sum reference._combine makes
        return (jax.tree_util.tree_map(np.add, held[0], updates),
                float(np.asarray(loss)[0]))

    first, loss = system_step()
    again, loss_again = system_step()
    say("LOSS", program="system", first=loss.hex(), again=loss_again.hex())
    differing("system_vs_itself", again, first)
    del again
    want, want_loss = reference_step()
    again, loss_again = reference_step()
    say("LOSS", program="reference", first=want_loss.hex(),
        again=loss_again.hex(), equals_the_system_s=want_loss == loss)
    differing("reference_vs_itself", again, want)
    del again
    differing("system_vs_reference", first, want)


if __name__ == "__main__":
    main()
