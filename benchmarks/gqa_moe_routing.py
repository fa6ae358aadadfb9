"""How an expert cell's routed load moves while it trains (``gqa_moe``'s,
and with ``--workload`` any family's that sows ``moe_metrics``; blocks
without an expert layer have no load to read): the share of
each layer's assignments that falls on the held experts, the passes of the
row buffer, the fullest and emptiest held expert, and the step's time, every
``--every`` steps from the cell's own initial state.

A chip that holds 16 of 64 experts is a steady load only if that share stays
where the deployment puts it (0.25).  PERF.md section 6 (PR 34) has what this
script read on the chip with the router trained from the held experts'
outputs alone (``--train-router 1``: 0.25 -> 0.88) and with the routing
weights as constants of the backward pass (what the configuration states).

Run on the chip (one line a reading, a summary at the end):
  chiprun -- python3 benchmarks/gqa_moe_routing.py --seed 2147483801 --steps 40
"""

import argparse
import json
import os
import statistics
import sys
import time

sys.path.insert(0, os.path.join(os.path.dirname(__file__), ".."))

import jax
import numpy as np

import bluefog_tpu as bf
from chipbench import cell as cells


def overridden(args):
    """``cells.open_cell`` with the optimizer's rate and the deployment's
    ``router_trains`` replaced where the command line says so."""
    real = cells.open_cell

    def open_cell(manifest, workload):
        config, traffic = real(manifest, workload)
        if args.learning_rate is not None:
            config = {**config, "optimizer": {
                **config["optimizer"], "learning_rate": args.learning_rate}}
        if args.train_router is not None:
            config = {**config, "deployment": {
                **config["deployment"],
                "router_trains": bool(args.train_router)}}
        return config, traffic
    return open_cell


def main(argv=None):
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", default="smallthinker.t16384.solo")
    ap.add_argument("--manifest", default=os.path.join(
        os.path.dirname(__file__), "..", "BENCHMARK.json"))
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--steps", type=int, default=40)
    ap.add_argument("--every", type=int, default=4)
    ap.add_argument("--learning-rate", type=float)
    ap.add_argument("--train-router", type=int, choices=(0, 1))
    args = ap.parse_args(argv)

    bf.configure_compile_cache()
    cells.open_cell = overridden(args)
    cell = cells.build_cell(cells.Manifest.load(args.manifest),
                            args.workload, args.seed)
    model = cell.family.model

    @jax.jit
    def record(params, model_state, batch):
        params, model_state = jax.tree_util.tree_map(
            lambda t: t[0], (params, model_state))      # rank 0's
        _, sown = model.apply({"params": params, **model_state},
                              batch[0][:, :-1], mutable=["moe_metrics"])
        # the blocks that hold an expert layer, in order (a model of
        # single-sub-layer blocks has them among mixer blocks)
        blocks = sorted(sown["moe_metrics"], key=lambda b: int(b[6:]))
        return [{k: sown["moe_metrics"][b]["moe"][k][0]
                 for k in ("held_share", "row_passes", "rows_per_expert")}
                for b in blocks]

    def reading(step, state):
        layers = jax.device_get(record(*state[:2], cell.ring[step % len(
            cell.ring)]))
        return {"held_share": [round(float(r["held_share"]), 4)
                               for r in layers],
                "row_passes": [int(r["row_passes"]) for r in layers],
                "rows_min_max": [[int(r["rows_per_expert"].min()),
                                  int(r["rows_per_expert"].max())]
                                 for r in layers]}

    state, cell.state = cell.state, None
    readings, times = [], []
    for k in range(args.steps + 1):
        if k % args.every == 0 or k == args.steps:
            readings.append({"step": k, **reading(k, state)})
            print(json.dumps(readings[-1]), flush=True)
        if k == args.steps:
            break
        start = time.perf_counter()
        state, loss = cell.step(state, cell.ring[k % len(cell.ring)])
        loss = float(np.asarray(loss)[0])
        times.append((time.perf_counter() - start) * 1e3)
        print(json.dumps({"step": k, "step_ms": round(times[-1], 1),
                          "loss": round(loss, 4)}), flush=True)
    shares = np.array([r["held_share"] for r in readings])
    summary = {
        "workload": args.workload, "seed": args.seed, "steps": args.steps,
        "learning_rate": cell.config["optimizer"]["learning_rate"],
        "router_trains": cell.config["deployment"]["router_trains"],
        "held_share_first": readings[0]["held_share"],
        "held_share_last": readings[-1]["held_share"],
        "held_share_min": shares.min(axis=0).tolist(),
        "held_share_max": shares.max(axis=0).tolist(),
        "row_passes_max": np.max([r["row_passes"] for r in readings],
                                 axis=0).tolist(),
        # the first step of a process also waits for the program's load
        "step_ms_first_5": round(statistics.median(times[1:6]), 1),
        "step_ms_last_5": round(statistics.median(times[-5:]), 1)}
    print("SUMMARY " + json.dumps(summary), flush=True)
    return summary


if __name__ == "__main__":
    main()
