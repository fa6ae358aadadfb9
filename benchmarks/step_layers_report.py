"""The step by layer, instruction by instruction, from one traced run of a
benchmark cell: what PERF.md section 5's layer table is made from.

``chipbench/run.py`` prints one number a metric; the phase an instruction
fell to, and the phases no metric prints (``attention_kernel``,
``expert_ffn``, ``ssm_scan``), stay inside ``reducers/scope_ms.py``, whose
report belongs to ``phases/step.json`` alone (it names its file by cell and
seed).  This script runs the harness with its own arguments and writes every
attribution the run's metrics make to
``chipbench_out/<cell>.seed<n>.<rules>.layers.json``: the phases in ms a step
and chip (they sum to ``device_ms_per_step``), ``held`` and the mixed
fusions, the join's seconds, and every instruction above 0.002 ms a step with
its phase, its pass under ``step.json``, its ``op_name`` and what it fuses.

Run on the chip, from the root of a checkout (the result line is the
harness's; one ``step_layers_report:`` line a rule table goes before it):
  chiprun -- python3 benchmarks/step_layers_report.py \
      --workload gpt2s.t2048.solo --seed 2147492011 --seconds 20 --trace 1
"""

import collections
import json
import os
import re
import sys

sys.path.insert(0, os.path.join(os.path.dirname(__file__), ".."))

from chipbench import run, xplane
from chipbench.cell import REPO
from chipbench.reducers import scope_ms

BY_PASS = "step"
FLOOR_MS = 0.002     # a step: instructions under it are summed by name


def layers(measured, rules_name, att, floor_ms=FLOOR_MS):
    """``att`` (``scope_ms.attribute``'s) per step and chip, with the
    instructions behind it."""
    per_step = 1e6 * measured.traced_steps * len(measured.trace.lanes)
    program = scope_ms.Program(measured.hlo, scope_ms.load_rules(rules_name))
    by_pass = scope_ms.Program(measured.hlo, scope_ms.load_rules(BY_PASS))
    self_ns = collections.Counter()
    for events in measured.trace.lanes.values():
        for name, ns in xplane.self_times(events):
            self_ns[name] += ns
    rows, small = [], collections.Counter()
    for name, ns in self_ns.most_common():
        phase = program.phase(name)
        if ns / per_step < floor_ms:
            small[f"{phase}|{xplane.base_name(name)}"] += ns / per_step
            continue
        rows.append({"phase": phase, "pass": by_pass.phase(name),
                     "name": name, "ms_per_step": ns / per_step,
                     "op_name": program.op_name(name) or "",
                     "fused": sorted(program.fused_phases(name))})
    phases = {p: att["ns"][p] / per_step for p in att["phases"]}
    return {"cell": measured.cell.name, "rules": rules_name,
            "unit": "ms per step and chip", "phases_ms": phases,
            "device_ms_per_step": sum(phases.values()),
            "held_ms": {p: att["held"][p] / per_step for p in att["phases"]},
            "mixed_ms": {k: v / per_step for k, v in att["mixed"].items()},
            "reducer_seconds": att["seconds"], "rows": rows,
            "below_floor_ms": dict(small)}


def head_loss_chunks(cell):
    """How ``ops/head_loss.py`` cut each ``head_loss`` call of the cell's
    loss: ``{site: {"chunks": n, "chunk_rows": rows}}`` from the gauges the
    call sets as it is traced, read off one more trace of the loss with
    metrics on (shapes alone, nothing runs: the step itself cannot be built
    with metrics on).  Empty where the loss makes no such call."""
    import jax

    from bluefog_tpu.metrics import registry

    key = jax.random.PRNGKey(0)
    family = cell.family
    params, model_state = jax.eval_shape(family.init, key)
    batch = jax.eval_shape(family.make_batch, key)
    reg = registry.metrics_start()
    try:
        jax.eval_shape(family.loss, params, model_state, batch)
        snapshot = reg.snapshot()
    finally:
        registry.metrics_stop()
    sites = collections.defaultdict(dict)
    for series, value in snapshot.items():
        found = re.fullmatch(r'bf_head_loss_(chunks|chunk_rows)'
                             r'\{site="(\w+)"\}', series)
        if found:
            sites[found.group(2)][found.group(1)] = int(value)
    return dict(sites)


def main(argv=None):
    harness_argv = sys.argv[1:] if argv is None else list(argv)
    attribute = scope_ms.attribute

    def attribute_and_write(measured, rules_name):
        att = attribute(measured, rules_name)
        table = layers(measured, rules_name, att)
        chunks = ({"head_loss_chunks": head_loss_chunks(measured.cell)}
                  if "head_loss" in table["phases_ms"] else {})
        table.update(chunks)
        seed = scope_ms.seed_of_this_run()
        path = os.path.join(REPO, "chipbench_out", (
            f"{measured.cell.name}.seed{seed}.{rules_name}.layers.json"))
        os.makedirs(os.path.dirname(path), exist_ok=True)
        with open(path, "w") as f:
            json.dump(table, f)
        print("step_layers_report: " + json.dumps(
            {"rules": rules_name, "file": os.path.relpath(path, REPO),
             "reducer_seconds": round(att["seconds"], 3),
             "phases_ms": {p: round(v, 3)
                           for p, v in table["phases_ms"].items()},
             **chunks}),
            flush=True)
        return att

    # the metrics reach the join through this name (scope_ms.attribution)
    # and the report's seed through the command line (seed_of_this_run)
    scope_ms.attribute, argv0 = attribute_and_write, sys.argv
    sys.argv = [argv0[0]] + harness_argv
    try:
        return run.main(harness_argv)
    finally:
        scope_ms.attribute, sys.argv = attribute, argv0


if __name__ == "__main__":
    sys.exit(main())
