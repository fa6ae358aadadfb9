"""Device time of one phase of the step, per step and chip: forward,
backward, recompute, optimizer, gossip fuse/split, gossip pack/unpack, the
exchange, and what no phase owns.

The device trace carries no scope: an op event is named by its instruction
and nothing else.  The scope reaches the metric through a join.  A trace
event's name (``xplane.instruction_name``) is looked up in the compiled
step's HLO text (``measured.hlo``), where the instruction's line holds
``metadata={op_name="jit(step)/shard_map/bf.optim.apply/add"}``: the JAX
name stack at the point the op was traced, with the program's
``jax.named_scope``s (``bf.<layer>.<phase>``), flax's module names and JAX's
transform names (``jvp(..)``, ``transpose(..)``, ``rematted_computation``).

Each instruction falls into exactly one phase: that of the first rule of
``phases/<params["rules"]>.json`` that matches.  A rule is
``[phase, field, regex]`` with ``field`` either ``op_name`` or ``line`` (the
instruction's whole HLO line).  What matches no rule is
``other`` if it has an ``op_name`` and ``unattributed`` if it has none or
is missing from the text, with one exception: a fusion without an
``op_name`` of its own whose fused computation holds ops of one phase only
is booked under that phase.  A fusion with a name is booked under it (it is
its root's); the *mixed share* in the report says how far that can be
wrong.  Self times come from ``xplane.self_times``, so the phases of a lane
add up to its busy time.

``params``: ``rules`` (the table's name), ``phases`` (the phases to sum),
optionally ``share`` (report percent of all device time, not ms) and
``report`` (also write ``chipbench_out/<cell>.seed<n>.phases.json``: every
phase with its heaviest ``op_name`` prefixes, the heaviest instructions of
``other`` and ``unattributed``, the mixed share, and per phase
``held_ms_per_step``: the time of every instruction that holds an op of the
phase, its own or fused, which bounds the phase from above where XLA fused
it into another's kernels).
"""

import argparse
import collections
import json
import os
import re
import time

from chipbench import xplane
from chipbench.cell import REPO

OTHER, UNATTRIBUTED = "other", "unattributed"
TOP = 10
INSTRUCTION = re.compile(r"^\s+(?:ROOT )?%(\S+) = ")
COMPUTATION = re.compile(r"^(?:ENTRY )?%(\S+) \(.*\{$")
OPCODE = re.compile(r" ([a-z][a-z\-]*)\(")
OP_NAME = re.compile(r'op_name="((?:[^"\\]|\\.)*)"')
CALLS = re.compile(r"\bcalls=%([\w.\-]+)")
# inside a fused computation these move or make no data of their own: they
# do not make a fusion "mixed"
FREE = {"parameter", "constant", "iota", "broadcast", "bitcast", "reshape",
        "tuple", "get-tuple-element"}


class Program:
    """The compiled step's instructions by name, and which phase each is."""

    def __init__(self, hlo: str, rules):
        self.rules = [(phase, field, re.compile(pattern))
                      for phase, field, pattern in rules]
        self.lines = {}           # instruction name -> its HLO line
        self.bodies = collections.defaultdict(list)   # computation -> names
        computation = None
        for line in hlo.splitlines():
            if m := INSTRUCTION.match(line):
                self.lines[m.group(1)] = line
                self.bodies[computation].append(m.group(1))
            elif m := COMPUTATION.match(line):
                computation = m.group(1)

    def op_name(self, name):
        m = OP_NAME.search(self.lines.get(name, ""))
        return m.group(1) if m else None

    def by_rules(self, name):
        """The phase of the first matching rule, else ``None``."""
        line = self.lines.get(name)
        if line is None:
            return None
        fields = {"line": line, "op_name": self.op_name(name)}
        for phase, field, pattern in self.rules:
            if fields[field] is not None and pattern.search(fields[field]):
                return phase
        return None

    def fused_phases(self, name):
        """The phases of the ops a fusion instruction fuses (the named ones
        that compute something), or an empty set for any other instruction."""
        called = CALLS.search(self.lines.get(name, ""))
        phases = set()
        for inner in self.bodies.get(called.group(1), []) if called else []:
            opcode = OPCODE.search(self.lines[inner])
            if (self.op_name(inner) is not None
                    and (opcode is None or opcode.group(1) not in FREE)):
                phases.add(self.by_rules(inner) or OTHER)
        return phases

    def phase(self, name):
        phase = self.by_rules(name)
        if phase is not None:
            return phase
        if self.op_name(name) is not None:
            return OTHER
        inner = self.fused_phases(name)
        return inner.pop() if len(inner) == 1 else UNATTRIBUTED


def prefix_of(op_name):
    """``jit(step)/shard_map/jvp(LM)/block_3/up/dot_general`` ->
    ``jit(step)/shard_map/jvp(LM)/block_N/up``: the scope without the
    primitive, block indices folded; of a merged op's names, the first."""
    scope = op_name.split(";", 1)[0].rsplit("/", 1)[0]
    return re.sub(r"\d+", "N", scope)


def load_rules(name):
    path = os.path.join(os.path.dirname(os.path.dirname(
        os.path.abspath(__file__))), "phases", name + ".json")
    with open(path) as f:
        return json.load(f)["rules"]


def attribute(measured, rules_name):
    """Self time by phase over every lane of the trace, in ns, with what the
    report prints."""
    t0 = time.perf_counter()
    rules = load_rules(rules_name)
    program = Program(measured.hlo, rules)
    self_ns = collections.Counter()
    for events in measured.trace.lanes.values():
        for name, event_ns in xplane.self_times(events):
            self_ns[name] += event_ns
    ns = collections.Counter()
    prefixes = collections.defaultdict(collections.Counter)
    instructions = collections.defaultdict(collections.Counter)
    mixed, held = collections.Counter(), collections.Counter()
    for name, name_ns in self_ns.items():
        phase = program.phase(name)
        ns[phase] += name_ns
        op_name = program.op_name(name)
        if op_name is not None:
            prefixes[phase][prefix_of(op_name)] += name_ns
        if phase in (OTHER, UNATTRIBUTED):
            instructions[phase][xplane.base_name(name)] += name_ns
        fused = program.fused_phases(name)
        if len(fused) > 1:
            mixed["+".join(sorted(fused))] += name_ns
        for holds in fused | {phase}:
            held[holds] += name_ns
    phases = list(dict.fromkeys([p for p, _, _ in rules]
                                + [OTHER, UNATTRIBUTED]))
    return {"ns": ns, "phases": phases, "prefixes": prefixes,
            "instructions": instructions, "mixed": mixed, "held": held,
            "instructions_in_text": len(program.lines),
            "seconds": time.perf_counter() - t0}


_last = None     # (the Measured it was made from, rules name, attribution)


def attribution(measured, rules_name):
    """``attribute``, made once for a run: every metric of one traced run
    is handed the same ``measured``."""
    global _last
    if _last is None or _last[0] is not measured or _last[1] != rules_name:
        _last = (measured, rules_name, attribute(measured, rules_name))
    return _last[2]


def seed_of_this_run():
    """``--seed`` of the command line: a reducer is handed no seed."""
    ap = argparse.ArgumentParser(add_help=False)
    ap.add_argument("--seed")
    return ap.parse_known_args()[0].seed


def write_report(measured, att):
    per_step = 1e6 * measured.traced_steps * len(measured.trace.lanes)
    total = sum(att["ns"].values())

    def top(counter):
        return [[k, v / per_step] for k, v in counter.most_common(TOP)]

    report = {
        "cell": measured.cell.name, "unit": "ms per step and chip",
        "device_ms_per_step": total / per_step,
        "phases": {p: {"ms_per_step": att["ns"][p] / per_step,
                       "share_pct": 100.0 * att["ns"][p] / total,
                       "held_ms_per_step": att["held"][p] / per_step,
                       "top_scopes": top(att["prefixes"][p])}
                   for p in att["phases"]},
        "top_instructions": {p: top(att["instructions"][p])
                             for p in (OTHER, UNATTRIBUTED)},
        "mixed_share_pct": 100.0 * sum(att["mixed"].values()) / total,
        "mixed_fusions": top(att["mixed"]),
        "instructions_in_text": att["instructions_in_text"],
        "reducer_seconds": att["seconds"]}
    seed = seed_of_this_run()
    path = os.path.join(REPO, "chipbench_out", measured.cell.name + (
        f".seed{seed}" if seed is not None else "") + ".phases.json")
    os.makedirs(os.path.dirname(path), exist_ok=True)
    with open(path, "w") as f:
        json.dump(report, f, indent=1)
    print("chipbench: phases " + json.dumps(
        {p: round(v["ms_per_step"], 3) for p, v in report["phases"].items()}
        | {"mixed_share_pct": round(report["mixed_share_pct"], 3),
           "reducer_seconds": round(att["seconds"], 3)}), flush=True)


def reduce(measured, params):
    if measured.trace is None:
        return None
    # the harness executes this file anew for every metric; the attribution
    # is kept by the one imported copy, so a run makes it once
    from chipbench.reducers import scope_ms

    att = scope_ms.attribution(measured, params["rules"])
    if params.get("report"):
        scope_ms.write_report(measured, att)
    ns = sum(att["ns"][p] for p in params["phases"])
    if params.get("share"):
        return 100.0 * ns / sum(att["ns"].values())
    return ns / 1e6 / measured.traced_steps / len(measured.trace.lanes)
