"""The join of device trace and HLO text (``reducers/scope_ms.py``): on a
hand-made trace against hand-written HLO lines, and on the trace and the
compiled text that ``record_scoped_trace.py`` recorded on a v5e chip."""

import json
import os
import subprocess
import sys
import types

import pytest
from conftest import REPO

from chipbench import xplane
from chipbench.reducers import scope_ms
from chipbench.xplane import Event

DATA = os.path.join(os.path.dirname(__file__), "data")
STEP = "jit(step)/shard_map"

HLO = f"""HloModule jit_step, is_scheduled=true

%fused_computation.1 (param_0: f32[8]) -> f32[8] {{
  %param_0 = f32[8]{{0}} parameter(0)
  %constant.1 = f32[] constant(2), metadata={{op_name="{STEP}"}}
  %convolution.5 = f32[8]{{0}} convolution(%param_0, %param_0), metadata={{op_name="{STEP}/transpose(jvp(LM))/block_3/up/dot_general"}}
  %mul.1 = f32[8]{{0}} multiply(%convolution.5, %param_0), metadata={{op_name="{STEP}/bf.optim.base_update/mul"}}
  ROOT %add.1 = f32[8]{{0}} add(%mul.1, %param_0), metadata={{op_name="{STEP}/bf.optim.apply/add"}}
}}

%fused_computation.2 (param_0.1: f32[8]) -> f32[8] {{
  %param_0.1 = f32[8]{{0}} parameter(0)
  %slice.3 = f32[4]{{0}} slice(%param_0.1), slice={{[0:4]}}, metadata={{op_name="{STEP}/bf.gossip.unpack/slice"}}
  ROOT %concatenate.2 = f32[8]{{0}} concatenate(%slice.3, %slice.3), dimensions={{0}}, metadata={{op_name="{STEP}/bf.gossip.unpack/concatenate"}}
}}

%body (p: (s32[], f32[8])) -> (s32[], f32[8]) {{
  %p = (s32[], f32[8]{{0}}) parameter(0)
  %fusion.7 = f32[8]{{0}} fusion(%p), kind=kOutput, calls=%fused_computation.9, metadata={{op_name="{STEP}/jvp(LM)/block_0/up/dot_general"}}
  ROOT %fusion.8 = f32[8]{{0}} fusion(%fusion.7), kind=kLoop, calls=%fused_computation.9, metadata={{op_name="{STEP}/transpose(jvp(LM))/block_0/jvp(LM)/block_0/checkpoint/rematted_computation/tanh"}}
}}

ENTRY %main.1_spmd (x: f32[8]) -> f32[8] {{
  %x = f32[8]{{0}} parameter(0)
  %while.1 = (s32[], f32[8]{{0}}) while(%x), condition=%cond, body=%body, metadata={{op_name="{STEP}/jvp(LM)/while"}}
  %fusion.9 = f32[8]{{0}} fusion(%x), kind=kOutput, calls=%fused_computation.1, metadata={{op_name="{STEP}/transpose(jvp(LM))/block_3/up/dot_general"}}
  %fusion.10 = f32[8]{{0}} fusion(%x), kind=kLoop, calls=%fused_computation.9, metadata={{op_name="{STEP}/bf.optim.as_updates/sub"}}
  %shard_map.4 = f32[8]{{0}} custom-call(%x), custom_call_target="tpu_custom_call", custom_call_has_side_effect=true, metadata={{op_name="{STEP}/bf.gossip.pack/never"}}
  %collective-permute-start.1 = f32[8]{{0}} collective-permute-start(%x), source_target_pairs={{{{0,1}}}}, metadata={{op_name="{STEP}/bf.gossip.exchange/bf.neighbor_allreduce.slot0/ppermute"}}
  %mul.7 = f32[8]{{0}} multiply(%x, %x), metadata={{op_name="{STEP}/bf.gossip.exchange/mul"}}
  %fusion.11 = f32[8]{{0}} fusion(%x), kind=kLoop, calls=%fused_computation.9, metadata={{op_name="{STEP}/broadcast_in_dim;bf.gossip.split/reshape;bf.gossip.unpack/reshape"}}
  %fusion.12 = f32[8]{{0}} fusion(%x), kind=kLoop, calls=%fused_computation.9, metadata={{op_name="{STEP}/bf.gossip.fuse/concatenate"}}
  %fusion.13 = f32[8]{{0}} fusion(%x), kind=kLoop, calls=%fused_computation.2
  %copy-done.2 = f32[8]{{0}} copy-done(%x)
  ROOT %add.3 = f32[8]{{0}} add(%x, %x), metadata={{op_name="{STEP}/add"}}
}}
"""

# a loop op over two nested ops, then ops that touch; the second chip ran
# two of them only.  ghost.1 is in no line of the text.
LANES = {
    "/device:TPU:0": [
        Event("while.1", 0, 100), Event("fusion.7", 10, 40),
        Event("fusion.8", 50, 90), Event("fusion.9", 100, 130),
        Event("fusion.10", 130, 150), Event("shard_map.4", 150, 200),
        Event("collective-permute-start.1", 200, 205),
        Event("mul.7", 205, 210), Event("fusion.11", 210, 230),
        Event("fusion.12", 230, 240), Event("fusion.13", 240, 250),
        Event("copy-done.2", 250, 260), Event("add.3", 260, 270),
        Event("ghost.1", 270, 275)],
    "/device:TPU:1": [Event("fusion.9", 0, 30), Event("copy-done.2", 30, 50)],
}
WANT_NS = {"forward": 30 + 30, "recompute": 40, "backward": 30 + 30,
           "optimizer": 20, "exchange": 50 + 5 + 5, "gossip_pack": 20 + 10,
           "gossip_fuse": 10, "other": 10, "unattributed": 10 + 5 + 20}
STEPS = 2


def measured(hlo, trace, steps, name="handmade"):
    return types.SimpleNamespace(hlo=hlo, trace=trace, traced_steps=steps,
                                 cell=types.SimpleNamespace(name=name))


@pytest.fixture()
def handmade():
    return measured(HLO, xplane.Trace(LANES, []), STEPS)


def ms(handmade, *phases, **params):
    return scope_ms.reduce(handmade, {"rules": "step",
                                      "phases": list(phases), **params})


@pytest.mark.parametrize("phase", sorted(WANT_NS))
def test_each_instruction_falls_into_the_first_phase_that_matches(
        handmade, phase):
    """Rule order (the side-effecting kernel and the collective are the
    exchange whatever scope their line names; of a merged op's names pack
    wins over split; recomputed beats transposed beats jvp), no metadata,
    an instruction missing from the text, a fusion without a name taking its
    ops' one phase: per step and chip, so over steps and lanes."""
    assert ms(handmade, phase) == pytest.approx(
        WANT_NS[phase] / 1e6 / STEPS / len(LANES))


def test_the_phases_add_up_to_the_busy_time(handmade):
    att = scope_ms.attribute(handmade, "step")
    busy = sum(map(xplane.busy_ns, LANES.values()))
    assert sum(att["ns"].values()) == busy == sum(WANT_NS.values())
    assert set(att["ns"]) <= set(att["phases"])
    assert att["phases"][-2:] == ["other", "unattributed"]
    share = ms(handmade, "other", "unattributed", share=True)
    assert share == pytest.approx(100.0 * (10 + 35) / busy)


def test_the_mixed_share_is_time_in_fusions_of_more_than_one_phase(handmade):
    """fusion.9 is booked under its own name (backward) and fuses an
    optimizer pass; a constant under no scope does not make it mixed."""
    att = scope_ms.attribute(handmade, "step")
    assert dict(att["mixed"]) == {"backward+optimizer": 60}
    # what a phase could own at most: its own time and every fusion holding
    # one of its ops
    assert att["held"]["optimizer"] == WANT_NS["optimizer"] + 60
    assert att["held"]["backward"] == WANT_NS["backward"]


def test_a_metric_is_left_out_where_there_is_no_trace():
    no_trace = measured(HLO, None, STEPS)
    assert ms(no_trace, "forward") is None


def test_the_scope_without_the_primitive_and_the_block_index():
    assert scope_ms.prefix_of(
        f"{STEP}/jvp(LM)/block_3/up/dot_general") == f"{STEP}/jvp(LM)/block_N/up"
    assert scope_ms.prefix_of(
        f"{STEP}/bf.gossip.split/reshape;bf.gossip.unpack/reshape") == (
            f"{STEP}/bf.gossip.split")


NEW_METRICS = ["forward_ms_per_step", "backward_ms_per_step",
               "recompute_ms_per_step", "gossip_fuse_ms_per_step",
               "gossip_pack_ms_per_step", "step_unattributed_share"]


def test_the_manifests_metrics_through_the_harness_make_one_attribution(
        handmade, tmp_path, monkeypatch):
    """As ``run.py`` calls them: every metric's reducer module is executed
    anew from its file, all are handed the same ``measured``, and the trace
    is joined with the text once.  Every phase a metric names is a phase of
    the rule table."""
    from chipbench import cell as cells

    manifest = cells.Manifest.load(os.path.join(REPO, "BENCHMARK.json"))
    calls, attribute = [], scope_ms.attribute
    monkeypatch.setattr(scope_ms, "attribute",
                        lambda *a: calls.append(a) or attribute(*a))
    monkeypatch.setattr(scope_ms, "REPO", str(tmp_path))
    phases = {rule[0] for rule in scope_ms.load_rules("step")} | {
        "other", "unattributed"}
    values = {}
    for name in NEW_METRICS:
        entry = manifest.entry("per_layer", name)
        assert entry["source"] == "program_span" and "workloads" not in entry
        spec = cells.load_json(manifest.find("metrics", name))
        assert set(spec["params"]["phases"]) <= phases
        values[name] = manifest.module("reducers", spec["reducer"]).reduce(
            handmade, spec["params"])
    per_step = 1e6 * STEPS * len(LANES)
    assert values["backward_ms_per_step"] == pytest.approx(60 / per_step)
    assert values["gossip_pack_ms_per_step"] == pytest.approx(30 / per_step)
    assert values["step_unattributed_share"] == pytest.approx(
        100.0 * 45 / sum(WANT_NS.values()))
    assert len(calls) == 1
    assert [p.name for p in (tmp_path / "chipbench_out").iterdir()] == [
        "handmade.phases.json"]


def test_a_program_without_phase_scopes_reads_no_gossip_time(handmade):
    """The parent of the PR that opened the scopes: no op carries a ``bf.``
    scope, so the two metrics that read them are 0 and what the scopes held
    falls to ``other``; the three that read JAX's own names read as ever."""
    from chipbench import cell as cells

    manifest = cells.Manifest.load(os.path.join(REPO, "BENCHMARK.json"))
    unscoped = measured(HLO.replace("bf.optim.", "").replace(
        "bf.gossip.", ""), handmade.trace, STEPS)
    got = {name: scope_ms.reduce(unscoped, cells.load_json(
        manifest.find("metrics", name))["params"]) for name in NEW_METRICS[:5]}
    assert got["gossip_fuse_ms_per_step"] == got["gossip_pack_ms_per_step"] == 0
    assert got["forward_ms_per_step"] == ms(handmade, "forward")
    assert got["backward_ms_per_step"] == ms(handmade, "backward")
    assert ms(unscoped, "other") > ms(handmade, "other")


def test_a_cpu_run_with_the_phase_metrics_leaves_them_out():
    """The harness end to end on the CPU twin, from a manifest that is the
    tests' own plus the six phase metrics: a capture without a device lane
    gives no phase, and the run is none the worse for being asked."""
    proc = subprocess.run(
        [sys.executable, os.path.join(REPO, "chipbench", "run.py"),
         "--workload", "tiny.ring4", "--seed", "3", "--seconds", "1.5",
         "--trace", "1", "--manifest",
         os.path.join(DATA, "BENCHMARK.scopes.json")],
        capture_output=True, text=True, timeout=600)
    assert proc.returncode == 0, proc.stderr[-2000:]
    result = json.loads(proc.stdout.splitlines()[-1])
    assert result["correct"] is True
    assert result["metrics"]["gossip_calls_per_step"]["value"] == 2
    assert not set(NEW_METRICS) & set(result["metrics"])


# ---- recorded on the chip ---------------------------------------------------

RECORDED_STEPS = 3


@pytest.fixture(scope="module")
def recorded():
    with open(os.path.join(DATA, "scoped.hlo.txt")) as f:
        hlo = f.read()
    trace = xplane.read(os.path.join(DATA, "scoped.xplane.pb"))
    return measured(hlo, trace, RECORDED_STEPS, name="scoped")


def test_recorded_every_traced_instruction_is_in_the_text(recorded):
    """The join itself: a trace event's name is an instruction's name in
    ``compiled.as_text()``, whole, also where it is longer than 64
    characters."""
    program = scope_ms.Program(recorded.hlo, scope_ms.load_rules("step"))
    lane, = recorded.trace.lanes.values()
    names = {e.name for e in lane}
    assert len(names) > 50 and names <= set(program.lines)
    assert max(map(len, names)) > 64


def test_recorded_phases_add_up_and_each_layer_has_time(recorded):
    att = scope_ms.attribute(recorded, "step")
    lane, = recorded.trace.lanes.values()
    assert sum(att["ns"].values()) == pytest.approx(xplane.busy_ns(lane))
    for phase in ("forward", "backward", "recompute", "optimizer",
                  "exchange", "other", "unattributed"):
        assert att["ns"][phase] > 0, phase
    # layout copies XLA inserts carry no op_name: counted, not dropped
    assert att["instructions"]["unattributed"]["copy-done"] > 0


def test_recorded_kernels_fall_under_their_phase_by_themselves(recorded):
    """A named, side-effect-free Pallas kernel keeps the name stack it was
    called under (as the flash kernels do); the unnamed side-effecting one
    (as the gossip kernels are) is the exchange by its line."""
    program = scope_ms.Program(recorded.hlo, scope_ms.load_rules("step"))
    lane, = recorded.trace.lanes.values()
    kernels = {e.name: program.phase(e.name) for e in lane
               if "tpu_custom_call" in program.lines[e.name]}
    assert {xplane.base_name(k): v for k, v in kernels.items()} == {
        "scoped_fwd_kernel": "forward",
        "scoped_bwd_kernel_block_q_major_512_block_q_512_block_k_major_512"
        "_block_k_512": "backward",
        "train_step": "exchange"}


def test_recorded_report_holds_what_perf_md_is_written_from(
        recorded, tmp_path, monkeypatch):
    monkeypatch.setattr(scope_ms, "REPO", str(tmp_path))
    share = scope_ms.reduce(recorded, {
        "rules": "step", "phases": ["other", "unattributed"],
        "share": True, "report": True})
    path, = (tmp_path / "chipbench_out").iterdir()
    assert path.name.startswith("scoped.") and path.name.endswith(
        ".phases.json")
    report = json.loads(path.read_text())
    phases = report["phases"]
    assert sum(p["ms_per_step"] for p in phases.values()) == pytest.approx(
        report["device_ms_per_step"])
    assert share == pytest.approx(phases["other"]["share_pct"]
                                  + phases["unattributed"]["share_pct"])
    scopes = dict(phases["recompute"]["top_scopes"])
    assert any("block_N/checkpoint/rematted_computation" in s for s in scopes)
    assert 0 < report["mixed_share_pct"] < 100
    assert report["top_instructions"]["unattributed"][0][0] == "copy-done"
