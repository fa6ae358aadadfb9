"""The layer map of the decoder step (``phases/step_layers.json`` read by
``reducers/scope_ms.py``) on a small recorded text and event list: an
attention kernel as the chip's compiler prints it (one instruction over
three text lines, its ``op_name`` on the third), a transpose of
``local_attention``'s, the head's weight-gradient fusion, a residual add and
a copy XLA inserted; and the seven metric files against table and manifest.
"""

import os
import types

import pytest
from conftest import REPO

from chipbench import cell as cells
from chipbench import xplane
from chipbench.reducers import scope_ms
from chipbench.xplane import Event

STEP = "jit(step)/shard_map"
BLOCK = f"{STEP}/transpose(jvp(TransformerLM))/block_0"
KERNEL = "flash_attention_splash_mha_fwd_residuals.12"

# the kernel's three lines are the AOT-compiled gpt2s.t2048.solo step's,
# shapes and kernel body shortened
HLO = f"""HloModule jit_step, is_scheduled=true

%fused_computation.1 (param_0: f32[8]) -> f32[8] {{
  %param_0 = f32[8]{{0}} parameter(0)
  %dot.5 = f32[8]{{0}} dot(%param_0, %param_0), metadata={{op_name="{STEP}/transpose(jvp(TransformerLM))/bf.head.logits/lm_head/dot_general"}}
  %mul.1 = f32[8]{{0}} multiply(%dot.5, %param_0), metadata={{op_name="{STEP}/bf.optim.base_update/mul"}}
  ROOT %add.1 = f32[8]{{0}} add(%mul.1, %param_0), metadata={{op_name="{STEP}/transpose(jvp(TransformerLM))/bf.head.logits/lm_head/dot_general"}}
}}

ENTRY %main.1_spmd (x: f32[8]) -> f32[8] {{
  %x = f32[8]{{0}} parameter(0)
  %transpose.3 = f32[8]{{0}} transpose(%x), dimensions={{0}}, metadata={{op_name="{STEP}/jvp(TransformerLM)/block_0/bf.attn.kernel/transpose"}}
  %{KERNEL} = (f32[8,1024,128]{{2,1,0:T(8,128)}}, bf16[8,12,2048,64]{{3,2,1,0:T(8,128)(2,1)S(1)}}) custom-call(%transpose.3, %x), custom_call_target="tpu_custom_call", operand_layout_constraints={{f32[8]{{0}}}}, frontend_attributes={{kernel_metadata={{
"xprof_metadata":"{{\\"block_q\\": 1024, \\"block_kv\\": 1024, \\"use_fused_bwd_kernel\\": true}}"
}}}}, metadata={{op_name="{STEP}/jvp(TransformerLM)/block_0/bf.attn.kernel/vmap(jit(_splash_attention))/flash_attention_splash_mha_fwd_residuals/pallas_call" stack_frame_id=14}}, backend_config={{"custom_call_config":{{"body":"TUzvUgFNTElS"}}}}
  %fusion.9 = f32[8]{{0}} fusion(%x), kind=kOutput, calls=%fused_computation.1, metadata={{op_name="{STEP}/transpose(jvp(TransformerLM))/bf.head.logits/lm_head/dot_general"}}
  %reduce.4 = f32[8]{{0}} reduce(%x, %x), dimensions={{0}}, metadata={{op_name="{BLOCK}/bf.attn.kernel/reduce_sum"}}
  %fusion.2 = f32[8]{{0}} fusion(%x), kind=kLoop, calls=%fused_computation.9, metadata={{op_name="{BLOCK}/bf.block.norm/ln1/mul"}}
  %fusion.3 = f32[8]{{0}} fusion(%x), kind=kLoop, calls=%fused_computation.9, metadata={{op_name="{BLOCK}/bf.attn.project/qkv/dot_general"}}
  %fusion.4 = f32[8]{{0}} fusion(%x), kind=kLoop, calls=%fused_computation.9, metadata={{op_name="{BLOCK}/bf.mlp.dense/up/dot_general"}}
  %fusion.5 = f32[8]{{0}} fusion(%x), kind=kLoop, calls=%fused_computation.9, metadata={{op_name="{STEP}/transpose(jvp(TransformerLM))/bf.embed.lookup/tok/scatter-add"}}
  %add.6 = f32[8]{{0}} add(%x, %x), metadata={{op_name="{BLOCK}/add"}}
  %copy.7 = f32[8]{{0}} copy(%x)
  ROOT %add.3 = f32[8]{{0}} add(%x, %x), metadata={{op_name="{STEP}/bf.optim.apply/add"}}
}}
"""

LANE = [Event("transpose.3", 0, 10), Event(KERNEL, 10, 110),
        Event("fusion.9", 110, 170), Event("reduce.4", 170, 175),
        Event("fusion.2", 175, 182), Event("fusion.3", 182, 212),
        Event("fusion.4", 212, 252), Event("fusion.5", 252, 255),
        Event("add.6", 255, 259), Event("copy.7", 259, 279),
        Event("add.3", 279, 280)]
WANT_NS = {"attention_kernel": 100, "attention_wrap": 10 + 5,
           "head_loss": 60, "norm": 7, "attention_project": 30, "mlp": 40,
           "embed": 3, "optimizer": 1, "other": 4, "unattributed": 20}
STEPS = 2
LAYER_METRICS = {
    "head_loss_ms_per_step": "head_loss", "embed_ms_per_step": "embed",
    "norm_ms_per_step": "norm", "mlp_ms_per_step": "mlp",
    "attention_project_ms_per_step": "attention_project",
    "attention_wrap_ms_per_step": "attention_wrap"}


def measured(hlo):
    return types.SimpleNamespace(
        hlo=hlo, trace=xplane.Trace({"/device:TPU:0": LANE}, []),
        traced_steps=STEPS, cell=types.SimpleNamespace(name="handmade"))


@pytest.fixture(scope="module")
def manifest():
    return cells.Manifest.load(os.path.join(REPO, "BENCHMARK.json"))


def test_the_kernel_is_the_attention_kernels_by_its_name_alone():
    """The reader keeps the first text line of an instruction: the kernel's
    ``op_name`` (and the ``bf.attn.kernel`` in it) is out of its sight, and
    under ``step.json`` the kernel is ``unattributed`` for that."""
    program = scope_ms.Program(HLO, scope_ms.load_rules("step_layers"))
    assert program.op_name(KERNEL) is None
    assert program.phase(KERNEL) == "attention_kernel"
    by_pass = scope_ms.Program(HLO, scope_ms.load_rules("step"))
    assert by_pass.phase(KERNEL) == "unattributed"


def test_the_layers_add_up_to_the_lanes_busy_time():
    att = scope_ms.attribute(measured(HLO), "step_layers")
    assert {p: ns for p, ns in att["ns"].items() if ns} == WANT_NS
    assert sum(att["ns"].values()) == xplane.busy_ns(LANE) == 280
    assert set(att["ns"]) <= set(att["phases"])
    # the head's fusion holds an optimizer op: its time is the head's, and
    # the mixed share says so
    assert dict(att["mixed"]) == {"head_loss+optimizer": 60}


def test_the_seven_metrics_through_the_harness_make_one_attribution(
        manifest, monkeypatch):
    """As ``run.py`` calls them, in the manifest's order: the seven stand
    together at the end of ``per_layer``, so the table is joined once."""
    calls, attribute = [], scope_ms.attribute
    monkeypatch.setattr(scope_ms, "attribute",
                        lambda *a: calls.append(a) or attribute(*a))
    names = [m["name"] for m in manifest.data["per_layer"]][-7:]
    assert set(names) == set(LAYER_METRICS) | {"layers_unowned_share"}
    run = measured(HLO)
    values = {}
    for name in names:
        spec = cells.load_json(manifest.find("metrics", name))
        assert "report" not in spec["params"]     # step.json's file stays
        values[name] = manifest.module("reducers", spec["reducer"]).reduce(
            run, spec["params"])
    for name, phase in LAYER_METRICS.items():
        assert values[name] == pytest.approx(WANT_NS[phase] / 1e6 / STEPS)
    # the residual add and the nameless copy; the kernel is owned
    assert values["layers_unowned_share"] == pytest.approx(
        100.0 * (4 + 20) / 280)
    assert len(calls) == 1


def test_a_program_without_the_layer_scopes_reads_zero_and_does_not_raise(
        manifest):
    """The parent of the PR that opened the scopes, under this benchmark:
    the six layer metrics read 0, the share holds what they would own, and
    the kernels are found as ever."""
    unscoped = measured(
        HLO.replace("bf.head.logits/", "").replace("bf.attn.kernel/", "")
        .replace("bf.block.norm/", "").replace("bf.attn.project/", "")
        .replace("bf.mlp.dense/", "").replace("bf.embed.lookup/", ""))
    for name in LAYER_METRICS:
        params = cells.load_json(manifest.find("metrics", name))["params"]
        assert scope_ms.reduce(unscoped, params) == 0
    share = scope_ms.reduce(unscoped, cells.load_json(
        manifest.find("metrics", "layers_unowned_share"))["params"])
    assert share == pytest.approx(100.0 * (280 - 100 - 1) / 280)


@pytest.mark.parametrize("name", sorted(LAYER_METRICS)
                         + ["layers_unowned_share"])
def test_a_metric_names_a_phase_of_the_table_and_cells_of_the_manifest(
        manifest, name):
    spec = cells.load_json(manifest.find("metrics", name))
    assert spec["reducer"] == "scope_ms"
    assert spec["params"]["rules"] == "step_layers"
    phases = {rule[0] for rule in scope_ms.load_rules("step_layers")} | {
        "other", "unattributed"}
    assert set(spec["params"]["phases"]) <= phases
    entry = manifest.entry("per_layer", name)
    assert entry["source"] == "program_span"
    assert entry["moves"] == "throughput_per_chip"
    decoder_cells = {w["name"] for w in manifest.data["workloads"]
                     if w["config"] != "resnet50"}
    assert entry["workloads"] and set(entry["workloads"]) <= decoder_cells
