"""``benchmarks/step_layers_report.py`` on a handmade compiled text and event
list: the table it writes beside a traced run's result line names every
instruction's layer and pass, and its phases are the join's own."""

import os
import sys
import types

import pytest

from tests._util import REPO, load_script

STEP = "jit(step)/shard_map"
HLO = f"""HloModule jit_step, is_scheduled=true

ENTRY %main.1_spmd (x: f32[8]) -> f32[8] {{
  %x = f32[8]{{0}} parameter(0)
  %flash_attention_splash_mha_fwd_residuals.2 = f32[8]{{0}} custom-call(%x), custom_call_target="tpu_custom_call"
  %copy.3 = f32[8]{{0}} copy(%x), metadata={{op_name="{STEP}/jvp(TransformerLM)/block_0/bf.attn.kernel/transpose"}}
  %fusion.4 = f32[8]{{0}} fusion(%x), kind=kLoop, calls=%fused_computation.4, metadata={{op_name="{STEP}/transpose(jvp(TransformerLM))/bf.head.logits/lm_head/dot_general"}}
  %fusion.5 = f32[8]{{0}} fusion(%x), kind=kLoop, calls=%fused_computation.5, metadata={{op_name="{STEP}/rematted_computation/block_0/bf.mlp.dense/up/dot_general"}}
  %copy.6 = f32[8]{{0}} copy(%x)
  ROOT %add.7 = f32[8]{{0}} add(%x, %x), metadata={{op_name="{STEP}/jvp(TransformerLM)/block_0/add"}}
}}
"""
NAMES = ["flash_attention_splash_mha_fwd_residuals.2", "copy.3", "fusion.4",
         "fusion.5", "copy.6", "add.7"]
NS = [4000, 1000, 3000, 2000, 500, 1]
STEPS = 2


@pytest.fixture(scope="module")
def report():
    if REPO not in sys.path:
        sys.path.insert(0, REPO)
    return load_script(os.path.join("benchmarks", "step_layers_report.py"))


def test_every_instruction_is_listed_with_its_layer_and_its_pass(report):
    from chipbench import xplane
    from chipbench.reducers import scope_ms

    events, start = [], 0
    for name, ns in zip(NAMES, NS):
        events.append(xplane.Event(name, start, start + ns))
        start += ns
    measured = types.SimpleNamespace(
        hlo=HLO, trace=xplane.Trace({"/device:TPU:0": events}, []),
        traced_steps=STEPS, cell=types.SimpleNamespace(name="handmade"))
    att = scope_ms.attribute(measured, "step_layers")
    table = report.layers(measured, "step_layers", att, floor_ms=1e-6)
    per_step = 1e6 * STEPS
    assert table["phases_ms"] == {
        p: att["ns"][p] / per_step for p in att["phases"]}
    assert table["device_ms_per_step"] == pytest.approx(
        xplane.busy_ns(events) / per_step)
    assert [(r["name"], r["phase"], r["pass"]) for r in table["rows"]] == [
        (NAMES[0], "attention_kernel", "unattributed"),
        (NAMES[2], "head_loss", "backward"),
        (NAMES[3], "mlp", "recompute"),
        (NAMES[1], "attention_wrap", "forward"),
        (NAMES[4], "unattributed", "unattributed")]
    assert sum(r["ms_per_step"] for r in table["rows"]) + sum(
        table["below_floor_ms"].values()) == pytest.approx(
        table["device_ms_per_step"])
    assert table["below_floor_ms"] == {"other|add": 1 / per_step}


def test_the_head_s_chunks_are_read_off_one_trace_of_the_cell_s_loss(report,
                                                                     monkeypatch):
    """The gauges ``ops/head_loss.py`` sets as a call is traced, a call site
    a row, at the shapes the cell's family makes; a loss that never calls
    ``head_loss`` gives no row."""
    import jax
    import jax.numpy as jnp

    from bluefog_tpu.models.transformer import (
        GPTConfig, TransformerLM, next_token_loss)
    from bluefog_tpu.ops import head_loss as hl

    monkeypatch.setattr(hl, "_CHUNK_ELEMENTS", 64 * 50)
    monkeypatch.setattr(hl, "_MIN_ROWS", 8)
    model = TransformerLM(GPTConfig(
        vocab_size=50, hidden_size=16, num_layers=1, num_heads=2,
        max_position=128, mtp_depth=1))
    tokens = jnp.zeros((1, 8), jnp.int32)

    def loss(params, model_state, batch):
        return next_token_loss(model, params, model_state, batch,
                               mtp_weight=0.1), model_state

    cell = types.SimpleNamespace(family=types.SimpleNamespace(
        init=lambda key: (model.init(key, tokens,
                                     next_tokens=tokens)["params"], {}),
        make_batch=lambda key: jnp.zeros((3, 102), jnp.int32),   # 300 rows
        loss=loss))
    assert report.head_loss_chunks(cell) == {
        "main": {"chunks": 5, "chunk_rows": 64},
        "mtp": {"chunks": 5, "chunk_rows": 64}}
    cell.family.loss = lambda params, model_state, batch: (
        jnp.float32(0), model_state)
    assert report.head_loss_chunks(cell) == {}
