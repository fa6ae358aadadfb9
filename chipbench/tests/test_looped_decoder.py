"""The family ``looped_decoder``: its FLOP and byte functions against hand
counts at the published widths (ISSUE 51's forecast), what the manifest
gained, and a tiny configuration of it through the harness's command line on
a virtual CPU device, with the new per-layer metrics asked for (a manifest
written here; no file of ``chipbench/`` proper is touched)."""

import json
import os
import subprocess
import sys

import pytest

from conftest import REPO

from chipbench import looped_decoder_flops as flops

T, BATCH, D, R, L = 4096, 1, 2048, 4, 8
CELL = "ouro.t4096.solo"
NEW_METRICS = ["ouro_attention_ms_per_step", "ouro_attention_roofline",
               "ouro_head_loss_ms_per_step", "ouro_exit_ms_per_step",
               "ouro_norm_ms_per_step", "ouro_mlp_ms_per_step",
               "ouro_attention_project_ms_per_step", "ouro_embed_ms_per_step",
               "ouro_attention_wrap_ms_per_step", "ouro_layers_unowned_share"]
SHAPES = dict(rounds=R, layers=L, vocab_rows=49152, hidden=D, heads=16,
              kv_heads=16, head_dim=128, ffn_width=5632, seq_len=T)


def test_model_flops_by_hand():
    """A token's forward pass: 32 block passes of 102.8 MFLOP of matmuls and
    16.8 of causal attention, four heads of 201.3 (ISSUE 51): 4.63 GFLOP,
    13.9 with the backward, 57 TFLOP a step of 4,096 tokens."""
    matmuls = 2 * (4 * D * D + 3 * D * 5632)
    assert matmuls == 102_760_448
    attention = 2 * 2 * 16 * 128 * T // 2           # QK^T and PV, halved
    assert attention == 16_777_216
    block = dict(SHAPES)
    for key in ("rounds", "layers", "vocab_rows"):
        del block[key]
    assert flops.block_forward_flops_per_token(**block) == matmuls + attention
    head = 2 * D * 49152
    assert head == 201_326_592
    forward = R * L * (matmuls + attention) + R * (head + 2 * D)
    assert flops.forward_flops_per_token(**SHAPES) == forward
    assert round(forward / 1e6) == 4631
    assert flops.train_flops_per_token(**SHAPES) == 3 * forward
    assert 56.8e12 < 3 * forward * T < 57.0e12
    # where the work is: the four exits' heads 17 %, attention 12 %
    assert 0.17 < R * head / forward < 0.18
    assert 0.11 < R * L * attention / forward < 0.12
    # the leaves are used R times: 4 x the FLOPs a parameter of one pass
    once = flops.forward_flops_per_token(**{**SHAPES, "rounds": 1})
    assert forward == R * once


def test_the_attention_kernels_operations_and_bytes_by_hand():
    """32 calls forward, as many recomputed, as many backward, at 16 heads
    on 16 of 128: 9.9 TFLOP (50 ms of matrix unit) and 8.6 GB a step."""
    ops, nbytes = flops.attention_cost(BATCH, 16, 16, T, 128, rounds=R,
                                       layers=L, forward_calls=2)
    pairs = T * T // 2
    assert ops == R * L * (2 * 2 + 5) * 2 * BATCH * 16 * pairs * 128
    q = BATCH * 16 * T * 128 * 2
    kv, rows = 2 * q, BATCH * 16 * T * 4
    assert nbytes == R * L * (2 * (2 * q + kv + rows)
                              + 4 * q + 2 * kv + rows)
    assert 50.0 < ops / 197e12 * 1e3 < 50.5
    assert 10.4 < nbytes / 819e9 * 1e3 < 10.6
    once = flops.attention_cost(BATCH, 16, 16, T, 128, rounds=1, layers=1)
    assert once[0] == 7 * 2 * 16 * pairs * 128


def test_the_real_cell_prices_its_kernels_from_the_configuration():
    from chipbench import cell as cells

    manifest = cells.Manifest.load(os.path.join(REPO, "BENCHMARK.json"))
    config, traffic = cells.open_cell(manifest, CELL)
    family = manifest.module("families", "looped_decoder").build(config,
                                                                 traffic)
    assert family.kernel_costs() == {"attention": flops.attention_cost(
        BATCH, 16, 16, T, 128, rounds=R, layers=L, forward_calls=2)}
    assert family.flops_per_item() == flops.train_flops_per_token(**SHAPES)
    assert family.items_per_step == BATCH * T == 4096
    assert family.beta == 0.1
    cfg = family.model.cfg
    assert (cfg.rounds, cfg.sandwich_norm, cfg.exit_gate) == (R, True, True)
    assert cfg.layer_types == ("full_rotary_attention",) * L


def test_the_manifest_gains_one_configuration_one_cell_and_its_metrics():
    real = json.load(open(os.path.join(REPO, "BENCHMARK.json")))
    names = [m["name"] for m in real["per_layer"]]
    first = names.index(NEW_METRICS[0])
    assert names[first:first + len(NEW_METRICS)] == NEW_METRICS
    mine = real["per_layer"][first:first + len(NEW_METRICS)]
    assert all(m["workloads"] == [CELL] for m in mine)
    assert all(m["moves"] == "throughput_per_chip" for m in mine)
    assert [m["name"] for m in real["per_layer"]
            if CELL in m.get("workloads", [])] == NEW_METRICS
    cell = real["workloads"][[w["name"] for w in real["workloads"]].index(
        CELL)]
    assert cell == {"name": CELL, "config": "ouro-2.6b",
                    "traffic": "t4096.b1.remat.solo", "chips": 1,
                    "why": cell["why"]}
    assert len(cell["why"]) <= 200
    assert len(real["workloads"]) >= 11
    assert sum(w["chips"] == 4 for w in real["workloads"]) == 1
    config = real["configs"][[c["name"] for c in real["configs"]].index(
        "ouro-2.6b")]
    assert len(config["why"]) <= 200
    assert config["reduced"] == ["num_hidden_layers"]
    assert config["file"] == "chipbench/configs/ouro-2.6b.json"
    for m in mine:
        spec = json.load(open(os.path.join(
            REPO, "chipbench", "metrics", m["name"] + ".json")))
        assert os.path.exists(os.path.join(
            REPO, "chipbench", "reducers", spec["reducer"] + ".py"))
        if "rules" in spec["params"]:
            assert spec["params"]["rules"] == "step_loop"
        if m["name"].endswith("_roofline"):
            assert (m["unit"], m["better"]) == ("%", "higher")
            assert spec["params"]["cost"] == "attention"


def test_the_loop_s_rule_table_is_the_layer_table_s_rows_and_the_exit():
    phases = os.path.join(REPO, "chipbench", "phases")
    base = json.load(open(os.path.join(phases, "step_layers.json")))["rules"]
    mine = json.load(open(os.path.join(phases, "step_loop.json")))["rules"]
    added = [rule for rule in mine if rule not in base]
    assert added == [["exit", "op_name", "bf\\.loop\\.exit"]]
    kept = [rule for rule in mine if rule in base]
    assert kept == [rule for rule in base if rule in kept]     # their order
    assert {rule[0] for rule in base} - {rule[0] for rule in kept} == {
        "expert_dispatch", "expert_ffn", "ssm_scan", "ssm_mix"}


@pytest.fixture(scope="module")
def tiny_manifest(tmp_path_factory):
    """The accepted manifest's metrics over one tiny cell of the family."""
    root = tmp_path_factory.mktemp("looped_decoder")
    (root / "traffic").mkdir()
    (root / "traffic" / "t48.b2.remat.solo.json").write_text(json.dumps({
        "ranks": 1, "batch": 2, "seq_len": 48, "remat": True,
        "comm": "neighbor", "topology": "ExponentialTwoGraph",
        "backend": "auto"}))
    real = json.load(open(os.path.join(REPO, "BENCHMARK.json")))
    cell = "tinyouro.solo"
    per_layer = [{**m, "workloads": [cell]} for m in real["per_layer"]
                 if "workloads" not in m or CELL in m["workloads"]]
    path = root / "BENCHMARK.json"
    path.write_text(json.dumps({
        "paths": [str(root), "chipbench"],
        "configs": [{"name": "tiny-ouro", "file": os.path.join(
            REPO, "tests", "data", "looped_decoder", "tiny-ouro.json")}],
        "workloads": [{"name": cell, "config": "tiny-ouro",
                       "traffic": "t48.b2.remat.solo", "chips": 1}],
        "end_to_end": real["end_to_end"], "per_layer": per_layer}))
    return str(path), cell, [m["name"] for m in per_layer]


@pytest.mark.parametrize("trace", [0, 1])
def test_tiny_cell_through_the_command_line_is_correct(tiny_manifest, trace):
    """Set-up, window, agreement (the plain reference included) and every
    reducer the new cell's metrics name, on the CPU: device metrics are left
    out of the line, none raises."""
    manifest, cell, names = tiny_manifest
    proc = subprocess.run(
        [sys.executable, os.path.join(REPO, "chipbench", "run.py"),
         "--workload", cell, "--seed", "2147483659", "--seconds", "2",
         "--trace", str(trace), "--manifest", manifest],
        capture_output=True, text=True, timeout=900,
        env={**os.environ, "XLA_FLAGS": "--xla_force_host_platform_device_"
             "count=1"})
    assert proc.returncode == 0, proc.stderr[-3000:]
    result = json.loads(proc.stdout.splitlines()[-1])
    assert result["correct"] is True and result["failed"] == 0
    assert '"model_loss"' in proc.stdout
    assert set(NEW_METRICS) <= set(names)
    if trace:
        assert result["metrics"]["compiles_in_window"]["value"] == 0
        assert not set(NEW_METRICS) & set(result["metrics"])        # CPU
    else:
        assert result["metrics"] == {}
