"""The family ``conv_gqa_moe``: its FLOP and byte functions against hand
counts at the published widths (ISSUE 43's table), and a tiny configuration
of it through the harness's command line on a virtual CPU device, with the
new per-layer metrics asked for (a manifest written here; no file of
``chipbench/`` proper is touched)."""

import json
import os
import subprocess
import sys

import pytest

from conftest import REPO

from chipbench import conv_gqa_moe_flops as flops

T, BATCH, D = 8192, 4, 2048
KINDS = ("conv", "full_attention", "conv", "conv", "conv")
CELL = "lfm2moe.t8192.solo"
NEW_METRICS = ["lfm2_short_conv_ms_per_step", "lfm2_conv_gate_ms_per_step",
               "lfm2_expert_dispatch_ms_per_step",
               "lfm2_grouped_matmul_ms_per_step",
               "lfm2_grouped_matmul_roofline", "lfm2_attention_ms_per_step",
               "lfm2_attention_roofline", "lfm2_conv_gate_roofline",
               "lfm2_attention_project_ms_per_step",
               "lfm2_expert_ffn_ms_per_step"]
SHAPES = dict(kinds=KINDS, hidden=D, heads=32, kv_heads=8, head_dim=64,
              seq_len=T, dense_blocks=1, dense_width=7168, router_outputs=32,
              top_k=4, experts_held=8, expert_width=1792, vocab_rows=16384)


def test_model_flops_by_hand():
    """A token's forward pass, in multiply-adds: the table of ISSUE 43."""
    conv = 3 * D * D + D * D                                  # 16,777,216
    projections = D * (32 + 2 * 8) * 64 + 32 * 64 * D         # 10,485,760
    scores = 32 * 2 * 64 * T // 2                             # 16,777,216
    dense = 3 * D * 7168                                      # 44,040,192
    routed = D * 32 + 4 * 8 / 32 * 3 * D * 1792               # 11,075,584
    head = D * 16384                                          # 33,554,432
    assert flops.short_conv_macs(D) == conv
    assert flops.attention_macs(D, 32, 8, 64, T) == projections + scores
    macs = 4 * conv + projections + scores + dense + 4 * routed + head
    assert macs == 216_268_800
    assert flops.forward_flops_per_token(**SHAPES) == 2 * macs
    assert flops.train_flops_per_token(**SHAPES) == 6 * macs == 1_297_612_800
    # where the work is: the conv operators 31 %, the held experts 20 %
    assert 0.30 < 4 * conv / macs < 0.32
    assert 0.20 < 4 * routed / macs < 0.21


def test_the_gate_and_convolution_s_bytes_by_hand():
    """Four tensors of 32,768 x 2,048 bf16 a forward pass, seven a backward
    pass, the forward twice under remat, four layers: 9.83 ms at 819 GB/s."""
    tensor = BATCH * T * D * 2                                # 134,217,728
    ops, nbytes = flops.gate_conv_cost(BATCH * T, D, layers=4,
                                       forward_calls=2)
    assert nbytes == 4 * tensor * (2 * 4 + 7) == 8_053_063_680
    assert ops == 4 * BATCH * T * D * 7 * 4
    once = flops.gate_conv_cost(BATCH * T, D, forward_calls=1)[1]
    assert once == 11 * tensor
    assert 9.8 < nbytes / 819e9 * 1e3 < 9.9
    # the bound is the memory one by far: no matrix unit is involved
    assert ops / 197e12 < 0.01 * nbytes / 819e9


def test_the_real_cell_prices_its_kernels_from_the_configuration():
    from chipbench import cell as cells

    manifest = cells.Manifest.load(os.path.join(REPO, "BENCHMARK.json"))
    config, traffic = cells.open_cell(manifest, CELL)
    family = manifest.module("families", "conv_gqa_moe").build(config, traffic)
    costs = family.kernel_costs()
    pairs = T * T // 2
    # 32 heads of 64; QK^T and PV forward (twice under remat), five products
    # backward; keys and values at the 8 heads they are projected in
    q, kv, rows = BATCH * 32 * T * 64 * 2, 2 * BATCH * 8 * T * 64 * 2, (
        BATCH * 32 * T * 4)
    assert costs["attention"] == (
        9 * 2 * BATCH * 32 * 64 * pairs,
        2 * (2 * q + kv + rows) + 4 * q + 2 * kv + rows)
    # 32,768 of 131,072 assignments a layer; nine products and three more
    assert costs["grouped_matmul"][0] == 4 * 12 * 2 * 32768 * D * 1792
    assert costs["gate_conv"][1] == 8_053_063_680
    assert family.flops_per_item() == 1_297_612_800
    assert family.items_per_step == BATCH * T == 32768


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
    assert cell == {"name": CELL, "config": "lfm2-8b-a1b",
                    "traffic": "t8192.b4.remat.solo", "chips": 1,
                    "why": cell["why"]}
    assert len(cell["why"]) <= 200
    config = real["configs"][[c["name"] for c in real["configs"]].index(
        "lfm2-8b-a1b")]
    assert len(config["why"]) <= 200
    assert config["file"] == "chipbench/configs/lfm2-8b-a1b.json"
    for m in mine:
        spec = json.load(open(os.path.join(
            REPO, "chipbench", "metrics", m["name"] + ".json")))
        assert os.path.exists(os.path.join(
            REPO, "chipbench", "reducers", spec["reducer"] + ".py"))
        if "rules" in spec["params"]:
            assert spec["params"]["rules"] == "step_conv"
        if m["name"].endswith("_roofline"):
            assert (m["unit"], m["better"]) == ("%", "higher")


def test_the_conv_rule_table_is_step_json_with_five_phases_above_recompute():
    phases = os.path.join(REPO, "chipbench", "phases")
    base = json.load(open(os.path.join(phases, "step.json")))["rules"]
    mine = json.load(open(os.path.join(phases, "step_conv.json")))["rules"]
    added = [rule for rule in mine if rule not in base]
    assert [rule[0] for rule in added] == [
        "short_conv_project", "conv_gate", "expert_dispatch", "expert_ffn",
        "attention_project"]
    assert [rule for rule in mine if rule in base] == base
    assert mine.index(added[-1]) + 1 == [r[0] for r in mine].index("recompute")


@pytest.fixture(scope="module")
def tiny_manifest(tmp_path_factory):
    """The accepted manifest's metrics over one tiny cell of the family."""
    root = tmp_path_factory.mktemp("conv_gqa_moe")
    (root / "traffic").mkdir()
    (root / "traffic" / "t48.b2.remat.solo.json").write_text(json.dumps({
        "ranks": 1, "batch": 2, "seq_len": 48, "remat": True,
        "comm": "neighbor", "topology": "ExponentialTwoGraph",
        "backend": "auto"}))
    real = json.load(open(os.path.join(REPO, "BENCHMARK.json")))
    cell = "tinylfm2.solo"
    per_layer = [{**m, "workloads": [cell]} for m in real["per_layer"]
                 if "workloads" not in m or CELL in m["workloads"]]
    path = root / "BENCHMARK.json"
    path.write_text(json.dumps({
        "paths": [str(root), "chipbench"],
        "configs": [{"name": "tiny-lfm2", "file": os.path.join(
            REPO, "tests", "data", "conv_gqa_moe", "tiny-lfm2.json")}],
        "workloads": [{"name": cell, "config": "tiny-lfm2",
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
