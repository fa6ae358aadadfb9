"""The family ``mamba2_gqa_moe``: its FLOP and byte functions against hand
counts at the published widths (ISSUE 48's table and forecast), and a tiny
configuration of it through the harness's command line on a virtual CPU
device, with the new per-layer metrics asked for (a manifest written here;
no file of ``chipbench/`` proper is touched)."""

import json
import os
import subprocess
import sys

import pytest

from conftest import REPO

from chipbench import mamba2_gqa_moe_flops as flops

T, BATCH, D = 8192, 2, 2688
KINDS = "MEMEM*EME"
CELL = "nemotron3nano.t8192.solo"
NEW_METRICS = ["nemotron_ssd_scan_ms_per_step", "nemotron_ssd_mix_ms_per_step",
               "nemotron_ssd_kernel_ms_per_step", "nemotron_ssd_roofline",
               "nemotron_expert_dispatch_ms_per_step",
               "nemotron_expert_ffn_ms_per_step",
               "nemotron_grouped_matmul_ms_per_step",
               "nemotron_grouped_matmul_roofline",
               "nemotron_attention_ms_per_step",
               "nemotron_attention_roofline"]
SHAPES = dict(kinds=KINDS, hidden=D, mamba_heads=64, mamba_head_dim=64,
              state=128, groups=8, heads=32, kv_heads=2, head_dim=128,
              seq_len=T, router_outputs=128, top_k=6, experts_held=8,
              expert_width=1856, shared_width=3712, vocab_rows=16384)


def test_model_flops_by_hand():
    """A token's forward pass: the table of ISSUE 48, the scan counted as
    the recurrence (5 operations a state element) where the issue's
    forecast counted the chunked form's products (80.9 MFLOP a block)."""
    mamba = D * (2 * 4096 + 2 * 8 * 128 + 64) + 4096 * D          # macs
    assert mamba == 27_697_152 + 11_010_048 == flops.mamba2_macs(
        D, 64, 64, 128, 8)
    scan = 5 * 64 * 64 * 128                                       # ops
    assert scan == 2_621_440 == flops.ssd_forward_ops(64, 64, 128)
    projections = D * (32 + 2 * 2) * 128 + 32 * 128 * D            # 23.4 M
    scores = 32 * 2 * 128 * T // 2                                 # 33.6 M
    assert flops.attention_macs(D, 32, 2, 128, T) == projections + scores
    assert 2 * (projections + scores) == 113_901_568               # 113.9
    experts = D * 128 + 2 * D * 3712 + 6 * 8 / 128 * 2 * D * 1856
    assert experts == flops.expert_macs(D, 128, 6, 8, 1856, 3712)
    assert round(2 * experts) == 48_082_944                        # 48.1
    head = D * 16384
    forward = 4 * (2 * mamba + scan) + 2 * (projections + scores) + (
        4 * 2 * experts) + 2 * head
    assert flops.forward_flops_per_token(**SHAPES) == forward
    assert round(forward) == 714_457_088
    assert flops.train_flops_per_token(**SHAPES) == 3 * forward
    # where the work is: the Mamba-2 blocks 45 %, the expert blocks 27 %,
    # attention 16 %, the head 12 %; the scan itself under 2 %
    assert 0.44 < 4 * (2 * mamba + scan) / forward < 0.46
    assert 0.26 < 4 * 2 * experts / forward < 0.28
    assert 0.15 < 113_901_568 / forward < 0.17
    assert 0.12 < 2 * head / forward < 0.13
    assert 4 * scan / forward < 0.02


def test_the_scan_s_operations_and_bytes_by_hand():
    """Four layers of 16,384 tokens, the forward twice under remat: 26
    operations a state element and token; 21 KB a token forward, 33.8 KB
    backward: 4.97 GB, 6.06 ms at 819 GB/s against 4.53 ms of matrix unit."""
    ops, nbytes = flops.ssd_cost(BATCH, T, 64, 64, 128, 8, layers=4,
                                 forward_calls=2)
    assert ops == 4 * BATCH * T * 64 * 64 * 128 * (2 * 5 + 16)
    operands = 64 * 64 * 2 + 2 * 8 * 128 * 2 + 2 * 64 * 4          # 12,800
    result = 64 * 64 * 2
    assert nbytes == 4 * BATCH * T * (2 * (operands + result)
                                      + 2 * operands + result)
    assert nbytes == 4_966_055_936
    assert 6.0 < nbytes / 819e9 * 1e3 < 6.1
    assert 4.5 < ops / 197e12 * 1e3 < 4.6


def test_the_real_cell_prices_its_kernels_from_the_configuration():
    from chipbench import cell as cells

    manifest = cells.Manifest.load(os.path.join(REPO, "BENCHMARK.json"))
    config, traffic = cells.open_cell(manifest, CELL)
    family = manifest.module("families", "mamba2_gqa_moe").build(config,
                                                                 traffic)
    costs = family.kernel_costs()
    pairs = T * T // 2
    # 32 heads of 128; QK^T and PV forward (twice under remat), five
    # products backward; keys and values at the 2 heads they are projected in
    q, kv, rows = BATCH * 32 * T * 128 * 2, 2 * BATCH * 2 * T * 128 * 2, (
        BATCH * 32 * T * 4)
    assert costs["attention"] == (
        9 * 2 * BATCH * 32 * 128 * pairs,
        2 * (2 * q + kv + rows) + 4 * q + 2 * kv + rows)
    # 6,144 of 98,304 assignments a layer; TWO products an expert: four
    # forward under remat and four backward, four layers, at 1,856 columns
    assert costs["grouped_matmul"][0] == 4 * 8 * 2 * 6144 * D * 1856
    assert costs["ssd"] == flops.ssd_cost(BATCH, T, 64, 64, 128, 8, layers=4,
                                          forward_calls=2)
    assert family.flops_per_item() == 3 * 714_457_088
    assert family.items_per_step == BATCH * T == 16384
    assert family.kinds == KINDS


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
    assert cell == {"name": CELL, "config": "nemotron-3-nano-30b-a3b",
                    "traffic": "t8192.b2.remat.solo", "chips": 1,
                    "why": cell["why"]}
    assert len(cell["why"]) <= 200 and "1/16" in cell["why"]
    assert len(real["workloads"]) == 10
    assert sum(w["chips"] == 4 for w in real["workloads"]) == 1
    config = real["configs"][[c["name"] for c in real["configs"]].index(
        "nemotron-3-nano-30b-a3b")]
    assert len(config["why"]) <= 200
    assert config["file"] == "chipbench/configs/nemotron-3-nano-30b-a3b.json"
    for m in mine:
        spec = json.load(open(os.path.join(
            REPO, "chipbench", "metrics", m["name"] + ".json")))
        assert os.path.exists(os.path.join(
            REPO, "chipbench", "reducers", spec["reducer"] + ".py"))
        if "rules" in spec["params"]:
            assert spec["params"]["rules"] == "step_ssd"
        if m["name"].endswith("_roofline"):
            assert (m["unit"], m["better"]) == ("%", "higher")
            assert spec["params"]["cost"] in ("ssd", "grouped_matmul",
                                              "attention")


def test_the_ssd_rule_table_is_step_json_with_four_phases_above_recompute():
    phases = os.path.join(REPO, "chipbench", "phases")
    base = json.load(open(os.path.join(phases, "step.json")))["rules"]
    mine = json.load(open(os.path.join(phases, "step_ssd.json")))["rules"]
    added = [rule for rule in mine if rule not in base]
    assert [rule[0] for rule in added] == [
        "ssd_scan", "ssd_mix", "expert_dispatch", "expert_ffn"]
    assert [rule for rule in mine if rule in base] == base
    assert mine.index(added[-1]) + 1 == [r[0] for r in mine].index("recompute")


@pytest.fixture(scope="module")
def tiny_manifest(tmp_path_factory):
    """The accepted manifest's metrics over one tiny cell of the family."""
    root = tmp_path_factory.mktemp("mamba2_gqa_moe")
    (root / "traffic").mkdir()
    (root / "traffic" / "t48.b2.remat.solo.json").write_text(json.dumps({
        "ranks": 1, "batch": 2, "seq_len": 48, "remat": True,
        "comm": "neighbor", "topology": "ExponentialTwoGraph",
        "backend": "auto"}))
    real = json.load(open(os.path.join(REPO, "BENCHMARK.json")))
    cell = "tinynemotron.solo"
    per_layer = [{**m, "workloads": [cell]} for m in real["per_layer"]
                 if "workloads" not in m or CELL in m["workloads"]]
    path = root / "BENCHMARK.json"
    path.write_text(json.dumps({
        "paths": [str(root), "chipbench"],
        "configs": [{"name": "tiny-nemotron", "file": os.path.join(
            REPO, "tests", "data", "mamba2_gqa_moe", "tiny-nemotron.json")}],
        "workloads": [{"name": cell, "config": "tiny-nemotron",
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
