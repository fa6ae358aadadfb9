"""The family ``gqa_moe``: its attention cost function and its model FLOPs
against hand counts at the published widths, and a tiny configuration of it
through the harness's command line on a virtual CPU device, with the new
per-layer metrics asked for (a manifest written here; no file of
``chipbench/`` proper is touched)."""

import json
import os
import subprocess
import sys

import pytest

from conftest import REPO

from chipbench import gqa_moe_flops

T = 16384
FULL = T * T // 2                        # 134,217,728 visible pairs
BAND = 4096 * T - 4096 * 4096 // 2       # 58,720,256 under the window
KINDS = ("full_attention",) + ("window_rotary_attention",) * 3
CELL = "smallthinker.t16384.solo"
NEW_METRICS = {"gqa_attention_ms_per_step", "gqa_attention_roofline",
               "st_grouped_matmul_ms_per_step", "st_grouped_matmul_roofline",
               "st_expert_dispatch_ms_per_step", "gqa_project_ms_per_step"}


def test_windows_of_the_period():
    assert gqa_moe_flops.windows_of(KINDS, 4096) == [None, 4096, 4096, 4096]
    # the window layers see 3,584 keys a query on average, the full one 8,192
    assert BAND / T == 3584 and FULL / T == 8192


def test_gqa_attention_cost_by_hand():
    """28 heads, QK^T and PV at 128 forward (twice under remat) and five
    products backward: 9 * 2 * 28 * 128 FLOPs a visible pair; one full layer
    and three window layers."""
    flops, nbytes = gqa_moe_flops.gqa_attention_cost(
        1, 28, 4, T, 128, windows=[None, 4096, 4096, 4096], forward_calls=2)
    assert flops == 9 * 2 * 28 * 128 * (FULL + 3 * BAND)
    assert flops == 20_023_137_533_952
    q = 28 * T * 128 * 2                   # also o, do, dq
    kv = 2 * 4 * T * 128 * 2               # as projected, not repeated
    rows = 28 * T * 4
    assert nbytes == 4 * (2 * (2 * q + kv + rows) + 4 * q + 2 * kv + rows)
    assert nbytes == 4_316_987_392
    # without remat the forward is paid once
    once, _ = gqa_moe_flops.gqa_attention_cost(
        1, 28, 4, T, 128, windows=[None], forward_calls=1)
    assert once == 7 * 2 * 28 * 128 * FULL
    # a window wider than the sequence is the full triangle
    wide, _ = gqa_moe_flops.gqa_attention_cost(
        1, 28, 4, 2048, 128, windows=[4096], forward_calls=1)
    assert wide == 7 * 2 * 28 * 128 * 2048 * 2048 / 2


def test_model_flops_by_hand():
    projections = 2560 * (28 + 2 * 4) * 128 + 28 * 128 * 2560   # 20,971,520
    router = 2560 * 64
    held = 6 * 16 / 64 * 3 * 2560 * 768                          # 8,847,360
    scores = 28 * 2 * 128 * (FULL + 3 * BAND) // T               # 135,790,592
    head = 2560 * 18992
    macs = 4 * (projections + router + held) + scores + head
    assert macs == 304_340_992
    got = gqa_moe_flops.train_flops_per_token(
        kinds=KINDS, hidden=2560, heads=28, kv_heads=4, head_dim=128,
        seq_len=T, window=4096, router_outputs=64, top_k=6, experts_held=16,
        expert_width=768, vocab_rows=18992)
    assert got == 6 * macs == 1_826_045_952
    # the attention kernels of the two masks are about 45 % of it
    assert 0.42 < scores / macs < 0.46


def test_the_real_cell_prices_its_kernels_from_the_configuration():
    from chipbench import cell as cells

    manifest = cells.Manifest.load(os.path.join(REPO, "BENCHMARK.json"))
    config, traffic = cells.open_cell(manifest, CELL)
    family = manifest.module("families", "gqa_moe").build(config, traffic)
    costs = family.kernel_costs()
    assert costs["gqa_attention"] == (20_023_137_533_952, 4_316_987_392)
    assert costs["grouped_matmul"][0] == 4 * 12 * 2 * 24576 * 2560 * 768
    assert family.flops_per_item() == 1_826_045_952
    assert family.items_per_step == T


def test_the_manifest_gains_one_cell_and_six_metrics_of_it():
    real = json.load(open(os.path.join(REPO, "BENCHMARK.json")))
    mine = [m for m in real["per_layer"] if m.get("workloads") == [CELL]]
    assert {m["name"] for m in mine} == NEW_METRICS
    assert all(m["moves"] == "throughput_per_chip" for m in mine)
    assert real["per_layer"][-len(mine):] == mine        # appended, at the end
    assert real["workloads"][-1] == {
        "name": CELL, "config": "smallthinker-21b-a3b",
        "traffic": "t16384.b1.remat.solo", "chips": 1,
        "why": real["workloads"][-1]["why"]}
    assert len(real["workloads"][-1]["why"]) <= 200
    for m in mine:
        spec = json.load(open(os.path.join(
            REPO, "chipbench", "metrics", m["name"] + ".json")))
        assert os.path.exists(os.path.join(
            REPO, "chipbench", "reducers", spec["reducer"] + ".py"))
        if "rules" in spec["params"]:
            assert os.path.exists(os.path.join(
                REPO, "chipbench", "phases", spec["params"]["rules"] + ".json"))


@pytest.fixture(scope="module")
def tiny_manifest(tmp_path_factory):
    """The accepted manifest's metrics over one tiny cell of the family."""
    root = tmp_path_factory.mktemp("gqa_moe")
    (root / "traffic").mkdir()
    (root / "traffic" / "t40.b2.remat.solo.json").write_text(json.dumps({
        "ranks": 1, "batch": 2, "seq_len": 40, "remat": True,
        "comm": "neighbor", "topology": "ExponentialTwoGraph",
        "backend": "auto"}))
    real = json.load(open(os.path.join(REPO, "BENCHMARK.json")))
    cell = "tinygqamoe.solo"
    per_layer = [{**m, "workloads": [cell]} for m in real["per_layer"]
                 if "workloads" not in m or CELL in m["workloads"]]
    path = root / "BENCHMARK.json"
    path.write_text(json.dumps({
        "paths": [str(root), "chipbench"],
        "configs": [{"name": "tiny-gqa-moe", "file": os.path.join(
            REPO, "tests", "data", "gqa_moe", "tiny-gqa-moe.json")}],
        "workloads": [{"name": cell, "config": "tiny-gqa-moe",
                       "traffic": "t40.b2.remat.solo", "chips": 1}],
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
    assert NEW_METRICS <= set(names)
    if trace:
        assert result["metrics"]["compiles_in_window"]["value"] == 0
        assert "gqa_attention_roofline" not in result["metrics"]    # CPU
    else:
        assert result["metrics"] == {}
