"""The family ``linear_latent_moe``: the delta rule's cost function and the
model's FLOPs against hand counts at the published widths, and a tiny
configuration of it through the harness's command line on a virtual CPU
device, with the new per-layer metrics asked for (a manifest written here;
no file of ``chipbench/`` proper is touched)."""

import json
import os
import subprocess
import sys

import pytest

from conftest import REPO

from chipbench import kda_flops

T, HEADS, D = 8192, 16, 128
KINDS = ("kda",) * 4 + ("latent_attention",) + ("kda",) * 2
CELL = "ling3flash.t8192.solo"
NEW_METRICS = {"kda_scan_ms_per_step", "kda_scan_roofline",
               "kda_mix_ms_per_step"}


def test_kda_cost_counts_the_recurrence_by_hand():
    """A token and head: decay 1, k^T S 2, the rank-one update 2, S^T q 2
    operations a state element forward; 22 backward (the states again and
    the adjoints); the forward twice under remat.  Nothing of the chunking:
    no chunk length enters."""
    ops, nbytes = kda_flops.kda_cost(1, T, HEADS, D, D, layers=6,
                                     forward_calls=2)
    assert kda_flops.DELTA_FORWARD_OPS == 1 + 2 + 2 + 2
    assert ops == 6 * T * HEADS * D * D * (2 * 7 + 22) == 463_856_467_968
    operands = T * HEADS * (2 * D * 2 + D * 2 + D * 4 + 4)   # q k v g beta
    result = T * HEADS * D * 2
    assert nbytes == 6 * (2 * (operands + result)
                          + operands + result + operands)
    assert nbytes == 4_643_094_528
    once, _ = kda_flops.kda_cost(2, 64, 4, 128, 64, forward_calls=1)
    assert once == 2 * 64 * 4 * 128 * 64 * 29


def test_the_bound_is_the_memory_one_and_the_share_can_be_read():
    from chipbench.peaks import peaks_for

    flops, hbm = peaks_for("TPU v5 lite")
    ops, nbytes = kda_flops.kda_cost(1, T, HEADS, D, D, layers=6,
                                     forward_calls=2)
    compute_ms, memory_ms = ops / flops * 1e3, nbytes / hbm * 1e3
    assert 2.3 < compute_ms < 2.4 and 5.6 < memory_ms < 5.7


def test_model_flops_by_hand():
    kda_projections = 5 * 2560 * 16 * 128 + 2 * 2560 * 16     # 26,296,320
    delta = 7 * 16 * 128 * 128                                # 1,835,008 ops
    mla_projections = (2560 * 16 * 192 + 2560 * (512 + 64)
                       + 512 * 16 * 256 + 2560 * 16 + 16 * 128 * 2560)
    assert mla_projections == 16_719_872
    scores = 16 * T * (192 + 128) // 2                        # 20,971,520
    dense = 3 * 2560 * 6144
    expert_block = 2560 * 512 + 3 * 2560 * 768 + 0.125 * 3 * 2560 * 768
    head = 2560 * 19648
    macs = (6 * kda_projections + mla_projections + scores + dense
            + 6 * expert_block + head)
    assert macs == 340_631_552
    got = kda_flops.train_flops_per_token(
        kinds=KINDS, hidden=2560, heads=16, kda_dim=128, kv_rank=512,
        nope=128, rope=64, v_dim=128, seq_len=T, dense_blocks=1,
        dense_width=6144, expert_width=768, shared_experts=1,
        router_outputs=512, top_k=8, experts_held=8, vocab_rows=19648)
    assert got == 3 * (2 * macs + 6 * delta) == 2_076_819_456
    # the six KDA mixers are about 47 % of a token's forward pass
    share = 6 * (2 * kda_projections + delta) / (got / 3)
    assert 0.46 < share < 0.48


def test_the_real_cell_prices_its_kernels_from_the_configuration():
    from chipbench import cell as cells

    manifest = cells.Manifest.load(os.path.join(REPO, "BENCHMARK.json"))
    config, traffic = cells.open_cell(manifest, CELL)
    family = manifest.module("families", "linear_latent_moe").build(
        config, traffic)
    costs = family.kernel_costs()
    assert costs["kda"] == (463_856_467_968, 4_643_094_528)
    # one latent layer: 9 products of 2 * 16 * T * T / 2, five at 192 + four
    # at 128 under remat
    assert costs["mla_attention"][0] == 16 * T * T * (5 * 192 + 4 * 128)
    assert costs["grouped_matmul"][0] == 6 * 12 * 2 * 1024 * 2560 * 768
    assert family.flops_per_item() == 2_076_819_456
    assert family.items_per_step == T


def test_the_manifest_gains_one_cell_and_three_metrics_of_it():
    real = json.load(open(os.path.join(REPO, "BENCHMARK.json")))
    mine = [m for m in real["per_layer"] if m.get("workloads") == [CELL]]
    assert {m["name"] for m in mine} == NEW_METRICS
    assert all(m["moves"] == "throughput_per_chip" for m in mine)
    assert real["per_layer"][-len(mine):] == mine        # appended, at the end
    assert real["workloads"][-1] == {
        "name": CELL, "config": "ling-3.0-flash",
        "traffic": "t8192.b1.remat.solo", "chips": 1,
        "why": real["workloads"][-1]["why"]}
    assert len(real["workloads"][-1]["why"]) <= 200
    assert len(real["configs"][-1]["why"]) <= 200
    assert real["configs"][-1]["name"] == "ling-3.0-flash"
    for m in mine:
        spec = json.load(open(os.path.join(
            REPO, "chipbench", "metrics", m["name"] + ".json")))
        assert os.path.exists(os.path.join(
            REPO, "chipbench", "reducers", spec["reducer"] + ".py"))
        if "rules" in spec["params"]:
            assert os.path.exists(os.path.join(
                REPO, "chipbench", "phases", spec["params"]["rules"] + ".json"))


def test_the_kda_rule_table_is_step_json_with_two_phases_above_recompute():
    phases = os.path.join(REPO, "chipbench", "phases")
    base = json.load(open(os.path.join(phases, "step.json")))["rules"]
    mine = json.load(open(os.path.join(phases, "step_kda.json")))["rules"]
    added = [rule for rule in mine if rule not in base]
    assert [rule[0] for rule in added] == ["kda_scan", "kda_mix"]
    assert [rule for rule in mine if rule in base] == base
    assert mine.index(added[1]) + 1 == [r[0] for r in mine].index("recompute")


@pytest.fixture(scope="module")
def tiny_manifest(tmp_path_factory):
    """The accepted manifest's metrics over one tiny cell of the family."""
    root = tmp_path_factory.mktemp("linear_latent_moe")
    (root / "traffic").mkdir()
    (root / "traffic" / "t40.b2.remat.solo.json").write_text(json.dumps({
        "ranks": 1, "batch": 2, "seq_len": 40, "remat": True,
        "comm": "neighbor", "topology": "ExponentialTwoGraph",
        "backend": "auto"}))
    real = json.load(open(os.path.join(REPO, "BENCHMARK.json")))
    cell = "tinyling.solo"
    per_layer = [{**m, "workloads": [cell]} for m in real["per_layer"]
                 if "workloads" not in m or CELL in m["workloads"]]
    path = root / "BENCHMARK.json"
    path.write_text(json.dumps({
        "paths": [str(root), "chipbench"],
        "configs": [{"name": "tiny-ling", "file": os.path.join(
            REPO, "tests", "data", "linear_latent_moe", "tiny-ling.json")}],
        "workloads": [{"name": cell, "config": "tiny-ling",
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
        assert "kda_scan_roofline" not in result["metrics"]    # CPU
    else:
        assert result["metrics"] == {}
