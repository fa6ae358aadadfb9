"""The family ``sambay``: its two kernel cost functions and its model FLOPs
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

from chipbench import sambay_flops

FULL = 8192 * 8192 // 2                 # 33,554,432 visible pairs
BAND = 512 * 8192 - 512 * 512 // 2      # 4,063,232 under the window
KINDS = ("mamba", "diff_attention_window", "mamba", "diff_attention", "gmu",
         "cross_diff_attention")


def test_visible_pairs_full_band_and_a_window_wider_than_the_sequence():
    assert sambay_flops.visible_pairs(8192) == FULL
    assert sambay_flops.visible_pairs(8192, 512) == BAND == 4_063_232
    assert sambay_flops.visible_pairs(256, 512) == 256 * 256 / 2


def test_diff_attention_cost_by_hand():
    """40 maps a layer, QK^T at 64 and PV at 128: forward 2 * 40 * S * 192
    FLOPs over S visible pairs, twice under remat; backward 2 * 40 * S *
    (3 * 64 + 2 * 128); the window layer, the full layer, the cross layer."""
    flops, nbytes = sambay_flops.diff_attention_cost(
        1, 40, 20, 8192, 64, windows=[512, None, None], forward_calls=2)
    a_pair = 2 * 40 * (2 * 192 + 448)                          # 66,560
    assert flops == a_pair * (2 * FULL + BAND) == 4_737_214_709_760
    q = 40 * 8192 * 64 * 2
    kv = 2 * 20 * 8192 * 64 * 2            # as projected, not repeated
    o = 40 * 8192 * 128 * 2
    rows = 40 * 8192 * 4
    assert nbytes == 3 * (4 * (q + kv + o) + 3 * rows) == 2_025_062_400
    # without remat the forward is paid once
    once, _ = sambay_flops.diff_attention_cost(
        1, 40, 20, 8192, 64, windows=[None], forward_calls=1)
    assert once == 2 * 40 * FULL * (192 + 448)


def test_selective_scan_cost_by_hand():
    ops, nbytes = sambay_flops.selective_scan_cost(
        1, 8192, 5120, 16, layers=2, forward_calls=2)
    elements = 8192 * 5120 * 16
    assert ops == 2 * elements * (2 * 9 + 22) == 53_687_091_200
    wide, narrow = 8192 * 5120, 8192 * 16 * 2
    forward = wide * (2 + 4 + 2) + 2 * narrow      # x, delta in; m out; B, C
    backward = wide * (2 + 4 + 2) + 2 * narrow + wide * (2 + 4) + 2 * narrow
    assert nbytes == 2 * (2 * forward + backward) == 2_520_776_704


def test_model_flops_by_hand_leave_the_scan_out():
    mlp = 6 * 3 * 2560 * 10240
    mamba = 2 * (2560 * 10240 + 5120 * 192 + 160 * 5120 + 5120 * 2560)
    attention = 2 * (2560 * 5120 + 2560 * 2560)
    gmu, cross = 2 * 2560 * 5120, 2 * 2560 * 2560
    maps = 40 * 192 * (2 * FULL + BAND) // 8192
    head = 2560 * 25008
    macs = mlp + mamba + attention + gmu + cross + maps + head
    assert macs == 763_494_400
    got = sambay_flops.train_flops_per_token(
        kinds=KINDS, hidden=2560, ffn_width=10240, vocab_rows=25008,
        heads=40, kv_heads=20, inner=5120, state=16, dt_rank=160,
        seq_len=8192, window=512)
    assert got == 6 * macs == 4_580_966_400
    # the state size is in no matrix product but x_proj's two slices
    more_state = sambay_flops.train_flops_per_token(
        kinds=("mamba",), hidden=2560, ffn_width=10240, vocab_rows=25008,
        heads=40, kv_heads=20, inner=5120, state=32, dt_rank=160,
        seq_len=8192, window=512)
    base = sambay_flops.train_flops_per_token(
        kinds=("mamba",), hidden=2560, ffn_width=10240, vocab_rows=25008,
        heads=40, kv_heads=20, inner=5120, state=16, dt_rank=160,
        seq_len=8192, window=512)
    assert more_state - base == 6 * 5120 * 2 * 16


def test_the_real_cell_prices_its_kernels_from_the_configuration():
    from chipbench import cell as cells

    manifest = cells.Manifest.load(os.path.join(REPO, "BENCHMARK.json"))
    config, traffic = cells.open_cell(manifest, "phi4flash.t8192.solo")
    family = manifest.module("families", "sambay").build(config, traffic)
    costs = family.kernel_costs()
    assert costs["diff_attention"] == (4_737_214_709_760, 2_025_062_400)
    assert costs["selective_scan"] == (53_687_091_200, 2_520_776_704)
    assert family.flops_per_item() == 4_580_966_400
    assert family.items_per_step == 8192


@pytest.fixture(scope="module")
def tiny_manifest(tmp_path_factory):
    """The accepted manifest's metrics over one tiny cell of the family."""
    root = tmp_path_factory.mktemp("sambay")
    (root / "traffic").mkdir()
    (root / "traffic" / "t40.b2.remat.solo.json").write_text(json.dumps({
        "ranks": 1, "batch": 2, "seq_len": 40, "remat": True,
        "comm": "neighbor", "topology": "ExponentialTwoGraph",
        "backend": "auto"}))
    real = json.load(open(os.path.join(REPO, "BENCHMARK.json")))
    cell = "tinysambay.solo"
    per_layer = [{**m, "workloads": [cell]} for m in real["per_layer"]
                 if "workloads" not in m
                 or "phi4flash.t8192.solo" in m["workloads"]]
    path = root / "BENCHMARK.json"
    path.write_text(json.dumps({
        "paths": [str(root), "chipbench"],
        "configs": [{"name": "tiny-sambay", "file": os.path.join(
            REPO, "tests", "data", "sambay", "tiny-sambay.json")}],
        "workloads": [{"name": cell, "config": "tiny-sambay",
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
    assert {"ssm_scan_ms_per_step", "ssm_scan_roofline",
            "ssm_mix_ms_per_step", "diff_attention_ms_per_step",
            "diff_attention_roofline"} <= set(names)
    if trace:
        assert result["metrics"]["compiles_in_window"]["value"] == 0
        assert "ssm_scan_roofline" not in result["metrics"]    # CPU
    else:
        assert result["metrics"] == {}
