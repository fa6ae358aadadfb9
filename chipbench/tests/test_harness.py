"""The harness end to end on the CPU, from data files that live only under
``tests/data``: a tiny configuration, a tiny traffic mix, a metric and a
reducer found by name, with no edit to a file of ``chipbench/`` proper."""

import json
import os
import subprocess
import sys

import numpy as np
import pytest

from conftest import REPO

MANIFEST = os.path.join(REPO, "chipbench", "tests", "data", "BENCHMARK.json")
RESULT_KEYS = {"correct", "attempted", "failed", "metrics", "device"}


def run_cli(workload, trace, env=None, seconds=1.5):
    proc = subprocess.run(
        [sys.executable, os.path.join(REPO, "chipbench", "run.py"),
         "--workload", workload, "--seed", "3", "--seconds", str(seconds),
         "--trace", str(trace), "--manifest", MANIFEST],
        capture_output=True, text=True, timeout=600,
        env={**os.environ, **(env or {})})
    return proc


@pytest.mark.parametrize("workload,chips", [("tiny.ring4", 4),
                                            ("tinyres.exp2x4", 4),
                                            ("tiny.solo", 1)])
def test_untraced_run_prints_the_contracts_line(workload, chips):
    proc = run_cli(workload, 0)
    assert proc.returncode == 0, proc.stderr[-2000:]
    result = json.loads(proc.stdout.splitlines()[-1])
    assert set(result) == RESULT_KEYS
    assert result["correct"] is True and result["failed"] == 0
    assert result["attempted"] > 8
    assert result["device"]["platform"] == "cpu"
    assert result["device"]["count"] == chips
    # a CPU run never prints a time or a rate under a device metric's name
    assert result["metrics"] == {}


def test_traced_run_finds_the_test_only_metric_and_reducer():
    proc = run_cli("tiny.ring4", 1)
    assert proc.returncode == 0, proc.stderr[-2000:]
    result = json.loads(proc.stdout.splitlines()[-1])
    assert set(result) == RESULT_KEYS          # no device lane, no breakdown
    metrics = result["metrics"]
    assert metrics["steps_completed"]["value"] > 8      # tests/data only
    assert metrics["compiles_in_window"]["value"] == 0
    # a ring of four: one exchange with each of two neighbours, fused
    assert metrics["gossip_calls_per_step"]["value"] == 2
    assert metrics["gossip_bytes_per_step"]["value"] > 0
    # host_clock and device_trace metrics are left out on the CPU
    assert "mfu" not in metrics and "device_idle_share" not in metrics
    assert result["correct"] is True


def test_no_accelerator_and_no_pin_is_an_error():
    proc = run_cli("tiny.solo", 0, env={"JAX_PLATFORMS": ""})
    assert proc.returncode != 0
    assert not proc.stdout.strip().endswith("}")
    assert "no TPU found" in proc.stderr


def test_unknown_workload_is_an_error():
    proc = run_cli("no.such.cell", 0)
    assert proc.returncode != 0 and "no.such.cell" in proc.stderr


def test_a_kind_without_a_peak_is_an_error():
    from chipbench.peaks import peaks_for

    assert peaks_for("TPU v5 lite") == (197e12, 819e9)
    with pytest.raises(SystemExit):
        peaks_for("TPU v9 imaginary")


@pytest.fixture(scope="module")
def settled_cell():
    """tiny.ring4 built in this process and stepped a little, so the ranks'
    weights have drifted apart."""
    from chipbench import cell as cells
    from chipbench import run

    manifest = cells.Manifest.load(MANIFEST)
    cell = cells.build_cell(manifest, "tiny.ring4", seed=5)
    state, cell.state = cell.state, None
    state, k, _ = run.drive(cell.step, state, cell.ring, 0, steps=12)
    return cell, state, k


def agreement_with(monkeypatch, settled_cell, w=None):
    import jax

    from chipbench import reference, run

    cell, state, k = settled_cell
    # the steps donate their input: give each trial its own copy
    state = jax.tree_util.tree_map(lambda x: x + 0, state)
    if w is not None:
        monkeypatch.setattr(reference, "mixing_matrix", lambda *_: w)
    return run.agreement(cell, state, k)


def test_agreement_holds_for_the_topologys_w(monkeypatch, settled_cell):
    ok, leaves, loss_err = agreement_with(monkeypatch, settled_cell)
    assert ok, (leaves[:3], loss_err)
    assert len(leaves) == 4 * 29          # every leaf of every rank


@pytest.mark.parametrize("wrong", ["identity", "uniform"])
def test_agreement_fails_for_a_wrong_w(monkeypatch, settled_cell, wrong):
    w = np.eye(4) if wrong == "identity" else np.full((4, 4), 0.25)
    ok, leaves, _ = agreement_with(monkeypatch, settled_cell, w=w)
    assert not ok and leaves[0][0] > 1.0, leaves[:3]


def test_a_leaf_takes_the_first_exception_that_names_it():
    from chipbench.reference import allowance

    tolerance = {"rtol": 1e-3, "atol": 1e-6, "exceptions": [
        {"leaves": r"\['qkv'\]\['bias'\]", "rtol": 0.0, "atol": 2e-3}]}
    assert allowance("[0]['block_1']['qkv']['bias']", 0.5, tolerance) == 2e-3
    assert allowance("[0]['block_1']['qkv']['kernel']", 0.5, tolerance) == (
        pytest.approx(5e-4 + 1e-6))
