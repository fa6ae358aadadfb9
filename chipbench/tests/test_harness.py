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


def agreement_with(monkeypatch, settled_cell, w=None, report=None):
    import jax

    from chipbench import reference, run

    cell, state, k = settled_cell
    # the steps donate their input: give each trial its own copy
    state = jax.tree_util.tree_map(lambda x: x + 0, state)
    if w is not None:
        monkeypatch.setattr(reference, "mixing_matrix", lambda *_: w)
    return run.agreement(cell, state, k, report)


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


def buffers(arrays):
    """Device buffer -> its bytes, for every shard of ``arrays``: a buffer
    that several arrays share counts once."""
    return {s.data.unsafe_buffer_pointer(): s.data.nbytes
            for a in arrays for s in a.addressable_shards}


def live_bytes():
    import gc

    import jax

    gc.collect()
    return sum(buffers(jax.live_arrays()).values())


@pytest.fixture(scope="module", params=["tiny.solo", "tiny.ring4"])
def watched_agreement(request):
    """One agreement check of the cell, watched from outside: the state it
    was given, the bytes alive on the devices at each of the system's steps,
    and what ``reference.run`` was handed."""
    import jax

    from chipbench import cell as cells
    from chipbench import reference, run

    cell = cells.build_cell(cells.Manifest.load(MANIFEST), request.param,
                            seed=7)
    state, cell.state = cell.state, None
    state, k, _ = run.drive(cell.step, state, cell.ring, 0, steps=6)
    params, model_state, opt_state = state
    # copies of the host's own: a view of a CPU buffer would pin it
    seen = {"given": jax.tree_util.tree_map(
        np.array, jax.device_get((params, model_state, opt_state.base_state))),
        "bytes_at_step": []}
    del params, model_state, opt_state
    system_step, reference_run = cell.step, reference.run

    def step(state, batch):
        seen["bytes_at_step"].append(live_bytes())
        return system_step(state, batch)

    def run_reference(family, base_opt, atc, w, states, *rest, **kwargs):
        blocks = jax.tree_util.tree_leaves(states)
        others = [a for a in jax.live_arrays()
                  if not any(a is b for b in blocks)]
        seen["handed"] = jax.tree_util.tree_map(
            np.array, jax.device_get(states))
        seen["shared"] = set(buffers(blocks)) & set(buffers(others))
        seen["on_own_device"] = [
            [b.devices() == {d} for b in jax.tree_util.tree_leaves(s)]
            for s, d in zip(states, cell.devices)]
        donated = [leaf for s in states
                   for leaf in jax.tree_util.tree_leaves(s[1:])]
        out = reference_run(family, base_opt, atc, w, states, *rest, **kwargs)
        seen["donated"] = [leaf.is_deleted() for leaf in donated]
        return out

    with pytest.MonkeyPatch.context() as patch:
        patch.setattr(cell, "step", step)
        patch.setattr(reference, "run", run_reference)
        seen["bytes_before"] = live_bytes()
        seen["result"] = run.agreement(cell, state, k)
    return cell, seen


@pytest.mark.parametrize("check", [
    "starts_from_the_same_bits", "one_state_on_the_chip",
    "blocks_of_its_own"])
def test_the_references_state_waits_on_the_host(watched_agreement, check):
    cell, seen = watched_agreement
    ok, leaves, loss_err = seen["result"]
    assert ok, (leaves[:3], loss_err)
    if check == "starts_from_the_same_bits":
        # what reference.run starts from is the state agreement was given
        import jax

        given = jax.tree_util.tree_leaves(seen["given"])
        assert given
        for r, handed in enumerate(seen["handed"]):
            handed = jax.tree_util.tree_leaves(handed)
            assert len(handed) == len(given)
            for a, b in zip(given, handed):
                assert b.shape == (1,) + a.shape[1:] and b.dtype == a.dtype
                assert a[r:r + 1].tobytes() == b.tobytes()
    elif check == "one_state_on_the_chip":
        # during the system's steps no second copy of the state is alive on
        # the devices; the steps' own [ranks] f32 losses are the slack
        assert len(seen["bytes_at_step"]) == 3
        slack = 2 * 4 * len(cell.devices)
        assert max(seen["bytes_at_step"]) <= seen["bytes_before"] + slack
    else:
        # arrays of the reference's own, each on its rank's device: no
        # buffer shared with another live array, so that donating them
        # (reference.run does) deletes nothing that is read later
        assert seen["shared"] == set()
        assert all(all(rank) for rank in seen["on_own_device"])
        assert seen["donated"] and all(seen["donated"])


def plain_decoder_loss(params, model_state, batch, heads=4, positions=True):
    """``models/transformer.py``'s decoder and the family's loss in plain
    ``jax.numpy`` and f32: what a family brings as ``reference_loss``.
    ``positions=False`` leaves out the position embedding, a dropped term."""
    import jax
    import jax.numpy as jnp

    def norm(x, p):
        mean = x.mean(-1, keepdims=True)
        var = ((x - mean) ** 2).mean(-1, keepdims=True)
        return (x - mean) / jnp.sqrt(var + 1e-6) * p["scale"] + p["bias"]

    def dense(x, p):
        return x @ p["kernel"] + p.get("bias", 0.0)

    tokens, targets = batch[:, :-1], batch[:, 1:]
    b, t = tokens.shape
    x = params["tok"]["embedding"][tokens]
    if positions:
        x = x + params["pos"]["embedding"][:t]
    causal = jnp.tril(jnp.ones((t, t), bool))
    for i in range(sum(name.startswith("block_") for name in params)):
        p = params[f"block_{i}"]
        q, k, v = (h.reshape(b, t, heads, -1) for h in jnp.split(
            dense(norm(x, p["ln1"]), p["qkv"]), 3, axis=-1))
        scores = jnp.einsum("bqhd,bkhd->bhqk", q, k) / q.shape[-1] ** 0.5
        weights = jax.nn.softmax(jnp.where(causal, scores, -jnp.inf), -1)
        x = x + dense(jnp.einsum("bhqk,bkhd->bqhd", weights, v).reshape(
            b, t, -1), p["proj"])
        x = x + dense(jax.nn.gelu(dense(norm(x, p["ln2"]), p["up"])),
                      p["down"])
    logits = dense(norm(x, params["ln_f"]), params["lm_head"])
    picked = jnp.take_along_axis(jax.nn.log_softmax(logits),
                                 targets[..., None], -1)
    return -picked.mean()


class FamilyWithReference:
    """The cell's family, and a ``reference_loss`` beside it."""

    def __init__(self, family, reference_loss):
        self.family, self.reference_loss = family, reference_loss

    def __getattr__(self, name):
        return getattr(self.family, name)


@pytest.mark.parametrize("positions,agrees", [(True, True), (False, False)])
def test_a_familys_reference_loss_is_held_to_model_loss_rtol(
        monkeypatch, settled_cell, positions, agrees):
    import functools

    from chipbench import run

    cell, state, k = settled_cell
    monkeypatch.setattr(cell, "family", FamilyWithReference(
        cell.family, functools.partial(plain_decoder_loss,
                                       positions=positions)))
    monkeypatch.setattr(cell, "config", {**cell.config, "tolerance": {
        **cell.config["tolerance"], "model_loss_rtol": 1e-4}})
    report = {}
    ok, leaves, _ = agreement_with(monkeypatch, settled_cell, report=report)
    assert leaves[0][0] <= 1.0        # optimizer and gossip agree either way
    assert ok is agrees, report
    assert (report["model_loss"]["rel_err"] <= 1e-4) is agrees
    assert report["model_loss"]["system"] > 0


def test_without_reference_loss_nothing_of_the_model_is_evaluated(
        monkeypatch, settled_cell):
    report = {}
    ok, _, _ = agreement_with(monkeypatch, settled_cell, report=report)
    assert ok and set(report) == {"memory"}
