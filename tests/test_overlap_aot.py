"""Comm/compute overlap as a REGRESSION TEST, via AOT TPU compilation.

The overlap contract (reference SURVEY.md §3.3: gossip rides under
backprop) is checkable without hardware: the PJRT topology API compiles for
a v5e:2x4 slice offline, and the scheduled HLO shows whether compute sits
inside the async collective windows.  Skips cleanly when libtpu / the
topology API is unavailable.

Marked ``slow``: loading the AOT TPU topology costs ~8 minutes of fixture
setup in this container — a third of the tier-1 1,470 s budget for one
module — so the budgeted run (``-m 'not slow'``) excludes it and the full
suite (plain ``pytest``) keeps it.
"""

import jax
import jax.numpy as jnp
import numpy as np
import optax
import pytest
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from bluefog_tpu.utils.inspect import collective_overlap_report

pytestmark = pytest.mark.slow


def test_gossip_step_overlaps_in_compiled_tpu_schedule(tpu_aot_topology):
    # (benchmarks/overlap_report.py compiles the same harness shape with a
    # heavier model for the published numbers; this test stays small so the
    # suite remains fast)
    topo = tpu_aot_topology
    n = len(topo.devices)  # single source for every shape below
    mesh = Mesh(np.array(topo.devices), ("bf",))

    from bluefog_tpu.models import LeNet5
    from bluefog_tpu.optim import DistributedNeighborAllreduceOptimizer
    from bluefog_tpu.parallel.api import shard_map
    from bluefog_tpu.topology import ExponentialTwoGraph
    from bluefog_tpu.topology.schedule import build_schedule

    model = LeNet5(num_classes=10)
    sched = build_schedule(ExponentialTwoGraph(n))
    opt = DistributedNeighborAllreduceOptimizer(
        optax.sgd(0.1), topology=sched, axis_name="bf")

    def step(p_blk, x_blk, y_blk):
        p = jax.tree_util.tree_map(lambda t: t[0], p_blk)
        st = opt.init(p)

        def loss_fn(p):
            return optax.softmax_cross_entropy_with_integer_labels(
                model.apply(p, x_blk[0]), y_blk[0]).mean()

        loss, g = jax.value_and_grad(loss_fn)(p)
        upd, st = opt.update(g, st, p)
        p = optax.apply_updates(p, upd)
        return jax.tree_util.tree_map(lambda t: t[None], p), loss[None]

    fn = jax.jit(shard_map(
        step, mesh=mesh, in_specs=(P("bf"),) * 3,
        out_specs=(P("bf"), P("bf")), check_vma=False))

    batch = 8
    params = jax.eval_shape(
        lambda k: model.init(k, jnp.zeros((batch, 28, 28, 1))),
        jax.random.PRNGKey(0))

    def stacked(t):
        return jax.ShapeDtypeStruct((n,) + t.shape, t.dtype,
                                    sharding=NamedSharding(mesh, P("bf")))

    args = (
        jax.tree_util.tree_map(stacked, params),
        jax.ShapeDtypeStruct((n, batch, 28, 28, 1), jnp.float32,
                             sharding=NamedSharding(mesh, P("bf"))),
        jax.ShapeDtypeStruct((n, batch), jnp.int32,
                             sharding=NamedSharding(mesh, P("bf"))),
    )
    rep = collective_overlap_report(fn, *args)
    # the fused gossip emits async start/done pairs...
    assert rep["pairs"] > 0, rep
    # ...and the latency-hiding scheduler puts real compute inside windows
    assert rep["overlapped_fraction"] > 0, rep


# ---- the four-rank GPT-2 cell's step, compiled for the host it runs on ----

GPT2_MARKS = {
    "attention_fwd": r"%flash_attention\S* = ",
    "attention_bwd": r"%flash_mha_bwd\S* = ",
}
GB = 1e9


def _compile_cell_step(monkeypatch, comm):
    """``chipbench/cell.py::build_step`` for ``gpt2s.t2048.exp2x4`` (the
    widths of ``chipbench/configs/gpt2-small.json``, T=2048, batch 8, AdamW,
    ``ExponentialTwoGraph(4)``, adapt-with-combine), lowered on shapes for
    ``v5e:2x2`` in the ring order ``bf.init`` uses."""
    import importlib
    import os
    import sys
    import types

    from conftest import aot_topology

    repo = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    if repo not in sys.path:
        sys.path.insert(0, repo)
    from chipbench import cell as cells

    from bluefog_tpu.topology import ExponentialTwoGraph
    from bluefog_tpu.topology.mapping import ici_ring_order
    from bluefog_tpu.topology.schedule import build_schedule

    # what the chip would answer: a TPU backend for the attention kernel's
    # eligibility
    ring_attention = importlib.import_module("bluefog_tpu.ops.ring_attention")
    monkeypatch.setattr(ring_attention, "_flash_eligible",
                        lambda *a, **k: True)

    devices = ici_ring_order(aot_topology("v5e:2x2").devices)
    n = len(devices)
    mesh = Mesh(np.array(devices), ("bf",))
    manifest = cells.Manifest.load(os.path.join(repo, "BENCHMARK.json"))
    config, traffic = cells.open_cell(manifest, "gpt2s.t2048.exp2x4")
    assert not config["atc"]
    traffic = {**traffic, "comm": comm}
    family = manifest.module("families", config["family"]).build(
        config, traffic)
    ctx = types.SimpleNamespace(
        schedule=build_schedule(ExponentialTwoGraph(n)), axis_name="bf",
        mesh=mesh)
    opt, step = cells.build_step(family, config, traffic, ctx)

    def init(key):
        params, model_state = family.init(key)
        return (params, model_state, opt.init(params)), family.make_batch(key)

    sharding = NamedSharding(mesh, P("bf"))
    state, batch = jax.tree_util.tree_map(
        lambda t: jax.ShapeDtypeStruct((n,) + t.shape, t.dtype,
                                       sharding=sharding),
        jax.eval_shape(init, jax.random.PRNGKey(0)))
    return step.lower(state, batch).compile()


def test_gpt2_step_exchanges_in_bounded_async_pieces_on_v5e_2x2(monkeypatch):
    """The contract of PR 31, checked where no chip is needed (about three
    minutes of compilation): on four ranks the parameter exchange lowers to
    asynchronous collective-permutes and to no core-blocking kernel; no piece is larger than the largest leaf; what
    XLA keeps in flight across the whole step (the handful it opens before
    the forward pass) is a few small pieces, every other transfer opens
    after the last attention backward kernel, where the loss's memory is
    free; and the exchange costs no more than 0.80 GB of HLO temp over the
    same step without communication (0.58 GB when written; the chip read
    0.41 GB, where the kernels it replaces read 0.66 GB: PERF.md, PR 31).

    ISSUE 31 asked for "every start after the head's weight-gradient
    fusion": in the ENTRY schedule that fusion is the last heavy
    instruction of the step (XLA moves every weight-gradient fusion that
    consumes a received buffer behind the backward pass), so the property
    that holds, and that bounds memory, is the one asserted here."""
    from bluefog_tpu.utils.inspect import transfer_schedule

    comm = _compile_cell_step(monkeypatch, "neighbor")
    none = _compile_cell_step(monkeypatch, "none")
    text = comm.as_text()
    assert "custom_call_has_side_effect=true" not in text, (
        "a core-blocking kernel in the exchange")
    sched = transfer_schedule(text, GPT2_MARKS)
    transfers = sched["transfers"]
    # 168,614,400 f32 parameters over two slots: gossip_bytes_per_step
    assert sum(t[2] for t in transfers) == 1_348_915_200
    largest_leaf = 50304 * 768 * 4
    assert max(t[2] for t in transfers) == largest_leaf
    # fused buffers stay under twice fuse_apply's threshold
    fused = [t[2] for t in transfers
             if t[2] not in (largest_leaf, 8192 * 768 * 4, 768 * 3072 * 4,
                             768 * 2304 * 4)]
    assert fused and max(fused) < 2 * (4 << 20), sorted(set(fused))
    first_fwd = sched["marks"]["attention_fwd"][0]
    last_bwd = sched["marks"]["attention_bwd"][-1]
    early = [t for t in transfers if t[0] < first_fwd]
    assert sum(t[2] for t in early) <= 64 << 20, early
    assert all(t[0] >= last_bwd for t in transfers if t not in early), (
        "a transfer opens between the forward pass and the end of the "
        "backward pass, where activations fill the memory")
    over = (comm.memory_analysis().temp_size_in_bytes
            - none.memory_analysis().temp_size_in_bytes)
    assert over <= 0.80 * GB, over / GB
