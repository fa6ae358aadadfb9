"""The FULL op layers through the pallas branch under TPU-interpret.

test_pallas_gossip.py exercises the bare kernels; these tests force
``backend='pallas'`` through the real op-layer code paths —
``ops/collectives.neighbor_allreduce`` (pytree dispatch, collective-id
enumeration) and the window family (``win_put``/``win_accumulate`` deliver
with name-derived collective-id bases and in-edge masks) — with
``BLUEFOG_TPU_PALLAS_INTERPRET=1`` routing the kernels through Mosaic
emulation on the CPU mesh, asserted equal to the XLA backend.
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import Mesh, PartitionSpec as P

from bluefog_tpu.ops import collectives as C, windows as W
from bluefog_tpu.parallel.api import shard_map
from bluefog_tpu.topology import ExponentialTwoGraph, RingGraph
from bluefog_tpu.topology.schedule import build_schedule

N = 8


@pytest.fixture(autouse=True)
def _interpret(monkeypatch):
    monkeypatch.setenv("BLUEFOG_TPU_PALLAS_INTERPRET", "1")


def _mesh():
    return Mesh(np.array(jax.devices()[:N]), ("bf",))


def _run(body, *inputs):
    return jax.jit(shard_map(
        body, mesh=_mesh(), in_specs=(P("bf"),) * len(inputs),
        out_specs=P("bf"), check_vma=False))(*inputs)


def test_gossip_op_layer_pallas_matches_xla():
    sched = build_schedule(ExponentialTwoGraph(N))
    tree = {
        "a": jnp.arange(N * 6, dtype=jnp.float32).reshape(N, 6),
        "b": jnp.arange(N * 4, dtype=jnp.float32).reshape(N, 2, 2) / 7.0,
    }

    def body(backend):
        def fn(xs):
            return C.neighbor_allreduce(xs, sched, "bf", backend=backend)
        return fn

    got = _run(body("pallas"), tree)
    want = _run(body("xla"), tree)
    for k in tree:
        np.testing.assert_allclose(np.asarray(got[k]), np.asarray(want[k]),
                                   rtol=1e-5, atol=1e-6)


def test_two_windows_one_program_distinct_semaphores():
    """Gradient-tracking's shape: TWO windows delivered in ONE jitted
    program.  Their name-derived collective-id bases must stay distinct
    after the interpret-mode compact remap (a raw modulo fold collided
    1/30 of name pairs — regression for that), or one kernel's handshake
    absorbs the other's."""
    from bluefog_tpu.ops.pallas_gossip import _interpret_collective_id

    # distinct originals always map to distinct compact ids
    seen = {_interpret_collective_id(cid)
            for cid in (7, 1024, 2048, 2048 + 27 * 30720, 2**29 + 5)}
    assert len(seen) == 5

    sched = build_schedule(RingGraph(N))
    xs = jnp.arange(N * 3, dtype=jnp.float32).reshape(N, 3)

    def body(backend, suffix):
        def fn(v):
            sx = W.win_create(v, sched, "bf", name=f"gt_x_{suffix}")
            sy = W.win_create(2 * v, sched, "bf", name=f"gt_y_{suffix}")
            sx = W.win_put(sx, v, "bf", backend=backend)
            sy = W.win_accumulate(sy, 2 * v, "bf", backend=backend)
            ox, _ = W.win_update(sx, "bf")
            oy, _ = W.win_update(sy, "bf")
            return ox + oy
        return fn

    got = _run(body("pallas", "pl"), xs)
    want = _run(body("xla", "x"), xs)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=1e-5, atol=1e-6)


def test_window_family_pallas_matches_xla():
    """win_put + win_accumulate + win_update through the pallas deliver
    branch (two leaves -> two collective ids off the name-derived base)."""
    sched = build_schedule(RingGraph(N))
    tree = {
        "w": jnp.arange(N * 5, dtype=jnp.float32).reshape(N, 5),
        "b": jnp.arange(N, dtype=jnp.float32).reshape(N, 1) * 3.0,
    }

    def body(backend, wname):
        def fn(xs):
            st = W.win_create(xs, sched, "bf", name=wname)
            st = W.win_put(st, xs, "bf", backend=backend)
            st = W.win_accumulate(st, xs, "bf", backend=backend)
            out, _ = W.win_update(st, "bf")
            return out
        return fn

    got = _run(body("pallas", "pl_probe"), tree)
    want = _run(body("xla", "xla_probe"), tree)
    for k in tree:
        np.testing.assert_allclose(np.asarray(got[k]), np.asarray(want[k]),
                                   rtol=1e-5, atol=1e-6)


def test_gossip_chunked_leaf_matches_xla(monkeypatch):
    """A leaf beyond the per-invocation cap splits into cap-sized chunks
    (one kernel + collective id each) and must reproduce the XLA gossip
    bit-for-bit at f32 tolerance.  Cap shrunk to 4 KiB so a 4,100-float
    leaf chunks 5-ways under emulation."""
    monkeypatch.setenv("BLUEFOG_TPU_PALLAS_MAX_BYTES", str(4 << 10))
    sched = build_schedule(ExponentialTwoGraph(N))
    # deliberately NOT a multiple of the chunk size: exercises the uneven
    # tail chunk (array_split) and per-chunk tile padding
    tree = {"big": jnp.arange(N * 4100, dtype=jnp.float32).reshape(N, 4100)
                   / 997.0,
            "small": jnp.arange(N * 3, dtype=jnp.float32).reshape(N, 3)}

    from bluefog_tpu.ops import pallas_gossip as pg
    calls = []
    real = pg.neighbor_allreduce_pallas

    def spy(leaf, *a, **kw):
        calls.append((int(np.prod(leaf.shape)), kw.get("collective_id")))
        return real(leaf, *a, **kw)

    monkeypatch.setattr(pg, "neighbor_allreduce_pallas", spy)

    def body(backend):
        def fn(xs):
            return C.neighbor_allreduce(xs, sched, "bf", backend=backend)
        return fn

    got = _run(body("pallas"), tree)
    want = _run(body("xla"), tree)
    for k in tree:
        np.testing.assert_allclose(np.asarray(got[k]), np.asarray(want[k]),
                                   rtol=1e-5, atol=1e-6)
    # 4100 floats = 16,400 B at a 4,096 B cap -> 5 chunks + 1 small leaf,
    # with six DISTINCT collective ids in the gossip range
    chunk_calls = [c for c in calls if c[0] != 3]
    assert len(chunk_calls) == 5, calls
    ids = {cid for _, cid in calls}
    assert len(ids) == 6 and all(1024 <= i < 2048 for i in ids), calls


def test_default_optimizer_path_is_async_and_a_forced_kernel_path_chunks(
        monkeypatch):
    """The DEFAULT optimizer path (backend='auto', fused buffers) on a TPU
    mesh hands a tree beyond one kernel's payload to XLA's asynchronous
    collective-permutes (PR 31: the kernels occupy the core while they
    wait, so nothing of a 30 ms exchange was hidden); a tree one kernel
    carries still rides the kernel; and a FORCED backend='pallas' chunks
    the fused buffer as before.  All three produce the same training step."""
    import optax
    import bluefog_tpu as bf
    from bluefog_tpu.optim import DistributedNeighborAllreduceOptimizer
    from bluefog_tpu.ops import pallas_gossip as pg
    from bluefog_tpu.topology import ExponentialTwoGraph

    # pretend the CPU mesh is a TPU slice (interpret mode executes the
    # kernels); shrink the cap so the fused buffer (5,000 floats = 20 KB)
    # is beyond one kernel's payload and needs 3 chunks at 8 KiB
    monkeypatch.setattr(pg, "on_tpu_platform", lambda: True)
    monkeypatch.setenv("BLUEFOG_TPU_PALLAS_MAX_BYTES", str(8 << 10))

    calls = []
    real = pg.neighbor_allreduce_pallas

    def spy(leaf, *a, **kw):
        calls.append(int(np.prod(leaf.shape)))
        return real(leaf, *a, **kw)

    monkeypatch.setattr(pg, "neighbor_allreduce_pallas", spy)

    params = {"w1": jnp.ones((N, 40, 100), jnp.float32),
              "w2": jnp.ones((N, 1000), jnp.float32)}
    grads = jax.tree_util.tree_map(
        lambda t: jnp.broadcast_to(
            jnp.arange(N, dtype=jnp.float32).reshape((N,) + (1,) *
                                                     (t.ndim - 1)), t.shape),
        params)

    def run_step(backend="auto"):
        opt = DistributedNeighborAllreduceOptimizer(
            optax.sgd(0.1), topology=ExponentialTwoGraph(N), axis_name="bf",
            backend=backend)

        def body(p, g):
            st = opt.init(p)
            upd, _ = opt.update(g, st, p)
            return optax.apply_updates(p, upd)

        return jax.jit(shard_map(
            body, mesh=_mesh(), in_specs=(P("bf"), P("bf")),
            out_specs=P("bf"), check_vma=False))(params, grads)

    want = run_step()
    assert not calls, "a tree beyond the cap must take the asynchronous path"

    # forced kernels: fused buffer = 5,000 floats -> ceil(20,000 B / 8,192 B)
    # = 3 chunks
    forced = run_step("pallas")
    assert len(calls) == 3 and sum(calls) == 5000, calls

    # a cap the whole tree fits under: auto keeps the kernel, one invocation
    calls.clear()
    monkeypatch.setenv("BLUEFOG_TPU_PALLAS_MAX_BYTES", str(32 << 10))
    small = run_step()
    assert calls == [5000], calls

    # and the kill switch still forces XLA
    monkeypatch.setenv("BLUEFOG_TPU_PALLAS_GOSSIP", "0")
    calls.clear()
    run_step()
    assert not calls, "kill switch must force XLA"
    for got in (forced, small):
        for k in params:
            np.testing.assert_allclose(np.asarray(got[k]), np.asarray(want[k]),
                                       rtol=1e-5, atol=1e-6)
