"""The window op layer through its pallas branch under TPU-interpret.

test_pallas_gossip.py exercises the bare kernel; these tests force
``backend='pallas'`` through the real op-layer code path of the window
family (``win_put``/``win_accumulate`` deliver with name-derived
collective-id bases and in-edge masks) — with
``BLUEFOG_TPU_PALLAS_INTERPRET=1`` routing the kernels through Mosaic
emulation on the CPU mesh, asserted equal to the XLA backend.
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import Mesh, PartitionSpec as P

from bluefog_tpu.ops import windows as W
from bluefog_tpu.parallel.api import shard_map
from bluefog_tpu.topology import RingGraph
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
