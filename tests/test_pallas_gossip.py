"""The Pallas RDMA window-deliver kernel under TPU-interpret emulation
(``pltpu.InterpretParams`` runs the Mosaic semantics — semaphores, remote
DMAs — on the CPU mesh).  This validates the genuine TPU one-sided path
(SURVEY.md §7 hard-part #1) without multi-chip hardware.  The gossip kernel
that stood beside it went with PR 47; its inputs are checked on the one
gossip path in ``test_collectives.py``."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import PartitionSpec as P

import bluefog_tpu as bf
from bluefog_tpu.ops import pallas_gossip
from bluefog_tpu.parallel.api import shard_map
from bluefog_tpu.topology import (
    ExponentialTwoGraph,
    MeshGrid2DGraph,
    RingGraph,
    build_schedule,
)

N = 8


def _run(body, *inputs, n_out=1):
    bf.init()
    ctx = bf.get_context()
    f = jax.jit(shard_map(
        body, mesh=ctx.mesh, in_specs=(P("bf"),) * len(inputs),
        out_specs=(P("bf"),) * n_out if n_out > 1 else P("bf"),
        check_vma=False,
    ))
    return f(*inputs)


def rank_values(shape=(4,)):
    base = jnp.arange(N, dtype=jnp.float32).reshape((N,) + (1,) * len(shape))
    return jnp.broadcast_to(base, (N,) + shape)


def test_pallas_deliver_put_and_accumulate():
    topo = RingGraph(N)
    sched = build_schedule(topo)
    k = sched.num_slots

    def body(xs):
        x = xs[0]
        bufs = jnp.zeros((k,) + x.shape, x.dtype)
        bufs = pallas_gossip.deliver_pallas(
            x, bufs, sched, "bf", accumulate=False, interpret=True
        )
        bufs = pallas_gossip.deliver_pallas(
            x, bufs, sched, "bf", accumulate=True, interpret=True
        )
        return bufs[None]

    out = np.asarray(_run(body, rank_values((4,))))  # (N, k, 4)
    # slot k holds 2x the value of the rank feeding that slot (put then acc)
    for r in range(N):
        for slot in range(k):
            src = sched.recv_src[r, slot]
            np.testing.assert_allclose(out[r, slot], 2.0 * src, rtol=1e-6)


def test_pallas_deliver_bf16_wire():
    """bf16 payloads ride a bf16 wire (half the ICI bytes) through the
    window transport too; accumulate semantics match the portable path's
    leaf-dtype adds."""
    topo = RingGraph(N)
    sched = build_schedule(topo)
    k = sched.num_slots

    def body(xs):
        x = xs[0].astype(jnp.bfloat16)
        bufs = jnp.zeros((k,) + x.shape, jnp.bfloat16)
        bufs = pallas_gossip.deliver_pallas(
            x, bufs, sched, "bf", accumulate=False, interpret=True)
        bufs = pallas_gossip.deliver_pallas(
            x, bufs, sched, "bf", accumulate=True, interpret=True)
        assert bufs.dtype == jnp.bfloat16
        return bufs[None]

    out = np.asarray(_run(body, rank_values((3, 7))), np.float64)
    for r in range(N):
        for slot in range(k):
            src = sched.recv_src[r, slot]
            np.testing.assert_allclose(out[r, slot], 2.0 * src,
                                       rtol=1e-2, atol=1e-2)


def test_wire_dtype_selection(monkeypatch):
    """bf16 leaves are counted at 2 bytes (the wire is bf16): up to 2x the
    f32 cutoff still within the window transport's routing cutoff."""
    assert pallas_gossip._wire_dtype(jnp.bfloat16) == jnp.bfloat16
    assert pallas_gossip._wire_dtype(jnp.float32) == jnp.float32
    assert pallas_gossip._wire_dtype(jnp.float16) == jnp.float32

    sched = build_schedule(ExponentialTwoGraph(N))
    cutoff_elems = pallas_gossip.DEFAULT_AUTO_MAX_BYTES // 4
    f32_big = jnp.zeros((cutoff_elems + 1,), jnp.float32)
    bf16_same = jnp.zeros((cutoff_elems + 1,), jnp.bfloat16)
    assert pallas_gossip.leaf_wire_bytes(f32_big) == 4 * (cutoff_elems + 1)
    assert pallas_gossip.leaf_wire_bytes(bf16_same) == 2 * (cutoff_elems + 1)
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    assert pallas_gossip.auto_window_backend(sched, f32_big) == "xla"
    assert pallas_gossip.auto_window_backend(sched, bf16_same) == "pallas"


def test_pallas_rejects_non_circulant():
    sched = build_schedule(MeshGrid2DGraph(6))
    x = jnp.zeros((4,))
    with pytest.raises(ValueError, match="circulant"):
        pallas_gossip.deliver_pallas(
            x, jnp.zeros((sched.num_slots, 4)), sched, "bf",
            accumulate=False, interpret=True)


def test_circulant_shift_extraction():
    assert pallas_gossip.circulant_shifts(build_schedule(RingGraph(N))) == (1, N - 1)
    assert pallas_gossip.circulant_shifts(build_schedule(ExponentialTwoGraph(N))) == (1, 2, 4)
    assert pallas_gossip.circulant_shifts(build_schedule(MeshGrid2DGraph(6))) is None


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16],
                         ids=["f32_wire", "bf16_wire"])
def test_tiled_store_covers_full_tiles_and_remainder(dtype, monkeypatch):
    """Payloads beyond one row tile are stored in a loop over full tiles plus
    a static remainder: rank-distinct values across full tiles + a ragged
    tail must land whole.  The tile is shrunk because the emulation stalls
    on payloads past ~32 KiB: f32 pads to 40 rows (2 tiles of 16 + 8), bf16
    to 48 (1 tile of 32 + 16)."""
    monkeypatch.setattr(pallas_gossip, "_TILE_ROWS",
                        16 if dtype == jnp.float32 else 32)
    sched = build_schedule(ExponentialTwoGraph(N))
    elems = 40 * 128 - 100
    x = (jnp.arange(N, dtype=jnp.float32)[:, None]
         + jnp.linspace(0.0, 1.0, elems)[None, :]).astype(dtype)

    def deliver(xs):
        bufs = jnp.zeros((sched.num_slots,) + xs[0].shape, xs.dtype)
        return pallas_gossip.deliver_pallas(
            xs[0], bufs, sched, "bf", accumulate=True, interpret=True)[None]

    landed = np.asarray(_run(deliver, x), np.float64)
    src = np.asarray(sched.recv_src)
    for k in range(sched.num_slots):
        np.testing.assert_array_equal(
            landed[:, k], np.asarray(x, np.float64)[src[:, k]])
