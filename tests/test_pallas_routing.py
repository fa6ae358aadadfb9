"""Routing policy for the Pallas RDMA window transport.

`backend='auto'` of ``win_put`` / ``win_accumulate`` must provably choose
per the stated conditions (pallas_gossip.auto_window_backend): real TPU +
multi-device + circulant + every leaf of the payload one kernel carries ->
pallas; anything else -> XLA.  The policy is pure and cheap, so every
branch is asserted directly; interpret-mode kernel parity is covered by
test_pallas_gossip.py / test_pallas_op_layer.py.  Gossip has no routing:
it runs on collective-permutes alone (test_collectives.py).
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from bluefog_tpu.ops import pallas_gossip as pg
from bluefog_tpu.topology import ExponentialTwoGraph, RingGraph, StarGraph
from bluefog_tpu.topology.schedule import build_schedule

SMALL = jnp.zeros((1024,), jnp.float32)          # 4 KiB
BIG = jnp.zeros((2 << 20,), jnp.float32)         # 8 MiB > 4 MiB cutoff


@pytest.fixture
def on_tpu(monkeypatch):
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")


def test_auto_is_xla_on_cpu():
    sched = build_schedule(RingGraph(8))
    assert jax.default_backend() == "cpu"
    assert pg.auto_window_backend(sched, SMALL) == "xla"


def test_auto_picks_pallas_on_tpu_small_circulant(on_tpu):
    for topo in (RingGraph(8), ExponentialTwoGraph(8)):
        assert pg.auto_window_backend(build_schedule(topo), SMALL) == "pallas"
    # pytrees: every leaf within the cutoff
    tree = {"a": SMALL, "b": jnp.zeros((16, 16), jnp.bfloat16)}
    assert pg.auto_window_backend(build_schedule(RingGraph(8)), tree) == "pallas"


CAP = pg.DEFAULT_AUTO_MAX_BYTES


def _f32(nbytes):
    return jax.ShapeDtypeStruct((nbytes // 4,), jnp.float32)


def test_the_cutoff_follows_the_cap_override(on_tpu, monkeypatch):
    """``BLUEFOG_TPU_PALLAS_MAX_BYTES`` is the cutoff of ``auto``."""
    sched = build_schedule(RingGraph(8))
    assert pg.auto_window_backend(sched, BIG) == "xla"
    monkeypatch.setenv("BLUEFOG_TPU_PALLAS_MAX_BYTES", str(8 << 20))
    assert pg.auto_window_backend(sched, BIG) == "pallas"
    monkeypatch.setenv("BLUEFOG_TPU_PALLAS_MAX_BYTES", "1024")
    assert pg.auto_window_backend(sched, SMALL) == "xla"


def test_a_forced_backend_is_not_routed(on_tpu):
    """``resolve_backend`` asks the rule only for ``auto``: a forced
    ``'pallas'`` stays the kernel at any size, a forced ``'xla'`` stays
    XLA for a payload the kernel would take."""
    sched = build_schedule(RingGraph(8))
    assert pg.resolve_backend("pallas", sched, BIG) == "pallas"
    assert pg.resolve_backend("xla", sched, SMALL) == "xla"
    assert pg.resolve_backend("auto", sched, BIG) == "xla"
    assert pg.resolve_backend("auto", sched, SMALL) == "pallas"
    with pytest.raises(ValueError, match="unknown backend"):
        pg.resolve_backend("rdma", sched, SMALL)


def test_window_deliver_keeps_size_cutoff(on_tpu):
    """The window transport cannot split a leaf (persistent landing
    buffers), so the cap is a routing cutoff, leaf by leaf: the largest
    leaf decides, not the tree."""
    sched = build_schedule(RingGraph(8))
    assert pg.auto_window_backend(sched, BIG) == "xla"
    assert pg.auto_window_backend(sched, {"a": SMALL, "b": BIG}) == "xla"
    assert pg.auto_window_backend(sched, SMALL) == "pallas"
    assert pg.auto_window_backend(sched, _f32(CAP)) == "pallas"
    assert pg.auto_window_backend(sched, _f32(CAP + 4)) == "xla"
    # every leaf at the cap, the tree far over it: still the kernel
    assert pg.auto_window_backend(
        sched, {"a": _f32(CAP), "b": [_f32(CAP)] * 3}) == "pallas"


def test_nonpositive_cap_disables_kernels(on_tpu, monkeypatch):
    """MAX_BYTES=0 is the 'always XLA' setting under auto."""
    monkeypatch.setenv("BLUEFOG_TPU_PALLAS_MAX_BYTES", "0")
    sched = build_schedule(RingGraph(8))
    assert pg.auto_window_backend(sched, SMALL) == "xla"
    assert pg.auto_window_backend(
        sched, jnp.zeros((0,), jnp.float32)) == "xla"


@pytest.mark.parametrize("itemsize", [4, 2], ids=["f32_wire", "bf16_wire"])
@pytest.mark.parametrize("num_slots", [1, 2, 3])
def test_vmem_plan_stays_under_the_limit(num_slots, itemsize):
    """Arithmetic, no device: at the largest payload auto can route to the
    kernel (the per-invocation cap) its VMEM plan — every whole-payload
    buffer plus headroom — fits the budget, and the limit the call states
    covers the plan.  The stores are tiled, so the plan does not depend on
    the wire dtype."""
    cap = pg.DEFAULT_AUTO_MAX_BYTES
    plan = pg.vmem_plan_bytes(cap, num_slots)
    assert plan == (2 * num_slots + 1) * cap + pg._VMEM_HEADROOM
    assert plan <= pg._VMEM_BUDGET
    dtype = jnp.float32 if itemsize == 4 else jnp.bfloat16
    block = jax.ShapeDtypeStruct((cap // itemsize // 128, 128), dtype)
    stated = pg._vmem_limit(block, num_slots)
    assert plan <= stated <= pg._VMEM_BUDGET
    # one row tile of temporaries per operand fits the headroom
    assert (num_slots + 2) * pg._TILE_ROWS * 128 * 4 <= pg._VMEM_HEADROOM


def test_small_kernels_keep_the_compiler_default_limit():
    block = jax.ShapeDtypeStruct((8, 128), jnp.float32)
    assert pg._vmem_limit(block, 2) == pg._VMEM_COMPILER_DEFAULT


def test_dense_schedule_leaves_the_vmem_budget(on_tpu):
    """A schedule with so many slots that the plan leaves the budget takes
    XLA under auto, and a forced kernel raises instead of asking the
    compiler for VMEM the chip does not have."""
    from bluefog_tpu.topology import FullyConnectedGraph

    dense = build_schedule(FullyConnectedGraph(16))  # 15 slots
    assert pg.circulant_shifts(dense) is not None
    assert pg.auto_window_backend(dense, BIG) == "xla"
    assert pg.auto_window_backend(dense, _f32(CAP)) == "xla"  # under the cap
    assert pg.auto_window_backend(dense, SMALL) == "pallas"  # small fits
    block = jax.ShapeDtypeStruct(
        (pg.DEFAULT_AUTO_MAX_BYTES // 4 // 128, 128), jnp.float32)
    with pytest.raises(ValueError, match="budget"):
        pg._vmem_limit(block, dense.num_slots)


def test_auto_rejects_non_circulant_and_single_device(on_tpu):
    star = build_schedule(StarGraph(8))
    assert pg.circulant_shifts(star) is None
    assert pg.auto_window_backend(star, SMALL) == "xla"

    from bluefog_tpu.topology.graphs import Topology
    solo = build_schedule(Topology(weights=np.ones((1, 1)), name="solo"))
    assert pg.auto_window_backend(solo, SMALL) == "xla"


def test_auto_rejects_zero_slot_schedules(on_tpu):
    """A multi-device identity topology builds a circulant schedule with ZERO
    slots (no edges); auto must take XLA — the grid-free kernel cannot lower
    with no receive buffers."""
    from bluefog_tpu.topology.graphs import Topology

    ident = build_schedule(Topology(weights=np.eye(8), name="identity8"))
    assert ident.num_slots == 0 and ident.is_circulant
    assert pg.auto_window_backend(ident, SMALL) == "xla"


def test_deliver_pallas_zero_slot_returns_bufs_unchanged():
    """The window transport has the same degenerate case as gossip: no
    out-neighbors -> slot buffers unchanged, no kernel built."""
    from jax.sharding import Mesh, PartitionSpec as P

    from bluefog_tpu.parallel.api import shard_map
    from bluefog_tpu.topology.graphs import Topology

    sched = build_schedule(Topology(weights=np.eye(8), name="identity8"))
    assert not pg.is_pallas_supported(sched)  # and the guard below holds too
    mesh = Mesh(np.array(jax.devices()[:8]), ("bf",))
    payload = jnp.ones((8, 4), jnp.float32)
    bufs = jnp.zeros((8, 0, 4), jnp.float32)  # K=0 slots
    out = jax.jit(shard_map(
        lambda p, b: pg.deliver_pallas(p[0], b[0], sched, "bf",
                                       accumulate=False)[None],
        mesh=mesh, in_specs=(P("bf"), P("bf")), out_specs=P("bf"),
        check_vma=False))(payload, bufs)
    assert out.shape == (8, 0, 4)


def test_gate_predicates_agree(on_tpu):
    """is_pallas_supported and 'auto' routing share ONE platform predicate
    (on_tpu_platform) — they can never disagree about the same schedule."""
    from bluefog_tpu.topology.graphs import Topology

    for topo in (RingGraph(8), ExponentialTwoGraph(8), StarGraph(8),
                 Topology(weights=np.ones((1, 1)), name="solo"),
                 Topology(weights=np.eye(8), name="identity8")):
        sched = build_schedule(topo)
        assert pg.is_pallas_supported(sched) == \
            (pg.auto_window_backend(sched, SMALL) == "pallas"), topo.name


def test_gate_predicates_agree_on_cpu():
    sched = build_schedule(RingGraph(8))
    assert not pg.on_tpu_platform()
    assert not pg.is_pallas_supported(sched)
    assert pg.auto_window_backend(sched, SMALL) == "xla"


def test_window_base_collision_raises(monkeypatch):
    """Two distinct window names in one CRC32 bucket would share barrier
    semaphores; the registry refuses the second claimant."""
    import zlib

    # operate on a copy so neither the probe claim nor the 'stable_window'
    # claim below leaks into the process-global registry
    monkeypatch.setattr(pg, "_claimed_bases", dict(pg._claimed_bases))
    bucket = zlib.crc32(b"collision_probe") % (1 << 20)
    monkeypatch.setitem(pg._claimed_bases, bucket, "earlier_window")
    with pytest.raises(ValueError, match="collides"):
        pg.window_collective_id_base("collision_probe")
    # same-name re-derivation is always fine (idempotent claims)
    base = pg.window_collective_id_base("stable_window")
    assert pg.window_collective_id_base("stable_window") == base


def test_window_base_released_on_free(monkeypatch):
    """A freed window releases its bucket: per-experiment window names in a
    long-lived process must not accumulate spurious collisions."""
    import zlib

    monkeypatch.setattr(pg, "_claimed_bases", dict(pg._claimed_bases))
    pg.window_collective_id_base("ephemeral_win")
    bucket = zlib.crc32(b"ephemeral_win") % (1 << 20)
    monkeypatch.setitem(pg._claimed_bases, bucket, "ephemeral_win")
    pg.release_window_collective_id("ephemeral_win")
    assert bucket not in pg._claimed_bases
    # releasing someone ELSE's bucket is a no-op
    pg.window_collective_id_base("other_win")
    pg.release_window_collective_id("not_the_owner")
    assert zlib.crc32(b"other_win") % (1 << 20) in pg._claimed_bases

    # end-to-end: bf.win_free releases, so re-creating under a name that
    # shares the bucket (here: the same name) never raises
    import bluefog_tpu as bf
    from bluefog_tpu.topology import RingGraph
    import jax.numpy as jnp

    bf.init(topology=RingGraph(8))
    x = jnp.ones((8, 4), jnp.float32)
    for _ in range(3):
        assert bf.win_create(x, "recycled_win")
        bf.win_put(x, "recycled_win")
        bf.win_free("recycled_win")


def test_kill_switch(on_tpu, monkeypatch):
    sched = build_schedule(RingGraph(8))
    monkeypatch.setenv("BLUEFOG_TPU_PALLAS_GOSSIP", "0")
    assert pg.auto_window_backend(sched, SMALL) == "xla"


def test_win_put_consults_policy(monkeypatch):
    """The window transport's backend='auto' routes through the policy."""
    import bluefog_tpu as bf

    calls = {}
    real = pg.auto_window_backend

    def fake_policy(sched, x):
        calls["hit"] = True
        return real(sched, x)

    monkeypatch.setattr(pg, "auto_window_backend", fake_policy)
    bf.init(topology=RingGraph(8))
    x = jnp.ones((8, 4), jnp.float32)
    assert bf.win_create(x, "routing_probe")
    bf.win_put(x, "routing_probe")
    assert calls.get("hit"), "window auto did not consult auto_window_backend"
    bf.win_free("routing_probe")
