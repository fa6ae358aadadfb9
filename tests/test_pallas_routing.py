"""Routing policy for the Pallas RDMA gossip transport.

`backend='auto'` must provably choose per the stated conditions
(pallas_gossip.auto_gossip_backend): real TPU + multi-device + circulant +
a payload one kernel carries (the whole gossip tree; each leaf of a window
payload) -> pallas; anything else -> XLA.  The policy is pure
and cheap, so every branch is asserted directly; integration (the XLA side
of auto on the CPU mesh + interpret-mode kernel parity) is covered by
test_collectives.py / test_pallas_gossip.py.
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
    assert pg.auto_gossip_backend(sched, SMALL) == "xla"


def test_auto_picks_pallas_on_tpu_small_circulant(on_tpu):
    for topo in (RingGraph(8), ExponentialTwoGraph(8)):
        assert pg.auto_gossip_backend(build_schedule(topo), SMALL) == "pallas"
    # pytrees: every leaf within the cutoff
    tree = {"a": SMALL, "b": jnp.zeros((16, 16), jnp.bfloat16)}
    assert pg.auto_gossip_backend(build_schedule(RingGraph(8)), tree) == "pallas"


CAP = pg.DEFAULT_AUTO_MAX_BYTES


def _f32(nbytes):
    return jax.ShapeDtypeStruct((nbytes // 4,), jnp.float32)


@pytest.mark.parametrize("tree, want", [
    (_f32(CAP), "pallas"),                            # one kernel's payload
    (_f32(CAP + 4), "xla"),                           # one element beyond it
    ({"a": _f32(CAP // 2), "b": _f32(CAP // 2)}, "pallas"),
    # every leaf under the cap, the tree over it: the whole payload decides
    ({"a": _f32(CAP // 2), "b": _f32(CAP // 2 + 4)}, "xla"),
    # bf16 travels as bf16: twice the elements ride one kernel
    (jax.ShapeDtypeStruct((CAP // 2,), jnp.bfloat16), "pallas"),
    (jax.ShapeDtypeStruct((CAP // 2 + 1,), jnp.bfloat16), "xla"),
    (BIG, "xla"),
    ({"a": SMALL, "b": BIG}, "xla"),
], ids=["at_cap", "over_cap", "tree_at_cap", "tree_over_cap", "bf16_at_cap",
        "bf16_over_cap", "big_leaf", "small_and_big"])
def test_auto_gossip_takes_the_async_path_beyond_one_kernels_payload(
        on_tpu, tree, want):
    """A Pallas gossip kernel occupies the TensorCore while its RDMAs fly
    (168 us a 4 MiB kernel on a v5e, 180 of them a GPT-2-small step, none
    hidden: PERF.md, PR 31), XLA's collective-permute-start/-done do not.
    ``auto`` keeps the kernels for a payload one invocation carries and
    gives everything larger to XLA, deciding from the tree's on-wire bytes
    alone."""
    sched = build_schedule(RingGraph(8))
    assert pg.auto_gossip_backend(sched, tree) == want


def test_any_optimizer_tree_is_on_the_async_side(on_tpu):
    """What ``decentralized_optimizer`` hands the exchange — a few fused
    buffers of about 8 MiB and the large leaves — is far beyond one
    kernel's payload at either shape the rule was set on (PERF.md, PR 31):
    GPT-2 small's 27 large leaves and 13 buffers, ResNet-50's 3 and 9."""
    sched = build_schedule(ExponentialTwoGraph(4))
    gpt2 = {"big": [_f32(154_533_888)] * 2 + [_f32(25_165_824)]
            + [_f32(9_437_184)] * 24, "fused": [_f32(9_470_000)] * 12}
    resnet = {"big": [_f32(9_437_184)] * 3, "fused": [_f32(8_500_000)] * 9}
    assert pg.auto_gossip_backend(sched, gpt2) == "xla"
    assert pg.auto_gossip_backend(sched, resnet) == "xla"


def test_the_cutoff_follows_the_cap_override(on_tpu, monkeypatch):
    """One number, no new knob: ``BLUEFOG_TPU_PALLAS_MAX_BYTES`` is the
    chunk cap of a forced kernel path and the cutoff of ``auto``."""
    sched = build_schedule(RingGraph(8))
    assert pg.auto_gossip_backend(sched, BIG) == "xla"
    monkeypatch.setenv("BLUEFOG_TPU_PALLAS_MAX_BYTES", str(8 << 20))
    assert pg.auto_gossip_backend(sched, BIG) == "pallas"
    monkeypatch.setenv("BLUEFOG_TPU_PALLAS_MAX_BYTES", "1024")
    assert pg.auto_gossip_backend(sched, SMALL) == "xla"


def test_a_forced_backend_is_not_routed(on_tpu):
    """``resolve_backend`` asks the rule only for ``auto``: a forced
    ``'pallas'`` stays the kernels at any size (the op layer chunks), a
    forced ``'xla'`` stays XLA for a payload the kernels would take."""
    sched = build_schedule(RingGraph(8))
    assert pg.resolve_backend("pallas", sched, BIG) == "pallas"
    assert pg.resolve_backend("xla", sched, SMALL) == "xla"
    assert pg.resolve_backend("auto", sched, BIG) == "xla"
    assert pg.resolve_backend("auto", sched, SMALL) == "pallas"
    with pytest.raises(ValueError, match="unknown backend"):
        pg.resolve_backend("rdma", sched, SMALL)


def test_window_deliver_keeps_size_cutoff(on_tpu):
    """The window transport cannot chunk (persistent landing buffers), so
    for it the cap stays a routing cutoff."""
    sched = build_schedule(RingGraph(8))
    assert pg.auto_gossip_backend(sched, BIG, chunkable=False) == "xla"
    assert pg.auto_gossip_backend(
        sched, {"a": SMALL, "b": BIG}, chunkable=False) == "xla"
    assert pg.auto_gossip_backend(sched, SMALL, chunkable=False) == "pallas"
    # and the cutoff is tunable
    import os
    os.environ["BLUEFOG_TPU_PALLAS_MAX_BYTES"] = str(1 << 30)
    try:
        assert pg.auto_gossip_backend(sched, BIG, chunkable=False) == "pallas"
    finally:
        del os.environ["BLUEFOG_TPU_PALLAS_MAX_BYTES"]


def test_nonpositive_cap_disables_kernels(on_tpu, monkeypatch):
    """MAX_BYTES=0 was the de facto 'always XLA' setting before chunking;
    it must keep meaning that under auto — and raise loudly (not
    ZeroDivisionError) if pallas is forced anyway."""
    monkeypatch.setenv("BLUEFOG_TPU_PALLAS_MAX_BYTES", "0")
    sched = build_schedule(RingGraph(8))
    assert pg.auto_gossip_backend(sched, SMALL) == "xla"
    assert pg.auto_gossip_backend(sched, SMALL, chunkable=False) == "xla"
    with pytest.raises(ValueError, match="must be positive"):
        pg.leaf_chunk_count(SMALL)


def test_leaf_chunk_plan():
    # 8 MiB f32 leaf at the default 4 MiB cap -> 2 chunks; bf16 ships at
    # half the bytes -> 1 chunk at 4 MiB
    assert pg.leaf_wire_bytes(BIG) == 8 << 20
    assert pg.leaf_chunk_count(BIG) == 2
    assert pg.leaf_chunk_count(BIG.astype(jnp.bfloat16)) == 1
    assert pg.leaf_chunk_count(SMALL) == 1
    # a ResNet-50-sized fused f32 buffer (~25.5M params, ~102 MiB wire)
    fused = jax.ShapeDtypeStruct((25_500_000,), jnp.float32)
    assert pg.leaf_chunk_count(fused) == 25
    assert pg.leaf_chunk_count(fused, limit=1 << 30) == 1


@pytest.mark.parametrize("deliver", [False, True], ids=["gossip", "deliver"])
@pytest.mark.parametrize("itemsize", [4, 2], ids=["f32_wire", "bf16_wire"])
@pytest.mark.parametrize("num_slots", [1, 2, 3])
def test_vmem_plan_stays_under_the_limit(num_slots, itemsize, deliver):
    """Arithmetic, no device: at the largest payload the planner can emit
    (the per-invocation cap) the kernel's VMEM plan — every whole-payload
    buffer plus headroom — fits the budget, and the limit the call states
    covers the plan.  The reduction is tiled, so the plan does not depend
    on the wire dtype."""
    cap = pg.DEFAULT_AUTO_MAX_BYTES
    copies = 2 * num_slots + 1 if deliver else num_slots + 2
    plan = pg.vmem_plan_bytes(cap, num_slots, deliver=deliver)
    assert plan == copies * cap + pg._VMEM_HEADROOM
    assert plan <= pg._VMEM_BUDGET
    dtype = jnp.float32 if itemsize == 4 else jnp.bfloat16
    block = jax.ShapeDtypeStruct((cap // itemsize // 128, 128), dtype)
    stated = pg._vmem_limit(block, num_slots, deliver=deliver)
    assert plan <= stated <= pg._VMEM_BUDGET
    # one row tile of f32 temporaries per operand fits the headroom
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
    assert pg.auto_gossip_backend(dense, BIG) == "xla"
    assert pg.auto_gossip_backend(dense, SMALL) == "pallas"  # small fits
    block = jax.ShapeDtypeStruct(
        (pg.DEFAULT_AUTO_MAX_BYTES // 4 // 128, 128), jnp.float32)
    with pytest.raises(ValueError, match="budget"):
        pg._vmem_limit(block, dense.num_slots)


def test_auto_rejects_non_circulant_and_single_device(on_tpu):
    star = build_schedule(StarGraph(8))
    assert pg.circulant_shifts(star) is None
    assert pg.auto_gossip_backend(star, SMALL) == "xla"

    from bluefog_tpu.topology.graphs import Topology
    solo = build_schedule(Topology(weights=np.ones((1, 1)), name="solo"))
    assert pg.auto_gossip_backend(solo, SMALL) == "xla"


def test_auto_rejects_zero_slot_schedules(on_tpu):
    """A multi-device identity topology builds a circulant schedule with ZERO
    slots (no edges); auto must take XLA — the grid-free kernel cannot lower
    with no receive buffers."""
    from bluefog_tpu.topology.graphs import Topology

    ident = build_schedule(Topology(weights=np.eye(8), name="identity8"))
    assert ident.num_slots == 0 and ident.is_circulant
    assert pg.auto_gossip_backend(ident, SMALL) == "xla"


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


def test_pallas_zero_slot_degenerates_to_self_term():
    """Forced backend='pallas' on a 0-slot schedule returns sw*x instead of
    crashing in kernel lowering (interpret-free: no kernel is built)."""
    from jax.sharding import Mesh, PartitionSpec as P

    from bluefog_tpu.parallel.api import shard_map
    from bluefog_tpu.topology.graphs import Topology

    sched = build_schedule(Topology(weights=np.eye(8), name="identity8"))
    mesh = Mesh(np.array(jax.devices()[:8]), ("bf",))
    xs = jnp.arange(8 * 4, dtype=jnp.float32).reshape(8, 4)
    out = jax.jit(shard_map(
        lambda v: pg.neighbor_allreduce_pallas(v[0], sched, "bf")[None],
        mesh=mesh, in_specs=(P("bf"),), out_specs=P("bf"),
        check_vma=False))(xs)
    np.testing.assert_allclose(np.asarray(out), np.asarray(xs), rtol=1e-6)


def test_gate_predicates_agree(on_tpu):
    """is_pallas_supported and 'auto' routing share ONE platform predicate
    (on_tpu_platform) — they can never disagree about the same schedule."""
    from bluefog_tpu.topology.graphs import Topology

    for topo in (RingGraph(8), ExponentialTwoGraph(8), StarGraph(8),
                 Topology(weights=np.ones((1, 1)), name="solo"),
                 Topology(weights=np.eye(8), name="identity8")):
        sched = build_schedule(topo)
        assert pg.is_pallas_supported(sched) == \
            (pg.auto_gossip_backend(sched, SMALL) == "pallas"), topo.name


def test_gate_predicates_agree_on_cpu():
    sched = build_schedule(RingGraph(8))
    assert not pg.on_tpu_platform()
    assert not pg.is_pallas_supported(sched)
    assert pg.auto_gossip_backend(sched, SMALL) == "xla"


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
    assert pg.auto_gossip_backend(sched, SMALL) == "xla"


def test_neighbor_allreduce_consults_policy(monkeypatch):
    """backend='auto' actually dispatches on the policy's answer."""
    from bluefog_tpu.ops import collectives as C

    calls = {}

    def fake_policy(sched, x, **kw):
        calls["hit"] = True
        return "xla"

    monkeypatch.setattr(pg, "auto_gossip_backend", fake_policy)
    from jax.sharding import Mesh, PartitionSpec as P

    from bluefog_tpu.parallel.api import shard_map

    sched = build_schedule(RingGraph(8))
    mesh = Mesh(np.array(jax.devices()[:8]), ("bf",))
    fn = jax.jit(shard_map(
        lambda v: C.neighbor_allreduce(v, sched, "bf", backend="auto"),
        mesh=mesh, in_specs=(P("bf"),), out_specs=P("bf"), check_vma=False))
    out = fn(jnp.ones((8, 4), jnp.float32))
    jax.block_until_ready(out)
    assert calls.get("hit"), "auto did not consult auto_gossip_backend"


def test_win_put_consults_policy(monkeypatch):
    """The window transport's backend='auto' routes through the same
    policy as gossip (deliver = the RDMA kernels in put/acc mode)."""
    import bluefog_tpu as bf

    calls = {}
    real = pg.auto_gossip_backend

    def fake_policy(sched, x, **kw):
        calls["hit"] = True
        # the window transport must declare itself non-chunkable
        assert kw.get("chunkable") is False
        return real(sched, x, **kw)

    monkeypatch.setattr(pg, "auto_gossip_backend", fake_policy)
    bf.init(topology=RingGraph(8))
    x = jnp.ones((8, 4), jnp.float32)
    assert bf.win_create(x, "routing_probe")
    bf.win_put(x, "routing_probe")
    assert calls.get("hit"), "window auto did not consult auto_gossip_backend"
    bf.win_free("routing_probe")
