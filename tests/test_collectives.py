"""Collective-op tests on an 8-virtual-device mesh — the SPMD analog of the
reference's ``mpirun -np N pytest test/torch_ops_test.py`` suite (SURVEY.md
§4): each rank fills its tensor with its own rank id; results are asserted
against the closed-form ``W @ x`` of the known mixing matrix, over dtypes and
static/dynamic/weighted variants."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import bluefog_tpu as bf
from bluefog_tpu import ops
from bluefog_tpu.topology import (
    ExponentialTwoGraph,
    FullyConnectedGraph,
    MeshGrid2DGraph,
    RingGraph,
    StarGraph,
    SymmetricExponentialGraph,
    build_schedule,
    one_peer_exponential_two_schedules,
)
from bluefog_tpu.topology.graphs import Topology

N = 8
DTYPES = [jnp.float32, jnp.float64, jnp.bfloat16]


def rank_values(shape=(4,), dtype=jnp.float32):
    """Stacked input: rank r's tensor is all-r."""
    base = jnp.arange(N, dtype=jnp.float32).reshape((N,) + (1,) * len(shape))
    return jnp.broadcast_to(base, (N,) + shape).astype(dtype)


def expected_mix(topo, x):
    w = topo.weights
    xs = np.asarray(x, dtype=np.float64).reshape(N, -1)
    return (w @ xs).reshape(np.asarray(x).shape)


TOPOS = [
    ExponentialTwoGraph(N),
    RingGraph(N, 0),
    RingGraph(N, 1),
    MeshGrid2DGraph(N),
    StarGraph(N, center_rank=3),
    FullyConnectedGraph(N),
]


@pytest.mark.parametrize("topo", TOPOS, ids=lambda t: t.name)
def test_neighbor_allreduce_closed_form(topo):
    bf.init(topology=topo)
    x = rank_values((4, 3))
    out = bf.neighbor_allreduce(x)
    np.testing.assert_allclose(np.asarray(out), expected_mix(topo, x), rtol=1e-6)


@pytest.mark.parametrize("dtype", DTYPES, ids=str)
def test_neighbor_allreduce_dtypes(dtype):
    if dtype == jnp.float64:
        jax.config.update("jax_enable_x64", True)
    try:
        topo = RingGraph(N)
        bf.init(topology=topo)
        x = rank_values((8,), dtype)
        out = bf.neighbor_allreduce(x)
        assert out.dtype == dtype
        tol = 5e-2 if dtype == jnp.bfloat16 else 1e-6
        np.testing.assert_allclose(
            np.asarray(out, dtype=np.float64), expected_mix(topo, x), rtol=tol, atol=tol
        )
    finally:
        if dtype == jnp.float64:
            jax.config.update("jax_enable_x64", False)


def test_neighbor_allreduce_pytree():
    topo = ExponentialTwoGraph(N)
    bf.init(topology=topo)
    tree = {"a": rank_values((2,)), "b": [rank_values((3, 2)), rank_values(())]}
    out = bf.neighbor_allreduce(tree)
    for leaf, ref in zip(jax.tree_util.tree_leaves(out), jax.tree_util.tree_leaves(tree)):
        np.testing.assert_allclose(np.asarray(leaf), expected_mix(topo, ref), rtol=1e-6)


def test_neighbor_allreduce_per_call_weights():
    """Per-call self/recv weight overrides (the reference's per-call
    self_weight/src_weights) — pattern static, weights traced."""
    topo = RingGraph(N)
    bf.init(topology=topo)
    x = rank_values((4,))
    out = bf.neighbor_allreduce(x, self_weight=0.5, recv_weights=jnp.array([0.25, 0.25]))
    w = np.zeros((N, N))
    for i in range(N):
        w[i, i] = 0.5
        w[i, (i - 1) % N] += 0.25
        w[i, (i + 1) % N] += 0.25
    ref = (w @ np.asarray(x).reshape(N, -1)).reshape(N, 4)
    np.testing.assert_allclose(np.asarray(out), ref, rtol=1e-6)


def test_neighbor_allreduce_send_weights():
    """Reference per-call ``dst_weights`` parity: rank i ships
    ``send_w[i, k] * x_i`` in slot k, so the effective mix is
    ``out_j = w_jj x_j + sum_k recv_w[j,k] * send_w[src,k] * x_src``."""
    topo = RingGraph(N)
    bf.init(topology=topo)
    sched = build_schedule(topo)
    x = rank_values((3,))

    # uniform (num_slots,) vector: every rank halves what it ships
    half = np.full((sched.num_slots,), 0.5, np.float32)
    out = np.asarray(bf.neighbor_allreduce(x, send_weights=half), np.float64)
    w = topo.weights.copy()
    off = w - np.diag(np.diag(w))
    want = (np.diag(np.diag(w)) + 0.5 * off) @ np.asarray(x, np.float64).reshape(N, -1)
    np.testing.assert_allclose(out.reshape(N, -1), want, rtol=1e-6)

    # per-rank (size, num_slots) table: rank i scales its payload by i
    table = np.tile(np.arange(N, dtype=np.float32)[:, None],
                    (1, sched.num_slots))
    out2 = np.asarray(bf.neighbor_allreduce(x, send_weights=table), np.float64)
    scaled = off * np.arange(N)[None, :]  # column src scaled by src's factor
    want2 = (np.diag(np.diag(w)) + scaled) @ np.asarray(x, np.float64).reshape(N, -1)
    np.testing.assert_allclose(out2.reshape(N, -1), want2, rtol=1e-6)


def test_neighbor_allreduce_topology_override():
    bf.init(topology=RingGraph(N))
    topo2 = ExponentialTwoGraph(N)
    x = rank_values((4,))
    out = bf.neighbor_allreduce(x, topology=topo2)
    np.testing.assert_allclose(np.asarray(out), expected_mix(topo2, x), rtol=1e-6)


# What the retired Pallas gossip kernel's tests fed it (tests/test_pallas_
# gossip.py, _op_layer.py, _routing.py before PR 47), on the one path left.
# A gossip is the period of topologies it applies, one a call.
GOSSIPS = {
    "ring": [RingGraph(N)],
    "exp2": [ExponentialTwoGraph(N)],
    "symm_exp": [SymmetricExponentialGraph(N)],
    # three phases of one slot through neighbor_allreduce_dynamic's switch;
    # after the period every rank holds the exact global average
    "one_peer_exp2": one_peer_exponential_two_schedules(N),
    # no edge, so no slot: the self term alone
    "no_slot": [Topology(weights=np.eye(N), name="identity8")],
}
PIECE = 4 << 20   # fuse_apply's piece size: a leaf over it ships unfused


def _payload(kind, dtype):
    """Rank-distinct values that also vary along each leaf."""
    def leaf(*shape):
        size = int(np.prod(shape))
        return (jnp.arange(N, dtype=jnp.float32)[:, None]
                + jnp.linspace(0.0, 1.0, size)[None, :]
                ).astype(dtype).reshape((N,) + shape)

    if kind == "aligned":       # whole (8, 128) tiles
        return leaf(16, 128)
    if kind == "unaligned":
        return leaf(7, 13)
    over = PIECE // np.dtype(dtype).itemsize + 5     # one leaf over a piece
    return {"big": leaf(over), "small": [leaf(7, 13), leaf(5)]}


@pytest.mark.parametrize("kind,dtype,gossip", [
    *[(kind, dtype, gossip)
      for gossip in ("ring", "exp2", "symm_exp")
      for dtype in ("float32", "bfloat16")
      for kind in ("aligned", "unaligned", "tree_over_a_piece")],
    *[("tree_over_a_piece", dtype, gossip)
      for gossip in ("one_peer_exp2", "no_slot")
      for dtype in ("float32", "bfloat16")],
])
def test_gossip_equals_W_x_where_the_kernel_was_checked(kind, dtype, gossip):
    """``fuse_apply`` over ``neighbor_allreduce`` (``_dynamic`` for a period
    of schedules), as the optimizers call it, against ``W @ x`` in float64:
    circulant topologies, both wire widths, a tile-aligned leaf, one that is
    not, and a tree with a leaf above the piece size beside fused ones."""
    from jax.sharding import PartitionSpec as P
    from bluefog_tpu.ops import collectives as C
    from bluefog_tpu.parallel.api import shard_map

    ctx = bf.init()
    topos = GOSSIPS[gossip]
    scheds = [build_schedule(t) for t in topos]

    def mix(t, k):
        if len(scheds) == 1:
            return C.neighbor_allreduce(t, scheds[0], "bf")
        return C.neighbor_allreduce_dynamic(t, scheds, k, "bf")

    step = jax.jit(shard_map(
        lambda xs, k: C.fuse_apply(lambda t: mix(t, k), xs),
        mesh=ctx.mesh, in_specs=(P("bf"), P()), out_specs=P("bf"),
        check_vma=False))
    x = _payload(kind, jnp.dtype(dtype))
    out, mixing = x, np.eye(N)
    for k, topo in enumerate(topos):
        out = step(out, jnp.asarray(k))
        mixing = topo.weights @ mixing
    # bf16 is rounded once a call
    tol = 1e-6 if dtype == "float32" else 1e-2 * len(topos)
    for got, sent in zip(jax.tree_util.tree_leaves(out),
                         jax.tree_util.tree_leaves(x)):
        assert got.dtype == sent.dtype and got.shape == sent.shape
        want = mixing @ np.asarray(sent, np.float64).reshape(N, -1)
        np.testing.assert_allclose(
            np.asarray(got, np.float64).reshape(N, -1), want,
            rtol=tol, atol=tol)


def _mixing_entry_points():
    from bluefog_tpu import optim
    from bluefog_tpu.ops import collectives as C
    from bluefog_tpu.optim import optimizers

    return [
        C.neighbor_allreduce, C.sharded_neighbor_allreduce,
        C.neighbor_allreduce_dynamic, C.neighbor_allreduce_aperiodic,
        C._aperiodic_capped, C.hierarchical_neighbor_allreduce,
        C.hierarchical_neighbor_allreduce_2d, C.pair_gossip,
        optimizers._gossip, optim.DistributedNeighborAllreduceOptimizer,
        optim.DistributedHierarchicalNeighborAllreduceOptimizer,
        optim.DistributedChocoSGDOptimizer,
        optim.DistributedGradientTrackingOptimizer,
        optim.DistributedExactDiffusionOptimizer,
    ]


@pytest.mark.parametrize("fn", _mixing_entry_points(),
                         ids=lambda fn: fn.__name__)
def test_no_mixing_entry_point_takes_a_transport(fn):
    """Gossip has one lowering, so nothing that mixes takes a ``backend`` or
    a collective-id range (they went with the Pallas gossip kernel, PR 47)."""
    import inspect

    names = list(inspect.signature(fn).parameters)
    assert not [n for n in names
                if n == "backend" or n.startswith("collective_id")], names


def test_the_one_backend_keyword_left_does_nothing_and_refuses_a_kernel():
    """``decentralized_optimizer`` keeps ``backend`` while ``chipbench/
    cell.py`` passes it (ROADMAP D6a): ``'auto'`` and ``'xla'`` build the
    same transformation, anything else is refused."""
    import inspect
    import optax
    from bluefog_tpu.optim import decentralized_optimizer

    assert "backend" in inspect.signature(decentralized_optimizer).parameters
    for backend in ("auto", "xla"):
        decentralized_optimizer(optax.sgd(0.1), RingGraph(N), "bf",
                                backend=backend)
    for backend in ("pallas", "rdma"):
        with pytest.raises(ValueError, match="unknown backend"):
            decentralized_optimizer(optax.sgd(0.1), RingGraph(N), "bf",
                                    backend=backend)


def test_allreduce_average_and_sum():
    bf.init()
    x = rank_values((4,))
    np.testing.assert_allclose(np.asarray(bf.allreduce(x)), 3.5, rtol=1e-6)
    np.testing.assert_allclose(np.asarray(bf.allreduce(x, average=False)), 28.0, rtol=1e-6)


def test_broadcast():
    bf.init()
    x = rank_values((4,))
    out = bf.broadcast(x, root_rank=5)
    np.testing.assert_allclose(np.asarray(out), 5.0)


def test_allgather():
    bf.init()
    x = rank_values((2,))
    out = bf.allgather(x)
    assert out.shape == (N, N, 2)
    for r in range(N):
        np.testing.assert_allclose(np.asarray(out[r, :, 0]), np.arange(N))


def test_allgather_pytree():
    bf.init()
    out = bf.allgather({"a": rank_values((2,)), "b": rank_values(())})
    assert out["a"].shape == (N, N, 2)
    assert out["b"].shape == (N, N)
    np.testing.assert_allclose(np.asarray(out["b"][3]), np.arange(N))


def test_topology_object_schedule_cached():
    """Passing the same Topology object repeatedly must reuse one schedule
    (and therefore one compiled program)."""
    from bluefog_tpu.parallel.api import _schedule_for

    bf.init()
    topo = RingGraph(N)
    assert _schedule_for(topo) is _schedule_for(topo)
    x = rank_values((4,))
    out1 = bf.neighbor_allreduce(x, topology=topo)
    out2 = bf.neighbor_allreduce(x, topology=topo)
    np.testing.assert_allclose(np.asarray(out1), np.asarray(out2))


def test_neighbor_allgather_regular():
    topo = RingGraph(N)
    bf.init(topology=topo)
    x = rank_values((3,))
    slots, mask = bf.neighbor_allgather(x)
    assert slots.shape == (N, 2, 3)
    assert bool(np.asarray(mask).all())
    sched = bf.get_context().schedule
    for r in range(N):
        for k in range(sched.num_slots):
            src = sched.recv_src[r, k]
            np.testing.assert_allclose(np.asarray(slots[r, k]), float(src))


def test_neighbor_allgather_irregular_mask():
    topo = StarGraph(N, center_rank=0)
    bf.init(topology=topo)
    x = rank_values((2,))
    slots, mask = bf.neighbor_allgather(x)
    m = np.asarray(mask)
    assert m[0].sum() == N - 1  # hub hears everyone
    for r in range(1, N):
        assert m[r].sum() == 1  # leaves hear only the hub
        k = int(np.argmax(m[r]))
        np.testing.assert_allclose(np.asarray(slots[r, k]), 0.0)


def test_barrier():
    bf.init()
    assert bf.barrier() is True


def test_hierarchical_neighbor_allreduce():
    """4 machines x 2 local ranks: local exact average then machine-ring
    gossip; all local ranks end identical (reference guarantee)."""
    bf.init(local_size=2, machine_topology=RingGraph(4))
    x = rank_values((4,))
    out = np.asarray(bf.hierarchical_neighbor_allreduce(x), dtype=np.float64)
    # machine means: (0+1)/2, (2+3)/2, ... = 0.5, 2.5, 4.5, 6.5
    means = np.array([0.5, 2.5, 4.5, 6.5])
    w = RingGraph(4).weights
    ref_m = w @ means
    for m in range(4):
        np.testing.assert_allclose(out[2 * m], ref_m[m], rtol=1e-6)
        np.testing.assert_allclose(out[2 * m + 1], ref_m[m], rtol=1e-6)


def test_hierarchical_local_size_4():
    """2 machines x 4 local ranks: the counterpart-lane expansion must pair
    every one of the 4 local lanes, not just lane 0/1 (verdict weak #9)."""
    bf.init(local_size=4, machine_topology=RingGraph(2))
    x = rank_values((3,))
    out = np.asarray(bf.hierarchical_neighbor_allreduce(x), dtype=np.float64)
    means = np.array([1.5, 5.5])  # mean(0..3), mean(4..7)
    ref_m = RingGraph(2).weights @ means
    for m in range(2):
        for l in range(4):
            np.testing.assert_allclose(out[4 * m + l], ref_m[m], rtol=1e-6)


def test_hierarchical_irregular_machine_graph():
    """4 machines x 2 local ranks over a star machine graph — irregular
    per-machine degree (center talks to 3 peers, leaves to 1)."""
    topo = StarGraph(4, center_rank=1)
    bf.init(local_size=2, machine_topology=topo)
    x = rank_values((2,))
    out = np.asarray(bf.hierarchical_neighbor_allreduce(x), dtype=np.float64)
    means = np.array([0.5, 2.5, 4.5, 6.5])
    ref_m = topo.weights @ means
    for m in range(4):
        np.testing.assert_allclose(out[2 * m], ref_m[m], rtol=1e-6)
        np.testing.assert_allclose(out[2 * m + 1], ref_m[m], rtol=1e-6)


def test_hierarchical_exp2_machine_graph_local_size_2():
    """4 machines on the exp2 machine graph — multiple permute slots per
    round, still exact per closed form."""
    topo = ExponentialTwoGraph(4)
    bf.init(local_size=2, machine_topology=topo)
    x = rank_values((2,))
    out = np.asarray(bf.hierarchical_neighbor_allreduce(x), dtype=np.float64)
    means = np.array([0.5, 2.5, 4.5, 6.5])
    ref_m = topo.weights @ means
    for m in range(4):
        np.testing.assert_allclose(out[2 * m], ref_m[m], rtol=1e-6)
        np.testing.assert_allclose(out[2 * m + 1], ref_m[m], rtol=1e-6)


@pytest.mark.parametrize("local", [2, 4])
def test_hierarchical_two_level_mesh_matches_flat(local):
    """Multi-slice form: explicit (machine, local) mesh — pmean on the inner
    axis + machine-axis ppermute — must agree with the flat-mesh path and the
    closed form for both 4x2 and 2x4 shapes."""
    nm = N // local
    topo = RingGraph(nm) if nm > 1 else None
    if topo is None:
        pytest.skip("single machine")
    bf.init(local_size=local, machine_topology=topo)
    x = rank_values((3,))
    flat = np.asarray(bf.hierarchical_neighbor_allreduce(x), np.float64)
    two = np.asarray(
        bf.hierarchical_neighbor_allreduce(x, two_level_mesh=True), np.float64)
    np.testing.assert_allclose(two, flat, rtol=1e-6)
    means = np.arange(N, dtype=np.float64).reshape(nm, local).mean(1)
    ref_m = topo.weights @ means
    for m in range(nm):
        for l in range(local):
            np.testing.assert_allclose(two[local * m + l], ref_m[m], rtol=1e-6)


def test_hierarchical_two_level_bf16():
    """bf16 payloads through the two-level mesh accumulate in f32 (same
    contract as every other collective here)."""
    bf.init(local_size=2, machine_topology=RingGraph(4))
    x = rank_values((4,), jnp.bfloat16)
    flat = np.asarray(bf.hierarchical_neighbor_allreduce(x), np.float64)
    two = np.asarray(
        bf.hierarchical_neighbor_allreduce(x, two_level_mesh=True), np.float64)
    np.testing.assert_allclose(two, flat, rtol=1e-2)


def test_send_weights_bf16():
    bf.init(topology=RingGraph(N))
    sched = build_schedule(RingGraph(N))
    x = rank_values((3,), jnp.bfloat16)
    half = np.full((sched.num_slots,), 0.5, np.float32)
    out = bf.neighbor_allreduce(x, send_weights=half)
    assert out.dtype == jnp.bfloat16
    w = RingGraph(N).weights
    off = w - np.diag(np.diag(w))
    want = (np.diag(np.diag(w)) + 0.5 * off) @ np.arange(N, dtype=np.float64)[:, None] * np.ones((1, 3))
    np.testing.assert_allclose(np.asarray(out, np.float64).reshape(N, 3),
                               want, rtol=2e-2)


def test_hier_mesh_shape():
    bf.init(local_size=2, machine_topology=RingGraph(4))
    ctx = bf.get_context()
    m = ctx.hier_mesh
    assert m.devices.shape == (4, 2)
    assert m.axis_names == (ctx.machine_axis_name, ctx.local_axis_name)
    # rank r sits at (r // local, r % local): flat and two-level agree
    assert m.devices[1, 1] == ctx.devices[3]


def test_hierarchical_requires_machine_topology():
    bf.init()  # local_size=1 on a single host -> machine topo exists (8 machines)
    # but with local_size=8 there is a single machine: no machine topology
    bf.shutdown()
    bf.init(local_size=8)
    with pytest.raises(RuntimeError):
        bf.hierarchical_neighbor_allreduce(rank_values((2,)))


def test_pair_gossip():
    bf.init()
    ctx = bf.get_context()
    from jax.sharding import PartitionSpec as P
    from bluefog_tpu.parallel.api import shard_map

    # pair ranks (0<->1), (2<->3), ...
    perm = [(i, i ^ 1) for i in range(N)]
    f = shard_map(
        lambda xs: ops.pair_gossip(xs, ctx.axis_name, perm=perm),
        mesh=ctx.mesh, in_specs=(P("bf"),), out_specs=P("bf"), check_vma=False,
    )
    out = f(rank_values((2,)))
    ref = np.repeat(np.arange(0, N, 2) + 0.5, 2)
    np.testing.assert_allclose(np.asarray(out)[:, 0], ref, rtol=1e-6)


def test_in_out_neighbor_queries():
    bf.init(topology=ExponentialTwoGraph(N))
    assert bf.in_neighbor_ranks(0) == [4, 6, 7]
    assert bf.out_neighbor_ranks(0) == [1, 2, 4]
    assert bf.size() == N
    assert bf.local_size() == 1
    assert bf.machine_size() == N


def test_set_topology_rebuilds_schedule():
    bf.init()
    assert bf.load_topology().name == "ExponentialTwoGraph"
    bf.set_topology(RingGraph(N))
    assert bf.load_topology().name.startswith("RingGraph")
    x = rank_values((4,))
    out = bf.neighbor_allreduce(x)
    np.testing.assert_allclose(np.asarray(out), expected_mix(RingGraph(N), x), rtol=1e-6)


class TestFuseApply:
    """Fusion-buffer parity (reference tensor_queue fusion, SURVEY.md §2.1):
    fused gossip must be bit-for-bit identical to leaf-wise gossip."""

    def test_fused_matches_unfused(self):
        from jax.sharding import PartitionSpec as P

        from bluefog_tpu.ops import collectives as C
        from bluefog_tpu.parallel.api import shard_map as smap
        from bluefog_tpu.topology import ExponentialTwoGraph
        from bluefog_tpu.topology.schedule import build_schedule

        bf.init(topology=ExponentialTwoGraph(N))
        ctx = bf.get_context()
        sched = build_schedule(ExponentialTwoGraph(N))
        tree = {
            "w": rank_values((4, 3), jnp.float32),
            "b": rank_values((5,), jnp.bfloat16),
            "scale": rank_values((), jnp.float32),
        }

        def run(fused):
            def step(blk):
                local = jax.tree_util.tree_map(lambda t: t[0], blk)
                fn = lambda t: C.neighbor_allreduce(t, sched, "bf")
                out = C.fuse_apply(fn, local) if fused else fn(local)
                return jax.tree_util.tree_map(lambda t: t[None], out)

            return jax.jit(smap(
                step, mesh=ctx.mesh, in_specs=(P(ctx.axis_name),),
                out_specs=P(ctx.axis_name), check_vma=False))(tree)

        a, b = run(True), run(False)
        for leaf_a, leaf_b in zip(jax.tree_util.tree_leaves(a),
                                  jax.tree_util.tree_leaves(b)):
            np.testing.assert_array_equal(np.asarray(leaf_a),
                                          np.asarray(leaf_b))
            assert leaf_a.dtype == leaf_b.dtype

    def test_large_leaves_ship_unfused(self):
        """Leaves >= threshold_bytes bypass the concat/split round-trip (the
        reference fusion buffer's size cutoff) but still ride the same
        collective and produce identical results."""
        from jax.sharding import PartitionSpec as P

        from bluefog_tpu.ops import collectives as C
        from bluefog_tpu.parallel.api import shard_map as smap
        from bluefog_tpu.topology import ExponentialTwoGraph
        from bluefog_tpu.topology.schedule import build_schedule

        bf.init(topology=ExponentialTwoGraph(N))
        ctx = bf.get_context()
        sched = build_schedule(ExponentialTwoGraph(N))
        tree = {
            "big": rank_values((64, 8), jnp.float32),    # 2 KiB >= threshold
            "s1": rank_values((4,), jnp.float32),
            "s2": rank_values((3,), jnp.float32),
        }

        def run(fused):
            def step(blk):
                local = jax.tree_util.tree_map(lambda t: t[0], blk)
                fn = lambda t: C.neighbor_allreduce(t, sched, "bf")
                out = (C.fuse_apply(fn, local, threshold_bytes=1024)
                       if fused else fn(local))
                return jax.tree_util.tree_map(lambda t: t[None], out)

            return jax.jit(smap(
                step, mesh=ctx.mesh, in_specs=(P(ctx.axis_name),),
                out_specs=P(ctx.axis_name), check_vma=False))(tree)

        a, b = run(True), run(False)
        for leaf_a, leaf_b in zip(jax.tree_util.tree_leaves(a),
                                  jax.tree_util.tree_leaves(b)):
            np.testing.assert_array_equal(np.asarray(leaf_a),
                                          np.asarray(leaf_b))
            assert leaf_a.dtype == leaf_b.dtype

    def test_single_leaf_passthrough(self):
        from bluefog_tpu.ops import collectives as C

        bf.init()
        called = {}

        def fn(t):
            called["x"] = t
            return t

        x = jnp.ones((3,))
        out = C.fuse_apply(fn, x)
        assert called["x"] is x and out is x


class TestFuseApplyGrouping:
    """``fuse_apply`` packs the small leaves into buffers of about
    ``threshold_bytes`` (PR 31): what the collective is handed, buffer by
    buffer, and that the values do not depend on the packing."""

    @staticmethod
    def _tree():
        # f32 leaves of 40..400 B and bf16 leaves of 20..200 B, interleaved
        # in tree order, and two leaves at or over a 1 KiB threshold
        tree = {}
        for i in range(10):
            tree[f"a{i:02d}"] = rank_values((10 * (i + 1),), jnp.float32)
            tree[f"b{i:02d}"] = rank_values((10 * (i + 1),), jnp.bfloat16)
        tree["big_at"] = rank_values((256,), jnp.float32)      # == 1 KiB
        tree["big_over"] = rank_values((32, 20), jnp.float32)  # 2.5 KiB
        return tree

    @staticmethod
    def _local(tree):
        return jax.tree_util.tree_map(lambda t: t[0], tree)

    @pytest.mark.parametrize("threshold", [64, 256, 1024, 1 << 20])
    def test_no_fused_buffer_reaches_twice_the_threshold(self, threshold):
        from bluefog_tpu.ops import collectives as C

        seen = {}

        def fn(t):
            seen.update(t)
            return t

        local = self._local(self._tree())
        out = C.fuse_apply(fn, local, threshold_bytes=threshold)
        nbytes = lambda a: a.size * a.dtype.itemsize
        small = [l for l in jax.tree_util.tree_leaves(local)
                 if nbytes(l) < threshold]
        assert all(b.ndim == 1 and nbytes(b) < 2 * threshold
                   for b in seen["fused"]), [nbytes(b) for b in seen["fused"]]
        # every buffer but the last of its dtype was closed AT the threshold
        for dt in (jnp.float32, jnp.bfloat16):
            of_dt = [b for b in seen["fused"] if b.dtype == dt]
            assert all(nbytes(b) >= threshold for b in of_dt[:-1])
        assert (sum(map(nbytes, seen["fused"])) == sum(map(nbytes, small)))
        # identity collective: the tree comes back leaf for leaf
        for a, b in zip(jax.tree_util.tree_leaves(out),
                        jax.tree_util.tree_leaves(local)):
            assert a.dtype == b.dtype and a.shape == b.shape
            np.testing.assert_array_equal(np.asarray(a), np.asarray(b))

    def test_leaves_at_or_over_the_threshold_ride_unfused(self):
        from bluefog_tpu.ops import collectives as C

        seen = {}

        def fn(t):
            seen.update(t)
            return t

        local = self._local(self._tree())
        C.fuse_apply(fn, local, threshold_bytes=1024)
        assert [b.shape for b in seen["big"]] == [(256,), (32, 20)]
        assert any(b is local["big_at"] for b in seen["big"])
        assert any(b is local["big_over"] for b in seen["big"])
        assert all(b.size * b.dtype.itemsize < 1024 + 400
                   for b in seen["fused"])

    def test_threshold_none_is_one_buffer_a_dtype(self):
        from bluefog_tpu.ops import collectives as C

        seen = {}

        def fn(t):
            seen.update(t)
            return t

        C.fuse_apply(fn, self._local(self._tree()), threshold_bytes=None)
        assert seen["big"] == []
        assert sorted(str(b.dtype) for b in seen["fused"]) == [
            "bfloat16", "float32"]

    @pytest.mark.parametrize("threshold", [64, 256, 1024, None])
    def test_values_equal_the_unfused_call_on_a_mixed_dtype_tree(
            self, threshold):
        from jax.sharding import PartitionSpec as P

        from bluefog_tpu.ops import collectives as C
        from bluefog_tpu.parallel.api import shard_map as smap
        from bluefog_tpu.topology import ExponentialTwoGraph
        from bluefog_tpu.topology.schedule import build_schedule

        bf.init(topology=ExponentialTwoGraph(N))
        ctx = bf.get_context()
        sched = build_schedule(ExponentialTwoGraph(N))
        tree = self._tree()

        def run(fused):
            def step(blk):
                local = jax.tree_util.tree_map(lambda t: t[0], blk)
                fn = lambda t: C.neighbor_allreduce(t, sched, "bf")
                out = (C.fuse_apply(fn, local, threshold_bytes=threshold)
                       if fused else fn(local))
                return jax.tree_util.tree_map(lambda t: t[None], out)

            return jax.jit(smap(
                step, mesh=ctx.mesh, in_specs=(P(ctx.axis_name),),
                out_specs=P(ctx.axis_name), check_vma=False))(tree)

        a, b = run(True), run(False)
        for leaf_a, leaf_b in zip(jax.tree_util.tree_leaves(a),
                                  jax.tree_util.tree_leaves(b)):
            np.testing.assert_array_equal(np.asarray(leaf_a),
                                          np.asarray(leaf_b))
            assert leaf_a.dtype == leaf_b.dtype

    def test_a_schedule_with_no_slot_is_the_identity(self):
        """One rank (the benchmark's one-chip cells): nothing is exchanged,
        the tree comes back bit for bit and no collective is compiled.  (On
        the TPU XLA also removes the packing itself, as it did the single
        buffer a dtype before PR 31: ``tests/test_overlap_aot.py``.)"""
        from jax.sharding import PartitionSpec as P

        from bluefog_tpu.ops import collectives as C
        from bluefog_tpu.parallel.api import shard_map as smap
        from bluefog_tpu.topology import ExponentialTwoGraph
        from bluefog_tpu.topology.schedule import build_schedule

        ctx = bf.init(topology=ExponentialTwoGraph(1), size=1)
        sched = build_schedule(ExponentialTwoGraph(1))
        assert sched.num_slots == 0
        tree = jax.tree_util.tree_map(lambda t: t[:1], self._tree())

        def step(blk):
            local = jax.tree_util.tree_map(lambda t: t[0], blk)
            out = C.fuse_apply(
                lambda t: C.neighbor_allreduce(t, sched, "bf"), local,
                threshold_bytes=256)
            return jax.tree_util.tree_map(lambda t: t[None], out)

        fn = jax.jit(smap(step, mesh=ctx.mesh, in_specs=(P(ctx.axis_name),),
                          out_specs=P(ctx.axis_name), check_vma=False))
        out = fn(tree)
        for a, b in zip(jax.tree_util.tree_leaves(out),
                        jax.tree_util.tree_leaves(tree)):
            np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
        assert "collective-permute" not in fn.lower(tree).compile().as_text()


class TestCollectiveCensus:
    """HLO-level proof of the fusion win: one ppermute per schedule slot
    instead of one per leaf (utils.inspect counts post-optimization HLO)."""

    def test_fusion_reduces_permute_count(self):
        from jax.sharding import PartitionSpec as P

        from bluefog_tpu.ops import collectives as C
        from bluefog_tpu.parallel.api import shard_map as smap
        from bluefog_tpu.topology import ExponentialTwoGraph
        from bluefog_tpu.topology.schedule import build_schedule
        from bluefog_tpu.utils.inspect import collective_census

        bf.init(topology=ExponentialTwoGraph(N))
        ctx = bf.get_context()
        sched = build_schedule(ExponentialTwoGraph(N))
        n_leaves = 20
        tree = {f"w{i}": jnp.ones((N, 4, 4)) for i in range(n_leaves)}

        def make(fused):
            def step(blk):
                local = jax.tree_util.tree_map(lambda t: t[0], blk)
                fn = lambda t: C.neighbor_allreduce(t, sched, "bf")
                out = C.fuse_apply(fn, local) if fused else fn(local)
                return jax.tree_util.tree_map(lambda t: t[None], out)

            return jax.jit(smap(
                step, mesh=ctx.mesh, in_specs=(P(ctx.axis_name),),
                out_specs=P(ctx.axis_name), check_vma=False))

        slots = sched.num_slots
        unfused = collective_census(make(False), tree)
        fused = collective_census(make(True), tree)
        assert unfused["collective-permute"] == n_leaves * slots
        assert fused["collective-permute"] == slots


class TestOverlapReport:
    """parse_overlap_windows against synthetic scheduled-HLO text (the TPU
    async form; CPU lowers collectives synchronously, so the real-module
    TPU case is exercised by benchmarks/overlap_report.py via AOT compile)."""

    HLO = "\n".join([
        "ENTRY %main {",
        "  %collective-permute-start.1 = (f32[8]) collective-permute-start(%p0)",
        "  %fusion.1 = f32[8] fusion(%a), kind=kLoop",
        "  %dot.7 = f32[8,8] dot(%b, %c)",
        "  %collective-permute-start.12 = (f32[8]) collective-permute-start(%p1)",
        "  %copy-done.3 = f32[8] copy-done(%cp)",   # untracked family: ignored
        "  %convolution.2 = f32[8] convolution(%d, %e)",
        "  %cpd.12 = f32[8] collective-permute-done(%collective-permute-start.12)",
        "  %fusion.2 = f32[8] fusion(%f), kind=kOutput",
        "  %cpd.1 = f32[8] collective-permute-done(%collective-permute-start.1)",
        "}",
    ])

    def test_windows_and_exact_name_matching(self):
        from bluefog_tpu.utils.inspect import parse_overlap_windows

        rep = parse_overlap_windows(self.HLO)
        assert rep["pairs"] == 2
        # .12's done must NOT close .1 (prefix name): .12 saw 1 compute op
        # (convolution), .1 saw fusion.1 + dot + convolution + fusion.2 = 4
        assert sorted(rep["windows"]) == [1, 4]
        assert rep["overlapped_fraction"] == 1.0

    def test_no_async_pairs(self):
        from bluefog_tpu.utils.inspect import parse_overlap_windows

        rep = parse_overlap_windows(
            "%pp = f32[8] collective-permute(%x)\n%f = f32[8] fusion(%x)")
        assert rep["pairs"] == 0 and rep["mean_compute_in_flight"] == 0.0


class TestTransferSchedule:
    """transfer_schedule reads the ENTRY computation alone: positions in
    compute instructions, payload bytes, and the marks a caller asks for."""

    HLO = "\n".join([
        "%fused_computation.1 {",
        "  %dot.99 = f32[8,8] dot(%q, %r)",          # not ENTRY: not counted
        "}",
        "ENTRY %main {",
        "  %collective-permute-start.1 = (f32[768,3072]{1,0}, f32[768,3072]{1,0}, u32[], u32[]) collective-permute-start(%p0)",
        "  %fwd_kernel.3 = (f32[8]) custom-call(%a), custom_call_target=\"tpu_custom_call\"",
        "  %fusion.1 = f32[8] fusion(%a), kind=kLoop",
        "  %collective-permute-start.12 = (bf16[1024]{0}, bf16[1024]{0}) collective-permute-start(%p1)",
        "  %copy-done.3 = f32[8] copy-done(%cp)",
        "  %convolution.2 = f32[8] convolution(%d, %e)",
        "  %cpd.12 = bf16[1024]{0} collective-permute-done(%collective-permute-start.12)",
        "  %fusion.2 = f32[8] fusion(%f), kind=kOutput",
        "  %cpd.1 = f32[768,3072]{1,0} collective-permute-done(%collective-permute-start.1)",
        "}",
    ])

    def test_positions_bytes_and_marks(self):
        from bluefog_tpu.utils.inspect import transfer_schedule

        rep = transfer_schedule(self.HLO, {"kernel": r"%fwd_kernel\S* = ",
                                           "absent": r"no such thing"})
        assert rep["compute_ops"] == 4
        # closing order; (opened_at, closed_at, bytes of ONE payload)
        assert rep["transfers"] == [(2, 3, 2048), (0, 4, 768 * 3072 * 4)]
        assert rep["marks"] == {"kernel": [1], "absent": []}

    def test_a_module_without_async_pairs(self):
        from bluefog_tpu.utils.inspect import transfer_schedule

        rep = transfer_schedule(
            "%pp = f32[8] collective-permute(%x)\n%f = f32[8] fusion(%x)")
        assert rep["transfers"] == [] and rep["compute_ops"] == 1


class TestLayoutCopies:
    """layout_copies counts the stand-alone copy / transpose instructions of
    the ENTRY computation, with the bytes they write: the passes that move a
    buffer into another layout and compute nothing (PERF.md, PR 33)."""

    HLO = "\n".join([
        "%fused_computation.7 {",
        "  %copy.90 = bf16[64,64]{0,1} copy(%p)",          # inside a fusion
        "}",
        "%while_body.3 {",
        "  %copy.91 = f32[1024,1024]{1,0} copy(%q)",       # nested: not ENTRY
        "}",
        "ENTRY %main {",
        '  %copy.1 = bf16[8,12,2048,64]{3,2,1,0:T(8,128)(2,1)} copy(%fusion.4), metadata={op_name="jit(step)/jvp(M)/block_0/transpose"}',
        "  %transpose.2 = f32[768,2304]{0,1:T(8,128)} transpose(%p1), dimensions={1,0}",
        "  %fusion.5 = bf16[8,2048,768]{2,1,0} fusion(%copy.1), kind=kLoop, calls=%fused_computation.7",
        "  %copy-start.1 = (f32[16]{0}, f32[16]{0}, u32[]) copy-start(%p2)",
        "  %copy-done.1 = f32[16]{0} copy-done(%copy-start.1)",
        "  %while.1 = (f32[1024,1024]{1,0}) while(%t), body=%while_body.3",
        '  ROOT %copy.3 = s32[4]{0} copy(%p3), metadata={op_name="jit(step)/tail"}',
        "}",
        "%later_computation.9 {",
        "  %copy.92 = f32[4096]{0} copy(%r)",              # after ENTRY's end
        "}",
    ])

    def test_counts_the_entry_computations_copies_and_transposes(self):
        from bluefog_tpu.utils.inspect import layout_copies

        rep = layout_copies(self.HLO)
        activation, weight = 8 * 12 * 2048 * 64 * 2, 768 * 2304 * 4
        assert rep["count"] == 3
        assert rep["bytes"] == activation + weight + 16
        assert rep["largest"] == [
            (activation, "copy.1", "jit(step)/jvp(M)/block_0/transpose"),
            (weight, "transpose.2", ""), (16, "copy.3", "jit(step)/tail")]

    @pytest.mark.parametrize("absent", ["copy.90", "copy.91", "copy.92",
                                        "fusion.5", "copy-start.1",
                                        "copy-done.1", "while.1"])
    def test_fusions_nested_computations_and_async_copies_do_not_count(
            self, absent):
        from bluefog_tpu.utils.inspect import layout_copies

        names = [name for _, name, _ in layout_copies(
            self.HLO, largest=100)["largest"]]
        assert absent not in names and len(names) == 3

    def test_largest_bounds_the_list_and_not_the_totals(self):
        from bluefog_tpu.utils.inspect import layout_copies

        rep = layout_copies(self.HLO, largest=1)
        assert rep["count"] == 3 and len(rep["largest"]) == 1
        assert layout_copies("%f = f32[8] fusion(%x)") == {
            "count": 0, "bytes": 0, "largest": []}
