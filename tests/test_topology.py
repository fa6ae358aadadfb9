"""Topology-library unit tests — mirrors the reference's
``test/common/topology_util_test.py`` pyramid layer (SURVEY.md §4): pure
pytest, no devices: row-stochasticity, neighbor sets, equivalence, dynamic
generators, schedule lowering round-trips."""

import math

import numpy as np
import pytest

from bluefog_tpu.topology import (
    ExponentialGraph,
    ExponentialTwoGraph,
    FullyConnectedGraph,
    GetDynamicOnePeerSendRecvRanks,
    GetInnerOuterExpo2DynamicSendRecvRanks,
    GetInnerOuterRingDynamicSendRecvRanks,
    GetRecvWeights,
    GetSendWeights,
    GossipSchedule,
    IsRegularGraph,
    IsTopologyEquivalent,
    MeshGrid2DGraph,
    RingGraph,
    StarGraph,
    SymmetricExponentialGraph,
    Topology,
    build_schedule,
    dynamic_topologies_from_generator,
    one_peer_exponential_two_schedules,
    one_peer_ring_schedules,
    remap_topology,
)

ALL_SIZES = [2, 3, 4, 7, 8, 16]


def _constructors(size):
    return [
        ExponentialTwoGraph(size),
        ExponentialGraph(size, base=3),
        SymmetricExponentialGraph(size),
        RingGraph(size, 0),
        RingGraph(size, 1),
        RingGraph(size, 2),
        MeshGrid2DGraph(size),
        StarGraph(size),
        FullyConnectedGraph(size),
    ]


@pytest.mark.parametrize("size", ALL_SIZES)
def test_row_stochastic_and_nonnegative(size):
    for topo in _constructors(size):
        w = topo.weights
        assert np.allclose(w.sum(axis=1), 1.0), topo.name
        assert (w >= 0).all(), topo.name


def test_exponential_two_neighbors():
    topo = ExponentialTwoGraph(8)
    # rank 0 sends to +1, +2, +4
    assert topo.out_neighbors(0) == [1, 2, 4]
    assert topo.in_neighbors(0) == [4, 6, 7]
    # uniform 1/(indeg+1) weights
    assert math.isclose(topo.self_weight(0), 0.25)
    assert all(math.isclose(w, 0.25) for w in GetRecvWeights(topo, 0)[1].values())


def test_exponential_non_power_of_two():
    topo = ExponentialTwoGraph(6)
    assert topo.out_neighbors(0) == [1, 2, 4]
    assert np.allclose(topo.weights.sum(axis=1), 1.0)


def test_ring_styles():
    bi = RingGraph(5, 0)
    assert bi.in_neighbors(2) == [1, 3]
    assert math.isclose(bi.self_weight(2), 1 / 3)
    right = RingGraph(5, 1)
    assert right.in_neighbors(2) == [1]
    assert right.out_neighbors(2) == [3]
    left = RingGraph(5, 2)
    assert left.in_neighbors(2) == [3]
    # size-2 ring: the two directions coincide
    tiny = RingGraph(2, 0)
    assert tiny.in_neighbors(0) == [1]
    assert math.isclose(tiny.self_weight(0), 0.5)


def test_mesh_grid_doubly_stochastic():
    topo = MeshGrid2DGraph(6)  # 2x3 grid
    w = topo.weights
    assert np.allclose(w.sum(axis=0), 1.0)  # column-stochastic too (MH weights)
    assert np.allclose(w, w.T)
    assert IsRegularGraph(topo)
    # corner rank 0 of the 2x3 grid: neighbors are 1 (right) and 3 (below)
    assert topo.in_neighbors(0) == [1, 3]


def test_mesh_grid_explicit_shape():
    topo = MeshGrid2DGraph(8, shape=(2, 4))
    assert topo.size == 8
    with pytest.raises(ValueError):
        MeshGrid2DGraph(8, shape=(3, 3))


def test_star():
    topo = StarGraph(5, center_rank=2)
    assert topo.in_neighbors(2) == [0, 1, 3, 4]
    assert topo.in_neighbors(0) == [2]
    assert math.isclose(topo.self_weight(2), 1 / 5)
    assert math.isclose(topo.self_weight(0), 1 / 2)


def test_fully_connected_exact_average():
    topo = FullyConnectedGraph(4)
    x = np.array([1.0, 2.0, 3.0, 10.0])
    assert np.allclose(topo.weights @ x, x.mean())


def test_equivalence_and_remap():
    a, b = ExponentialTwoGraph(8), ExponentialTwoGraph(8)
    assert IsTopologyEquivalent(a, b)
    assert not IsTopologyEquivalent(a, RingGraph(8))
    assert not IsTopologyEquivalent(a, ExponentialTwoGraph(4))
    assert not IsTopologyEquivalent(a, None)
    perm = list(reversed(range(8)))
    r = remap_topology(a, perm)
    assert not IsTopologyEquivalent(a, r) or a.size == 1
    assert IsTopologyEquivalent(a, remap_topology(r, perm))  # involution


def test_send_recv_weights_duality():
    topo = ExponentialTwoGraph(8)
    for r in range(8):
        _, send = GetSendWeights(topo, r)
        for dst, w in send.items():
            self_w, recv = GetRecvWeights(topo, dst)
            assert math.isclose(recv[r], w)
            del self_w


def test_from_edges_uniform_weights():
    topo = Topology.from_edges(4, [(0, 1), (2, 1), (1, 0)])
    assert math.isclose(topo.weights[1, 0], 1 / 3)
    assert math.isclose(topo.weights[1, 2], 1 / 3)
    assert math.isclose(topo.weights[1, 1], 1 / 3)
    assert math.isclose(topo.weights[3, 3], 1.0)


def test_networkx_round_trip():
    nx = pytest.importorskip("networkx")
    topo = MeshGrid2DGraph(6)
    g = topo.to_networkx()
    back = Topology.from_networkx(g)
    assert IsTopologyEquivalent(topo, back)
    del nx


# -- schedules ---------------------------------------------------------------


@pytest.mark.parametrize("size", ALL_SIZES)
def test_schedule_reproduces_mixing_matrix(size):
    for topo in _constructors(size):
        sched = build_schedule(topo)
        assert np.allclose(sched.mixing_matrix(), topo.weights, atol=1e-9), topo.name


def test_circulant_fast_path():
    assert build_schedule(ExponentialTwoGraph(8)).is_circulant
    assert build_schedule(RingGraph(8)).is_circulant
    assert build_schedule(FullyConnectedGraph(4)).is_circulant
    assert not build_schedule(StarGraph(8)).is_circulant
    assert not build_schedule(MeshGrid2DGraph(6)).is_circulant


def test_schedule_slot_counts():
    # circulant: one slot per shift class
    assert build_schedule(ExponentialTwoGraph(8)).num_slots == 3
    assert build_schedule(RingGraph(8)).num_slots == 2
    # star(n): greedy coloring needs >= n-1 slots at the hub
    s = build_schedule(StarGraph(5))
    assert s.num_slots >= 4
    for perm in s.perms:
        srcs = [a for a, _ in perm]
        dsts = [b for _, b in perm]
        assert len(set(srcs)) == len(srcs)
        assert len(set(dsts)) == len(dsts)


# -- dynamic generators ------------------------------------------------------


def test_one_peer_generator_cycles():
    topo = ExponentialTwoGraph(8)
    gen = GetDynamicOnePeerSendRecvRanks(topo, 0)
    seen = [next(gen) for _ in range(6)]
    # cycles through out-neighbors 1,2,4 and in-neighbors 7,6,4 (offset order)
    assert [s for s, _ in seen] == [[1], [2], [4], [1], [2], [4]]
    assert [r for _, r in seen] == [[7], [6], [4], [7], [6], [4]]


def test_dynamic_topologies_consistent():
    topo = ExponentialTwoGraph(8)
    topos = dynamic_topologies_from_generator(
        8, lambda r: GetDynamicOnePeerSendRecvRanks(topo, r), num_steps=6
    )
    assert len(topos) == 6
    for t in topos:
        assert np.allclose(t.weights.sum(axis=1), 1.0)
        for r in range(8):
            assert t.in_degree(r) == 1
            assert t.out_degree(r) == 1


def test_one_peer_exp2_schedules():
    topos = one_peer_exponential_two_schedules(8)
    assert len(topos) == 3
    for k, t in enumerate(topos):
        assert t.in_neighbors(0) == [(0 - 2**k) % 8]
        assert math.isclose(t.self_weight(0), 0.5)
    # product over one period mixes mass from every rank to every rank
    prod = np.eye(8)
    for t in topos:
        prod = t.weights @ prod
    assert (prod > 0).all()


def test_one_peer_ring_schedules():
    topos = one_peer_ring_schedules(8)
    assert len(topos) == 2
    assert topos[0].in_neighbors(0) == [7]
    assert topos[1].in_neighbors(0) == [1]


def test_inner_outer_generators_consistent():
    for factory in (
        lambda r: GetInnerOuterRingDynamicSendRecvRanks(8, 2, r),
        lambda r: GetInnerOuterExpo2DynamicSendRecvRanks(8, 2, r),
    ):
        topos = dynamic_topologies_from_generator(8, factory, num_steps=8)
        for t in topos:
            for r in range(8):
                assert t.in_degree(r) <= 1


def test_bad_weight_matrix_rejected():
    with pytest.raises(ValueError):
        Topology(weights=np.array([[0.5, 0.2], [0.5, 0.5]]))
    with pytest.raises(ValueError):
        Topology(weights=np.array([[1.5, -0.5], [0.0, 1.0]]))


class TestICIRingOrder:
    """ici_ring_order must produce a path where consecutive devices are one
    torus hop apart (SURVEY.md §7: ring -> ICI torus ring is exact)."""

    class FakeDev:
        def __init__(self, id, coords):
            self.id = id
            self.coords = coords

    @staticmethod
    def _torus_dist(a, b, dims):
        return sum(min(abs(x - y), d - abs(x - y))
                   for x, y, d in zip(a, b, dims))

    @pytest.mark.parametrize("dims", [(4, 4), (2, 4), (4, 2, 2)])
    def test_consecutive_are_adjacent(self, dims):
        import itertools

        from bluefog_tpu.topology.mapping import ici_ring_order

        devs = [self.FakeDev(i, c) for i, c in
                enumerate(itertools.product(*[range(d) for d in dims]))]
        # scramble to prove the sort does the work
        import random as _r
        _r.Random(0).shuffle(devs)
        ordered = ici_ring_order(devs)
        assert len(ordered) == len(devs)
        for a, b in zip(ordered, ordered[1:]):
            assert self._torus_dist(a.coords, b.coords, dims) == 1, (
                f"{a.coords} -> {b.coords} is not one hop")
        # the closing edge matters too: ring topologies wrap last -> first
        assert self._torus_dist(ordered[-1].coords, ordered[0].coords,
                                dims) == 1

    def test_v5e_2x2_host_order(self):
        """The four-chip v5e host as JAX reports it (id i at coords
        (i % 2, i // 2, 0)): the snake is device ids [0, 2, 3, 1] — a
        PERMUTED mesh, which is what peers addressed by mesh position must
        survive (checked on hardware by chip_smoke.py)."""
        from bluefog_tpu.topology.mapping import ici_ring_order

        devs = [self.FakeDev(i, (i % 2, i // 2, 0)) for i in range(4)]
        ordered = ici_ring_order(devs)
        assert [d.id for d in ordered] == [0, 2, 3, 1]
        for a, b in zip(ordered, ordered[1:] + ordered[:1]):
            assert self._torus_dist(a.coords, b.coords, (2, 2, 1)) == 1

    def test_no_coords_falls_back_to_id(self):
        from bluefog_tpu.topology.mapping import ici_ring_order

        class Bare:
            def __init__(self, id):
                self.id = id

        devs = [Bare(3), Bare(0), Bare(2), Bare(1)]
        assert [d.id for d in ici_ring_order(devs)] == [0, 1, 2, 3]
