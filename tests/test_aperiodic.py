"""Aperiodic (per-call arbitrary edge set) dynamic topology gossip.

The reference changes the topology per call via ``src_weights=`` with no
recompilation concern (eager MPI); the XLA answer is
``neighbor_allreduce_aperiodic``: circulant-rotation decomposition with the
mixing matrix as *data* (SURVEY.md §7 hard-part #2).  Tests assert

1. closed-form correctness ``out == W @ xs`` for random irregular matrices,
2. **one compile** across many different edge sets (the core requirement),
3. the jittable one-peer exp2 matrix builder matches the schedule variant,
4. the optimizer integration (callable topology) trains without retracing.
"""

import functools

import jax
import jax.numpy as jnp
import numpy as np
import optax
import pytest
from jax.sharding import Mesh, PartitionSpec as P

from bluefog_tpu.parallel.api import shard_map

from bluefog_tpu.ops.collectives import neighbor_allreduce_aperiodic
from bluefog_tpu.optim import DistributedNeighborAllreduceOptimizer
from bluefog_tpu.topology.dynamic import (
    one_peer_exp2_mixing_matrix,
    one_peer_exponential_two_schedules,
)
from bluefog_tpu.topology.schedule import build_schedule

N = 8


def _mesh():
    return Mesh(np.array(jax.devices()[:N]), ("bf",))


def _random_mixing_matrix(rng, n=N, max_degree=3):
    """Row-stochastic W with a random edge set of random in-degrees."""
    w = np.zeros((n, n))
    for i in range(n):
        deg = rng.integers(0, max_degree + 1)
        nbrs = rng.choice([j for j in range(n) if j != i],
                          size=deg, replace=False)
        weights = rng.random(deg + 1) + 0.1
        weights /= weights.sum()
        w[i, i] = weights[0]
        for j, wt in zip(nbrs, weights[1:]):
            w[i, j] = wt
    return w


@pytest.fixture
def gossip_fn():
    mesh = _mesh()
    traces = {"count": 0}

    def fn(xs, w):
        traces["count"] += 1
        return neighbor_allreduce_aperiodic(xs, w, "bf")

    jitted = jax.jit(shard_map(
        fn, mesh=mesh, in_specs=(P("bf"), P()), out_specs=P("bf"),
        check_vma=False,
    ))
    return jitted, traces


def test_matches_dense_oracle_many_edge_sets_one_compile(gossip_fn):
    jitted, traces = gossip_fn
    rng = np.random.default_rng(0)
    xs = rng.standard_normal((N, 5, 3)).astype(np.float32)
    for _ in range(6):
        w = _random_mixing_matrix(rng)
        got = jitted(jnp.asarray(xs), jnp.asarray(w, jnp.float32))
        want = np.einsum("ij,jkl->ikl", w, xs)
        np.testing.assert_allclose(np.asarray(got), want, rtol=1e-5,
                                   atol=1e-5)
    assert traces["count"] == 1, (
        f"aperiodic gossip retraced {traces['count']}x across changing edge "
        "sets; the edge set must be data, not program")


def test_pytree_and_dtypes(gossip_fn):
    jitted, _ = gossip_fn
    rng = np.random.default_rng(1)
    w = _random_mixing_matrix(rng)
    tree = {
        "a": rng.standard_normal((N, 4)).astype(np.float32),
        "b": rng.standard_normal((N, 2, 2)).astype(np.float32),
    }
    got = jitted({k: jnp.asarray(v) for k, v in tree.items()},
                 jnp.asarray(w, jnp.float32))
    for key in tree:
        want = np.einsum("ij,j...->i...", w, tree[key])
        np.testing.assert_allclose(np.asarray(got[key]), want, rtol=1e-5,
                                   atol=1e-5)


def test_bf16_accumulates_in_f32(gossip_fn):
    jitted, _ = gossip_fn
    rng = np.random.default_rng(2)
    w = _random_mixing_matrix(rng)
    xs = rng.standard_normal((N, 16)).astype(np.float32)
    got = jitted(jnp.asarray(xs, jnp.bfloat16), jnp.asarray(w, jnp.float32))
    assert got.dtype == jnp.bfloat16
    want = np.einsum("ij,jk->ik", w, xs)
    np.testing.assert_allclose(np.asarray(got, np.float32), want, rtol=0.05,
                               atol=0.05)


def test_one_peer_exp2_matrix_matches_schedules():
    """The jittable matrix builder reproduces the precompiled schedule
    period exactly (same weights, same edges, for every phase)."""
    topos = one_peer_exponential_two_schedules(N)
    for step in range(2 * len(topos)):
        w = np.asarray(one_peer_exp2_mixing_matrix(N, step))
        want = topos[step % len(topos)].weights
        np.testing.assert_allclose(w, want, atol=1e-7)


def test_one_peer_exp2_matrix_traced_step():
    f = jax.jit(lambda s: one_peer_exp2_mixing_matrix(N, s))
    for step in range(4):
        np.testing.assert_allclose(
            np.asarray(f(step)),
            np.asarray(one_peer_exp2_mixing_matrix(N, step)), atol=1e-7)


class TestDegreeCapped:
    """max_rotations=D: runtime-shift rotation slots (D * ceil(log2 n)
    ppermutes) instead of the full n-1 decomposition — the program-size
    answer for pod-scale meshes (VERDICT r3 weak #3)."""

    def _jit(self, cap):
        mesh = _mesh()
        return jax.jit(shard_map(
            lambda xs, w: neighbor_allreduce_aperiodic(
                xs, w, "bf", max_rotations=cap),
            mesh=mesh, in_specs=(P("bf"), P()), out_specs=P("bf"),
            check_vma=False))

    def test_matches_oracle_within_cap(self):
        jitted = self._jit(3)
        rng = np.random.default_rng(7)
        xs = rng.standard_normal((N, 5)).astype(np.float32)
        for _ in range(4):
            # <= 3 distinct nonzero shifts --> <= 3 active rotations
            w = np.zeros((N, N))
            shifts = rng.choice(range(1, N), size=3, replace=False)
            for i in range(N):
                w[i, i] = 0.4
                for s in shifts:
                    w[i, (i - s) % N] = 0.2
            got = jitted(jnp.asarray(xs), jnp.asarray(w, jnp.float32))
            want = w @ xs
            np.testing.assert_allclose(np.asarray(got), want, rtol=1e-5,
                                       atol=1e-5)

    def test_one_peer_needs_one_slot(self):
        jitted = self._jit(1)
        xs = np.random.default_rng(8).standard_normal((N, 4)).astype(
            np.float32)
        for step in range(4):
            w = np.asarray(one_peer_exp2_mixing_matrix(N, step))
            got = jitted(jnp.asarray(xs), jnp.asarray(w, jnp.float32))
            np.testing.assert_allclose(np.asarray(got), w @ xs, rtol=1e-5,
                                       atol=1e-5)

    def test_cap_overflow_poisons_with_nan(self):
        """More active rotations than slots must be LOUD (NaN), never a
        silently dropped edge."""
        jitted = self._jit(2)
        xs = np.ones((N, 3), np.float32)
        w = np.full((N, N), 1.0 / N)  # full graph: n-1 active rotations
        got = np.asarray(jitted(jnp.asarray(xs), jnp.asarray(w, jnp.float32)))
        assert np.isnan(got).all()

    def test_fuzz_cap_vs_full_and_overflow(self):
        """Randomized: for random circulant-sparse W, capped == full when
        the cap covers the active rotations, NaN-poisoned when it cannot."""
        rng = np.random.default_rng(11)
        xs = rng.standard_normal((N, 4)).astype(np.float32)
        jit_cache = {}

        def run(cap):
            if cap not in jit_cache:
                jit_cache[cap] = self._jit(cap)
            return jit_cache[cap]

        for trial in range(8):
            n_active = int(rng.integers(1, 5))
            shifts = rng.choice(range(1, N), size=n_active, replace=False)
            w = np.zeros((N, N))
            for i in range(N):
                w[i, i] = 0.5
                for s in shifts:
                    w[i, (i - s) % N] = 0.5 / n_active
            got = run(4)(jnp.asarray(xs), jnp.asarray(w, jnp.float32))
            np.testing.assert_allclose(np.asarray(got), w @ xs, rtol=1e-5,
                                       atol=1e-5, err_msg=f"trial {trial}")
            if n_active > 1:
                under = run(n_active - 1)(jnp.asarray(xs),
                                          jnp.asarray(w, jnp.float32))
                assert np.isnan(np.asarray(under)).all(), (
                    f"trial {trial}: cap {n_active - 1} < {n_active} active "
                    "rotations must poison, not drop edges")

    def test_compile_census_n64(self):
        """Program-size census at n=64 (pod-scale proxy): the capped
        program must contain an order-of-magnitude fewer collective
        permutes than the full decomposition's 63.  Lowering census runs
        on an ABSTRACT 64-device mesh (no need for 64 real devices)."""
        from jax.sharding import AbstractMesh

        n = 64
        mesh64 = AbstractMesh((n,), ("bf",))

        def lower(cap):
            fn = jax.jit(shard_map(
                lambda xs, w: neighbor_allreduce_aperiodic(
                    xs, w, "bf", max_rotations=cap),
                mesh=mesh64, in_specs=(P("bf"), P()), out_specs=P("bf"),
                check_vma=False))
            return fn.lower(
                jax.ShapeDtypeStruct((n, 8), jnp.float32),
                jax.ShapeDtypeStruct((n, n), jnp.float32)).as_text()

        full = lower(None)
        capped = lower(3)
        count_full = full.count("collective_permute")
        count_capped = capped.count("collective_permute")
        # full: one per rotation (63); capped: 3 slots x ceil(log2 64) = 18
        assert count_full >= n - 1, count_full
        assert count_capped <= 3 * 6, count_capped
        assert count_capped < count_full / 3
        assert len(capped) < len(full), (len(capped), len(full))


def _optimizer_harness(opt, mesh):
    """(init, jitted_step) over the stacked rank representation for an
    optimizer — shared by the callable-topology tests."""
    init = jax.jit(shard_map(
        lambda q: jax.tree_util.tree_map(
            lambda t: jnp.asarray(t)[None], opt.init(q[0])),
        mesh=mesh, in_specs=(P("bf"),), out_specs=P("bf"), check_vma=False))

    def step_fn(p, st, g):
        upd, st = opt.update(g, st, p)
        return optax.apply_updates(p, upd), st

    jitted = jax.jit(shard_map(
        lambda q, s, g: jax.tree_util.tree_map(
            lambda t: t[None],
            step_fn(q[0], jax.tree_util.tree_map(lambda t: t[0], s), g[0])),
        mesh=mesh, in_specs=(P("bf"),) * 3, out_specs=P("bf"),
        check_vma=False))
    return init, jitted


def test_optimizer_callable_topology_respects_cap():
    """max_rotations reaches the optimizer's aperiodic path: a capped
    one-peer training run is bit-compatible with the uncapped one, and the
    cap is rejected outside the aperiodic mode."""
    mesh = _mesh()

    def run(cap):
        opt = DistributedNeighborAllreduceOptimizer(
            optax.sgd(0.1), topology=functools.partial(
                one_peer_exp2_mixing_matrix, N),
            axis_name="bf", atc=True, max_rotations=cap)
        init, jitted = _optimizer_harness(opt, mesh)
        rng = np.random.default_rng(4)
        p = jnp.asarray(rng.standard_normal((N, 6)), jnp.float32)
        st = init(p)
        for step in range(3):
            g = jnp.asarray(rng.standard_normal((N, 6)), jnp.float32)
            p, st = jitted(p, st, g)
        return np.asarray(p)

    np.testing.assert_allclose(run(1), run(None), rtol=1e-5, atol=1e-6)

    from bluefog_tpu.topology import RingGraph
    with pytest.raises(ValueError, match="callable-topology"):
        DistributedNeighborAllreduceOptimizer(
            optax.sgd(0.1), topology=RingGraph(N), axis_name="bf",
            max_rotations=2)


def test_optimizer_callable_topology_one_compile():
    """DistributedNeighborAllreduceOptimizer(topology=callable) gossips a
    different edge set every step inside ONE compiled train step, and the
    result matches manually applying W to the post-SGD params."""
    mesh = _mesh()
    opt = DistributedNeighborAllreduceOptimizer(
        optax.sgd(0.1), topology=functools.partial(
            one_peer_exp2_mixing_matrix, N),
        axis_name="bf", atc=True)

    def step_fn(p, st, g):
        upd, st = opt.update(g, st, p)
        return optax.apply_updates(p, upd), st

    rng = np.random.default_rng(3)
    p0 = jnp.asarray(rng.standard_normal((N, 6)), jnp.float32)

    init = jax.jit(shard_map(
        lambda p: jax.tree_util.tree_map(
            lambda t: jnp.asarray(t)[None], opt.init(p[0])),
        mesh=mesh, in_specs=(P("bf"),), out_specs=P("bf"), check_vma=False))
    st = init(p0)

    jitted = jax.jit(shard_map(
        lambda p, st, g: jax.tree_util.tree_map(
            lambda t: t[None],
            step_fn(p[0], jax.tree_util.tree_map(lambda t: t[0], st), g[0])),
        mesh=mesh, in_specs=(P("bf"),) * 3, out_specs=P("bf"),
        check_vma=False))

    p, want = p0, np.asarray(p0)
    for step in range(4):
        g = jnp.asarray(rng.standard_normal((N, 6)), jnp.float32)
        p, st = jitted(p, st, g)
        w = np.asarray(one_peer_exp2_mixing_matrix(N, step))
        want = w @ (want - 0.1 * np.asarray(g))  # ATC: W (p + update)
    np.testing.assert_allclose(np.asarray(p), want, rtol=1e-5, atol=1e-5)
