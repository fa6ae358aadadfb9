"""Optimizer-layer tests — the analog of the reference's
``test/torch_optimizer_test.py`` convergence smokes (SURVEY.md §4): each rank
minimizes its own quadratic ``||w - c_r||^2 / 2``; the average-loss optimum is
``mean(c_r)``, reached (to O(lr) bias) by decentralized SGD."""

import jax
import jax.numpy as jnp
import numpy as np
import optax
import pytest
from jax import lax
from jax.sharding import PartitionSpec as P

import bluefog_tpu as bf
from bluefog_tpu.optim import (
    CommunicationType,
    DistributedGradientAllreduceOptimizer,
    DistributedHierarchicalNeighborAllreduceOptimizer,
    DistributedNeighborAllreduceOptimizer,
    DistributedWinPutOptimizer,
    decentralized_optimizer,
)
from bluefog_tpu.parallel.api import shard_map
from bluefog_tpu.topology import (
    ExponentialTwoGraph,
    RingGraph,
    one_peer_exponential_two_schedules,
)
from tests._util import walk_jaxpr

N = 8
DIM = 4


def targets():
    """Stacked per-rank targets c_r = r (as DIM-vectors)."""
    return jnp.broadcast_to(jnp.arange(N, dtype=jnp.float32)[:, None], (N, DIM))


def run_quadratic(opt, steps=300, mesh=None, spec=None):
    """Jitted shard_map training loop on per-rank quadratics.  ``mesh`` and
    ``spec`` must be passed together (e.g. ``ctx.hier_mesh`` + its axis-pair
    spec for the two-level mesh); both omitted = flat context mesh."""
    if (mesh is None) != (spec is None):
        raise ValueError("pass mesh and spec together")
    if mesh is None:
        bf.init()
        ctx = bf.get_context()
        mesh, spec = ctx.mesh, P("bf")

    def body(c):
        w0 = jnp.zeros_like(c)
        state = opt.init(w0)

        def step(carry, _):
            w, st = carry
            g = w - c
            upd, st = opt.update(g, st, w)
            return (optax.apply_updates(w, upd), st), None

        (w, _), _ = lax.scan(step, (w0, state), None, length=steps)
        return w

    f = jax.jit(shard_map(body, mesh=mesh, in_specs=(spec,),
                          out_specs=spec, check_vma=False))
    return np.asarray(f(targets()))


def test_neighbor_allreduce_optimizer_converges_atc():
    opt = DistributedNeighborAllreduceOptimizer(
        optax.sgd(0.05), topology=ExponentialTwoGraph(N), axis_name="bf", atc=True
    )
    w = run_quadratic(opt)
    c_bar = 3.5
    assert np.abs(w - c_bar).max() < 0.5          # near the average optimum
    assert (w.max(axis=0) - w.min(axis=0)).max() < 0.4  # near-consensus


def test_neighbor_allreduce_optimizer_converges_awc():
    opt = DistributedNeighborAllreduceOptimizer(
        optax.sgd(0.05), topology=ExponentialTwoGraph(N), axis_name="bf", atc=False
    )
    w = run_quadratic(opt)
    assert np.abs(w - 3.5).max() < 0.5


def test_gradient_allreduce_matches_centralized_sgd():
    """The centralized baseline must track single-node SGD on the averaged
    gradient exactly."""
    lr, steps = 0.1, 50
    opt = DistributedGradientAllreduceOptimizer(optax.sgd(lr), axis_name="bf")
    w = run_quadratic(opt, steps=steps)
    # closed form: w_{t+1} = w_t - lr (w_t - c_bar); all ranks identical
    ref = 3.5 * (1 - (1 - lr) ** steps)
    np.testing.assert_allclose(w, ref, rtol=1e-5)
    np.testing.assert_allclose(w.max(axis=0), w.min(axis=0), rtol=1e-6)


def test_dynamic_one_peer_optimizer():
    scheds = one_peer_exponential_two_schedules(N)
    opt = DistributedNeighborAllreduceOptimizer(
        optax.sgd(0.05), topology=scheds, axis_name="bf", atc=True
    )
    w = run_quadratic(opt)
    assert np.abs(w - 3.5).max() < 0.5


def test_num_steps_per_communication():
    """With k=4 and communication_type=empty-until-comm, the first 3 steps are
    purely local: ranks stay on their own trajectories, then mix."""
    k = 4
    opt = DistributedNeighborAllreduceOptimizer(
        optax.sgd(0.1), topology=ExponentialTwoGraph(N), axis_name="bf",
        atc=True, num_steps_per_communication=k,
    )
    w3 = run_quadratic(opt, steps=3)
    # after 3 local steps: w_r = c_r (1 - 0.9^3), no mixing yet
    ref = np.arange(N)[:, None] * (1 - 0.9**3)
    np.testing.assert_allclose(w3, np.broadcast_to(ref, (N, DIM)), rtol=1e-5)
    w4 = run_quadratic(opt, steps=4)
    spread_local = (np.broadcast_to(np.arange(N)[:, None] * (1 - 0.9**4), (N, DIM))).std()
    assert w4.std() < spread_local  # 4th step mixed

    # steady state carries an O(k*lr*spread) bias vs the k=1 case
    w_long = run_quadratic(opt, steps=400)
    assert np.abs(w_long - 3.5).max() < 1.0


def test_runtime_cadence_matches_static_and_retunes_without_retrace():
    """The local-SGD gate as a TRACED runtime operand
    (``runtime_cadence=True``): (1) at a fixed cadence the trajectory is
    IDENTICAL to the static ``num_steps_per_communication`` form; (2)
    ``set_comm_every`` retunes the gate between steps with zero
    recompilation — the hook a communication controller actuates gossip
    cadence through at round boundaries."""
    from bluefog_tpu.optim import get_comm_every, set_comm_every

    bf.init()
    ctx = bf.get_context()
    mesh, spec = ctx.mesh, P("bf")

    def make(dynamic):
        return DistributedNeighborAllreduceOptimizer(
            optax.sgd(0.1), topology=ExponentialTwoGraph(N),
            axis_name="bf", atc=True, num_steps_per_communication=4,
            runtime_cadence=dynamic)

    w_static = run_quadratic(make(False), steps=12)
    w_dyn = run_quadratic(make(True), steps=12)
    np.testing.assert_allclose(w_dyn, w_static, rtol=1e-5)

    # live retune: k=4 -> k=1 mid-run, same compiled step throughout
    opt = make(True)

    @jax.jit
    def step(w, s):
        def body(v, sv):
            upd, sv2 = opt.update(v - targets()[0] * 0, sv, v)
            return optax.apply_updates(v, upd), sv2
        return shard_map(body, mesh=mesh, in_specs=(spec, P()),
                         out_specs=(spec, P()), check_vma=False)(w, s)

    w = targets()
    st = opt.init(jnp.zeros((DIM,)))
    for _ in range(4):
        w, st = step(w, st)
    cache_pre = step._cache_size()
    comm_rounds_k4 = int(st.comm_count)
    assert get_comm_every(st) == 4
    st = set_comm_every(st, 1)
    for _ in range(4):
        w, st = step(w, st)
    assert step._cache_size() == cache_pre  # no retrace on retune
    # at k=4: one comm round in 4 steps; at k=1: four in four
    assert int(st.comm_count) == comm_rounds_k4 + 4

    # guards
    with pytest.raises(TypeError, match="runtime_cadence"):
        set_comm_every(make(False).init(jnp.zeros((DIM,))), 2)
    with pytest.raises(ValueError, match="gossip communication types"):
        decentralized_optimizer(
            optax.sgd(0.1), None, "bf",
            communication_type=CommunicationType.allreduce,
            runtime_cadence=True)


def test_dynamic_schedules_with_local_steps_cycle_all_phases():
    """Regression: with num_steps_per_communication=k>1 the dynamic schedule
    index must advance per communication *round*, not per step — otherwise
    (count % n_schedules) can stick on one matching and consensus dies."""
    scheds = one_peer_exponential_two_schedules(N)  # 3 phases
    opt = DistributedNeighborAllreduceOptimizer(
        optax.sgd(0.05), topology=scheds, axis_name="bf",
        atc=True, num_steps_per_communication=3,
    )
    w = run_quadratic(opt, steps=600)
    # stuck on one matching -> pair averages [2,3,4,5,...]: spread 3.0 and
    # max error 1.5; correct cycling keeps an O(k*lr) residual well below that
    assert np.abs(w - 3.5).max() < 1.2
    assert (w.max(axis=0) - w.min(axis=0)).max() < 2.0


def test_topology_required_for_neighbor_allreduce():
    with pytest.raises(ValueError, match="requires a topology"):
        decentralized_optimizer(optax.sgd(0.1), None, "bf")
    with pytest.raises(ValueError, match="single static topology"):
        DistributedWinPutOptimizer(
            optax.sgd(0.1),
            topology=one_peer_exponential_two_schedules(N),
            axis_name="bf",
        )


def test_empty_communication_type_is_local_sgd():
    opt = decentralized_optimizer(
        optax.sgd(0.1), None, "bf", communication_type=CommunicationType.empty
    )
    w = run_quadratic(opt, steps=100)
    # each rank converges to its own target
    np.testing.assert_allclose(
        w, np.broadcast_to(np.arange(N)[:, None], (N, DIM)), atol=1e-3
    )


def test_win_put_optimizer_converges():
    opt = DistributedWinPutOptimizer(
        optax.sgd(0.05), topology=ExponentialTwoGraph(N), axis_name="bf"
    )
    w = run_quadratic(opt)
    assert np.abs(w - 3.5).max() < 0.5
    assert (w.max(axis=0) - w.min(axis=0)).max() < 0.4


def test_hierarchical_optimizer_converges():
    opt = DistributedHierarchicalNeighborAllreduceOptimizer(
        optax.sgd(0.05), machine_topology=RingGraph(4), local_size=2,
        axis_name="bf", atc=True,
    )
    w = run_quadratic(opt)
    assert np.abs(w - 3.5).max() < 0.5
    # ATC: the combine runs last, so intra-machine pairs are exactly equal
    for m in range(4):
        np.testing.assert_allclose(w[2 * m], w[2 * m + 1], rtol=1e-6)


def test_hierarchical_optimizer_two_level_mesh_matches_flat():
    """The optimizer's (machine_axis, local_axis) form over ctx.hier_mesh
    produces the same trajectory as the flat form (multi-slice/DCN shape)."""
    flat = DistributedHierarchicalNeighborAllreduceOptimizer(
        optax.sgd(0.05), machine_topology=RingGraph(4), local_size=2,
        axis_name="bf", atc=True)
    w_flat = run_quadratic(flat)

    bf.init(local_size=2, machine_topology=RingGraph(4))
    ctx = bf.get_context()
    two = DistributedHierarchicalNeighborAllreduceOptimizer(
        optax.sgd(0.05), machine_topology=ctx.machine_schedule,
        axis_name=(ctx.machine_axis_name, ctx.local_axis_name), atc=True)
    w_two = run_quadratic(
        two, mesh=ctx.hier_mesh,
        spec=P((ctx.machine_axis_name, ctx.local_axis_name)))
    np.testing.assert_allclose(w_two, w_flat, rtol=1e-5, atol=1e-6)


def test_hierarchical_optimizer_flat_requires_local_size():
    with pytest.raises(ValueError, match="local_size"):
        DistributedHierarchicalNeighborAllreduceOptimizer(
            optax.sgd(0.05), machine_topology=RingGraph(4), axis_name="bf")


def test_adam_base_optimizer():
    """Any optax transformation works as the base (the reference wraps
    arbitrary torch.optim instances)."""
    opt = DistributedNeighborAllreduceOptimizer(
        optax.adam(0.05), topology=ExponentialTwoGraph(N), axis_name="bf", atc=True
    )
    w = run_quadratic(opt, steps=500)
    # adam's per-rank gradient normalization biases the decentralized fixed
    # point (known property); assert tight consensus near the optimum
    assert (w.max(axis=0) - w.min(axis=0)).max() < 0.2
    assert np.abs(w - 3.5).max() < 1.0


class TestGradientTracking:
    """DistributedGradientTrackingOptimizer (DIGing): exact global optimum
    at a CONSTANT step size under heterogeneous data — the property plain
    decentralized SGD provably lacks (it stalls at an O(lr) bias)."""

    def test_exact_convergence_beats_dsgd_bias(self):
        from bluefog_tpu.optim import DistributedGradientTrackingOptimizer

        lr = 0.05
        gt = DistributedGradientTrackingOptimizer(
            optax.sgd(lr), RingGraph(N), "bf")
        dsgd = DistributedNeighborAllreduceOptimizer(
            optax.sgd(lr), topology=RingGraph(N), axis_name="bf", atc=True)
        w_gt = run_quadratic(gt, steps=800)
        w_dsgd = run_quadratic(dsgd, steps=800)
        c_bar = 3.5
        err_gt = np.abs(w_gt - c_bar).max()
        err_dsgd = np.abs(w_dsgd - c_bar).max()
        # GT: exact (machine-precision-ish); DSGD: stuck at its O(lr)
        # bias on the ring with these heterogeneous targets
        assert err_gt < 1e-3, err_gt
        assert err_gt < err_dsgd / 10, (err_gt, err_dsgd)
        # and perfect consensus
        assert (w_gt.max(axis=0) - w_gt.min(axis=0)).max() < 1e-3

    def test_tracking_invariant(self):
        """sum_i y_i == sum_i u_i after every step (the telescoping
        invariant that makes y converge to the average update)."""
        from bluefog_tpu.optim import DistributedGradientTrackingOptimizer

        bf.init()
        ctx = bf.get_context()
        opt = DistributedGradientTrackingOptimizer(
            optax.sgd(0.1), RingGraph(N), "bf")

        def body(c):
            w = jnp.zeros_like(c)
            st = opt.init(w)
            sums = []
            for _ in range(3):
                g = w - c
                upd, st = opt.update(g, st, w)
                w = optax.apply_updates(w, upd)
                sums.append(jnp.stack([
                    lax.psum(st.y, "bf").sum(),
                    lax.psum(st.prev_g, "bf").sum()]))
            return jnp.stack(sums)

        f = jax.jit(shard_map(body, mesh=ctx.mesh, in_specs=(P("bf"),),
                              out_specs=P(), check_vma=False))
        sums = np.asarray(f(targets()))
        np.testing.assert_allclose(sums[:, 0], sums[:, 1], rtol=1e-5)

    def test_composes_with_momentum(self):
        from bluefog_tpu.optim import DistributedGradientTrackingOptimizer

        opt = DistributedGradientTrackingOptimizer(
            optax.sgd(0.03, momentum=0.9), RingGraph(N), "bf")
        w = run_quadratic(opt, steps=800)
        assert np.abs(w - 3.5).max() < 1e-2

    def test_time_varying_topology_rejected(self):
        from bluefog_tpu.optim import DistributedGradientTrackingOptimizer
        from bluefog_tpu.topology import one_peer_exponential_two_schedules

        with pytest.raises(ValueError, match="single static"):
            DistributedGradientTrackingOptimizer(
                optax.sgd(0.1), one_peer_exponential_two_schedules(N), "bf")


class TestExactDiffusion:
    """DistributedExactDiffusionOptimizer (D2): bias-free like gradient
    tracking but with ONE gossip per step instead of two."""

    def test_exact_convergence_beats_dsgd_bias(self):
        from bluefog_tpu.optim import DistributedExactDiffusionOptimizer

        lr = 0.05
        ed = DistributedExactDiffusionOptimizer(
            optax.sgd(lr), RingGraph(N), "bf")
        dsgd = DistributedNeighborAllreduceOptimizer(
            optax.sgd(lr), topology=RingGraph(N), axis_name="bf", atc=True)
        w_ed = run_quadratic(ed, steps=800)
        w_dsgd = run_quadratic(dsgd, steps=800)
        err_ed = np.abs(w_ed - 3.5).max()
        err_dsgd = np.abs(w_dsgd - 3.5).max()
        assert err_ed < 1e-3, err_ed
        assert err_ed < err_dsgd / 10, (err_ed, err_dsgd)
        assert (w_ed.max(axis=0) - w_ed.min(axis=0)).max() < 1e-3

    def test_asymmetric_topology_rejected(self):
        from bluefog_tpu.optim import DistributedExactDiffusionOptimizer

        with pytest.raises(ValueError, match="symmetric"):
            DistributedExactDiffusionOptimizer(
                optax.sgd(0.1), ExponentialTwoGraph(N), "bf")

    def test_composes_with_momentum(self):
        from bluefog_tpu.optim import DistributedExactDiffusionOptimizer

        opt = DistributedExactDiffusionOptimizer(
            optax.sgd(0.03, momentum=0.9), RingGraph(N), "bf")
        w = run_quadratic(opt, steps=800)
        assert np.abs(w - 3.5).max() < 1e-2

    def test_bf16_params_state_stable_and_converges(self):
        """Two regressions in one run (ADVICE r4 medium + the bug its fix
        exposed): (a) the state pytree's dtypes must be step-invariant so
        lax.scan carries and checkpoint templates hold; (b) exact
        diffusion's implicit dual does not survive bf16 param quantization
        — without the f32 master-weight state, bf16 runs freeze at a
        spurious consensus (measured: 8.0 for targets averaging 3.5)."""
        from bluefog_tpu.optim import DistributedExactDiffusionOptimizer

        opt = DistributedExactDiffusionOptimizer(
            optax.sgd(0.05), RingGraph(N), "bf")
        bf.init()
        ctx = bf.get_context()

        def body(c):
            w0 = jnp.zeros_like(c)
            st0 = opt.init(w0)

            def step(carry, _):
                w, st = carry
                upd, st = opt.update((w - c).astype(w.dtype), st, w)
                return (optax.apply_updates(w, upd), st), None

            (w, st), _ = lax.scan(step, (w0, st0), None, length=400)
            # invariant the scan itself enforces: post-step state matches
            # the init template's dtypes
            chex = jax.tree_util.tree_map(
                lambda a, b: jnp.asarray(a.dtype == b.dtype), st0, st)
            return w, chex

        f = jax.jit(shard_map(
            body, mesh=ctx.mesh, in_specs=(P("bf"),),
            out_specs=(P("bf"), P()), check_vma=False))
        w, same = f(targets().astype(jnp.bfloat16))
        assert all(bool(x) for x in jax.tree_util.tree_leaves(same))
        w = np.asarray(w, np.float32)
        # bf16 ulp at 3.5 is 0.03125; allow a few ulps of combine rounding
        assert np.abs(w - 3.5).max() < 0.1, w


def _gt():
    from bluefog_tpu.optim import DistributedGradientTrackingOptimizer
    return DistributedGradientTrackingOptimizer(
        optax.sgd(0.05), RingGraph(N), "bf")


def _ed():
    from bluefog_tpu.optim import DistributedExactDiffusionOptimizer
    return DistributedExactDiffusionOptimizer(
        optax.sgd(0.05), RingGraph(N), "bf")


def _dnao(topology, **kw):
    return DistributedNeighborAllreduceOptimizer(
        optax.sgd(0.05), topology=topology, axis_name="bf", **kw)


# (optimizer, mixes an update, the slots of its schedules added up)
GOSSIP_OPTIMIZERS = {
    "exp2_awc": (lambda: _dnao(ExponentialTwoGraph(N)), 1, 3),
    "exp2_atc": (lambda: _dnao(ExponentialTwoGraph(N), atc=True), 1, 3),
    # one-peer exponential-2: three phases of one slot, a lax.switch branch each
    "one_peer_awc": (
        lambda: _dnao(one_peer_exponential_two_schedules(N)), 1, 3),
    "one_peer_atc": (
        lambda: _dnao(one_peer_exponential_two_schedules(N), atc=True), 1, 3),
    # the y-mix and the params-mix: two exchanges, independent dataflow
    "gradient_tracking": (_gt, 2, 2),
    "exact_diffusion": (_ed, 1, 2),
    # the one entry point that still takes the keyword (chipbench/cell.py
    # passes it): both values it accepts give the same exchange
    "backend_auto": (lambda: decentralized_optimizer(
        optax.sgd(0.05), RingGraph(N), "bf", backend="auto"), 1, 2),
    "backend_xla_atc": (lambda: decentralized_optimizer(
        optax.sgd(0.05), RingGraph(N), "bf", atc=True, backend="xla"), 1, 2),
}


@pytest.mark.parametrize("name", GOSSIP_OPTIMIZERS)
def test_an_update_is_collective_permutes_and_no_kernel(name, monkeypatch):
    """Gossip has one lowering (PR 47): an ``update`` holds ``slots x
    pieces`` ppermutes a mix and no ``pallas_call``, whatever the optimizer.
    Gradient tracking's two mixes cannot interfere, being dataflow: that is
    what their separate collective-id ranges protected under the kernels."""
    import functools

    from bluefog_tpu.ops import collectives as C

    # 3,000 bytes: w1 (4,096) ships alone, the rest in one buffer -> 2 pieces
    monkeypatch.setattr(C, "fuse_apply", functools.partial(
        C.fuse_apply, threshold_bytes=3000))
    make, mixes, slots = GOSSIP_OPTIMIZERS[name]
    opt = make()
    ctx = bf.init()
    params = {"w1": jnp.ones((N, 16, 64)), "b1": jnp.ones((N, 64)),
              "w2": jnp.ones((N, 64, 8))}

    def update(p_blk):
        p = jax.tree_util.tree_map(lambda t: t[0], p_blk)
        updates, _ = opt.update(p, opt.init(p), p)
        return jax.tree_util.tree_map(lambda t: t[None], updates)

    fn = shard_map(update, mesh=ctx.mesh, in_specs=(P("bf"),),
                   out_specs=P("bf"), check_vma=False)
    found = [eqn.primitive.name for eqn, _ in walk_jaxpr(
        jax.make_jaxpr(fn)(params).jaxpr)]
    assert found.count("ppermute") == mixes * slots * 2
    assert "pallas_call" not in found
