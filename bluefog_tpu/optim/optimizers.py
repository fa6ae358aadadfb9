"""Decentralized distributed optimizers (optax-compatible).

Reference parity: ``bluefog/torch/optimizers.py`` (upstream-relative).  The
reference wraps ``torch.optim`` with per-parameter backward hooks that launch
non-blocking communication overlapping backprop, then ``step()`` synchronizes
and combines (SURVEY.md §3.3).  The TPU-native translation: the communication
is part of the jitted SPMD train step, and **XLA's latency-hiding scheduler
provides the overlap** the reference gets from its background thread — the
gossip ``ppermute``s have no data dependency on the backward pass in AWC
("adapt-with-combine") mode, so the ICI DMA engines can move them while the
MXU computes.  What the scheduler makes of that freedom on a v5e (PERF.md,
PR 31): it fuses each leaf's mix into that leaf's weight-gradient fusion,
moves those fusions behind the backward pass and runs the transfers beside
them, five in flight at a time.  That hides the transfers of all but the
largest leaves (what the exchange adds to a four-rank GPT-2-small step fell
from 35.6 to 21.6 ms); the two 154 MB leaves' transfers, which wait for
one giant fusion each, and the slower fusions are what is left.

Modes (reference: adapt_then_combine / adapt_with_combine):

- **ATC**: ``p' = W (p + update)`` — combine after the local step; gossip
  depends on the fresh update (sequential, tighter consensus).
- **AWC**: ``p' = W p + update`` — gossip of the *pre-step* params has no
  dependency on the gradient computation, so communication and backprop
  overlap.  This is the reference's default overlap contract.

Everything is an ``optax.GradientTransformation`` operating *inside* the SPMD
context (``shard_map`` over the gossip axis): params/grads are the per-rank
local values.  ``num_steps_per_communication=k`` runs ``k-1`` purely local
steps between gossip rounds (local-SGD flavor), via ``lax.cond`` on a counter
carried in the optimizer state.
"""

from __future__ import annotations

import enum
from typing import Any, NamedTuple, Optional, Sequence, Union

import jax
import jax.numpy as jnp
import numpy as np
import optax
from jax import lax

from bluefog_tpu.blackbox import recorder as _bb
from bluefog_tpu.metrics import comm as _mt
from bluefog_tpu.metrics import registry as _mreg
from bluefog_tpu.ops import collectives as C
from bluefog_tpu.ops import windows as W
from bluefog_tpu.topology.graphs import Topology
from bluefog_tpu.topology.schedule import GossipSchedule, build_schedule

__all__ = [
    "CommunicationType",
    "decentralized_optimizer",
    "optimizer_state_specs",
    "shard_optimizer_state",
    "set_comm_every",
    "get_comm_every",
    "DistributedNeighborAllreduceOptimizer",
    "DistributedGradientAllreduceOptimizer",
    "DistributedHierarchicalNeighborAllreduceOptimizer",
    "DistributedWinPutOptimizer",
    "DistributedChocoSGDOptimizer",
    "DistributedGradientTrackingOptimizer",
    "DistributedExactDiffusionOptimizer",
]


class CommunicationType(enum.Enum):
    """Reference ``optimizers.CommunicationType`` (upstream)."""

    neighbor_allreduce = "neighbor.allreduce"
    hierarchical_neighbor_allreduce = "hierarchical.neighbor.allreduce"
    allreduce = "allreduce"
    win_put = "win.put"
    empty = "empty"


class _DecentralizedState(NamedTuple):
    base_state: Any
    count: jnp.ndarray       # update counter (drives num_steps_per_communication)
    comm_count: jnp.ndarray  # communication-round counter (drives dynamic schedules)


class _DecentralizedStateDyn(NamedTuple):
    """State of ``runtime_cadence=True`` optimizers: the local-SGD gate
    rides along as a TRACED int32 operand (``comm_every``), so a
    runtime controller retunes the gossip cadence between steps by
    rewriting one scalar in the state — zero recompilation, which is
    what lets a :class:`bluefog_tpu.control.CommPlan`'s cadence land on
    a jitted SPMD step at a round boundary."""

    base_state: Any
    count: jnp.ndarray
    comm_count: jnp.ndarray
    comm_every: jnp.ndarray  # int32 scalar: gossip every k-th step


def set_comm_every(state, k):
    """Retune a ``runtime_cadence=True`` optimizer's local-SGD gate to
    ``k`` (gossip every k-th step; 1 = every step).  Returns the updated
    state — pure data, same pytree structure, so the next jitted
    ``update`` call reuses the compiled program.  Round-boundary
    actuation: call between steps, never inside one."""
    if not isinstance(state, _DecentralizedStateDyn):
        raise TypeError(
            "set_comm_every needs a runtime_cadence=True optimizer state "
            f"(got {type(state).__name__}; pass runtime_cadence=True to "
            "decentralized_optimizer)")
    # np.int32 -> a STRONG-typed scalar aval identical to init's, and
    # device_put onto the OLD leaf's sharding — a retune must never
    # force the jitted step to re-lower (a fresh uncommitted scalar
    # where the carried state leaf was replicated over the mesh would)
    new = jnp.asarray(np.int32(max(int(k), 1)))
    old = state.comm_every
    if isinstance(old, jax.Array):
        try:
            new = jax.device_put(new, old.sharding)
        except (AttributeError, ValueError):
            pass  # abstract/traced state (inside jit): aval match suffices
    return state._replace(comm_every=new)


def get_comm_every(state) -> int:
    """The current local-SGD gate of a ``runtime_cadence=True`` state."""
    if not isinstance(state, _DecentralizedStateDyn):
        raise TypeError(
            "get_comm_every needs a runtime_cadence=True optimizer state "
            f"(got {type(state).__name__})")
    return int(state.comm_every)


def optimizer_state_specs(rule_table, params, opt_or_state, *,
                          abstract: bool = True):
    """Spec tree for a decentralized optimizer's state, derived from the
    SAME :class:`~bluefog_tpu.sharding.RuleTable` that shards ``params``
    — the state-tree rule derivation of the unified sharding subsystem.

    ``opt_or_state`` is either an ``optax.GradientTransformation`` (its
    state is built with ``jax.eval_shape`` over ``init`` — nothing is
    materialized) or an already-built state tree.  Moment leaves
    (``mu``/``nu``, gradient-tracking trackers, the wrapped
    ``base_state`` of :func:`decentralized_optimizer`) inherit the spec
    of the parameter they shadow by tree-path-suffix + shape matching,
    so **changing one rule re-shards the param AND its optimizer state
    consistently** (the acceptance invariant ``tests/test_sharding.py``
    pins); scalar counters (``count``, ``comm_count``, ``comm_every``)
    resolve replicated."""
    from bluefog_tpu.sharding.rules import opt_state_specs

    state = opt_or_state
    if hasattr(opt_or_state, "init"):
        if abstract:
            state = jax.eval_shape(opt_or_state.init, params)
        else:
            state = opt_or_state.init(params)
    return opt_state_specs(rule_table, params, state)


def shard_optimizer_state(rule_table, params, state, mesh):
    """Place an optimizer state tree onto ``mesh`` under the rule
    table's derived specs (:func:`optimizer_state_specs`) — the
    checkpoint-load / cold-start boundary, using the same
    ``make_shard_and_gather_fns`` machinery as the params."""
    from bluefog_tpu.sharding.apply import make_shard_and_gather_fns

    specs = optimizer_state_specs(rule_table, params, state)
    shard_fns, _ = make_shard_and_gather_fns(specs, mesh)
    return jax.tree_util.tree_map(lambda fn, leaf: fn(leaf),
                                  shard_fns, state)


def _as_schedules(topology) -> Sequence[GossipSchedule]:
    if isinstance(topology, (Topology, GossipSchedule)):
        topology = [topology]
    return [t if isinstance(t, GossipSchedule) else build_schedule(t) for t in topology]


def _gossip(params, scheds, count, axis_name):
    if len(scheds) == 1:
        return C.neighbor_allreduce(params, scheds[0], axis_name)
    return C.neighbor_allreduce_dynamic(params, scheds, count, axis_name)


def decentralized_optimizer(
    base: optax.GradientTransformation,
    topology: Union[Topology, GossipSchedule, Sequence, None],
    axis_name: Union[str, Sequence[str]],
    *,
    communication_type: CommunicationType = CommunicationType.neighbor_allreduce,
    atc: bool = False,
    num_steps_per_communication: int = 1,
    local_size: int = 1,
    machine_topology=None,
    backend: str = "auto",
    max_rotations: Optional[int] = None,
    runtime_cadence: bool = False,
) -> optax.GradientTransformation:
    """Wrap ``base`` so each update also performs decentralized averaging.

    Args:
      topology: static topology/schedule; a *sequence* of them for periodic
        time-varying gossip (cycled by the step counter, e.g.
        ``one_peer_exponential_two_schedules(n)``); or a **callable**
        ``step -> (n, n) mixing matrix`` (traced step) for aperiodic gossip —
        arbitrary edge sets every round with zero recompilation
        (e.g. ``topology.one_peer_exp2_mixing_matrix``).
      axis_name: gossip mesh axis (call inside ``shard_map``); the
        hierarchical mode also accepts the ``(machine_axis, local_axis)``
        pair of a two-level mesh (``ctx.hier_mesh`` — the multi-slice/DCN
        form, dispatching to ``hierarchical_neighbor_allreduce_2d``).
      communication_type: which combine to run (reference enum).
      atc: adapt-with-combine when False (the reference's default):
        ``W p + update``, where the exchange of ``p`` depends on nothing the
        step computes, so XLA's asynchronous collective-permutes run it
        beside the weight-gradient and optimizer fusions.  Adapt-then-
        combine when True: ``W (p + update)``, a true dependency of every
        transfer on its leaf's update — nothing can hide that exchange
        behind the step's own compute, and it keeps its order.
      num_steps_per_communication: gossip every k-th step (local SGD).
      local_size / machine_topology: for the hierarchical mode.
      backend: does nothing.  Gossip has one transport from PR 47 on
        (``ops/collectives.py::neighbor_allreduce``); the keyword stays,
        accepting ``'auto'`` and ``'xla'``, only until ``chipbench/cell.py``
        stops passing it (ROADMAP D6a).
      max_rotations: program-size cap for the CALLABLE-topology (aperiodic)
        mode at pod scale — D runtime-shift rotation slots instead of the
        full n-1 decomposition; exceeding D active rotations NaN-poisons
        the output (see
        :func:`bluefog_tpu.ops.collectives.neighbor_allreduce_aperiodic`).
      runtime_cadence: make the local-SGD gate a TRACED runtime operand:
        the state carries ``comm_every`` (initialized from
        ``num_steps_per_communication``) and :func:`set_comm_every`
        retunes it between steps with ZERO recompilation — the hook a
        runtime communication controller (:mod:`bluefog_tpu.control`)
        actuates gossip cadence through at round boundaries.  The gate
        is then always a ``lax.cond`` (even at cadence 1), so the
        compiled program differs from the static form; gossip-mode
        communication types only.

    Returns an ``optax.GradientTransformation`` whose ``update`` REQUIRES
    ``params``; the returned updates fold the communication in, so plain
    ``optax.apply_updates(params, updates)`` yields the combined params.
    """
    if backend not in ("auto", "xla"):
        raise ValueError(
            f"unknown backend {backend!r}: gossip runs on collective-permutes "
            "alone; pass 'auto' or 'xla', or leave the keyword out")
    ct = communication_type
    scheds = None
    matrix_fn = None
    if ct == CommunicationType.neighbor_allreduce:
        if topology is None:
            raise ValueError(
                "communication_type=neighbor_allreduce requires a topology"
            )
        if callable(topology) and not isinstance(
                topology, (Topology, GossipSchedule)):
            # aperiodic mode: `topology(step) -> (n, n) mixing matrix` with a
            # traced step — any edge set every round, one compile
            # (ops.collectives.neighbor_allreduce_aperiodic)
            matrix_fn = topology
        else:
            scheds = _as_schedules(topology)
    if max_rotations is not None and matrix_fn is None:
        # silently ignoring the cap would let the full uncapped program
        # build at pod scale — the exact blowup the parameter exists to stop
        raise ValueError(
            "max_rotations applies only to the callable-topology "
            "(aperiodic) mode; static topologies/schedules compile one "
            "ppermute per edge slot already")
    mscheds = None
    if ct == CommunicationType.hierarchical_neighbor_allreduce:
        if machine_topology is None:
            raise ValueError("hierarchical mode needs machine_topology")
        mscheds = _as_schedules(machine_topology)
        if len(mscheds) != 1:
            raise ValueError("hierarchical mode takes a single machine topology")
    if runtime_cadence and ct in (CommunicationType.allreduce,
                                  CommunicationType.empty):
        raise ValueError(
            "runtime_cadence applies to the gossip communication types "
            "(there is no local-SGD gate to retune on "
            f"{ct.value!r})")

    def init_fn(params):
        if runtime_cadence:
            return _DecentralizedStateDyn(
                base.init(params), jnp.zeros((), jnp.int32),
                jnp.zeros((), jnp.int32),
                jnp.asarray(max(1, num_steps_per_communication), jnp.int32))
        return _DecentralizedState(
            base.init(params), jnp.zeros((), jnp.int32), jnp.zeros((), jnp.int32)
        )

    def _combine(params, count):
        # fuse_apply: the small leaves in flat buffers of about 4 MiB → a few
        # ppermutes/psums per slot instead of one per parameter leaf
        # (reference fusion-buffer parity), none much larger than a large leaf
        if ct == CommunicationType.neighbor_allreduce:
            if matrix_fn is not None:
                return C.fuse_apply(
                    lambda t: C.neighbor_allreduce_aperiodic(
                        t, matrix_fn(count), axis_name,
                        max_rotations=max_rotations), params)
            return C.fuse_apply(
                lambda t: _gossip(t, scheds, count, axis_name), params)
        if ct == CommunicationType.hierarchical_neighbor_allreduce:
            if isinstance(axis_name, (tuple, list)):
                # two-level (machine, local) mesh: the multi-slice form —
                # axis_name = (machine_axis, local_axis)
                m_ax, l_ax = axis_name
                return C.fuse_apply(
                    lambda t: C.hierarchical_neighbor_allreduce_2d(
                        t, mscheds[0], machine_axis=m_ax, local_axis=l_ax),
                    params)
            return C.fuse_apply(
                lambda t: C.hierarchical_neighbor_allreduce(
                    t, mscheds[0], axis_name, local_size=local_size), params)
        # allreduce/empty never reach here: comm_step short-circuits them
        # (allreduce averages grads in update_fn instead of combining params)
        return params

    def update_fn(grads, state, params=None):
        if params is None:
            raise ValueError("decentralized_optimizer requires params in update()")
        if ct == CommunicationType.allreduce:
            # centralized baseline: average gradients, plain step (fused)
            grads = C.fuse_apply(
                lambda t: C.allreduce(t, axis_name, average=True), grads)
        # Phase scopes (bf.<layer>.<phase>, docs/metrics.md "Reading a device
        # trace"): trace-time metadata only, leaf-level and disjoint.
        with jax.named_scope("bf.optim.base_update"):
            updates, base_state = base.update(grads, state.base_state, params)

        k = num_steps_per_communication

        def apply(p):
            with jax.named_scope("bf.optim.apply"):
                return optax.apply_updates(p, updates)

        def comm_step(p):
            if ct == CommunicationType.allreduce or ct == CommunicationType.empty:
                new_p = apply(p)
            elif atc:
                new_p = _combine(apply(p), state.comm_count)
            else:  # AWC: gossip(p) has no dependency on updates -> overlaps
                mixed = _combine(p, state.comm_count)
                new_p = apply(mixed)
            return new_p

        local_step = apply

        if runtime_cadence:
            # the gate is a TRACED operand: (count+1) % comm_every == 0
            # with comm_every read from the state, so set_comm_every
            # retunes the cadence between steps without recompiling
            do_comm = (state.count + 1) % jnp.maximum(
                state.comm_every, 1) == 0
            new_params = lax.cond(do_comm, comm_step, local_step, params)
            new_comm_count = state.comm_count + do_comm.astype(jnp.int32)
            comm_inc = do_comm.astype(jnp.float32)
        elif k <= 1 or ct in (CommunicationType.allreduce,
                              CommunicationType.empty):
            new_params = comm_step(params)
            new_comm_count = state.comm_count + 1
            comm_inc = 1.0
        else:
            do_comm = (state.count + 1) % k == 0
            new_params = lax.cond(do_comm, comm_step, local_step, params)
            new_comm_count = state.comm_count + do_comm.astype(jnp.int32)
            comm_inc = do_comm.astype(jnp.float32)
        new_count = state.count + 1

        # express as optax updates so callers use apply_updates as usual
        with jax.named_scope("bf.optim.as_updates"):
            new_updates = jax.tree_util.tree_map(
                lambda np_, p: (np_.astype(jnp.float32) - p.astype(jnp.float32)).astype(p.dtype),
                new_params, params,
            )
        if _mreg.current() is not None:
            # per-execution step / communication-round counters (comm_inc
            # is the traced local-SGD gate, so skipped rounds don't count);
            # trace-time gated — zero HLO when metrics are off
            new_updates = _mt.count(
                new_updates,
                [("bf_optimizer_steps_total", 1.0),
                 ("bf_optimizer_comm_rounds_total", comm_inc)],
                {"opt": ct.value, "atc": str(bool(atc)).lower()})
        # flight-recorder step event with the TRACED step counter
        # (identity unless BLUEFOG_TPU_BLACKBOX=jit at trace time): a hang
        # dump then shows the last optimizer update each rank completed
        new_updates = _bb.traced_event(
            new_updates, "optimizer_step", fields={"opt": ct.value},
            traced={"step": state.count.astype(jnp.float32)},
            axis_name=axis_name if isinstance(axis_name, str) else None)
        if runtime_cadence:
            return new_updates, _DecentralizedStateDyn(
                base_state, new_count, new_comm_count, state.comm_every)
        return new_updates, _DecentralizedState(base_state, new_count, new_comm_count)

    return optax.GradientTransformation(init_fn, update_fn)


# ---------------------------------------------------------------------------
# Reference-named factories
# ---------------------------------------------------------------------------


def DistributedNeighborAllreduceOptimizer(
    base: optax.GradientTransformation,
    *,
    topology,
    axis_name: str,
    atc: bool = False,
    num_steps_per_communication: int = 1,
    max_rotations: Optional[int] = None,
    runtime_cadence: bool = False,
) -> optax.GradientTransformation:
    """Reference ``bf.DistributedNeighborAllreduceOptimizer`` (confirmed in
    BASELINE.json): decentralized gossip averaging of parameters each step."""
    return decentralized_optimizer(
        base, topology, axis_name,
        communication_type=CommunicationType.neighbor_allreduce,
        atc=atc, num_steps_per_communication=num_steps_per_communication,
        max_rotations=max_rotations, runtime_cadence=runtime_cadence,
    )


def DistributedGradientAllreduceOptimizer(
    base: optax.GradientTransformation, *, axis_name: str
) -> optax.GradientTransformation:
    """Reference ``bf.DistributedGradientAllreduceOptimizer`` — the
    Horovod-style centralized baseline: grads are globally averaged."""
    return decentralized_optimizer(
        base, None, axis_name, communication_type=CommunicationType.allreduce,
    )


def DistributedHierarchicalNeighborAllreduceOptimizer(
    base: optax.GradientTransformation,
    *,
    machine_topology,
    local_size: Optional[int] = None,
    axis_name,
    atc: bool = False,
    num_steps_per_communication: int = 1,
) -> optax.GradientTransformation:
    """Reference ``bf.DistributedHierarchicalNeighborAllreduceOptimizer``:
    intra-machine exact average + machine-level gossip each step.

    ``axis_name`` is either the flat gossip axis (then ``local_size`` is
    required — machines are ``axis_index_groups``) or the
    ``(machine_axis, local_axis)`` pair of a two-level mesh
    (``ctx.hier_mesh`` — the multi-slice/DCN form; ``local_size`` is implied
    by the mesh and may be omitted)."""
    if isinstance(axis_name, (tuple, list)):
        if len(axis_name) != 2:
            raise ValueError(
                f"two-level axis_name must be (machine_axis, local_axis), "
                f"got {axis_name!r}")
    elif local_size is None:
        raise ValueError("flat-mesh hierarchical mode requires local_size")
    return decentralized_optimizer(
        base, None, axis_name,
        communication_type=CommunicationType.hierarchical_neighbor_allreduce,
        atc=atc, num_steps_per_communication=num_steps_per_communication,
        local_size=local_size, machine_topology=machine_topology,
    )


class _WinPutState(NamedTuple):
    base_state: Any
    win: W.WindowState
    count: jnp.ndarray


def DistributedWinPutOptimizer(
    base: optax.GradientTransformation,
    *,
    topology,
    axis_name: str,
    num_steps_per_communication: int = 1,
    async_: bool = False,
    lr: Optional[float] = None,
):
    """Reference ``bf.DistributedWinPutOptimizer`` (confirmed in
    BASELINE.json): after the local step, push parameters to out-neighbors via
    ``win_put`` and merge landed neighbor params via ``win_update`` — the
    one-sided, barrier-free variant (SURVEY.md §3.4).

    Two modes:

    - ``async_=False`` (default): an ``optax.GradientTransformation`` whose
      window dataflow compiles into the SPMD step (the MPI window memory of
      the reference becomes window state carried inside the optimizer state,
      allocated by ``init`` from the parameter shapes).  Same program counter
      on every rank — the one-sidedness is dataflow, not timing.
    - ``async_=True``: returns an
      :class:`~bluefog_tpu.runtime.async_windows.AsyncWinPutOptimizer` —
      rank loops on the host runtime stepping at **independent rates** over
      real model parameters, depositing into the native passive-target
      window table with no barrier anywhere (the reference's actual
      execution model over MPI).  ``base`` is ignored in this mode (the
      subgradient-push update is plain SGD on the de-biased iterate); pass
      the learning rate via ``lr``.  The async mode's rank loops are
      THREADS of this process; for the reference's literal deployment shape
      — one OS process per rank, windows in shared memory or served over
      TCP across hosts — drive
      :func:`~bluefog_tpu.runtime.async_windows.run_async_dsgd_rank` from
      your per-process launcher instead (``examples/async_dsgd_mp.py``).
    """
    if async_:
        from bluefog_tpu.runtime.async_windows import AsyncWinPutOptimizer

        topo = topology
        if not isinstance(topo, Topology):
            raise TypeError(
                "async_=True requires a Topology (host rank loops, not a "
                f"compiled schedule); got {type(topology)}")
        if lr is None:
            # `base`'s learning rate lives in optax closures and cannot be
            # recovered — a silent default would diverge from what the sync
            # call site requested, so demand it explicitly
            raise ValueError(
                "async_=True applies plain SGD on the de-biased iterate "
                "(base is unused); pass the learning rate via lr=")
        if num_steps_per_communication != 1:
            raise ValueError(
                "async_=True has no synchronous communication rounds; "
                "num_steps_per_communication does not apply")
        return AsyncWinPutOptimizer(topo, lr=lr)

    if lr is not None:
        raise ValueError(
            "lr= applies only to async_=True (the sync path takes its "
            "learning rate from `base`); remove lr= or set async_=True")
    scheds = _as_schedules(topology)
    if len(scheds) != 1:
        raise ValueError(
            "DistributedWinPutOptimizer takes a single static topology "
            "(dynamic schedule lists are only supported by the "
            "neighbor_allreduce optimizer)"
        )
    sched = scheds[0]

    def init_fn(params):
        win = W.win_create(params, sched, axis_name, name="winput_opt")
        return _WinPutState(base.init(params), win, jnp.zeros((), jnp.int32))

    def update_fn(grads, state, params=None):
        if params is None:
            raise ValueError("DistributedWinPutOptimizer requires params in update()")
        updates, base_state = base.update(grads, state.base_state, params)
        stepped = optax.apply_updates(params, updates)

        k = num_steps_per_communication

        def comm(args):
            p, win = args
            win = W.win_sync(win, p)            # publish my new params
            win = W.win_put(win, p, axis_name)  # push to out-neighbors' buffers
            merged, win = W.win_update(win, axis_name)  # weighted merge
            return merged, win

        def local(args):
            p, win = args
            return p, win

        if k <= 1:
            new_p, new_win = comm((stepped, state.win))
        else:
            new_p, new_win = lax.cond(
                (state.count + 1) % k == 0, comm, local, (stepped, state.win)
            )

        new_updates = jax.tree_util.tree_map(
            lambda np_, p: (np_.astype(jnp.float32) - p.astype(jnp.float32)).astype(p.dtype),
            new_p, params,
        )
        return new_updates, _WinPutState(base_state, new_win, state.count + 1)

    return optax.GradientTransformation(init_fn, update_fn)


# ---------------------------------------------------------------------------
# Compressed decentralized SGD (CHOCO-SGD) — beyond-reference surface
# ---------------------------------------------------------------------------


class _ChocoState(NamedTuple):
    base_state: Any
    choco: Any  # ops.compression.ChocoState (mirror copies + round counter)


def DistributedChocoSGDOptimizer(
    base: optax.GradientTransformation,
    topology: Union[Topology, GossipSchedule],
    axis_name: Union[str, Sequence[str]],
    *,
    compressor=None,
    gamma: Optional[float] = None,
    key=None,
) -> optax.GradientTransformation:
    """CHOCO-SGD: local step, then COMPRESSED gossip that still reaches
    exact consensus (Koloskova et al., ICML 2019 — no reference counterpart:
    upstream's wire is always full-precision; SURVEY.md §2.4).

    The wire per round carries only each leaf's compressed innovation —
    e.g. ``compression.random_block_k(0.1)`` ships 10% of the bytes with no
    index overhead (shared-seed masks).  Requires a SYMMETRIC mixing matrix
    (ring/grid/full — checked at setup time, loudly); ``gamma`` is the
    consensus step size, which must SHRINK as compression gets more
    aggressive or the recursion diverges (measured on the 8-rank ring:
    ratio 0.25 converges at γ = 0.3 and blows up at γ = 0.5).  The default
    ``gamma=None`` uses the compressor's contraction quality δ (= its kept
    ratio) — stable in every measured configuration; larger hand-tuned
    values buy faster consensus.

    State carries mirror copies of each in-neighbor's public params (one per
    schedule slot), so memory is (num_slots + 1) × params — the standard
    CHOCO trade: memory for wire bytes.

    Hierarchical (multi-slice/DCN) form: pass
    ``axis_name=(machine_axis, local_axis)`` with ``topology`` = the
    MACHINE topology — exact pmean inside each machine over ICI, compressed
    CHOCO across machines where the wire is DCN and compression matters
    most (:func:`bluefog_tpu.ops.compression.hierarchical_choco_gossip`).
    """
    from bluefog_tpu.ops import compression as CP

    sched = topology if isinstance(topology, GossipSchedule) \
        else build_schedule(topology)
    mix = sched.mixing_matrix()
    if not np.allclose(mix, mix.T, atol=1e-8):
        raise ValueError(
            "CHOCO-SGD requires a symmetric mixing matrix for exact "
            "consensus (ring/grid/full); got an asymmetric one "
            f"(max |W - W^T| = {np.abs(mix - mix.T).max():.3g}).  The "
            "directed exp2 graph is the usual culprit — use RingGraph / "
            "MeshGrid2DGraph / FullyConnectedGraph")
    comp = compressor if compressor is not None else CP.random_block_k(0.1)
    if gamma is None:
        gamma = float(comp.delta)
    hier = isinstance(axis_name, (tuple, list))
    if hier and len(axis_name) != 2:
        raise ValueError("hierarchical axis_name must be "
                         "(machine_axis, local_axis)")

    def init_fn(params):
        return _ChocoState(base.init(params), CP.choco_init(params, sched))

    def update_fn(grads, state, params=None):
        if params is None:
            raise ValueError("DistributedChocoSGDOptimizer requires params "
                             "in update()")
        updates, base_state = base.update(grads, state.base_state, params)
        stepped = optax.apply_updates(params, updates)
        if hier:
            m_ax, l_ax = axis_name
            new_p, choco = CP.hierarchical_choco_gossip(
                stepped, state.choco, sched, m_ax, l_ax,
                compressor=comp, gamma=gamma, key=key)
        else:
            new_p, choco = CP.choco_gossip(
                stepped, state.choco, sched, axis_name,
                compressor=comp, gamma=gamma, key=key)
        new_updates = jax.tree_util.tree_map(
            lambda np_, p: (np_.astype(jnp.float32)
                            - p.astype(jnp.float32)).astype(p.dtype),
            new_p, params,
        )
        return new_updates, _ChocoState(base_state, choco)

    return optax.GradientTransformation(init_fn, update_fn)


# ---------------------------------------------------------------------------
# Gradient tracking (DIGing) — beyond-reference optimizer surface
# ---------------------------------------------------------------------------


class _GTState(NamedTuple):
    base_state: Any
    y: Any        # tracking variable: running estimate of the GLOBAL avg grad
    prev_g: Any   # last step's local (post-base-transform) update direction


def DistributedGradientTrackingOptimizer(
    base: optax.GradientTransformation,
    topology: Union[Topology, GossipSchedule],
    axis_name: str,
) -> optax.GradientTransformation:
    """Gradient tracking (DIGing / Aug-DGM family): decentralized training
    that converges to the GLOBAL optimum with a constant step size under
    heterogeneous per-rank data, where plain decentralized SGD stalls at a
    topology-dependent bias.

    The recursion (W = the gossip mixing matrix):

        x_{t+1} = W x_t − y_t                     (gossip params, step by y)
        y_{t+1} = W y_t + u_{t+1} − u_t           (track the average update)

    ``u`` is the base transform's update direction (so GT composes with
    momentum/Adam: it tracks whatever ``base`` emits, scaled updates
    included); y_0 = u_0 makes Σ_i y_i = Σ_i u_i invariant — y converges to
    the average update across ranks, which is what kills the bias.

    The reference ships gradient tracking only as a window-ops *example*
    (`examples/pytorch_*` upstream; here
    ``examples/decentralized_optimization.py``); this optimizer makes it a
    first-class, jit-fused training surface like the other four.  Both
    gossips ride the same fused ppermute fabric (``fuse_apply``) and
    overlap with compute like every other collective here.

    Applicability, measured honestly: GT's win is the smooth/(near-)convex
    or low-noise regime, where it converges to the exact optimum while
    DSGD stalls at its bias (the test gate shows >10x).  Under noisy
    minibatch gradients on deep nets the tracked direction is a stale,
    ring-mixed average that lags the fast-moving local gradients — short
    LeNet runs measured it well BEHIND plain gossip at every lr/momentum
    tried — so prefer ``DistributedNeighborAllreduceOptimizer`` for
    stochastic deep training and reach for GT when heterogeneity bias, not
    gradient noise, is the binding constraint.
    """
    scheds = _as_schedules(topology)
    if len(scheds) != 1:
        raise ValueError("gradient tracking takes a single static topology "
                         "(time-varying W breaks the tracking invariant)")
    sched = scheds[0]

    def _mix(tree):
        return C.fuse_apply(
            lambda t: C.neighbor_allreduce(t, sched, axis_name), tree)

    def init_fn(params):
        zeros = jax.tree_util.tree_map(jnp.zeros_like, params)
        # y_0 must equal u_0; signal "first step" with prev_g = None via a
        # counter-free sentinel: an extra zeros tree plus a flag would cost
        # a cond — instead initialize y = 0, prev_g = 0, and the first
        # update's y_1 = W·0 + u_1 − 0 = u_1, which IS the correct y_0 = u_0
        # start shifted by one mixing round (standard DIGing-ATC variant).
        return _GTState(base.init(params), zeros, zeros)

    def update_fn(grads, state, params=None):
        if params is None:
            raise ValueError("DistributedGradientTrackingOptimizer requires "
                             "params in update()")
        u, base_state = base.update(grads, state.base_state, params)
        # u is a DESCENT update (optax convention: apply_updates adds it),
        # so the tracking recursion uses it directly
        y = jax.tree_util.tree_map(
            lambda ym, un, uo: ym + un - uo, _mix(state.y), u, state.prev_g)
        new_p = jax.tree_util.tree_map(
            lambda xm, yt: (xm.astype(jnp.float32)
                            + yt.astype(jnp.float32)),
            _mix(params), y)
        new_updates = jax.tree_util.tree_map(
            lambda np_, p: (np_ - p.astype(jnp.float32)).astype(p.dtype),
            new_p, params)
        return new_updates, _GTState(base_state, y, u)

    return optax.GradientTransformation(init_fn, update_fn)


# ---------------------------------------------------------------------------
# Exact diffusion (D2) — beyond-reference optimizer surface
# ---------------------------------------------------------------------------


class _EDState(NamedTuple):
    base_state: Any
    prev_psi: Any  # last step's psi = x + u (None-sentinel via first flag)
    master: Any  # float32 master copy of params — see dtype note below
    first: jnp.ndarray  # bool: no correction term on the first step


def DistributedExactDiffusionOptimizer(
    base: optax.GradientTransformation,
    topology: Union[Topology, GossipSchedule],
    axis_name: str,
) -> optax.GradientTransformation:
    """Exact diffusion / D² (Yuan, Ying, Zhao & Sayed, 2017): bias-free
    decentralized training with ONE gossip per step.

    The recursion:

        ψ_t = x_{t-1} + u_t                    (local step)
        φ_t = ψ_t + x_{t-1} − ψ_{t-1}          (diffusion correction)
        x_t = W φ_t                            (combine)

    Like gradient tracking it removes plain DSGD's O(lr) heterogeneity
    bias, but with HALF the communication (one gossip per step instead of
    two) at the price of requiring a SYMMETRIC, positive-semidefinite-
    friendly mixing matrix (ring/grid/full; checked at setup).  The first
    step has no ψ_{t-1} — it runs plain ATC diffusion, which is the
    standard initialization.

    Upstream ships exact diffusion only inside the window-ops example
    (`examples/decentralized_optimization.py` here); this makes it a
    first-class jit-fused optimizer.

    Precision note: unlike DSGD/GT/CHOCO, exact diffusion's dual variable
    is *implicit* in the difference of consecutive ψ iterates, so
    quantizing x to bf16 every combine step destroys the conservation law
    the "exact" in the name depends on (measured: bf16 runs freeze at a
    spurious consensus once per-step corrections round to zero).  The
    state therefore carries a float32 master copy of the parameters; the
    whole recursion runs in f32 and the returned updates merely move the
    (possibly low-precision) visible params to the cast of the master.
    Consequence: params must be updated ONLY through this transform's
    updates, or the master desyncs.
    """
    scheds = _as_schedules(topology)
    if len(scheds) != 1:
        raise ValueError("exact diffusion takes a single static topology")
    sched = scheds[0]
    mix_np = sched.mixing_matrix()
    if not np.allclose(mix_np, mix_np.T, atol=1e-8):
        raise ValueError(
            "exact diffusion requires a symmetric mixing matrix "
            "(ring/grid/full); got an asymmetric one (max |W - W^T| = "
            f"{np.abs(mix_np - mix_np.T).max():.3g})")

    def _mix(tree):
        return C.fuse_apply(
            lambda t: C.neighbor_allreduce(t, sched, axis_name), tree)

    def init_fn(params):
        # prev_psi and master live in float32 regardless of param dtype:
        # (a) state dtypes must be step-invariant (lax.scan carries,
        # checkpoint templates from opt.init), (b) the recursion's implicit
        # dual only survives in f32 — see the docstring's precision note.
        f32 = lambda t: jnp.asarray(t, jnp.float32)
        return _EDState(base.init(params),
                        jax.tree_util.tree_map(
                            lambda t: jnp.zeros(t.shape, jnp.float32),
                            params),
                        jax.tree_util.tree_map(f32, params),
                        jnp.ones((), jnp.bool_))

    def update_fn(grads, state, params=None):
        if params is None:
            raise ValueError("DistributedExactDiffusionOptimizer requires "
                             "params in update()")
        u, base_state = base.update(grads, state.base_state, params)
        # x is the f32 master, NOT the visible (possibly bf16) params
        psi = jax.tree_util.tree_map(
            lambda x, un: x + un.astype(jnp.float32), state.master, u)
        # first step: phi = psi (no correction); after: psi + x - prev_psi
        phi = jax.tree_util.tree_map(
            lambda ps, x, pp: jnp.where(state.first, ps, ps + x - pp),
            psi, state.master, state.prev_psi)
        new_x = _mix(phi)
        new_updates = jax.tree_util.tree_map(
            lambda nx, p: (nx - p.astype(jnp.float32)).astype(p.dtype),
            new_x, params)
        return new_updates, _EDState(base_state, psi, new_x,
                                     jnp.zeros((), jnp.bool_))

    return optax.GradientTransformation(init_fn, update_fn)
