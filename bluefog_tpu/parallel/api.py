"""Array-level (host-side) op API — the ``bf.*`` surface of the reference.

Reference parity (upstream-relative): module-level functions of
``bluefog/torch/mpi_ops.py`` and the helpers of ``bluefog/torch/utility.py``.

Representation: where the reference's process-per-rank model gives each rank a
private ``tensor``, the SPMD model stacks all ranks' values into one global
array with a leading ``size``-length *rank axis*, sharded over the gossip mesh
axis (``P('bf')``).  ``x[r]`` is rank ``r``'s value.  Every function here
wraps the corresponding in-SPMD primitive from ``bluefog_tpu.ops`` in a
``shard_map`` over the context mesh; inside a user's own ``shard_map``-ed
training step, call the ``bluefog_tpu.ops`` primitives directly instead.

Because everything is jitted XLA, the reference's nonblocking/handle surface
(``*_nonblocking``, ``poll``, ``synchronize`` — SURVEY.md §3.2) maps onto
JAX's async dispatch: every call here *is* nonblocking (returns a future-like
Array); ``jax.block_until_ready`` is the ``synchronize`` analog, and overlap
with compute is handled by the XLA scheduler rather than a background thread.
"""

from __future__ import annotations

import contextlib
import functools
import threading
from typing import Dict, Optional

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax, shard_map
from jax.sharding import PartitionSpec as P

from bluefog_tpu import ops as _ops
from bluefog_tpu.ops.windows import WindowState
from bluefog_tpu.parallel.context import get_context
from bluefog_tpu.topology.graphs import Topology
from bluefog_tpu.topology.schedule import GossipSchedule, build_schedule
from bluefog_tpu.utils import lockcheck as _lc

__all__ = [
    "allreduce",
    "allgather",
    "broadcast",
    "barrier",
    "neighbor_allreduce",
    "neighbor_allreduce_aperiodic",
    "neighbor_allgather",
    "hierarchical_neighbor_allreduce",
    "win_create",
    "win_free",
    "win_put",
    "win_get",
    "win_accumulate",
    "win_update",
    "win_update_then_collect",
    "win_mutex",
    "win_mutex_break",
    "win_mutex_sweep",
    "broadcast_parameters",
    "allreduce_parameters",
    "broadcast_optimizer_state",
    "rank_stack",
    "rank_shard",
]


@functools.lru_cache(maxsize=256)
def _schedule_for(topology: Topology) -> GossipSchedule:
    # Topologies hash by identity, so repeated calls with the same Topology
    # object reuse one schedule — keeping _cached_op / _cached_win_op warm
    # instead of recompiling per call.
    return build_schedule(topology)


def _sched(topology) -> GossipSchedule:
    if topology is None:
        return get_context().schedule
    if isinstance(topology, GossipSchedule):
        return topology
    return _schedule_for(topology)


def _smap(fn, n_in: int = 1, replicated_in: int = 0):
    ctx = get_context()
    ax = ctx.axis_name
    in_specs = tuple([P(ax)] * n_in + [P()] * replicated_in)
    return shard_map(
        fn, mesh=ctx.mesh, in_specs=in_specs, out_specs=P(ax), check_vma=False,
    )


# Cache of jitted shard_map callables.  Eager api calls would otherwise
# re-stage the shard_map on every invocation (the analog of the reference
# re-registering MPI datatypes per call); keyed by everything that changes the
# staged program.  Schedules hash by identity — reuse the context's schedule
# (or hold on to your own) to stay cache-warm.
@functools.lru_cache(maxsize=512)
def _cached_op(op_name: str, mesh, axis_name: str, sched, *static):
    ax = axis_name

    if op_name == "neighbor_allreduce":
        has_sw, has_rw, has_dw = static

        def fn(xs, sw, rw, dw):
            return _ops.neighbor_allreduce(
                xs, sched, ax,
                self_weight=sw if has_sw else None,
                recv_weights=rw if has_rw else None,
                send_weights=dw if has_dw else None,
            )

        return jax.jit(shard_map(
            fn, mesh=mesh, in_specs=(P(ax), P(), P(), P()), out_specs=P(ax),
            check_vma=False,
        ))

    if op_name == "neighbor_allreduce_aperiodic":
        (max_rotations,) = static

        def ap_fn(xs, w):
            return _ops.neighbor_allreduce_aperiodic(
                xs, w, ax, max_rotations=max_rotations)

        return jax.jit(shard_map(
            ap_fn, mesh=mesh, in_specs=(P(ax), P()), out_specs=P(ax),
            check_vma=False,
        ))

    if op_name == "allreduce":
        (average,) = static
        f = lambda xs: _ops.allreduce(xs, ax, average=average)
    elif op_name == "broadcast":
        (root,) = static
        f = lambda xs: _ops.broadcast(xs, root, ax)
    elif op_name == "allgather":
        # [None] must apply per leaf, not to the tree_map'd result
        f = lambda xs: jax.tree_util.tree_map(
            lambda leaf: lax.all_gather(leaf, ax, axis=0, tiled=True)[None], xs
        )
    else:
        raise KeyError(op_name)
    return jax.jit(shard_map(
        f, mesh=mesh, in_specs=(P(ax),), out_specs=P(ax), check_vma=False,
    ))


def rank_stack(x, size: Optional[int] = None):
    """Replicate a host value into the stacked per-rank representation:
    ``out[r] = x`` for every rank (pytree-polymorphic)."""
    n = size or get_context().size
    return jax.tree_util.tree_map(
        lambda leaf: jnp.broadcast_to(jnp.asarray(leaf)[None], (n,) + jnp.asarray(leaf).shape), x
    )


def rank_shard(x):
    """Device-put a stacked array so the rank axis lies on the gossip mesh."""
    ctx = get_context()
    sharding = jax.sharding.NamedSharding(ctx.mesh, P(ctx.axis_name))
    return jax.tree_util.tree_map(lambda leaf: jax.device_put(leaf, sharding), x)


# ---------------------------------------------------------------------------
# Collectives
# ---------------------------------------------------------------------------


def neighbor_allreduce(x, *, topology=None, self_weight=None, recv_weights=None,
                       send_weights=None):
    """Stacked-array ``bf.neighbor_allreduce``: ``out[i] = W[i,i] x[i] +
    sum_j W[i,j] x[j]`` with ``W`` from ``topology`` (default: context).

    ``send_weights`` is the reference's per-call ``dst_weights``: slot-indexed
    sender-side scaling applied to the shipped payload (``(num_slots,)``, or
    ``(size, num_slots)`` for a per-rank table)."""
    ctx = get_context()
    sched = _sched(topology)
    f = _cached_op(
        "neighbor_allreduce", ctx.mesh, ctx.axis_name, sched,
        self_weight is not None, recv_weights is not None,
        send_weights is not None,
    )
    sw = jnp.asarray(self_weight if self_weight is not None else 0.0, jnp.float32)
    rw = jnp.asarray(
        recv_weights if recv_weights is not None else jnp.zeros((sched.num_slots,)),
        jnp.float32,
    )
    dw = jnp.asarray(
        send_weights if send_weights is not None else jnp.zeros((sched.num_slots,)),
        jnp.float32,
    )
    return f(x, sw, rw, dw)


def neighbor_allreduce_aperiodic(x, mixing_matrix, *,
                                 max_rotations: Optional[int] = None):
    """Stacked-array gossip with an arbitrary per-call topology: ``out =
    W @ xs`` for any row-stochastic ``(size, size)`` ``W`` — edge set *and*
    weights are data, so changing them never recompiles.  ``max_rotations``
    caps program size for large meshes (degree-bounded dynamic graphs); see
    :func:`bluefog_tpu.ops.collectives.neighbor_allreduce_aperiodic`."""
    ctx = get_context()
    f = _cached_op(
        "neighbor_allreduce_aperiodic", ctx.mesh, ctx.axis_name, None,
        max_rotations)
    return f(x, jnp.asarray(mixing_matrix, jnp.float32))


def neighbor_allgather(x, *, topology=None):
    """Stacked ``bf.neighbor_allgather``: returns ``(slots, mask)``; see
    :func:`bluefog_tpu.ops.collectives.neighbor_allgather` for the padding
    deviation from the reference's ragged concatenation."""
    ctx = get_context()
    sched = _sched(topology)

    def fn(xs):
        slots, mask = _ops.neighbor_allgather(xs[0], sched, ctx.axis_name)
        return slots[None], mask[None]

    f = shard_map(
        fn, mesh=ctx.mesh, in_specs=(P(ctx.axis_name),),
        out_specs=(P(ctx.axis_name), P(ctx.axis_name)), check_vma=False,
    )
    return f(x)


def allreduce(x, *, average: bool = True):
    ctx = get_context()
    return _cached_op("allreduce", ctx.mesh, ctx.axis_name, None, average)(x)


def allgather(x):
    """Stacked allgather: every rank's row becomes the full stack — output
    shape ``(size, size, ...)`` per the stacked-representation convention."""
    ctx = get_context()
    return _cached_op("allgather", ctx.mesh, ctx.axis_name, None)(x)


def broadcast(x, root_rank: int = 0):
    ctx = get_context()
    return _cached_op("broadcast", ctx.mesh, ctx.axis_name, None, root_rank)(x)


def barrier():
    """Block the host until all in-flight device work completes."""
    ctx = get_context()
    out = _smap(lambda xs: xs + _ops.barrier(ctx.axis_name))(
        jnp.zeros((ctx.size,), jnp.float32)
    )
    jax.block_until_ready(out)
    return True


def hierarchical_neighbor_allreduce(x, *, machine_topology=None, self_weight=None,
                                    recv_weights=None, two_level_mesh=False):
    """Stacked ``bf.hierarchical_neighbor_allreduce`` (intra-machine exact
    average + machine-level gossip; requires ``init(local_size=...)``).

    ``two_level_mesh=True`` runs over ``ctx.hier_mesh`` — an explicit
    ``(machine, local)`` mesh where the local average is a ``pmean`` on the
    inner (ICI) axis and the machine gossip a ``ppermute`` on the outer (DCN)
    axis; numerically identical to the flat path, and the form a multi-slice
    deployment uses so the machine hops ride DCN."""
    ctx = get_context()
    msched = machine_topology
    if msched is None:
        if ctx.machine_schedule is None:
            raise RuntimeError("no machine topology: init(local_size=...) first")
        msched = ctx.machine_schedule
    elif isinstance(msched, Topology):
        msched = build_schedule(msched)
    if two_level_mesh:
        mesh2 = ctx.hier_mesh
        spec = P((ctx.machine_axis_name, ctx.local_axis_name))
        return shard_map(
            lambda xs: _ops.hierarchical_neighbor_allreduce_2d(
                xs, msched,
                machine_axis=ctx.machine_axis_name,
                local_axis=ctx.local_axis_name,
                self_weight=self_weight, recv_weights=recv_weights,
            ),
            mesh=mesh2, in_specs=(spec,), out_specs=spec, check_vma=False,
        )(x)
    return _smap(
        lambda xs: _ops.hierarchical_neighbor_allreduce(
            xs, msched, ctx.axis_name, local_size=ctx.local_size,
            self_weight=self_weight, recv_weights=recv_weights,
        )
    )(x)


# ---------------------------------------------------------------------------
# Window registry (one-sided ops)
# ---------------------------------------------------------------------------


@functools.lru_cache(maxsize=512)
def _cached_win_op(op_name: str, mesh, axis_name: str, sched, *static):
    """Jitted shard_map callables for window ops (same caching story as
    :func:`_cached_op`)."""
    ax = axis_name

    if op_name == "create":
        (name,) = static

        def create_fn(xs):
            return _ops.win_create(xs, sched, ax, name=name)

        return jax.jit(shard_map(
            create_fn, mesh=mesh, in_specs=(P(ax),), out_specs=P(ax),
            check_vma=False,
        ))

    if op_name in ("put", "accumulate"):
        op = _ops.win_put if op_name == "put" else _ops.win_accumulate

        def deliver_fn(st, xs, dw):
            return op(st, xs, ax, dst_weight=dw)

        return jax.jit(shard_map(
            deliver_fn, mesh=mesh, in_specs=(P(ax), P(ax), P()),
            out_specs=P(ax), check_vma=False,
        ))

    if op_name == "get":
        return jax.jit(shard_map(
            lambda st: _ops.win_get(st, ax), mesh=mesh, in_specs=(P(ax),),
            out_specs=P(ax), check_vma=False,
        ))

    if op_name == "update":
        has_sw, has_rw = static

        def update_fn(st, sw, rw):
            return _ops.win_update(
                st, ax,
                self_weight=sw if has_sw else None,
                recv_weights=rw if has_rw else None,
            )

        return jax.jit(shard_map(
            update_fn, mesh=mesh, in_specs=(P(ax), P(), P()),
            out_specs=(P(ax), P(ax)), check_vma=False,
        ))

    if op_name == "update_then_collect":
        return jax.jit(shard_map(
            lambda st: _ops.win_update_then_collect(st, ax), mesh=mesh,
            in_specs=(P(ax),), out_specs=(P(ax), P(ax)), check_vma=False,
        ))

    raise KeyError(op_name)


def win_create(x, name: str, *, topology=None, zero_init: bool = False) -> bool:
    """Register window ``name`` over stacked tensor(-tree) ``x``
    (reference ``bf.win_create``; collective there, pure allocation here)."""
    ctx = get_context()
    sched = _sched(topology)
    if zero_init:
        x = jax.tree_util.tree_map(lambda leaf: jnp.zeros_like(leaf), x)
    f = _cached_win_op("create", ctx.mesh, ctx.axis_name, sched, name)
    ctx.windows[name] = f(x)
    return True


def win_free(name: Optional[str] = None) -> bool:
    """Drop one window (or all, matching the reference's ``win_free()``)."""
    from bluefog_tpu.ops import pallas_gossip as _pg

    ctx = get_context()
    if name is None:
        for n in ctx.windows:
            _pg.release_window_collective_id(n)
        ctx.windows.clear()
    else:
        ctx.windows.pop(name, None)
        # a freed window must not poison its collective-id bucket for the
        # rest of a long-lived process
        _pg.release_window_collective_id(name)
    return True


def _get_win(name: str) -> WindowState:
    ctx = get_context()
    if name not in ctx.windows:
        raise KeyError(f"no window named {name!r}; call win_create first")
    return ctx.windows[name]


def win_put(x, name: str, *, dst_weight=1.0) -> bool:
    ctx = get_context()
    state = _get_win(name)
    f = _cached_win_op("put", ctx.mesh, ctx.axis_name, state.spec.schedule)
    ctx.windows[name] = f(state, x, jnp.asarray(dst_weight, jnp.float32))
    return True


def win_accumulate(x, name: str, *, dst_weight=1.0) -> bool:
    ctx = get_context()
    state = _get_win(name)
    f = _cached_win_op("accumulate", ctx.mesh, ctx.axis_name, state.spec.schedule)
    ctx.windows[name] = f(state, x, jnp.asarray(dst_weight, jnp.float32))
    return True


def win_get(name: str) -> bool:
    ctx = get_context()
    state = _get_win(name)
    f = _cached_win_op("get", ctx.mesh, ctx.axis_name, state.spec.schedule)
    ctx.windows[name] = f(state)
    return True


def win_update(name: str, *, self_weight=None, recv_weights=None):
    """Returns the stacked averaged tensor and refreshes the window
    (reference ``bf.win_update``)."""
    ctx = get_context()
    state = _get_win(name)
    sched = state.spec.schedule
    f = _cached_win_op(
        "update", ctx.mesh, ctx.axis_name, sched,
        self_weight is not None, recv_weights is not None,
    )
    sw = jnp.asarray(self_weight if self_weight is not None else 0.0, jnp.float32)
    rw = jnp.asarray(
        recv_weights if recv_weights is not None else jnp.zeros((sched.num_slots,)),
        jnp.float32,
    )
    out, new_state = f(state, sw, rw)
    ctx.windows[name] = new_state
    return out


def win_update_then_collect(name: str):
    ctx = get_context()
    state = _get_win(name)
    f = _cached_win_op(
        "update_then_collect", ctx.mesh, ctx.axis_name, state.spec.schedule
    )
    out, new_state = f(state)
    ctx.windows[name] = new_state
    return out


_win_mutexes: Dict[str, object] = {}
_win_mutexes_guard = _lc.lock("parallel.api._win_mutexes_guard")
_dist_held = threading.local()  # per-thread reentrancy counts per name


def _coordination_client():
    """The jax.distributed coordination-service client, or None when this is
    a single-controller process (no distributed runtime to coordinate with).

    In a multi-controller job a missing client is an ERROR, not a fallback:
    silently downgrading to the process-local lock would let two controllers
    into the critical section — the exact race win_mutex exists to prevent.
    """
    import jax

    if jax.process_count() <= 1:
        return None
    try:
        from jax._src.distributed import global_state

        client = global_state.client
    except Exception as e:
        raise RuntimeError(
            "win_mutex: multi-controller job but the jax.distributed "
            "coordination-service client is unavailable — refusing to "
            "downgrade to a process-local lock") from e
    if client is None:
        raise RuntimeError(
            "win_mutex: multi-controller job but jax.distributed was not "
            "initialized with a coordination service")
    return client


_WIN_MUTEX_PREFIX = "bluefog_tpu/win_mutex/"
# break subkeys live in a DISJOINT prefix: a lock key derived from a window
# literally named "x.break" can never collide with window "x"'s break key
_WIN_MUTEX_BREAK_PREFIX = "bluefog_tpu/win_mutex_break/"
_LEASE_MARK = " lease_until="


def _is_not_found(e: BaseException) -> bool:
    """The coordination client raises (rather than returning None) for a
    missing key; distinguish that definitive answer from transient RPC
    failures."""
    return "NOT_FOUND" in str(e)


def _parse_lock_value(v: str):
    """``(owner, lease_expiry_unix_or_None, lease_duration_s_or_None)``
    from a lock key's value (stamp format ``<expiry>[/<duration>]``).
    Values without the lease marker (older writers, hand-planted keys) have
    no lease and are NEVER auto-stolen."""
    if _LEASE_MARK in v:
        owner, _, stamp = v.rpartition(_LEASE_MARK)
        expiry, _, dur = stamp.partition("/")
        try:
            return owner, float(expiry), (float(dur) if dur else None)
        except ValueError:
            return v, None, None
    return v, None, None


@contextlib.contextmanager
def win_mutex(name: str = "win", *, for_self: bool = True, ranks=None,
              timeout_s: float = 60.0, poll_interval_s: float = 0.002,
              lease_s: float = 30.0):
    """Mutual exclusion over window ``name`` (reference ``bf.win_mutex``,
    an MPI passive-target ``MPI_Win_lock_all`` epoch guarding concurrent
    one-sided access — ``bluefog/torch/mpi_win_ops.cc``).

    Scope — stated precisely, per deployment shape:

    - **Single controller** (``jax.process_count() == 1``): a process-local
      reentrant lock per window name.  Device-side one-sided transfers inside
      a jitted step are ordered by data dependencies, so the only real race
      is host threads (background :func:`enqueue_host_op` workers vs the main
      thread) mutating the same named window — which this serializes.
    - **Multi-controller** (``jax.distributed`` initialized, >1 processes): a
      **distributed lock on the coordination service** — acquisition is an
      atomic key creation (the service rejects duplicates), release deletes
      the key, and contenders poll.  This is the cross-process exclusion the
      reference gets from ``MPI_Win_lock_all``; it is reentrant within a
      thread, and raises ``TimeoutError`` after ``timeout_s``.

    **Lease / failure semantics** (multi-controller): the lock value carries
    a lease stamp (expiry + duration) that a background heartbeat refreshes
    every ``lease_s/3`` while the holder is alive — a live holder is never
    stolen no matter how long its critical section runs.  If the holder
    DIES, the heartbeat stops and the next contender recovers the lock
    automatically.  Stealing requires ALL of: (a) the stamp is wall-clock
    expired, (b) the contender has watched the value stay *unchanged* for a
    full lease duration on its own monotonic clock — so cross-host clock
    skew alone can never steal from a heartbeating holder — and (c) the
    contender wins the atomic break subkey and re-confirms the value is
    still unchanged immediately before deleting.  Keys without a lease
    stamp (planted by hand or by older writers) are never auto-stolen;
    those still need :func:`win_mutex_break` after the owner is known dead.
    ``lease_s=None`` disables the lease entirely (release failures then
    propagate, since no self-healing would follow them).  A holder frozen
    (not dead) past its lease can be stolen; its refresher detects the loss
    on its next beat, logs it, and stops re-stamping so the double-hold is
    bounded by one refresh period.  Residual window, stated honestly: the
    service has no compare-and-delete, so a breaker dying between its
    re-confirmation and the delete can still race a revival — the same
    post-failure ambiguity MPI has after ``MPI_Win_lock_all`` owner loss.

    ``for_self``/``ranks`` are accepted for reference call-site
    compatibility; the lock is per-window-name, not per-rank.
    """
    del for_self, ranks  # lock granularity is the window name
    client = _coordination_client()
    if client is None:
        with _win_mutexes_guard:
            lock = _win_mutexes.setdefault(
                name, _lc.rlock("parallel.api._win_mutexes[]"))
        with lock:
            yield
        return

    import time as _time

    held = getattr(_dist_held, "counts", None)
    if held is None:
        held = _dist_held.counts = {}
    if held.get(name, 0) > 0:  # reentrant within this thread
        held[name] += 1
        try:
            yield
        finally:
            held[name] -= 1
        return

    import jax
    import os as _os

    key = _WIN_MUTEX_PREFIX + name
    owner = f"{jax.process_index()}:{_os.getpid()}:{threading.get_ident()}"

    def stamped():
        if lease_s is None:
            return owner
        return (f"{owner}{_LEASE_MARK}"
                f"{_time.time() + lease_s:.3f}/{lease_s:.1f}")

    deadline = _time.monotonic() + timeout_s
    backoff = poll_interval_s
    tracker = _StealTracker(client, key, owner)
    while True:
        try:
            client.key_value_set(key, stamped())  # atomic: raises if held
            break
        except Exception as e:
            if "ALREADY_EXISTS" not in str(e):
                raise
            tracker.poll()
            if _time.monotonic() > deadline:
                holder = ""
                try:
                    holder = client.key_value_try_get(key)
                except Exception:
                    pass
                raise TimeoutError(
                    f"win_mutex({name!r}): lock held for {timeout_s:.0f}s "
                    f"by {holder!r} (process:pid:thread); a leased lock "
                    "recovers automatically when its owner dies — if this "
                    "one has no lease and the owner is dead, recover with "
                    "win_mutex_break(name)") from e
            # exponential backoff: N contenders busy-polling the (single)
            # coordination service with failing RPCs would starve its
            # heartbeat work at pod scale
            _time.sleep(backoff)
            backoff = min(backoff * 2, 0.1)
    held[name] = 1
    stop_refresh = threading.Event()
    refresher = None
    if lease_s is not None:
        def refresh():
            # a live holder's lease must never lapse: re-stamp well inside
            # the lease period until release.  If the key is no longer ours
            # (stolen from a frozen incarnation of us), say so and STOP —
            # blindly re-stamping would silently overwrite the new holder.
            # TRANSIENT RPC errors must NOT kill the heartbeat: the next
            # beat is only lease_s/3 away and the lease survives two missed
            # beats — exiting on the first blip would make a live holder
            # silently stealable, the exact thing the lease forbids.
            from bluefog_tpu.utils import log

            while not stop_refresh.wait(lease_s / 3.0):
                try:
                    cur = client.key_value_try_get(key)
                except Exception as e:
                    if _is_not_found(e):
                        cur = None  # definitively gone: lost
                    else:
                        continue  # transient: retry next beat
                if cur is None or _parse_lock_value(cur)[0] != owner:
                    log.error(
                        "win_mutex(%r): lease LOST (key now %r) — this "
                        "holder was frozen past its lease and the lock was "
                        "stolen; exclusion is no longer guaranteed for the "
                        "remainder of this critical section", name, cur)
                    return
                try:
                    client.key_value_set(key, stamped(),
                                         allow_overwrite=True)
                except Exception:
                    continue  # transient: the stamp retries next beat
        refresher = threading.Thread(target=refresh, daemon=True)
        refresher.start()
    try:
        yield
    finally:
        held[name] = 0
        stop_refresh.set()
        joined = True
        if refresher is not None:
            refresher.join(timeout=5)
            joined = not refresher.is_alive()
        if not joined:
            # a refresher stuck in an in-flight key_value_set could land
            # AFTER our delete and resurrect the key as a ghost; leave the
            # key to lease expiry instead (self-healing, bounded by lease_s)
            from bluefog_tpu.utils import log

            log.warn("win_mutex(%r): refresher still in flight at release; "
                     "leaving key to lease expiry", name)
        elif lease_s is None:
            # no lease means no self-healing: a failed delete here must be
            # LOUD, or the key wedges every later acquisition silently
            client.key_value_delete(key)
        else:
            try:
                # shrink (not close — no CAS) the stolen-lock window: only
                # delete what is still ours
                cur = client.key_value_try_get(key)
                if _parse_lock_value(cur)[0] == owner:
                    client.key_value_delete(key)
            except Exception as e:
                # a missing key is a CLEAN outcome (stolen and already
                # released by the thief), not an RPC failure to warn about
                if not _is_not_found(e):
                    from bluefog_tpu.utils import log

                    log.warn("win_mutex(%r): release delete failed (%s); "
                             "the lease will self-heal", name, e)


class _StealTracker:
    """Per-contender steal state: recovers a key whose leased holder died.

    Rate-limited (one try_get per ~lease/10, not per poll — N contenders
    must not double the coordination service's RPC load), and skew-immune:
    stealing additionally requires the value to have stayed UNCHANGED for a
    full lease duration on this contender's monotonic clock, which a live
    holder's heartbeat (every lease/3) makes impossible regardless of how
    far apart the hosts' wall clocks are."""

    def __init__(self, client, key: str, owner: str):
        self.client = client
        self.key = key
        self.owner = owner
        self.observed: Optional[str] = None
        self.first_seen = 0.0   # monotonic time self.observed appeared
        self.next_check = 0.0   # monotonic rate limiter

    def poll(self) -> None:
        import time as _time

        now_m = _time.monotonic()
        if now_m < self.next_check:
            return
        try:
            cur = self.client.key_value_try_get(self.key)
        except Exception:
            self.observed = None
            return  # key gone — the acquire loop will race for it
        if cur != self.observed:
            self.observed, self.first_seen = cur, now_m
        _, expiry, dur = _parse_lock_value(cur)
        if expiry is None:
            self.next_check = now_m + 1.0
            return  # lease-less values are never auto-stolen
        confirm_s = max(1.0, dur if dur is not None else 2.0)
        self.next_check = now_m + max(0.5, confirm_s / 10.0)
        if _time.time() <= expiry:
            return  # writer-clock says live
        if now_m - self.first_seen < confirm_s:
            return  # not yet watched unchanged for a full lease
        if _break_stale(self.client, self.key, self.owner, cur):
            self.observed = None


def _break_stale(client, key: str, breaker: str, observed: str) -> bool:
    """Delete ``key`` iff its value is still exactly ``observed``,
    serialized through an atomic break subkey (one breaker at a time; a
    last-moment refresh or re-acquire changes the value and aborts).
    Returns True if the stale key was deleted."""
    import time as _time

    now = _time.time()
    assert key.startswith(_WIN_MUTEX_PREFIX), key
    bkey = _WIN_MUTEX_BREAK_PREFIX + key[len(_WIN_MUTEX_PREFIX):]
    bval = f"{breaker}{_LEASE_MARK}{now + 10.0:.3f}/10.0"
    try:
        client.key_value_set(bkey, bval)  # atomic: one breaker at a time
    except Exception as e:
        if "ALREADY_EXISTS" not in str(e):
            return False
        # the previous breaker may itself have died mid-break
        try:
            bheld = client.key_value_try_get(bkey)
            _, bexp, _ = _parse_lock_value(bheld)
            if bexp is not None and now > bexp:
                client.key_value_delete(bkey)
        except Exception:
            pass
        return False
    stole = False
    try:
        cur = client.key_value_try_get(key)
        if cur == observed:  # unchanged since observed expired: truly stale
            client.key_value_delete(key)
            stole = True
            from bluefog_tpu.utils import log

            log.warn("win_mutex: broke expired lock %s (was %r)", key,
                     observed)
    except Exception:
        pass
    finally:
        try:
            client.key_value_delete(bkey)
        except Exception:
            pass
    return stole


def win_mutex_sweep(grace_s: float = 0.0) -> int:
    """Clear every win_mutex key whose lease expired more than ``grace_s``
    ago — the restart-path janitor (a supervisor-restarted worker calls this
    before re-entering training so locks its previous incarnation died
    holding cannot deadlock the job until per-acquire stealing notices).

    Deletions go through the same break-subkey + value-unchanged protocol
    as per-acquire stealing (on a FRESH read, not the enumeration snapshot),
    so the sweep serializes with live contenders and cannot delete a lock
    that was just stolen and re-acquired.  Returns the number of keys
    cleared; 0 under a single controller or when the service cannot
    enumerate keys."""
    import os as _os
    import time as _time

    client = _coordination_client()
    if client is None:
        return 0
    try:
        entries = client.key_value_dir_get(_WIN_MUTEX_PREFIX)
    except Exception:
        return 0
    removed = 0
    now = _time.time()
    breaker = f"sweep:{_os.getpid()}:{threading.get_ident()}"
    for entry in entries:
        key = entry[0] if isinstance(entry, (tuple, list)) else entry
        try:
            value = client.key_value_try_get(key)  # fresh, never snapshot
        except Exception:
            continue
        _, expiry, _ = _parse_lock_value(value)
        if expiry is not None and now > expiry + grace_s:
            if _break_stale(client, key, breaker, value):
                removed += 1
    return removed


def win_mutex_break(name: str = "win") -> bool:
    """Forcibly release a distributed :func:`win_mutex` whose holder died
    (the ``MPI_Win_unlock_all``-after-failure analog).  Returns True if a
    held lock was cleared.  **Only** call this when the owner named by the
    TimeoutError is known dead — breaking a live holder's lock removes the
    exclusion it is relying on."""
    client = _coordination_client()
    if client is None:
        # single-controller: a holder's death is process death, so there is
        # no dead-owner state to clear — and dropping a live RLock would let
        # a second thread into the critical section. Pure no-op.
        return False
    try:
        client.key_value_delete(_WIN_MUTEX_PREFIX + name)
        return True
    except Exception:
        return False


# ---------------------------------------------------------------------------
# Parameter-sync helpers (reference bluefog/torch/utility.py)
# ---------------------------------------------------------------------------


def broadcast_parameters(params, root_rank: int = 0):
    """Make every rank's parameter tree equal to ``root_rank``'s (reference
    ``bf.broadcast_parameters`` — used at init so all ranks start agreed)."""
    return broadcast(params, root_rank)


def allreduce_parameters(params):
    """Replace each rank's parameters with the global average (reference
    ``bf.allreduce_parameters`` — post-training consensus averaging)."""
    return allreduce(params, average=True)


def broadcast_optimizer_state(opt_state, root_rank: int = 0):
    """Broadcast an optimizer state tree (reference
    ``bf.broadcast_optimizer_state``; here any pytree of arrays works,
    non-array leaves pass through untouched)."""
    arrays, treedef = jax.tree_util.tree_flatten(opt_state)
    is_arr = [hasattr(a, "dtype") or isinstance(a, (int, float, np.ndarray)) for a in arrays]
    stacked = [a for a, ok in zip(arrays, is_arr) if ok]
    if stacked:
        out = broadcast(stacked, root_rank)
        it = iter(out)
        arrays = [next(it) if ok else a for a, ok in zip(arrays, is_arr)]
    return jax.tree_util.tree_unflatten(treedef, arrays)


# ---------------------------------------------------------------------------
# Nonblocking host-op surface (reference bluefog/torch/mpi_ops.py poll /
# synchronize over handle_manager; SURVEY.md §3.2).  Device collectives are
# XLA-async by construction, so handles here track *host* ops (checkpoint IO,
# DCN staging, metric flushes) running on the native C++ engine thread.
# ---------------------------------------------------------------------------


def enqueue_host_op(fn, *, op: str = "host_op", name: str = "") -> int:
    """Run ``fn()`` on the background engine thread; returns a handle."""
    from bluefog_tpu.runtime import engine

    return engine().enqueue(fn, op=op, name=name)


def poll(handle: int) -> bool:
    """True once the host op behind ``handle`` has completed."""
    from bluefog_tpu.runtime import engine

    return engine().poll(handle)


def synchronize(handle: int, timeout_s=None):
    """Block until the host op completes and clear its handle (reference
    ``bf.synchronize`` = WaitAndClear).  Re-raises the op's exception."""
    from bluefog_tpu.runtime import engine

    return engine().synchronize(handle, timeout_s=timeout_s)


def wait_all_host_ops(timeout_s=None):
    """Drain every pending host op (used before shutdown / checkpoints)."""
    from bluefog_tpu.runtime import engine

    return engine().wait_all(timeout_s=timeout_s)
