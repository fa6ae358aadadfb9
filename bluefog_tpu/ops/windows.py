"""One-sided window ops — the TPU-native answer to MPI RMA.

Reference parity (upstream-relative; names confirmed in BASELINE.json):
``bluefog/torch/mpi_win_ops.{py,cc}`` + ``MPIController::Win*`` in
``bluefog/common/mpi_controller.cc``.  The reference allocates, per registered
tensor, one *self* buffer plus one buffer per in-neighbor backed by
``MPI_Win`` memory; ``win_put``/``win_accumulate`` write into the
destination's buffer without receiver involvement, and ``win_update`` forms a
weighted average of self + neighbor buffers.  Push-sum / gradient-tracking /
exact-diffusion algorithms are built on these (BASELINE.json configs[2,3]).

Design here: a window is a **functional state** (:class:`WindowState`, a
pytree) threaded through the training step.

- Portable backend (this module): the one-sided *dataflow* is expressed with
  ``lax.ppermute`` into per-slot buffers.  Execution is synchronous inside the
  SPMD program (both sides' programs contain the permute — exactly like the
  reference's NCCL backend, which emulates windows with paired
  ``ncclSend``/``ncclRecv``; SURVEY.md §2.4), but the *semantics* are
  one-sided: the destination's values are not consumed until ``win_update``,
  and puts/accumulates from different steps interleave freely.
- TPU backend (``bluefog_tpu.ops.pallas_gossip.deliver_pallas``, routed by
  ``backend='auto'|'pallas'``): within a slice the same state transitions
  run as Pallas async remote DMA (``pltpu.make_async_remote_copy``),
  making the transfer genuinely one-sided at the hardware level.
- Host runtime (``bluefog_tpu.runtime.async_windows`` + the shm/TCP
  transports): the genuinely *asynchronous* execution model — ranks at
  independent rates, deposits crossing thread/process/host boundaries with
  no receiver involvement.

All ops are jit-compatible and pytree-polymorphic.
"""

from __future__ import annotations

from typing import Any, Optional

import jax
import jax.numpy as jnp
from flax import struct
from jax import lax

from bluefog_tpu.blackbox import recorder as _bb
from bluefog_tpu.metrics import comm as _mt
from bluefog_tpu.topology.graphs import Topology
from bluefog_tpu.topology.schedule import GossipSchedule, build_schedule
from bluefog_tpu.utils import timeline as _tl

__all__ = [
    "WindowSpec",
    "WindowState",
    "win_create",
    "win_partition",
    "win_free",
    "win_put",
    "win_get",
    "win_accumulate",
    "win_update",
    "win_update_then_collect",
    "win_sync",
    "win_associated_p",
]


def _as_schedule(s) -> GossipSchedule:
    if isinstance(s, GossipSchedule):
        return s
    if isinstance(s, Topology):
        return build_schedule(s)
    raise TypeError(f"expected Topology or GossipSchedule, got {type(s)}")


class WindowSpec(struct.PyTreeNode):
    """Static window metadata (hashable side of the state).

    ``partition``: the window buffers' declared sharding — a canonical
    ``((leaf_name, PartitionSpec), ...)`` tuple covering ``self_buf``'s
    leaves, resolved from the ONE rule table when the window was created
    with ``win_create(rule_table=)`` (the unified-sharding contract: a
    window buffer is partitioned exactly like the leaf it windows; the
    tuple form keeps the static metadata hashable for jit).  ``None``
    means undeclared (legacy/replicated); a declaration that DISAGREES
    with the live rule table is what the BF-SHD002 lint flags."""

    schedule: GossipSchedule = struct.field(pytree_node=False)
    name: str = struct.field(pytree_node=False, default="win")
    partition: Any = struct.field(pytree_node=False, default=None)


class WindowState(struct.PyTreeNode):
    """Per-rank window memory, as seen inside ``shard_map``.

    Attributes:
      self_buf: pytree — this rank's published value (what peers ``win_get``).
      peer_bufs: matching pytree with a leading ``(K,)`` slot axis — the
        landing buffers for in-edges, one per schedule slot (reference: one
        buffer per in-neighbor).
      spec: static metadata.
      assoc_self / assoc_peers: the **associated push-sum scalar** ``p`` and
        its landing slots — populated when the window was created with
        ``associated_p=True`` (the reference's win-ops-with-associated-p mode,
        SURVEY.md §2.1 ``mpi_win_ops.cc``): every put/accumulate/get moves the
        same weight fraction of ``p`` alongside the tensor, and updates merge
        it with the same weights, so ``self_buf / p`` debiases directed
        (column-substochastic) gossip.  ``None`` when the mode is off.
    """

    self_buf: Any
    peer_bufs: Any
    spec: WindowSpec = struct.field(pytree_node=False)
    assoc_self: Optional[jnp.ndarray] = None
    assoc_peers: Optional[jnp.ndarray] = None


def _slot_mask(sched: GossipSchedule, axis_name: str):
    """(K,) bool — which slots have a real in-edge at this rank."""
    i = lax.axis_index(axis_name)
    return jnp.asarray(sched.recv_src >= 0)[i]


def win_create(x, schedule, axis_name: str, *, name: str = "win",
               associated_p: bool = False, rule_table=None,
               partition=None) -> WindowState:
    """Allocate window buffers for tensor(-tree) ``x``.

    Peer slots are initialized with copies of ``x`` so that a ``win_update``
    before any communication returns ``x`` unchanged (matching the reference's
    WinCreate initialization).  Collective in the reference (all ranks must
    call it); here it is pure allocation.

    ``associated_p=True`` additionally carries the push-sum scalar: ``p``
    starts at 1 on every rank; every subsequent put/accumulate/get/update
    moves and merges it with the tensor's weights.  Read it with
    :func:`win_associated_p`; ``self_buf / p`` is the debiased value.  In
    this mode the landing slots start **empty** (zeros for both tensor and
    ``p``) so the (x, p) mass pairs stay consistent: all initial mass lives
    at self with weight 1.

    ``rule_table`` (a :class:`bluefog_tpu.sharding.RuleTable`): resolve
    and DECLARE the window buffers' partitioning from the one rule table
    — the same table that shards the parameters and optimizer state, so
    changing a rule re-shards the window consistently.  ``partition``
    (a matching spec pytree, or the canonical name->spec tuple) declares
    it explicitly instead; the BF-SHD002 lint flags a declaration that
    disagrees with the table.  Read back with :func:`win_partition`.
    """
    sched = _as_schedule(schedule)
    k = sched.num_slots
    if rule_table is not None and partition is not None:
        raise ValueError("pass rule_table OR partition, not both")
    if rule_table is not None:
        partition = rule_table.resolve_tree(x)
    if partition is not None and not isinstance(partition, tuple):
        from bluefog_tpu.sharding.rules import named_leaves as _nl

        from jax.sharding import PartitionSpec as _P

        partition = tuple(
            (n, s) for n, s in _nl(
                partition, is_leaf=lambda v: isinstance(v, _P)))

    def init_peers(leaf):
        if associated_p:
            return jnp.zeros((k,) + leaf.shape, leaf.dtype)
        return jnp.broadcast_to(leaf[None], (k,) + leaf.shape).astype(leaf.dtype)

    return WindowState(
        self_buf=jax.tree_util.tree_map(jnp.asarray, x),
        peer_bufs=jax.tree_util.tree_map(init_peers, x),
        spec=WindowSpec(schedule=sched, name=name, partition=partition),
        assoc_self=jnp.ones(()) if associated_p else None,
        assoc_peers=jnp.zeros((k,)) if associated_p else None,
    )


def win_partition(state: WindowState):
    """The window buffers' declared partitioning: ``{leaf_name:
    PartitionSpec}`` resolved from the rule table at :func:`win_create`
    time, or ``None`` when the window was created undeclared
    (legacy/replicated).  This is the readback the BF-SHD002 lint checks
    against the LIVE rule table — a window created under one table and
    gossiped under another is a silent wire-shape mismatch."""
    part = state.spec.partition
    if part is None:
        return None
    return dict(part)


def win_associated_p(state: WindowState) -> jnp.ndarray:
    """The window's associated push-sum scalar ``p`` (reference: the
    associated-p readback)."""
    if state.assoc_self is None:
        raise ValueError(
            f"window {state.spec.name!r} was created without associated_p")
    return state.assoc_self


def win_free(state: WindowState) -> None:
    """Parity no-op — functional state is freed by dropping the reference."""
    return None


def _deliver(state: WindowState, payload, axis_name: str, *, accumulate: bool,
             backend: str = "auto",
             assoc_payload=None, op_name: str = "bf.win_deliver") -> WindowState:
    sched = state.spec.schedule
    # per-op B/E runtime spans (identity without an active timeline): B once
    # the payload is live, E once the landing buffers materialize — the
    # reference's per-tensor stage events for the window family
    payload = _tl.device_stage(payload, op_name, phase="B",
                               category="window", axis_name=axis_name)
    # blackbox round markers for the window family (identity unless
    # BLUEFOG_TPU_BLACKBOX=jit at trace time)
    bb_cid = _bb.next_collective_id(op_name.replace("bf.", ""))
    bb_fields = {"op": op_name.replace("bf.", ""), "cid": bb_cid,
                 "window": state.spec.name,
                 "bytes": _mt.tree_bytes(payload)}
    payload = _bb.traced_event(payload, "collective_begin",
                               fields=bb_fields, axis_name=axis_name)
    # auto_window_backend's stated conditions: the landing buffers are
    # persistent window state, so a payload with a leaf beyond one kernel's
    # cap routes to XLA (nothing splits it)
    from bluefog_tpu.ops import pallas_gossip

    backend = pallas_gossip.resolve_backend(backend, sched, payload)
    mask = _slot_mask(sched, axis_name)

    def per_leaf(peers, leaf):
        new_slots = []
        for k, perm in enumerate(sched.perms):
            recvd = lax.ppermute(leaf, axis_name, perm)
            slot = peers[k] + recvd if accumulate else recvd
            # Slots with no in-edge this rank got zeros from the permute:
            # keep the old buffer there.
            new_slots.append(jnp.where(mask[k], slot, peers[k]))
        return jnp.stack(new_slots) if new_slots else peers

    new_assoc = state.assoc_peers
    if state.assoc_self is not None and assoc_payload is not None:
        # the associated scalar rides the portable path on every backend —
        # a () payload is latency noise next to the tensor transfer
        new_assoc = per_leaf(state.assoc_peers, assoc_payload)

    if backend == "pallas":
        # distinct collective_id per leaf (leaf kernels may overlap on
        # hardware; each needs its own barrier semaphore), and a distinct
        # NAME-derived base per window — two windows delivered in one
        # jitted program (e.g. gradient-tracking's x and y windows) must
        # not share semaphores either.  Windows own ids [2048, ...).
        base = pallas_gossip.window_collective_id_base(state.spec.name)
        peer_leaves, treedef = jax.tree_util.tree_flatten(state.peer_bufs)
        if len(peer_leaves) > pallas_gossip.WINDOW_LEAF_CAP:
            raise ValueError(
                f"window {state.spec.name!r} has {len(peer_leaves)} leaves, "
                f"above the {pallas_gossip.WINDOW_LEAF_CAP}-leaf pallas cap "
                "(collective ids would bleed into the next window's bucket); "
                "use backend='xla' or fuse leaves")
        payload_leaves = treedef.flatten_up_to(payload)
        # trace-time lease record: the analysis audit sees this window's
        # id bucket next to every concurrent window lease in the
        # program (window buckets are disjoint by construction via the
        # CRC32 claim table; the lease makes that checkable, not assumed)
        from bluefog_tpu.analysis.registry import GLOBAL_LEASES

        GLOBAL_LEASES.lease(
            f"window:{state.spec.name}", base=base, used=len(peer_leaves),
            limit=base + pallas_gossip.WINDOW_LEAF_CAP, family="windows")
        outs = [
            pallas_gossip.deliver_pallas(
                leaf, peers, sched, axis_name, accumulate=accumulate,
                collective_id=base + idx,
            )
            for idx, (peers, leaf) in enumerate(zip(peer_leaves, payload_leaves))
        ]
        new_peers = jax.tree_util.tree_unflatten(treedef, outs)
    else:
        new_peers = jax.tree_util.tree_map(per_leaf, state.peer_bufs, payload)
    # wire accounting for the window family: every slot ships the full
    # payload tree (identity when metrics are off)
    new_peers = _mt.record_collective(
        new_peers, op=op_name.replace("bf.", ""),
        bytes_per_round=_mt.tree_bytes(payload) * sched.num_slots,
        messages_per_round=_mt.tree_leaf_count(payload) * sched.num_slots,
        schedule=sched.name, backend=backend,
        extra={"window": state.spec.name})
    new_peers = _bb.traced_event(new_peers, "collective_end",
                                 fields=bb_fields, axis_name=axis_name)
    new_peers = _tl.device_stage(new_peers, op_name, phase="E",
                                 category="window", axis_name=axis_name)
    return state.replace(peer_bufs=new_peers, assoc_peers=new_assoc)


def _weighted(dst_weight):
    """``leaf -> dst_weight * leaf`` with f32 arithmetic for low-precision
    leaves (push-sum fractions like 1/3 are not representable in bf16/f16 —
    the same concern the reference's fp16 custom MPI sum addresses,
    SURVEY.md §2.1 ``half.h``)."""

    def apply(leaf):
        acc = (jnp.float32 if leaf.dtype in (jnp.bfloat16, jnp.float16)
               else leaf.dtype)
        return (jnp.asarray(dst_weight, acc) * leaf.astype(acc)).astype(
            leaf.dtype)

    return apply


def _prepare_payload(state: WindowState, x, dst_weight):
    """Shared put/accumulate preamble: ``x=None`` ships the tracked
    ``self_buf`` (the associated-p mass-safe path); the associated scalar is
    weighted identically."""
    if x is not None and state.assoc_self is not None:
        # Shipping a tensor that is not the window's tracked state would
        # silently desynchronize the (x, p) push-sum recursion and bias
        # self_buf / p — a convergence bug with no visible symptom.  Force
        # callers through x=None (ships self_buf) or win_sync first.
        raise ValueError(
            f"window {state.spec.name!r} carries an associated push-sum "
            "scalar; pass x=None (ships self_buf) or win_sync(state, x) "
            "first so the (x, p) mass pair stays consistent")
    if x is None:
        x = state.self_buf
    payload = jax.tree_util.tree_map(_weighted(dst_weight), x)
    assoc = (None if state.assoc_self is None
             else _weighted(dst_weight)(state.assoc_self))
    return payload, assoc


def win_put(
    state: WindowState,
    x,
    axis_name: str,
    *,
    dst_weight=1.0,
    backend: str = "auto",
) -> WindowState:
    """Write ``dst_weight * x`` into every out-neighbor's landing buffer.

    ``dst_weight`` may be a traced scalar (push-sum sends ``1/(out_deg+1)``
    fractions — the reference's per-call ``dst_weights``).  The destination is
    not involved until it chooses to ``win_update``.  ``backend='pallas'``
    performs the transfer as a genuine one-sided RDMA on TPU slices.

    Associated-p windows: the scalar ``dst_weight * p`` ships alongside.
    Mass consistency requires the tensor shipped to be the window's tracked
    state — pass ``x=None`` (ships ``self_buf``) or ``win_sync`` the value in
    first; an explicit ``x`` on an associated-p window raises, because
    shipping an unrelated tensor silently desynchronizes the (x, p)
    recursions and biases ``self_buf / p``.
    """
    payload, assoc = _prepare_payload(state, x, dst_weight)
    return _deliver(state, payload, axis_name, accumulate=False,
                    backend=backend, assoc_payload=assoc,
                    op_name="bf.win_put")


def win_accumulate(
    state: WindowState,
    x,
    axis_name: str,
    *,
    dst_weight=1.0,
    backend: str = "auto",
) -> WindowState:
    """Like :func:`win_put` but adds into the destination buffer
    (``MPI_Accumulate(MPI_SUM)`` semantics).  The associated-p mass caveat in
    :func:`win_put` applies: pass ``x=None`` to ship ``self_buf``."""
    payload, assoc = _prepare_payload(state, x, dst_weight)
    return _deliver(state, payload, axis_name, accumulate=True,
                    backend=backend, assoc_payload=assoc,
                    op_name="bf.win_accumulate")


def win_get(state: WindowState, axis_name: str) -> WindowState:
    """Pull each in-neighbor's *published* value (their ``self_buf``) into the
    corresponding landing slot (one-sided read)."""
    return _deliver(state, state.self_buf, axis_name, accumulate=False,
                    assoc_payload=state.assoc_self, op_name="bf.win_get")


def win_update(
    state: WindowState,
    axis_name: str,
    *,
    self_weight=None,
    recv_weights=None,
):
    """Weighted-average self + landing buffers; publish and return the result.

    ``out = w_self * self_buf + sum_k w_k * peer_bufs[k]``, with weights from
    the window's topology by default (per-call overrides as in the reference).
    Returns ``(out, new_state)`` with ``self_buf = out``.
    """
    sched = state.spec.schedule
    i = lax.axis_index(axis_name)
    mask = _slot_mask(sched, axis_name)
    state = state.replace(self_buf=_tl.device_stage(
        state.self_buf, "bf.win_update", phase="B", category="window",
        axis_name=axis_name))

    def one(self_leaf, peers):
        acc_dt = jnp.float32 if self_leaf.dtype in (jnp.bfloat16, jnp.float16) else self_leaf.dtype
        if self_weight is None:
            w_self = jnp.asarray(sched.self_weights, acc_dt)[i]
        else:
            w_self = jnp.asarray(self_weight, acc_dt)
        if recv_weights is None:
            w_recv = jnp.asarray(sched.recv_weights, acc_dt)[i]
        else:
            w_recv = jnp.asarray(recv_weights, acc_dt)
        out = w_self * self_leaf.astype(acc_dt)
        for k in range(sched.num_slots):
            out = out + jnp.where(mask[k], w_recv[k], 0.0) * peers[k].astype(acc_dt)
        return out.astype(self_leaf.dtype)

    out = jax.tree_util.tree_map(one, state.self_buf, state.peer_bufs)
    # no wire transfer — count the merge rounds so deposit volume can be
    # read per consume (bytes/update = deposit bytes / update rounds)
    out = _mt.count(out, [("bf_window_update_rounds_total", 1.0)],
                    {"op": "win_update", "window": state.spec.name})
    out = _tl.device_stage(out, "bf.win_update", phase="E",
                           category="window", axis_name=axis_name)
    new_state = state.replace(self_buf=out)
    if state.assoc_self is not None:
        new_state = new_state.replace(
            assoc_self=one(state.assoc_self, state.assoc_peers))
    return out, new_state


def win_update_then_collect(state: WindowState, axis_name: str):
    """Sum-collect variant used by push-sum: ``out = self_buf + sum_k
    peer_bufs[k]`` over real slots, then **reset** the landing buffers to zero
    (accumulated mass must be consumed exactly once).  Returns
    ``(out, new_state)``.

    Mirrors the reference's ``win_update_then_collect`` (upstream —
    UNVERIFIED exact reset semantics; chosen to conserve push-sum mass).
    """
    sched = state.spec.schedule
    mask = _slot_mask(sched, axis_name)
    state = state.replace(self_buf=_tl.device_stage(
        state.self_buf, "bf.win_update_then_collect", phase="B",
        category="window", axis_name=axis_name))

    def one(self_leaf, peers):
        acc_dt = jnp.float32 if self_leaf.dtype in (jnp.bfloat16, jnp.float16) else self_leaf.dtype
        out = self_leaf.astype(acc_dt)
        for k in range(sched.num_slots):
            out = out + jnp.where(mask[k], 1.0, 0.0) * peers[k].astype(acc_dt)
        return out.astype(self_leaf.dtype)

    out = jax.tree_util.tree_map(one, state.self_buf, state.peer_bufs)
    out = _tl.device_stage(out, "bf.win_update_then_collect", phase="E",
                           category="window", axis_name=axis_name)
    zeroed = jax.tree_util.tree_map(jnp.zeros_like, state.peer_bufs)
    new_state = state.replace(self_buf=out, peer_bufs=zeroed)
    if state.assoc_self is not None:
        new_state = new_state.replace(
            assoc_self=one(state.assoc_self, state.assoc_peers),
            assoc_peers=jnp.zeros_like(state.assoc_peers))
    return out, new_state


def win_sync(state: WindowState, x=None) -> WindowState:
    """Publish a new local value without communicating (the reference's
    ``win_sync``-style refresh of the self window)."""
    if x is None:
        return state
    return state.replace(self_buf=jax.tree_util.tree_map(jnp.asarray, x))
