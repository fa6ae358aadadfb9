"""Collective ops for decentralized training, as SPMD primitives.

Every function here is designed to be called *inside* a ``shard_map``-ed (or
``pmap``-ed) function body, with ``axis_name`` naming the gossip mesh axis.
They are pure, jit-compatible, and work on arbitrary pytrees.

Reference parity (upstream-relative; see SURVEY.md §2.2/§3):

===========================================  ===================================
reference (``bluefog/torch/mpi_ops.py``)     here
===========================================  ===================================
``allreduce(tensor, average=True)``          :func:`allreduce`
``broadcast(tensor, root_rank)``             :func:`broadcast`
``allgather(tensor)``                        :func:`allgather`
``neighbor_allreduce(t, self_weight,         :func:`neighbor_allreduce`
  src_weights, dst_weights)``                  (weights via schedule or
                                               per-call overrides)
dynamic per-call topology                    :func:`neighbor_allreduce_dynamic`
``neighbor_allgather(t)``                    :func:`neighbor_allgather`
``hierarchical_neighbor_allreduce(t)``       :func:`hierarchical_neighbor_allreduce`
``barrier()``                                :func:`barrier`
``pair_gossip(t, target_rank)``              :func:`pair_gossip`
===========================================  ===================================

The reference executes the weighted average on the host CPU after
``MPI_Neighbor_allgatherv`` (SURVEY.md §3.2); here each ``ppermute`` is an
asynchronous ``collective-permute-start`` / ``-done`` pair that the DMA
engines serve while the core computes, and XLA fuses the weighted sum into
whatever consumes it (in a train step: the weight-gradient fusion of the
same leaf).  The background-thread/negotiation machinery of
``bluefog/common/operations.cc`` has no equivalent because XLA's static
schedule already guarantees every rank issues identical collectives in
identical order.
"""

from __future__ import annotations

import functools
from typing import Optional, Sequence

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax

from bluefog_tpu.blackbox import recorder as _bb
from bluefog_tpu.metrics import comm as _mt
from bluefog_tpu.metrics import registry as _mreg
from bluefog_tpu.topology.graphs import Topology
from bluefog_tpu.topology.schedule import GossipSchedule, build_schedule
from bluefog_tpu.utils import timeline as _tl

__all__ = [
    "allreduce",
    "allgather",
    "broadcast",
    "barrier",
    "fuse_apply",
    "neighbor_allreduce",
    "sharded_neighbor_allreduce",
    "neighbor_allgather",
    "neighbor_allreduce_dynamic",
    "neighbor_allreduce_aperiodic",
    "hierarchical_neighbor_allreduce",
    "hierarchical_neighbor_allreduce_2d",
    "pair_gossip",
]


def fuse_apply(fn, x, *, threshold_bytes: int = 4 << 20):
    """Tensor fusion: run a tree-polymorphic collective on a few flat
    buffers of bounded size instead of per-leaf.

    The reference batches small tensors through a fusion buffer so each
    negotiation round issues one wire transfer (`bluefog/common/tensor_queue`
    fusion-buffer manager, SURVEY.md §2.1).  The XLA analog: a model like
    ResNet-50 has ~160 parameter leaves, and leaf-wise gossip emits ~160
    ``ppermute`` ops per schedule slot — each with its own latency.  Packing
    the small leaves into 1-D buffers turns that into about nine
    bandwidth-bound transfers per slot, then splits back.

    ``threshold_bytes`` is the one rule for what is "large", for a leaf and
    for a buffer alike.  Leaves at or above it ship unfused: a large tensor
    is already one bandwidth-bound transfer, so concatenating it buys no
    latency and costs a full transient copy of the leaf (concat + split) in
    HBM — the same reason the reference's fusion buffer has a size cutoff.
    The smaller leaves of one dtype are packed in tree order, and a buffer
    is closed as soon as it holds ``threshold_bytes``: no buffer reaches
    twice the threshold, whatever the model.  A single buffer per dtype (the
    form before PR 31) made the fused group the largest thing exchanged —
    113.7 MB in the GPT-2 cell — and its concatenation, its landing buffers
    and its output stayed alive across the backward pass, 0.46 GB a chip;
    in pieces the size of the large leaves they are transients.  Set
    ``threshold_bytes=None`` to fuse everything into one buffer per dtype.

    ``fn`` must be shape-polymorphic and leaf-wise (all collectives here
    are).  Leaves keep their dtypes: each dtype group is fused separately, so
    mixed bf16/f32 trees behave exactly as unfused.
    """
    leaves, treedef = jax.tree_util.tree_flatten(x)
    if len(leaves) <= 1:
        return fn(x)
    large = float("inf") if threshold_bytes is None else threshold_bytes
    big, groups, open_group = [], [], {}  # open_group: dtype -> [bytes, idxs]
    for i, leaf in enumerate(leaves):
        a = jnp.asarray(leaf)
        nbytes = a.size * a.dtype.itemsize
        if nbytes >= large:
            big.append(i)
            continue
        group = open_group.setdefault(str(a.dtype), [0, []])
        group[0] += nbytes
        group[1].append(i)
        if group[0] >= large:
            groups.append(open_group.pop(str(a.dtype))[1])
    groups.extend(idxs for _, idxs in open_group.values())
    with jax.named_scope("bf.gossip.fuse"):
        bufs = [jnp.concatenate([jnp.asarray(leaves[i]).ravel() for i in idxs])
                for idxs in groups]
    # One fn call over {fused buffers} ∪ {large leaves}: fn is leaf-wise, so
    # large leaves ride the same collective unfused, with no extra copy.
    out_all = fn({"fused": bufs, "big": [leaves[i] for i in big]})
    out = [None] * len(leaves)
    for i, leaf in zip(big, out_all["big"]):
        out[i] = leaf
    with jax.named_scope("bf.gossip.split"):
        for idxs, buf in zip(groups, out_all["fused"]):
            off = 0
            for i in idxs:
                sz = int(np.prod(jnp.shape(leaves[i]), dtype=np.int64))
                out[i] = buf[off:off + sz].reshape(jnp.shape(leaves[i]))
                off += sz
    return jax.tree_util.tree_unflatten(treedef, out)


def _as_schedule(s) -> GossipSchedule:
    if isinstance(s, GossipSchedule):
        return s
    if isinstance(s, Topology):
        return build_schedule(s)
    raise TypeError(f"expected Topology or GossipSchedule, got {type(s)}")


def _rank_weights(
    schedule: GossipSchedule,
    axis_name: str,
    self_weight,
    recv_weights,
    dtype,
):
    """Per-rank (self_w, recv_w[K]) as traced scalars, f32 accumulate dtype."""
    i = lax.axis_index(axis_name)
    if self_weight is None:
        self_w = jnp.asarray(schedule.self_weights, dtype=dtype)[i]
    else:
        self_w = jnp.asarray(self_weight, dtype=dtype)
    if recv_weights is None:
        recv_w = jnp.asarray(schedule.recv_weights, dtype=dtype)[i]
    else:
        recv_w = jnp.asarray(recv_weights, dtype=dtype)
    return self_w, recv_w


def _acc_dtype(x) -> jnp.dtype:
    # Accumulate gossip averages in f32 when inputs are low-precision: the
    # mixing weights (1/3, 1/5, ...) are not representable in bf16 and the
    # repeated averaging is exactly the kind of op that drifts.
    if x.dtype in (jnp.bfloat16, jnp.float16):
        return jnp.float32
    return x.dtype


def neighbor_allreduce(
    x,
    schedule,
    axis_name: str,
    *,
    self_weight=None,
    recv_weights=None,
    send_weights=None,
):
    """Weighted average with in-neighbors: ``out_i = w_ii x_i + sum_k w_ik x_k``.

    Args:
      x: array or pytree; each rank's local value.
      schedule: :class:`GossipSchedule` (or a :class:`Topology`, lowered on the
        fly — prefer pre-building at setup time).
      axis_name: the gossip mesh axis.
      self_weight / recv_weights: optional per-call traced overrides (scalar /
        ``(num_slots,)``), the analog of the reference's per-call
        ``self_weight=/src_weights=`` arguments.  Because only *weights* change
        (the ppermute pattern is static), overriding them does not recompile.
      send_weights: optional per-call SENDER-side scaling, the analog of the
        reference's ``dst_weights=`` (each rank scales what it ships per out
        slot before the transfer): ``(num_slots,)`` traced — slot ``k``'s
        payload leaves this rank as ``send_weights[k] * x`` — or a
        ``(size, num_slots)`` table, from which each rank takes its own row.
        The receiver's ``recv_weights`` then apply on top, exactly as
        upstream composes ``src_weights`` x ``dst_weights``.

    Lowering: one ``lax.ppermute`` per schedule slot and leaf (a single ICI
    rotation for circulant graphs) + fused multiply-adds.  On a TPU each is
    a ``collective-permute-start`` / ``-done`` pair: the DMA engines move
    the bytes while the TensorCore runs whatever XLA schedules between the
    two, and the received buffers land in HBM.  XLA:TPU keeps about five
    such transfers in flight: it opens the first five at the top of the
    program and each further one where an earlier one closes, next to the
    consumer of its result — so a caller who wants the exchange hidden
    gives it heavy consumers (the optimizers do: the mix is fused into each
    leaf's weight-gradient fusion) and pieces of bounded size
    (:func:`fuse_apply`).

    This is the one transport.  Until PR 47 a ``backend=`` argument could
    route the exchange to a fused Pallas RDMA kernel, which folded the
    weighted sum into the arrival path in VMEM; but a Pallas kernel IS the
    core's program while its transfers fly, so none of it overlapped
    compute.  On four v5e chips with GPT-2 small's tree 35.6 ms of a
    186.7 ms step were exposed under the kernels against 21.6 of 172.7
    under the collective-permutes, which also held 0.22 GiB less memory; a
    ResNet-50 tree showed 5.35 ms against 4.47 (PERF.md §6, PR 31).  No
    payload a caller sent reached the kernel after that, and it went.
    """
    sched = _as_schedule(schedule)
    # runtime per-round spans (B once inputs are live, E once the weighted
    # merge materializes; per-rank lanes) — identity unless a timeline is
    # active at trace time.  The reference emits the analogous per-tensor
    # enqueue/execute stage events from operations.cc (SURVEY.md §5).
    x = _tl.device_stage(x, "bf.neighbor_allreduce", phase="B",
                         axis_name=axis_name)
    # blackbox flight-recorder round markers (identity unless
    # BLUEFOG_TPU_BLACKBOX=jit at trace time): a begin without a matching
    # end in a hang dump names the exact round this rank wedged in.  The
    # cid is a trace-time call-site id, identical across SPMD processes,
    # so bfblackbox-tpu can align ranks on (step, cid).
    bb_cid = _bb.next_collective_id("neighbor_allreduce")
    bb_fields = {"op": "neighbor_allreduce", "cid": bb_cid,
                 "schedule": sched.name, "bytes": _mt.tree_bytes(x)}
    x = _bb.traced_event(x, "collective_begin", fields=bb_fields,
                         axis_name=axis_name)

    send_w = (None if send_weights is None
              else jnp.asarray(send_weights, jnp.float32))
    if send_w is not None and send_w.ndim == 2:
        # (size, num_slots) table: take this rank's row
        send_w = send_w[lax.axis_index(axis_name)]

    def one(leaf):
        acc_dt = _acc_dtype(leaf)
        self_w, recv_w = _rank_weights(sched, axis_name, self_weight, recv_weights, acc_dt)
        out = self_w * leaf.astype(acc_dt)
        for k, perm in enumerate(sched.perms):
            # named_scope: per-slot attribution in jax.profiler/Perfetto
            # device traces (free — trace-time metadata only)
            with jax.named_scope(f"bf.neighbor_allreduce.slot{k}"):
                shipped = (leaf if send_w is None
                           else (send_w[k].astype(acc_dt)
                                 * leaf.astype(acc_dt)).astype(leaf.dtype))
                recvd = lax.ppermute(shipped, axis_name, perm)
                out = out + recv_w[k] * recvd.astype(acc_dt)
        return out.astype(leaf.dtype)

    with jax.named_scope("bf.gossip.exchange"):
        out = jax.tree_util.tree_map(one, x)
    # one ppermute per slot per leaf; every slot ships the full tree
    out = _mt.record_collective(
        out, op="neighbor_allreduce",
        bytes_per_round=_mt.tree_bytes(x) * sched.num_slots,
        messages_per_round=_mt.tree_leaf_count(x) * sched.num_slots,
        schedule=sched.name, backend="xla")
    out = _bb.traced_event(out, "collective_end", fields=bb_fields,
                           axis_name=axis_name)
    return _tl.device_stage(out, "bf.neighbor_allreduce", phase="E",
                            axis_name=axis_name)


def sharded_neighbor_allreduce(
    x,
    schedule,
    axis_name: str,
    *,
    rule_table=None,
    specs=None,
    inner_axes=None,
    **kwargs,
):
    """Gossip-of-meshes :func:`neighbor_allreduce`: the gossip step of a
    hybrid ``(bf, fsdp/tp)`` mesh, where every leaf of ``x`` is a LOCAL
    SHARD and each inner-mesh coordinate exchanges only its own shard
    with the same coordinate on neighbor meshes.

    Call inside ``shard_map`` over the hybrid mesh.  Because gossip is
    element-wise, shard-locality needs no extra collectives — the
    ``ppermute`` over ``axis_name`` already moves only the local shard;
    what this wrapper adds is the RULE-TABLE contract and its
    enforcement:

    - ``rule_table`` (a :class:`bluefog_tpu.sharding.RuleTable`) or a
      pre-resolved ``specs`` pytree declares every leaf's partitioning —
      the same single source of truth that shards the parameters,
      optimizer state, and window buffers.  A leaf whose spec mentions
      ``axis_name`` raises: sharding the gossip axis would mix
      *different* model coordinates across ranks, which is never what a
      decentralized-DP outer loop means.
    - **No gather on the hot path** is a checked property, not a hope:
      the BF-SHD lint pass traces this function over a hybrid mesh and
      walks the jaxpr for ``all_gather``/``all_to_all`` over the inner
      axes (BF-SHD003).
    - Per-execution wire accounting: ``bf_sharded_bytes_total`` (shard
      bytes this rank ships per round) and
      ``bf_gather_bytes_saved_total`` (what gather-then-gossip would
      have added), labelled with the joined inner axes.

    ``inner_axes``: ``{axis: size}`` of the inner mesh (used for the
    savings accounting and axis validation); remaining ``kwargs`` pass
    through to :func:`neighbor_allreduce`.
    """
    from bluefog_tpu.sharding.mesh import shard_size_ratio
    from bluefog_tpu.sharding.rules import (RuleTable as _RuleTable,
                                            spec_mentions as _spec_mentions)

    if rule_table is not None and specs is not None:
        raise ValueError("pass rule_table OR specs, not both")
    if isinstance(rule_table, _RuleTable):
        specs = rule_table.resolve_tree(x)
    elif rule_table is not None and specs is None:
        specs = rule_table  # duck-typed: an already-resolved spec tree
    if specs is None:
        raise ValueError(
            "sharded_neighbor_allreduce needs the rule table (or its "
            "resolved specs) — the single-source-of-truth contract; use "
            "plain neighbor_allreduce for unsharded trees")

    from jax.sharding import PartitionSpec as _P

    spec_leaves = jax.tree_util.tree_leaves(
        specs, is_leaf=lambda s: isinstance(s, _P))
    leaves = jax.tree_util.tree_leaves(x)
    if len(spec_leaves) != len(leaves):
        raise ValueError(f"spec tree has {len(spec_leaves)} leaves, "
                         f"x has {len(leaves)}")
    axes = dict(inner_axes or {})
    for spec in spec_leaves:
        if _spec_mentions(spec, axis_name):
            raise ValueError(
                f"spec {spec} shards a leaf over the GOSSIP axis "
                f"{axis_name!r}; gossip mixes same-coordinate "
                "elements across ranks — shard over inner axes only")

    sched = _as_schedule(schedule)
    out = neighbor_allreduce(x, sched, axis_name, **kwargs)

    # what this rank ships is already shard-local (leaf shapes here are
    # the local shards); the gather-then-gossip wire would ship each
    # leaf's full size instead
    shard_bytes = _mt.tree_bytes(x) * sched.num_slots
    saved = 0
    for leaf, spec in zip(leaves, spec_leaves):
        size = getattr(leaf, "size", None)
        dtype = getattr(leaf, "dtype", None)
        if size is None or dtype is None:
            continue
        ratio = shard_size_ratio(spec, axes)
        saved += int(size) * int(dtype.itemsize) * (ratio - 1)
    axis_label = "+".join(sorted(axes)) if axes else ""
    counters = [("bf_sharded_bytes_total", float(shard_bytes))]
    if saved:
        counters.append(
            ("bf_gather_bytes_saved_total", float(saved * sched.num_slots)))
    return _mt.count(out, counters,
                     labels={"leaf": "<spmd>", "axis": axis_label})


def neighbor_allreduce_dynamic(
    x,
    schedules: Sequence,
    step,
    axis_name: str,
):
    """Time-varying gossip: applies ``schedules[step % len(schedules)]``.

    ``step`` may be a traced integer (e.g. the optimizer step counter): the
    period's schedules are compiled once into a ``lax.switch`` — this is the
    recompilation-free answer to the reference's per-call ``src_weights``
    dynamic-topology API (SURVEY.md §7 hard-part #2).
    """
    scheds = [_as_schedule(s) for s in schedules]
    if len(scheds) == 1:
        return neighbor_allreduce(x, scheds[0], axis_name)
    branches = [
        functools.partial(neighbor_allreduce, schedule=s, axis_name=axis_name)
        for s in scheds
    ]
    # Timeline spans are hoisted OUTSIDE the switch: an ordered io_callback
    # inside a branch threads an effect token through the branch signature
    # and XLA's sharding propagation CHECK-fails (hard process abort) on the
    # extra entry parameter.  Exactly one branch runs per step, so one outer
    # B/E pair carries the same information.
    x = _tl.device_stage(x, "bf.neighbor_allreduce", phase="B",
                         axis_name=axis_name)
    # Blackbox round markers, hoisted like spans/metrics (one begin/end
    # per step, outside the switch), with the TRACED step recorded so the
    # cross-rank merge aligns rounds on real step numbers.
    bb_cid = _bb.next_collective_id("neighbor_allreduce_dynamic")
    bb_fields = {"op": "neighbor_allreduce_dynamic", "cid": bb_cid,
                 "schedule": f"dynamic[{len(scheds)}]",
                 "bytes": _mt.tree_bytes(x)}
    bb_step = {"step": jnp.asarray(step, jnp.float32)}
    x = _bb.traced_event(x, "collective_begin", fields=bb_fields,
                         traced=bb_step, axis_name=axis_name)
    # Metrics follow the same hoisting rule as timeline spans: the inner
    # neighbor_allreduce records are suppressed inside the switch (exactly
    # one branch runs per step) and ONE outer record carries the taken
    # branch's cost, selected by the traced phase index — so the counter
    # reflects the actual schedule of every step without per-branch
    # callbacks.
    idx = jnp.asarray(step) % len(scheds)
    with _tl.suppress_device_stage(), _mt.suppress_comm_metrics(), \
            _bb.suppress_blackbox():
        out = lax.switch(idx, branches, x)
    if _mreg.current() is not None:
        payload = _mt.tree_bytes(x)
        leaves = _mt.tree_leaf_count(x)
        out = _mt.record_collective(
            out, op="neighbor_allreduce_dynamic",
            bytes_per_round=jnp.asarray(
                [payload * s.num_slots for s in scheds], jnp.float32)[idx],
            messages_per_round=jnp.asarray(
                [leaves * s.num_slots for s in scheds], jnp.float32)[idx],
            schedule=f"dynamic[{len(scheds)}]", backend="xla")
    out = _bb.traced_event(out, "collective_end", fields=bb_fields,
                           traced=bb_step, axis_name=axis_name)
    return _tl.device_stage(out, "bf.neighbor_allreduce", phase="E",
                            axis_name=axis_name)


def neighbor_allreduce_aperiodic(x, mixing_matrix, axis_name: str,
                                 max_rotations: Optional[int] = None):
    """Gossip with an **arbitrary per-call topology** in one compile:
    ``out_i = sum_j W[i, j] x_j`` for any row-stochastic ``W`` within the
    full graph — the TPU answer to the reference's per-call
    ``self_weight=/src_weights=`` arguments when the *edge set* (not just
    the weights) changes every step (``bluefog/torch/mpi_ops.py``;
    SURVEY.md §7 hard-part #2).

    How (default, ``max_rotations=None``): any directed graph on ``n``
    ranks decomposes into the ``n-1`` circulant rotations.  Each rotation's
    ``ppermute`` is compiled once (static pattern); which rotations
    actually run is decided at **runtime** by a ``lax.cond`` on whether any
    edge of that rotation carries nonzero weight — changing ``W`` between
    calls re-selects rotations and re-weights edges with zero
    recompilation, and unused rotations cost nothing (the cond executes
    only the taken branch).  A one-peer dynamic exp2 step therefore pays
    for exactly one ICI rotation, not ``n-1``.

    **Degree-capped form** (``max_rotations=D``): the full decomposition
    emits ``n-1`` conditional ppermutes — a program-size/compile-time cost
    that grows linearly with the mesh (127 at a v5p-128 target).  With a
    cap, the program instead materializes ``D`` rotation slots whose shifts
    are selected at RUNTIME (the active rotations of ``W``, lowest shift
    first), each executed as a conditional power-of-two ppermute chain
    (``ceil(log2 n)`` static ppermutes per slot, only the set bits of the
    shift taken) — ``D * ceil(log2 n)`` ppermutes total, e.g. 21 instead of
    127 for ``D=3, n=128``.  Dynamic graphs are typically degree-bounded
    (one-peer: 1 rotation/step; static exp2: log2 n), so ``D`` small is the
    common case.  Contract: if ``W`` activates MORE than ``D`` rotations,
    every output is poisoned with NaN (fail-loud — silently dropping edges
    would corrupt the consensus direction instead).

    Args:
      x: array or pytree; each rank's local value.
      mixing_matrix: ``(n, n)`` array, ``W[i, j]`` = the weight rank ``i``
        applies to rank ``j``'s value (``W[i, i]`` the self weight).  Must be
        **replicated** across ranks (pass it with a ``P()`` spec): the
        rotation-used predicates must agree on every rank or the program
        deadlocks, exactly as mismatched ``src_weights`` deadlock the
        reference's MPI negotiation.
      max_rotations: program-size cap ``D`` (see above), or None for the
        full ``n-1``-rotation decomposition.

    See :func:`bluefog_tpu.topology.dynamic.one_peer_exp2_mixing_matrix` for
    a jittable step->W builder.
    """
    n = lax.axis_size(axis_name)
    i = lax.axis_index(axis_name)
    W = jnp.asarray(mixing_matrix, jnp.float32)
    if W.shape != (n, n):
        raise ValueError(f"mixing_matrix shape {W.shape} != ({n}, {n})")
    rows = jnp.arange(n)

    if max_rotations is not None:
        return _aperiodic_capped(x, W, axis_name, n, i, rows,
                                 int(max_rotations))

    def one(leaf):
        acc_dt = _acc_dtype(leaf)
        out = W[i, i].astype(acc_dt) * leaf.astype(acc_dt)
        for s in range(1, n):
            srcs = (rows - s) % n
            rot_w = W[rows, srcs]          # (n,) rotation-s edge weights
            used = jnp.any(rot_w != 0.0)   # replicated: same on all ranks
            perm = [(a, (a + s) % n) for a in range(n)]

            def fold(o):
                recvd = lax.ppermute(leaf, axis_name, perm)
                return o + rot_w[i].astype(acc_dt) * recvd.astype(acc_dt)

            out = lax.cond(used, fold, lambda o: o, out)
        return out.astype(leaf.dtype)

    out = jax.tree_util.tree_map(one, x)
    if _mreg.current() is not None:
        # data-dependent cost: only ACTIVE rotations run their ppermute —
        # the traced active count rides the record as an operand, so the
        # counter reflects each call's actual edge set
        shifts_all = jnp.arange(1, n)
        srcs_all = (rows[None, :] - shifts_all[:, None]) % n
        active = jnp.sum(jnp.any(W[rows[None, :], srcs_all] != 0.0,
                                 axis=1)).astype(jnp.float32)
        out = _mt.record_collective(
            out, op="neighbor_allreduce_aperiodic",
            bytes_per_round=active * _mt.tree_bytes(x),
            messages_per_round=active * _mt.tree_leaf_count(x),
            schedule=f"aperiodic[n={n}]", backend="xla")
    return out


def _aperiodic_capped(x, W, axis_name: str, n: int, i, rows, cap: int):
    """Degree-capped aperiodic gossip body: ``cap`` runtime-shift rotation
    slots, each a conditional power-of-two ppermute chain."""
    if cap < 1:
        raise ValueError(f"max_rotations must be >= 1, got {cap}")
    cap = min(cap, n - 1)  # only n-1 distinct rotations exist
    # per-rotation activity, computed once for the whole tree (replicated
    # on every rank, as the predicates must be)
    shifts_all = jnp.arange(1, n)                        # (n-1,)
    srcs_all = (rows[None, :] - shifts_all[:, None]) % n  # (n-1, n)
    rot_w_all = W[rows[None, :], srcs_all]               # (n-1, n)
    used = jnp.any(rot_w_all != 0.0, axis=1)             # (n-1,)
    used_count = used.sum()
    # the first `cap` ACTIVE shifts, lowest first (stable argsort of the
    # inactive mask); slots beyond the active count are disabled
    order = jnp.argsort(~used, stable=True)[:cap]
    sel_shift = shifts_all[order]                        # (cap,) runtime
    sel_active = used[order]
    overflow = used_count > cap

    # power-of-two ppermute chain: shift s executes only its set bits
    pows = []
    p = 1
    while p < n:
        pows.append(p)
        p *= 2

    def one(leaf):
        acc_dt = _acc_dtype(leaf)
        out = W[i, i].astype(acc_dt) * leaf.astype(acc_dt)
        for d in range(cap):
            shift = sel_shift[d]

            def fold(o, shift=shift):
                rot = leaf
                for pk in pows:
                    perm = [(a, (a + pk) % n) for a in range(n)]
                    bit = (shift // pk) % 2 == 1

                    def hop(r, perm=perm):
                        return lax.ppermute(r, axis_name, perm)

                    rot = lax.cond(bit, hop, lambda r: r, rot)
                # this rank's weight for the arriving value: W[i, i-shift]
                w = W[i, (i - shift) % n]
                return o + w.astype(acc_dt) * rot.astype(acc_dt)

            out = lax.cond(sel_active[d], fold, lambda o: o, out)
        # exceeding the cap must be LOUD inside jit: poison, don't drop
        out = jnp.where(overflow, jnp.full_like(out, jnp.nan), out)
        return out.astype(leaf.dtype)

    out = jax.tree_util.tree_map(one, x)
    if _mreg.current() is not None:
        # each active slot hops once per SET BIT of its runtime shift
        popcount = sum(((sel_shift // p) % 2 for p in pows),
                       start=jnp.zeros_like(sel_shift))
        hops = jnp.sum(jnp.where(sel_active, popcount, 0)).astype(
            jnp.float32)
        out = _mt.record_collective(
            out, op="neighbor_allreduce_aperiodic",
            bytes_per_round=hops * _mt.tree_bytes(x),
            messages_per_round=hops * _mt.tree_leaf_count(x),
            schedule=f"aperiodic[n={n},cap={cap}]", backend="xla")
    return out


def neighbor_allgather(x, schedule, axis_name: str):
    """Collect in-neighbor tensors.

    Returns ``(slots, mask)`` where ``slots`` has shape ``(K, *x.shape)`` —
    slot ``k`` holds the payload from the rank feeding this rank's slot ``k``
    (``schedule.recv_src``) — and ``mask`` is a ``(K,)`` bool validity mask.

    SPMD deviation from the reference: ``bf.neighbor_allgather`` returns a
    ragged concatenation sized by the rank's in-degree; XLA requires static
    uniform shapes, so irregular graphs are padded to ``K = num_slots`` with
    the mask marking real entries.  For regular graphs ``mask`` is all-True
    and ``slots`` is exactly the reference's output (stacked, slot order =
    ``recv_src`` order).
    """
    sched = _as_schedule(schedule)
    i = lax.axis_index(axis_name)
    parts = []
    for perm in sched.perms:
        parts.append(lax.ppermute(x, axis_name, perm))
    slots = jnp.stack(parts) if parts else jnp.zeros((0,) + x.shape, x.dtype)
    mask = jnp.asarray(sched.recv_src >= 0)[i]
    return slots, mask


def allreduce(x, axis_name: str, *, average: bool = True):
    """Global sum (or mean, the reference default) over the gossip axis."""

    def one(leaf):
        s = lax.psum(leaf, axis_name)
        if average:
            n = lax.axis_size(axis_name)
            s = (s.astype(_acc_dtype(leaf)) / n).astype(leaf.dtype)
        return s

    bb_cid = _bb.next_collective_id("allreduce")
    bb_fields = {"op": "allreduce", "cid": bb_cid,
                 "bytes": _mt.tree_bytes(x)}
    x = _bb.traced_event(x, "collective_begin", fields=bb_fields,
                         axis_name=axis_name)
    out = jax.tree_util.tree_map(one, x)
    out = _mt.record_collective(
        out, op="allreduce", bytes_per_round=_mt.tree_bytes(x),
        messages_per_round=_mt.tree_leaf_count(x), backend="xla")
    return _bb.traced_event(out, "collective_end", fields=bb_fields,
                            axis_name=axis_name)


def allgather(x, axis_name: str, *, axis: int = 0, tiled: bool = False):
    """Gather every rank's tensor; concatenated along ``axis`` when ``tiled``
    (the reference concatenates along dim 0), stacked otherwise."""
    return jax.tree_util.tree_map(
        lambda leaf: lax.all_gather(leaf, axis_name, axis=axis, tiled=tiled), x
    )


def broadcast(x, root_rank: int, axis_name: str):
    """Every rank gets ``root_rank``'s value.

    Lowered as a masked ``psum`` — on ICI this is a single optimized reduction
    rather than a host-coordinated tree as in the reference's MPI path.
    """
    i = lax.axis_index(axis_name)

    def one(leaf):
        contrib = jnp.where(i == root_rank, leaf, jnp.zeros_like(leaf))
        # psum promotes bool to int32; restore the input dtype (per-dtype
        # parity with the reference's typed entry points, SURVEY.md §2.1)
        return lax.psum(contrib, axis_name).astype(leaf.dtype)

    return jax.tree_util.tree_map(one, x)


def barrier(axis_name: str):
    """Synchronization point for API parity (``bf.barrier``).  SPMD programs
    are implicitly ordered by their collectives; this issues a trivial psum so
    the host can block on its completion."""
    return lax.psum(jnp.zeros((), jnp.float32), axis_name)


def pair_gossip(x, axis_name: str, *, perm, self_weight=0.5):
    """Average with a single partner: ``out = w x + (1-w) x_partner``.

    Mirrors the reference's ``pair_gossip(tensor, target_rank)`` (upstream,
    UNVERIFIED name — see SURVEY.md §2.2).  SPMD deviation: the reference's
    per-process ``target_rank`` argument becomes the full pairing ``perm`` —
    a list of ``(src, dst)`` pairs covering every participating rank (all
    ranks must agree on the pairing, which the reference leaves implicit).
    Ranks absent from ``perm``'s destinations keep their own value.
    """
    got = lax.ppermute(x, axis_name, perm)
    w = jnp.asarray(self_weight, _acc_dtype(x))
    # Ranks not named as a destination receive zeros; they keep their own value.
    dsts = sorted(d for _, d in perm)
    i = lax.axis_index(axis_name)
    is_dst = jnp.isin(i, jnp.asarray(dsts))
    mixed = (w * x.astype(w.dtype) + (1 - w) * got.astype(w.dtype)).astype(x.dtype)
    return jnp.where(is_dst, mixed, x)


def hierarchical_neighbor_allreduce(
    x,
    machine_schedule,
    axis_name: str,
    *,
    local_size: int,
    self_weight=None,
    recv_weights=None,
):
    """Intra-machine exact average, then machine-level gossip.

    The reference's ``hierarchical_neighbor_allreduce`` (confirmed in
    BASELINE.json): ranks on one machine first average exactly (reference:
    local-communicator allreduce; here: ``psum`` over ``axis_index_groups``
    riding intra-slice ICI), then machines gossip along ``machine_schedule``
    with every local rank exchanging with its counterpart on the peer machine
    (reference: cross-communicator neighbor collective; here the machine-graph
    permutation is expanded to a rank-level ppermute).  All local ranks end
    with identical values, as upstream guarantees.

    ``machine_schedule`` is a schedule/topology over ``n_machines =
    axis_size / local_size`` nodes.
    """
    x = _tl.device_stage(x, "bf.hierarchical_neighbor_allreduce", phase="B",
                         axis_name=axis_name)
    msched = _as_schedule(machine_schedule)
    bb_cid = _bb.next_collective_id("hierarchical_neighbor_allreduce")
    bb_fields = {"op": "hierarchical_neighbor_allreduce", "cid": bb_cid,
                 "schedule": msched.name, "bytes": _mt.tree_bytes(x)}
    x = _bb.traced_event(x, "collective_begin", fields=bb_fields,
                         axis_name=axis_name)
    n_machines = msched.size
    groups = [list(range(m * local_size, (m + 1) * local_size)) for m in range(n_machines)]

    # Expand machine-level matchings to rank-level: each local rank talks to
    # the same local rank on the peer machine (pure ICI/DCN-parallel lanes).
    rank_perms = []
    for perm in msched.perms:
        rp = []
        for (src_m, dst_m) in perm:
            for l in range(local_size):
                rp.append((src_m * local_size + l, dst_m * local_size + l))
        rank_perms.append(tuple(rp))

    i = lax.axis_index(axis_name)
    machine = i // local_size

    def one(leaf):
        acc_dt = _acc_dtype(leaf)
        local_avg = (lax.psum(leaf, axis_name, axis_index_groups=groups).astype(acc_dt)
                     / local_size)
        if self_weight is None:
            self_w = jnp.asarray(msched.self_weights, acc_dt)[machine]
        else:
            self_w = jnp.asarray(self_weight, acc_dt)
        if recv_weights is None:
            recv_w = jnp.asarray(msched.recv_weights, acc_dt)[machine]
        else:
            recv_w = jnp.asarray(recv_weights, acc_dt)
        out = self_w * local_avg
        for k, rp in enumerate(rank_perms):
            with jax.named_scope(f"bf.hierarchical.machine_slot{k}"):
                recvd = lax.ppermute(local_avg.astype(leaf.dtype), axis_name, rp)
                out = out + recv_w[k] * recvd.astype(acc_dt)
        return out.astype(leaf.dtype)

    out = jax.tree_util.tree_map(one, x)
    # accounted: the machine-hop ppermutes (every local lane ships the
    # local average per machine slot); the intra-machine psum is ICI-local
    out = _mt.record_collective(
        out, op="hierarchical_neighbor_allreduce",
        bytes_per_round=_mt.tree_bytes(x) * len(rank_perms),
        messages_per_round=_mt.tree_leaf_count(x) * len(rank_perms),
        schedule=msched.name, backend="xla")
    out = _bb.traced_event(out, "collective_end", fields=bb_fields,
                           axis_name=axis_name)
    return _tl.device_stage(out, "bf.hierarchical_neighbor_allreduce",
                            phase="E", axis_name=axis_name)


def hierarchical_neighbor_allreduce_2d(
    x,
    machine_schedule,
    *,
    machine_axis: str,
    local_axis: str,
    self_weight=None,
    recv_weights=None,
):
    """Hierarchical gossip over a two-level ``(machine, local)`` mesh.

    The multi-slice deployment form of :func:`hierarchical_neighbor_allreduce`:
    instead of one flat mesh axis with ``axis_index_groups``, the mesh is
    ``Mesh(devices.reshape(n_machines, local_size), (machine_axis,
    local_axis))`` — in a real multi-slice/multi-pod job the outer axis maps
    onto DCN and the inner axis onto each slice's ICI (reference analog: the
    cross vs local MPI communicators of ``bluefog/common/mpi_context.cc``,
    SURVEY.md §2.4).  The local exact average is a ``pmean`` riding ICI; the
    machine gossip is a ``ppermute`` *over the machine axis itself*, so every
    local lane crosses DCN in parallel and the counterpart-lane pairing of
    the flat path holds by construction.
    """
    # lane id = linearized (machine, local) rank, matching the flat path
    x = _tl.device_stage(x, "bf.hierarchical_neighbor_allreduce_2d", phase="B",
                         axis_name=(machine_axis, local_axis))
    msched = _as_schedule(machine_schedule)
    bb_cid = _bb.next_collective_id("hierarchical_neighbor_allreduce_2d")
    bb_fields = {"op": "hierarchical_neighbor_allreduce_2d", "cid": bb_cid,
                 "schedule": msched.name, "bytes": _mt.tree_bytes(x)}
    x = _bb.traced_event(x, "collective_begin", fields=bb_fields,
                         axis_name=(machine_axis, local_axis))

    def one(leaf):
        acc_dt = _acc_dtype(leaf)
        local_avg = lax.pmean(leaf.astype(acc_dt), local_axis)
        m = lax.axis_index(machine_axis)
        if self_weight is None:
            self_w = jnp.asarray(msched.self_weights, acc_dt)[m]
        else:
            self_w = jnp.asarray(self_weight, acc_dt)
        if recv_weights is None:
            recv_w = jnp.asarray(msched.recv_weights, acc_dt)[m]
        else:
            recv_w = jnp.asarray(recv_weights, acc_dt)
        out = self_w * local_avg
        for k, perm in enumerate(msched.perms):
            with jax.named_scope(f"bf.hierarchical2d.machine_slot{k}"):
                recvd = lax.ppermute(local_avg.astype(leaf.dtype),
                                     machine_axis, perm)
                out = out + recv_w[k] * recvd.astype(acc_dt)
        return out.astype(leaf.dtype)

    out = jax.tree_util.tree_map(one, x)
    out = _mt.record_collective(
        out, op="hierarchical_neighbor_allreduce_2d",
        bytes_per_round=_mt.tree_bytes(x) * len(msched.perms),
        messages_per_round=_mt.tree_leaf_count(x) * len(msched.perms),
        schedule=msched.name, backend="xla")
    out = _bb.traced_event(out, "collective_end", fields=bb_fields,
                           axis_name=(machine_axis, local_axis))
    return _tl.device_stage(out, "bf.hierarchical_neighbor_allreduce_2d",
                            phase="E", axis_name=(machine_axis, local_axis))
