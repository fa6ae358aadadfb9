"""Pallas TPU kernels: gossip exchange and one-sided delivery via inter-chip
RDMA (``pltpu.make_async_remote_copy``).

This is the genuinely *native* layer of the build (SURVEY.md §7 "one-sided
layer"): the TPU equivalent of the reference's MPI RMA machinery
(``MPIController::WinPut/WinAccumulate/WinUpdate`` over ``MPI_Win`` memory,
``bluefog/common/mpi_controller.cc``, upstream-relative) and of its NCCL
send/recv emulation (``nccl_controller.cc``).

Two kernels, both restricted to **circulant schedules** (every standard
topology: ring, exponential-2, symmetric-exp, one-peer phases — each slot is
a uniform shift ``i -> i+s``, i.e. one ICI rotation):

- :func:`neighbor_allreduce_pallas` — fused gossip: per slot, RDMA the local
  tensor into the in-neighbor slot buffer of ``rank+s``, then reduce the
  arrived slots into ``w_self*x + sum_k w_k*recv_k`` in row tiles.  Against
  the XLA lowering (ppermute + adds) this fuses the weighted reduction into
  the arrival path — one VMEM pass instead of ppermute-materialize-then-add.
- :func:`deliver_pallas` — the ``win_put``/``win_accumulate`` transport:
  RDMA payloads into per-slot landing buffers (the reference's per-neighbor
  ``MPI_Win`` memory) without touching them on the compute path; the receiver
  consumes them only at ``win_update``.

Synchronization protocol (per kernel invocation, SPMD-symmetric):
1. barrier handshake with in/out-neighbors via the global barrier semaphore —
   guarantees the remote landing buffers are live before any RDMA starts
   (the reference gets this from ``MPI_Win_create``'s collective epoch);
2. per-slot RDMA start; sender tracks ``send_sem``, the in-flight data
   signals the *receiver's* ``recv_sem`` on arrival;
3. ``wait_recv`` on every slot before reducing (gossip), per slot before
   storing (deliver).

Use on real multi-chip slices; single-chip and CPU meshes route to the XLA
path automatically (``backend='auto'``), and so does every gossip payload
beyond one kernel's cap: the kernels occupy the TensorCore while they wait,
XLA's asynchronous collective-permutes do not (``auto_gossip_backend``).
"""

from __future__ import annotations

import functools
from typing import Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax

from bluefog_tpu.topology.schedule import GossipSchedule

__all__ = [
    "is_pallas_supported",
    "circulant_shifts",
    "auto_gossip_backend",
    "auto_max_bytes",
    "leaf_wire_bytes",
    "leaf_chunk_count",
    "neighbor_allreduce_pallas",
    "deliver_pallas",
    "DEFAULT_AUTO_MAX_BYTES",
]

_LANES = 128
_SUBLANES = 8

# Per-kernel-invocation payload cap in on-wire bytes (bf16 leaves ship as
# bf16, the rest as f32), and the routing cutoff of backend='auto'
# (auto_gossip_backend, condition 4): a gossip tree of at most this many
# bytes, or a window payload whose every leaf is, rides the kernels; larger
# ones take XLA's asynchronous collective-permutes, which the core does not
# wait for.  Under a FORCED backend='pallas' the gossip op layer chunks any
# larger leaf into <=cap pieces (one kernel per chunk, distinct collective
# ids): every received payload accumulates in VMEM on arrival and never
# lands in HBM (~2*num_slots HBM passes fewer than ppermute-then-add), at
# the price of a core that runs nothing else meanwhile.  The WINDOW deliver
# path cannot chunk (its landing buffers are persistent window state).
# Override with BLUEFOG_TPU_PALLAS_MAX_BYTES.
DEFAULT_AUTO_MAX_BYTES = 4 << 20

# VMEM plan.  One invocation keeps every whole-payload buffer it touches
# resident at once: gossip holds x, out and one landing buffer per slot
# (num_slots + 2 copies); deliver holds x plus the old and the new slot
# buffers (2*num_slots + 1 copies).  The reduction itself runs in
# _TILE_ROWS-row tiles, so its f32 temporaries are tile-sized whatever the
# wire dtype and fit in _VMEM_HEADROOM.  (Reducing the whole block in one
# expression made the compiler spill ~3 f32 payload copies: on v5e:2x2 a
# two-slot f32 kernel was refused from 3.3 MiB and a bf16 one from 2.7 MiB,
# under a 4 MiB cap.)  Each pallas_call states its vmem_limit_bytes from this
# arithmetic — never below the compiler's own default — and a plan beyond
# _VMEM_BUDGET (half a v5e core's 128 MiB; only a schedule of 14+ slots at
# the default cap gets there) routes 'auto' to XLA and makes a forced
# 'pallas' raise.
_VMEM_BUDGET = 64 << 20
_VMEM_HEADROOM = 2 << 20
_VMEM_COMPILER_DEFAULT = 16 << 20
_TILE_ROWS = 512


def vmem_plan_bytes(payload_bytes: int, num_slots: int, *,
                    deliver: bool = False) -> int:
    """VMEM one kernel invocation needs for a ``payload_bytes`` on-wire
    payload over ``num_slots`` slots (see the plan above)."""
    copies = 2 * num_slots + 1 if deliver else num_slots + 2
    return copies * int(payload_bytes) + _VMEM_HEADROOM


def _vmem_limit(block, num_slots: int, *, deliver: bool = False) -> int:
    """The ``vmem_limit_bytes`` a kernel over ``block`` states."""
    payload = block.size * block.dtype.itemsize
    plan = vmem_plan_bytes(payload, num_slots, deliver=deliver)
    if plan > _VMEM_BUDGET:
        raise ValueError(
            f"pallas {'deliver' if deliver else 'gossip'} kernel over "
            f"{num_slots} slots needs {plan} bytes of VMEM for a "
            f"{payload}-byte payload, beyond the {_VMEM_BUDGET}-byte "
            "budget; use backend='xla' for a schedule this dense")
    return max(plan, _VMEM_COMPILER_DEFAULT)


def auto_max_bytes() -> int:
    """The effective per-invocation payload cap (env-overridable).  A
    non-positive override means "never use the kernels": auto routes to
    XLA (the pre-chunking de facto meaning of ``MAX_BYTES=0``), and a
    *forced* ``backend='pallas'`` raises in :func:`leaf_chunk_count`."""
    import os

    return int(os.environ.get("BLUEFOG_TPU_PALLAS_MAX_BYTES",
                              DEFAULT_AUTO_MAX_BYTES))


def leaf_wire_bytes(leaf) -> int:
    """On-wire byte size of one leaf (bf16 ships as bf16, the rest as f32)."""
    dt = _wire_dtype(getattr(leaf, "dtype", jnp.float32))
    return (int(np.prod(jnp.shape(leaf), dtype=np.int64))
            * np.dtype(dt).itemsize)


def leaf_chunk_count(leaf, limit: Optional[int] = None) -> int:
    """How many kernel invocations the gossip op layer will split ``leaf``
    into (1 = unchunked)."""
    limit = auto_max_bytes() if limit is None else limit
    if limit <= 0:
        raise ValueError(
            "BLUEFOG_TPU_PALLAS_MAX_BYTES must be positive to run the "
            f"pallas backend (got {limit}); a non-positive cap only makes "
            "sense as 'never use the kernels', which backend='auto' "
            "honors by routing to XLA")
    return max(1, -(-leaf_wire_bytes(leaf) // limit))


def on_tpu_platform() -> bool:
    """THE platform predicate for every pallas-transport gate (auto routing
    and :func:`is_pallas_supported` both call this — one predicate, one
    answer): the default JAX backend is ``'tpu'``."""
    return jax.default_backend() == "tpu"


def auto_gossip_backend(sched: GossipSchedule, x, *,
                        chunkable: bool = True) -> str:
    """Resolve ``backend='auto'`` for a gossip call: ``'pallas'`` or ``'xla'``.

    The stated conditions under which auto selects the RDMA kernels — ALL
    must hold:

    1. a real TPU backend (:func:`on_tpu_platform`) — CPU test meshes
       always take XLA (the non-interpret kernel cannot run there);
    2. multi-device mesh (``sched.size > 1``) — nothing to exchange on one
       chip;
    3. a circulant schedule (every slot one uniform ICI rotation — all
       standard topologies; irregular graphs take XLA);
    4. a payload one kernel carries.  A Pallas kernel IS the TensorCore's
       program while it runs: handshake, RDMA, ``wait_recv``, and no matmul
       beside it — 168 us a 4 MiB kernel on a v5e, 30 ms a step for a
       GPT-2-small tree in 180 of them, none of it hidden (PERF.md, PR 31).
       ``lax.ppermute`` lowers to ``collective-permute-start`` / ``-done``
       and the DMA engines move the bytes while the core computes.  So
       gossip callers (``chunkable=True``, the default) get the kernels
       only while the WHOLE tree's on-wire bytes fit one invocation's cap
       (:data:`DEFAULT_AUTO_MAX_BYTES`): there the core waits one handshake
       and at most one cap's transfer, and the weighted sum never leaves
       VMEM.  Anything larger — any optimizer tree — takes XLA.  The window
       deliver path (``chunkable=False``) has nothing beside it to hide
       behind and cannot chunk its persistent landing buffers: for it the
       cap is a per-leaf cutoff, every leaf at most the cap;
    5. not disabled via ``BLUEFOG_TPU_PALLAS_GOSSIP=0`` (the kill switch if
       a deployment's kernels misbehave);
    6. the kernel's VMEM plan (:func:`vmem_plan_bytes`) for the largest
       leaf fits the budget — true for every schedule under 14 slots at
       the default cap.

    A forced ``backend='pallas'`` skips this rule: the op layer then splits
    leaves beyond the cap into cap-sized chunks, one kernel each.
    """
    import os

    if os.environ.get("BLUEFOG_TPU_PALLAS_GOSSIP", "1") in ("0", "off"):
        return "xla"
    if sched.size <= 1 or not circulant_shifts(sched):
        return "xla"  # non-circulant (None) or zero slots (()): both XLA
    if not on_tpu_platform():
        return "xla"
    leaves = jax.tree_util.tree_leaves(x)
    if not leaves:
        return "xla"
    limit = auto_max_bytes()
    if limit <= 0:
        return "xla"  # explicit "never use the kernels" override
    wire = [leaf_wire_bytes(l) for l in leaves]
    if (sum(wire) if chunkable else max(wire)) > limit:
        return "xla"
    if vmem_plan_bytes(max(wire), sched.num_slots,
                       deliver=not chunkable) > _VMEM_BUDGET:
        return "xla"  # schedule too dense for the kernel's VMEM plan
    return "pallas"


def resolve_backend(backend: str, sched: GossipSchedule, x, *,
                    chunkable: bool = True) -> str:
    """Shared backend resolution for every transport that can ride the RDMA
    kernels (gossip and the window deliver path): validate the name and
    resolve ``'auto'`` through :func:`auto_gossip_backend`.  Window callers
    pass ``chunkable=False`` (persistent landing buffers cannot chunk)."""
    if backend not in ("auto", "xla", "pallas"):
        raise ValueError(
            f"unknown backend {backend!r}; expected 'auto', 'xla', or "
            "'pallas'")
    if backend == "auto":
        return auto_gossip_backend(sched, x, chunkable=chunkable)
    return backend


def interpret_requested() -> bool:
    """``BLUEFOG_TPU_PALLAS_INTERPRET=1`` runs every pallas-backend op
    through TPU-interpret emulation — the full op layers (gossip pytree
    dispatch, window deliver with collective-id bases and masks) execute
    their REAL pallas branch on a CPU mesh in CI, not just the bare
    kernels the dedicated kernel tests cover.  Never set in production
    (emulation is orders of magnitude slower).  Kernel entry points
    resolve this themselves when ``interpret`` is left at None."""
    import os

    return os.environ.get("BLUEFOG_TPU_PALLAS_INTERPRET") == "1"


# The interpret machinery models barrier semaphores with int16 ids; the
# name-derived window bases (up to ~2^30) overflow it.  Under EMULATION
# ONLY, ids are remapped through a trace-time table assigning compact
# sequential ids — collision-free by construction (a raw modulo would fold
# distinct windows onto one semaphore, the exact hazard the bases exist to
# prevent).  Hardware keeps the full id space.
_interpret_ids: dict = {}


def _interpret_collective_id(cid: int) -> int:
    return _interpret_ids.setdefault(cid, 1 + len(_interpret_ids))


# CRC32 bucket -> window name that claimed it.  Two window names hashing to
# the same bucket would silently share barrier semaphores inside one jitted
# program — the exact hazard the name-derived base exists to prevent — so the
# first claimant owns the bucket and any later colliding name raises.
WINDOW_LEAF_CAP = 1024  # collective ids per window; bases are spaced this far
_claimed_bases: dict = {}


def window_collective_id_base(name: str) -> int:
    """Deterministic per-window collective-id base.  Two windows delivered
    in ONE jitted program must not share barrier semaphores, so each
    window's leaf kernels enumerate from a name-derived base: 2048 + a CRC32
    bucket spaced :data:`WINDOW_LEAF_CAP` apart (the per-call leaf cap).
    Stable across processes (CRC32, not Python hash) as SPMD requires.

    Bucket collisions (distinct names, same CRC32 bucket) raise rather than
    silently sharing semaphores; rename one window to resolve.
    """
    import zlib

    bucket = zlib.crc32(name.encode()) % (1 << 20)
    owner = _claimed_bases.setdefault(bucket, name)
    if owner != name:
        raise ValueError(
            f"window name {name!r} collides with existing window {owner!r} "
            f"in collective-id bucket {bucket} (CRC32 % 2^20); the two would "
            "share barrier semaphores if delivered in one program — rename "
            "one of them (or win_free the other first if it no longer "
            "exists)")
    return 2048 + bucket * WINDOW_LEAF_CAP


def release_window_collective_id(name: str) -> None:
    """Release ``name``'s collective-id bucket (call when the window is
    freed): the semaphore-sharing hazard only exists between windows
    delivered in one program, so a FREED window must not poison its bucket
    for the rest of a long-lived process (per-experiment window names would
    otherwise accumulate spurious collisions)."""
    import zlib

    bucket = zlib.crc32(name.encode()) % (1 << 20)
    if _claimed_bases.get(bucket) == name:
        del _claimed_bases[bucket]


def circulant_shifts(sched: GossipSchedule) -> Optional[Tuple[int, ...]]:
    """Per-slot uniform shifts, or None if the schedule is not circulant."""
    if not sched.is_circulant:
        return None
    shifts = []
    for perm in sched.perms:
        (src0, dst0) = perm[0]
        shifts.append((dst0 - src0) % sched.size)
    return tuple(shifts)


def is_pallas_supported(sched: GossipSchedule) -> bool:
    """True when the schedule can ride the RDMA kernels (circulant, at least
    one slot, more than one device) and we are on a real TPU backend (the
    shared :func:`on_tpu_platform` predicate — never disagrees with
    ``'auto'`` routing about the same schedule)."""
    if sched.size <= 1 or not circulant_shifts(sched):
        return False
    return on_tpu_platform()


def _wire_dtype(dtype) -> jnp.dtype:
    """On-wire dtype for a leaf: bf16 leaves ship as bf16 (HALF the ICI
    bytes — the dominant cost of a gossip step on real hardware), everything
    else as f32.  Reduction precision per kernel: the GOSSIP kernel's
    weighted sum runs in f32 regardless of wire (the XLA path's
    ``_acc_dtype`` discipline); the deliver kernel's ``acc`` mode adds in
    the wire dtype, exactly matching the portable window path's leaf-dtype
    slot adds (``ops/windows.py`` ``peers[k] + recvd``)."""
    return jnp.bfloat16 if dtype == jnp.bfloat16 else jnp.float32


def _pad_to_tiles(flat: jnp.ndarray) -> Tuple[jnp.ndarray, int]:
    """Pad a flat vector to a tile-aligned (R, 128) 2-D block (min sublane
    count is dtype-dependent: 8 for f32, 16 for bf16)."""
    n = flat.shape[0]
    sublanes = _SUBLANES * (4 // max(flat.dtype.itemsize, 1))
    per_tile = sublanes * _LANES
    padded = int(np.ceil(max(n, 1) / per_tile)) * per_tile
    flat = jnp.pad(flat, (0, padded - n))
    return flat.reshape(padded // _LANES, _LANES), n


def _for_each_row_tile(n_rows: int, body) -> None:
    """Run ``body(rows)`` over ``[0, n_rows)`` in :data:`_TILE_ROWS`-row
    slices — a ``fori_loop`` over the full tiles plus one static remainder —
    so a whole-block reduction never materializes payload-sized values."""
    from jax.experimental import pallas as pl

    full, rem = divmod(n_rows, _TILE_ROWS)
    if full:
        def step(t, carry):
            body(pl.ds(pl.multiple_of(t * _TILE_ROWS, _TILE_ROWS),
                       _TILE_ROWS))
            return carry

        lax.fori_loop(0, full, step, 0)
    if rem:
        body(pl.ds(full * _TILE_ROWS, rem))


def _make_exchange_kernel(shifts: Sequence[int], size: int, axis_name: str,
                          mode: str, num_slots: int):
    """Build the shared RDMA exchange kernel body.

    mode: 'gossip'  -> out = sw*x + sum_k rw[k]*recv_k
          'put'     -> out_bufs[k] = recv_k (masked by mask[k])
          'acc'     -> out_bufs[k] = old_bufs[k] + recv_k (masked)
    """
    from jax.experimental import pallas as pl  # deferred: TPU-only path
    from jax.experimental.pallas import tpu as pltpu

    n_shifts = len(shifts)

    if mode == "gossip":
        def kernel(x_ref, sw_ref, rw_ref, out_ref, comm_buf, send_sem, recv_sem):
            my = lax.axis_index(axis_name)
            barrier = pltpu.get_barrier_semaphore()
            # handshake: signal each IN-neighbor (my-s) that my landing
            # buffers are live; the n_shifts signals I then wait for come
            # from my OUT-neighbors (my+s) — exactly my RDMA targets — so
            # no RDMA starts before its destination buffer exists
            for s in shifts:
                pltpu.semaphore_signal(
                    barrier, inc=1,
                    device_id=lax.rem(my - s + size, size),
                    device_id_type=pltpu.DeviceIdType.LOGICAL,
                )
            pltpu.semaphore_wait(barrier, n_shifts)

            rdmas = []
            for k, s in enumerate(shifts):
                rdma = pltpu.make_async_remote_copy(
                    src_ref=x_ref,
                    dst_ref=comm_buf.at[k],
                    send_sem=send_sem.at[k],
                    recv_sem=recv_sem.at[k],
                    device_id=lax.rem(my + s, size),
                    device_id_type=pltpu.DeviceIdType.LOGICAL,
                )
                rdma.start()
                rdmas.append(rdma)

            for rdma in rdmas:
                rdma.wait_recv()
            # accumulate in f32 whatever the wire dtype (bf16 wires halve
            # ICI bytes; the reduction still runs at f32, matching the XLA
            # path's _acc_dtype discipline)
            sw = sw_ref[0, 0]
            rws = [rw_ref[0, k] for k in range(n_shifts)]

            def reduce_tile(rows):
                acc = sw * x_ref[rows, :].astype(jnp.float32)
                for k in range(n_shifts):
                    acc = acc + rws[k] * comm_buf[k, rows, :].astype(
                        jnp.float32)
                out_ref[rows, :] = acc.astype(out_ref.dtype)

            _for_each_row_tile(x_ref.shape[0], reduce_tile)
            for rdma in rdmas:
                rdma.wait_send()
        return kernel

    def kernel(x_ref, bufs_ref, mask_ref, out_bufs_ref, send_sem, recv_sem):
        my = lax.axis_index(axis_name)
        barrier = pltpu.get_barrier_semaphore()
        # signal in-neighbors; wait for out-neighbors (RDMA targets) — see
        # the gossip kernel's handshake comment
        for s in shifts:
            pltpu.semaphore_signal(
                barrier, inc=1,
                device_id=lax.rem(my - s + size, size),
                device_id_type=pltpu.DeviceIdType.LOGICAL,
            )
        pltpu.semaphore_wait(barrier, n_shifts)

        rdmas = []
        for k, s in enumerate(shifts):
            rdma = pltpu.make_async_remote_copy(
                src_ref=x_ref,
                dst_ref=out_bufs_ref.at[k],
                send_sem=send_sem.at[k],
                recv_sem=recv_sem.at[k],
                device_id=lax.rem(my + s, size),
                device_id_type=pltpu.DeviceIdType.LOGICAL,
            )
            rdma.start()
            rdmas.append(rdma)
        for k, rdma in enumerate(rdmas):
            rdma.wait_recv()
            keep = mask_ref[0, k] > 0

            def store_tile(rows, k=k, keep=keep):
                landed = out_bufs_ref[k, rows, :]
                old = bufs_ref[k, rows, :]
                new = old + landed if mode == "acc" else landed
                out_bufs_ref[k, rows, :] = jnp.where(keep, new, old)

            _for_each_row_tile(x_ref.shape[0], store_tile)
        for rdma in rdmas:
            rdma.wait_send()
    return kernel


def neighbor_allreduce_pallas(
    x: jnp.ndarray,
    sched: GossipSchedule,
    axis_name: str,
    *,
    self_weight=None,
    recv_weights=None,
    collective_id: int = 7,
    interpret: Optional[bool] = None,
):
    """Fused RDMA gossip step for one array (any shape/dtype; internally a
    padded tile-aligned (R,128) block in the wire dtype — bf16 for bf16
    leaves, halving ICI bytes; f32 otherwise; accumulation is f32 either
    way).  Call inside ``shard_map``; circulant schedules only — gate with
    :func:`is_pallas_supported`."""
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    shifts = circulant_shifts(sched)
    if shifts is None:
        raise ValueError("pallas gossip requires a circulant schedule")
    if interpret is None:
        interpret = interpret_requested()
    if interpret:
        collective_id = _interpret_collective_id(collective_id)
    if not shifts:
        # 0-slot schedule (no edges — e.g. identity mixing): nothing to
        # exchange, and a grid-free kernel with zero receive buffers cannot
        # lower; the gossip degenerates to the self-weighted term.
        i0 = lax.axis_index(axis_name)
        sw0 = (jnp.asarray(sched.self_weights, jnp.float32)[i0]
               if self_weight is None
               else jnp.asarray(self_weight, jnp.float32))
        return (sw0 * x.astype(jnp.float32)).astype(x.dtype)
    n = sched.size
    i = lax.axis_index(axis_name)

    orig_dtype = x.dtype
    wire = _wire_dtype(orig_dtype)
    with jax.named_scope("bf.gossip.pack"):
        flat = x.astype(wire).reshape(-1)
        block, true_len = _pad_to_tiles(flat)

        sw = (jnp.asarray(sched.self_weights, jnp.float32)[i]
              if self_weight is None else jnp.asarray(self_weight, jnp.float32))
        rw = (jnp.asarray(sched.recv_weights, jnp.float32)[i]
              if recv_weights is None else jnp.asarray(recv_weights, jnp.float32))
        sw = sw.reshape(1, 1)
        rw = rw.reshape(1, -1)

    kernel = _make_exchange_kernel(shifts, n, axis_name, "gossip", sched.num_slots)
    # No scope and no name= here (nor around any caller): the kernel's name
    # in the device trace is the innermost name-stack entry above this call,
    # and the benchmark finds the gossip kernels by it (``shard_map.N``).
    out = pl.pallas_call(
        kernel,
        out_shape=jax.ShapeDtypeStruct(block.shape, wire),
        in_specs=[
            pl.BlockSpec(memory_space=pltpu.VMEM),
            pl.BlockSpec((1, 1), memory_space=pltpu.SMEM),
            pl.BlockSpec((1, len(shifts)), memory_space=pltpu.SMEM),
        ],
        out_specs=pl.BlockSpec(memory_space=pltpu.VMEM),
        scratch_shapes=[
            pltpu.VMEM((len(shifts),) + block.shape, wire),
            pltpu.SemaphoreType.DMA((len(shifts),)),
            pltpu.SemaphoreType.DMA((len(shifts),)),
        ],
        compiler_params=pltpu.CompilerParams(
            has_side_effects=True, collective_id=collective_id,
            vmem_limit_bytes=_vmem_limit(block, len(shifts)),
        ),
        interpret=pltpu.InterpretParams() if interpret else False,
    )(block, sw, rw)
    with jax.named_scope("bf.gossip.unpack"):
        return out.reshape(-1)[:true_len].reshape(x.shape).astype(orig_dtype)


def deliver_pallas(
    payload: jnp.ndarray,
    bufs: jnp.ndarray,
    sched: GossipSchedule,
    axis_name: str,
    *,
    accumulate: bool,
    collective_id: int = 8,
    interpret: Optional[bool] = None,
):
    """RDMA transport for ``win_put``/``win_accumulate``: sends ``payload`` to
    every out-neighbor's landing slot; returns the updated ``(K, ...)`` slot
    buffers for this rank.  Circulant schedules only."""
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    shifts = circulant_shifts(sched)
    if shifts is None:
        raise ValueError("pallas deliver requires a circulant schedule")
    if interpret is None:
        interpret = interpret_requested()
    if interpret:
        collective_id = _interpret_collective_id(collective_id)
    if not shifts:
        # 0-slot schedule: no out-neighbors, nothing lands — the slot
        # buffers are unchanged (a zero-receive grid-free kernel cannot
        # lower; same degenerate case as neighbor_allreduce_pallas).
        return bufs
    n = sched.size
    i = lax.axis_index(axis_name)

    orig_dtype = payload.dtype
    wire = _wire_dtype(orig_dtype)
    k_slots = len(shifts)
    with jax.named_scope("bf.gossip.pack"):
        flat = payload.astype(wire).reshape(-1)
        block, true_len = _pad_to_tiles(flat)
        bufs_f = bufs.astype(wire).reshape(k_slots, -1)
        bufs_block = jnp.pad(
            bufs_f, ((0, 0), (0, block.size - bufs_f.shape[1]))
        ).reshape((k_slots,) + block.shape)

        mask = jnp.asarray(sched.recv_src >= 0, jnp.int32)[i].reshape(1, -1)

    kernel = _make_exchange_kernel(
        shifts, n, axis_name, "acc" if accumulate else "put", sched.num_slots
    )
    # no scope, no name=: as in neighbor_allreduce_pallas
    out_bufs = pl.pallas_call(
        kernel,
        out_shape=jax.ShapeDtypeStruct(bufs_block.shape, wire),
        in_specs=[
            pl.BlockSpec(memory_space=pltpu.VMEM),
            pl.BlockSpec(memory_space=pltpu.VMEM),
            pl.BlockSpec((1, k_slots), memory_space=pltpu.SMEM),
        ],
        out_specs=pl.BlockSpec(memory_space=pltpu.VMEM),
        scratch_shapes=[
            pltpu.SemaphoreType.DMA((k_slots,)),
            pltpu.SemaphoreType.DMA((k_slots,)),
        ],
        compiler_params=pltpu.CompilerParams(
            has_side_effects=True, collective_id=collective_id,
            vmem_limit_bytes=_vmem_limit(block, k_slots, deliver=True),
        ),
        interpret=pltpu.InterpretParams() if interpret else False,
    )(block, bufs_block, mask)
    with jax.named_scope("bf.gossip.unpack"):
        return (out_bufs.reshape(k_slots, -1)[:, : bufs_f.shape[1]]
                .reshape(bufs.shape).astype(orig_dtype))
