"""Pallas TPU kernel for one-sided window delivery via inter-chip RDMA
(``pltpu.make_async_remote_copy``), and what routes a window op to it.

This is the TPU equivalent of the reference's MPI RMA machinery
(``MPIController::WinPut/WinAccumulate/WinUpdate`` over ``MPI_Win`` memory,
``bluefog/common/mpi_controller.cc``, upstream-relative; SURVEY.md §7
"one-sided layer").

What the file holds:

- :func:`deliver_pallas` — the ``win_put``/``win_accumulate`` transport,
  restricted to **circulant schedules** (every standard topology: ring,
  exponential-2, symmetric-exp, one-peer phases — each slot is a uniform
  shift ``i -> i+s``, i.e. one ICI rotation): RDMA payloads into per-slot
  landing buffers (the reference's per-neighbor ``MPI_Win`` memory) without
  touching them on the compute path; the receiver consumes them only at
  ``win_update``.
- :func:`auto_window_backend` / :func:`resolve_backend` — when
  ``backend='auto'`` of ``ops/windows.py`` takes the kernel;
- :func:`window_collective_id_base` — one barrier-semaphore id bucket a
  window name, so two windows delivered in one program share none.

Synchronization protocol (per kernel invocation, SPMD-symmetric):
1. barrier handshake with in/out-neighbors via the global barrier semaphore —
   guarantees the remote landing buffers are live before any RDMA starts
   (the reference gets this from ``MPI_Win_create``'s collective epoch);
2. per-slot RDMA start; sender tracks ``send_sem``, the in-flight data
   signals the *receiver's* ``recv_sem`` on arrival;
3. ``wait_recv`` per slot before storing.

Gossip (``ops/collectives.py::neighbor_allreduce``) does not come here.  Until
PR 47 this file also held a fused gossip kernel; on the chip it lost to XLA's
asynchronous collective-permutes at every payload a caller sent (a Pallas
kernel IS the TensorCore's program while its transfers fly, so none of the
exchange hid behind the backward pass: PERF.md §6, PR 31), and it went.  No
cell runs a window op yet (ROADMAP S6), so the same question is open for
this kernel.
"""

from __future__ import annotations

from typing import Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax

from bluefog_tpu.topology.schedule import GossipSchedule
from bluefog_tpu.tracing import startup

__all__ = [
    "is_pallas_supported",
    "circulant_shifts",
    "auto_window_backend",
    "auto_max_bytes",
    "leaf_wire_bytes",
    "deliver_pallas",
    "DEFAULT_AUTO_MAX_BYTES",
]

_LANES = 128
_SUBLANES = 8

# Per-kernel-invocation payload cap in on-wire bytes (bf16 leaves ship as
# bf16, the rest as f32), and the routing cutoff of backend='auto'
# (auto_window_backend, condition 4): a window payload whose every leaf is
# at most this many bytes rides the kernel, a larger one takes XLA's
# collective-permutes.  The landing buffers are persistent window state, so
# a leaf cannot be split into pieces.  Override with
# BLUEFOG_TPU_PALLAS_MAX_BYTES.
DEFAULT_AUTO_MAX_BYTES = 4 << 20

# VMEM plan.  One invocation keeps every whole-payload buffer it touches
# resident at once: x plus the old and the new slot buffers
# (2*num_slots + 1 copies).  The stores run in _TILE_ROWS-row tiles, so
# their temporaries are tile-sized whatever the wire dtype and fit in
# _VMEM_HEADROOM.  (Handling the whole block in one expression made the
# compiler spill ~3 f32 payload copies: on v5e:2x2 a two-slot f32 kernel was
# refused from 3.3 MiB and a bf16 one from 2.7 MiB, under a 4 MiB cap.)
# The pallas_call states its vmem_limit_bytes from this arithmetic — never
# below the compiler's own default — and a plan beyond _VMEM_BUDGET (half a
# v5e core's 128 MiB; only a schedule of 8+ slots at the default cap gets
# there) routes 'auto' to XLA and makes a forced 'pallas' raise.
_VMEM_BUDGET = 64 << 20
_VMEM_HEADROOM = 2 << 20
_VMEM_COMPILER_DEFAULT = 16 << 20
_TILE_ROWS = 512


def vmem_plan_bytes(payload_bytes: int, num_slots: int) -> int:
    """VMEM one kernel invocation needs for a ``payload_bytes`` on-wire
    payload over ``num_slots`` slots (see the plan above)."""
    return (2 * num_slots + 1) * int(payload_bytes) + _VMEM_HEADROOM


def _vmem_limit(block, num_slots: int) -> int:
    """The ``vmem_limit_bytes`` the kernel over ``block`` states."""
    payload = block.size * block.dtype.itemsize
    plan = vmem_plan_bytes(payload, num_slots)
    if plan > _VMEM_BUDGET:
        raise ValueError(
            f"pallas deliver kernel over {num_slots} slots needs {plan} "
            f"bytes of VMEM for a {payload}-byte payload, beyond the "
            f"{_VMEM_BUDGET}-byte budget; use backend='xla' for a schedule "
            "this dense")
    return max(plan, _VMEM_COMPILER_DEFAULT)


def auto_max_bytes() -> int:
    """The effective per-leaf payload cap (env-overridable).  A non-positive
    override means "never use the kernel": auto routes to XLA."""
    import os

    return int(os.environ.get("BLUEFOG_TPU_PALLAS_MAX_BYTES",
                              DEFAULT_AUTO_MAX_BYTES))


def leaf_wire_bytes(leaf) -> int:
    """On-wire byte size of one leaf (bf16 ships as bf16, the rest as f32)."""
    dt = _wire_dtype(getattr(leaf, "dtype", jnp.float32))
    return (int(np.prod(jnp.shape(leaf), dtype=np.int64))
            * np.dtype(dt).itemsize)


def on_tpu_platform() -> bool:
    """THE platform predicate for every pallas-transport gate (auto routing
    and :func:`is_pallas_supported` both call this — one predicate, one
    answer): the default JAX backend is ``'tpu'``."""
    return jax.default_backend() == "tpu"


def auto_window_backend(sched: GossipSchedule, payload) -> str:
    """Resolve ``backend='auto'`` for a window delivery (``win_put`` /
    ``win_accumulate``): ``'pallas'`` or ``'xla'``.

    The stated conditions under which auto selects the RDMA kernel — ALL
    must hold:

    1. a real TPU backend (:func:`on_tpu_platform`) — CPU test meshes
       always take XLA (the non-interpret kernel cannot run there);
    2. multi-device mesh (``sched.size > 1``) — nothing to exchange on one
       chip;
    3. a circulant schedule (every slot one uniform ICI rotation — all
       standard topologies; irregular graphs take XLA) with at least one
       slot;
    4. every leaf of the payload at most one invocation's cap on the wire
       (:func:`auto_max_bytes`): the landing buffers are persistent window
       state and cannot be split, and the kernel holds whole payloads in
       VMEM;
    5. not disabled via ``BLUEFOG_TPU_PALLAS_GOSSIP=0`` (the kill switch if
       a deployment's kernels misbehave);
    6. the kernel's VMEM plan (:func:`vmem_plan_bytes`) for the largest
       leaf fits the budget — true for every schedule under 8 slots at
       the default cap.

    A forced ``backend='pallas'`` skips this rule.
    """
    import os

    if os.environ.get("BLUEFOG_TPU_PALLAS_GOSSIP", "1") in ("0", "off"):
        return "xla"
    if sched.size <= 1 or not circulant_shifts(sched):
        return "xla"  # non-circulant (None) or zero slots (()): both XLA
    if not on_tpu_platform():
        return "xla"
    leaves = jax.tree_util.tree_leaves(payload)
    if not leaves:
        return "xla"
    limit = auto_max_bytes()
    if limit <= 0:
        return "xla"  # explicit "never use the kernel" override
    largest = max(leaf_wire_bytes(l) for l in leaves)
    if largest > limit:
        return "xla"
    if vmem_plan_bytes(largest, sched.num_slots) > _VMEM_BUDGET:
        return "xla"  # schedule too dense for the kernel's VMEM plan
    return "pallas"


def resolve_backend(backend: str, sched: GossipSchedule, payload) -> str:
    """The window transport's backend resolution: validate the name and
    resolve ``'auto'`` through :func:`auto_window_backend`."""
    if backend not in ("auto", "xla", "pallas"):
        raise ValueError(
            f"unknown backend {backend!r}; expected 'auto', 'xla', or "
            "'pallas'")
    if backend == "auto":
        return auto_window_backend(sched, payload)
    return backend


def interpret_requested() -> bool:
    """``BLUEFOG_TPU_PALLAS_INTERPRET=1`` runs every pallas-backend window
    op through TPU-interpret emulation — the full op layer (window deliver
    with collective-id bases and masks) executes its REAL pallas branch on
    a CPU mesh in CI, not just the bare kernel the dedicated kernel tests
    cover.  Never set in production (emulation is orders of magnitude
    slower).  :func:`deliver_pallas` resolves this itself when
    ``interpret`` is left at None."""
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
    """True when the schedule can ride the RDMA kernel (circulant, at least
    one slot, more than one device) and we are on a real TPU backend (the
    shared :func:`on_tpu_platform` predicate — never disagrees with
    ``'auto'`` routing about the same schedule)."""
    if sched.size <= 1 or not circulant_shifts(sched):
        return False
    return on_tpu_platform()


def _wire_dtype(dtype) -> jnp.dtype:
    """On-wire dtype for a leaf: bf16 leaves ship as bf16 (HALF the ICI
    bytes), everything else as f32.  The deliver kernel's ``acc`` mode adds
    in the wire dtype, exactly matching the portable window path's
    leaf-dtype slot adds (``ops/windows.py`` ``peers[k] + recvd``)."""
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
    so a whole-block store never materializes payload-sized values."""
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
                          accumulate: bool):
    """Build the RDMA exchange kernel body: ``out_bufs[k] = recv_k`` (put) or
    ``old_bufs[k] + recv_k`` (``accumulate``), each masked by ``mask[k]``."""
    from jax.experimental.pallas import tpu as pltpu  # deferred: TPU-only path

    n_shifts = len(shifts)

    def kernel(x_ref, bufs_ref, mask_ref, out_bufs_ref, send_sem, recv_sem):
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
                new = old + landed if accumulate else landed
                out_bufs_ref[k, rows, :] = jnp.where(keep, new, old)

            _for_each_row_tile(x_ref.shape[0], store_tile)
        for rdma in rdmas:
            rdma.wait_send()
    return kernel


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
        # lower).
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

    kernel = _make_exchange_kernel(shifts, n, axis_name, accumulate)
    startup.kernel_traced("window_deliver")
    # no name=: in a device trace the kernel is ``shard_map.N``, the pattern
    # the benchmark's gossip_kernel_ms_per_step matches
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
            vmem_limit_bytes=_vmem_limit(block, k_slots),
        ),
        interpret=pltpu.InterpretParams() if interpret else False,
    )(block, bufs_block, mask)
    with jax.named_scope("bf.gossip.unpack"):
        return (out_bufs.reshape(k_slots, -1)[:, : bufs_f.shape[1]]
                .reshape(bufs.shape).astype(orig_dtype))
