"""Process-wide framework context: mesh, topology, schedules, windows.

Reference parity (upstream-relative): ``bluefog/common/basics.py``
(``BlueFogBasics``: init/shutdown/rank/size/local_rank/set_topology/...) and
``bluefog/common/global_state.h``.  What the reference does with
``MPI_Init_thread`` + a background engine thread, the TPU build does by
constructing a ``jax.sharding.Mesh`` over the (ICI-ordered) devices — there is
no engine thread because XLA's async dispatch plays that role (SURVEY.md §7).

SPMD semantics note: the reference is one-process-per-rank, so ``bf.rank()``
identifies the calling process.  Under a single JAX controller every gossip
rank lives in the same process; ``rank()`` therefore refers to *mesh
positions*: host-level code passes an explicit rank to neighbor queries, and
device-level code uses ``lax.axis_index(ctx.axis_name)``.  In multi-controller
deployments (``jax.distributed``), ``process_rank()`` exposes the controller
index like the reference's ``rank()``.
"""

from __future__ import annotations

import dataclasses
import math
from typing import Any, Dict, List, Optional, Sequence

import numpy as np

from bluefog_tpu.topology.graphs import ExponentialTwoGraph, Topology
from bluefog_tpu.topology.mapping import ici_ring_order
from bluefog_tpu.topology.schedule import GossipSchedule, build_schedule
from bluefog_tpu.tracing import startup
from bluefog_tpu.utils import log

__all__ = [
    "BluefogContext",
    "init",
    "shutdown",
    "initialized",
    "get_context",
    "size",
    "rank",
    "local_size",
    "local_rank",
    "machine_size",
    "machine_rank",
    "process_rank",
    "set_topology",
    "load_topology",
    "set_machine_topology",
    "load_machine_topology",
    "in_neighbor_ranks",
    "out_neighbor_ranks",
    "in_neighbor_machine_ranks",
    "out_neighbor_machine_ranks",
]


@dataclasses.dataclass
class BluefogContext:
    """Everything the framework holds between calls."""

    mesh: Any  # jax.sharding.Mesh
    axis_name: str
    devices: List[Any]
    local_size: int
    topology: Topology
    schedule: GossipSchedule
    machine_topology: Optional[Topology] = None
    machine_schedule: Optional[GossipSchedule] = None
    dynamic_schedules: Optional[List[GossipSchedule]] = None
    windows: Dict[str, Any] = dataclasses.field(default_factory=dict)

    @property
    def size(self) -> int:
        return len(self.devices)

    @property
    def n_machines(self) -> int:
        return self.size // self.local_size

    @property
    def machine_axis_name(self) -> str:
        return self.axis_name + "_machine"

    @property
    def local_axis_name(self) -> str:
        return self.axis_name + "_local"

    @property
    def hier_mesh(self):
        """Two-level ``(machine, local)`` mesh over the same devices — the
        multi-slice deployment form (outer axis rides DCN, inner axis each
        slice's ICI; reference analog: cross vs local MPI communicators,
        ``bluefog/common/mpi_context.cc``).  Built lazily; rank ``r`` sits at
        mesh position ``(r // local_size, r % local_size)``, so flat-mesh and
        two-level collectives agree rank-for-rank."""
        if self._hier_mesh is None:
            from jax.sharding import Mesh

            self._hier_mesh = Mesh(
                np.array(self.devices).reshape(self.n_machines, self.local_size),
                (self.machine_axis_name, self.local_axis_name),
            )
        return self._hier_mesh

    _hier_mesh: Any = None


_CTX: Optional[BluefogContext] = None


@startup.spanned("bf.setup.init", "init")
def init(
    *,
    topology: Optional[Topology] = None,
    machine_topology: Optional[Topology] = None,
    size: Optional[int] = None,
    local_size: Optional[int] = None,
    devices: Optional[Sequence[Any]] = None,
    axis_name: str = "bf",
    use_ici_order: bool = True,
) -> BluefogContext:
    """Initialize the framework (the reference's ``bf.init()``, SURVEY.md §3.1).

    Builds the gossip mesh over ``devices`` (default: all of
    ``jax.devices()``, snake-ordered along ICI so ring edges are physical
    hops), installs the default ``ExponentialTwoGraph`` topology exactly as
    the reference does, and — when ``local_size > 1`` — a machine-level
    topology for hierarchical ops.

    Args:
      size: number of gossip ranks (default: all devices).
      local_size: devices per "machine" for hierarchical mode (default: JAX's
        ``local_device_count`` when running multi-process, else 1).
    """
    global _CTX
    import jax
    from jax.sharding import Mesh

    startup.RECORD.look_at_backend()
    if devices is None:
        devices = jax.devices()
        startup.RECORD.look_at_backend()
    devices = list(devices)
    if use_ici_order:
        devices = ici_ring_order(devices)
    if size is not None:
        if size > len(devices):
            raise ValueError(f"size {size} exceeds available devices {len(devices)}")
        devices = devices[:size]
    n = len(devices)

    if local_size is None:
        local_size = jax.local_device_count() if jax.process_count() > 1 else 1
        if n % local_size != 0:
            local_size = 1
    if n % local_size != 0:
        raise ValueError(f"size {n} not divisible by local_size {local_size}")

    topo = topology if topology is not None else ExponentialTwoGraph(n)
    if topo.size != n:
        raise ValueError(f"topology size {topo.size} != mesh size {n}")

    n_machines = n // local_size
    mtopo = machine_topology
    if mtopo is None and n_machines > 1:
        mtopo = ExponentialTwoGraph(n_machines)

    mesh = Mesh(np.array(devices), (axis_name,))
    _CTX = BluefogContext(
        mesh=mesh,
        axis_name=axis_name,
        devices=devices,
        local_size=local_size,
        topology=topo,
        schedule=build_schedule(topo),
        machine_topology=mtopo,
        machine_schedule=build_schedule(mtopo) if mtopo is not None else None,
    )
    log.info(
        "bluefog_tpu.init: %d ranks (%d machines x %d local), topology=%s",
        n, n_machines, local_size, topo.name,
    )
    try:
        # arm the blackbox crash/hang dump triggers (excepthooks, fatal
        # signals, faulthandler, atexit-after-exception) at framework
        # bring-up — the watchdog path dumps on its own, but a rank dying
        # of an uncaught exception must leave its flight recorder behind
        # too.  No-op when BLUEFOG_TPU_BLACKBOX=0; idempotent.
        from bluefog_tpu import blackbox

        blackbox.install()
    except Exception:
        pass
    return _CTX


def shutdown() -> None:
    """Tear down the context (reference ``bf.shutdown()``)."""
    global _CTX
    _CTX = None


def initialized() -> bool:
    return _CTX is not None


def get_context() -> BluefogContext:
    if _CTX is None:
        raise RuntimeError("bluefog_tpu.init() has not been called")
    return _CTX


def size() -> int:
    return get_context().size


def rank(default: int = 0) -> int:
    """Mesh-rank of this controller's first device (see module docstring for
    SPMD semantics; use ``lax.axis_index`` inside device code)."""
    import jax

    ctx = get_context()
    if jax.process_count() > 1:
        first_local = [d for d in ctx.devices if d.process_index == jax.process_index()]
        if first_local:
            return ctx.devices.index(first_local[0])
    return default


def process_rank() -> int:
    import jax

    return jax.process_index()


def local_size() -> int:
    return get_context().local_size


def local_rank(rank_: Optional[int] = None) -> int:
    r = rank() if rank_ is None else rank_
    return r % get_context().local_size


def machine_size() -> int:
    return get_context().n_machines


def machine_rank(rank_: Optional[int] = None) -> int:
    r = rank() if rank_ is None else rank_
    return r // get_context().local_size


def set_topology(topology: Optional[Topology] = None, is_weighted: bool = True) -> bool:
    """Install a new virtual topology and recompile the gossip schedule
    (reference ``bf.set_topology`` — which rebuilds the MPI dist-graph
    communicator; here we rebuild the ppermute schedule).

    ``is_weighted=False`` mirrors the upstream flag: the topology's weights are
    replaced by uniform ``1/(in_degree+1)`` rows.
    """
    ctx = get_context()
    topo = topology if topology is not None else ExponentialTwoGraph(ctx.size)
    if hasattr(topo, "number_of_nodes"):  # networkx interop
        topo = Topology.from_networkx(topo)
    if topo.size != ctx.size:
        raise ValueError(f"topology size {topo.size} != mesh size {ctx.size}")
    if not is_weighted:
        topo = Topology.from_edges(topo.size, topo.edges, name=topo.name)
    if ctx.windows:
        log.warn("set_topology with %d live windows: window schedules keep the "
                 "topology they were created with", len(ctx.windows))
    ctx.topology = topo
    ctx.schedule = build_schedule(topo)
    ctx.dynamic_schedules = None
    return True


def load_topology() -> Topology:
    """Reference ``bf.load_topology()``."""
    return get_context().topology


def set_machine_topology(topology: Topology, is_weighted: bool = True) -> bool:
    """Machine-level analog for hierarchical ops (upstream
    ``set_machine_topology``)."""
    ctx = get_context()
    if topology.size != ctx.n_machines:
        raise ValueError(
            f"machine topology size {topology.size} != n_machines {ctx.n_machines}"
        )
    if not is_weighted:
        topology = Topology.from_edges(topology.size, topology.edges, name=topology.name)
    ctx.machine_topology = topology
    ctx.machine_schedule = build_schedule(topology)
    return True


def load_machine_topology() -> Optional[Topology]:
    return get_context().machine_topology


def in_neighbor_ranks(rank_: Optional[int] = None) -> List[int]:
    r = rank() if rank_ is None else rank_
    return get_context().topology.in_neighbors(r)


def out_neighbor_ranks(rank_: Optional[int] = None) -> List[int]:
    r = rank() if rank_ is None else rank_
    return get_context().topology.out_neighbors(r)


def in_neighbor_machine_ranks(machine_rank_: Optional[int] = None) -> List[int]:
    ctx = get_context()
    if ctx.machine_topology is None:
        return []
    m = machine_rank() if machine_rank_ is None else machine_rank_
    return ctx.machine_topology.in_neighbors(m)


def out_neighbor_machine_ranks(machine_rank_: Optional[int] = None) -> List[int]:
    ctx = get_context()
    if ctx.machine_topology is None:
        return []
    m = machine_rank() if machine_rank_ is None else machine_rank_
    return ctx.machine_topology.out_neighbors(m)
