"""bluefog_tpu — a TPU-native decentralized deep-learning training framework.

A ground-up re-design of the capabilities of the reference project
``wowML/bluefog`` (a Bluefog-lineage decentralized training library for
PyTorch/MPI/NCCL) for TPUs and the JAX/XLA/Pallas stack.

Where the reference runs one OS process per rank, a C++ background engine, and
MPI/NCCL on the wire, this framework is SPMD-first:

- a *rank* is a device (or a mesh coordinate) in a ``jax.sharding.Mesh``;
- ``neighbor_allreduce`` and friends lower to ``lax.ppermute`` /
  ``lax.psum`` collectives on the ICI interconnect, fused by XLA into the
  training step (replacing the reference's background-thread + negotiation
  engine — see SURVEY.md §7);
- one-sided window ops (``win_put`` / ``win_get`` / ``win_accumulate`` /
  ``win_update``) are functional state transitions backed by ppermute on any
  backend and by Pallas async remote DMA on TPU;
- optimizers are functional wrappers compatible with optax.

Reference parity map (upstream-relative paths; the reference mount was empty
during the survey — see SURVEY.md header):

==============================================  =================================
reference                                       here
==============================================  =================================
bluefog/common/topology_util.py                 bluefog_tpu.topology
bluefog/torch/mpi_ops.py (collectives)          bluefog_tpu.ops.collectives
bluefog/torch/mpi_win_ops.{py,cc}               bluefog_tpu.ops.windows
bluefog/torch/optimizers.py                     bluefog_tpu.optim
bluefog/common/basics.py (init/rank/size/...)   bluefog_tpu.parallel.context
bluefog/common/{operations,mpi_controller}.cc   XLA SPMD + bluefog_tpu.runtime
bluefog/common/timeline.{h,cc}                  bluefog_tpu.utils.timeline
bluefog/run/ (bfrun launcher)                   bluefog_tpu.runtime.launch
==============================================  =================================
"""

import sys as _sys
import time as _time

_import_t0, _import_modules = _time.time(), len(_sys.modules)

# the record of this process's start: it times this file's imports a
# subpackage (bf.setup.import.<name>), in the order they stand here
from bluefog_tpu.tracing import startup as _startup

_import = _startup.ImportSpan(_import_t0, _import_modules)
_import.lap("tracing")
from bluefog_tpu import topology
_import.lap("topology")
from bluefog_tpu.parallel.context import (
    init,
    shutdown,
    initialized,
    size,
    rank,
    process_rank,
    local_size,
    local_rank,
    machine_size,
    machine_rank,
    set_topology,
    load_topology,
    set_machine_topology,
    load_machine_topology,
    in_neighbor_ranks,
    out_neighbor_ranks,
    in_neighbor_machine_ranks,
    out_neighbor_machine_ranks,
    get_context,
)
from bluefog_tpu.parallel.api import (
    allreduce,
    allgather,
    broadcast,
    neighbor_allreduce,
    neighbor_allreduce_aperiodic,
    neighbor_allgather,
    hierarchical_neighbor_allreduce,
    barrier,
    win_create,
    win_free,
    win_put,
    win_get,
    win_accumulate,
    win_update,
    win_update_then_collect,
    win_mutex,
    win_mutex_break,
    win_mutex_sweep,
    broadcast_parameters,
    allreduce_parameters,
    broadcast_optimizer_state,
    rank_stack,
    rank_shard,
    enqueue_host_op,
    poll,
    synchronize,
    wait_all_host_ops,
)
_import.lap("parallel")
from bluefog_tpu.utils import (
    timeline_start,
    timeline_stop,
    timeline_start_activity,
    timeline_end_activity,
    timeline_context,
)
from bluefog_tpu.utils.checkpoint import CheckpointManager, run_with_restart
from bluefog_tpu.utils.compile_cache import configure_compile_cache
_import.lap("utils")
from bluefog_tpu import metrics
from bluefog_tpu.metrics import metrics_active, metrics_start, metrics_stop
_import.lap("metrics")
from bluefog_tpu import blackbox
_import.lap("blackbox")

__version__ = "0.1.0"

_import.close()
del _import, _import_t0, _import_modules
