"""In-SPMD collective primitives (call these inside ``shard_map``/``pjit``).

These are the TPU-native lowering of the reference's communication layer
(``bluefog/torch/mpi_ops.py`` + ``bluefog/common/mpi_controller.cc``,
upstream-relative): neighbor collectives become ``lax.ppermute`` matchings
along the ICI mesh, dense collectives become ``lax.psum``/``all_gather``, and
the weighted combination fuses into the surrounding XLA program instead of
running on the host as in the reference (SURVEY.md §3.2 "HOT CPU" note).
"""

from bluefog_tpu.ops.collectives import (
    allreduce,
    allgather,
    broadcast,
    barrier,
    neighbor_allreduce,
    neighbor_allgather,
    neighbor_allreduce_dynamic,
    neighbor_allreduce_aperiodic,
    fuse_apply,
    hierarchical_neighbor_allreduce,
    hierarchical_neighbor_allreduce_2d,
    pair_gossip,
)
from bluefog_tpu.ops.windows import (
    WindowSpec,
    WindowState,
    win_create,
    win_free,
    win_put,
    win_get,
    win_accumulate,
    win_update,
    win_update_then_collect,
    win_sync,
    win_associated_p,
)
from bluefog_tpu.ops.ring_attention import (
    ring_attention,
    all_to_all_attention,
    local_attention,
    zigzag_shard,
    zigzag_unshard,
)
from bluefog_tpu.ops.selective_scan import selective_scan
from bluefog_tpu.ops.moe import (
    RouterOutput,
    switch_router,
    top2_router,
    get_router,
    expert_parallel_ffn,
    moe_ffn_reference,
)
from bluefog_tpu.ops.compression import (
    Compressor,
    identity,
    random_block_k,
    top_k,
    ChocoState,
    choco_init,
    choco_gossip,
    hierarchical_choco_gossip,
)
