"""Distributed optimizers — decentralized training wrappers around optax.

Reference parity (upstream-relative): ``bluefog/torch/optimizers.py`` —
``CommunicationType``, ``DistributedNeighborAllreduceOptimizer``,
``DistributedWinPutOptimizer`` (both confirmed in BASELINE.json),
``DistributedGradientAllreduceOptimizer``,
``DistributedHierarchicalNeighborAllreduceOptimizer``, adapt-then-combine vs
adapt-with-combine modes, ``num_steps_per_communication`` (local SGD).
"""

from bluefog_tpu.optim.optimizers import (
    CommunicationType,
    decentralized_optimizer,
    optimizer_state_specs,
    shard_optimizer_state,
    set_comm_every,
    get_comm_every,
    DistributedNeighborAllreduceOptimizer,
    DistributedGradientAllreduceOptimizer,
    DistributedHierarchicalNeighborAllreduceOptimizer,
    DistributedWinPutOptimizer,
    DistributedChocoSGDOptimizer,
    DistributedGradientTrackingOptimizer,
    DistributedExactDiffusionOptimizer,
)
