"""Fleet health plane multi-process test worker (one OS process per rank).

argv: <rank> <n_ranks> <barrier_dir> <variant: chaos|clean> <steps>

Every rank runs ``run_async_dsgd_rank(transport="tcp",
fleet=FleetConfig(every=1))`` — the telemetry publisher appends one
``fleet.<rank>`` record per round into the barrier directory.  Under
the ``chaos`` variant rank 2's window SERVER delays EVERY inbound
frame 150 ms (``server:delay:ms=150:rate=1.0`` — a deterministic
straggler): its senders' ack EWMAs toward it blow up, their records
carry the lag, and the ``bffleet-tpu --check`` replay the test runs
afterwards must name rank 2 and exit nonzero — while the ``clean``
twin replays to exit 0.

Rank 0 additionally asserts the EXACT push-sum mass audit (total ==
n to 1e-9·n) — the publisher reads telemetry, it never moves mass.

Prints ``FLEET_MP_OK <rank>`` on success.
"""

import os
import sys

os.environ["JAX_PLATFORMS"] = "cpu"

SLOW_RANK = 2
CHAOS_SPEC = "server:delay:ms=150:rate=1.0:seed=1"


def main():
    rank, n = int(sys.argv[1]), int(sys.argv[2])
    barrier_dir, variant, steps = sys.argv[3], sys.argv[4], int(sys.argv[5])

    if variant == "chaos" and rank == SLOW_RANK:
        os.environ["BLUEFOG_TPU_CHAOS"] = CHAOS_SPEC

    import numpy as np

    from bluefog_tpu.fleet import FleetConfig
    from bluefog_tpu.runtime.async_windows import (FileBarrier,
                                                   run_async_dsgd_rank)
    from bluefog_tpu.topology import FullyConnectedGraph

    def loss_and_grad(r, step, params):
        # zero-gradient pure averaging: consensus dynamics, no jax
        return 0.0, {"w": np.zeros_like(np.asarray(params["w"]))}

    rep = run_async_dsgd_rank(
        FullyConnectedGraph(n), rank,
        {"w": np.arange(32.0, dtype=np.float64)}, loss_and_grad,
        barrier=FileBarrier(barrier_dir, n, rank),
        duration_s=60.0,
        # ~50 ms rounds: the 150 ms chaos ack latency lands within the
        # first few rounds' EWMAs, so detection latency is measured in
        # rounds, not in EWMA warm-up time
        skew_s=0.05,
        name=f"fleet_mp_{os.path.basename(barrier_dir)}",
        transport="tcp", tcp_bind="127.0.0.1",
        # every rank carries the same step target: without elastic
        # stopped-detection, one rank stopping early would just idle at
        # the stop barrier while the others burn duration_s
        stop_after_steps=steps,
        fleet=FleetConfig(every=1))

    if rank == 0:
        assert rep is not None
        assert abs(rep.total_mass - n) <= 1e-9 * n, rep.total_mass
        assert rep.dead_ranks == [], rep.dead_ranks
        assert min(rep.steps_per_rank) >= steps, rep.steps_per_rank

    print(f"FLEET_MP_OK {rank}", flush=True)


if __name__ == "__main__":
    main()
