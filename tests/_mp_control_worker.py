"""Control-plane multi-process test worker (one OS process per rank).

argv: <rank> <capacity> <barrier_dir> <duration_s>

Rank 3's window SERVER runs behind a chaos lossy/slow link
(``server:delay:ms=40:rate=0.9`` + ``server:drop:rate=0.02`` — the
lossy-link trigger, seeded, deterministic per traffic).  Every rank
runs ``run_async_dsgd_rank(control=ControlConfig(...))`` with a BOUNDED
deposit queue, so the slow link back-pressures its senders honestly —
the degradation the controller exists to undo.  Rank 0 asserts:

- the controllers converged on a plan penalizing rank 3 (its edges
  reduced to the ring spine);
- the EXACT push-sum mass audit holds (total == capacity to 1e-9·n):
  a plan change moves edges, never mass, and reconnect/replay keeps
  the lossy link exactly-once;
- every rank reached its step target (nobody starved).

Prints ``CTL_MP_OK <rank>`` on success.
"""

import os
import sys

os.environ["JAX_PLATFORMS"] = "cpu"


def main():
    rank, capacity = int(sys.argv[1]), int(sys.argv[2])
    barrier_dir, duration_s = sys.argv[3], float(sys.argv[4])

    if rank == 3:
        # rank 3 owns the lossy/slow link: its SERVER delays 90% of
        # inbound frames 40 ms and cuts ~2% of connections — every
        # deposit toward it crawls, and its senders feel it through
        # the bounded queue
        os.environ["BLUEFOG_TPU_CHAOS"] = (
            "server:delay:ms=40:rate=0.9:seed=1;"
            "server:drop:rate=0.02:seed=2")

    import numpy as np

    from bluefog_tpu.control import ControlConfig
    from bluefog_tpu.runtime.async_windows import (FileBarrier,
                                                   run_async_dsgd_rank)
    from bluefog_tpu.runtime.resilience import ResilienceConfig
    from bluefog_tpu.topology import ExponentialTwoGraph

    def loss_and_grad(r, step, params):
        # zero-gradient pure averaging: consensus dynamics without a
        # jax dependency in the hot loop
        return 0.0, {"w": np.zeros_like(np.asarray(params["w"]))}

    rep = run_async_dsgd_rank(
        ExponentialTwoGraph(capacity), rank,
        {"w": np.arange(64.0, dtype=np.float64)}, loss_and_grad,
        barrier=FileBarrier(barrier_dir, capacity, rank),
        duration_s=duration_s, skew_s=0.004,
        name=f"ctl_mp_{os.path.basename(barrier_dir)}",
        transport="tcp", tcp_bind="127.0.0.1",
        resilience=ResilienceConfig(
            barrier_timeout_s=90.0, reconnect_budget=8, seed=rank),
        control=ControlConfig(evidence_every=8, cooldown_rounds=16,
                              min_lag_s=0.02),
        stop_after_steps=250,
        stream_options=dict(max_in_flight=2, max_queue_items=8))

    if rank == 0:
        assert rep is not None
        assert rep.control_plan is not None
        assert 3 in rep.control_plan.slow or rep.plan_changes >= 1, \
            rep.control_plan
        assert abs(rep.total_mass - capacity) <= 1e-9 * capacity, \
            rep.total_mass
        assert min(rep.steps_per_rank) >= 250, rep.steps_per_rank
        assert rep.dead_ranks == [], rep.dead_ranks

    print(f"CTL_MP_OK {rank}", flush=True)


if __name__ == "__main__":
    main()
