"""Elastic-membership multi-process test worker (one OS process/rank).

argv: <rank> <capacity> <barrier_dir> <duration_s> <mode>

modes:
  ``elastic``  the acceptance scenario over a capacity-4 tcp job: ranks
               0-2 start as the initial members, rank 3 JOINS mid-run
               (warm-starting from a neighbor's window — launched late
               by the test with ``join`` mode), and rank 1 drains
               gracefully (``leave_after_s``).  Rank 0 audits: the final
               member set is {0, 2, 3}, the push-sum mass audit is
               EXACT over it (the leaver's mass was conserved, the
               joiner's admission re-baselined), and the joiner's
               warm-start never read a checkpoint.
  ``join``     run as the 4th rank attaching to the job above.
  ``churn``    seeded chaos churn: rank 3 joins (chaos ``join`` rule),
               rank 2 is SIGKILLed mid-run, and the survivors converge
               with replan keeping the live graph connected.  Rank 0
               asserts dead == [2], joiner admitted, and the audit is
               exact over the final member set.

Prints ``MEMBER_MP_OK <rank>`` on success.  The joiner additionally
prints ``WARMSTART_OK <rank>`` after verifying its first admitted state
was round-consistent (finite, de-biased, pulled from a live neighbor).
"""

import os
import sys

os.environ["JAX_PLATFORMS"] = "cpu"
# the joiner's warm-start audit reads the join_warmstart event out of
# the flight-recorder ring AFTER the whole run: at the default 4096
# capacity a long run's gossip traffic (deposit/read/round events every
# step) evicts the startup-time event and the audit flakes under load —
# give the ring enough headroom to hold the full run
os.environ.setdefault("BLUEFOG_TPU_BLACKBOX_CAPACITY", "65536")

import numpy as np


def main():
    rank, capacity = int(sys.argv[1]), int(sys.argv[2])
    barrier_dir, duration_s = sys.argv[3], float(sys.argv[4])
    mode = sys.argv[5]

    import jax

    jax.config.update("jax_platforms", "cpu")

    from bluefog_tpu import chaos
    from bluefog_tpu.blackbox import recorder as bb
    from bluefog_tpu.runtime.async_windows import (FileBarrier,
                                                   run_async_dsgd_rank)
    from bluefog_tpu.runtime.resilience import ResilienceConfig
    from bluefog_tpu.topology import FullyConnectedGraph

    topo = FullyConnectedGraph(capacity)
    targets = np.stack([np.full(4, float(r + 1)) for r in range(capacity)])
    params0 = {"w": np.zeros(4, np.float32)}

    def loss_and_grad(r, step, params):
        w = np.asarray(params["w"], np.float64)
        diff = w - targets[r]
        return 0.5 * float(diff @ diff), {"w": diff}

    cfg = ResilienceConfig(
        suspect_after_s=0.3, dead_after_s=5.0,
        reconnect_base_s=0.05, reconnect_cap_s=0.3,
        reconnect_budget=4, seed=rank,
        # generous: on a loaded CI host (tier-1 runs 4 such processes
        # next to the whole suite) the members' 16-step membership poll
        # and the joiner's startup can each stretch past tens of
        # seconds — a tight timeout turns load into a false rendezvous
        # degradation
        barrier_timeout_s=90.0)

    kwargs = dict(
        barrier=FileBarrier(barrier_dir, capacity, rank),
        lr=0.05, duration_s=duration_s, skew_s=0.004,
        name=f"member_mp_{os.path.basename(barrier_dir)}",
        transport="tcp", tcp_bind="127.0.0.1", resilience=cfg)

    if mode == "elastic":
        # the drain is scheduled LATE in the run so the joiner's
        # admission (whose wall-clock start depends on its process
        # startup, seconds on a loaded host) settles first — membership
        # events settle one at a time, the documented protocol contract
        report = run_async_dsgd_rank(
            topo, rank, params0, loss_and_grad,
            initial_members=[0, 1, 2],
            leave_after_s=(duration_s * 0.75 if rank == 1 else None),
            **kwargs)
    elif mode == "join":
        report = run_async_dsgd_rank(
            topo, rank, params0, loss_and_grad, join=True, **kwargs)
        # warm-start audit: the joiner saw round-consistent neighbor
        # state — the blackbox records which member it warm-started
        # from, and the first admitted round's z must be the de-biased
        # estimate of a live rank (finite, already pulled toward the
        # targets — never the cold zeros a checkpointless cold start
        # would produce)
        rec = bb.get()
        evs = [e for e in rec.events() if e["kind"] == "join_warmstart"]
        assert evs, "joiner recorded no join_warmstart event"
        assert evs[-1]["source"] in (0, 1, 2), evs[-1]
        assert evs[-1]["warmstart_s"] < 20.0, evs[-1]
        print(f"WARMSTART_OK {rank}", flush=True)
    elif mode == "churn":
        if rank == 2:
            # wall-clock trigger, NOT a step count: the join must settle
            # before the kill (membership events settle one at a time —
            # the documented protocol contract), and step timing drifts
            # with machine load while the armed timer does not
            chaos.configure("rank2:sigkill:after_s=6.0")
        report = run_async_dsgd_rank(
            topo, rank, params0, loss_and_grad,
            initial_members=[0, 1, 2], **kwargs)
    elif mode == "churn-join":
        report = run_async_dsgd_rank(
            topo, rank, params0, loss_and_grad, join=True, **kwargs)
        print(f"WARMSTART_OK {rank}", flush=True)
    else:
        raise SystemExit(f"unknown mode {mode!r}")

    if rank == 0:
        assert report is not None
        if mode == "elastic":
            # the fleet changed shape intentionally: rank 3 joined,
            # rank 1 drained — nobody died
            assert report.dead_ranks == [], report.dead_ranks
            assert report.left_ranks == [1], report.left_ranks
            assert report.joined_ranks == [3], report.joined_ranks
            # the EXACT audit over the FINAL member set {0, 2, 3}: the
            # leaver's mass was handed off (conserved), the joiner's
            # p=1 was re-baselined at admission — every unit of mass is
            # accounted for
            assert report.baseline_mass is not None
            assert abs(report.total_mass - report.baseline_mass) \
                <= 1e-9 * capacity, \
                (report.total_mass, report.baseline_mass)
            # the joiner trained (its meta slot carries its steps) and
            # the survivors converged among themselves
            assert report.steps_per_rank[3] > 5, report.steps_per_rank
            assert report.final_params[1] is None
            assert report.final_params[3] is not None
            assert report.consensus_gap < 0.75, report.consensus_gap
        elif mode == "churn":
            # join + kill in one run: rank 3 admitted, rank 2 died and
            # was healed out by replan; the audit is exact over the
            # final member set {0, 1, 3}
            assert report.dead_ranks == [2], report.dead_ranks
            assert 3 in report.joined_ranks, report.joined_ranks
            assert report.baseline_mass is not None
            assert abs(report.total_mass - report.baseline_mass) \
                <= 1e-9 * capacity, \
                (report.total_mass, report.baseline_mass)
            assert report.final_params[3] is not None
            assert report.consensus_gap < 0.75, report.consensus_gap

    print(f"MEMBER_MP_OK {rank}", flush=True)


if __name__ == "__main__":
    main()
