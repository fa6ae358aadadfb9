"""Peer-fault-tolerance multi-process test worker (one OS process/rank).

argv: <rank> <nranks> <barrier_dir> <duration_s> <mode>

modes:
  ``kill2``     rank 2 SIGKILLs itself mid-run (chaos ``at_step``); the
                survivors must detect the death through their failing
                deposit streams (reconnect budget exhausted), heal the
                mixing weights over the surviving set, hold the
                quiesce-rendezvous, and finish — rank 0 then asserts the
                EXACT mass audit over the survivors
                (``total_mass == baseline_mass``).
  ``sigstop1``  rank 1 freezes itself (SIGSTOP) for a moment and thaws
                (a helper child sends SIGCONT); nobody dies — the
                survivors' peer health dips to SUSPECT and recovers, the
                run completes, and the global mass audit stays EXACT
                (sum p == n): a paused peer costs latency, never mass.

Prints ``RES_MP_OK <rank>`` on success (rank 2 in kill2 mode prints
nothing — it is dead, which is the point).
"""

import os
import sys

os.environ["JAX_PLATFORMS"] = "cpu"

import numpy as np


def main():
    rank, nranks = int(sys.argv[1]), int(sys.argv[2])
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

    topo = FullyConnectedGraph(nranks)
    targets = np.stack([np.full(4, float(r + 1)) for r in range(nranks)])
    params0 = {"w": np.zeros(4, np.float32)}

    def loss_and_grad(r, step, params):
        w = np.asarray(params["w"], np.float64)
        diff = w - targets[r]
        return 0.5 * float(diff @ diff), {"w": diff}

    if mode == "kill2":
        if rank == 2:
            chaos.configure("rank2:sigkill:at_step=12")
        cfg = ResilienceConfig(
            suspect_after_s=0.3, dead_after_s=5.0,
            reconnect_base_s=0.05, reconnect_cap_s=0.3,
            reconnect_budget=4, seed=rank,
            barrier_timeout_s=20.0)
    elif mode == "sigstop1":
        if rank == 1:
            chaos.configure("rank1:sigstop:after_s=1.0:for_s=0.8")
        cfg = ResilienceConfig(
            suspect_after_s=0.3, dead_after_s=60.0,
            reconnect_base_s=0.05, reconnect_budget=4, seed=rank,
            heartbeat_interval_s=0.2, barrier_timeout_s=30.0)
    else:
        raise SystemExit(f"unknown mode {mode!r}")

    report = run_async_dsgd_rank(
        topo, rank, params0, loss_and_grad,
        barrier=FileBarrier(barrier_dir, nranks, rank),
        lr=0.05, duration_s=duration_s, skew_s=0.004,
        name=f"res_mp_{mode}_{os.path.basename(barrier_dir)}",
        transport="tcp", tcp_bind="127.0.0.1", resilience=cfg)

    if rank == 0:
        assert report is not None
        if mode == "kill2":
            # the peer was declared DEAD and healed out...
            assert report.dead_ranks == [2], report.dead_ranks
            # ...early enough that a post-heal baseline exists, and the
            # EXACT audit over the surviving set holds: every unit of
            # push-sum mass the survivors held at the rendezvous is
            # still among the survivors at the end — reconnect replay
            # double-applied nothing, the healed weights leaked nothing
            assert report.baseline_mass is not None
            assert abs(report.total_mass - report.baseline_mass) \
                <= 1e-9 * nranks, \
                (report.total_mass, report.baseline_mass)
            # survivors kept training well past the kill step
            assert report.steps_per_rank[0] > 40, report.steps_per_rank
            assert report.steps_per_rank[1] > 40, report.steps_per_rank
            # the corpse never published its meta (it was SIGKILLed)
            assert report.steps_per_rank[2] == 0, report.steps_per_rank
            # survivors converged among themselves
            assert report.final_params[2] is None
            assert report.consensus_gap < 0.75, report.consensus_gap
        else:  # sigstop1
            # nobody died: a paused peer costs latency, never mass —
            # the ORIGINAL global audit stays exact over all ranks
            assert report.dead_ranks == [], report.dead_ranks
            assert abs(report.total_mass - nranks) < 1e-9 * nranks, \
                report.total_mass
            assert min(report.steps_per_rank) > 10, report.steps_per_rank
            # the health timeline recorded the dip and the recovery
            rec = bb.get()
            kinds = [e["kind"] for e in rec.events()] if rec else []
            assert "peer_suspect" in kinds, kinds[-40:]
            assert ("peer_recovered" in kinds or "peer_rejoin" in kinds), \
                kinds[-40:]

    print(f"RES_MP_OK {rank}", flush=True)


if __name__ == "__main__":
    main()
