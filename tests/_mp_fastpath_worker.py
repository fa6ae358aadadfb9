"""Same-host shm fast-path multi-process test worker (one process/rank).

argv: <rank> <nranks> <barrier_dir> <duration_s>

One mode, the acceptance scenario for the raw-speed hot path: a 3-rank
TCP-transport dsgd run with ``stream_options={"shm": True}`` — deposits
route through the named-shm window table instead of the loopback wire —
under two simultaneous faults:

- rank 2 SIGKILLs itself mid-run (the kill-one-rank leg: survivors must
  detect the death through the TCP control channel, heal, and finish);
- rank 1's window SERVER drops a connection once (``server:drop``), so
  the TCP leg under the shm route reconnects and replays exactly once
  while shm deposits keep flowing.

Rank 0 asserts the exact post-heal mass audit AND that the shm route
really carried deposits (``bf_shm_deposits_total`` > 0: the audit was
exercised through shared memory, not a silent TCP fallback).

Prints ``FP_MP_OK <rank>`` on success (rank 2 prints nothing — dead).
"""

import os
import sys

os.environ["JAX_PLATFORMS"] = "cpu"

import numpy as np


def main():
    rank, nranks = int(sys.argv[1]), int(sys.argv[2])
    barrier_dir, duration_s = sys.argv[3], float(sys.argv[4])

    import jax

    jax.config.update("jax_platforms", "cpu")

    from bluefog_tpu import chaos
    from bluefog_tpu.metrics import registry as mreg
    from bluefog_tpu.runtime.async_windows import (FileBarrier,
                                                   run_async_dsgd_rank)
    from bluefog_tpu.runtime.resilience import ResilienceConfig
    from bluefog_tpu.topology import FullyConnectedGraph

    reg = mreg.metrics_start()
    topo = FullyConnectedGraph(nranks)
    targets = np.stack([np.full(4, float(r + 1)) for r in range(nranks)])
    params0 = {"w": np.zeros(4, np.float32)}

    def loss_and_grad(r, step, params):
        w = np.asarray(params["w"], np.float64)
        diff = w - targets[r]
        return 0.5 * float(diff @ diff), {"w": diff}

    if rank == 2:
        chaos.configure("rank2:sigkill:at_step=12")
    elif rank == 1:
        # one server-side connection drop, aimed past the attach
        # handshakes into heartbeat steady state (0.25 s cadence, two
        # inbound connections): the TCP control/fallback leg under the
        # shm route must reconnect + resume exactly once
        chaos.configure("server:drop:after_frames=12:times=1")
    cfg = ResilienceConfig(
        suspect_after_s=0.3, dead_after_s=5.0,
        reconnect_base_s=0.05, reconnect_cap_s=0.3,
        reconnect_budget=4, seed=rank, barrier_timeout_s=20.0)

    report = run_async_dsgd_rank(
        topo, rank, params0, loss_and_grad,
        barrier=FileBarrier(barrier_dir, nranks, rank),
        lr=0.05, duration_s=duration_s, skew_s=0.004,
        name=f"fp_mp_{os.path.basename(barrier_dir)}",
        transport="tcp", tcp_bind="127.0.0.1",
        stream_options={"shm": True}, resilience=cfg)

    snap = reg.snapshot()
    shm_total = sum(v for k, v in snap.items()
                    if k.startswith("bf_shm_deposits_total"))
    # every live rank's deposits rode the shm table (the fast path
    # engaged for real — this is the assertion that makes the mass
    # audit below an audit OF the shm route)
    assert shm_total > 0, snap

    if rank == 0:
        assert report is not None
        assert report.dead_ranks == [2], report.dead_ranks
        # the EXACT audit over the surviving set: every unit of push-sum
        # mass the survivors held at the post-heal rendezvous is still
        # among them at the end — shm deposits applied exactly once,
        # the dropped TCP connection replayed exactly once
        assert report.baseline_mass is not None
        assert abs(report.total_mass - report.baseline_mass) \
            <= 1e-9 * nranks, (report.total_mass, report.baseline_mass)
        assert report.steps_per_rank[0] > 40, report.steps_per_rank
        assert report.steps_per_rank[1] > 40, report.steps_per_rank
        assert report.steps_per_rank[2] == 0, report.steps_per_rank
        assert report.final_params[2] is None

    print(f"FP_MP_OK {rank}", flush=True)


if __name__ == "__main__":
    main()
