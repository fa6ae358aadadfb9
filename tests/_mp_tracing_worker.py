"""Causal-tracing multi-process acceptance worker (one process per rank).

argv: <rank> <capacity> <barrier_dir> <trace_dir> <steps>

Every rank runs a tcp dsgd loop with ``BLUEFOG_TPU_TRACE`` armed at the
shared ``trace_dir`` (one ``trace-rank<k>.jsonl`` per rank — the
one-process-per-rank shape ``set_rank`` pins).  Rank 2's window SERVER
runs behind ``server:delay`` chaos, so every deposit INTO rank 2 crawls
and its senders feel it through the bounded in-flight window — the
edge ``bftrace-tpu`` must then name as the per-round critical path.

Prints ``TRC_MP_OK <rank>`` on success; the TEST process merges the
trace files and asserts the attribution.
"""

import os
import sys

os.environ["JAX_PLATFORMS"] = "cpu"


def main():
    rank, capacity = int(sys.argv[1]), int(sys.argv[2])
    barrier_dir, trace_dir = sys.argv[3], sys.argv[4]
    steps = int(sys.argv[5])

    # arm tracing BEFORE the package imports (env-lazy, like blackbox)
    os.environ["BLUEFOG_TPU_TRACE"] = trace_dir
    if rank == 2:
        # rank 2's server delays EVERY inbound frame 40 ms (rate=1 —
        # a probabilistic rate leaves unlucky runs where the healthy
        # ranks' ping-pong gating time rivals the chaos edge): every
        # deposit toward it is slow, its senders back-pressure on the
        # bounded in-flight window, and the 0->2 / 1->2 edges carry
        # the fleet's gating wall-clock by a wide margin
        os.environ["BLUEFOG_TPU_CHAOS"] = "server:delay:ms=40:rate=1:seed=3"

    import numpy as np

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
        {"w": np.arange(32.0, dtype=np.float64)}, loss_and_grad,
        barrier=FileBarrier(barrier_dir, capacity, rank),
        duration_s=120.0, skew_s=0.002,
        name=f"trc_mp_{os.path.basename(barrier_dir)}",
        transport="tcp", tcp_bind="127.0.0.1",
        resilience=ResilienceConfig(
            barrier_timeout_s=90.0, reconnect_budget=8, seed=rank),
        stop_after_steps=steps,
        stream_options=dict(max_in_flight=2, max_queue_items=4))

    if rank == 0:
        assert rep is not None
        assert abs(rep.total_mass - capacity) <= 1e-9 * capacity, \
            rep.total_mass
        assert min(rep.steps_per_rank) >= steps, rep.steps_per_rank

    # land the spans before exit (the atexit hook would too; explicit
    # beats implicit for a subprocess the test will immediately read)
    from bluefog_tpu.tracing import recorder as trc

    trc.flush()
    print(f"TRC_MP_OK {rank}", flush=True)


if __name__ == "__main__":
    main()
