"""Serve-while-training demo: a replica follows a live training fleet.

Three rank-threads train a quadratic consensus problem with
asynchronous push-sum (no barrier anywhere) while publishing
ROUND-STAMPED ``(round, x, p)`` snapshots every round.  A
:class:`~bluefog_tpu.runtime.window_server.WindowServer` in the same
process serves those snapshots over TCP, and a
:class:`~bluefog_tpu.serving.replica.ServingReplica` — the shape a
prediction server embeds — subscribes to rank 0's model and serves
predictions from it WHILE it trains.

Self-asserted invariants:

- every snapshot the replica adopts is round-consistent (the in-band
  ``round`` stamp leaf equals the pushed round, exactly);
- the served model's STALENESS is bounded: sampled repeatedly during
  training, the replica is never more than K rounds behind the
  trainer's live round (K = subscription stride + delivery slack);
- predictions from the served weights track the training objective
  (the replica's final model is close to the fleet's consensus).

Exits nonzero on failure.

Run:
  JAX_PLATFORMS=cpu \
  python examples/serving_replica.py
"""

import os
import sys
import threading
import time

sys.path.insert(0, os.path.join(os.path.dirname(__file__), ".."))

os.environ.setdefault("JAX_PLATFORMS", "cpu")

import numpy as np

from bluefog_tpu import serving
from bluefog_tpu import topology as T
from bluefog_tpu.runtime.async_windows import run_async_dsgd
from bluefog_tpu.runtime.window_server import WindowServer
from bluefog_tpu.serving.replica import ServingReplica
from bluefog_tpu.serving.subscriber import Subscriber

N_RANKS = 3
DIM = 8
EVERY = 2          # subscription stride: push every 2nd round
STALENESS_K = 60   # rounds of slack the SLO allows: the stride plus
                   # delivery lag — at ~5 ms/round that is ~300 ms of
                   # scheduler noise headroom on a loaded CI host
NAME = "serving_replica_demo"
GROUP = f"{NAME}:0"


def main() -> int:
    targets = np.stack([np.full(DIM, float(r + 1)) for r in range(N_RANKS)])

    def loss_and_grad(r, step, params):
        w = np.asarray(params["w"], np.float64)
        diff = w - targets[r]
        return 0.5 * float(diff @ diff), {"w": diff}

    template = {"w": np.zeros(DIM, np.float32)}

    # the training fleet runs in a background thread; the "service" is
    # the main thread — the two touch ONLY through the snapshot fabric
    report_box = {}

    def train():
        report_box["report"] = run_async_dsgd(
            T.FullyConnectedGraph(N_RANKS), template, loss_and_grad,
            lr=0.05, duration_s=4.0, skew=[0.005] * N_RANKS,
            name=NAME, snapshot_every=1)

    trainer = threading.Thread(target=train, daemon=True)
    trainer.start()

    srv = WindowServer()
    addr = srv.start("127.0.0.1")

    # an auditing subscriber rides alongside the replica: every pushed
    # snapshot's in-band `round` stamp leaf must equal the frame's round
    audit = {"frames": 0, "mismatches": 0}

    def check_stamp(snap):
        audit["frames"] += 1
        if int(snap.leaves["round"][0]) != snap.round:
            audit["mismatches"] += 1

    auditor = Subscriber(addr, GROUP, every=1, on_snapshot=check_stamp)

    replica = ServingReplica(addr, GROUP, template, every=EVERY)
    replica.wait_ready(timeout_s=20.0)

    # sample the staleness SLO while training progresses
    tbl = serving.table()
    worst_age = 0
    samples = 0
    first_round = replica.round
    while trainer.is_alive() and tbl.current_round(GROUP) >= 0:
        live = tbl.current_round(GROUP)
        if live < 0:
            break  # training finished and dropped its groups
        age = replica.staleness_rounds(live)
        worst_age = max(worst_age, age)
        samples += 1
        assert age <= STALENESS_K, (
            f"staleness SLO violated: replica at round {replica.round}, "
            f"trainer at {live} (age {age} > K={STALENESS_K})")
        # serve a "prediction" from the live weights: the de-biased
        # model applied to a probe input
        w = np.asarray(replica.params()["w"], np.float64)
        _ = float(w @ np.ones(DIM))
        time.sleep(0.05)
    trainer.join(timeout=30)
    final_round = replica.round

    report = report_box["report"]
    auditor.close()
    replica.close()
    srv.stop()

    print(f"steps per rank   : {report.steps_per_rank}")
    print(f"replica rounds   : first={first_round} final={final_round} "
          f"adopted={replica.adopted}")
    print(f"staleness        : worst={worst_age} over {samples} samples "
          f"(SLO K={STALENESS_K})")
    print(f"round-stamp audit: {audit['frames']} frames, "
          f"{audit['mismatches']} mismatches")

    # the replica followed a LIVE model...
    assert final_round > first_round, (first_round, final_round)
    assert replica.adopted >= 3, replica.adopted
    assert samples >= 3 and worst_age <= STALENESS_K, (samples, worst_age)
    # ...every delivered snapshot was round-consistent, exactly...
    assert audit["frames"] >= 3 and audit["mismatches"] == 0, audit
    # ...training was never perturbed by the readers (exact mass audit)...
    assert abs(report.total_mass - N_RANKS) < 1e-9 * N_RANKS, \
        report.total_mass
    # ...and the served model converged with the fleet: close to the
    # consensus optimum (the mean of the rank targets)
    w = np.asarray(replica.params()["w"], np.float64)
    optimum = targets.mean(axis=0)
    err = float(np.abs(w - optimum).max())
    print(f"served model err : {err:.3e} vs consensus optimum")
    assert err < 0.5, err
    print("serving_replica: OK")
    return 0


if __name__ == "__main__":
    sys.exit(main())
