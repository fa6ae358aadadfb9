"""Elastic membership demo: ranks join, leave, and warm-start mid-run.

An autoscaling decentralized fleet in one process: a capacity-5 job
starts with 3 member ranks training a quadratic consensus problem over
asynchronous push-sum windows.  At t=0.5s a 4th rank JOINS — it
warm-starts by reading a live member's published (x, p) window snapshot
(no checkpoint file anywhere) and is admitted at a round boundary.  At
t=1.5s one of the original ranks LEAVES gracefully — it hands its
entire push-sum mass to its out-neighbors in drain-flagged deposits, so
the mass audit stays exact (a leaver's mass is conserved, unlike a
corpse's, which is written off).  The mixing graph re-plans over the
live member set at every membership boundary
(``topology.replan`` — deterministic in the member list).

Self-asserting; exits nonzero on failure.

Run:
  JAX_PLATFORMS=cpu \
  python examples/elastic_membership.py
"""

import os
import sys

sys.path.insert(0, os.path.join(os.path.dirname(__file__), ".."))

os.environ.setdefault("JAX_PLATFORMS", "cpu")

import numpy as np

from bluefog_tpu import topology as T
from bluefog_tpu.runtime.async_windows import run_async_dsgd
from bluefog_tpu.runtime.resilience import ResilienceConfig

CAPACITY = 5
DIM = 6


def main() -> int:
    # each rank pulls toward its own target; consensus lands on the mean
    targets = np.stack([np.full(DIM, float(r + 1))
                        for r in range(CAPACITY)])

    def loss_and_grad(r, step, params):
        w = np.asarray(params["w"], np.float64)
        diff = w - targets[r]
        return 0.5 * float(diff @ diff), {"w": diff}

    report = run_async_dsgd(
        T.FullyConnectedGraph(CAPACITY),       # the job's CAPACITY
        {"w": np.zeros(DIM, np.float32)},
        loss_and_grad,
        lr=0.05,
        duration_s=2.5,
        skew=[0.001] * CAPACITY,
        name="elastic_membership_demo",
        resilience=ResilienceConfig(suspect_after_s=0.2, dead_after_s=0.6),
        join_at_s={3: 0.5,                     # rank 3 attaches at 0.5 s
                   4: []},                     # rank 4: reserved capacity
        leave_at_s={1: 1.5},                   # rank 1 drains at 1.5 s
    )

    print(f"steps per rank : {report.steps_per_rank}")
    print(f"joined         : {report.joined_ranks}")
    print(f"left           : {report.left_ranks}")
    print(f"consensus gap  : {report.consensus_gap:.2e}")
    print(f"mass audit     : total={report.total_mass:.12f} "
          f"baseline={report.baseline_mass}")

    # the elastic lifecycle happened...
    assert report.joined_ranks == [3], report.joined_ranks
    assert report.left_ranks == [1], report.left_ranks
    assert report.dead_ranks == [], report.dead_ranks
    # ...the joiner trained meaningfully after its warm-start...
    assert report.steps_per_rank[3] > 20, report.steps_per_rank
    # ...the final members reached consensus...
    assert report.consensus_gap < 0.5, report.consensus_gap
    # ...and the push-sum mass audit is EXACT over the churn: 3 initial
    # units + 1 admission, the leaver's unit conserved via its handoff
    assert report.baseline_mass == 4.0, report.baseline_mass
    assert abs(report.total_mass - report.baseline_mass) < 1e-9, \
        report.total_mass
    print("elastic_membership: OK")
    return 0


if __name__ == "__main__":
    sys.exit(main())
