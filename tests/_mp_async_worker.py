"""Async-DSGD multi-process test worker (one OS process per rank).

argv: <rank> <nranks> <barrier_dir> <duration_s> <skew_ms> [transport]

Runs one rank of :func:`run_async_dsgd_rank` over a ring: cross-process
``MPI_Put``-style deposits through named-shm windows, NO barrier in the
training loop, deliberately skewed step rates.  Rank 0 audits the returned
report and asserts the two invariants the reference's one-sided path
guarantees (SURVEY §3.4):

1. **mass conservation** — push-sum mass (sum of p) stays exactly the world
   size under arbitrary cross-process interleaving;
2. **convergence under skew** — every rank's de-biased iterate lands near
   the TRUE (plain-mean) optimum of the per-rank quadratics despite the
   rate skew: the push-sum ``p`` weighting is precisely the de-biasing that
   keeps a fast rank from dominating (Nedić & Olshevsky) — observed
   empirically here, with a small consensus gap, while the measured step
   counts confirm the skew really happened.

Prints ASYNC_MP_OK <rank> on success.
"""

import os
import sys

os.environ["JAX_PLATFORMS"] = "cpu"

import numpy as np


def main():
    rank, nranks = int(sys.argv[1]), int(sys.argv[2])
    barrier_dir, duration_s = sys.argv[3], float(sys.argv[4])
    skew_ms = float(sys.argv[5])
    transport = sys.argv[6] if len(sys.argv) > 6 else "shm"

    import jax

    jax.config.update("jax_platforms", "cpu")

    from bluefog_tpu.runtime.async_windows import (FileBarrier,
                                                   run_async_dsgd_rank)
    from bluefog_tpu.topology import RingGraph

    topo = RingGraph(nranks)
    # per-rank quadratic: 0.5*||w - c_r||^2 ; global optimum = mean of c_r,
    # async stationary point = step-rate-weighted mean of c_r
    targets = np.stack([np.full(4, float(r + 1)) for r in range(nranks)])
    params0 = {"w": np.zeros(4, np.float32)}

    def loss_and_grad(r, step, params):
        w = np.asarray(params["w"], np.float64)
        diff = w - targets[r]
        return 0.5 * float(diff @ diff), {"w": diff}

    report = run_async_dsgd_rank(
        topo, rank, params0, loss_and_grad,
        barrier=FileBarrier(barrier_dir, nranks, rank),
        lr=0.05, duration_s=duration_s, skew_s=skew_ms / 1000.0,
        name=f"dsgd_mp_test_{os.path.basename(barrier_dir)}",
        transport=transport, tcp_bind="127.0.0.1")

    if rank == 0:
        assert report is not None
        # 1. mass conservation is EXACT (f64 sums of halving fractions)
        assert abs(report.total_mass - nranks) < 1e-9 * nranks, \
            f"mass leaked: {report.total_mass} != {nranks}"
        # skew really happened: rank 0 (no extra sleep) outstepped the
        # slowest rank, and everyone took real steps
        steps = report.steps_per_rank
        assert min(steps) >= 5, steps
        assert steps[0] > 1.5 * steps[-1], \
            f"no skew observed in step counts {steps}"
        # 2. convergence: near the TRUE mean optimum — the p de-biasing
        # cancels the rate skew (a fast rank holds proportionally less mass,
        # so its extra gradient steps carry proportionally less weight)
        c_mean = targets.mean(0)
        spread = float(np.abs(targets - c_mean).max())
        zs = np.stack([np.asarray(p["w"], np.float64)
                       for p in report.final_params])
        err = float(np.abs(zs - c_mean).max())
        assert err < 0.35 * spread, \
            f"far from mean optimum: err={err}, spread={spread}"
        gap = report.consensus_gap
        assert gap < 0.25 * spread, f"consensus gap {gap} vs spread {spread}"
        # rank 0's LOCAL loss is consistent with an iterate inside the
        # 0.35*spread band already asserted on the parameters (for
        # heterogeneous targets the local loss does NOT go to zero: at
        # exact consensus rank 0 still pays 0.5*||c_mean - c_0||^2, which
        # for n >= 3 equals its cold-start loss — so bound the loss by
        # the quadratic's value over the allowed parameter band instead
        # of pinning it to the consensus point)
        # NOTE the last recorded loss is MID-TRAINING (evaluated before
        # the final drain folds in-flight mass in), and between merges a
        # rank's de-biased iterate legitimately excursions toward its own
        # local optimum — so the band uses a wider deviation than the
        # 0.35*spread asserted on the post-drain parameters above
        l0 = report.losses[0]
        dist = np.abs(c_mean - targets[0])
        dev = 0.5 * spread
        lo = 0.5 * float((np.maximum(dist - dev, 0.0) ** 2).sum())
        hi = 0.5 * float(((dist + dev) ** 2).sum())
        assert lo <= l0[-1] <= hi, (l0[-1], lo, hi)

    print(f"ASYNC_MP_OK {rank}", flush=True)


if __name__ == "__main__":
    main()
