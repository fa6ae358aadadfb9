"""Serve-while-training multi-process acceptance worker.

argv: <mode> <id> <n_train> <barrier_dir> <duration_s> <name> [target]

modes:
  ``train``      one tcp dsgd rank (id = rank) publishing a round-stamped
                 ``(round, x, p)`` snapshot EVERY round.  Reader-side
                 chaos (``read:*`` / ``sub:*`` in ``BLUEFOG_TPU_CHAOS``,
                 set by the test) fires in THIS process — the serving
                 host — and must not perturb training: rank 0 asserts
                 the push-sum mass audit is EXACT (total == n to 1e-9·n,
                 i.e. identical to a chaos-free run) and that nobody
                 died.  Prints ``TRAIN_OK <rank>`` (rank 0 adds
                 ``AUDIT mass=...``).
  ``subscribe``  a reader process following trainer ``target``'s group
                 with a resumable Subscriber plus SnapshotClient spot
                 reads.  Audits EVERY delivered snapshot exactly:
                 in-band ``round`` stamp leaf == frame round, rounds
                 strictly increasing (no duplicate, no regression,
                 across any number of chaos-induced resumes), p > 0 and
                 x finite.  Prints ``SERVE_OK <id> delivered=N
                 resumes=R skipped=S``.

The test harness additionally SIGKILLs one subscriber mid-run and
SIGSTOP/SIGCONTs another — reader death and stall must leave training
and the surviving readers untouched.
"""

import os
import sys
import time

os.environ["JAX_PLATFORMS"] = "cpu"

import numpy as np


def _read_winaddr(barrier_dir: str, rank: int, timeout_s: float = 60.0):
    path = os.path.join(barrier_dir, f"winaddr.{rank}")
    deadline = time.monotonic() + timeout_s
    while True:
        try:
            with open(path) as f:
                host, port = f.read().strip().rsplit(":", 1)
            return host, int(port)
        except (FileNotFoundError, ValueError):
            if time.monotonic() > deadline:
                raise RuntimeError(f"no winaddr for rank {rank}")
            time.sleep(0.05)


def train(rank: int, n: int, barrier_dir: str, duration_s: float,
          name: str) -> None:
    import jax

    jax.config.update("jax_platforms", "cpu")

    from bluefog_tpu.runtime.async_windows import (FileBarrier,
                                                   run_async_dsgd_rank)
    from bluefog_tpu.runtime.resilience import ResilienceConfig
    from bluefog_tpu.topology import FullyConnectedGraph

    targets = np.stack([np.full(4, float(r + 1)) for r in range(n)])

    def loss_and_grad(r, step, params):
        w = np.asarray(params["w"], np.float64)
        diff = w - targets[r]
        return 0.5 * float(diff @ diff), {"w": diff}

    cfg = ResilienceConfig(
        suspect_after_s=0.5, dead_after_s=8.0,
        reconnect_base_s=0.05, reconnect_cap_s=0.3,
        reconnect_budget=5, seed=rank, barrier_timeout_s=90.0)
    report = run_async_dsgd_rank(
        FullyConnectedGraph(n), rank, {"w": np.zeros(4, np.float32)},
        loss_and_grad,
        barrier=FileBarrier(barrier_dir, n, rank),
        lr=0.05, duration_s=duration_s, skew_s=0.004,
        name=name, transport="tcp", tcp_bind="127.0.0.1",
        resilience=cfg, snapshot_every=1)
    if rank == 0:
        assert report is not None
        # the acceptance line: reader chaos (kills, stalls, torn reads,
        # torn pushes) must leave training's audit IDENTICAL to a
        # chaos-free run — exact mass conservation over the fixed fleet
        assert report.dead_ranks == [], report.dead_ranks
        assert abs(report.total_mass - n) <= 1e-9 * n, report.total_mass
        assert min(report.steps_per_rank) > 10, report.steps_per_rank
        print(f"AUDIT mass={report.total_mass!r} "
              f"steps={report.steps_per_rank}", flush=True)
    print(f"TRAIN_OK {rank}", flush=True)


def subscribe(sub_id: int, n: int, barrier_dir: str, duration_s: float,
              name: str, target: int) -> None:
    from bluefog_tpu.serving.client import SnapshotClient
    from bluefog_tpu.serving.subscriber import Subscriber

    addr = _read_winaddr(barrier_dir, target)
    group = f"{name}:{target}"
    sub = Subscriber(addr, group, every=1,
                     reconnect=dict(base_s=0.05, cap_s=0.4, budget=12,
                                    seed=sub_id),
                     idle_timeout_s=4.0, queue_max=64)
    delivered = 0
    last = -1
    # the audit window starts at the FIRST delivered snapshot: trainer
    # startup (jax import + rendezvous) must not eat the window
    first_deadline = time.monotonic() + 90.0
    deadline = None
    while True:
        now = time.monotonic()
        if (deadline or first_deadline) <= now:
            break
        try:
            snap = sub.get(timeout_s=0.5)
        except RuntimeError:
            break  # trainer gone for good (end of run)
        if snap is None:
            continue
        if deadline is None:
            deadline = time.monotonic() + duration_s
        # ---- the exact round-stamp audit, per delivered snapshot ----
        assert snap.round > last, (
            f"duplicate/regressed round {snap.round} after {last}")
        stamp = int(snap.leaves["round"][0])
        assert stamp == snap.round, (
            f"TORN snapshot: stamp leaf {stamp} != frame round "
            f"{snap.round}")
        p = float(snap.leaves["p"][0])
        assert p > 0.0 and np.isfinite(snap.leaves["x"]).all(), (
            "non-finite snapshot state")
        last = snap.round
        delivered += 1
    resumes = sub.resumes
    skipped = sub.skipped_rounds
    sub.close()

    # spot reads through the pull path too: round-consistent, stamped,
    # and at least as fresh as the subscription's cursor floor
    client = SnapshotClient(addr, group,
                            retry=dict(budget=8, cap_s=0.4, seed=sub_id))
    pulled = 0
    for _ in range(3):
        try:
            snap = client.snapshot(min_round=1, wait_s=5.0)
        except (RuntimeError, OSError):
            break  # trainer already tearing down
        assert int(snap.leaves["round"][0]) == snap.round, snap.round
        pulled += 1
    client.close()

    assert delivered >= 5, f"subscriber {sub_id} delivered {delivered}"
    print(f"SERVE_OK {sub_id} delivered={delivered} resumes={resumes} "
          f"skipped={skipped} pulled={pulled}", flush=True)


def main() -> None:
    mode = sys.argv[1]
    ident, n = int(sys.argv[2]), int(sys.argv[3])
    barrier_dir, duration_s = sys.argv[4], float(sys.argv[5])
    name = sys.argv[6]
    if mode == "train":
        train(ident, n, barrier_dir, duration_s, name)
    elif mode == "subscribe":
        subscribe(ident, n, barrier_dir, duration_s, name,
                  int(sys.argv[7]))
    else:
        raise SystemExit(f"unknown mode {mode!r}")


if __name__ == "__main__":
    main()
