"""Cross-process asynchronous decentralized SGD — self-asserting.

``examples/async_dsgd.py`` runs the reference's asynchronous execution model
(``DistributedWinPutOptimizer``, SURVEY.md §3.4) with rank *threads*.  This
example runs it the way the reference actually deploys — **one OS process
per rank** (``mpirun -np N``): each process exposes its landing window and
deposits into its neighbors' windows directly (``MPI_Put`` crossing a real
process boundary, no receiver involvement, no barrier anywhere in the
training loop).  ``--transport shm`` (default) backs the windows with named
POSIX shared memory (same-host ranks); ``--transport tcp`` serves each
process's windows over the TCP window server — the cross-host/DCN shape,
demoed here on loopback.

Each rank-process trains a small MLP regressor on its own shard of a
synthetic linear problem, with deliberately skewed step rates.  The parent
re-execs this file with ``--worker R`` per rank and asserts from rank 0's
report:

  1. the skew materialized (fastest rank >= 1.5x the steps of the slowest),
  2. push-sum mass is conserved exactly (sum of p == n to 1e-9),
  3. rank 0's loss fell by >= 50%,
  4. ranks agree: consensus gap small relative to parameter scale.

Run:  python examples/async_dsgd_mp.py [--ranks 2] [--duration 3]

``--resilient`` (tcp transport) arms the peer-fault-tolerance layer
(docs/resilience.md): deposit streams reconnect with bounded backoff and
replay idempotently, a dead peer is healed out of the mixing weights, and
the surviving set's mass audit stays exact.  Pair it with the chaos CLI
to watch one of three ranks get SIGKILLed mid-run and the survivors
finish anyway::

    bfchaos-tpu --spec "rank2:sigkill:at_step=25" -- \\
        python examples/async_dsgd_mp.py --ranks 3 --transport tcp \\
        --duration 4 --resilient
"""

import argparse
import os
import subprocess
import sys
import tempfile

sys.path.insert(0, os.path.join(os.path.dirname(__file__), ".."))

_REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def worker(rank: int, n: int, bdir: str, duration_s: float, lr: float,
           transport: str, resilient: bool = False):
    import jax

    jax.config.update("jax_platforms", "cpu")
    import jax.numpy as jnp
    import numpy as np

    from bluefog_tpu.runtime.async_windows import (FileBarrier,
                                                   run_async_dsgd_rank)
    from bluefog_tpu.topology import RingGraph

    # shard r of a synthetic linear regression y = X @ w* + noise
    rng = np.random.default_rng(1234)
    w_star = rng.standard_normal(16).astype(np.float32)
    X = rng.standard_normal((n * 64, 16)).astype(np.float32)
    y = X @ w_star + 0.01 * rng.standard_normal(n * 64).astype(np.float32)
    Xr = jnp.asarray(X[rank * 64:(rank + 1) * 64])
    yr = jnp.asarray(y[rank * 64:(rank + 1) * 64])

    params0 = {"w": jnp.zeros(16, jnp.float32), "b": jnp.zeros((), jnp.float32)}

    @jax.jit
    def lag(params):
        def loss_fn(p):
            pred = Xr @ p["w"] + p["b"]
            return jnp.mean((pred - yr) ** 2)

        return jax.value_and_grad(loss_fn)(params)

    def loss_and_grad(r, step, params):
        loss, grads = lag(params)
        return float(loss), grads

    # base sleep scales with rank count so the skew stays visible above
    # scheduler contention when many rank processes share few cores; the
    # pipelined tcp transport runs background sender/ack threads that
    # raise every rank's per-step floor by several ms, so its skew must
    # be an order larger to dominate
    base = 0.004 if transport == "tcp" else 0.0005
    skew_s = base * max(n - 1, 1) * (1.0 + 4.0 * rank / max(n - 1, 1))
    resilience = None
    if resilient:
        from bluefog_tpu.runtime.resilience import ResilienceConfig

        # a complete graph gives every survivor a direct stream to the
        # victim, so detection is transport-native on all of them
        resilience = ResilienceConfig(reconnect_base_s=0.05,
                                      reconnect_cap_s=0.3,
                                      reconnect_budget=4, seed=rank,
                                      barrier_timeout_s=20.0)
    from bluefog_tpu.topology import FullyConnectedGraph

    topo = (FullyConnectedGraph(n) if resilient and n > 2
            else RingGraph(n))
    report = run_async_dsgd_rank(
        topo, rank, params0, loss_and_grad,
        barrier=FileBarrier(bdir, n, rank), lr=lr, duration_s=duration_s,
        skew_s=skew_s, name=f"async_dsgd_mp_{os.path.basename(bdir)}",
        transport=transport, tcp_bind="127.0.0.1", resilience=resilience)

    if rank == 0:
        steps = report.steps_per_rank
        if report.dead_ranks:
            alive = [r for r in range(n) if r not in report.dead_ranks]
            assert min(steps[r] for r in alive) >= 5, steps
            if report.baseline_mass is not None:
                assert abs(report.total_mass - report.baseline_mass) \
                    <= 1e-9 * n, (report.total_mass, report.baseline_mass)
            print(f"steps/rank: {steps}  (rank(s) {report.dead_ranks} "
                  "died mid-run; survivors healed and finished)")
            print(f"surviving mass: {report.total_mass:.12f}  "
                  f"(post-heal baseline {report.baseline_mass})")
            print(f"OK — survived peer death over {transport}; audit "
                  "exact over the surviving set")
        else:
            assert min(steps) >= 5, f"a rank starved: {steps}"
            assert max(steps) >= 1.5 * min(steps), f"no skew in {steps}"
            assert abs(report.total_mass - n) < 1e-9 * n, report.total_mass
            l0 = report.losses[0]
            assert l0[-1] < 0.5 * l0[0], (l0[0], l0[-1])
            import numpy as np

            scale = float(np.abs(w_star).max())
            assert report.consensus_gap < 0.05 * scale, \
                report.consensus_gap
            print(f"steps/rank: {steps}  (skewed, barrier-free)")
            print(f"push-sum mass: {report.total_mass:.12f}  "
                  f"(== {n} exactly)")
            print(f"rank-0 loss: {l0[0]:.3f} -> {l0[-1]:.4f}")
            print(f"consensus gap: {report.consensus_gap:.2e}")
            print(f"OK — async DSGD spanned real OS processes over "
                  f"{transport} with no barrier")
    print(f"WORKER_DONE {rank}", flush=True)


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--ranks", type=int, default=2)
    ap.add_argument("--duration", type=float, default=3.0, metavar="SECONDS")
    ap.add_argument("--lr", type=float, default=0.1)
    ap.add_argument("--transport", choices=["shm", "tcp"], default="shm",
                    help="deposit fabric: shm (same host) or tcp (the\n                    cross-host/DCN window server, demoed on loopback)")
    ap.add_argument("--resilient", action="store_true",
                    help="arm peer-fault tolerance (tcp): reconnect/"
                         "replay, self-healing gossip — pair with "
                         "bfchaos-tpu to kill a rank mid-run")
    ap.add_argument("--worker", type=int, default=None, help=argparse.SUPPRESS)
    ap.add_argument("--bdir", default=None, help=argparse.SUPPRESS)
    args = ap.parse_args()
    if args.resilient and args.transport != "tcp":
        ap.error("--resilient requires --transport tcp (detection is "
                 "transport-native on the deposit streams)")

    if args.worker is not None:
        worker(args.worker, args.ranks, args.bdir, args.duration, args.lr,
               args.transport, args.resilient)
        return

    env = dict(os.environ)
    env["JAX_PLATFORMS"] = "cpu"
    env["PYTHONPATH"] = _REPO + os.pathsep + env.get("PYTHONPATH", "")
    with tempfile.TemporaryDirectory() as bdir:
        procs = [
            subprocess.Popen(
                [sys.executable, os.path.abspath(__file__),
                 "--ranks", str(args.ranks), "--duration", str(args.duration),
                 "--lr", str(args.lr), "--transport", args.transport,
                 "--worker", str(r), "--bdir", bdir]
                + (["--resilient"] if args.resilient else []),
                env=env, cwd=_REPO)
            for r in range(args.ranks)
        ]
        try:
            rcs = [p.wait(timeout=120 + args.duration * 4) for p in procs]
        except subprocess.TimeoutExpired:
            # one hung worker (e.g. stuck at a barrier because a peer died)
            # must not orphan the rest against a vanishing barrier dir
            for p in procs:
                p.kill()
            for p in procs:
                p.wait()
            print("FAILED: a worker timed out; all workers killed",
                  file=sys.stderr)
            sys.exit(1)
    if args.resilient:
        # under chaos a rank may legitimately die mid-run (that is the
        # demo); the verdict is rank 0's — it audits the survivors
        if rcs[0] != 0:
            print(f"FAILED: reporting rank exit codes {rcs}",
                  file=sys.stderr)
            sys.exit(1)
        dead = [r for r, rc in enumerate(rcs) if rc]
        if dead:
            print(f"(rank(s) {dead} were killed by chaos; survivors "
                  "audited clean)")
    elif any(rcs):
        print(f"FAILED: worker exit codes {rcs}", file=sys.stderr)
        sys.exit(1)


if __name__ == "__main__":
    main()
