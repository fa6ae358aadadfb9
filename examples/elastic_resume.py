"""Elastic re-topology demo: lose half the slice, resume, keep converging.

The reference has no elastic story — a rank failure kills the MPI job
(SURVEY.md §5).  Here the same checkpoint drives training across a world
change: 8 gossip ranks train a quadratic consensus problem, checkpoint, and
then a "failure" takes half the slice away — the run resumes on 4 ranks via
``run_with_restart``'s automatic rank-axis resize (orphaned replicas fold
into survivors by averaging, so no rank's progress is lost) and converges to
the same optimum.

Self-asserting; exits nonzero on failure.

Run:
  JAX_PLATFORMS=cpu XLA_FLAGS=--xla_force_host_platform_device_count=8 \
  python examples/elastic_resume.py
"""

import argparse
import os
import sys
import tempfile

sys.path.insert(0, os.path.join(os.path.dirname(__file__), ".."))

import jax
import jax.numpy as jnp
import numpy as np
import optax
from jax import lax
from jax.sharding import PartitionSpec as P

import bluefog_tpu as bf
from bluefog_tpu.optim import DistributedNeighborAllreduceOptimizer
from bluefog_tpu.parallel.api import shard_map
from bluefog_tpu.topology import ExponentialTwoGraph, RingGraph
from bluefog_tpu.utils.checkpoint import CheckpointManager, run_with_restart

DIM = 6


def targets(n):
    """Rank r's local objective is ||w - c_r||^2; the consensus optimum is
    mean(c) — identical no matter how many ranks share the work."""
    return jnp.stack([jnp.full((DIM,), float(r)) for r in range(n)])


def make_phase(n, devices, steps, ckpt_every, mgr, seen=None):
    """A training phase at world size n: returns train_fn for
    run_with_restart (state = rank-stacked params).  ``seen`` (optional
    dict) records the start step the phase was entered at."""
    bf.shutdown()
    ctx = bf.init(topology=(ExponentialTwoGraph(n) if n > 2 else RingGraph(n)),
                  devices=devices)
    opt = DistributedNeighborAllreduceOptimizer(
        optax.sgd(0.05), topology=ctx.schedule, axis_name=ctx.axis_name)
    c = bf.rank_shard(targets(n))

    def body(w_blk, c_blk):
        w = w_blk[0]
        st = opt.init(w)

        def one(carry, _):
            w, st = carry
            g = w - c_blk[0]
            upd, st = opt.update(g, st, w)
            return (optax.apply_updates(w, upd), st), None

        (w, _), _ = lax.scan(one, (w, st), None, length=ckpt_every)
        return w[None]

    step_fn = jax.jit(shard_map(
        body, mesh=ctx.mesh, in_specs=(P(ctx.axis_name),) * 2,
        out_specs=P(ctx.axis_name), check_vma=False))

    def train_fn(state, start):
        if seen is not None:
            seen["start"] = start
        # state = {"w": (n, DIM)} — Orbax stores containers, not bare arrays
        w = bf.rank_shard(jnp.asarray(np.asarray(state["w"])))
        for s in range(start, steps // ckpt_every):
            w = step_fn(w, c)
            mgr.save(s + 1, {"w": w})
        mgr.wait()
        return {"w": w}

    return train_fn


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--steps", type=int, default=120,
                    help="total scan steps per phase")
    ap.add_argument("--ckpt-every", type=int, default=30)
    args = ap.parse_args()
    bf.configure_compile_cache()

    devs = jax.devices()
    if len(devs) < 8:
        raise SystemExit("need 8 devices (use the CPU-mesh env, see docstring)")
    ckdir = tempfile.mkdtemp(prefix="elastic_")
    mgr = CheckpointManager(ckdir, async_save=False)

    # ---- phase 1: world 8 ------------------------------------------------
    train8 = make_phase(8, devs[:8], args.steps, args.ckpt_every, mgr)
    w8 = np.asarray(run_with_restart(train8, mgr,
                                     {"w": jnp.zeros((8, DIM))})["w"])
    print(f"world 8 after {args.steps} steps: mean w = {w8.mean(0)[:3]}...")

    # ---- "failure": half the slice is gone; resume at world 4 ------------
    # run_with_restart restores the latest world-8 checkpoint and resizes it
    # onto the 4-rank template (rank j folds ranks j and j+4 by mean).
    seen = {}
    train4 = make_phase(4, devs[:4], 2 * args.steps, args.ckpt_every, mgr,
                        seen=seen)
    w4 = np.asarray(run_with_restart(train4, mgr,
                                     {"w": jnp.zeros((4, DIM))})["w"])

    # World 8's optimum is mean(0..7) = 3.5; world 4's local targets alone
    # would give 1.5 — reaching ~1.5 after resume proves training CONTINUED
    # on the new world (re-anchored to its objective) from folded state, not
    # from scratch (folded start = 3.5-ish, far from 0).
    print(f"world 4 after resume: mean w = {w4.mean(0)[:3]}...")
    gap = np.abs(w4.mean(0) - 1.5).max()
    spread = (w4.max(0) - w4.min(0)).max()
    print(f"optimum gap {gap:.3f}, consensus spread {spread:.3f}, "
          f"phase-2 entered at checkpoint step {seen.get('start')}")

    ok = True
    if not seen.get("start"):
        ok = False
        print("FAIL: phase 2 did not resume from the world-8 checkpoint "
              "(started from scratch)")
    if gap > 0.3:
        ok = False
        print("FAIL: resumed world did not converge to its consensus optimum")
    if spread > 0.3:
        ok = False
        print("FAIL: resumed ranks did not reach consensus")
    mgr.close()
    if not ok:
        sys.exit(1)
    print("OK — resumed on half the world from the same checkpoint and "
          "converged (elastic re-topology)")


if __name__ == "__main__":
    main()
