"""Decentralized optimization algorithms on the one-sided / gossip layers —
the reference's decentralized-optimization example scripts (upstream
``examples/pytorch_least_squares*.py`` family; BASELINE.json configs[2,3]:
push-sum DSGD on a time-varying directed graph via win_accumulate, and
gradient-tracking / EXTRA-style methods on MeshGrid2DGraph via win_get).

Problem: distributed least squares.  Rank r holds (A_r, b_r); the network
minimizes  f(x) = sum_r ||A_r x - b_r||^2 / 2  whose optimum x* solves
(sum A_r^T A_r) x* = sum A_r^T b_r — computed in closed form for validation.

Algorithms:
- ``push_sum``      — directed ring, mass-weighted gossip via win_accumulate;
                      handles non-doubly-stochastic (directed) topologies.
- ``gradient_tracking`` — MeshGrid2D, tracks the global average gradient via
                      an auxiliary variable; converges to the *exact* optimum
                      with a constant step size (win_get path).
- ``exact_diffusion``  — correction-term diffusion, exact convergence on
                      doubly-stochastic topologies.

Run:  JAX_PLATFORMS=cpu XLA_FLAGS=--xla_force_host_platform_device_count=8 \
      python examples/decentralized_optimization.py \
      --algorithm gradient_tracking
"""

import argparse
import os
import sys

sys.path.insert(0, os.path.join(os.path.dirname(__file__), ".."))  # repo-root run

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax
from jax.sharding import PartitionSpec as P

import bluefog_tpu as bf
from bluefog_tpu.ops import collectives as C
from bluefog_tpu.ops import windows as W
from bluefog_tpu.parallel.api import shard_map
from bluefog_tpu.topology import MeshGrid2DGraph, RingGraph, build_schedule

DIM = 6


def make_problem(n, key):
    ka, kb = jax.random.split(key)
    A = jax.random.normal(ka, (n, 12, DIM))
    b = jax.random.normal(kb, (n, 12))
    AtA = np.einsum("rmi,rmj->ij", np.asarray(A), np.asarray(A))
    Atb = np.einsum("rmi,rm->i", np.asarray(A), np.asarray(b))
    x_star = np.linalg.solve(AtA, Atb)
    return A, b, x_star


def grad(A, b, x):
    return A.T @ (A @ x - b)


def push_sum(n, A, b, steps, lr):
    """Push-sum subgradient method on the directed ring (win_accumulate with
    the associated push-sum scalar — the reference's win-ops-with-associated-p
    mode: the weight ``p`` rides every transfer automatically)."""
    topo = RingGraph(n, connect_style=1)
    sched = build_schedule(topo)

    def body(A_blk, b_blk):
        Ar, br = A_blk[0], b_blk[0]
        win = W.win_create(jnp.zeros((DIM,)), sched, "bf", associated_p=True)

        def step(win, t):
            x, p = win.self_buf, W.win_associated_p(win)
            z = x / jnp.maximum(p, 1e-12)       # de-biased estimate
            lr_t = lr / jnp.sqrt(1.0 + t / 100.0)  # diminishing step: exact limit
            x = x - lr_t * grad(Ar, br, z) * p  # scaled subgradient step
            win = W.win_sync(win, x)            # republish post-gradient mass
            # send half the (value, p) mass to the out-neighbor — p ships
            # automatically with the same dst_weight
            win = W.win_accumulate(win, None, "bf", dst_weight=0.5)
            win = win.replace(self_buf=0.5 * win.self_buf,
                              assoc_self=0.5 * win.assoc_self)
            _, win = W.win_update_then_collect(win, "bf")
            return win, None

        win, _ = lax.scan(step, win, jnp.arange(steps))
        p = W.win_associated_p(win)
        return (win.self_buf / jnp.maximum(p, 1e-12))[None]

    return body


def gradient_tracking(n, A, b, steps, lr):
    """Gradient tracking on MeshGrid2D — the win_get config: each rank
    publishes (x, y) in a window, pulls neighbors' copies, and mixes."""
    topo = MeshGrid2DGraph(n)
    sched = build_schedule(topo)

    def body(A_blk, b_blk):
        Ar, br = A_blk[0], b_blk[0]
        x = jnp.zeros((DIM,))
        g = grad(Ar, br, x)
        y = g
        win = W.win_create({"x": x, "y": y}, sched, "bf")

        def step(carry, t):
            x, y, g_prev, win = carry
            win = W.win_sync(win, {"x": x, "y": y})        # publish
            win = W.win_get(win, "bf")                     # one-sided pull
            mixed, win = W.win_update(win, "bf")           # weighted mix
            x_new = mixed["x"] - lr * y
            g_new = grad(Ar, br, x_new)
            y_new = mixed["y"] + g_new - g_prev
            return (x_new, y_new, g_new, win), None

        (x, y, _, _), _ = lax.scan(step, (x, y, g, win), jnp.arange(steps))
        return x[None]

    return body


def exact_diffusion(n, A, b, steps, lr):
    """Exact diffusion (ATC form) on the bidirectional ring (gossip layer)."""
    topo = RingGraph(n, connect_style=0)
    sched = build_schedule(topo)

    def body(A_blk, b_blk):
        Ar, br = A_blk[0], b_blk[0]
        x = jnp.zeros((DIM,))
        psi_prev = x

        def step(carry, t):
            x, psi_prev = carry
            phi = x - lr * grad(Ar, br, x)
            psi = phi + x - psi_prev
            x_new = C.neighbor_allreduce(psi, sched, "bf")
            return (x_new, phi), None

        (x, _), _ = lax.scan(step, (x, psi_prev), jnp.arange(steps))
        return x[None]

    return body


ALGORITHMS = {
    # (builder, steps, lr, tolerance) — lr bounded by the topology's spectral
    # gap x local curvature; gradient tracking diverges past ~0.008 on the
    # 2x4 grid with this problem scale (verified against a numpy oracle)
    "push_sum": (push_sum, 6000, 0.01, 2e-2),
    "gradient_tracking": (gradient_tracking, 2500, 0.004, 1e-5),
    "exact_diffusion": (exact_diffusion, 800, 0.02, 1e-3),
}


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--algorithm", choices=sorted(ALGORITHMS), default="gradient_tracking")
    ap.add_argument("--steps", type=int, default=None)
    ap.add_argument("--lr", type=float, default=None)
    args = ap.parse_args()
    bf.configure_compile_cache()

    n = len(jax.devices())
    bf.init()
    ctx = bf.get_context()

    builder, d_steps, d_lr, tol = ALGORITHMS[args.algorithm]
    steps = args.steps or d_steps
    lr = args.lr or d_lr

    A, b, x_star = make_problem(n, jax.random.PRNGKey(7))
    body = builder(n, A, b, steps, lr)
    f = jax.jit(shard_map(
        body, mesh=ctx.mesh, in_specs=(P("bf"), P("bf")), out_specs=P("bf"),
        check_vma=False,
    ))
    xs = np.asarray(f(A, b))

    err = np.abs(xs - x_star).max()
    consensus = (xs.max(axis=0) - xs.min(axis=0)).max()
    print(f"{args.algorithm}: steps={steps} lr={lr}")
    print(f"  max|x_r - x*|     = {err:.3e}")
    print(f"  consensus spread  = {consensus:.3e}")
    print(f"  x*                = {np.round(x_star, 4)}")
    assert err < tol, f"failed to reach optimum (err={err:.3e}, tol={tol})"
    print("OK")


if __name__ == "__main__":
    main()
