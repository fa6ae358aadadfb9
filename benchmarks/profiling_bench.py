"""Continuous-profiling overhead + attribution benchmark.

Three questions, all acceptance-gated (ISSUE 18):

1. **What does an armed sampler cost the host path?**  On the PR 4
   transport bench shape (ResNet-50-sized leaf mixture, pipelined
   batched deposits into a remote process's window server), measure
   per-round latency with the profiler OFF and ON (97 Hz, the shipping
   default), interleaved A/B so machine drift is fair to both.  Gate:
   enabled p50 overhead ≤ 1%.

2. **Is the disabled path exactly free?**  Not "cheap": ZERO.  No
   ``bf-prof-sampler`` thread exists, and arming then disarming the
   profiler leaves freshly-jitted HLO byte-identical (the profiler
   must never hook compilation).  Gate: both hold.

3. **Do samples attribute?**  Run the fleet digital twin
   (``FleetSim``, 64 simulated ranks) under the profiler: the sim's
   rounds execute inside ``sim``-source phase spans, so the merged
   profile must attribute ≥ 60% of samples to real phases and its top
   frames must name the simulator's event core (``core.py`` /
   ``fleet.py``) — the bfsim hot path as measured evidence.

Run:  python benchmarks/profiling_bench.py [--small]
Prints one JSON line (committed as BENCH_profiling.json at the repo
root).  rc=0 when every gate holds, rc=1 otherwise.
"""

import argparse
import json
import os
import subprocess
import sys
import tempfile
import threading
import time

sys.path.insert(0, os.path.join(os.path.dirname(__file__), ".."))

import numpy as np

_RESNET50_LEAVES = ([2048 * 1024, 1024 * 1024 * 2, 2359296, 2359296,
                     1179648, 1179648, 589824, 589824, 262144, 262144]
                    + [65536] * 40 + [2048] * 60 + [512] * 50)
_SMALL_LEAVES = [65536] * 4 + [2048] * 8

_OWNER_CODE = """
import os, sys
os.environ.setdefault('JAX_PLATFORMS', 'cpu')
os.environ.pop('BLUEFOG_TPU_PROFILE', None)  # the owner is unprofiled
import numpy as np
sys.path.insert(0, {repo!r})
from bluefog_tpu.runtime.async_windows import AsyncWindow
from bluefog_tpu.runtime.window_server import WindowServer
sizes = {sizes!r}
wins = [AsyncWindow(f'prb:{{i}}', 1, n, np.float32)
        for i, n in enumerate(sizes)]
srv = WindowServer()
_, port = srv.start('127.0.0.1')
print(f'PORT {{port}}', flush=True)
sys.stdin.readline()
srv.stop()
for w in wins:
    w.free()
print('OWNER_OK', flush=True)
"""


def _percentile(xs, q):
    if not xs:
        return float("nan")
    xs = sorted(xs)
    i = min(len(xs) - 1, int(round(q * (len(xs) - 1))))
    return xs[i]


# ---------------------------------------------------------------------------
# leg 1 — enabled overhead on the transport round
# ---------------------------------------------------------------------------


def _run_rounds(port, sizes, payloads, rounds, profiled, prof_dir):
    """One client pass: per-round deposit-all-leaves + flush fence,
    returns per-round wall latencies.  ``profiled`` arms the 97 Hz
    sampler for the pass (it samples the main thread, the stream's
    sender thread, and the ack reader — the real enabled cost)."""
    from bluefog_tpu.profiling import sampler as ps
    from bluefog_tpu.runtime.window_server import (DepositStream,
                                                   PipelinedRemoteWindow)

    if profiled:
        ps.configure(prof_dir, rank=0, hz=97.0)
    stream = DepositStream(("127.0.0.1", port), 30.0,
                           max_in_flight=4, max_queue_items=1024,
                           max_batch_bytes=16 << 20)
    rws = [PipelinedRemoteWindow(("127.0.0.1", port), f"prb:{i}",
                                 stream=stream)
           for i in range(len(sizes))]
    for rw, p in zip(rws, payloads):  # warmup
        rw.deposit_async(0, p, accumulate=True)
    stream.flush()
    lat = []
    for _ in range(rounds):
        r0 = time.perf_counter()
        for rw, p in zip(rws, payloads):
            rw.deposit_async(0, p, accumulate=True)
        stream.flush()
        lat.append(time.perf_counter() - r0)
    for rw in rws:
        rw.close()
    if profiled:
        ps.reset()
    return lat


def bench_overhead(sizes, rounds, trials):
    payloads = [np.ones(n, np.float32) for n in sizes]
    owner = subprocess.Popen(
        [sys.executable, "-c",
         _OWNER_CODE.format(repo=os.path.join(os.path.dirname(
             os.path.abspath(__file__)), ".."), sizes=list(sizes))],
        stdin=subprocess.PIPE, stdout=subprocess.PIPE, text=True)
    line = owner.stdout.readline().split()
    assert line[0] == "PORT", line
    port = int(line[1])
    lat = {"off": [], "on": []}
    try:
        with tempfile.TemporaryDirectory(prefix="bf-prof-bench-") as td:
            for _ in range(trials):  # interleaved A/B: fair to drift
                lat["off"] += _run_rounds(port, sizes, payloads, rounds,
                                          False, td)
                lat["on"] += _run_rounds(port, sizes, payloads, rounds,
                                         True, td)
    finally:
        owner.stdin.write("\n")
        owner.stdin.flush()
        owner.wait(timeout=30)
    dense_mb = sum(s * 4 for s in sizes) / 1e6

    def stats(xs):
        p50 = _percentile(xs, 0.50)
        return {"round_p50_ms": round(p50 * 1e3, 3),
                "round_p99_ms": round(_percentile(xs, 0.99) * 1e3, 3),
                "MBps": round(dense_mb / 1e0 / p50, 1),
                "rounds": len(xs)}

    off, on = stats(lat["off"]), stats(lat["on"])
    frac = on["round_p50_ms"] / off["round_p50_ms"] - 1.0
    return {
        "variants": {"profiled_off": off, "profiled_on": on},
        "enabled_overhead_frac": round(frac, 4),
        "dense_mb_per_round": round(dense_mb, 1),
        "hz": 97.0,
        "overhead_ok": frac <= 0.01,
    }


# ---------------------------------------------------------------------------
# leg 2 — the disabled path is exactly zero
# ---------------------------------------------------------------------------


def bench_disabled():
    import jax
    import jax.numpy as jnp
    from bluefog_tpu.profiling import sampler as ps

    name = ps.Profiler.THREAD_NAME
    no_thread_before = not any(t.name == name
                               for t in threading.enumerate())

    @jax.jit
    def fn(x):
        return (x * 2.0 + 1.0).sum()

    x = jnp.arange(64.0)
    hlo_off = fn.lower(x).compile().as_text()
    with tempfile.TemporaryDirectory(prefix="bf-prof-bench-") as td:
        ps.configure(td, rank=0, hz=97.0)
        thread_when_armed = any(t.name == name
                                for t in threading.enumerate())
        hlo_on = fn.lower(x).compile().as_text()
        ps.reset()
    no_thread_after = not any(t.name == name
                              for t in threading.enumerate())
    hlo_identical = hlo_on == hlo_off
    return {
        "sampler_thread_absent_when_disabled": (no_thread_before
                                                and no_thread_after),
        "sampler_thread_present_when_armed": thread_when_armed,
        "hlo_byte_identical": hlo_identical,
        "disabled_ok": (no_thread_before and no_thread_after
                        and thread_when_armed and hlo_identical),
    }


# ---------------------------------------------------------------------------
# leg 3 — phase attribution on the fleet digital twin
# ---------------------------------------------------------------------------


def bench_sim(n_ranks, horizon_s):
    from bluefog_tpu.profiling import report as pr
    from bluefog_tpu.profiling import sampler as ps
    from bluefog_tpu.sim.fleet import FleetSim, SimConfig

    with tempfile.TemporaryDirectory(prefix="bf-prof-bench-") as td:
        ps.configure(td, rank=0, hz=400.0)
        t0 = time.perf_counter()
        sim = FleetSim(SimConfig(n_ranks=n_ranks, seed=3))
        sim.run(horizon_s)
        wall = time.perf_counter() - t0
        ps.reset()
        rep = pr.merge(td)
    top = pr.top_table(rep, n=8)
    core_named = any(("core.py:" in fr or "fleet.py:" in fr)
                     for fr, _, _ in top)
    attributed = rep["attributed_frac"]
    return {
        "sim_ranks": n_ranks,
        "sim_horizon_s": horizon_s,
        "sim_wall_s": round(wall, 2),
        "samples": rep["samples"],
        "phase_frac": rep["phase_frac"],
        "attributed_frac": round(attributed, 4),
        "top_frames": [[fr, n] for fr, n, _ in top],
        "sim_attrib_ok": (attributed >= 0.60 and core_named
                          and rep["samples"] >= 200),
    }


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--small", action="store_true",
                    help="small leaf set + short sim (CI smoke)")
    ap.add_argument("--rounds", type=int, default=8)
    ap.add_argument("--trials", type=int, default=4)
    args = ap.parse_args()

    sizes = _SMALL_LEAVES if args.small else _RESNET50_LEAVES
    overhead = bench_overhead(sizes, args.rounds, args.trials)
    disabled = bench_disabled()
    sim = bench_sim(n_ranks=16 if args.small else 64,
                    horizon_s=10.0 if args.small else 60.0)

    ok = (overhead["overhead_ok"] and disabled["disabled_ok"]
          and sim["sim_attrib_ok"])
    report = {
        "metric": "profiling_overhead_and_attribution",
        "tree": "small" if args.small else "resnet50",
        "leaves": len(sizes),
        "params": int(sum(sizes)),
        **overhead,
        **disabled,
        **sim,
    }
    print(json.dumps(report))
    return 0 if ok else 1


if __name__ == "__main__":
    os.environ.setdefault("JAX_PLATFORMS", "cpu")
    sys.exit(main())
