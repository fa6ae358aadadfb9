"""Causal-tracing overhead + critical-path-accuracy benchmark.

Two questions, both acceptance-gated (ISSUE 11):

1. **What does tracing cost the host path?**  On the PR 4 transport
   bench shape (ResNet-50-sized leaf mixture, pipelined batched
   deposits into a remote process's window server), measure per-round
   latency with tracing DISABLED (the shipping default: one env read +
   a None test per hook) and ENABLED (spans buffered + the wire trace
   header + extended acks).  The disabled path's budget is < 2%: the
   bench measures the per-hook disabled cost directly and bounds its
   share of a round, because a same-process A/B of "hooks present,
   disabled" vs "hooks absent" would require checking out the previous
   commit.  The enabled-path ratio is reported for context (tracing is
   opt-in; it has no budget, only honesty).

2. **Does the analyzer name the right edge?**  Against constructed
   ground truths — ring fleets with one KNOWN slow edge injected at a
   random position, server-side phases attached — ``critical_path``
   must name the injected edge in every case (accuracy 1.0), with the
   gating-time selector (a chatty fast edge must not outrank the slow
   edge rounds actually waited on).

Run:  python benchmarks/tracing_bench.py [--small]
Prints one JSON line (committed as BENCH_tracing.json at the repo
root).  No TPU, no jax required; rc=0 on any host, rc=1 when a gate
fails.
"""

import argparse
import json
import os
import subprocess
import sys
import tempfile
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
os.environ.pop('BLUEFOG_TPU_TRACE', None)  # the owner is untraced
import numpy as np
sys.path.insert(0, {repo!r})
from bluefog_tpu.runtime.async_windows import AsyncWindow
from bluefog_tpu.runtime.window_server import WindowServer
sizes = {sizes!r}
wins = [AsyncWindow(f'trb:{{i}}', 1, n, np.float32)
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
# overhead leg
# ---------------------------------------------------------------------------


def _run_rounds(port, sizes, payloads, rounds, traced, trace_dir):
    """One client pass: per-round deposit-all-leaves + flush fence,
    returns per-round wall latencies.  ``traced`` arms the process
    recorder BEFORE the stream is built (FEATURE_TRACE is a
    construction-time decision)."""
    from bluefog_tpu.runtime.window_server import (DepositStream,
                                                   PipelinedRemoteWindow)
    from bluefog_tpu.tracing import recorder as trc

    if traced:
        trc.configure(trace_dir, rank=0, job="tracing_bench")
    else:
        trc.reset()
    stream = DepositStream(("127.0.0.1", port), 30.0,
                           max_in_flight=4, max_queue_items=1024,
                           max_batch_bytes=16 << 20)
    rws = [PipelinedRemoteWindow(("127.0.0.1", port), f"trb:{i}",
                                 stream=stream)
           for i in range(len(sizes))]
    assert stream._trace_on == traced
    for rw, p in zip(rws, payloads):  # warmup
        rw.deposit_async(0, p, accumulate=True)
    stream.flush()
    lat = []
    for k in range(rounds):
        r0 = time.perf_counter()
        if traced:
            with trc.span("round", "dsgd", round_=k):
                for rw, p in zip(rws, payloads):
                    rw.deposit_async(0, p, accumulate=True)
                stream.flush()
        else:
            for rw, p in zip(rws, payloads):
                rw.deposit_async(0, p, accumulate=True)
            stream.flush()
        lat.append(time.perf_counter() - r0)
    for rw in rws:
        rw.close()
    if traced:
        trc.flush()
        trc.reset()
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
        with tempfile.TemporaryDirectory(prefix="bf-trace-bench-") as td:
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
    return {
        "variants": {"traced_off": off, "traced_on": on},
        "enabled_overhead_frac": round(
            on["round_p50_ms"] / off["round_p50_ms"] - 1.0, 4),
        "dense_mb_per_round": round(dense_mb, 1),
    }


def bench_disabled_hook(sizes, round_p50_ms):
    """The disabled path, measured directly: ns per hook when no
    recorder exists, times the hooks one transport round executes,
    as a fraction of the measured round — the honest < 2% bound."""
    from bluefog_tpu.tracing import recorder as trc

    trc.reset()
    n = 200_000
    t0 = time.perf_counter()
    for _ in range(n):
        trc.get()
    get_ns = (time.perf_counter() - t0) / n * 1e9
    t0 = time.perf_counter()
    for _ in range(n):
        with trc.span("x"):
            pass
    span_ns = (time.perf_counter() - t0) / n * 1e9
    # hooks per round on this shape: deposit_async does ONE trc.get()
    # per leaf; the sender/ack threads one per batch; the dsgd loop a
    # handful of span() shells
    hooks = len(sizes) + 16
    bound = (hooks * get_ns + 8 * span_ns) / (round_p50_ms * 1e6)
    return {"disabled_get_ns": round(get_ns, 1),
            "disabled_span_ns": round(span_ns, 1),
            "hooks_per_round": hooks,
            "disabled_overhead_frac_bound": round(bound, 6)}


# ---------------------------------------------------------------------------
# critical-path accuracy leg
# ---------------------------------------------------------------------------


def _ring_trace(n_ranks, slow_src, rounds, rng):
    """A ring fleet (r deposits to (r+1) % n) with ONE slow edge
    injected at slow_src -> (slow_src+1) % n; returns (spans, edge)."""
    dst = (slow_src + 1) % n_ranks
    spans, sid = [], 1
    for k in range(rounds):
        for r in range(n_ranks):
            slow = r == slow_src
            rdur = 0.9 if (r == dst) else 0.3 + rng.uniform(0, 0.05)
            spans.append(dict(sid=sid, par=0, tid=5, name="round",
                              cat="dsgd", rank=r, round=k, t0=float(k),
                              dur=rdur))
            sid += 1
            wdur = 0.7 if slow else 0.08 + rng.uniform(0, 0.03)
            wire = dict(sid=sid, par=0, tid=5, name="wire", cat="tcp",
                        rank=r, round=k, t0=k + 0.05, dur=wdur,
                        dst=f"w:{(r + 1) % n_ranks}", seq=k)
            sid += 1
            spans.append(wire)
            t_apply = k + (0.8 if slow else 0.15)
            spans.append(dict(sid=sid, par=wire["sid"], tid=5,
                              name="apply", cat="tcp_srv",
                              rank=(r + 1) % n_ranks, round=k,
                              t0=t_apply, dur=0.02))
            sid += 1
    return spans, [slow_src, dst]


def bench_accuracy(cases=20, seed=7):
    import bluefog_tpu.tracing.analyze as tan

    rng = np.random.default_rng(seed)
    correct = 0
    details = []
    for c in range(cases):
        n = int(rng.choice([3, 4, 6]))
        slow_src = int(rng.integers(0, n))
        spans, truth = _ring_trace(n, slow_src, rounds=6,
                                   rng=np.random.default_rng(seed + c))
        cp = tan.critical_path(tan.build_graph(spans))
        got = cp.get("gating_edge")
        ok = got == truth
        correct += ok
        details.append({"ranks": n, "truth": truth, "got": got})
    return {"cases": cases, "correct": correct,
            "accuracy": correct / cases, "details": details}


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--small", action="store_true",
                    help="small leaf set (CI smoke)")
    ap.add_argument("--rounds", type=int, default=8)
    ap.add_argument("--trials", type=int, default=3)
    args = ap.parse_args()

    sizes = _SMALL_LEAVES if args.small else _RESNET50_LEAVES
    overhead = bench_overhead(sizes, args.rounds, args.trials)
    disabled = bench_disabled_hook(
        sizes, overhead["variants"]["traced_off"]["round_p50_ms"])
    accuracy = bench_accuracy()

    ok_disabled = disabled["disabled_overhead_frac_bound"] < 0.02
    ok_accuracy = accuracy["accuracy"] == 1.0
    report = {
        "metric": "tracing_overhead_and_attribution",
        "tree": "small" if args.small else "resnet50",
        "leaves": len(sizes),
        "params": int(sum(sizes)),
        **overhead,
        **disabled,
        "critical_path_accuracy": {k: v for k, v in accuracy.items()
                                   if k != "details"},
        "gates": {"disabled_overhead_under_2pct": ok_disabled,
                  "accuracy_1_0": ok_accuracy},
    }
    print(json.dumps(report))
    return 0 if (ok_disabled and ok_accuracy) else 1


if __name__ == "__main__":
    os.environ.setdefault("JAX_PLATFORMS", "cpu")
    sys.exit(main())
