"""Cross-host (TCP/DCN) window deposit transport benchmark.

Measures the host leg the device profile cannot see (PROFILE §6): sustained
one-sided deposit throughput and per-round latency into a REMOTE process's
window table over loopback TCP, for a ResNet-50-sized parameter tree split
into per-leaf windows — the deposit shape of one async-dsgd gossip round
toward one out-neighbor.

Five deposit variants, same byte stream:

- ``sync``       — the v1-wire-equivalent baseline: one blocking
                   request/response round-trip per leaf with v1's client
                   copy discipline (tobytes + frame join) — what every
                   dsgd round paid before this transport existed.
- ``pipelined``  — :class:`PipelinedRemoteWindow`: fire-and-forget
                   ``deposit_async`` per leaf, ONE batched frame + one ack
                   per round, bounded in-flight window, ``flush()`` fence
                   at the end of the run.
- ``pipelined_f32`` — pipelined + f32 wire codec (halves f64 bytes; the
                   compression leg of the DCN story).  ``--codec topk``
                   swaps in the top-k codec.
- ``shm``        — same stream, ``shm=True``: the owner is co-located, so
                   deposits route through the named-shm window table and
                   the loopback TCP hop disappears (skipped when the
                   native runtime is unavailable).
- ``striped``    — :class:`StripedDepositStream`: N parallel connections
                   to the one peer, window names spread by
                   :func:`stripe_of` — N senders and N server-side
                   appliers instead of one of each (``--stripes``).

Plus a compute/gossip **overlap** A/B (``--no-overlap`` to skip): a real
3-rank mp-dsgd run, traced, serial vs ``overlap=True`` — the tracer's
per-round ``overlap`` field is the measured hidden-fold fraction, and the
before/after :func:`bluefog_tpu.tracing.analyze.analyze` reports are the
PROFILE §6 evidence (``--profiles DIR`` writes them as
``TRACE_transport_before.json`` / ``TRACE_transport_after.json``).

The committed ``BENCH_transport.json`` carries ``*_ok`` gate booleans
(pipelined/shm/striped beat their single-stream baselines on the median
of interleaved per-trial ratios; measured overlap fraction > 0), which
``bffleet-tpu --check`` and the tier-1 suite verify like every other
committed bench trajectory.

The server runs in a SEPARATE OS process (like production: the owner's
daemon thread receives while the owner computes), so client and server do
not share a GIL.  Round latency: for ``sync``, wall time per round; for
the pipelined variants, the send→ack latency of each round's batch (the
fence a round would pay if it fenced every round).

Run:  python benchmarks/window_transport_bench.py [--small]
Prints one JSON line (committed as BENCH_transport.json at the repo root).
No TPU, no jax required; rc=0 on any host.
"""

import argparse
import json
import os
import subprocess
import sys
import time

sys.path.insert(0, os.path.join(os.path.dirname(__file__), ".."))

import numpy as np

# ResNet-50-ish split: ~25.6M params across a few big conv/fc-scale leaves
# and many small bn/bias-scale ones — the mixture is what batching earns
# its keep on (small leaves are pure round-trip overhead when sync).
_RESNET50_LEAVES = ([2048 * 1024, 1024 * 1024 * 2, 2359296, 2359296,
                     1179648, 1179648, 589824, 589824, 262144, 262144]
                    + [65536] * 40 + [2048] * 60 + [512] * 50)
_SMALL_LEAVES = [65536] * 4 + [2048] * 8


_OWNER_CODE = """
import os, socket, struct, sys, threading
os.environ.setdefault('JAX_PLATFORMS', 'cpu')
import numpy as np
sys.path.insert(0, {repo!r})
from bluefog_tpu.runtime.async_windows import (AsyncWindow, _fallback,
                                               shm_unlink_window)
from bluefog_tpu.runtime import native
from bluefog_tpu.runtime.window_server import WindowServer
sizes = {sizes!r}
# shm-backed windows when the native runtime allows: the same window
# table serves both the TCP variants (server-side apply lands in shm)
# and the shm fast-path variant (client-side apply, no wire)
shm_ok = native.load() is not None
if shm_ok:
    for i in range(len(sizes)):
        shm_unlink_window(f'tpb:{{i}}')
wins = [AsyncWindow(f'tpb:{{i}}', 1, n, np.{dtype}, shm=shm_ok)
        for i, n in enumerate(sizes)]
srv = WindowServer()
_, port = srv.start('127.0.0.1')

# v1-compat listener for the sync baseline: the deposit path of the
# PRE-pipelining server, copy discipline included (_recv_exact builds a
# bytes() of every payload before frombuffer) — what a v1 peer actually
# cost the owner per deposit.
_HDR = struct.Struct('<IBH'); _BODY = struct.Struct('<iBBq')
_STATUS = struct.Struct('<q')
_lib = native.load()

def _recv_exact(sock, n):
    buf = bytearray(n); view = memoryview(buf); got = 0
    while got < n:
        r = sock.recv_into(view[got:], n - got)
        if r == 0:
            raise ConnectionError()
        got += r
    return bytes(buf)

def _v1_conn(sock):
    sock.setsockopt(socket.IPPROTO_TCP, socket.TCP_NODELAY, 1)
    dtypes = {{0: np.dtype(np.float32), 1: np.dtype(np.float64)}}
    try:
        while True:
            magic, op, name_len = _HDR.unpack(_recv_exact(sock, _HDR.size))
            name = _recv_exact(sock, name_len)
            slot, flags, dtype, n_elems = _BODY.unpack(
                _recv_exact(sock, _BODY.size))
            payload = _recv_exact(sock, n_elems * dtypes[dtype].itemsize)
            arr = np.frombuffer(payload, dtypes[dtype])
            if _lib is not None:
                rc = _lib.bf_win_deposit(name, slot, arr.ctypes.data,
                                         n_elems, flags & 1)
            else:
                rc = _fallback().deposit(name.decode(), slot, arr,
                                         bool(flags & 1))
            sock.sendall(_STATUS.pack(rc))
    except (ConnectionError, OSError):
        return

def _v1_listen(ls):
    while True:
        try:
            c, _ = ls.accept()
        except OSError:
            return
        threading.Thread(target=_v1_conn, args=(c,), daemon=True).start()

ls = socket.socket(); ls.bind(('127.0.0.1', 0)); ls.listen(64)
v1_port = ls.getsockname()[1]
threading.Thread(target=_v1_listen, args=(ls,), daemon=True).start()

print(f'PORT {{port}} {{v1_port}} {{int(shm_ok)}}', flush=True)
sys.stdin.readline()          # parent: all variants done
ls.close()
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


class _V1SyncClient:
    """The pre-pipelining wire, faithfully: one persistent connection per
    window handle, one BLOCKING request/response round-trip per deposit,
    and v1's client copy discipline — ``arr.tobytes()`` then a joined
    ``hdr + name + body + payload`` frame (two full-payload copies the v2
    clients eliminated).  Paired with the owner process's v1-compat
    listener, which reproduces the v1 server's copy discipline too
    (``_recv_exact`` materializes a ``bytes`` of every payload), so the
    baseline is the pre-pipelining path end to end."""

    def __init__(self, port, name):
        import socket as _socket

        from bluefog_tpu.runtime import window_server as ws

        self._ws = ws
        self._name_b = name.encode()
        self._sock = _socket.create_connection(("127.0.0.1", port),
                                               timeout=30)
        self._sock.setsockopt(_socket.IPPROTO_TCP, _socket.TCP_NODELAY, 1)

    def deposit(self, slot, arr, *, accumulate=True):
        ws = self._ws
        payload = arr.tobytes()  # v1 copy #1
        msg = (ws._HDR.pack(ws._MAGIC, ws._OP_DEPOSIT, len(self._name_b))
               + self._name_b
               + ws._BODY.pack(slot, 1 if accumulate else 0,
                               1 if arr.dtype == np.float64 else 0,
                               arr.size)
               + payload)       # v1 copy #2: the frame join
        self._sock.sendall(msg)
        buf = b""
        while len(buf) < 8:
            got = self._sock.recv(8 - len(buf))
            if not got:
                raise ConnectionError("server closed")
            buf += got
        (rc,) = ws._STATUS.unpack(buf)
        if rc < 0:
            raise RuntimeError(f"v1-style deposit failed ({rc})")
        return rc

    def close(self):
        self._sock.close()


def bench_sync(port, sizes, payloads, rounds, dtype):
    """The synchronous per-deposit baseline (v1-wire-equivalent): round
    latency and sustained throughput coincide, nothing overlaps
    anything."""
    rws = [_V1SyncClient(port, f"tpb:{i}") for i in range(len(sizes))]
    for rw, p in zip(rws, payloads):  # warmup (connections, buffers)
        rw.deposit(0, p, accumulate=True)
    lat = []
    t0 = time.perf_counter()
    for _ in range(rounds):
        r0 = time.perf_counter()
        for rw, p in zip(rws, payloads):
            rw.deposit(0, p, accumulate=True)
        lat.append(time.perf_counter() - r0)
    dt = time.perf_counter() - t0
    for rw in rws:
        rw.close()
    return dt, lat


def bench_pipelined(port, sizes, payloads, rounds, dtype, codec=None,
                    shm=False):
    """ONE :class:`DepositStream` to the peer: a round's leaves coalesce
    into batched multi-deposit frames (the per-peer progress-engine
    deployment shape).  Two phases: round LATENCY is measured honestly —
    a fence (``flush``) per round, so each sample is enqueue->applied —
    then sustained THROUGHPUT with the fence only at the end, which is
    how the dsgd loop actually runs (one fence per training run, not per
    round)."""
    from bluefog_tpu.runtime.window_server import DepositStream

    stream = DepositStream(("127.0.0.1", port), codec=codec,
                           max_in_flight=8, shm=shm)
    names = [f"tpb:{i}".encode() for i in range(len(sizes))]

    def one_round():
        for nm, p in zip(names, payloads):
            # copy=False: the bench payloads are immutable, so the wire
            # path is measured without the snapshot memcpy the reusing
            # dsgd loop pays
            stream.deposit_async(nm, 0, p, accumulate=True, copy=False)

    one_round()               # warmup (threads, buffers, cwnd)
    stream.flush(timeout_s=600)
    lat = []
    for _ in range(rounds):   # latency phase: fence every round
        r0 = time.perf_counter()
        one_round()
        stream.flush(timeout_s=600)
        lat.append(time.perf_counter() - r0)
    t0 = time.perf_counter()
    for _ in range(rounds):   # throughput phase: fence once at the end
        one_round()
    stream.flush(timeout_s=600)
    dt = time.perf_counter() - t0
    if shm:
        # the variant must measure what it claims: every deposit after
        # warmup routed through the shm table, none fell back to TCP
        assert stream.shm_deposits > 0, "shm fast path never engaged"
    stream.close()
    return dt, lat


def bench_striped(port, sizes, payloads, rounds, dtype, n_stripes):
    """:class:`StripedDepositStream`: the line-rate DCN shape — N
    parallel connections to the one peer, window names spread across
    stripes by :func:`stripe_of`, one fence across all stripes at the
    end (same audit discipline as one stream)."""
    from bluefog_tpu.runtime.window_server import StripedDepositStream

    stream = StripedDepositStream(("127.0.0.1", port),
                                  n_stripes=n_stripes,
                                  max_in_flight=8)
    names = [f"tpb:{i}".encode() for i in range(len(sizes))]

    def one_round():
        for nm, p in zip(names, payloads):
            stream.deposit_async(nm, 0, p, accumulate=True, copy=False)

    one_round()               # warmup (threads, buffers, cwnd)
    stream.flush(timeout_s=600)
    lat = []
    for _ in range(rounds):   # latency phase: fence every round
        r0 = time.perf_counter()
        one_round()
        stream.flush(timeout_s=600)
        lat.append(time.perf_counter() - r0)
    t0 = time.perf_counter()
    for _ in range(rounds):   # throughput phase: fence once at the end
        one_round()
    stream.flush(timeout_s=600)
    dt = time.perf_counter() - t0
    stream.close()
    return dt, lat


_AB_WORKER_CODE = """
import os, sys
os.environ['JAX_PLATFORMS'] = 'cpu'
os.environ['BLUEFOG_TPU_TRACE'] = {tdir!r}
sys.path.insert(0, {repo!r})
import numpy as np
from bluefog_tpu.runtime.async_windows import FileBarrier, run_async_dsgd_rank
from bluefog_tpu.topology.graphs import RingGraph

def lg(rank, step, z):
    acc = z
    for _ in range({spin}):          # compute leg: the overlap's cover
        acc = acc * 0.999 + z * 0.001
    return float(np.sum(acc ** 2)), 2 * acc

rep = run_async_dsgd_rank(
    RingGraph(3), {rank}, np.ones({d}), lg,
    barrier=FileBarrier({bdir!r}, 3, {rank}), duration_s=120.0,
    stop_after_steps={steps}, transport='tcp', name={name!r},
    stream_options={stream_options!r}, overlap={overlap!r})
print('MASS', rep.total_mass if rep is not None else None, flush=True)
"""


def bench_overlap_ab(repo, env, *, small, profiles_dir=None):
    """Compute/gossip overlap, measured on the real thing: a 3-rank
    mp-dsgd ring over loopback TCP, traced, run twice — serial
    (``overlap=False``, plain single-stream TCP: the BEFORE profile)
    and with the full hot path on (``overlap=True`` + shm fast path +
    2 stripes: the AFTER profile).  The tracer's per-round ``overlap``
    field is the measured hidden-fold fraction (exactly 0 in the
    before run); the two :func:`~bluefog_tpu.tracing.analyze.analyze`
    reports are the PROFILE §6 critical-path evidence."""
    import shutil
    import tempfile

    from bluefog_tpu.tracing.analyze import analyze

    d = 4096 if small else 65536
    steps = 12 if small else 30
    spin = 4 if small else 12
    out = {}
    reports = {}
    for tag, overlap, opts in (
            ("before", False, {}),
            ("after", True, {"shm": True, "stripes": 2})):
        tdir = tempfile.mkdtemp(prefix=f"tpb_trace_{tag}_")
        bdir = tempfile.mkdtemp(prefix=f"tpb_bar_{tag}_")
        try:
            procs = [subprocess.Popen(
                [sys.executable, "-c", _AB_WORKER_CODE.format(
                    tdir=tdir, repo=repo, spin=spin, rank=r, d=d,
                    bdir=bdir, steps=steps, name=f"tpov_{tag}",
                    stream_options=opts, overlap=overlap)],
                stdout=subprocess.PIPE, stderr=subprocess.STDOUT,
                text=True, env=env, cwd=repo) for r in range(3)]
            outs = [p.communicate(timeout=300)[0] for p in procs]
            assert all(p.returncode == 0 for p in procs), outs
            assert any("MASS 3.0" in o or "MASS 2.99" in o
                       for o in outs), outs
            rep = analyze(tdir)
            reports[tag] = rep
            rr = rep["rounds"]["per_rank"]
            ovs = [st["overlap_mean"] for st in rr.values()
                   if "overlap_mean" in st]
            out[tag] = {
                "round_mean_ms": round(1e3 * sum(
                    st["round_mean_s"] for st in rr.values())
                    / max(1, len(rr)), 2),
                "overlap_mean": round(sum(ovs) / len(ovs), 4) if ovs
                                else 0.0,
                "gating_edge": rep["critical_path"].get("gating_edge"),
                "dominant_phase":
                    rep["critical_path"].get("dominant_phase"),
            }
        finally:
            if profiles_dir and tag in reports:
                with open(os.path.join(
                        profiles_dir,
                        f"TRACE_transport_{tag}.json"), "w") as f:
                    json.dump(reports[tag], f, indent=1, sort_keys=True)
            shutil.rmtree(tdir, ignore_errors=True)
            shutil.rmtree(bdir, ignore_errors=True)
    out["overlap_ok"] = out["after"]["overlap_mean"] > 0.0
    return out


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--rounds", type=int, default=12)
    ap.add_argument("--trials", type=int, default=3,
                    help="trials per variant; the reported numbers are the "
                    "best trial (interference-minimal), all trials listed")
    ap.add_argument("--small", action="store_true",
                    help="tiny tree for CI smoke (seconds, not minutes)")
    ap.add_argument("--dtype", default="float32",
                    choices=["float32", "float64"])
    ap.add_argument("--codec", default="f32", choices=["f32", "topk"],
                    help="wire codec for the compressed variant")
    ap.add_argument("--stripes", type=int, default=2,
                    help="stripe count for the striped variant (the "
                    "autotuner's first widening step; raise on multi-core "
                    "DCN hosts where parallel appliers pay off)")
    ap.add_argument("--no-overlap", action="store_true",
                    help="skip the traced compute/gossip overlap A/B")
    ap.add_argument("--profiles", default=None, metavar="DIR",
                    help="write TRACE_transport_{before,after}.json "
                    "(full bftrace analyze reports) into DIR")
    args = ap.parse_args()

    sizes = _SMALL_LEAVES if args.small else _RESNET50_LEAVES
    rounds = max(3, args.rounds // 3) if args.small else args.rounds
    dtype = np.dtype(args.dtype)
    rng = np.random.default_rng(0)
    payloads = [np.ascontiguousarray(rng.standard_normal(n), dtype)
                for n in sizes]
    dense_mb = sum(n * dtype.itemsize for n in sizes) / 1e6

    repo = os.path.abspath(os.path.join(os.path.dirname(__file__), ".."))
    env = dict(os.environ)
    env["PYTHONPATH"] = repo + os.pathsep + env.get("PYTHONPATH", "")
    env.setdefault("JAX_PLATFORMS", "cpu")
    owner = subprocess.Popen(
        [sys.executable, "-c", _OWNER_CODE.format(
            repo=repo, sizes=sizes, dtype=args.dtype)],
        stdin=subprocess.PIPE, stdout=subprocess.PIPE,
        stderr=subprocess.STDOUT, text=True, env=env, cwd=repo)
    try:
        port = v1_port = None
        shm_capable = False
        for line in owner.stdout:
            if line.startswith("PORT "):
                _, a, b, c = line.split()
                port, v1_port = int(a), int(b)
                shm_capable = bool(int(c))
                break
        assert port and v1_port, "owner never published its ports"

        # variants are INTERLEAVED per trial and the headline speedup is
        # the median of per-trial ratios: shared/throttled hosts drift by
        # 2-3x over tens of seconds, so only temporally adjacent runs
        # compare fairly.  Per-variant stats come from its best trial.
        bench_fns = [
            ("sync", lambda: bench_sync(
                v1_port, sizes, payloads, rounds, dtype)),
            ("pipelined", lambda: bench_pipelined(
                port, sizes, payloads, rounds, dtype)),
            (f"pipelined_{args.codec}", lambda: bench_pipelined(
                port, sizes, payloads, rounds, dtype, codec=args.codec)),
            ("striped", lambda: bench_striped(
                port, sizes, payloads, rounds, dtype, args.stripes)),
        ]
        if shm_capable:
            bench_fns.append(("shm", lambda: bench_pipelined(
                port, sizes, payloads, rounds, dtype, shm=True)))
        trials = max(1, args.trials)
        runs = {name: [] for name, _ in bench_fns}
        for _ in range(trials):
            for name, fn in bench_fns:
                runs[name].append(fn())
        variants = {}
        for name, _ in bench_fns:
            dt, lat = min(runs[name], key=lambda r: r[0])
            variants[name] = {
                "MBps": round(dense_mb * rounds / dt, 1),
                "round_p50_ms": round(_percentile(lat, 0.50) * 1e3, 2),
                "round_p99_ms": round(_percentile(lat, 0.99) * 1e3, 2),
                "wall_s": round(dt, 3),
                "trial_MBps": [round(dense_mb * rounds / d, 1)
                               for d, _ in runs[name]],
            }

        def _median_ratio(fast, slow):
            # per-trial ratios of temporally adjacent runs (see above)
            rs = sorted(s / f for (f, _), (s, _)
                        in zip(runs[fast], runs[slow]))
            return rs, rs[len(rs) // 2]

        ratios, speedup = _median_ratio("pipelined", "sync")
        _, striped_speedup = _median_ratio("striped", "pipelined")
        shm_speedup = None
        if shm_capable:
            _, shm_speedup = _median_ratio("shm", "pipelined")
        owner.stdin.write("done\n")
        owner.stdin.flush()
        tail = owner.stdout.read()
        assert owner.wait(timeout=60) == 0 and "OWNER_OK" in tail, tail
    finally:
        if owner.poll() is None:
            owner.kill()
            owner.wait()

    doc = {
        "metric": "window_transport_MBps",
        "sync_baseline": "v1 wire end to end: per-deposit blocking ack, "
                         "client tobytes + frame-join copies, server "
                         "recv-buffer bytes() copy",
        "tree": "small" if args.small else "resnet50",
        "leaves": len(sizes),
        "params": int(sum(sizes)),
        "dense_mb_per_round": round(dense_mb, 1),
        "rounds": rounds,
        "dtype": args.dtype,
        "codec": args.codec,
        "stripes": args.stripes,
        "variants": variants,
        "trial_speedups": [round(r, 2) for r in ratios],
        "speedup_pipelined_vs_sync": round(speedup, 2),
        "pipelined_ok": speedup > 1.0,
        "speedup_striped_vs_pipelined": round(striped_speedup, 2),
        "striped_ok": striped_speedup > 1.0,
    }
    if shm_speedup is not None:
        doc["speedup_shm_vs_tcp"] = round(shm_speedup, 2)
        doc["shm_ok"] = shm_speedup > 1.0
    if not args.no_overlap:
        repo_env = dict(env)
        doc["overlap"] = bench_overlap_ab(
            repo, repo_env, small=args.small,
            profiles_dir=args.profiles)
    print(json.dumps(doc))
    return 0


if __name__ == "__main__":
    sys.exit(main())
