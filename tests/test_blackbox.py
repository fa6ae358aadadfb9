"""Flight recorder & cross-rank hang forensics (bluefog_tpu.blackbox).

Covers the acceptance surface of the subsystem:

1. ring-buffer semantics: bounded, ordered, open-span tracking, off-able
   via BLUEFOG_TPU_BLACKBOX=0;
2. dump machinery: file structure, the watchdog (Heartbeat) trigger with
   the last-beat step, supervisor collection across restarts;
3. cross-rank merge & diagnosis: (step, collective-id) alignment, the
   stuck-round report, suspect-rank selection for both wedged-but-dumping
   and missing-dump (SIGSTOP) ranks, the CLI round trip;
4. the zero-overhead contract: jitted paths are IDENTICAL HLO with
   recording off or in (default) host mode; ``=jit`` mode emits only
   *unordered* callbacks (BF-COMM012 guards the ordered abort class);
5. the end-to-end forensics round trip: a multi-process run with one rank
   SIGSTOPped — survivors' watchdogs dump, ``bfblackbox-tpu`` names the
   stalled rank and the round it never completed (``pytest.mark.slow``:
   multi-process, excluded from the tier-1 budget).
"""

import json
import os
import signal
import subprocess
import sys
import time

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import Mesh, PartitionSpec as P

from bluefog_tpu import blackbox
from bluefog_tpu.blackbox import merge, recorder
from bluefog_tpu.parallel.api import shard_map
from bluefog_tpu.topology import RingGraph, build_schedule
from tests._util import REPO as _REPO, clean_env

N = 8


@pytest.fixture(autouse=True)
def _blackbox_clean(monkeypatch, tmp_path):
    """Every test starts with a pristine recorder and no ambient blackbox
    env (mode, capacity, rank) bleeding in or out.  The incident dir is
    pinned to the test's tmp dir so a stray dump can never land in the
    repo (tests that assert on dump paths override it themselves)."""
    for var in ("BLUEFOG_TPU_BLACKBOX", "BLUEFOG_TPU_BLACKBOX_CAPACITY",
                "BLUEFOG_TPU_RANK", "BLUEFOG_TPU_WORLD"):
        monkeypatch.delenv(var, raising=False)
    monkeypatch.setenv("BLUEFOG_TPU_BLACKBOX_DIR",
                       str(tmp_path / "ambient-blackbox"))
    recorder.reset()
    dmod = sys.modules["bluefog_tpu.blackbox.dump"]
    dmod._prior_headers.clear()
    yield
    recorder.reset()
    dmod._prior_headers.clear()


def _mesh():
    return Mesh(np.array(jax.devices()[:N]), ("bf",))


def _smap(fn):
    return shard_map(fn, mesh=_mesh(), in_specs=(P("bf"),),
                     out_specs=P("bf"), check_vma=False)


def _gossip_jaxpr():
    from bluefog_tpu.ops.collectives import neighbor_allreduce

    sched = build_schedule(RingGraph(N))
    return jax.make_jaxpr(_smap(
        lambda v: neighbor_allreduce(v, sched, "bf")))(
            jnp.ones((N, 4), jnp.float32))


# ---------------------------------------------------------------------------
# 1. ring-buffer semantics
# ---------------------------------------------------------------------------


class TestRecorder:
    def test_ring_is_bounded_and_ordered(self):
        rec = recorder.FlightRecorder(capacity=16)
        for i in range(100):
            rec.record("e", i=i)
        evs = rec.events()
        assert len(evs) == 16
        assert [e["i"] for e in evs] == list(range(84, 100))
        assert rec.dropped == 84

    def test_begin_end_tracks_open_spans(self):
        rec = recorder.FlightRecorder(capacity=64)
        rec.begin("collective", key=("c", 0), op="g", step=0)
        rec.begin("collective", key=("c", 1), op="g", step=1)
        rec.end("collective", key=("c", 0), op="g", step=0)
        (open_ev,) = rec.open_spans()
        assert open_ev["step"] == 1
        kinds = [e["kind"] for e in rec.events()]
        assert kinds == ["collective_begin", "collective_begin",
                         "collective_end"]

    def test_open_span_table_is_bounded(self):
        rec = recorder.FlightRecorder(capacity=8)
        for i in range(2000):
            rec.begin("collective", key=("c", i), i=i)
        assert len(rec.open_spans()) <= recorder._MAX_OPEN

    def test_occurrence_pairing_is_fifo(self):
        """Stepless jitted rounds pair begin/end FIFO per (cid, rank):
        with jax's async dispatch, round N+1's begin can fire before
        round N's end — distinct occurrence keys keep both visible in
        the open-span table (review finding)."""
        rec = recorder.FlightRecorder(capacity=64)
        k = ("na#0", 3)
        o1 = rec.begin_occurrence(k)
        o2 = rec.begin_occurrence(k)
        assert o1 != o2
        assert rec.end_occurrence(k) == o1  # oldest first
        assert rec.end_occurrence(k) == o2
        # drained: a further end gets a fresh id, never a stale one
        assert rec.end_occurrence(k) not in (o1, o2)

    def test_snapshot_survives_held_lock(self):
        """events()/open_spans() must not block forever when the lock is
        held (a fatal-signal handler dumps ON the thread it interrupted,
        which may hold it) — timeout + unlocked best-effort read."""
        rec = recorder.FlightRecorder(capacity=8)
        rec.record("e", i=1)
        rec._lock.acquire()
        try:
            t0 = time.monotonic()
            evs = rec.events()
            assert time.monotonic() - t0 < 5.0
            assert [e["i"] for e in evs] == [1]
        finally:
            rec._lock.release()

    def test_env_capacity_honored(self, monkeypatch):
        monkeypatch.setenv("BLUEFOG_TPU_BLACKBOX_CAPACITY", "5")
        rec = recorder.FlightRecorder()
        assert rec.capacity == 5

    def test_disabled_via_env(self, monkeypatch):
        monkeypatch.setenv("BLUEFOG_TPU_BLACKBOX", "0")
        assert not recorder.enabled()
        assert recorder.get() is None
        recorder.record("e")  # must be a silent no-op
        x = jnp.ones((4,))
        assert recorder.traced_event(x, "e") is x
        assert blackbox.dump("test") is None

    def test_on_by_default_host_mode_only(self):
        assert recorder.enabled()
        assert not recorder.jit_enabled()
        recorder.record("e", k=1)
        (ev,) = recorder.get().events()
        assert ev["kind"] == "e" and ev["k"] == 1

    def test_always_on_host_paths_feed_the_ring(self):
        from bluefog_tpu.runtime.async_windows import AsyncWindow

        win = AsyncWindow("bbx_unit_win", 1, 4, np.float64)
        try:
            win.deposit(0, np.ones(4))
            win.read(0, consume=True)
        finally:
            win.free()
        kinds = [e["kind"] for e in recorder.get().events()]
        assert "window_deposit" in kinds and "window_read" in kinds
        dep = [e for e in recorder.get().events()
               if e["kind"] == "window_deposit"][0]
        assert dep["window"] == "bbx_unit_win" and dep["bytes"] == 32


# ---------------------------------------------------------------------------
# 2. dump machinery
# ---------------------------------------------------------------------------


def _read_dump(path):
    return [json.loads(l) for l in open(path) if l.strip()]


class TestDump:
    def test_dump_file_structure(self, tmp_path):
        rec = recorder.configure(capacity=32, rank=3)
        rec.begin("collective", key=("c", 0), op="g", cid="g#0", step=7)
        path = blackbox.dump("unit_test", directory=str(tmp_path),
                             extra={"note": "x"})
        assert path and path.endswith("blackbox-rank3.jsonl")
        lines = _read_dump(path)
        hdr = lines[0]
        assert hdr["header"] and hdr["rank"] == 3 \
            and hdr["reason"] == "unit_test" and hdr["note"] == "x"
        assert any("event" in l for l in lines)
        (spans,) = [l["open_spans"] for l in lines if "open_spans" in l]
        assert spans and spans[0]["step"] == 7
        (stacks,) = [l["stacks"] for l in lines if "stacks" in l]
        assert any("MainThread" in s["thread"] for s in stacks)
        assert lines[-1]["end"] is True

    def test_dump_embeds_metrics_snapshot(self, tmp_path):
        from bluefog_tpu.metrics import registry as mreg

        try:
            reg = mreg.metrics_start()
            reg.counter("bf_test_total").inc(5)
            path = blackbox.dump("with_metrics", directory=str(tmp_path))
            lines = _read_dump(path)
            (metrics,) = [l["metrics"] for l in lines if "metrics" in l]
            assert metrics["bf_test_total"] == 5
        finally:
            mreg.metrics_stop()
            mreg._STOPPED = False

    def test_watchdog_dumps_with_last_step(self, tmp_path, monkeypatch):
        """The Heartbeat deadline-miss trigger: the dump lands before any
        escalation and carries the last-beat step (satellite)."""
        from bluefog_tpu.utils.failure import Heartbeat

        monkeypatch.setenv("BLUEFOG_TPU_BLACKBOX_DIR", str(tmp_path))
        monkeypatch.setenv("BLUEFOG_TPU_RANK", "5")
        hb = Heartbeat(0.25, action="callback")
        with hb:
            hb.beat(123)
            deadline = time.monotonic() + 10.0
            path = tmp_path / "blackbox-rank5.jsonl"
            while not path.exists() and time.monotonic() < deadline:
                time.sleep(0.05)
        assert path.exists(), "watchdog never dumped"
        hdr = _read_dump(path)[0]
        assert hdr["reason"] == "heartbeat_timeout"
        assert hdr["last_step"] == 123
        assert hdr["beats"] == 1
        # heartbeat beats are themselves ring events
        lines = _read_dump(path)
        assert any(l.get("event", {}).get("kind") == "heartbeat_beat"
                   for l in lines)

    def test_heartbeat_stop_joins_monitor_thread(self):
        """stop() must not leak bf-heartbeat threads (satellite)."""
        import threading

        from bluefog_tpu.utils.failure import Heartbeat

        hb = Heartbeat(60, action="callback")
        hb.start()
        hb.stop()
        assert not [t for t in threading.enumerate()
                    if t.name == "bf-heartbeat"]

    def test_hangs_total_counter_bumped(self):
        from bluefog_tpu.metrics import registry as mreg
        from bluefog_tpu.utils.failure import Heartbeat

        try:
            reg = mreg.metrics_start()
            hb = Heartbeat(0.15, action="callback")
            with hb:
                deadline = time.monotonic() + 10.0
                while hb.hangs_detected == 0 \
                        and time.monotonic() < deadline:
                    time.sleep(0.05)
            snap = reg.snapshot()
            (key,) = [k for k in snap if k.startswith("bf_hangs_total")]
            assert snap[key] >= 1
        finally:
            mreg.metrics_stop()
            mreg._STOPPED = False

    def test_install_excepthook_dumps_on_uncaught(self, tmp_path):
        """blackbox.install() (wired into bf.init and the bfrun-tpu exec
        path) must leave a dump behind when a process dies of an
        uncaught exception."""
        script = tmp_path / "crasher.py"
        script.write_text(
            f"import sys; sys.path.insert(0, {_REPO!r})\n"
            "import os\n"
            "os.environ.setdefault('JAX_PLATFORMS', 'cpu')\n"
            "from bluefog_tpu import blackbox\n"
            "assert blackbox.install()\n"
            "from bluefog_tpu.blackbox import recorder\n"
            "recorder.record('optimizer_step', step=9)\n"
            "raise RuntimeError('boom')\n")
        env = clean_env()
        env["BLUEFOG_TPU_BLACKBOX_DIR"] = str(tmp_path / "inc")
        env["BLUEFOG_TPU_RANK"] = "4"
        proc = subprocess.run([sys.executable, str(script)],
                              capture_output=True, text=True, timeout=120,
                              env=env, cwd=_REPO)
        assert proc.returncode != 0
        path = tmp_path / "inc" / "blackbox-rank4.jsonl"
        assert path.exists(), proc.stderr
        hdr = _read_dump(path)[0]
        assert hdr["reason"] == "exception:RuntimeError"
        assert "boom" in hdr["exception"]

    def test_signal_handler_chains_user_handler(self, tmp_path):
        """install() must CHAIN a pre-existing SIGTERM handler (e.g.
        checkpoint-on-preemption), not clobber it (review finding): on
        SIGTERM both the blackbox dump and the user handler run."""
        script = tmp_path / "sig.py"
        script.write_text(
            f"import sys; sys.path.insert(0, {_REPO!r})\n"
            "import os, signal\n"
            "os.environ.setdefault('JAX_PLATFORMS', 'cpu')\n"
            "marker = sys.argv[1]\n"
            "def user_handler(signum, frame):\n"
            "    open(marker, 'w').close()\n"
            "    os._exit(0)\n"
            "signal.signal(signal.SIGTERM, user_handler)\n"
            "from bluefog_tpu import blackbox\n"
            "assert blackbox.install()\n"
            "os.kill(os.getpid(), signal.SIGTERM)\n"
            "import time; time.sleep(30)\n")
        marker = tmp_path / "user_handler_ran"
        env = clean_env()
        env["BLUEFOG_TPU_BLACKBOX_DIR"] = str(tmp_path / "inc")
        env["BLUEFOG_TPU_RANK"] = "6"
        proc = subprocess.run(
            [sys.executable, str(script), str(marker)],
            capture_output=True, text=True, timeout=120, env=env,
            cwd=_REPO)
        assert proc.returncode == 0, (proc.returncode, proc.stderr)
        assert marker.exists()  # the user's handler still ran
        path = tmp_path / "inc" / "blackbox-rank6.jsonl"
        assert path.exists()    # ...and the dump happened first
        assert _read_dump(path)[0]["reason"] == "signal:SIGTERM"

    def test_install_is_wired_into_init(self):
        """bf.init() arms the dump triggers (the review finding: an
        advertised trigger nobody calls is no trigger at all)."""
        import bluefog_tpu as bf

        # the package re-exports dump() the FUNCTION over the submodule
        # name; reach the module itself through sys.modules
        dmod = sys.modules["bluefog_tpu.blackbox.dump"]
        prev = dmod._installed
        try:
            dmod._installed = False
            bf.init()
            assert dmod._installed
        finally:
            dmod._installed = prev
            bf.shutdown()

    def test_later_dump_carries_earlier_headers_forward(self, tmp_path):
        """Escalation chains (heartbeat_timeout -> SIGTERM) dump to the
        SAME per-rank file; the last writer must not erase the first
        dump's reason and last-beat step (review finding)."""
        recorder.configure(capacity=16, rank=0)
        blackbox.dump("heartbeat_timeout", directory=str(tmp_path),
                      extra={"last_step": 77})
        path = blackbox.dump("signal:SIGTERM", directory=str(tmp_path))
        hdr = _read_dump(path)[0]
        assert hdr["reason"] == "signal:SIGTERM"
        (prev,) = [p for p in hdr["previous_dumps"]
                   if p["reason"] == "heartbeat_timeout"]
        assert prev["last_step"] == 77

    def test_collect_attempt_layers_restarts(self, tmp_path):
        recorder.configure(capacity=8, rank=0)
        blackbox.dump("attempt1", directory=str(tmp_path))
        moved = blackbox.collect_attempt(str(tmp_path), 1)
        assert moved == 1
        blackbox.dump("attempt2", directory=str(tmp_path))
        # both attempts visible to the recursive merge; newest wins per rank
        dumps = merge.load_incident(str(tmp_path))
        assert dumps[0].header["reason"] == "attempt2"
        layered = tmp_path / "restart-1" / "blackbox-rank0.jsonl"
        assert layered.exists()
        assert _read_dump(layered)[0]["reason"] == "attempt1"


# ---------------------------------------------------------------------------
# 3. cross-rank merge & diagnosis
# ---------------------------------------------------------------------------


def _simulate_incident(directory, world=3, wedged=2, stop_at=3, dump_wedged=True):
    """Per-rank dumps for a ring run wedged at round ``stop_at``.

    ``dump_wedged=True``: the wedged rank entered the round and never
    exited, but could still dump (a Python-level wedge); everyone else
    completed it.  ``dump_wedged=False``: the SIGSTOP shape — the wedged
    rank wrote nothing, and the SURVIVORS are the ones stuck inside the
    round, blocked on the silent peer."""
    for r in range(world):
        rec = recorder.configure(capacity=128, rank=r)
        for step in range(stop_at + 1):
            rec.begin("collective", key=("c", r, step), op="ring",
                      cid="ring#0", step=step, rank=r,
                      peers=[(r - 1) % world, (r + 1) % world])
            if step == stop_at and (r == wedged or not dump_wedged):
                break
            rec.end("collective", key=("c", r, step), op="ring",
                    cid="ring#0", step=step, rank=r)
        if r != wedged or dump_wedged:
            blackbox.dump("sim", directory=directory, rank=r)
    recorder.reset()


class TestMerge:
    def test_alignment_names_wedged_rank_and_round(self, tmp_path):
        _simulate_incident(str(tmp_path))
        dumps = merge.load_incident(str(tmp_path))
        assert sorted(dumps) == [0, 1, 2]
        report = merge.diagnose(dumps)
        (stuck,) = report["stuck_rounds"]
        assert stuck["step"] == 3 and stuck["cid"] == "ring#0"
        assert stuck["stuck_ranks"] == [2]
        assert stuck["completed_ranks"] == [0, 1]
        assert report["suspect_ranks"] == [2]
        assert report["last_completed"]["2"] == [2, "ring#0"]

    def test_missing_dump_rank_is_prime_suspect(self, tmp_path):
        """The SIGSTOP shape: the wedged rank writes NO dump; against the
        expected world size it must still be named."""
        _simulate_incident(str(tmp_path), wedged=1, dump_wedged=False)
        dumps = merge.load_incident(str(tmp_path))
        assert sorted(dumps) == [0, 2]
        report = merge.diagnose(dumps, expect_ranks=3)
        assert report["missing_ranks"] == [1]
        assert report["suspect_ranks"] == [1]
        assert "no blackbox dump" in report["suspect_reason"]
        # the survivors' begin events name the suspect as their peer
        assert (0, 1) in report["suspect_edges"] or \
            (2, 1) in report["suspect_edges"]

    def test_clean_run_diagnoses_no_hang(self, tmp_path):
        for r in range(2):
            rec = recorder.configure(capacity=32, rank=r)
            for step in range(3):
                rec.begin("collective", key=("c", r, step), op="ring",
                          cid="ring#0", step=step, rank=r)
                rec.end("collective", key=("c", r, step), op="ring",
                        cid="ring#0", step=step, rank=r)
            blackbox.dump("clean", directory=str(tmp_path), rank=r)
        report = merge.diagnose(merge.load_incident(str(tmp_path)))
        assert not report["stuck_rounds"]
        assert not report["suspect_ranks"]

    def test_events_without_step_align_by_occurrence(self, tmp_path):
        """Jit-path events need not carry a step; the k-th round of a cid
        is the same round on every rank (identical SPMD program order)."""
        for r in range(2):
            rec = recorder.configure(capacity=32, rank=r)
            for k in range(3):
                rec.begin("collective", key=("c", r, k), op="na",
                          cid="na#0", rank=r)
                if r == 1 and k == 2:
                    break
                rec.end("collective", key=("c", r, k), op="na",
                        cid="na#0", rank=r)
            blackbox.dump("occ", directory=str(tmp_path), rank=r)
        report = merge.diagnose(merge.load_incident(str(tmp_path)))
        (stuck,) = report["stuck_rounds"]
        assert stuck["step"] == 2 and stuck["cid"] == "na#0"
        assert stuck["stuck_ranks"] == [1]

    def test_orphan_end_from_truncated_ring_is_not_a_stuck_round(
            self, tmp_path):
        """A ring whose retained suffix starts MID-ROUND (oldest event is
        a stepless end whose begin was evicted) must not shift the
        occurrence pairing: a healthy rank stays healthy (review
        finding)."""
        rec = recorder.configure(capacity=64, rank=0)
        # orphan end first (its begin fell off the ring)...
        rec.record("collective_end", op="na", cid="na#0", rank=0)
        # ...then two clean stepless rounds
        for _ in range(2):
            rec.record("collective_begin", op="na", cid="na#0", rank=0)
            rec.record("collective_end", op="na", cid="na#0", rank=0)
        blackbox.dump("trunc", directory=str(tmp_path), rank=0)
        report = merge.diagnose(merge.load_incident(str(tmp_path)))
        assert not report["stuck_rounds"], report["stuck_rounds"]

    def test_ring_eviction_reported_as_alignment_caveat(self, tmp_path):
        rec = recorder.configure(capacity=4, rank=0)
        for i in range(10):  # overflow the 4-slot ring
            rec.record("e", i=i)
        blackbox.dump("evict", directory=str(tmp_path), rank=0)
        report = merge.diagnose(merge.load_incident(str(tmp_path)))
        (caveat,) = report["caveats"]
        assert "evicted 6 event(s)" in caveat

    def test_mixed_eviction_and_truncation_carries_both_reasons(
            self, tmp_path):
        """One file showing BOTH orphan causes — ring eviction and a
        torn (truncated) line — must carry both reasons through the
        report; naming eviction alone sends the operator chasing ring
        capacity when the file was also cut mid-write (regression)."""
        rec = recorder.configure(capacity=4, rank=0)
        for i in range(10):  # overflow the 4-slot ring: dropped=6
            rec.record("e", i=i)
        blackbox.dump("mixed", directory=str(tmp_path), rank=0)
        (path,) = tmp_path.glob("blackbox-rank0.jsonl")
        with open(path, "a") as f:
            f.write('{"event": {"kind": "collec')  # torn line, same file
        report = merge.diagnose(merge.load_incident(str(tmp_path)))
        (caveat,) = report["caveats"]
        assert "evicted 6 event(s)" in caveat
        assert "truncated" in caveat and "1 torn line(s)" in caveat
        # and the CLI text renderer surfaces it verbatim
        text = merge._format_report(report, str(tmp_path))
        assert f"caveat: {caveat}" in text

    def test_truncation_without_end_marker_is_its_own_caveat(
            self, tmp_path):
        """A dump cut before its end marker is truncation evidence even
        with zero torn lines — the eviction count died with the
        marker, so the caveat must say the file is incomplete."""
        rec = recorder.configure(capacity=64, rank=0)
        rec.record("e", i=0)
        blackbox.dump("cut", directory=str(tmp_path), rank=0)
        (path,) = tmp_path.glob("blackbox-rank0.jsonl")
        lines = open(path).read().splitlines()
        assert json.loads(lines[-1]).get("end")
        with open(path, "w") as f:
            f.write("\n".join(lines[:-1]) + "\n")  # drop the end marker
        report = merge.diagnose(merge.load_incident(str(tmp_path)))
        (caveat,) = report["caveats"]
        assert "no end marker" in caveat and "evicted" not in caveat

    def test_cli_round_trip_with_trace_export(self, tmp_path):
        _simulate_incident(str(tmp_path), wedged=1, dump_wedged=False)
        trace = str(tmp_path / "merged.json")
        proc = subprocess.run(
            [sys.executable, "-m", "bluefog_tpu.blackbox", str(tmp_path),
             "--expect-ranks", "3", "--trace", trace],
            capture_output=True, text=True, timeout=120,
            env={**os.environ, "JAX_PLATFORMS": "cpu"}, cwd=_REPO)
        assert proc.returncode == 0, proc.stderr
        assert "suspect rank(s): [1]" in proc.stdout
        assert "ring#0" in proc.stdout
        assert "HANG" in proc.stdout
        events = json.load(open(trace))
        pids = {e["pid"] for e in events if e.get("ph") in ("b", "e")}
        assert pids == {0, 2}  # one chrome pid per dumped rank

    def test_cli_json_output(self, tmp_path):
        _simulate_incident(str(tmp_path))
        proc = subprocess.run(
            [sys.executable, "-m", "bluefog_tpu.blackbox", str(tmp_path),
             "--json"],
            capture_output=True, text=True, timeout=120,
            env={**os.environ, "JAX_PLATFORMS": "cpu"}, cwd=_REPO)
        assert proc.returncode == 0, proc.stderr
        report = json.loads(proc.stdout)
        assert report["suspect_ranks"] == [2]

    def test_cli_empty_dir_fails_loud(self, tmp_path):
        assert merge.main([str(tmp_path)]) == 1

    def test_torn_dump_tail_is_tolerated(self, tmp_path):
        """A crash mid-write leaves a truncated last line; the merge must
        read everything before it rather than rejecting the file."""
        _simulate_incident(str(tmp_path), world=2, wedged=1)
        path = tmp_path / "blackbox-rank0.jsonl"
        with open(path, "a") as f:
            f.write('{"event": {"kind": "collec')  # torn tail
        dumps = merge.load_incident(str(tmp_path))
        assert 0 in dumps and dumps[0].events


# ---------------------------------------------------------------------------
# 4. zero overhead when disabled + unordered-callback contract
# ---------------------------------------------------------------------------


class TestJittedPathContract:
    def test_hooks_identity_when_off_and_in_host_mode(self, monkeypatch):
        x = jnp.ones((4,))
        monkeypatch.setenv("BLUEFOG_TPU_BLACKBOX", "0")
        assert recorder.traced_event(x, "e") is x
        monkeypatch.setenv("BLUEFOG_TPU_BLACKBOX", "1")
        assert recorder.traced_event(x, "e") is x  # host mode: no jit hooks

    def test_identical_jaxpr_off_and_host_mode(self, monkeypatch):
        """The acceptance gate: instrumented collective paths lower to
        the SAME program with recording disabled and in default host
        mode — zero HLO, no callbacks."""
        monkeypatch.setenv("BLUEFOG_TPU_BLACKBOX", "0")
        off = str(_gossip_jaxpr())
        monkeypatch.setenv("BLUEFOG_TPU_BLACKBOX", "1")
        host = str(_gossip_jaxpr())
        assert off == host
        assert "callback" not in off

    def test_jit_mode_uses_only_unordered_callbacks(self, monkeypatch):
        from bluefog_tpu.analysis.jaxpr_lint import lint_jaxpr

        monkeypatch.setenv("BLUEFOG_TPU_BLACKBOX", "jit")
        closed = _gossip_jaxpr()
        assert "io_callback" in str(closed)  # hooks are present...
        diags = lint_jaxpr(closed, name="blackbox_instrumented")
        codes = [d.code for d in diags]
        assert "BF-COMM012" not in codes      # ...and NOT ordered
        assert "BF-COMM010" in codes          # plain callback warning only
        assert not any(d.severity == "error" for d in diags)

    def test_lint_flags_ordered_recorder_hook(self):
        """Seeded violation (satellite): a recorder hook written with
        ordered=True must be caught by BF-COMM012 before it can abort a
        job, and the message must point at the sanctioned pattern."""
        from jax.experimental import io_callback

        from bluefog_tpu.analysis.jaxpr_lint import lint_jaxpr

        rec = recorder.FlightRecorder(capacity=8)

        def bad_hook(x):
            z = io_callback(
                lambda v: (rec.record("collective_begin", op="bad"),
                           np.float32(0.0))[1],
                jax.ShapeDtypeStruct((), jnp.float32), x, ordered=True)
            return x + z

        closed = jax.make_jaxpr(bad_hook)(jnp.float32(1.0))
        (diag,) = [d for d in lint_jaxpr(closed, name="seeded")
                   if d.code == "BF-COMM012"]
        assert diag.severity == "error"
        assert "blackbox.recorder" in diag.message

    def test_jit_mode_records_begin_end_per_rank(self, monkeypatch):
        from bluefog_tpu.ops.collectives import neighbor_allreduce

        monkeypatch.setenv("BLUEFOG_TPU_BLACKBOX", "jit")
        sched = build_schedule(RingGraph(N))
        fn = jax.jit(_smap(lambda v: neighbor_allreduce(v, sched, "bf")))
        jax.block_until_ready(fn(jnp.ones((N, 4), jnp.float32)))
        jax.effects_barrier()
        rec = recorder.get()
        begins = [e for e in rec.events() if e["kind"] == "collective_begin"]
        ends = [e for e in rec.events() if e["kind"] == "collective_end"]
        assert len(begins) == N and len(ends) == N
        assert {e["rank"] for e in begins} == set(range(N))
        assert begins[0]["op"] == "neighbor_allreduce"
        assert begins[0]["bytes"] == 16  # 4 f32 per-rank shard
        assert rec.open_spans() == []  # every round closed

    def test_jit_mode_stays_differentiable(self, monkeypatch):
        from bluefog_tpu.ops.collectives import neighbor_allreduce

        monkeypatch.setenv("BLUEFOG_TPU_BLACKBOX", "jit")
        sched = build_schedule(RingGraph(N))
        fn = jax.jit(_smap(jax.grad(
            lambda v: (neighbor_allreduce(v, sched, "bf") ** 2).sum())))
        g = fn(jnp.arange(N * 4, dtype=jnp.float32).reshape(N, 4))
        jax.block_until_ready(g)
        assert np.isfinite(np.asarray(g)).all()


# ---------------------------------------------------------------------------
# 5. end-to-end forensics round trip (multi-process, SIGSTOP)
# ---------------------------------------------------------------------------


@pytest.mark.slow
class TestSigstopForensics:
    WORLD = 3
    VICTIM = 1

    def test_sigstop_rank_is_named_with_its_round(self, tmp_path):
        """One rank of a multi-process window-server/barrier run is
        SIGSTOPped mid-training; the survivors' watchdogs must write
        blackbox files and bfblackbox-tpu must name the stalled rank and
        the (step, collective-id) it never completed."""
        incident = str(tmp_path / "incident")
        barrier = str(tmp_path / "barrier")
        os.makedirs(incident)
        env = clean_env()
        env["BLUEFOG_TPU_BLACKBOX_DIR"] = incident
        procs = []
        try:
            for r in range(self.WORLD):
                procs.append(subprocess.Popen(
                    [sys.executable,
                     os.path.join(_REPO, "tests", "_mp_blackbox_worker.py"),
                     str(r), str(self.WORLD), barrier, "50",
                     str(self.VICTIM)],
                    stdout=subprocess.PIPE, stderr=subprocess.STDOUT,
                    text=True, env=env, cwd=_REPO))
            victim = procs[self.VICTIM]
            # freeze the victim once it has completed a couple of rounds
            # (it sleeps 0.5 s after each, so the STOP lands between
            # rounds and the survivors wedge on its next barrier)
            seen = 0
            deadline = time.monotonic() + 120
            for line in victim.stdout:
                if line.startswith("STEP "):
                    seen = int(line.split()[1])
                    if seen >= 2:
                        break
                assert time.monotonic() < deadline, "victim never started"
            os.kill(victim.pid, signal.SIGSTOP)

            # survivors block at the victim's next barrier; their
            # watchdogs (2.5 s) dump into the incident dir
            want = [os.path.join(incident, f"blackbox-rank{r}.jsonl")
                    for r in range(self.WORLD) if r != self.VICTIM]
            deadline = time.monotonic() + 90
            while not all(os.path.exists(p) for p in want):
                assert time.monotonic() < deadline, \
                    f"survivors never dumped: {os.listdir(incident)}"
                time.sleep(0.25)
            assert not os.path.exists(os.path.join(
                incident, f"blackbox-rank{self.VICTIM}.jsonl"))

            proc = subprocess.run(
                [sys.executable, "-m", "bluefog_tpu.blackbox", incident,
                 "--expect-ranks", str(self.WORLD)],
                capture_output=True, text=True, timeout=120, env=env,
                cwd=_REPO)
            assert proc.returncode == 0, proc.stderr
            out = proc.stdout
            assert f"missing dumps from ranks [{self.VICTIM}]" in out
            assert f"suspect rank(s): [{self.VICTIM}]" in out
            assert "no blackbox dump" in out
            assert "ring_round#0" in out
            # the stuck round is at (or one past) the last step the
            # victim completed
            report = merge.diagnose(
                merge.load_incident(incident),
                expect_ranks=self.WORLD)
            (stuck,) = report["stuck_rounds"][:1]
            assert stuck["cid"] == "ring_round#0"
            assert stuck["step"] in (seen + 1, seen + 2), (stuck, seen)
            # survivors point at the victim as their ring peer
            assert all(self.VICTIM in s["peers_of_stuck"]
                       for s in report["stuck_rounds"])
        finally:
            for p in procs:
                try:
                    os.kill(p.pid, signal.SIGCONT)
                except OSError:
                    pass
                p.kill()
                p.wait()
                if p.stdout:
                    p.stdout.close()


@pytest.mark.slow
class TestSupervisorCollection:
    WORKER = r"""
import os, sys
sys.path.insert(0, {repo!r})
os.environ.setdefault("JAX_PLATFORMS", "cpu")
marker = {marker!r}
from bluefog_tpu import blackbox
from bluefog_tpu.blackbox import recorder
recorder.get().record("optimizer_step", step=1)
if not os.path.exists(marker):
    open(marker, "w").close()
    blackbox.dump("simulated_crash")
    os._exit(17)
print("WORKER_DONE")
"""

    def test_supervisor_collects_dumps_across_restarts(self, tmp_path):
        """run_supervised layers each failed attempt's blackbox files
        into restart-N/ so one incident tree survives the restart loop."""
        from bluefog_tpu.utils.failure import run_supervised

        incident = str(tmp_path / "incident")
        script = tmp_path / "worker.py"
        script.write_text(self.WORKER.format(
            repo=_REPO, marker=str(tmp_path / "crashed_once")))
        env = clean_env()
        # explicit incident_dir must beat an ambient env var (review
        # finding: setdefault lost to the environment)
        env["BLUEFOG_TPU_BLACKBOX_DIR"] = str(tmp_path / "wrong-dir")
        rc = run_supervised([sys.executable, str(script)], max_restarts=2,
                            env=env, incident_dir=incident,
                            restart_backoff_s=0.05)
        assert rc == 0
        layered = os.path.join(incident, "restart-1",
                               "blackbox-rank0.jsonl")
        assert os.path.exists(layered)
        assert _read_dump(layered)[0]["reason"] == "simulated_crash"
        # durable supervisor restart marker, surfaced by the CLI loader
        (marker,) = merge.load_supervisor_restarts(incident)
        assert marker["attempt"] == 1 and marker["returncode"] == 17
