"""Native C++ host runtime: build, timeline writer, async engine, logging.

Mirrors the reference's host-side C++ test surface (tensor_queue /
handle_manager / timeline; SURVEY.md §2.1) — here exercised through the
ctypes bindings exactly as the framework uses them.
"""

import ctypes
import json
import os
import threading
import time

import pytest

from bluefog_tpu.runtime import native


@pytest.fixture(scope="module")
def lib():
    lib = native.load()
    if lib is None:
        pytest.skip("native runtime unavailable (no g++?)")
    return lib


def test_build_produces_library(lib):
    assert os.path.exists(native._lib_path())


def test_library_name_follows_the_sources(tmp_path, monkeypatch):
    """The binary's name carries its sources' digest: a library built from
    other sources has another name, so a stale one can never be loaded,
    whatever its mtime."""
    import shutil

    current = native._lib_path()
    csrc = tmp_path / "csrc"
    shutil.copytree(native._CSRC, csrc,
                    ignore=shutil.ignore_patterns("libbf_runtime.*"))
    monkeypatch.setattr(native, "_CSRC", str(csrc))
    native._lib_path.cache_clear()
    try:
        same = native._lib_path()
        assert os.path.basename(same) == os.path.basename(current)
        with open(csrc / "logging.cc", "a") as f:
            f.write("\n// edited\n")
        native._lib_path.cache_clear()
        assert os.path.basename(native._lib_path()) != os.path.basename(same)
    finally:
        native._lib_path.cache_clear()


def test_log_level_roundtrip(lib):
    old = lib.bf_log_level()
    try:
        lib.bf_set_log_level(2)
        assert lib.bf_log_level() == 2
        lib.bf_log(2, b"info message from test")
        lib.bf_log(0, b"suppressed trace message")
    finally:
        lib.bf_set_log_level(old)


def test_timeline_writer_emits_valid_chrome_trace(tmp_path, lib):
    path = tmp_path / "trace.json"
    w = native.TimelineWriter(str(path))
    w.begin(b"neighbor_allreduce.grad", b"comm", 1)
    time.sleep(0.002)
    w.end(b"neighbor_allreduce.grad", b"comm", 1)
    w.instant(b"step", b"marker")
    w.close()

    events = json.loads(path.read_text())
    assert [e["ph"] for e in events] == ["B", "E", "i"]
    b, e, _ = events
    assert b["name"] == "neighbor_allreduce.grad"
    assert b["cat"] == "comm"
    assert e["ts"] >= b["ts"]


def test_timeline_double_start_fails(tmp_path, lib):
    path = tmp_path / "t.json"
    w = native.TimelineWriter(str(path))
    try:
        assert lib.bf_timeline_start(str(tmp_path / "t2.json").encode()) != 0
    finally:
        w.close()


def test_engine_enqueue_poll_synchronize(lib):
    eng = native.Engine()
    assert eng.native
    ran = threading.Event()
    h = eng.enqueue(ran.set, op="test", name="set_event")
    assert eng.synchronize(h, timeout_s=5) == 0
    assert ran.is_set()
    assert eng.poll(h) is False  # cleared handle reads as not-done


def test_engine_preserves_fifo_order(lib):
    eng = native.Engine()
    order = []
    handles = [
        eng.enqueue((lambda i=i: order.append(i)), name=f"op{i}")
        for i in range(32)
    ]
    for h in handles:
        eng.synchronize(h, timeout_s=5)
    assert order == list(range(32))


def test_engine_propagates_exceptions(lib):
    eng = native.Engine()

    def boom():
        raise ValueError("host op failed")

    h = eng.enqueue(boom)
    with pytest.raises(ValueError, match="host op failed"):
        eng.synchronize(h, timeout_s=5)


def test_engine_overlaps_with_main_thread(lib):
    """The engine thread runs ops while the main thread keeps working —
    the reference's comm/compute overlap contract (SURVEY.md §3.3)."""
    eng = native.Engine()
    started = threading.Event()
    release = threading.Event()

    def blocker():
        started.set()
        release.wait(timeout=10)

    h = eng.enqueue(blocker, name="blocker")
    assert started.wait(timeout=5)
    assert eng.poll(h) is False
    assert eng.pending_count() >= 1
    release.set()
    eng.synchronize(h, timeout_s=5)
    assert eng.pending_count() == 0


def test_engine_wait_timeout(lib):
    eng = native.Engine()
    release = threading.Event()
    h = eng.enqueue(lambda: release.wait(timeout=10), name="slow")
    with pytest.raises(TimeoutError):
        eng.synchronize(h, timeout_s=0.05)
    release.set()
    eng.synchronize(h, timeout_s=5)


def test_engine_wait_all(lib):
    eng = native.Engine()
    counter = []
    for i in range(8):
        eng.enqueue(lambda i=i: counter.append(i))
    eng.wait_all(timeout_s=5)
    assert len(counter) == 8


def test_py_engine_fallback_same_semantics():
    eng = native.PyEngine()
    try:
        out = []
        h1 = eng.enqueue(lambda: out.append(1))
        h2 = eng.enqueue(lambda: out.append(2))
        eng.synchronize(h1, timeout_s=5)
        eng.synchronize(h2, timeout_s=5)
        assert out == [1, 2]

        def boom():
            raise RuntimeError("py boom")

        with pytest.raises(RuntimeError, match="py boom"):
            eng.synchronize(eng.enqueue(boom), timeout_s=5)
        with pytest.raises(KeyError):
            eng.synchronize(10_000)
    finally:
        eng.shutdown()


def test_unknown_handle_raises(lib):
    eng = native.Engine()
    with pytest.raises(KeyError):
        eng.synchronize(99_999)


def test_wait_all_reraises_and_clears(lib):
    """wait_all must surface op failures (e.g. failed checkpoint IO) and
    clear handles so long runs don't leak the handle table."""
    eng = native.Engine()

    def boom():
        raise OSError("disk full")

    eng.enqueue(lambda: None)
    eng.enqueue(boom)
    eng.enqueue(lambda: None)
    with pytest.raises(OSError, match="disk full"):
        eng.wait_all(timeout_s=5)
    eng.wait_all(timeout_s=5)  # survivors drained, errors not re-raised twice
    assert eng.pending_count() == 0
    with native._handles_lock:
        assert not native._handles  # no trampoline leak


def test_callback_status_does_not_collide_with_sentinels(lib):
    """A raw C-level status of -1/-2 must not masquerade as unknown-handle
    or timeout (bf_wait reports status out-of-band)."""
    status = ctypes.c_int(123)
    cb = native._CALLBACK_T(lambda _arg: -2)
    h = lib.bf_enqueue(b"test", b"neg_status", cb, None)
    assert h >= 0
    rc = lib.bf_wait(h, 5000, ctypes.byref(status))
    assert rc == 0
    assert status.value == -2
    lib.bf_clear(h)


def test_engine_restarts_after_shutdown(lib):
    eng = native.Engine()
    eng.shutdown()
    out = []
    h = eng.enqueue(lambda: out.append(1))  # auto-restarts the thread
    eng.synchronize(h, timeout_s=5)
    assert out == [1]


def test_handles_valid_across_engine_instances(lib):
    a, b = native.Engine(), native.Engine()

    def boom():
        raise ValueError("cross-instance")

    h = a.enqueue(boom)
    with pytest.raises(ValueError, match="cross-instance"):
        b.synchronize(h, timeout_s=5)


def test_py_engine_restarts_after_shutdown():
    eng = native.PyEngine()
    eng.shutdown()
    out = []
    h = eng.enqueue(lambda: out.append(1))  # auto-restarts, like native
    eng.synchronize(h, timeout_s=5)
    assert out == [1]
    eng.shutdown()


def test_py_engine_double_shutdown_then_enqueue():
    """A stale shutdown sentinel must not kill the restarted worker."""
    eng = native.PyEngine()
    eng.shutdown()
    eng.shutdown()  # idempotent: no second sentinel
    out = []
    h = eng.enqueue(lambda: out.append(1))
    eng.synchronize(h, timeout_s=5)
    assert out == [1]
    eng.shutdown()
