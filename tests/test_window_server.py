"""Cross-host one-sided window transport (runtime/window_server.py).

The DCN half of the MPI_Put story: deposits land in another PROCESS's
native window table over TCP with no owner involvement (the shm backing
covers same-host; this covers everything a socket reaches).  Asserted:
protocol round-trips, accumulate semantics, consume-exactly-once through
the remote read, owner-side visibility across a real process boundary,
and loud errors for missing windows / size mismatches.
"""

import os
import subprocess
import sys

import numpy as np
import pytest

from bluefog_tpu.runtime import native
from tests._util import REPO as _REPO, clean_env, uniq as _uniq

pytestmark = pytest.mark.skipif(
    native.load() is None, reason="native runtime unavailable")


def test_remote_deposit_roundtrip_same_process():
    from bluefog_tpu.runtime.async_windows import AsyncWindow
    from bluefog_tpu.runtime.window_server import RemoteWindow, WindowServer

    name = _uniq("ws_local")
    win = AsyncWindow(name, n_slots=2, n_elems=6, dtype=np.float64)
    srv = WindowServer()
    host, port = srv.start("127.0.0.1")
    try:
        rw = RemoteWindow(("127.0.0.1", port), name)
        p = np.arange(6, dtype=np.float64)
        assert rw.deposit(0, p, accumulate=True) == 1
        assert rw.deposit(0, p, accumulate=True) == 2
        rw.deposit(1, 5 * p, accumulate=False)

        # owner-side view
        buf, fresh = win.read(0, consume=True)
        assert fresh == 2
        np.testing.assert_allclose(buf, 2 * p)

        # remote consume-exactly-once via READ_SLOT
        out, fresh = rw.read(1, 6, np.float64, consume=True)
        assert fresh == 1
        np.testing.assert_allclose(out, 5 * p)
        out2, fresh2 = rw.read(1, 6, np.float64, consume=False)
        assert fresh2 == 0
        np.testing.assert_allclose(out2, 0.0)

        # passive win_get: remote read of the published self value
        win.set_self(np.full(6, 9.0))
        np.testing.assert_allclose(rw.read_self(6, np.float64), 9.0)
        rw.close()
    finally:
        srv.stop()
        win.free()


def test_remote_errors_are_loud():
    from bluefog_tpu.runtime.async_windows import AsyncWindow
    from bluefog_tpu.runtime.window_server import RemoteWindow, WindowServer

    name = _uniq("ws_err")
    win = AsyncWindow(name, n_slots=1, n_elems=4, dtype=np.float32)
    srv = WindowServer()
    _, port = srv.start("127.0.0.1")
    try:
        rw = RemoteWindow(("127.0.0.1", port), "no_such_window")
        with pytest.raises(RuntimeError, match="failed"):
            rw.deposit(0, np.ones(4, np.float32))
        rw.close()
        rw2 = RemoteWindow(("127.0.0.1", port), name)
        with pytest.raises(RuntimeError, match="mismatch|failed"):
            rw2.deposit(0, np.ones(99, np.float32))  # wrong size
        rw2.close()
        rw3 = RemoteWindow(("127.0.0.1", port), name)
        with pytest.raises(TypeError):
            rw3.deposit(0, np.ones(4, np.int32))
        # a lying dtype on a READ must be rejected before any buffer is
        # allocated (the native copy uses the WINDOW's element size — an
        # f64 reply into an f32 buffer would heap-overflow the owner)
        with pytest.raises(RuntimeError, match="failed"):
            rw3.read_self(4, np.float64)  # window is f32
        # geometry rejections on reads keep the connection usable
        win.set_self(np.full(4, 2.5, np.float32))
        np.testing.assert_allclose(rw3.read_self(4, np.float32), 2.5)
        rw3.close()
    finally:
        srv.stop()
        win.free()


def test_stop_quiesces_live_connections():
    """After stop(), deposits from an already-connected peer must fail —
    the owner relies on quiescence before reading/checkpointing."""
    from bluefog_tpu.runtime.async_windows import AsyncWindow
    from bluefog_tpu.runtime.window_server import RemoteWindow, WindowServer

    name = _uniq("ws_stop")
    win = AsyncWindow(name, n_slots=1, n_elems=3, dtype=np.float64)
    srv = WindowServer()
    _, port = srv.start("127.0.0.1")
    try:
        rw = RemoteWindow(("127.0.0.1", port), name)
        rw.deposit(0, np.ones(3))
        srv.stop()
        with pytest.raises((RuntimeError, OSError, ConnectionError)):
            rw.deposit(0, np.ones(3))
        rw.close()
        buf, fresh = win.read(0, consume=True)
        assert fresh == 1  # only the pre-stop deposit landed
    finally:
        win.free()


def test_fuzz_protocol_against_reference_model():
    """Randomized op stream over ONE persistent connection vs a Python
    model: any framing/desync bug in the wire protocol shows up as a
    mismatched counter or buffer within a few ops."""
    from bluefog_tpu.runtime.async_windows import AsyncWindow
    from bluefog_tpu.runtime.window_server import RemoteWindow, WindowServer

    name = _uniq("ws_fuzz")
    rng = np.random.default_rng(5)
    k, n = 2, 4
    win = AsyncWindow(name, n_slots=k, n_elems=n, dtype=np.float64)
    srv = WindowServer()
    _, port = srv.start("127.0.0.1")
    model = {s: {"buf": np.zeros(n), "dep": 0, "fresh": 0} for s in range(k)}
    self_model = np.zeros(n)
    try:
        rw = RemoteWindow(("127.0.0.1", port), name)
        for step in range(200):
            r = rng.random()
            slot = int(rng.integers(k))
            if r < 0.45:
                v = rng.standard_normal(n)
                acc = bool(rng.random() < 0.7)
                got = rw.deposit(slot, v, accumulate=acc)
                m = model[slot]
                m["buf"] = m["buf"] + v if acc else v.copy()
                m["dep"] += 1
                m["fresh"] += 1
                assert got == m["dep"], step
            elif r < 0.8:
                consume = bool(rng.random() < 0.5)
                buf, fresh = rw.read(slot, n, np.float64, consume=consume)
                m = model[slot]
                assert fresh == m["fresh"], step
                np.testing.assert_allclose(buf, m["buf"], atol=1e-12,
                                           err_msg=f"step {step}")
                if consume:
                    m["buf"] = np.zeros(n)
                    m["fresh"] = 0
            elif r < 0.9:
                self_model = rng.standard_normal(n)
                win.set_self(self_model)  # owner-side publish
            else:
                np.testing.assert_allclose(rw.read_self(n, np.float64),
                                           self_model, atol=1e-12)
        rw.close()
    finally:
        srv.stop()
        win.free()


def test_concurrent_remote_writers_never_lose_updates():
    """Two client connections (each its own server handler thread) hammer
    one slot with accumulates while the owner occasionally peeks: the
    native slot mutex serializes every read-modify-write end to end
    through the TCP path."""
    import threading

    from bluefog_tpu.runtime.async_windows import AsyncWindow
    from bluefog_tpu.runtime.window_server import RemoteWindow, WindowServer

    name = _uniq("ws_race")
    reps = 150
    win = AsyncWindow(name, n_slots=1, n_elems=6, dtype=np.float64)
    srv = WindowServer()
    _, port = srv.start("127.0.0.1")
    errors = []
    try:
        def writer(value):
            try:
                rw = RemoteWindow(("127.0.0.1", port), name)
                p = np.full(6, value)
                for _ in range(reps):
                    rw.deposit(0, p, accumulate=True)
                rw.close()
            except BaseException as e:
                errors.append(e)

        ts = [threading.Thread(target=writer, args=(v,)) for v in (1.0, 5.0)]
        for t in ts:
            t.start()
        for _ in range(20):
            win.read(0, consume=False)  # owner peeks mid-race
        for t in ts:
            t.join(timeout=120)
        assert not errors, errors
        buf, fresh = win.read(0, consume=True)
        assert fresh == 2 * reps
        np.testing.assert_allclose(buf, np.full(6, reps * 6.0))
    finally:
        srv.stop()
        win.free()


def test_deposit_crosses_host_boundary_processes():
    """Owner process (subprocess) exposes a window via WindowServer; this
    process deposits over TCP; the owner observes the mass with no
    participation — MPI_Put over the DCN path."""
    from bluefog_tpu.runtime.window_server import RemoteWindow

    name = _uniq("ws_mp")
    code = (
        "import os, sys\n"
        "os.environ['JAX_PLATFORMS']='cpu'\n"
        "import numpy as np\n"
        "from bluefog_tpu.runtime.async_windows import AsyncWindow\n"
        "from bluefog_tpu.runtime.window_server import WindowServer\n"
        f"w = AsyncWindow({name!r}, 1, 5, np.float64)\n"
        "srv = WindowServer()\n"
        "_, port = srv.start('127.0.0.1')\n"
        "print(f'PORT {port}', flush=True)\n"
        "line = sys.stdin.readline()\n"  # parent says deposits done
        "buf, fresh = w.read(0, consume=True)\n"
        "assert fresh == 3, fresh\n"
        "np.testing.assert_allclose(buf, 3 * np.arange(5))\n"
        "srv.stop(); w.free()\n"
        "print('OWNER_OK', flush=True)\n"
    )
    env = clean_env()
    proc = subprocess.Popen([sys.executable, "-c", code],
                            stdin=subprocess.PIPE, stdout=subprocess.PIPE,
                            stderr=subprocess.STDOUT, text=True, env=env,
                            cwd=_REPO)
    try:
        port = None
        for line in proc.stdout:
            if line.startswith("PORT "):
                port = int(line.split()[1])
                break
        assert port, "owner never published its port"
        rw = RemoteWindow(("127.0.0.1", port), name)
        p = np.arange(5, dtype=np.float64)
        for _ in range(3):
            rw.deposit(0, p, accumulate=True)
        rw.close()
        proc.stdin.write("done\n")
        proc.stdin.flush()
        out = proc.stdout.read()
        assert proc.wait(timeout=60) == 0, out
        assert "OWNER_OK" in out, out
    finally:
        if proc.poll() is None:
            proc.kill()
            proc.wait()
