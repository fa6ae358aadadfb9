"""ctypes bindings for the native host runtime (``bluefog_tpu/csrc``).

The reference ships a C++ core compiled by setup.py's custom build_ext
(SURVEY.md §2.2 "Build").  Here the shared library is built lazily with g++
on first use (no pybind11 in the image; plain ``extern "C"`` + ctypes) and
cached next to the sources under a name that carries the sources' hash — a
binary built from other sources has another name and can never be loaded,
whatever its mtime (trees copied with their build products included).
Everything degrades gracefully: if no C++ toolchain is available,
``load()`` returns ``None`` and pure-Python fallbacks take over
(`bluefog_tpu.utils.timeline`, :class:`PyEngine` below).
"""

from __future__ import annotations

import ctypes
import functools
import glob
import os
import queue as _queue
import subprocess
import threading
from typing import Callable, Optional

from bluefog_tpu.utils import lockcheck as _lc
from bluefog_tpu.utils import log

_CSRC = os.path.join(os.path.dirname(os.path.dirname(__file__)), "csrc")
_SOURCES = ("logging.cc", "timeline.cc", "engine.cc", "windows.cc",
            "tfrecord.cc")


@functools.lru_cache(maxsize=1)
def _lib_path() -> str:
    """``libbf_runtime.<digest of the sources>.so`` next to the sources."""
    import hashlib

    h = hashlib.sha256()
    for name in _SOURCES + ("bf_runtime.h",):
        with open(os.path.join(_CSRC, name), "rb") as f:
            h.update(name.encode() + b"\0" + f.read() + b"\0")
    return os.path.join(_CSRC, f"libbf_runtime.{h.hexdigest()[:16]}.so")


_lib = None
_lib_attempted = False
_build_lock = _lc.lock("runtime.native._build_lock")

_CALLBACK_T = ctypes.CFUNCTYPE(ctypes.c_int, ctypes.c_void_p)


def build(force: bool = False) -> Optional[str]:
    """Compile the runtime library; returns its path or None on failure.

    Cross-process safe: serialized on an fcntl file lock, compiled to a
    temp path, then atomically renamed — a concurrent process can never
    dlopen a partially written library.
    """
    with _build_lock:
        lock_path = os.path.join(_CSRC, ".build.lock")
        try:
            import fcntl

            lock_file = open(lock_path, "w")
            fcntl.lockf(lock_file, fcntl.LOCK_EX)
        except Exception:
            lock_file = None
        try:
            lib_path = _lib_path()
            if not force and os.path.exists(lib_path):
                return lib_path
            tmp = f"{lib_path}.tmp.{os.getpid()}"
            cmd = [
                "g++", "-std=c++17", "-O2", "-fPIC", "-shared", "-pthread",
                "-Wall", "-o", tmp,
            ] + [os.path.join(_CSRC, s) for s in _SOURCES] + [
                # librt: shm_open/shm_unlink live there on pre-2.34 glibc;
                # omitting it builds a .so whose shm windows fail to dlopen
                # ("undefined symbol: shm_open") on those hosts
                "-lrt",
            ]
            try:
                try:
                    proc = subprocess.run(
                        cmd, capture_output=True, text=True, timeout=120
                    )
                except (OSError, subprocess.TimeoutExpired) as e:
                    log.warn("native runtime build failed to launch: %s", e)
                    return None
                if proc.returncode != 0:
                    log.warn("native runtime build failed:\n%s", proc.stderr)
                    return None
                os.replace(tmp, lib_path)
                for stale in glob.glob(
                        os.path.join(_CSRC, "libbf_runtime.*so")):
                    if stale != lib_path:
                        os.remove(stale)
                return lib_path
            finally:
                if os.path.exists(tmp):
                    try:
                        os.remove(tmp)
                    except OSError:
                        pass
        finally:
            if lock_file is not None:
                lock_file.close()


def _bind(lib: ctypes.CDLL) -> ctypes.CDLL:
    lib.bf_log_level.restype = ctypes.c_int
    lib.bf_set_log_level.argtypes = [ctypes.c_int]
    lib.bf_log.argtypes = [ctypes.c_int, ctypes.c_char_p]

    lib.bf_timeline_start.argtypes = [ctypes.c_char_p]
    lib.bf_timeline_start.restype = ctypes.c_int
    lib.bf_timeline_stop.restype = ctypes.c_int
    lib.bf_timeline_active.restype = ctypes.c_int
    for fn in (lib.bf_timeline_begin, lib.bf_timeline_end,
               lib.bf_timeline_async_begin, lib.bf_timeline_async_end):
        fn.argtypes = [ctypes.c_char_p, ctypes.c_char_p, ctypes.c_int64]
    lib.bf_timeline_instant.argtypes = [ctypes.c_char_p, ctypes.c_char_p]

    lib.bf_engine_start.restype = ctypes.c_int
    lib.bf_engine_shutdown.restype = ctypes.c_int
    lib.bf_engine_running.restype = ctypes.c_int
    lib.bf_enqueue.argtypes = [
        ctypes.c_char_p, ctypes.c_char_p, _CALLBACK_T, ctypes.c_void_p
    ]
    lib.bf_enqueue.restype = ctypes.c_int
    lib.bf_poll.argtypes = [ctypes.c_int]
    lib.bf_poll.restype = ctypes.c_int
    lib.bf_wait.argtypes = [
        ctypes.c_int, ctypes.c_int, ctypes.POINTER(ctypes.c_int)
    ]
    lib.bf_wait.restype = ctypes.c_int
    lib.bf_clear.argtypes = [ctypes.c_int]
    lib.bf_wait_all.argtypes = [ctypes.c_int]
    lib.bf_wait_all.restype = ctypes.c_int
    lib.bf_pending_count.restype = ctypes.c_int

    lib.bf_win_create.argtypes = [
        ctypes.c_char_p, ctypes.c_int, ctypes.c_longlong, ctypes.c_int
    ]
    lib.bf_win_create.restype = ctypes.c_int
    lib.bf_win_create_shm.argtypes = [
        ctypes.c_char_p, ctypes.c_int, ctypes.c_longlong, ctypes.c_int
    ]
    lib.bf_win_create_shm.restype = ctypes.c_int
    lib.bf_win_attach_shm.argtypes = [ctypes.c_char_p, ctypes.c_int]
    lib.bf_win_attach_shm.restype = ctypes.c_int
    lib.bf_win_shm_unlink.argtypes = [ctypes.c_char_p]
    lib.bf_win_shm_unlink.restype = ctypes.c_int
    lib.bf_win_info.argtypes = [
        ctypes.c_char_p, ctypes.POINTER(ctypes.c_int),
        ctypes.POINTER(ctypes.c_longlong), ctypes.POINTER(ctypes.c_int)
    ]
    lib.bf_win_info.restype = ctypes.c_int
    lib.bf_win_exists.argtypes = [ctypes.c_char_p]
    lib.bf_win_exists.restype = ctypes.c_int
    lib.bf_win_free.argtypes = [ctypes.c_char_p]
    lib.bf_win_free.restype = ctypes.c_int
    lib.bf_win_deposit.argtypes = [
        ctypes.c_char_p, ctypes.c_int, ctypes.c_void_p, ctypes.c_longlong,
        ctypes.c_int
    ]
    lib.bf_win_deposit.restype = ctypes.c_longlong
    lib.bf_win_read.argtypes = [
        ctypes.c_char_p, ctypes.c_int, ctypes.c_void_p, ctypes.c_longlong,
        ctypes.c_int
    ]
    lib.bf_win_read.restype = ctypes.c_longlong
    lib.bf_win_set_self.argtypes = [
        ctypes.c_char_p, ctypes.c_void_p, ctypes.c_longlong
    ]
    lib.bf_win_set_self.restype = ctypes.c_int
    lib.bf_win_read_self.argtypes = [
        ctypes.c_char_p, ctypes.c_void_p, ctypes.c_longlong
    ]
    lib.bf_win_read_self.restype = ctypes.c_int
    lib.bf_win_num_slots.argtypes = [ctypes.c_char_p]
    lib.bf_win_num_slots.restype = ctypes.c_int

    lib.bf_crc32c.argtypes = [ctypes.c_void_p, ctypes.c_longlong]
    lib.bf_crc32c.restype = ctypes.c_uint32
    lib.bf_tfrecord_index.argtypes = [
        ctypes.c_char_p, ctypes.POINTER(ctypes.c_longlong),
        ctypes.POINTER(ctypes.c_longlong), ctypes.c_longlong, ctypes.c_int,
        ctypes.POINTER(ctypes.c_longlong),
    ]
    lib.bf_tfrecord_index.restype = ctypes.c_longlong
    return lib


_load_lock = _lc.lock("runtime.native._load_lock")


def load() -> Optional[ctypes.CDLL]:
    """Build-if-needed and dlopen the native runtime (None on failure).

    Serialized on a lock: concurrent first callers block until the (possibly
    slow) g++ build finishes rather than observing a half-attempted state and
    silently pinning themselves to the pure-Python fallbacks.
    """
    global _lib, _lib_attempted
    with _load_lock:
        if _lib is not None or _lib_attempted:
            return _lib
        if os.environ.get("BLUEFOG_TPU_NO_NATIVE"):
            _lib_attempted = True
            return None
        path = build()
        if path is not None:
            try:
                _lib = _bind(ctypes.CDLL(path))
            except OSError as e:
                log.warn("native runtime load failed: %s", e)
                _lib = None
        _lib_attempted = True
        return _lib


class TimelineWriter:
    """Native chrome-trace writer (used by ``bluefog_tpu.utils.timeline``)."""

    def __init__(self, path: str):
        lib = load()
        if lib is None:
            raise RuntimeError("native runtime unavailable")
        if lib.bf_timeline_start(path.encode()) != 0:
            raise RuntimeError(f"bf_timeline_start({path!r}) failed")
        self._lib = lib

    def begin(self, name: bytes, cat: bytes, tid: int = 0):
        self._lib.bf_timeline_begin(name, cat, tid)

    def end(self, name: bytes, cat: bytes, tid: int = 0):
        self._lib.bf_timeline_end(name, cat, tid)

    def begin_async(self, name: bytes, cat: bytes, tid: int = 0):
        self._lib.bf_timeline_async_begin(name, cat, tid)

    def end_async(self, name: bytes, cat: bytes, tid: int = 0):
        self._lib.bf_timeline_async_end(name, cat, tid)

    def instant(self, name: bytes, cat: bytes):
        self._lib.bf_timeline_instant(name, cat)

    def close(self):
        if self._lib is not None:
            self._lib.bf_timeline_stop()
            self._lib = None


# Handle registry shared by every Engine instance: the C++ engine is
# process-global (one background thread, one handle space), so the Python
# bookkeeping that keeps ctypes trampolines alive and carries captured
# exceptions must be process-global too.
_handles_lock = _lc.lock("runtime.native._handles_lock")
_handles: dict = {}  # handle -> (trampoline, holder)


class Engine:
    """Async host-op engine over the native background thread.

    ``enqueue(fn)`` runs ``fn`` on the engine thread (ctypes re-acquires the
    GIL there) and returns a handle with reference ``poll`` /
    ``synchronize`` (= WaitAndClear) semantics.  Exceptions in ``fn`` are
    captured and re-raised at synchronize time.

    Instances are thin views over one process-global engine (the reference's
    single background thread started by ``bluefog_init``): handles are valid
    across instances and ``shutdown`` stops the shared thread.  Prefer the
    :func:`engine` singleton accessor.
    """

    def __init__(self):
        self._lib = load()
        if self._lib is not None:
            self._lib.bf_engine_start()
        else:
            self._py = _py_engine()

    @property
    def native(self) -> bool:
        return self._lib is not None

    def enqueue(self, fn: Callable[[], object], *, op: str = "host_op",
                name: str = "") -> int:
        if self._lib is None:
            return self._py.enqueue(fn, op=op, name=name)

        holder = {}

        def trampoline(_arg) -> int:
            try:
                fn()
                return 0
            except BaseException as e:  # surfaced at synchronize()
                holder["err"] = e
                return 1

        cb = _CALLBACK_T(trampoline)
        # enqueue + registration are atomic under _handles_lock: the handle
        # cannot escape to a racing synchronize() (which pops _handles) until
        # both have happened, and a failed enqueue registers nothing.
        with _handles_lock:
            self._lib.bf_engine_start()  # restartable after shutdown()
            handle = self._lib.bf_enqueue(op.encode(), name.encode(), cb, None)
            if handle >= 0:
                _handles[handle] = (cb, holder)
        if handle < 0:
            raise RuntimeError("engine not running")
        return handle

    def poll(self, handle: int) -> bool:
        if self._lib is None:
            return self._py.poll(handle)
        return self._lib.bf_poll(handle) == 1

    def synchronize(self, handle: int, timeout_s: Optional[float] = None):
        """Block until done, clear the handle, re-raise any exception."""
        if self._lib is None:
            return self._py.synchronize(handle, timeout_s)
        timeout_ms = -1 if timeout_s is None else int(timeout_s * 1000)
        status = ctypes.c_int(0)
        rc = self._lib.bf_wait(handle, timeout_ms, ctypes.byref(status))
        if rc == -2:
            raise TimeoutError(f"handle {handle} still pending")
        if rc == -1:
            raise KeyError(f"unknown handle {handle}")
        self._lib.bf_clear(handle)
        with _handles_lock:
            entry = _handles.pop(handle, None)
        if entry is not None and "err" in entry[1]:
            raise entry[1]["err"]
        return status.value

    def wait_all(self, timeout_s: Optional[float] = None):
        """Drain every known pending op, clearing handles and re-raising the
        first captured exception (checkpoint IO errors must not be lost)."""
        if self._lib is None:
            return self._py.wait_all(timeout_s)
        with _handles_lock:
            outstanding = list(_handles.keys())
        first_err = None
        for h in outstanding:
            try:
                self.synchronize(h, timeout_s=timeout_s)
            except KeyError:
                pass  # cleared by a concurrent synchronize
            except BaseException as e:
                if first_err is None:
                    first_err = e
        if first_err is not None:
            raise first_err

    def pending_count(self) -> int:
        if self._lib is None:
            return self._py.pending_count()
        return self._lib.bf_pending_count()

    def shutdown(self):
        if self._lib is None:
            return self._py.shutdown()
        self._lib.bf_engine_shutdown()


class PyEngine:
    """Pure-Python fallback with identical semantics (no C++ toolchain)."""

    def __init__(self):
        self._q: _queue.Queue = _queue.Queue()
        self._results: dict[int, object] = {}
        self._cv = _lc.condition("runtime.native.PyEngine._cv")
        self._next = 0
        self._stop = False
        self._thread = threading.Thread(
            target=self._loop, args=(self._q,), daemon=True)
        self._thread.start()

    def _loop(self, q):
        # Consumes its own queue (passed in, not read off self): a restart
        # swaps in a fresh queue, so a stale shutdown sentinel can only ever
        # stop the old thread it was meant for.
        while True:
            item = q.get()
            if item is None:
                return
            handle, fn = item
            try:
                fn()
                result = 0
            except BaseException as e:
                result = e
            with self._cv:
                self._results[handle] = result
                self._cv.notify_all()

    def enqueue(self, fn, *, op="host_op", name="") -> int:
        with self._cv:
            # Restartable after shutdown(), matching the native engine's
            # bf_engine_start-on-enqueue behavior.
            if self._stop:
                self._stop = False
                self._q = _queue.Queue()
                self._thread = threading.Thread(
                    target=self._loop, args=(self._q,), daemon=True)
                self._thread.start()
            handle = self._next
            self._next += 1
            self._results[handle] = None  # pending
            q = self._q
        q.put((handle, fn))
        return handle

    def poll(self, handle: int) -> bool:
        with self._cv:
            return self._results.get(handle) is not None

    def synchronize(self, handle: int, timeout_s=None):
        with self._cv:
            if handle not in self._results:
                raise KeyError(f"unknown handle {handle}")
            ok = self._cv.wait_for(
                lambda: self._results[handle] is not None, timeout=timeout_s)
            if not ok:
                raise TimeoutError(f"handle {handle} still pending")
            result = self._results.pop(handle)
        if isinstance(result, BaseException):
            raise result
        return 0

    def wait_all(self, timeout_s=None):
        """Drain all outstanding handles, re-raising the first exception."""
        with self._cv:
            outstanding = list(self._results.keys())
        first_err = None
        for h in outstanding:
            try:
                self.synchronize(h, timeout_s=timeout_s)
            except KeyError:
                pass  # cleared by a concurrent synchronize
            except BaseException as e:
                if first_err is None:
                    first_err = e
        if first_err is not None:
            raise first_err

    def pending_count(self) -> int:
        with self._cv:
            return sum(1 for v in self._results.values() if v is None)

    def shutdown(self):
        with self._cv:
            if self._stop:
                return  # idempotent: never post a second sentinel
            self._stop = True
            q, t = self._q, self._thread
        q.put(None)
        t.join(timeout=5)


# RLock: engine() holds this while Engine.__init__ runs, and the fallback
# path re-enters it through _py_engine() — a plain Lock self-deadlocks
# whenever the native .so is unavailable
_engine_lock = _lc.rlock("runtime.native._engine_lock")
_PY_ENGINE: Optional[PyEngine] = None


def _py_engine() -> PyEngine:
    """Shared fallback engine (keeps Engine instances views over one
    process-global queue, matching the native path)."""
    global _PY_ENGINE
    with _engine_lock:
        if _PY_ENGINE is None:
            _PY_ENGINE = PyEngine()
        return _PY_ENGINE


_ENGINE: Optional[Engine] = None


def engine() -> Engine:
    """Process-wide engine singleton (reference: the global background
    thread started by ``bluefog_init``; SURVEY.md §3.1)."""
    global _ENGINE
    with _engine_lock:
        if _ENGINE is None:
            _ENGINE = Engine()
        return _ENGINE
