"""A process's start, as a trace: from ``import bluefog_tpu`` to the
compiled step.

The step is measured from inside down to the scope; the start had one
number, read from outside.  This module keeps **one record of the start**
in :class:`~bluefog_tpu.tracing.recorder.SpanRecorder`'s record format
(``sid``, ``par``, ``tid``, ``name``, ``cat``, ``rank``, ``round``, ``t0``
in epoch seconds, ``dur``, then free fields) — no second recorder.  A start
happens once, and by the time anyone asks, the moment to switch a recorder
on has passed: so the record is **always kept**, there is no switch and no
environment variable, and it is bounded (``MAX_RECORDS`` spans, then a
``dropped`` count; the counters keep counting).  When a ``SpanRecorder`` is
armed (``BLUEFOG_TPU_TRACE``) :meth:`StartupRecord.export` sends the same
records through ``emit()`` into ``trace-*.jsonl`` under one ``tid``, at
interpreter exit or when called, and ``bftrace-tpu startup <dir>`` reads
them beside the job's rounds.

It is the record of the **process's** start and is never reset: the spans
are appended as they end, so whatever ended before a span that was kept was
kept too, and a reader that finds the compile it cuts at has the whole
start before it.  A second start in the same process
(``run_with_restart`` without a new process) re-traces only what
``jax.jit``'s own cache lost; its spans follow the first start's in the same
record while there is room, and a reader that cuts at the first compile of
a program does not see them.

The spans (name, ``cat``, where):

- ``bf.setup.import`` / ``import`` — first to last line of
  ``bluefog_tpu/__init__.py``; a child ``bf.setup.import.<subpackage>`` for
  each first-level subpackage it imports, in the order it imports them
  (whichever comes first pays for what both need), each with
  ``modules_loaded``, the growth of ``sys.modules``;
- ``bf.setup.backend`` / ``runtime`` — the accelerator runtime's start-up,
  which JAX reports no event for: from the program's last look at a runtime
  that was down to its first look at one that is up, and whatever the caller
  did between the two.  ``bf.init`` looks before and after its own device
  query; an entry point that asks for the devices itself says so just
  before (``configure_compile_cache``, which every one of them calls there);
- ``bf.setup.init`` / ``init`` — ``parallel.context.init``;
- ``bf.setup.trace``, ``bf.setup.lower``, ``bf.setup.compile`` / the
  program's ``fun_name`` — JAX's own time spans of every program's trace,
  lowering and backend compile (``jax.monitoring``), one listener of a
  kind registered once.  A span inside another of them (an inner jitted
  function's trace) is kept from ``MIN_NESTED_S`` on: every call of a
  jitted ``jax.numpy`` function is one, thousands a model.
  ``bf.setup.compile`` carries ``cache`` (``hit``, ``miss``, or ``off``
  where the persistent cache was not asked) and ``cache_read_s``, from the
  cache's own events inside it, and a program's own compile also
  ``counters``: the counters below as they stood when it ended, which is
  how a reader takes a counter *at* a compile it cuts the start at;
- ``bf.setup.trace.block`` / the block's kind — one call of a model
  block's ``__call__`` as Python traces it (:func:`spanned`);
- ``bf.setup.trace.kernel`` / the kernel's name — an instant, as Python
  reaches a ``pl.pallas_call`` (:func:`kernel_traced`): it says *when*, so
  that the trace span of the kernel's body that follows it can be given the
  kernel's name; how often is the counter's to say.

JAX reports a span when it ends, so a parent arrives after its children:
``par`` is filled on reading (:func:`parent_by_containment`: the innermost
span of the same thread that contains this one), and a span's **self time**
is its duration less what its children cover.

The counters (``docs/metrics.md``): ``bf_setup_programs_total{stage}``,
``bf_setup_cache_hits_total``, ``bf_setup_cache_misses_total``,
``bf_setup_cache_read_seconds_total``,
``bf_setup_kernel_traces_total{kernel}`` (:func:`kernel_traced`, a line
beside each ``pl.pallas_call``).  They are the one source of every count:
the benchmark's ``setup_cache_misses`` and ``setup_kernel_traces`` and the
``bftrace-tpu startup`` view read them from a compile span's ``counters``,
the metrics registry's summary line carries them as they stand, and the
bound on the spans does not touch them.

Every ``t0`` is ``time.time()``; ``process_t0`` is the process's own start
on the same clock (from ``/proc/self/stat`` and the boot time), so a span
can be put on the axis of a process's age — the axis ``setup_s`` is
measured on.  Nothing here enters a traced program: no ``named_scope``, no
callback; the hooks read the clock in Python while JAX traces.
"""

from __future__ import annotations

import atexit
import functools
import os
import sys
import threading
import time
from typing import Callable, Dict, List, Optional, Tuple, Union

from bluefog_tpu.tracing import recorder as _recorder
from bluefog_tpu.utils import lockcheck as _lc

__all__ = [
    "ImportSpan",
    "MAX_RECORDS",
    "MIN_NESTED_S",
    "RECORD",
    "StartupRecord",
    "TRACE_ID",
    "kernel_traced",
    "parent_by_containment",
    "process_start_epoch",
    "spanned",
]

#: spans kept; later ones are counted in ``dropped``
MAX_RECORDS = 2048
#: a JAX span inside another one is kept from this many seconds on
MIN_NESTED_S = 0.005
#: every record of every start carries this ``tid``
TRACE_ID = _recorder.trace_id_for("bf.setup")

_STAGE_OF_EVENT = {
    "/jax/core/compile/jaxpr_trace_duration": "trace",
    "/jax/core/compile/jaxpr_to_mlir_module_duration": "lower",
    "/jax/core/compile/backend_compile_duration": "compile",
}
_CACHE_ASKED = "/jax/compilation_cache/compile_requests_use_cache"
_CACHE_HIT = "/jax/compilation_cache/cache_hits"
_CACHE_READ = "/jax/compilation_cache/cache_retrieval_time_sec"

_LabelKey = Tuple[Tuple[str, str], ...]


def process_start_epoch() -> Optional[float]:
    """When this process was started, in epoch seconds: the kernel's record
    of its start (clock ticks after boot) plus the boot time.  ``None``
    where there is no ``/proc``."""
    try:
        with open("/proc/self/stat") as f:
            fields = f.read().rsplit(")", 1)[1].split()
        after_boot = int(fields[19]) / os.sysconf("SC_CLK_TCK")
        booted = time.time() - time.clock_gettime(time.CLOCK_BOOTTIME)
    except (OSError, AttributeError, IndexError, ValueError):
        return None
    return booted + after_boot


def _backend_up() -> bool:
    """Whether JAX has started its backends, without starting them or
    importing JAX."""
    bridge = sys.modules.get("jax._src.xla_bridge")
    up = getattr(bridge, "backends_are_initialized", None)
    return bool(up is not None and up())


def parent_by_containment(records: List[dict]) -> List[dict]:
    """Fill ``par``: the innermost span of the same ``thread`` that
    contains the span.  In place; returns the records in start order."""
    records.sort(key=lambda r: (r["t0"], -r["dur"]))
    stacks: Dict[object, List[dict]] = {}
    for rec in records:
        end = rec["t0"] + rec["dur"]
        stack = stacks.setdefault(rec.get("thread"), [])
        while stack and stack[-1]["t0"] + stack[-1]["dur"] < end:
            stack.pop()
        rec["par"] = stack[-1]["sid"] if stack else 0
        stack.append(rec)
    return records


class StartupRecord:
    """The spans and counters of one process's start (module docstring).

    ``on_time_span``, ``on_scalar``, ``on_event`` and ``on_duration`` are
    the listeners JAX calls; the rest is called by the program's hooks."""

    def __init__(self, limit: int = MAX_RECORDS):
        self.process_t0 = process_start_epoch()
        self.limit = limit
        self.records: List[dict] = []
        self.dropped = 0
        self.counters: Dict[Tuple[str, _LabelKey], float] = {}
        #: summed durations of JAX's spans by stage, dropped ones included
        self.stage_seconds = {stage: 0.0
                              for stage in _STAGE_OF_EVENT.values()}
        self._lock = _lc.lock("tracing.startup.StartupRecord._lock")
        # a thread's open JAX spans (``depth``) and the persistent cache's
        # events since its last compile
        self._thread = threading.local()
        self._backend_down_at: Optional[float] = None
        self._backend_seen_up = False
        # own sid -> the sid the armed recorder gave it
        self._exported: Dict[int, int] = {}

    # ------------------------------------------------------------ recording
    def add(self, name: str, cat: str = "", *, t0: float, dur: float,
            **fields) -> None:
        """One finished span.  ``par`` is filled on reading."""
        with self._lock:
            if len(self.records) >= self.limit:
                self.dropped += 1
                return
            self.records.append({
                "sid": len(self.records) + 1, "par": 0, "tid": TRACE_ID,
                "name": name, "cat": cat, "rank": None, "round": None,
                "t0": t0, "dur": dur, "thread": threading.get_ident(),
                **fields})

    def inc(self, name: str, amount: float = 1.0, **labels) -> None:
        key = (name, tuple(sorted((k, str(v)) for k, v in labels.items())))
        with self._lock:
            self.counters[key] = self.counters.get(key, 0.0) + amount

    def look_at_backend(self) -> None:
        """Note whether the accelerator runtime is up; the first look that
        finds it up after one that found it down records
        ``bf.setup.backend`` between the two."""
        if self._backend_seen_up:
            return
        now = time.time()
        if not _backend_up():
            self._backend_down_at = now
            return
        self._backend_seen_up = True
        if self._backend_down_at is not None:
            self.add("bf.setup.backend", "runtime",
                     t0=self._backend_down_at,
                     dur=now - self._backend_down_at)

    # ------------------------------------------------- jax.monitoring hooks
    def listen(self, on: bool = True) -> None:
        """Register the four listeners with JAX, or take them away."""
        from jax import monitoring

        for kind, listener in (("event_time_span", self.on_time_span),
                               ("scalar", self.on_scalar),
                               ("event", self.on_event),
                               ("event_duration_secs", self.on_duration)):
            if on:
                getattr(monitoring, f"register_{kind}_listener")(listener)
            else:       # JAX spells this one without the `_secs`
                getattr(monitoring, "unregister_" + kind.replace(
                    "_secs", "") + "_listener")(listener)

    def on_scalar(self, event: str, value: float, **_) -> None:
        # JAX reports a span's start as a scalar under the span's name
        if event in _STAGE_OF_EVENT:
            self._thread.depth = getattr(self._thread, "depth", 0) + 1

    def on_event(self, event: str, **_) -> None:
        if event == _CACHE_ASKED:
            self._thread.asked = True
        elif event == _CACHE_HIT:
            self._thread.hit = True

    def on_duration(self, event: str, duration: float, **_) -> None:
        if event == _CACHE_READ:
            self._thread.read_s = duration

    def on_time_span(self, event: str, start: float, end: float,
                     fun_name: str = "", **_) -> None:
        stage = _STAGE_OF_EVENT.get(event)
        if stage is None:
            return
        state = vars(self._thread)
        nested = state["depth"] = max(state.get("depth", 1) - 1, 0)
        if nested and stage != "compile":
            # every call of a jitted jax.numpy function inside a trace is
            # such a span, thousands a model and microseconds each
            if end - start >= MIN_NESTED_S:
                self.add(f"bf.setup.{stage}", fun_name, t0=start,
                         dur=end - start)
            return
        with self._lock:
            self.stage_seconds[stage] += end - start
        self.inc("bf_setup_programs_total", stage=stage)
        fields = {}
        if stage == "compile":
            # a backend compile is a program's own wherever it happens; the
            # cache's events since the thread's last one are this one's,
            # and none is left for the next
            asked, hit, read_s = (state.pop(key, None)
                                  for key in ("asked", "hit", "read_s"))
            cache = "off" if not asked else "hit" if hit else "miss"
            if cache == "hit":
                self.inc("bf_setup_cache_hits_total")
                self.inc("bf_setup_cache_read_seconds_total", read_s or 0.0)
            elif cache == "miss":
                self.inc("bf_setup_cache_misses_total")
            fields = {"cache": cache, "cache_read_s": read_s or 0.0,
                      "counters": self.counter_series()}
        self.add(f"bf.setup.{stage}", fun_name, t0=start, dur=end - start,
                 **fields)

    # --------------------------------------------------------------- reading
    def spans(self) -> List[dict]:
        """Copies of the records in start order, ``par`` filled."""
        with self._lock:
            copies = [dict(rec) for rec in self.records]
        return parent_by_containment(copies)

    def counter_series(self) -> Dict[str, float]:
        """The counters as they stand, as the metrics registry names series
        (``name{k="v"}``): what its summary line carries, and what a
        program's compile span keeps."""
        from bluefog_tpu.metrics.registry import format_series

        with self._lock:
            return {format_series(name, key): value
                    for (name, key), value in self.counters.items()}

    # ---------------------------------------------------------------- export
    def export(self, recorder: Optional[_recorder.SpanRecorder] = None
               ) -> int:
        """Send what has not been sent yet through the armed
        ``SpanRecorder`` (``recorder``, else the process's), parents first,
        then one ``bf.setup.record`` line that says what the spans cannot:
        ``process_t0`` and ``dropped``; and flush it.  Returns the number
        of spans sent; 0 with no recorder armed."""
        rec = recorder if recorder is not None else _recorder.get()
        if rec is None:
            return 0
        sent = 0
        for span in self.spans():
            if span["sid"] in self._exported:
                continue
            fields = {k: v for k, v in span.items() if k not in (
                "sid", "par", "tid", "name", "cat", "rank", "round", "t0",
                "dur")}
            self._exported[span["sid"]] = rec.emit(
                span["name"], span["cat"], t0=span["t0"], dur=span["dur"],
                parent=self._exported.get(span["par"]), trace_id=TRACE_ID,
                pid=os.getpid(), **fields)
            sent += 1
        if sent:
            rec.emit("bf.setup.record", "record", t0=time.time(),
                     dur=0.0, trace_id=TRACE_ID, pid=os.getpid(),
                     process_t0=self.process_t0, dropped=self.dropped)
        rec.flush()
        return sent


class ImportSpan:
    """``bf.setup.import`` and its children, for ``bluefog_tpu/__init__.py``:
    opened with the clock and ``len(sys.modules)`` its first line read,
    ``lap(name)`` after each first-level subpackage's imports, ``close()``
    on the last line."""

    def __init__(self, t0: float, modules: int):
        self._t0 = self._lap_t0 = t0
        self._modules = self._lap_modules = modules

    def lap(self, name: str) -> None:
        now, modules = time.time(), len(sys.modules)
        RECORD.add(f"bf.setup.import.{name}", "import", t0=self._lap_t0,
                   dur=now - self._lap_t0,
                   modules_loaded=modules - self._lap_modules)
        self._lap_t0, self._lap_modules = now, modules

    def close(self) -> None:
        RECORD.add("bf.setup.import", "import", t0=self._t0,
                   dur=time.time() - self._t0,
                   modules_loaded=len(sys.modules) - self._modules)


def spanned(name: str, cat: Union[str, Callable[..., str]]):
    """Decorator: every call of the function is a span ``name`` of the
    start's record, the clock read before and after in Python.  ``cat`` is
    the span's, or a function of the call's first argument that gives it (a
    block's kind, from the module).  The wrapper keeps the function's name,
    so nothing a program is traced into can tell it is there."""
    def wrap(fn):
        @functools.wraps(fn)
        def timed(*args, **kwargs):
            t0 = time.time()
            try:
                return fn(*args, **kwargs)
            finally:
                RECORD.add(name, cat if isinstance(cat, str) else cat(
                    args[0]), t0=t0, dur=time.time() - t0)
        return timed
    return wrap


def kernel_traced(kernel: str) -> None:
    """Count one trace of a Pallas kernel, and leave an instant
    ``bf.setup.trace.kernel`` (``cat`` the kernel) that says when: called as
    Python reaches its ``pl.pallas_call``.  A call site inside a shared
    ``jax.jit`` is reached once however many layers use it; a bare one once
    a layer and pass."""
    RECORD.inc("bf_setup_kernel_traces_total", kernel=kernel)
    RECORD.add("bf.setup.trace.kernel", kernel, t0=time.time(), dur=0.0)


def _install() -> StartupRecord:
    """A new record that listens to JAX, and its export at interpreter exit
    (after the armed recorder's own flush, which registers later and so
    runs first: ``export`` flushes again)."""
    record = StartupRecord()
    record.listen()
    atexit.register(record.export)
    return record


#: the process's record; a reload of this module keeps it, and its listeners
RECORD: StartupRecord = globals().get("RECORD") or _install()
