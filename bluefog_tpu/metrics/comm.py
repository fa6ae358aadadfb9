"""Communication instrumentation hooks.

Two kinds of hook, matching the hard constraint carried from PR 1 (no
ordered ``io_callback`` on jitted paths — XLA in this environment
CHECK-fails on the threaded effect token):

- **Jitted-path hooks** (:func:`record_collective`, :func:`count`):
  trace-time gated.  When no registry is active at trace time they are
  the identity with zero HLO footprint.  When active, the per-execution
  increments ride an *unordered* ``io_callback`` whose zero result is
  folded back into the instrumented tree — the proven ``device_stage``
  dataflow pattern — with the increment amounts passed as traced
  operands, so data-dependent costs (aperiodic gossip's active-rotation
  count, the dynamic switch's per-branch bytes) are recorded exactly.
  A ``custom_jvp`` shell keeps instrumented collectives differentiable
  (the callback fires on the primal; tangents pass through).
- **Host-path hooks** (:func:`inc` / :func:`observe` / :func:`set`):
  plain guarded registry calls for code that already runs on the host —
  the async window runtime, the TCP window server's daemon threads, the
  pipeline's trace-time bubble gauge.

Byte accounting convention: ``bytes`` is what *this rank* ships per
round (payload bytes x out-slots).  The callback fires once per local
device per execution, so the counter naturally sums to the global
gossip volume of the devices this process hosts.
"""

from __future__ import annotations

import contextlib
import threading
from typing import Dict, Optional, Sequence, Tuple, Union

from bluefog_tpu.metrics import registry as _reg

__all__ = [
    "record_collective",
    "count",
    "gauge",
    "inc",
    "observe",
    "set",
    "suppress_comm_metrics",
    "tree_bytes",
    "tree_leaf_count",
]

Number = Union[int, float]

_suppress = threading.local()


@contextlib.contextmanager
def suppress_comm_metrics():
    """Trace-time escape hatch: jitted hooks are the identity inside this
    block.  Control-flow wrappers compiling sub-computations into
    ``lax.switch``/``lax.cond`` branches use it to hoist the record
    OUTSIDE the branch (mirroring ``timeline.suppress_device_stage``), so
    one call site records one round — with the branch-dependent cost
    selected by a traced operand, not by duplicated callbacks."""
    prev = getattr(_suppress, "on", False)
    _suppress.on = True
    try:
        yield
    finally:
        _suppress.on = prev


def _suppressed() -> bool:
    return getattr(_suppress, "on", False)


def tree_bytes(x) -> int:
    """Static payload size of a pytree, from trace-time shape/dtype."""
    import jax

    total = 0
    for leaf in jax.tree_util.tree_leaves(x):
        size = getattr(leaf, "size", None)
        dtype = getattr(leaf, "dtype", None)
        if size is not None and dtype is not None:
            total += int(size) * int(dtype.itemsize)
    return total


def tree_leaf_count(x) -> int:
    import jax

    return len([l for l in jax.tree_util.tree_leaves(x)
                if getattr(l, "size", None) is not None])


def count(x, counters: Sequence[Tuple[str, object]],
          labels: Optional[Dict[str, object]] = None):
    """Increment ``counters`` (``(name, amount)`` pairs; amounts may be
    Python numbers or traced scalars) once per execution of the program
    position where this is traced, returning ``x`` unchanged.

    Trace-time gated: identity (zero HLO) when metrics are off or
    suppressed.  The callback keeps a reference to the registry active at
    trace time, so a compiled program keeps recording into the registry
    it was built against (and into nothing after ``metrics_stop``).
    """
    reg = _reg.current()
    if reg is None or _suppressed() or not counters:
        return x
    return _stamp_values(x, [reg.counter(name) for name, _ in counters],
                         "inc", [a for _, a in counters], labels)


def gauge(x, gauges: Sequence[Tuple[str, object]],
          labels: Optional[Dict[str, object]] = None):
    """Set ``gauges`` (``(name, value)`` pairs; values may be traced
    scalars) at every execution of the program position where this is
    traced, returning ``x`` unchanged: :func:`count`'s twin for a quantity
    whose last value is what a reader wants (a looped model's exit mass),
    trace-time gated alike."""
    reg = _reg.current()
    if reg is None or _suppressed() or not gauges:
        return x
    return _stamp_values(x, [reg.gauge(name) for name, _ in gauges], "set",
                         [v for _, v in gauges], labels)


def _stamp_values(x, objs, method, values, labels):
    """``getattr(obj, method)(value, **labels)`` for each pair, from an
    unordered callback folded into ``x``."""
    import jax.numpy as jnp
    import numpy as np

    from bluefog_tpu.utils.stamping import stamp

    lbls = {str(k): str(v) for k, v in (labels or {}).items()}
    # the metric objects were materialized at trace time: name/kind
    # conflicts surface there (at the call site), not inside a device
    # callback
    amounts = [jnp.asarray(a, jnp.float32) for a in values]

    def cb(_token, *vals):
        for obj, v in zip(objs, vals):
            getattr(obj, method)(float(v), **lbls)
        return np.float32(0.0)

    # fire-after-data, order-by-dataflow, custom_jvp differentiability:
    # the shared stamping shell (utils/stamping.py)
    return stamp(x, cb, *amounts)


def record_collective(x, *, op: str, bytes_per_round, messages_per_round,
                      schedule: str = "", backend: str = "",
                      extra: Optional[Dict[str, object]] = None):
    """Record one communication round at the program position where this
    is traced: ``bf_comm_rounds_total`` += 1, ``bf_comm_bytes_total`` +=
    ``bytes_per_round``, ``bf_comm_messages_total`` +=
    ``messages_per_round`` (amounts may be traced), labelled by
    ``op``/``schedule``/``backend``.  Returns ``x`` unchanged; identity
    when metrics are off."""
    reg = _reg.current()
    if reg is None or _suppressed():
        return x
    counters = [
        ("bf_comm_rounds_total", 1.0),
        ("bf_comm_bytes_total", bytes_per_round),
        ("bf_comm_messages_total", messages_per_round),
    ]
    labels: Dict[str, object] = {"op": op}
    if schedule:
        labels["schedule"] = schedule
    if backend:
        labels["backend"] = backend
    if extra:
        labels.update(extra)
    return count(x, counters, labels)


# ---------------------------------------------------------------------------
# Host-path conveniences (no tracing involved)
# ---------------------------------------------------------------------------


def inc(name: str, amount: Number = 1.0, **labels) -> None:
    reg = _reg.current()
    if reg is not None:
        reg.counter(name).inc(amount, **labels)


def observe(name: str, value: Number, **labels) -> None:
    reg = _reg.current()
    if reg is not None:
        reg.histogram(name).observe(value, **labels)


def set(name: str, value: Number, **labels) -> None:  # noqa: A001 — mirrors Gauge.set
    reg = _reg.current()
    if reg is not None:
        reg.gauge(name).set(value, **labels)
