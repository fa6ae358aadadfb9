"""One quantity of the process's start, from the record the program keeps of
it (``bluefog_tpu/tracing/startup.py``: spans on ``time.time()`` from the
first line of ``import bluefog_tpu`` on, JAX's own trace, lowering and
compile spans among them, and the ``bf_setup_*`` counters).  The reducer
reads the record of its own process, through the program's own reading of
it (``bluefog_tpu.tracing.analyze.startup_report``, which ``bftrace-tpu
startup`` prints): the interval, the cut and the unions are computed in one
place, so the benchmark and the operator's view cannot come apart.

The start is read **up to the cut**: the end of the first ``bf.setup.compile``
span whose program is ``params.until_program`` — the step's, which is where
``build_cell`` returns.  A traced run's arms and the agreement check's
programs come later and are left out, so a traced run reports the start an
untraced run is timed on.  The interval runs from the start of the
``bf.setup.import`` span to the cut; every span is clipped to it.

``params.value``:

- ``import_s`` — the ``bf.setup.import`` span;
- ``trace_s``, ``lower_s``, ``compile_s`` — the union of the spans of that
  stage (an inner jitted function's trace lies inside the outer one and is
  not counted twice).  On a warm start ``compile_s`` is reading and loading
  cached executables;
- ``cache_misses`` — the counter ``bf_setup_cache_misses_total`` at the cut
  (compiles that asked the persistent cache and found nothing): 0 says the
  start was warm;
- ``kernel_traces`` — the counters ``bf_setup_kernel_traces_total{kernel}``
  at the cut, summed: times Python reached a ``pl.pallas_call``;
- ``unspanned_s`` — the interval less the union of every span: what no span
  owns yet.

``None`` where the program keeps no such record, or the record holds no
import span or no compile of that program.  The record is bounded and
appended to in the order the spans end, so a cut that is there has the
whole start before it; a record that dropped spans and holds no cut is an
error, not a silence."""

SPAN_VALUES = {"import_s": "import", "trace_s": "trace", "lower_s": "lower",
               "compile_s": "compile"}


def quantity(spans, value, until_program):
    """``value`` (module docstring) of the record's ``spans``."""
    from bluefog_tpu.tracing import analyze

    reports = analyze.startup_report(spans, until_program)
    if not reports or reports[0]["until"] is None or not any(
            s["name"] == "bf.setup.import" for s in spans):
        return None
    report, = reports
    if value in SPAN_VALUES:
        return report["covered_s"].get(SPAN_VALUES[value], 0.0)
    if value == "cache_misses":
        return report["cache"]["misses"]
    if value == "kernel_traces":
        return sum((k["traces"] for k in report["kernels"].values()), 0.0)
    if value == "unspanned_s":
        return report["unspanned_s"]
    raise ValueError(f"startup_spans: no value {value!r}")


def reduce(measured, params):
    try:
        from bluefog_tpu.tracing import startup
    except ImportError:     # a program from before the record
        return None
    record = startup.RECORD
    found = quantity(record.spans(), params["value"],
                     params["until_program"])
    if found is None and record.dropped:
        raise RuntimeError(
            f"startup_spans: the start's record is full ({record.limit} "
            f"spans kept, {record.dropped} dropped) and holds no compile of "
            f"{params['until_program']}: the start cannot be read")
    return found
