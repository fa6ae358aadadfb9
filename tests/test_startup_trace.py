"""The start's record (``bluefog_tpu/tracing/startup.py``): JAX's trace,
lowering and compile spans with the cache's verdict, parents by containment,
the block and kernel hooks, the counters, the bound, the export through an
armed ``SpanRecorder``, and the benchmark's reducer over a made-up record.
Nothing the hooks do may reach a compiled program."""

import importlib
import importlib.util
import json
import os
import subprocess
import sys
import time

import jax
import jax.numpy as jnp
import pytest

import bluefog_tpu as bf
from bluefog_tpu.tracing import analyze, recorder, startup

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
STAGES = ("trace", "lower", "compile")
#: the record this process keeps since it imported the package: under
#: pytest it is full of earlier tests' programs long before this file runs
PROCESS_RECORD = startup.RECORD


@pytest.fixture(scope="module", autouse=True)
def record():
    """An empty record in the process's place, listening as it does."""
    fresh = startup.StartupRecord()
    fresh.listen()
    startup.RECORD = fresh
    yield fresh
    startup.RECORD = PROCESS_RECORD
    fresh.listen(False)


def spans_named(spans, name, cat=None):
    return [s for s in spans if s["name"] == name
            and (cat is None or s["cat"] == cat)]


def programs_counted(record):
    """``bf_setup_programs_total`` over its three stages."""
    return sum(value for series, value in record.counter_series().items()
               if series.startswith("bf_setup_programs_total"))


# ---- JAX's spans ------------------------------------------------------------

@pytest.fixture(scope="module")
def compiled_ahead_of_time():
    """A function with an inner jitted function, compiled once."""
    @jax.jit
    def startup_test_inner(x):
        time.sleep(2 * startup.MIN_NESTED_S)    # trace time, not run time
        return x * 2

    def startup_test_outer(x):
        time.sleep(2 * startup.MIN_NESTED_S)
        return startup_test_inner(x) + 1

    jax.jit(startup_test_outer).lower(jnp.ones(3)).compile()
    return startup.RECORD.spans()


@pytest.mark.parametrize("stage", STAGES)
def test_a_compile_ahead_of_time_leaves_one_span_a_stage(
        compiled_ahead_of_time, stage):
    """One ``trace``, one ``lower`` and one ``compile`` span with the
    program's ``fun_name``, in that order in time."""
    fun_name = ("startup_test_outer" if stage == "trace"
                else "jit(startup_test_outer)")
    span, = spans_named(compiled_ahead_of_time, f"bf.setup.{stage}", fun_name)
    assert span["dur"] > 0 and span["tid"] == startup.TRACE_ID
    assert set(span) >= {"sid", "par", "tid", "name", "cat", "rank", "round",
                         "t0", "dur"}
    if stage == "compile":
        assert span["cache"] in ("hit", "miss", "off")
        assert span["cache_read_s"] >= 0
    ends = [s["t0"] + s["dur"] for st in STAGES for s in spans_named(
        compiled_ahead_of_time, f"bf.setup.{st}") if "startup_test_outer"
        in s["cat"]]
    assert ends == sorted(ends)


def test_an_inner_trace_is_a_child_and_self_time_is_the_rest(
        compiled_ahead_of_time):
    outer, = spans_named(compiled_ahead_of_time, "bf.setup.trace",
                         "startup_test_outer")
    inner, = spans_named(compiled_ahead_of_time, "bf.setup.trace",
                         "startup_test_inner")
    assert inner["par"] == outer["sid"] and outer["par"] == 0
    assert outer["t0"] <= inner["t0"]
    assert inner["t0"] + inner["dur"] <= outer["t0"] + outer["dur"]
    children = [s for s in compiled_ahead_of_time
                if s["par"] == outer["sid"]]
    self_s = outer["dur"] - sum(c["dur"] for c in children)
    # the outer function's own sleep is its own; the inner one's is not
    assert 2 * startup.MIN_NESTED_S <= self_s <= outer["dur"] - inner["dur"]
    assert inner["dur"] >= 2 * startup.MIN_NESTED_S


def test_a_short_span_inside_another_is_not_kept():
    """Every ``jnp`` call inside a trace is a span of JAX's: the record
    keeps those of ``MIN_NESTED_S`` or more, and counts programs alone."""
    x = jnp.ones(5)         # an eager op is a program of its own
    before = programs_counted(startup.RECORD)
    jax.jit(lambda x: jnp.add(jnp.multiply(x, 2), 1)).lower(x).compile()
    assert programs_counted(startup.RECORD) == before + 3
    assert not spans_named(startup.RECORD.spans(), "bf.setup.trace",
                           "multiply")


# ---- the persistent cache, from two processes -------------------------------

_CACHE_SCRIPT = """
import json, sys
import jax, jax.numpy as jnp
from bluefog_tpu.tracing import startup
jax.config.update("jax_compilation_cache_dir", sys.argv[1])
jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
jax.config.update("jax_persistent_cache_min_entry_size_bytes", -1)
def cached_program(x):
    return jnp.tanh(x) @ x.T
jax.jit(cached_program).lower(jnp.ones((8, 8))).compile()
span, = [s for s in startup.RECORD.spans() if s["name"] == "bf.setup.compile"
         and s["cat"] == "jit(cached_program)"]
print("RESULT " + json.dumps({"span": span,
                              "counters": startup.RECORD.counter_series()}))
"""


@pytest.fixture(scope="module")
def two_starts(tmp_path_factory):
    """The same program compiled by two processes on one cache directory;
    the second with a ``SpanRecorder`` armed."""
    cache = tmp_path_factory.mktemp("cache")
    traces = tmp_path_factory.mktemp("traces")
    results = []
    for armed in (False, True):
        env = dict(os.environ, JAX_PLATFORMS="cpu", PYTHONPATH=REPO)
        env.pop("JAX_COMPILATION_CACHE_DIR", None)
        env.pop("BLUEFOG_TPU_TRACE", None)
        if armed:
            env["BLUEFOG_TPU_TRACE"] = str(traces)
        proc = subprocess.run(
            [sys.executable, "-c", _CACHE_SCRIPT, str(cache)], env=env,
            capture_output=True, text=True, timeout=120)
        assert proc.returncode == 0, proc.stderr[-2000:]
        line, = [ln for ln in proc.stdout.splitlines()
                 if ln.startswith("RESULT ")]
        results.append(json.loads(line[len("RESULT "):]))
    return results, str(traces)


@pytest.mark.parametrize("start,verdict", [(0, "miss"), (1, "hit")])
def test_a_start_says_whether_it_found_its_program_in_the_cache(
        two_starts, start, verdict):
    result = two_starts[0][start]
    assert result["span"]["cache"] == verdict
    counters = result["counters"]
    if verdict == "hit":
        assert counters.get("bf_setup_cache_misses_total", 0) == 0
        assert counters["bf_setup_cache_hits_total"] >= 1
        assert result["span"]["cache_read_s"] > 0
        assert counters["bf_setup_cache_read_seconds_total"] > 0
    else:
        assert counters["bf_setup_cache_misses_total"] >= 1
        assert result["span"]["cache_read_s"] == 0


def test_an_armed_recorder_gets_the_start_at_exit_under_one_tid(two_starts):
    """``BLUEFOG_TPU_TRACE`` set: the records are in ``trace-*.jsonl`` under
    one ``tid``, children under their parents, and ``bftrace-tpu startup``
    reads them."""
    _, traces = two_starts
    spans = [s for s in analyze.load_traces(traces)
             if s["name"].startswith("bf.setup")]
    assert {s["tid"] for s in spans} == {startup.TRACE_ID}
    by_sid = {s["sid"]: s for s in spans}
    imported, = spans_named(spans, "bf.setup.import")
    laps = [s for s in spans if s["name"].startswith("bf.setup.import.")]
    assert laps and all(by_sid[s["par"]] is imported for s in laps)
    note, = spans_named(spans, "bf.setup.record")
    assert note["dropped"] == 0
    # the view reads the counters as the program's compile span kept them
    counters = two_starts[0][1]["counters"]
    report, = analyze.startup_report(spans, "jit(cached_program)")
    assert report["counters"] == counters == spans_named(
        spans, "bf.setup.compile", "jit(cached_program)")[0]["counters"]
    assert report["cache"] == {
        "hits": counters["bf_setup_cache_hits_total"], "misses": 0.0,
        "read_s": counters["bf_setup_cache_read_seconds_total"]}
    assert report["programs_counted"]["compile"] == counters[
        'bf_setup_programs_total{stage="compile"}']
    assert report["programs"]["jit(cached_program)"]["compile"] > 0
    assert report["programs"]["jit(cached_program)"]["cache"] == "hit"
    assert 0 < report["import_at_age_s"] == pytest.approx(
        imported["t0"] - note["process_t0"])
    assert [i["name"] for i in report["imports"]] == [
        s["name"].rsplit(".", 1)[1] for s in laps]
    assert all(i["modules_loaded"] >= 0 for i in report["imports"])
    assert analyze.main(["startup", traces]) == 0


def test_a_compile_inside_another_span_keeps_its_own_cache_verdict():
    """An op run eagerly while a function is traced compiles inside the
    trace: its hit is its own, kept however short the compile, and the next
    program's miss stays a miss."""
    trace, compiled = (event for event, stage in
                       startup._STAGE_OF_EVENT.items() if stage != "lower")
    record = startup.StartupRecord()
    record.on_scalar(trace, 0.0)
    record.on_scalar(compiled, 0.0)
    record.on_event(startup._CACHE_ASKED)
    record.on_event(startup._CACHE_HIT)
    record.on_duration(startup._CACHE_READ, 0.0004)
    record.on_time_span(compiled, 1.0, 1.0005, fun_name="jit(eager_op)")
    record.on_time_span(trace, 0.5, 2.0, fun_name="outer")
    record.on_scalar(compiled, 0.0)
    record.on_event(startup._CACHE_ASKED)
    record.on_time_span(compiled, 3.0, 4.0, fun_name="jit(outer)")
    inner, outer = spans_named(record.spans(), "bf.setup.compile")
    assert (inner["cat"], inner["cache"]) == ("jit(eager_op)", "hit")
    assert (outer["cat"], outer["cache"]) == ("jit(outer)", "miss")
    assert outer["cache_read_s"] == 0.0
    assert outer["counters"] == record.counter_series() == {
        "bf_setup_cache_hits_total": 1.0,
        "bf_setup_cache_misses_total": 1.0,
        "bf_setup_cache_read_seconds_total": 0.0004,
        'bf_setup_programs_total{stage="compile"}': 2.0,
        'bf_setup_programs_total{stage="trace"}': 1.0}


# ---- the bound, the clock, the backend bracket, the import ------------------

def test_the_2049th_record_is_dropped_and_counted():
    record = startup.StartupRecord()
    for i in range(startup.MAX_RECORDS + 1):
        record.add("bf.setup.trace", "f", t0=float(i), dur=0.5)
        record.inc("bf_setup_programs_total", stage="trace")
    assert len(record.records) == startup.MAX_RECORDS == 2048
    assert record.dropped == 1
    assert programs_counted(record) == 2049


def test_spans_lie_on_the_axis_of_the_process_s_age():
    """``process_t0`` is the process's start on the spans' clock: the age
    the kernel reports (what the benchmark's ``setup_s`` is read from) is
    the epoch time less it."""
    with open("/proc/self/stat") as f:
        fields = f.read().rsplit(")", 1)[1].split()
    age = (time.clock_gettime(time.CLOCK_BOOTTIME)
           - int(fields[19]) / os.sysconf("SC_CLK_TCK"))
    assert time.time() - PROCESS_RECORD.process_t0 == pytest.approx(
        age, abs=0.05)
    imported, = spans_named(PROCESS_RECORD.spans(), "bf.setup.import")[:1]
    assert 0 < imported["t0"] - PROCESS_RECORD.process_t0 < age


def test_the_import_span_has_a_child_a_subpackage():
    spans = PROCESS_RECORD.spans()
    imported = spans_named(spans, "bf.setup.import")[0]
    laps = [s for s in spans if s["par"] == imported["sid"]
            and s["name"].startswith("bf.setup.import.")]
    assert [s["name"].rsplit(".", 1)[1] for s in laps] == [
        "tracing", "topology", "parallel", "utils", "metrics", "blackbox"]
    assert all(s["cat"] == "import" and s["modules_loaded"] >= 0
               for s in laps)
    assert sum(s["dur"] for s in laps) <= imported["dur"]
    assert sum(s["modules_loaded"] for s in laps) <= imported[
        "modules_loaded"]


def test_the_backend_s_start_lies_between_two_looks(monkeypatch):
    looks = iter([False, False, True, True])
    monkeypatch.setattr(startup, "_backend_up", lambda: next(looks))
    record = startup.StartupRecord()
    record.look_at_backend()
    time.sleep(0.01)
    record.look_at_backend()
    t_down = time.time()
    time.sleep(0.02)
    record.look_at_backend()
    record.look_at_backend()      # up already: nothing more is recorded
    span, = record.records
    assert (span["name"], span["cat"]) == ("bf.setup.backend", "runtime")
    assert span["t0"] <= t_down and 0.02 <= span["dur"] < 0.03 + 0.01


def test_init_is_a_span():
    before = len(spans_named(startup.RECORD.spans(), "bf.setup.init"))
    bf.init(size=1)
    try:
        spans = spans_named(startup.RECORD.spans(), "bf.setup.init", "init")
        assert len(spans) == before + 1
    finally:
        bf.shutdown()


@pytest.mark.parametrize("kind", ["time_span", "scalar", "event",
                                  "duration"])
def test_importing_twice_registers_one_listener_of_each_kind(kind, record):
    from jax._src import monitoring

    def listening():
        listeners = {
            "time_span": monitoring.get_event_time_span_listeners,
            "scalar": monitoring.get_scalar_listeners,
            "event": monitoring.get_event_listeners,
            "duration": monitoring.get_event_duration_listeners}[kind]()
        return [cb.__self__ for cb in listeners if type(getattr(
            cb, "__self__", None)).__name__ == "StartupRecord"]

    # the process's record since the first import, and this file's beside it
    assert listening() == [PROCESS_RECORD, record]
    importlib.reload(startup)
    importlib.reload(bf)
    assert startup.RECORD is record
    assert listening() == [PROCESS_RECORD, record]


# ---- the hooks leave the program alone --------------------------------------

def _small_step(remat):
    from bluefog_tpu.models.transformer import GPTConfig, TransformerLM

    cfg = GPTConfig(vocab_size=128, hidden_size=32, num_layers=2,
                    num_heads=2, max_position=16, remat=remat)
    model = TransformerLM(cfg)
    tokens = jnp.zeros((1, 16), jnp.int32)
    params = jax.eval_shape(model.init, jax.random.PRNGKey(0), tokens)

    def train_step(params, tokens):
        loss, grads = jax.value_and_grad(lambda p: model.apply(
            p, tokens).astype(jnp.float32).mean())(params)
        return jax.tree_util.tree_map(lambda p, g: p - 0.1 * g, params,
                                      grads), loss

    return jax.jit(train_step).lower(params, tokens).compile().as_text()


class _NoRecord:
    def add(self, *args, **kwargs):
        pass

    inc = look_at_backend = add


@pytest.mark.parametrize("remat", [False, True], ids=["plain", "remat"])
def test_the_compiled_step_is_the_same_to_the_byte_without_the_hooks(
        remat, monkeypatch):
    texts = []
    for stubbed in (False, True):
        jax.clear_caches()
        if stubbed:
            monkeypatch.setattr(startup, "RECORD", _NoRecord())
        texts.append(_small_step(remat))      # both calls on this one line
        if not stubbed:
            spans = startup.RECORD.spans()
            step = spans_named(spans, "bf.setup.trace", "train_step")[-1]
            blocks = [s for s in spans_named(spans, "bf.setup.trace.block")
                      if s["par"] == step["sid"]]
            # the block's kind, in the model's own words; a child of the
            # step's trace; as often as Python ran the block
            assert len(blocks) >= 2
            assert {b["cat"] for b in blocks} == {"fused_qkv"}
    assert texts[0] == texts[1]
    assert "bf.setup" not in texts[0]


@pytest.mark.parametrize("shared,traces", [(True, 1), (False, 3)],
                         ids=["shared_jit", "bare"])
def test_a_kernel_behind_a_shared_jit_is_traced_once_for_three_layers(
        shared, traces, monkeypatch):
    """``bf_setup_kernel_traces_total``: a ``pallas_call`` (interpret mode)
    reached through a shared ``jax.jit`` from three layers is traced once,
    and three times without it."""
    from bluefog_tpu.ops import short_conv

    if not shared:
        monkeypatch.setattr(short_conv, "_silu_forward",
                            short_conv._silu_forward.__wrapped__)
    jax.clear_caches()

    def three_layers(x, kernel, bias):
        for _ in range(3):
            x = short_conv.silu_short_conv(x, kernel, bias,
                                           backend="pallas_interpret")
        return x

    series = 'bf_setup_kernel_traces_total{kernel="bf_cconv_fwd"}'
    before = startup.RECORD.counter_series().get(series, 0)
    instants = len(spans_named(startup.RECORD.spans(),
                               "bf.setup.trace.kernel", "bf_cconv_fwd"))
    jax.make_jaxpr(three_layers)(
        jnp.ones((1, 48, 128)), jnp.ones((3, 128)), jnp.zeros((128,)))
    assert startup.RECORD.counter_series()[series] - before == traces
    assert len(spans_named(startup.RECORD.spans(), "bf.setup.trace.kernel",
                           "bf_cconv_fwd")) - instants == traces


# ---- the counters' carrier, the export --------------------------------------

def test_the_registry_s_summary_line_carries_the_counters(tmp_path):
    from bluefog_tpu.metrics import export

    path = tmp_path / "metrics.jsonl"
    bf.metrics_start(str(path))
    try:
        jax.jit(lambda x: x - 3).lower(jnp.ones(7)).compile()
    finally:
        bf.metrics_stop()
    summary, = [json.loads(line) for line in path.read_text().splitlines()
                if json.loads(line).get("summary")]
    assert summary["metrics"]['bf_setup_programs_total{stage="compile"}'] \
        == startup.RECORD.counter_series()[
            'bf_setup_programs_total{stage="compile"}']
    assert export is not None


def test_export_sends_each_record_once_parents_first(tmp_path):
    record = startup.StartupRecord()
    record.add("bf.setup.trace.block", "kda", t0=10.2, dur=0.1)
    record.add("bf.setup.trace", "step", t0=10.0, dur=1.0)
    rec = recorder.SpanRecorder(str(tmp_path), rank=3)
    assert record.export(rec) == 2
    record.add("bf.setup.lower", "jit(step)", t0=11.0, dur=0.5)
    assert record.export(rec) == 1 and record.export(rec) == 0
    spans = analyze.load_traces(str(tmp_path))
    block, = spans_named(spans, "bf.setup.trace.block")
    trace, = spans_named(spans, "bf.setup.trace")
    assert block["par"] == trace["sid"] and block["rank"] == 3
    assert len(spans_named(spans, "bf.setup.lower")) == 1
    assert {s["tid"] for s in spans} == {startup.TRACE_ID}
    newest = spans_named(spans, "bf.setup.record")[-1]
    assert newest["dropped"] == 0
    assert newest["process_t0"] == record.process_t0
    assert startup.StartupRecord().export(rec) == 0     # nothing to send


# ---- the benchmark's reducer, over a made-up record -------------------------

def _reducer():
    spec = importlib.util.spec_from_file_location(
        "startup_spans", os.path.join(REPO, "chipbench", "reducers",
                                      "startup_spans.py"))
    module = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(module)
    return module


def _made_up_record():
    def span(name, cat, t0, dur, **fields):
        return {"sid": 0, "par": 0, "tid": 1, "name": "bf.setup." + name,
                "cat": cat, "rank": None, "round": None, "t0": t0,
                "dur": dur, "thread": 1, **fields}

    def counters(hits, misses, read_s, k_fwd, k_bwd):
        """What a program's compile span keeps: the counters as they stood."""
        return {"bf_setup_cache_hits_total": hits,
                "bf_setup_cache_misses_total": misses,
                "bf_setup_cache_read_seconds_total": read_s,
                'bf_setup_programs_total{stage="compile"}': hits + misses,
                'bf_setup_kernel_traces_total{kernel="k_fwd"}': k_fwd,
                **({'bf_setup_kernel_traces_total{kernel="k_bwd"}': k_bwd}
                   if k_bwd else {})}

    return [
        span("import", "import", 100.0, 2.0),
        span("import.parallel", "import", 100.5, 1.0, modules_loaded=7),
        span("backend", "runtime", 102.5, 4.0),
        span("init", "init", 107.0, 0.5),
        span("trace", "init", 108.0, 3.0),
        span("trace", "inner", 108.5, 1.0),            # inside: not twice
        span("trace.block", "kda", 109.6, 1.0),
        span("trace.kernel", "k_fwd", 109.7, 0.0),
        span("trace.kernel", "k_fwd", 109.8, 0.0),
        span("lower", "jit(init)", 111.0, 1.0),
        span("compile", "jit(init)", 112.0, 2.0, cache="miss",
             cache_read_s=0.0, counters=counters(0.0, 1.0, 0.0, 2.0, 0.0)),
        span("trace", "train_step", 115.0, 5.0),
        span("trace.kernel", "k_bwd", 116.0, 0.0),
        span("lower", "jit(train_step)", 120.0, 2.0),
        span("compile", "jit(train_step)", 122.5, 1.5, cache="hit",
             cache_read_s=1.2, counters=counters(1.0, 1.0, 1.2, 2.0, 1.0)),
        # after the cut: an arm, the agreement check's program
        span("trace", "train_step", 130.0, 5.0),
        span("trace.kernel", "k_fwd", 131.0, 0.0),
        span("compile", "jit(train_step)", 136.0, 9.0, cache="miss",
             cache_read_s=0.0, counters=counters(1.0, 2.0, 1.2, 3.0, 1.0)),
        span("compile", "jit(reference)", 150.0, 9.0, cache="miss",
             cache_read_s=0.0, counters=counters(1.0, 3.0, 1.2, 3.0, 1.0)),
    ]


def test_the_view_reads_self_time_blocks_kernels_and_the_remainder():
    """``bftrace-tpu startup --until``: the same made-up start, as the
    operator's view reports it."""
    spans = _made_up_record()
    for sid, span in enumerate(spans, 1):
        span["sid"] = sid
    # a pallas_call traces its kernel in a jitted function named `wrapped`
    spans.append({**spans[0], "sid": 99, "name": "bf.setup.trace",
                  "cat": "wrapped", "t0": 109.72, "dur": 0.05})
    report, = analyze.startup_report(
        startup.parent_by_containment(spans), until="jit(train_step)")
    assert report["interval_s"] == pytest.approx(24.0)
    assert report["unspanned_s"] == pytest.approx(3.0)
    stage = report["by_stage"]
    assert stage["import"] == pytest.approx(1.0)        # 2 less its child
    assert stage["import.parallel"] == pytest.approx(1.0)
    # the init program's 3 s of trace less `inner` and the block; the step's 5
    assert stage["trace"] == pytest.approx(1.0 + 1.0 + 0.05 + 5.0)
    assert stage["trace.block"] == pytest.approx(0.95)
    assert report["blocks"] == {"kda": {
        "calls": 1, "seconds": pytest.approx(1.0),
        "self_s": pytest.approx(0.95)}}
    assert report["covered_s"]["trace"] == pytest.approx(8.0)
    assert report["kernels"] == {
        "k_fwd": {"traces": 2, "seconds": pytest.approx(0.05)},
        "k_bwd": {"traces": 1, "seconds": 0.0}}
    assert report["cache"] == {"hits": 1.0, "misses": 1.0,
                               "read_s": pytest.approx(1.2)}
    assert report["programs_counted"] == {"compile": 2.0}
    assert report["imports"] == [{"name": "parallel", "seconds": 1.0,
                                  "modules_loaded": 7}]
    assert report["import_at_age_s"] is None and report["dropped"] == 0
    assert report["programs"]["jit(train_step)"] == {
        "trace": pytest.approx(5.0), "lower": pytest.approx(2.0),
        "compile": pytest.approx(1.5), "cache": "hit",
        "cache_read_s": pytest.approx(1.2)}
    assert "inner" not in str(report["programs"])
    whole, = analyze.startup_report(spans)       # to the last span
    assert whole["interval_s"] == pytest.approx(59.0)
    assert whole["cache"]["misses"] == 3.0
    assert whole["kernels"]["k_fwd"]["traces"] == 3


def test_the_view_shows_the_record_s_own_line(capsys, tmp_path):
    """``process_t0`` and ``dropped`` reach the operator: the age at which
    the import began, and how many spans the bound cost."""
    record = startup.StartupRecord(limit=2)
    record.process_t0 = 90.0
    for made_up in _made_up_record()[:3]:
        record.add(made_up["name"], made_up["cat"], t0=made_up["t0"],
                   dur=made_up["dur"], modules_loaded=7)
    assert record.export(recorder.SpanRecorder(str(tmp_path), rank=0)) == 2
    report, = analyze.startup_report(analyze.load_traces(str(tmp_path)))
    assert report["dropped"] == 1
    assert report["import_at_age_s"] == pytest.approx(10.0)
    assert analyze.main(["startup", str(tmp_path)]) == 0
    out = capsys.readouterr().out
    assert "1 dropped" in out and "process age 10.00s" in out
    assert "parallel 1.00s (+7 modules)" in out


@pytest.mark.parametrize("value,expected", [
    ("import_s", 2.0), ("trace_s", 8.0), ("lower_s", 3.0),
    ("compile_s", 3.5), ("cache_misses", 1), ("kernel_traces", 3),
    # 24 s from the import's start to the cut, less 2 + 4 + 0.5 + 8 + 3 + 3.5
    ("unspanned_s", 3.0)])
def test_the_reducer_reads_the_start_up_to_the_step_s_first_compile(
        value, expected):
    got = _reducer().quantity(_made_up_record(), value, "jit(train_step)")
    assert got == pytest.approx(expected)
    assert _reducer().quantity(_made_up_record(), value,
                               "jit(no_such_program)") is None


# ---- one start of a benchmark cell, as benchmarks/start_report.py makes it --

@pytest.fixture(scope="module")
def start_reports(tmp_path_factory):
    """Two starts of the test manifest's smallest cell on one fresh cache
    directory: a cold one, then a warm one."""
    env = dict(os.environ, JAX_PLATFORMS="cpu", PYTHONPATH=REPO,
               JAX_COMPILATION_CACHE_DIR=str(tmp_path_factory.mktemp("cc")))
    env.pop("BLUEFOG_TPU_TRACE", None)
    outputs = []
    for _ in range(2):
        proc = subprocess.run(
            [sys.executable, os.path.join(REPO, "benchmarks",
                                          "start_report.py"),
             "--workload", "tiny.solo", "--seed", "2147483659", "--manifest",
             os.path.join(REPO, "chipbench", "tests", "data",
                          "BENCHMARK.json")],
            env=env, capture_output=True, text=True, timeout=300)
        assert proc.returncode == 0, proc.stderr[-2000:]
        outputs.append(proc.stdout)
    return outputs


@pytest.mark.parametrize("start,misses", [(0, 2), (1, 0)],
                         ids=["cold", "warm"])
def test_start_report_closes_the_accounting_of_one_start(
        start_reports, start, misses):
    """The harness's numbers and the record's, side by side: the misses say
    which kind of start it was, the compile spans are the harness's
    ``compile_s``, the interval lies on the process's age, and the parts
    leave nothing over."""
    out = start_reports[start]
    line, = [ln for ln in out.splitlines()
             if ln.startswith("start_report: ")]
    got = json.loads(line[len("start_report: "):])
    assert got["setup_cache_misses"] == misses
    assert got["setup_kernel_traces"] == 0 and got["dropped"] == 0
    assert got["setup_compile_s"] == pytest.approx(got["compile_s"],
                                                   rel=0.02)
    # build_cell returns a moment after the cut (longer on a busy host); the
    # kernel counts a process's start in clock ticks of 10 ms
    assert -0.05 < got["interval_by_ages_s"] - got["interval_s"] < 2.0
    parts = sum(got[key] for key in (
        "setup_import_s", "backend_span_s", "init_span_s", "setup_trace_s",
        "setup_lower_s", "setup_compile_s", "setup_unspanned_s"))
    # the parts cover the interval; they overlap only where an op run
    # eagerly inside a trace compiles there
    assert got["interval_s"] - 1e-3 <= parts < 1.02 * got["interval_s"]
    assert "bftrace startup: " in out
    assert f"cache: {2 - misses} hit(s), {misses} miss(es)" in out
    assert "program jit(train_step): " in out
