"""The seven set-up metrics: their files, their manifest entries, the
reducer over the record of its own process, over a program that keeps no
record, and through the harness on the CPU (where the two counts print)."""

import json
import os
import subprocess
import sys

import pytest

from conftest import REPO

from chipbench import cell as cells

NAMES = {"setup_import_s": "import_s", "setup_trace_s": "trace_s",
         "setup_lower_s": "lower_s", "setup_compile_s": "compile_s",
         "setup_cache_misses": "cache_misses",
         "setup_kernel_traces": "kernel_traces",
         "setup_unspanned_s": "unspanned_s"}
COUNTS = {"setup_cache_misses", "setup_kernel_traces"}


@pytest.fixture(scope="module")
def manifest():
    return cells.Manifest.load(os.path.join(REPO, "BENCHMARK.json"))


@pytest.mark.parametrize("name", sorted(NAMES))
def test_each_metric_moves_setup_s_in_every_cell(manifest, name):
    entry = manifest.entry("per_layer", name)
    assert entry["moves"] == "setup_s" and entry["better"] == "lower"
    assert "workloads" not in entry          # every cell has a start
    assert entry["source"] == ("program_counter" if name in COUNTS
                               else "program_span")
    assert entry["unit"] == ("count" if name in COUNTS else "s")
    spec = cells.load_json(manifest.find("metrics", name))
    assert spec["reducer"] == "startup_spans"
    assert spec["params"] == {"value": NAMES[name],
                              "until_program": "jit(train_step)"}


@pytest.fixture
def record(monkeypatch):
    """An empty record in the process's place (under pytest that one fills
    with earlier tests' programs), with an import span and listening."""
    import time

    from bluefog_tpu.tracing import startup

    fresh = startup.StartupRecord()
    fresh.add("bf.setup.import", "import", t0=time.time() - 1.0, dur=0.5)
    fresh.listen()
    monkeypatch.setattr(startup, "RECORD", fresh)
    yield fresh
    fresh.listen(False)


def test_the_reducer_reads_its_own_process_and_the_accounting_closes(
        manifest, record):
    import jax
    import jax.numpy as jnp

    def train_step(x):
        return jnp.tanh(x) * 2

    jax.jit(train_step).lower(jnp.ones(4)).compile()
    reducer = manifest.module("reducers", "startup_spans")
    got = {name: reducer.reduce(None, {"value": value,
                                       "until_program": "jit(train_step)"})
           for name, value in NAMES.items()}
    assert all(v is not None and v >= 0 for v in got.values()), got
    spans = record.spans()
    start = min(s["t0"] for s in spans if s["name"] == "bf.setup.import")
    cut = min(s["t0"] + s["dur"] for s in spans
              if s["name"] == "bf.setup.compile"
              and s["cat"] == "jit(train_step)")
    covered, covered_to = 0.0, start     # the union, done here by hand
    for t0, t1 in sorted((s["t0"], min(s["t0"] + s["dur"], cut))
                         for s in spans if start <= s["t0"] <= cut):
        covered += max(t1 - max(t0, covered_to), 0.0)
        covered_to = max(covered_to, t1)
    assert got["setup_unspanned_s"] + covered == pytest.approx(cut - start)
    assert got["setup_import_s"] == pytest.approx(0.5)
    assert got["setup_cache_misses"] == sum(
        v for k, v in record.counter_series().items()
        if k == "bf_setup_cache_misses_total")
    assert reducer.reduce(None, {"value": "trace_s",
                                 "until_program": "jit(never)"}) is None
    with pytest.raises(ValueError):
        reducer.reduce(None, {"value": "no_such",
                              "until_program": "jit(train_step)"})


def test_a_full_record_without_the_cut_is_an_error(manifest, monkeypatch):
    """The bound was reached before the step compiled: the spans that say
    where the start went are gone, and the reducer says so."""
    from bluefog_tpu.tracing import startup

    full = startup.StartupRecord(limit=1)
    full.add("bf.setup.import", "import", t0=0.0, dur=0.5)
    full.add("bf.setup.compile", "jit(train_step)", t0=1.0, dur=1.0)
    monkeypatch.setattr(startup, "RECORD", full)
    reducer = manifest.module("reducers", "startup_spans")
    with pytest.raises(RuntimeError, match="1 dropped"):
        reducer.reduce(None, {"value": "trace_s",
                              "until_program": "jit(train_step)"})


def test_a_program_without_the_record_reports_nothing(manifest, monkeypatch):
    """The parent of the PR that added the record: the reducer returns
    ``None`` and the line leaves the metric out."""
    import bluefog_tpu.tracing

    monkeypatch.setitem(sys.modules, "bluefog_tpu.tracing.startup", None)
    monkeypatch.delattr(bluefog_tpu.tracing, "startup")
    reducer = manifest.module("reducers", "startup_spans")
    for value in NAMES.values():
        assert reducer.reduce(None, {"value": value, "until_program":
                                     "jit(train_step)"}) is None


def test_a_traced_cpu_run_prints_the_two_counts(tmp_path):
    data = cells.load_json(os.path.join(
        REPO, "chipbench", "tests", "data", "BENCHMARK.json"))
    real = cells.load_json(os.path.join(REPO, "BENCHMARK.json"))
    data["per_layer"] += [m for m in real["per_layer"] if m["name"] in NAMES]
    path = tmp_path / "BENCHMARK.json"
    path.write_text(json.dumps(data))
    proc = subprocess.run(
        [sys.executable, os.path.join(REPO, "chipbench", "run.py"),
         "--workload", "tiny.solo", "--seed", "2147483659", "--seconds",
         "1.5", "--trace", "1", "--manifest", str(path)],
        capture_output=True, text=True, timeout=600)
    assert proc.returncode == 0, proc.stderr[-2000:]
    metrics = json.loads(proc.stdout.splitlines()[-1])["metrics"]
    assert set(metrics) & set(NAMES) == COUNTS
    assert metrics["setup_kernel_traces"] == {"value": 0, "unit": "count"}
    assert metrics["setup_cache_misses"]["value"] >= 0
