"""bench.py: every result names its device, the MFU denominator is the
published peak or nothing, and a failure is a failure.

The pure pieces (spec lookup, device record, roofline arithmetic, the
trace parser) run in tier-1; the end-to-end subprocess run on the CPU mesh
is ``slow`` (it compiles ResNet-50).
"""

import gzip
import json
import os
import subprocess
import sys

import pytest

from tests._util import REPO as _REPO, load_script


@pytest.fixture(scope="module")
def bench():
    return load_script("bench.py")


class FakeDev:
    def __init__(self, kind, platform="tpu"):
        self.device_kind = kind
        self.platform = platform


class TestNominalSpec:
    def test_known_kinds(self, bench):
        assert bench.nominal_spec([FakeDev("TPU v5 lite")]) == (197.0, 819.0)
        assert bench.nominal_spec([FakeDev("TPU v5p")]) == (459.0, 2765.0)
        assert bench.nominal_spec([FakeDev("TPU v4")]) == (275.0, 1228.0)
        assert bench.nominal_spec([FakeDev("TPU v6 lite")]) == (918.0, 1640.0)

    def test_longest_match_wins(self, bench):
        # "v5 lite" contains "v5"-family substrings; must not fall through
        # to a shorter key with different numbers
        tf, _ = bench.nominal_spec([FakeDev("tpu v5 lite chip")])
        assert tf == 197.0

    def test_unknown_kind_is_an_error(self, bench):
        with pytest.raises(SystemExit) as exc:
            bench.nominal_spec([FakeDev("QuantumAbacus 3000")])
        assert exc.value.code not in (0, None)
        assert "QuantumAbacus 3000" in str(exc.value.code)


class TestDeviceRecord:
    def test_names_platform_kind_and_count(self, bench):
        devs = [FakeDev("TPU v5 lite")] * 4
        record, spec = bench.device_record(devs)
        assert record == {"platform": "tpu", "device_kind": "TPU v5 lite",
                          "device_count": 4}
        assert spec == (197.0, 819.0)

    def test_unknown_tpu_kind_exits_nonzero(self, bench):
        with pytest.raises(SystemExit) as exc:
            bench.device_record([FakeDev("mystery")])
        assert exc.value.code not in (0, None)

    def test_cpu_needs_the_callers_own_pin(self, bench, monkeypatch):
        monkeypatch.delenv("JAX_PLATFORMS", raising=False)
        with pytest.raises(SystemExit) as exc:
            bench.device_record([FakeDev("cpu", platform="cpu")])
        assert "no TPU found" in str(exc.value.code)

    def test_pinned_cpu_reports_no_spec(self, bench, monkeypatch):
        monkeypatch.setenv("JAX_PLATFORMS", "cpu")
        record, spec = bench.device_record([FakeDev("cpu", platform="cpu")])
        assert record["platform"] == "cpu" and spec is None


def test_oom_is_classified_by_the_installed_error_type(bench):
    import jax

    oom = jax.errors.JaxRuntimeError(
        "RESOURCE_EXHAUSTED: Ran out of memory in memory space hbm")
    assert bench._is_oom(oom)
    assert bench._is_oom(MemoryError())
    assert not bench._is_oom(jax.errors.JaxRuntimeError("INTERNAL: boom"))
    assert not bench._is_oom(RuntimeError("RESOURCE_EXHAUSTED: not jax's"))


def test_mfu_and_roofline_estimate(bench):
    mem = {"temp": 8 << 30, "args": 100 << 20}  # 8 GiB act, 100 MiB args
    f = bench.mfu_fields(
        (197.0, 819.0), achieved_flops=50e12, best_mem=mem,
        flops_per_step=128 * 12.27e9, best_batch=128, best_ips=10000.0)
    assert f["mfu"] == pytest.approx(50 / 197, abs=1e-4)
    assert f["nominal_peak_tflops_per_sec"] == 197.0
    r = f["roofline_estimate"]
    assert r["hbm_bytes_per_step_est"] == mem["temp"] + mem["args"]
    # 8.1 GiB over 819 GB/s ~ 10.6 ms; compute 1.57 TF over 197 TF ~ 8 ms
    assert r["min_step_ms_memory"] == pytest.approx(10.6, abs=0.5)
    assert r["bound"] == "memory"
    assert r["measured_step_ms"] == pytest.approx(12.8, abs=0.1)


def _write_trace(tmp_path, events):
    run_dir = tmp_path / "plugins" / "profile" / "run"
    run_dir.mkdir(parents=True)
    with gzip.open(run_dir / "host.trace.json.gz", "wt") as f:
        json.dump({"traceEvents": events}, f)


class TestTraceParser:
    def test_trace_step_ms_from_synthetic_trace(self, bench, tmp_path):
        """_trace_device_step_ms reads a TensorBoard-layout trace and
        averages device op time over PROFILE_STEPS, selecting only the
        'XLA Ops' thread (not step envelopes)."""
        _write_trace(tmp_path, [
            {"ph": "M", "pid": 7, "name": "process_name",
             "args": {"name": "/device:TPU:0"}},
            {"ph": "M", "pid": 7, "tid": 1, "name": "thread_name",
             "args": {"name": "XLA Ops"}},
            {"ph": "M", "pid": 7, "tid": 2, "name": "thread_name",
             "args": {"name": "XLA Modules"}},
            # 3 steps x 2 ops of 1000 us on the op thread = 6000 us total
            *[{"ph": "X", "pid": 7, "tid": 1, "name": f"fusion.{i}",
               "ts": i * 1000, "dur": 1000} for i in range(6)],
            # module envelope spanning everything: must NOT be counted
            {"ph": "X", "pid": 7, "tid": 2, "name": "jit_step",
             "ts": 0, "dur": 6000},
        ])
        got = bench._trace_device_step_ms(str(tmp_path))
        assert got is not None
        assert abs(got - 6000 / 1e3 / bench.PROFILE_STEPS) < 1e-9

    def test_host_only_trace_returns_none(self, bench, tmp_path):
        """A CPU-only capture (no device pid / XLA Ops thread) holds no
        device time."""
        _write_trace(tmp_path, [{"ph": "X", "pid": 1, "tid": 1,
                                 "name": "python", "ts": 0, "dur": 500}])
        assert bench._trace_device_step_ms(str(tmp_path)) is None

    def test_device_pid_without_op_threads_is_not_divided(self, bench,
                                                          tmp_path):
        """A trace with a TPU pid but no labeled 'XLA Ops' threads cannot
        distinguish chips from extra per-device streams (DMA etc.), so it
        yields no per-chip figure at all."""
        _write_trace(tmp_path, [
            {"ph": "M", "pid": 7, "name": "process_name",
             "args": {"name": "/device:TPU:0"}},
            # two unlabeled streams under the device pid
            {"ph": "X", "pid": 7, "tid": 1, "name": "fusion.1",
             "ts": 0, "dur": 98000},
            {"ph": "X", "pid": 7, "tid": 2, "name": "dma", "ts": 0,
             "dur": 10000},
        ])
        assert bench._trace_device_step_ms(str(tmp_path)) is None


def test_free_device_memory_runs_on_cpu():
    """The buffer sweep must be safe to call anywhere.  Subprocess: it
    deletes EVERY live array in its process, which would poison other
    tests' cached arrays."""
    proc = subprocess.run(
        [sys.executable, "-c",
         "import jax; jax.config.update('jax_platforms', 'cpu')\n"
         "from tests._util import load_script\n"
         "import jax.numpy as jnp\n"
         "bench = load_script('bench.py')\n"
         "x = jnp.ones((8, 8)) + 1\n"
         "bench._free_device_memory()\n"
         "assert x.is_deleted()\n"
         "print('FREED')\n"],
        capture_output=True, text=True, cwd=_REPO, timeout=120)
    assert proc.returncode == 0, proc.stderr[-2000:]
    assert "FREED" in proc.stdout


def _run_bench(*argv):
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               XLA_FLAGS="--xla_force_host_platform_device_count=2")
    return subprocess.run(
        [sys.executable, "bench.py", *argv], capture_output=True, text=True,
        env=env, cwd=_REPO, timeout=540)


@pytest.mark.slow
def test_cpu_control_flow_run_names_its_device_and_carries_no_mfu():
    proc = _run_bench("--batch", "2", "--image-size", "32", "--steps", "2",
                      "--warmup", "1")
    assert proc.returncode == 0, proc.stderr[-2000:]
    out = json.loads(proc.stdout.strip().splitlines()[-1])
    assert out["metric"] == "resnet50_images_per_sec_per_chip"
    assert out["value"] > 0 and out["batch"] == 2 and out["sweep"]
    assert (out["platform"], out["device_kind"], out["device_count"]) == (
        "cpu", "cpu", 2)
    assert "mfu" not in out and "trace_device_step_ms" not in out
    assert out["flops_source"] in ("xla_cost_analysis", "analytic")


@pytest.mark.slow
def test_failed_measurement_exits_nonzero_and_prints_no_result():
    """No rescue path: a batch whose step cannot be built fails the run."""
    proc = _run_bench("--batch", "0", "--image-size", "32", "--steps", "1")
    assert proc.returncode != 0
    assert "resnet50_images_per_sec_per_chip" not in proc.stdout
