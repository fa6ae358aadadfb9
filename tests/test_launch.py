"""Launcher CLI (bfrun-tpu analog): simulate mode, env propagation, timeline."""

import json
import os
import subprocess
import sys

import pytest

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def _run_cli(cli_args, *, env_extra=None, timeout=180):
    env = dict(os.environ)
    env.pop("JAX_PLATFORMS", None)
    env.pop("XLA_FLAGS", None)
    env["PYTHONPATH"] = REPO + os.pathsep + env.get("PYTHONPATH", "")
    env.update(env_extra or {})
    return subprocess.run(
        [sys.executable, "-m", "bluefog_tpu.runtime.launch"] + cli_args,
        capture_output=True, text=True, timeout=timeout, env=env, cwd=REPO,
    )


def test_simulate_gives_virtual_devices(tmp_path):
    script = tmp_path / "probe.py"
    script.write_text(
        "import jax\n"
        "assert jax.devices()[0].platform == 'cpu', jax.devices()\n"
        "assert len(jax.devices()) == 8, jax.devices()\n"
        "print('DEVICES', len(jax.devices()))\n"
    )
    r = _run_cli(["--simulate", "8", str(script)])
    assert r.returncode == 0, r.stderr
    assert "DEVICES 8" in r.stdout


def test_env_propagation_and_script_args(tmp_path):
    script = tmp_path / "probe.py"
    script.write_text(
        "import os, sys\n"
        "print('VAR', os.environ['BF_TEST_VAR'])\n"
        "print('ARGS', sys.argv[1:])\n"
    )
    r = _run_cli(["-x", "BF_TEST_VAR=hello", "--num-processes", "1",
                  str(script), "--lr", "0.1"])
    assert r.returncode == 0, r.stderr
    assert "VAR hello" in r.stdout
    assert "ARGS ['--lr', '0.1']" in r.stdout


def test_bare_env_flag_requires_existing_var(tmp_path):
    script = tmp_path / "probe.py"
    script.write_text("print('ran')\n")
    r = _run_cli(["-x", "BF_DEFINITELY_UNSET_VAR", str(script)])
    assert r.returncode != 0
    assert "not set" in (r.stderr + r.stdout)


def test_timeline_flag_writes_trace(tmp_path):
    script = tmp_path / "probe.py"
    trace = tmp_path / "trace.json"
    script.write_text(
        "from bluefog_tpu.utils import timeline\n"
        "with timeline.timeline_context('launcher_span'):\n"
        "    pass\n"
        "timeline.timeline_stop()\n"
    )
    r = _run_cli(["--simulate", "2", "--timeline", str(trace), str(script)])
    assert r.returncode == 0, r.stderr
    events = json.loads(trace.read_text())
    assert any(e["name"] == "launcher_span" for e in events)


def test_supervising_parent_never_touches_a_device(tmp_path):
    """A chip belongs to one process: the --supervise parent must leave it
    to the child, so it may import jax but never initialise a backend."""
    script = tmp_path / "child.py"
    script.write_text("print('CHILD RAN')\n")
    from tests._util import clean_env

    proc = subprocess.run(
        [sys.executable, "-c",
         "import sys\n"
         "from bluefog_tpu.runtime.launch import main\n"
         "try:\n"
         f"    main(['--supervise', '0', {str(script)!r}])\n"
         "except SystemExit as e:\n"
         "    assert e.code == 0, e.code\n"
         "from jax._src import xla_bridge\n"
         "assert not xla_bridge.backends_are_initialized()\n"
         "print('PARENT CLEAN')\n"],
        capture_output=True, text=True, env=clean_env(), cwd=REPO,
        timeout=180)
    assert proc.returncode == 0, proc.stderr[-2000:]
    assert "CHILD RAN" in proc.stdout and "PARENT CLEAN" in proc.stdout


def test_no_cluster_to_detect_runs_single_process(tmp_path):
    """`bfrun-tpu train.py` with no cluster arguments on a machine with
    nothing to auto-detect warns once and runs the script."""
    script = tmp_path / "probe.py"
    script.write_text("print('RAN')\n")
    r = _run_cli([str(script)], env_extra={"JAX_PLATFORMS": "cpu"})
    assert r.returncode == 0, r.stderr
    assert "RAN" in r.stdout
    assert "jax.distributed.initialize skipped" in r.stderr


def test_interactive_repl_smoke():
    """ibfrun-tpu (the ibfrun analog) brings the framework up and serves a
    REPL: pipe a command stream in, assert the banner, evaluated output,
    and a clean exit."""
    import subprocess
    import sys

    from tests._util import REPO, clean_env

    code = "print('SIZE', bf.size(), ctx.axis_name)\n"
    env = clean_env()
    env["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
    proc = subprocess.run(
        [sys.executable, "-c",
         "import jax; jax.config.update('jax_platforms','cpu')\n"
         "from bluefog_tpu.runtime.launch import interactive_main\n"
         "interactive_main(['--topology', 'ring'])"],
        input=code, capture_output=True, text=True, env=env, cwd=REPO,
        timeout=300)
    assert proc.returncode == 0, proc.stderr[-1000:]
    banner_and_out = proc.stdout + proc.stderr  # code.interact banners -> stderr
    assert "bluefog_tpu interactive" in banner_and_out
    assert "topology=ring" in banner_and_out
    assert "SIZE 8" in proc.stdout
