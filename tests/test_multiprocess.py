"""Multi-process correctness: real OS processes, real cross-process
collectives, real distributed locks.

The reference runs its distributed suite under ``mpirun -np N pytest``
(SURVEY.md §4).  The equivalent here: this module spawns N worker processes
(``tests/_mp_worker.py``) that rendezvous through ``initialize_cluster``,
build one global mesh spanning the process boundary (2 virtual CPU devices
per process, gloo transport), and assert closed-form gossip/allreduce plus
cross-process ``win_mutex`` exclusion.  Plus: rendezvous failure must be
LOUD when a cluster was explicitly requested.
"""

import os
import socket
import subprocess
import sys

import pytest

from tests._util import REPO as _REPO, clean_env

_WORKER = os.path.join(_REPO, "tests", "_mp_worker.py")


def _free_port() -> int:
    with socket.socket() as s:
        s.bind(("127.0.0.1", 0))
        return s.getsockname()[1]


def _clean_env():
    # the workers configure their own platform/device-count (2 each) and
    # pin cpu themselves before importing jax
    return clean_env(cpu_pin=False)


@pytest.mark.parametrize("nproc", [2])
@pytest.mark.duration_budget(240)  # pre-existing heavyweight; tier-1 coverage load-bearing
def test_cluster_spans_processes(nproc):
    port = _free_port()
    procs = [
        subprocess.Popen(
            [sys.executable, _WORKER, str(pid), str(nproc), str(port)],
            stdout=subprocess.PIPE, stderr=subprocess.STDOUT, text=True,
            env=_clean_env(), cwd=_REPO)
        for pid in range(nproc)
    ]
    outs = []
    try:
        for p in procs:
            out, _ = p.communicate(timeout=180)
            outs.append(out)
    except subprocess.TimeoutExpired:
        for p in procs:
            p.kill()
        pytest.fail("multi-process workers timed out:\n" +
                    "\n".join(o or "" for o in outs))
    for pid, (p, out) in enumerate(zip(procs, outs)):
        assert p.returncode == 0, f"worker {pid} failed:\n{out}"
        assert f"MP_WORKER_OK {pid}" in out, f"worker {pid} output:\n{out}"


@pytest.mark.duration_budget(60)  # pre-existing heavyweight; tier-1 coverage load-bearing
def test_rendezvous_timeout_kills_the_process():
    """An explicitly requested cluster that cannot rendezvous must never
    degrade to silent single-process training.  In this jaxlib the
    distributed runtime's fatal check terminates the process on rendezvous
    timeout before Python sees an exception — maximally loud: assert the
    process died nonzero and never reached the code after initialize."""
    port = _free_port()
    code = (
        "import os\n"
        "os.environ['JAX_PLATFORMS'] = 'cpu'\n"
        "import jax\n"
        "jax.config.update('jax_platforms', 'cpu')\n"
        "from bluefog_tpu.runtime.launch import initialize_cluster\n"
        f"initialize_cluster('127.0.0.1:{port}', 2, 0, "
        "initialization_timeout=3)\n"
        "print('SILENT_FALLBACK')\n"
    )
    out = subprocess.run(
        [sys.executable, "-c", code], capture_output=True, text=True,
        env=_clean_env(), cwd=_REPO, timeout=120)
    assert out.returncode != 0, (
        "rendezvous timeout did not fail the process:\n" + out.stdout)
    assert "SILENT_FALLBACK" not in out.stdout


def test_win_mutex_break_single_controller_noop():
    """Single controller: a holder's death is process death — break is a
    documented no-op returning False (never drops a live RLock)."""
    import bluefog_tpu as bf

    bf.init()
    with bf.win_mutex("solo"):
        assert bf.win_mutex_break("solo") is False
    assert bf.win_mutex_break("solo") is False


def test_rendezvous_exception_policy(monkeypatch):
    """When initialize raises a catchable error: explicit cluster arguments
    escalate to RuntimeError; the fully-auto-detected call only warns."""
    import jax

    from bluefog_tpu.runtime import launch

    def boom(**kwargs):
        raise ValueError("no cluster here")

    monkeypatch.setattr(jax.distributed, "initialize", boom)
    with pytest.raises(RuntimeError, match="rendezvous failed"):
        launch.initialize_cluster("127.0.0.1:1", 2, 0)
    launch.initialize_cluster()  # auto-detect: warn, no raise
