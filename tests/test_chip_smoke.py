"""chip_smoke.py is the on-chip proof; here only its refusal is checkable:
a CPU backend must end in a non-zero exit that says why, with no result
line."""

import subprocess
import sys

from tests._util import REPO, clean_env, load_script


def test_refuses_a_cpu_backend():
    proc = subprocess.run([sys.executable, "chip_smoke.py"], env=clean_env(),
                          cwd=REPO, capture_output=True, text=True,
                          timeout=120)
    assert proc.returncode != 0
    assert "needs a TPU" in proc.stderr and "'cpu'" in proc.stderr
    assert '"ok"' not in proc.stdout


def test_watchdog_fits_the_check_window():
    assert 0 < load_script("chip_smoke.py").WATCHDOG_S < 1200
