"""The compile-cache helper: one decision, made from outside or not at all.

``JAX_COMPILATION_CACHE_DIR`` set -> the code sets no directory (JAX reads
the variable itself); unset -> ``<checkout>/.jax_cache``; never a path built
from ``tempfile``, a pid or the clock.  Subprocesses: the helper mutates
process-global jax config.
"""

import os
import subprocess
import sys
import tempfile

from tests._util import REPO, clean_env

_PROBE = (
    "import jax\n"
    "before = jax.config.jax_compilation_cache_dir\n"
    "import bluefog_tpu as bf\n"
    "returned = bf.configure_compile_cache()\n"
    "print(repr((before, returned, jax.config.jax_compilation_cache_dir)))\n"
)


def _probe(**env_overrides):
    env = clean_env()
    env.pop("JAX_COMPILATION_CACHE_DIR", None)
    env.update(env_overrides)
    proc = subprocess.run([sys.executable, "-c", _PROBE], env=env, cwd=REPO,
                          capture_output=True, text=True, timeout=120)
    assert proc.returncode == 0, proc.stderr[-2000:]
    return eval(proc.stdout.strip().splitlines()[-1])  # our own repr


def test_variable_set_means_code_sets_nothing(tmp_path):
    placed = str(tmp_path / "placed-from-outside")
    before, returned, after = _probe(JAX_COMPILATION_CACHE_DIR=placed)
    # jax picked the variable up by itself; the helper changed nothing
    assert before == after == returned == placed


def test_unset_means_the_checkout(tmp_path):
    before, returned, after = _probe()
    assert before is None
    assert returned == after == os.path.join(REPO, ".jax_cache")
    for volatile in (tempfile.gettempdir(), str(os.getpid())):
        assert volatile not in after


def test_one_assignment_in_the_repo():
    """Exactly one place assigns the cache directory — the helper — and it
    is guarded by the variable being unset."""
    import glob

    sources = [os.path.join(REPO, f) for f in
               ("chip_smoke.py", "__graft_entry__.py")]
    for pkg in ("bluefog_tpu", "examples", "benchmarks"):
        sources += glob.glob(os.path.join(REPO, pkg, "**", "*.py"),
                             recursive=True)
    hits = []
    for path in sources:
        with open(path, encoding="utf-8") as f:
            hits += [os.path.relpath(path, REPO) for line in f
                     if "compilation_cache_dir" in line]
    assert hits == [os.path.join("bluefog_tpu", "utils", "compile_cache.py")]


def test_checkout_cache_is_git_ignored():
    out = subprocess.run(
        ["git", "check-ignore", ".jax_cache/x", "chiprun_out/x"],
        cwd=REPO, capture_output=True, text=True)
    assert out.stdout.split() == [".jax_cache/x", "chiprun_out/x"], out
