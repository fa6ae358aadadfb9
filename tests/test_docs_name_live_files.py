"""A document that sends the reader to a file names a file that exists.

Every back-quoted token of the current documents that looks like a path of
this tree (it ends in a source or document suffix, or is an upper-case record
name) — a word of a quoted command line counts, ``python x.py --flag`` names
``x.py`` — must resolve: from the repo root where it begins with a top-level
directory, as the tail of some tracked path where it holds a ``/``, by its
basename otherwise.  The history documents (``CHANGES.md``, ``ROADMAP.md``,
``PERF.md``, ``SURVEY.md``) name what is gone on purpose and are not read.
"""

import fnmatch
import glob
import os
import re
import subprocess

import pytest

from tests._util import REPO

DOCUMENTS = (["README.md", "PARITY.md", "BASELINE.md",
              os.path.join(".claude", "skills", "verify", "SKILL.md")]
             + sorted(os.path.relpath(p, REPO) for p in
                      glob.glob(os.path.join(REPO, "docs", "*.md"))))
TOP_DIRECTORIES = ("benchmarks/", "bluefog_tpu/", "chipbench/", "examples/",
                   "tests/", "docs/")
NAMES_A_FILE = re.compile(
    r"^[\w./<>*{},-]+\.(py|md|cc|h|toml)$|^[A-Z][A-Z_]*[\w.*{},-]*\.jsonl?$")
# Paths of the reference project (upstream Bluefog, Horovod-style launchers):
# the documents quote them to say what each was translated into.
UPSTREAM = frozenset("""
    bluefog/common/basics.py bluefog/common/topology_util.py
    bluefog/torch/mpi_ops.py bluefog/torch/mpi_win_ops.py
    bluefog/torch/optimizers.py bluefog/torch/utility.py
    common/basics.py common/global_state.h common/half.h
    common/topology_util.py torch/mpi_ops.py torch/optimizers.py
    torch/utility.py run/run.py examples/pytorch_mnist.py
    interactive_run.py setup.py mpi_controller.cc operations.cc half.h
""".split())
# The user's own script in a launcher's command line, and the XLA source file
# a log line of the CPU cache loader names.
NOT_OF_ANY_TREE = frozenset({"train.py", "script.py", "cpu_aot_loader.cc"})


def tracked_files():
    out = subprocess.run(["git", "ls-files", "--cached", "--others",
                          "--exclude-standard"], cwd=REPO,
                         capture_output=True, text=True)
    if out.returncode == 0 and out.stdout.strip():
        files = out.stdout.split()
    else:  # an unpacked archive: every file under the root
        files = [os.path.relpath(os.path.join(d, f), REPO)
                 for d, _, names in os.walk(REPO) for f in names]
    return [f for f in files if os.path.exists(os.path.join(REPO, f))]


def expand(token):
    """``benchmarks/{a,b}_bench.py`` -> both names."""
    m = re.search(r"\{([^{}]*)\}", token)
    if not m:
        return [token]
    return [name for part in m.group(1).split(",") for name in
            expand(token[:m.start()] + part.strip() + token[m.end():])]


def named_files(text):
    for quoted in re.findall(r"`([^`\n]+)`", text):
        for word in quoted.split():
            word = word.split("::", 1)[0].rstrip(".,;:)").lstrip("(")
            if NAMES_A_FILE.match(word):
                yield from expand(word)


def resolves(token, files, basenames):
    if any(c in token for c in "*<>"):
        pattern = re.sub(r"<[^>]*>", "*", token)
        if pattern.startswith(TOP_DIRECTORIES):
            return bool(glob.glob(os.path.join(REPO, pattern)))
        return any(fnmatch.fnmatch(f, "*" + pattern) for f in files)
    if token.startswith(TOP_DIRECTORIES):
        return os.path.exists(os.path.join(REPO, token))
    if "/" in token:
        return any(f == token or f.endswith("/" + token) for f in files)
    return token in basenames


@pytest.fixture(scope="module")
def tree():
    files = tracked_files()
    return files, {os.path.basename(f) for f in files}


@pytest.mark.parametrize("document", DOCUMENTS)
def test_every_repo_path_a_document_names_exists(document, tree):
    files, basenames = tree
    with open(os.path.join(REPO, document), encoding="utf-8") as f:
        named = sorted(set(named_files(f.read())) - UPSTREAM - NOT_OF_ANY_TREE)
    assert named, "the rule found no path to check"
    missing = [t for t in named if not resolves(t, files, basenames)]
    assert not missing, f"{document} names files this tree does not hold"


def test_the_rule_catches_a_name_no_file_bears(tree):
    files, basenames = tree
    assert list(named_files("run `python retired.py --profile DIR`, then see "
                            "`RETIRED.md` and `ops/moe.py::routed_experts`, "
                            "`benchmarks/{relay,fleet}_bench.py`")) == [
        "retired.py", "RETIRED.md", "ops/moe.py", "benchmarks/relay_bench.py",
        "benchmarks/fleet_bench.py"]
    assert not resolves("retired.py", files, basenames)
    assert not resolves("benchmarks/retired.py", files, basenames)
    assert not resolves("ops/retired.py", files, basenames)
    assert resolves("ops/moe.py", files, basenames)
    assert resolves("BENCH_control.json", files, basenames)
