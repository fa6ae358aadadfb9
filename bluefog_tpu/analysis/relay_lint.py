"""BF-RLY lint: relay re-publish sites must speak resync/cursor-gap.

The relay tree's correctness hangs on one discipline at every
re-publish hop: a snapshot landed from upstream is re-published ONLY
strictly forward of the hop's cursor, and every gap (an upstream
resync, a torn delta, a re-parent) falls back to the full-frame resync
anchor rather than silently re-serving a replayed or diverged round.
Code that forwards a received snapshot into a table ``publish`` WITHOUT
any of that vocabulary is the delta-divergence twin of a round-blind
snapshot consumer (BF-SRV001): it will happily re-publish an upstream
replay backwards — children then see duplicate or regressed rounds —
or compound a desynced delta reconstruction into every tier below it.
Not a crash; a quietly diverging distribution tree.

The rule, per function (AST source lint, the BF-SRV001 pattern):

- a **re-publish site** is a call of an attribute named ``publish``
  inside a function that ALSO references snapshot-intake vocabulary —
  the attribute/name ``leaves`` or the type name ``Snapshot`` (i.e.
  the function forwards a RECEIVED snapshot; a plain publisher
  constructing its own leaves is out of scope) — in modules that
  import ``bluefog_tpu.relay`` or live under ``bluefog_tpu/relay/``;
- a site is **checked** when the enclosing function references the
  resync-anchor/cursor-gap vocabulary — ``resync``, ``anchor``,
  ``cursor`` as whole snake-case words — or handles
  :class:`~bluefog_tpu.runtime.delta.DeltaDesync`.

**BF-RLY001** (error): a re-publish site with none of the above.
**BF-RLY100** (info): scan summary.  **BF-RLY003** (warning): a file
the lint could not read/parse.
"""

from __future__ import annotations

import ast
import os
import re
from typing import List, Optional

from bluefog_tpu.analysis.report import Diagnostic

__all__ = ["find_republish_sites", "check_file"]

_VOCAB_RE = re.compile(r"(?:^|_)(resync|anchor|cursor)(?:_|$|s$)")
_INTAKE_NAMES = ("leaves", "Snapshot")
_DESYNC_NAMES = ("DeltaDesync",)


def _call_name(node: ast.Call) -> Optional[str]:
    f = node.func
    if isinstance(f, ast.Name):
        return f.id
    if isinstance(f, ast.Attribute):
        return f.attr
    return None


def _imports_relay(tree: ast.AST) -> bool:
    for node in ast.walk(tree):
        if isinstance(node, ast.Import):
            if any("bluefog_tpu.relay" in (a.name or "")
                   for a in node.names):
                return True
        elif isinstance(node, ast.ImportFrom):
            mod = node.module or ""
            if "relay" in mod and "bluefog_tpu" in mod:
                return True
            if mod == "bluefog_tpu" and any(
                    a.name == "relay" for a in node.names):
                return True
    return False


def _idents(fn: ast.AST):
    for sub in ast.walk(fn):
        if isinstance(sub, ast.Attribute):
            yield sub.attr
        elif isinstance(sub, ast.Name):
            yield sub.id


def _mentions_vocab(fn: ast.AST) -> bool:
    for ident in _idents(fn):
        if _VOCAB_RE.search(ident.lower()):
            return True
    for sub in ast.walk(fn):
        if isinstance(sub, ast.ExceptHandler) and sub.type is not None:
            for t in ast.walk(sub.type):
                if isinstance(t, (ast.Name, ast.Attribute)):
                    nm = t.id if isinstance(t, ast.Name) else t.attr
                    if nm in _DESYNC_NAMES:
                        return True
    return False


def _scan_function(fn: ast.AST, name: str, filename: str
                   ) -> List[Diagnostic]:
    sites = []
    for sub in ast.walk(fn):
        if (isinstance(sub, ast.Call)
                and isinstance(sub.func, ast.Attribute)
                and sub.func.attr == "publish"):
            sites.append(sub)
    if not sites:
        return []
    intake = any(i in _INTAKE_NAMES for i in _idents(fn))
    if not intake:
        return []  # a plain publisher, not a forwarding hop
    if _mentions_vocab(fn):
        return []
    line = min(c.lineno for c in sites)
    return [Diagnostic(
        "error", "BF-RLY001",
        f"{name} (at {filename}:{line}) re-publishes a received "
        "snapshot without resync-anchor/cursor-gap vocabulary — guard "
        "the publish against the hop's cursor (drop replayed rounds "
        "so children stay strictly increasing), or handle DeltaDesync "
        "and resync through a full-frame anchor; a guard-free "
        "forwarding hop propagates upstream replays and diverged "
        "deltas to every tier below it",
        pass_name="relay-lint", subject=name)]


def find_republish_sites(source: str, *, filename: str = "<source>",
                          relay_module: Optional[bool] = None
                          ) -> List[Diagnostic]:
    """Lint one Python source blob for guard-free re-publish hops."""
    try:
        tree = ast.parse(source, filename=filename)
    except SyntaxError as e:
        return [Diagnostic(
            "warning", "BF-RLY003",
            f"could not parse {filename}: {e}",
            pass_name="relay-lint", subject=filename)]
    in_scope = relay_module if relay_module is not None else (
        _imports_relay(tree)
        or os.sep + "relay" + os.sep in os.path.abspath(filename))
    if not in_scope:
        return []
    short = os.path.basename(filename)
    diags: List[Diagnostic] = []
    for node in ast.walk(tree):
        if isinstance(node, (ast.FunctionDef, ast.AsyncFunctionDef)):
            diags.extend(_scan_function(node, node.name, short))
    return diags


def check_file(path: str) -> List[Diagnostic]:
    try:
        with open(path, "r", encoding="utf-8") as f:
            src = f.read()
    except OSError as e:
        return [Diagnostic(
            "warning", "BF-RLY003", f"could not read {path}: {e}",
            pass_name="relay-lint", subject=os.path.basename(path))]
    return find_republish_sites(src, filename=path)
