"""Compiled-program introspection: count collectives, estimate cost.

The reference answers "what did my training step actually communicate?" with
its timeline (``bluefog/common/timeline.cc``); under XLA the authoritative
record is the compiled HLO itself.  These helpers compile a function and
report its collective-op census — used by tests to *prove* properties like
"fusion reduced ~160 per-leaf ppermutes to one per schedule slot", and by
users to sanity-check what a sharded step will put on the ICI wire.
"""

from __future__ import annotations

import math
import re
from typing import Any, Dict, Mapping, Optional

import jax

__all__ = ["collective_census", "compiled_flops", "collective_overlap_report",
           "layout_copies", "parse_overlap_windows", "transfer_schedule"]

_COLLECTIVE_OPS = (
    "collective-permute",
    "all-reduce",
    "all-gather",
    "all-to-all",
    "reduce-scatter",
    "collective-broadcast",
)


def collective_census(fn, *args, static_argnums=(), **lower_kwargs) -> Dict[str, int]:
    """Compile ``fn(*args)`` (jit if it isn't already) and count collective
    ops in the optimized HLO.

    Returns ``{op_name: count}`` for every collective present (zero-count ops
    omitted).  Counts are of *instructions* in the post-optimization module,
    so combiner passes (e.g. XLA merging adjacent all-reduces) are reflected.
    """
    jitted = fn if hasattr(fn, "lower") else jax.jit(
        fn, static_argnums=static_argnums)
    hlo = jitted.lower(*args, **lower_kwargs).compile().as_text()
    census: Dict[str, int] = {}
    for op in _COLLECTIVE_OPS:
        # async forms appear as `-start`/`-done` pairs; sync forms as bare
        # `op(`.  One logical collective = one start or one bare op; a
        # module can legally mix both, so sum them (the bare regex cannot
        # match the `-start` lines).
        n = (len(re.findall(rf"\b{op}-start\(", hlo))
             + len(re.findall(rf"\b{op}\(", hlo)))
        if n:
            census[op] = n
    return census


def collective_overlap_report(fn, *args, **lower_kwargs) -> Dict[str, Any]:
    """Measure communication/compute overlap in the *compiled schedule*.

    The reference overlaps gossip with backprop via per-parameter hooks and a
    background thread (SURVEY.md §3.3 — "this overlap is the performance
    contract"); under XLA the analogous contract is that collectives lower to
    ``-start``/``-done`` pairs with real compute scheduled inside the window.
    This walks the post-optimization HLO in emission order and, for every
    async collective window, counts the compute instructions (fusions,
    convolutions, dots, custom-calls) placed between ``start`` and ``done`` —
    compiler-level proof that the transfer is in flight while the math runs.

    Returns ``{"pairs": n, "windows": [per-window compute counts],
    "mean_compute_in_flight": float, "overlapped_fraction": share of windows
    with >= 1 compute op inside}``.
    """
    jitted = fn if hasattr(fn, "lower") else jax.jit(fn)
    hlo = jitted.lower(*args, **lower_kwargs).compile().as_text()
    return parse_overlap_windows(hlo)


def parse_overlap_windows(hlo: str) -> Dict[str, Any]:
    """Parse a post-optimization HLO module's text (in schedule order) into
    the overlap report of :func:`collective_overlap_report`."""
    start_re = re.compile(
        r"^\s*%?(?P<name>[\w.\-]+)\s*=.*\b[\w\-]+-start\(")
    collective_done_re = re.compile(
        "(" + "|".join(re.escape(op) for op in _COLLECTIVE_OPS) + r")-done\(")
    compute_re = re.compile(r"\b(fusion|convolution|dot|custom-call)\(")
    open_windows: Dict[str, int] = {}
    windows = []
    for line in hlo.splitlines():
        m = start_re.match(line)
        if m and any(f"{op}-start(" in line for op in _COLLECTIVE_OPS):
            open_windows[m.group("name")] = 0
            continue
        # only dones of the tracked collective families close windows, and
        # only by exact operand-name match (%name followed by a delimiter —
        # a done for %start.12 must not also close %start.1); an unmatched
        # done closes nothing.
        if collective_done_re.search(line) and open_windows:
            closed = [n for n in open_windows
                      if re.search(rf"%{re.escape(n)}[),\s]", line)]
            for n in closed:
                windows.append(open_windows.pop(n))
            if closed:
                continue
        if open_windows and compute_re.search(line):
            for n in open_windows:
                open_windows[n] += 1
    pairs = len(windows)
    return {
        "pairs": pairs,
        "windows": windows,
        "mean_compute_in_flight": (sum(windows) / pairs) if pairs else 0.0,
        "overlapped_fraction": (sum(1 for w in windows if w > 0) / pairs)
        if pairs else 0.0,
    }


_ITEMSIZE = {"pred": 1, "s8": 1, "u8": 1, "bf16": 2, "f16": 2, "s16": 2,
             "u16": 2, "f32": 4, "s32": 4, "u32": 4, "f64": 8, "s64": 8,
             "u64": 8}


def _entry_lines(hlo: str):
    """The instruction lines of a module's ENTRY computation (its text is
    the schedule; the fused and nested computations around it are not)."""
    entry = hlo[hlo.index("ENTRY "):] if "ENTRY " in hlo else hlo
    lines = entry.splitlines()
    end = next((i for i, line in enumerate(lines) if line.rstrip() == "}"),
               len(lines))
    return lines[:end]


_NAME_RE = re.compile(r"^\s*(?:ROOT )?%?([\w.\-]+) = ")
_SHAPE_RE = re.compile(r"= \(?(\w+)\[([\d,]*)\]")


def _output_bytes(line: str) -> int:
    dtype, dims = _SHAPE_RE.search(line).groups()
    size = math.prod(int(d) for d in dims.split(",") if d)
    return size * _ITEMSIZE.get(dtype, 4)


def transfer_schedule(hlo: str,
                      marks: Optional[Mapping[str, str]] = None) -> Dict[str, Any]:
    """Where each asynchronous collective-permute opens and closes in the
    compiled program's own schedule, with its payload's bytes.

    Reads the ENTRY computation of a post-optimization module (its text is
    the schedule; the fused computations printed before it are not) and
    numbers the compute instructions (fusion, convolution, dot,
    custom-call) as it goes.  Returns ``{"compute_ops": n, "transfers":
    [(opened_at, closed_at, nbytes), ...] in closing order, "marks": {name:
    [positions]}}``: ``opened_at`` / ``closed_at`` are the number of compute
    instructions scheduled before the ``-start`` / ``-done``, and ``marks``
    gives the positions of the instructions whose line matches each regular
    expression of ``marks`` (the attention kernels, a layer's weight
    gradient), so a test or a session without a chip can say what a
    transfer runs beside.  PERF.md (PR 31) reads the four-rank step with it:
    XLA:TPU keeps about five collective-permutes in flight, opens the first
    five before the forward pass and every other one where an earlier one
    closes, next to the weight-gradient fusion that consumes it.
    """
    compute_re = re.compile(r"\b(fusion|convolution|dot|custom-call)\(")
    done_re = re.compile(r"collective-permute-done\(%?([\w.\-]+)\)")
    mark_res = {k: re.compile(v) for k, v in (marks or {}).items()}
    n, opened, transfers = 0, {}, []
    found: Dict[str, list] = {k: [] for k in mark_res}
    for line in _entry_lines(hlo):
        m = _NAME_RE.match(line)
        if not m:
            continue
        if compute_re.search(line):
            n += 1
        for k, r in mark_res.items():
            if r.search(line):
                found[k].append(n)
        if "collective-permute-start(" in line:
            opened[m.group(1)] = (n, _output_bytes(line))
            continue
        d = done_re.search(line)
        if d and d.group(1) in opened:
            at, nbytes = opened.pop(d.group(1))
            transfers.append((at, n, nbytes))
    return {"compute_ops": n, "transfers": transfers, "marks": found}


def layout_copies(hlo: str, largest: int = 8) -> Dict[str, Any]:
    """The stand-alone ``copy`` and ``transpose`` instructions of the
    compiled program's ENTRY computation: passes over a buffer that move
    every byte of it and compute nothing, which XLA schedules where a
    producer's layout is not its consumer's (a head axis made by reshaping a
    matmul's output, on the TPU's tiled layouts).  A copy folded into a
    fusion, or inside a nested computation (a ``while`` body), is not one.

    Returns ``{"count": n, "bytes": the bytes they write, "largest":
    [(nbytes, instruction, op_name), ...]}``, the ``largest`` few first.
    On the chip the same instructions are the ``copy`` line of a traced
    run's ``breakdown`` (PERF.md, PR 33)."""
    op_re = re.compile(r"[\]}] (?:copy|transpose)\(")
    op_name_re = re.compile(r'op_name="([^"]*)"')
    found = []
    for line in _entry_lines(hlo):
        m = _NAME_RE.match(line)
        if not m or not op_re.search(line):
            continue
        name = op_name_re.search(line)
        found.append((_output_bytes(line), m.group(1),
                      name.group(1) if name else ""))
    found.sort(key=lambda f: -f[0])
    return {"count": len(found), "bytes": sum(f[0] for f in found),
            "largest": found[:largest]}


def compiled_flops(fn, *args, **lower_kwargs) -> float:
    """XLA's FLOP estimate for the compiled ``fn(*args)`` (cost analysis)."""
    jitted = fn if hasattr(fn, "lower") else jax.jit(fn)
    cost = jitted.lower(*args, **lower_kwargs).compile().cost_analysis()
    if isinstance(cost, (list, tuple)):
        cost = cost[0]
    return float(cost.get("flops", 0.0))
