"""Jaxpr comm-lint: walk a traced step function for communication hazards.

``jax.make_jaxpr`` gives the full closed program a step function will run
— including everything inside ``shard_map``, ``scan``, ``cond`` and
``switch`` bodies — *before* anything executes on a device.  This pass
walks that jaxpr and verifies the properties whose violation shows up at
scale as a hung barrier rather than a stack trace:

- **Permutation sanity** (``ppermute`` / collective_permute): every
  source and every destination in a ``perm`` must be distinct, and all
  ranks in range.  XLA's CollectivePermute with a duplicate destination
  is undefined (double-delivery) and a duplicate source drops a payload;
  on a real mesh either manifests as a deadlock or silent corruption.
  JAX does NOT validate this at trace time (verified: a duplicate
  destination traces cleanly), so the lint is the only pre-run check.
- **Axis-name hygiene**: a collective naming an axis the surrounding
  program never binds is either a typo'd gossip axis or a
  mesh-mismatch — flagged against the set of axes in scope (outer
  ``axis_sizes`` plus every enclosing ``shard_map``'s mesh axes).
- **Host callbacks** inside the step (``io_callback`` /
  ``pure_callback`` / ``debug_callback``): each one forces a device ->
  host sync per step — fine for a debug run, a throughput cliff in
  production.  Warning.
- **Buffer donation** (:func:`check_donation`): a train step that
  returns new optimizer state without donating the old one keeps two
  copies of every buffer live across the update — at production model
  sizes that is the difference between fitting in HBM and not.  Checked
  on the lowered StableHLO (``tf.aliasing_output`` attributes), which is
  what the runtime actually honors.
"""

from __future__ import annotations

from typing import Any, Dict, Iterable, List, Optional, Sequence, Tuple

from bluefog_tpu.analysis.report import Diagnostic

__all__ = [
    "check_permutation",
    "lint_jaxpr",
    "lint_step_fn",
    "check_donation",
]

# collectives whose params name mesh axes, and the param key that holds them
_AXIS_PARAM_KEYS = ("axis_name", "axes", "axis_index_groups")

_CALLBACK_PRIMS = ("io_callback", "pure_callback", "debug_callback",
                   "outside_call", "host_callback")


def check_permutation(
    perm: Sequence[Tuple[int, int]],
    axis_size: Optional[int],
    *,
    name: str = "ppermute",
) -> List[Diagnostic]:
    """Partial-permutation check for one ``perm``: distinct sources,
    distinct destinations, ranks within ``axis_size`` (skipped when the
    size is unknown).  This is the deadlock-freedom condition for a
    ``collective_permute``."""
    diags: List[Diagnostic] = []
    srcs = [s for s, _ in perm]
    dsts = [d for _, d in perm]
    dup_src = sorted({s for s in srcs if srcs.count(s) > 1})
    dup_dst = sorted({d for d in dsts if dsts.count(d) > 1})
    if dup_src:
        diags.append(Diagnostic(
            "error", "BF-COMM001",
            f"duplicate source rank(s) {dup_src[:4]} in perm: each source "
            "may feed at most one destination per collective_permute "
            "(duplicates drop payloads / deadlock the handshake)",
            pass_name="comm-lint", subject=name))
    if dup_dst:
        diags.append(Diagnostic(
            "error", "BF-COMM001",
            f"duplicate destination rank(s) {dup_dst[:4]} in perm: each "
            "destination may receive at most one payload per "
            "collective_permute (double-delivery is undefined)",
            pass_name="comm-lint", subject=name))
    if axis_size is not None:
        bad = [(s, d) for (s, d) in perm
               if not (0 <= s < axis_size and 0 <= d < axis_size)]
        if bad:
            diags.append(Diagnostic(
                "error", "BF-COMM003",
                f"rank pair(s) {bad[:4]} outside axis size {axis_size}",
                pass_name="comm-lint", subject=name))
    return diags


def _iter_axis_names(params: Dict[str, Any]) -> Iterable[str]:
    for key in ("axis_name", "axes"):
        v = params.get(key)
        if v is None:
            continue
        if isinstance(v, (tuple, list)):
            for a in v:
                if isinstance(a, str):
                    yield a
        elif isinstance(v, str):
            yield v


def _sub_jaxprs(value: Any):
    """Yield every (Closed)Jaxpr reachable from one eqn param value."""
    from jax.extend.core import ClosedJaxpr, Jaxpr

    if isinstance(value, ClosedJaxpr):
        yield value.jaxpr
    elif isinstance(value, Jaxpr):
        yield value
    elif isinstance(value, (tuple, list)):
        for v in value:
            yield from _sub_jaxprs(v)


def _walk(jaxpr, axis_sizes: Dict[str, int], name: str,
          diags: List[Diagnostic]) -> None:
    for eqn in jaxpr.eqns:
        prim = eqn.primitive.name
        params = dict(eqn.params)

        if prim == "ppermute":
            axes = list(_iter_axis_names(params))
            unknown = [a for a in axes if a not in axis_sizes]
            if unknown:
                diags.append(Diagnostic(
                    "error", "BF-COMM002",
                    f"ppermute names axis(es) {unknown} not bound by any "
                    f"enclosing mesh (in scope: {sorted(axis_sizes)})",
                    pass_name="comm-lint", subject=name))
            size: Optional[int] = None
            if axes and not unknown:
                size = 1
                for a in axes:
                    size *= axis_sizes[a]
            diags.extend(check_permutation(
                tuple(params.get("perm", ())), size,
                name=f"{name}:ppermute[{','.join(axes)}]"))
        elif any(k in params for k in _AXIS_PARAM_KEYS) and prim not in (
                "shard_map", "pjit", "xla_call", "xla_pmap"):
            # psum/psum2/pmax/all_gather/all_to_all/...: axis-name hygiene
            axes = list(_iter_axis_names(params))
            unknown = [a for a in axes if a not in axis_sizes]
            if unknown:
                diags.append(Diagnostic(
                    "error", "BF-COMM002",
                    f"{prim} names axis(es) {unknown} not bound by any "
                    f"enclosing mesh (in scope: {sorted(axis_sizes)})",
                    pass_name="comm-lint", subject=name))

        if any(cb in prim for cb in _CALLBACK_PRIMS):
            if params.get("ordered"):
                # the PR-1 abort class: an ordered io_callback threads an
                # effect token through the compiled program as an extra
                # entry parameter, and this environment's XLA sharding
                # propagation CHECK-fails on it (hard process abort, not
                # an exception) whenever the jitted step takes >= 2
                # arguments.  The timeline and metrics subsystems use
                # unordered callbacks with dataflow-enforced ordering for
                # exactly this reason — flag any reintroduction as an
                # error before it kills a job.
                diags.append(Diagnostic(
                    "error", "BF-COMM012",
                    f"ORDERED host callback ({prim}, ordered=True) inside "
                    "the step: the threaded effect token becomes an extra "
                    "entry parameter and XLA sharding propagation "
                    "CHECK-fails (process abort) on multi-argument jitted "
                    "steps — use ordered=False and enforce ordering by "
                    "dataflow (fold the callback result into the output), "
                    "as utils/timeline.device_stage, metrics.comm, and "
                    "blackbox.recorder.traced_event do",
                    pass_name="comm-lint", subject=name))
            else:
                diags.append(Diagnostic(
                    "warning", "BF-COMM010",
                    f"host callback ({prim}) inside the step: forces a "
                    "device->host sync every iteration; keep it off the "
                    "production hot path",
                    pass_name="comm-lint", subject=name))

        # descend: shard_map binds its mesh's axes, pmap binds its single
        # named axis — both are containers, not collectives
        inner_sizes = axis_sizes
        mesh = params.get("mesh")
        if prim == "shard_map" and mesh is not None:
            inner_sizes = dict(axis_sizes)
            try:
                inner_sizes.update(dict(mesh.shape))
            except Exception:
                pass
        elif prim == "xla_pmap":
            pmap_axis = params.get("axis_name")
            pmap_size = params.get("global_axis_size",
                                   params.get("axis_size"))
            if isinstance(pmap_axis, str) and isinstance(pmap_size, int):
                inner_sizes = dict(axis_sizes)
                inner_sizes[pmap_axis] = pmap_size
        for key, value in params.items():
            for sub in _sub_jaxprs(value):
                _walk(sub, inner_sizes, name, diags)


def lint_jaxpr(
    closed_jaxpr,
    *,
    axis_sizes: Optional[Dict[str, int]] = None,
    name: str = "step",
) -> List[Diagnostic]:
    """Lint an already-traced (closed) jaxpr.  ``axis_sizes`` seeds the
    axes in scope at top level (e.g. ``{'i': 8}`` for a function traced
    under ``pmap``/``shard_map`` externally); every ``shard_map``
    encountered during the walk adds its own mesh axes for its body."""
    diags: List[Diagnostic] = []
    jaxpr = getattr(closed_jaxpr, "jaxpr", closed_jaxpr)
    _walk(jaxpr, dict(axis_sizes or {}), name, diags)
    if not any(d.severity == "error" for d in diags):
        diags.append(Diagnostic(
            "info", "BF-COMM100",
            "communication program is permutation-safe (all ppermutes are "
            "partial permutations over bound axes)",
            pass_name="comm-lint", subject=name))
    return diags


def lint_step_fn(
    fn,
    *example_args,
    axis_sizes: Optional[Dict[str, int]] = None,
    name: Optional[str] = None,
    **example_kwargs,
) -> List[Diagnostic]:
    """Trace ``fn`` with ``jax.make_jaxpr`` and lint the result.

    ``fn`` must be traceable outside any mesh context — i.e. already
    wrapped in ``shard_map`` (the mesh travels inside the jaxpr) or free
    of collectives at top level.  Tracing failures are reported as a
    diagnostic, not raised: the lint CLI must survive one broken target
    and keep checking the rest.
    """
    import jax

    subject = name or getattr(fn, "__name__", repr(fn))
    try:
        closed = jax.make_jaxpr(fn)(*example_args, **example_kwargs)
    except Exception as e:  # noqa: BLE001 — any trace failure is a finding
        return [Diagnostic(
            "error", "BF-COMM020",
            f"tracing failed: {type(e).__name__}: {e}",
            pass_name="comm-lint", subject=subject)]
    return lint_jaxpr(closed, axis_sizes=axis_sizes, name=subject)


def check_donation(
    fn,
    *example_args,
    expect_donation: bool = True,
    name: Optional[str] = None,
    **example_kwargs,
) -> List[Diagnostic]:
    """Check buffer donation on a jitted function by lowering it and
    counting ``tf.aliasing_output`` input attributes in the StableHLO —
    the representation the runtime actually honors, so this cannot
    disagree with what executes.

    ``fn`` must expose ``.lower`` (i.e. be the result of ``jax.jit``).
    With ``expect_donation=True`` (a train step whose state should be
    donated), zero aliased inputs is a warning; otherwise the count is
    reported as info.
    """
    subject = name or getattr(fn, "__name__", repr(fn))
    lower = getattr(fn, "lower", None)
    if lower is None:
        return [Diagnostic(
            "error", "BF-COMM021",
            "check_donation needs a jitted function (jax.jit result with "
            f".lower); got {type(fn).__name__}",
            pass_name="comm-lint", subject=subject)]
    try:
        text = lower(*example_args, **example_kwargs).as_text()
    except Exception as e:  # noqa: BLE001
        return [Diagnostic(
            "error", "BF-COMM020",
            f"lowering failed: {type(e).__name__}: {e}",
            pass_name="comm-lint", subject=subject)]
    # donation shows up as a definite alias (tf.aliasing_output) when the
    # compiler could pair input and output at lowering, or as a donor mark
    # (jax.buffer_donor) when pairing is deferred to the runtime (the
    # usual form once shard_map/sharding is involved) — either satisfies
    # "the old state buffer is reusable"
    n_aliased = (text.count("tf.aliasing_output")
                 + text.count("jax.buffer_donor"))
    if n_aliased == 0 and expect_donation:
        return [Diagnostic(
            "warning", "BF-COMM011",
            "no input-output buffer aliasing in the lowered step: "
            "optimizer state is copied, not donated — pass "
            "donate_argnums for the state arguments or HBM holds two "
            "copies of every buffer across the update",
            pass_name="comm-lint", subject=subject)]
    return [Diagnostic(
        "info", "BF-COMM101",
        f"{n_aliased} input buffer(s) donated (aliased to outputs)",
        pass_name="comm-lint", subject=subject)]
