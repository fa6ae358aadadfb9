"""Lint CLI: run every static-analysis pass over the repo's own programs.

::

    python -m bluefog_tpu.analysis.lint [--size N] [--verbose] [--no-trace]

Exits nonzero iff any pass reports an error-severity diagnostic, so CI
(and the tier-1 suite, via ``tests/test_analysis.py``) fails fast when a
change breaks a communication invariant.

What it covers, deliberately the same surfaces the examples exercise:

1. **topology** — every built-in constructor (exp2, exp, symmetric-exp,
   ring x3 styles, grid, star, fully-connected) at the mesh size, plus
   the lowered :class:`GossipSchedule` of each.
2. **dynamic** — the one-peer exponential-2 and ring periods, the
   generator-materialized dynamic topologies, and the jittable aperiodic
   mixing matrices: per-phase stochasticity + period-union connectivity.
3. **collective-ids** — the window family's bucket arithmetic: a probe
   window's name-derived lease audited against its family's range.
4. **comm-lint** — traces gossip collectives and both distributed
   optimizers' update steps (``jax.make_jaxpr`` under ``shard_map``) and
   walks the jaxprs for permutation/axis/callback hazards; checks buffer
   donation on a jitted train step.
5. **examples** — scans ``examples/*.py`` for the topology constructors
   and dynamic schedules they reference and verifies each one it finds.

All passes run on CPU (the CLI forces an 8-virtual-device host mesh when
no accelerator is configured) — nothing here needs a TPU, which is the
point: the invariants are checked before the 128-chip job is submitted.
"""

from __future__ import annotations

import argparse
import os
import sys
from typing import List, Optional

from bluefog_tpu.analysis.report import Diagnostic, LintReport

__all__ = ["main", "run_all"]

_AXIS = "bf"


def _ensure_host_devices(n: int) -> None:
    """Force an ``n``-virtual-device CPU mesh unless the environment
    already configured a platform.  Must run before jax initializes a
    backend — callers go through :func:`main`/:func:`run_all`, which do."""
    os.environ.setdefault("JAX_PLATFORMS", "cpu")
    flags = os.environ.get("XLA_FLAGS", "")
    if "host_platform_device_count" not in flags:
        os.environ["XLA_FLAGS"] = (
            flags + f" --xla_force_host_platform_device_count={n}").strip()


# ---------------------------------------------------------------------------
# Passes
# ---------------------------------------------------------------------------


def _builtin_topologies(size: int):
    from bluefog_tpu import topology as T

    topos = [
        T.ExponentialTwoGraph(size),
        T.ExponentialGraph(size, base=2),
        T.SymmetricExponentialGraph(size, base=4),
        T.RingGraph(size, 0),
        T.RingGraph(size, 1),
        T.RingGraph(size, 2),
        T.MeshGrid2DGraph(size),
        T.StarGraph(size, center_rank=0),
        T.FullyConnectedGraph(size),
    ]
    return topos


def topology_pass(report: LintReport, size: int) -> None:
    from bluefog_tpu.analysis.topology_check import (check_schedule,
                                                     check_topology)
    from bluefog_tpu.topology import build_schedule

    for topo in _builtin_topologies(size):
        report.extend(check_topology(topo))
        report.extend(check_schedule(build_schedule(topo)))

    # elastic membership: every replan the runtime can produce while the
    # fleet grows/shrinks must itself verify (active-submatrix strong
    # connectivity — the B-connectivity-style guarantee that no member
    # pair is ever cut off — plus stochasticity and a nonzero gap).
    # Sweep the member-set sizes 1..size over a deterministic choice of
    # members (the same sorted-list mapping every rank uses).
    from bluefog_tpu import topology as T

    base = T.ExponentialTwoGraph(size)
    for m in range(1, size + 1):
        members = list(range(0, 2 * m, 2))[:m]  # spread, not a prefix
        members = [r % size for r in members][:m]
        if len(set(members)) < m:
            members = list(range(m))
        replanned = T.replan(base, members)
        report.extend(check_topology(
            replanned, name=f"replan[n={size},m={m}]"))
        # the control plane's penalized rebuilds: every plan the
        # controller can actuate (slow sets up to half the members,
        # every densify level) must itself verify — the ring spine's
        # strong-connectivity promise is a checked invariant, not a
        # comment
        for densify in (0, 1, 2):
            for n_slow in (1, max(1, m // 2)):
                slow = members[:n_slow]
                penalized = T.replan_penalized(
                    base, members, slow=slow, densify=densify)
                report.extend(check_topology(
                    penalized,
                    name=f"ctl[m={m},slow={n_slow},densify={densify}]"))


def dynamic_pass(report: LintReport, size: int) -> None:
    import numpy as np

    from bluefog_tpu.analysis.topology_check import check_dynamic_schedules
    from bluefog_tpu import topology as T

    report.extend(check_dynamic_schedules(
        T.one_peer_exponential_two_schedules(size), name="one_peer_exp2"))
    report.extend(check_dynamic_schedules(
        T.one_peer_ring_schedules(size), name="one_peer_ring"))

    base = T.ExponentialTwoGraph(size)
    period = max(1, base.max_in_degree)
    topos = T.dynamic_topologies_from_generator(
        size, lambda r: T.GetDynamicOnePeerSendRecvRanks(base, r),
        num_steps=period, name="one_peer_gen")
    report.extend(check_dynamic_schedules(topos, name="one_peer_gen"))

    # the jittable aperiodic form: one period of step -> W matrices
    import math

    phases = max(1, math.ceil(math.log2(size))) if size > 1 else 1
    mats = [np.asarray(T.one_peer_exp2_mixing_matrix(size, s))
            for s in range(phases)]
    report.extend(check_dynamic_schedules(mats, name="one_peer_exp2_matrix"))


def collective_id_pass(report: LintReport, size: int) -> None:
    from bluefog_tpu.analysis.registry import GLOBAL_LEASES
    from bluefog_tpu.ops import pallas_gossip

    # the one kernel family that takes collective ids is the window deliver
    # kernel: a window's name-derived bucket must stay in its family
    with GLOBAL_LEASES.scope() as reg:
        win_base = pallas_gossip.window_collective_id_base(
            "lint_winput_probe")
        pallas_gossip.release_window_collective_id("lint_winput_probe")
        reg.lease("window:winput_opt", base=win_base, used=4,
                  limit=win_base + pallas_gossip.WINDOW_LEAF_CAP,
                  family="windows")
        diags = reg.audit()
    report.extend(diags)
    if not any(d.severity == "error" for d in diags):
        report.add(Diagnostic(
            "info", "BF-ID100",
            "window bucket stays in its family",
            pass_name="collective-ids", subject="windows"))


def comm_lint_pass(report: LintReport, size: int) -> None:
    import jax
    import jax.numpy as jnp
    import numpy as np
    import optax
    from jax import lax
    from jax.sharding import Mesh, PartitionSpec as P

    from bluefog_tpu.analysis.jaxpr_lint import check_donation, lint_step_fn
    from bluefog_tpu.ops import collectives as C
    from bluefog_tpu.optim import (DistributedGradientTrackingOptimizer,
                                   DistributedNeighborAllreduceOptimizer)
    from bluefog_tpu.parallel.api import shard_map
    from bluefog_tpu import topology as T

    n_dev = len(jax.devices())
    if n_dev < size:
        # A backend initialized before _ensure_host_devices ran (jax was
        # imported and used earlier in this process) ignores the virtual-
        # device request; tracing a size-N schedule over a smaller mesh
        # would report false out-of-range errors (BF-COMM003), so skip
        # with a visible reason instead.
        report.add(Diagnostic(
            "warning", "BF-COMM030",
            f"comm-lint trace pass skipped: jax exposes {n_dev} device(s) "
            f"but the lint mesh needs {size}; run in a fresh process or "
            "pre-set XLA_FLAGS=--xla_force_host_platform_device_count="
            f"{size} before jax initializes",
            pass_name="comm-lint", subject="environment"))
        return

    mesh = Mesh(np.array(jax.devices()[:size]), (_AXIS,))
    x = jnp.zeros((size, 4), jnp.float32)

    def smap(body, n_in=1):
        return shard_map(body, mesh=mesh,
                         in_specs=(P(_AXIS),) * n_in,
                         out_specs=P(_AXIS), check_vma=False)

    # 1) plain gossip over a circulant, an irregular, and a dynamic graph
    gossip_targets = [
        ("neighbor_allreduce[exp2]",
         T.ExponentialTwoGraph(size), None),
        ("neighbor_allreduce[star]",
         T.StarGraph(size, center_rank=0), None),
    ]
    for name, topo, _ in gossip_targets:
        sched = T.build_schedule(topo)
        report.extend(lint_step_fn(
            smap(lambda v, s=sched: C.neighbor_allreduce(v, s, _AXIS)),
            x, name=name))

    dyn = [T.build_schedule(t)
           for t in T.one_peer_exponential_two_schedules(size)]
    report.extend(lint_step_fn(
        smap(lambda v: C.neighbor_allreduce_dynamic(v, dyn, 3, _AXIS)),
        x, name="neighbor_allreduce_dynamic[one_peer_exp2]"))

    # 1b) the blackbox flight recorder's jitted-path hooks: trace one
    # gossip step with BLUEFOG_TPU_BLACKBOX=jit so the recorder's
    # io_callbacks go through the same BF-COMM012 ordered-callback gate
    # as the timeline/metrics hooks (an ordered one is a process abort
    # on this XLA; the hooks must always be unordered + dataflow-folded)
    prev_mode = os.environ.get("BLUEFOG_TPU_BLACKBOX")
    os.environ["BLUEFOG_TPU_BLACKBOX"] = "jit"
    try:
        bb_sched = T.build_schedule(T.ExponentialTwoGraph(size))
        report.extend(lint_step_fn(
            smap(lambda v: C.neighbor_allreduce(v, bb_sched, _AXIS)),
            x, name="neighbor_allreduce[blackbox=jit]"))
    finally:
        if prev_mode is None:
            os.environ.pop("BLUEFOG_TPU_BLACKBOX", None)
        else:
            os.environ["BLUEFOG_TPU_BLACKBOX"] = prev_mode

    # 2) both distributed optimizers' jitted update step
    def optimizer_body(opt):
        def body(c):
            w0 = jnp.zeros_like(c)
            st = opt.init(w0)

            def step(carry, _):
                w, s = carry
                upd, s = opt.update(w - c, s, w)
                return (optax.apply_updates(w, upd), s), None

            (w, _), _ = lax.scan(step, (w0, st), None, length=2)
            return w

        return body

    dsgd = DistributedNeighborAllreduceOptimizer(
        optax.sgd(0.05), topology=T.ExponentialTwoGraph(size),
        axis_name=_AXIS)
    gt = DistributedGradientTrackingOptimizer(
        optax.sgd(0.05), T.MeshGrid2DGraph(size), _AXIS)
    report.extend(lint_step_fn(
        smap(optimizer_body(dsgd)), x,
        name="DistributedNeighborAllreduceOptimizer.update"))
    report.extend(lint_step_fn(
        smap(optimizer_body(gt)), x,
        name="DistributedGradientTrackingOptimizer.update"))

    # 3) buffer donation on the jitted hot path: the gossip train step
    # donates its parameter buffer, and the lowered StableHLO must show
    # the aliasing (this is the check that flags un-donated state)
    sched = T.build_schedule(T.ExponentialTwoGraph(size))

    def train_step(w, g):
        w = smap(lambda v, s=sched: C.neighbor_allreduce(v, s, _AXIS))(w)
        return w - 0.05 * g

    report.extend(check_donation(
        jax.jit(train_step, donate_argnums=(0,)), x, x,
        name="gossip_train_step"))


def window_pass(report: LintReport, size: int) -> None:
    """BF-WIN source lint over the surfaces that issue pipelined window
    deposits: the async runtime itself plus every example/benchmark that
    could copy its loop shape.  A dsgd/gossip loop that fires
    ``deposit_async`` and reaches its audit barrier without a ``flush()``
    fence is an error (see :mod:`bluefog_tpu.analysis.window_lint`)."""
    import glob

    from bluefog_tpu.analysis.window_lint import check_file

    root = os.path.dirname(os.path.dirname(os.path.dirname(
        os.path.abspath(__file__))))
    # NOT window_server.py itself: the transport's own delegation
    # wrappers (PipelinedRemoteWindow.deposit_async forwarding to its
    # stream) can never contain a fence by construction — the lint is
    # for USERS of the pipelined API
    targets = [
        os.path.join(root, "bluefog_tpu", "runtime", "async_windows.py"),
    ]
    targets += sorted(glob.glob(os.path.join(root, "examples", "*.py")))
    targets += sorted(glob.glob(os.path.join(root, "benchmarks", "*.py")))
    n = 0
    for path in targets:
        if not os.path.exists(path):
            continue
        n += 1
        report.extend(check_file(path))
    report.add(Diagnostic(
        "info", "BF-WIN100",
        f"window-lint scanned {n} file(s) for unfenced pipelined deposits",
        pass_name="window-lint", subject="runtime"))


def resilience_pass(report: LintReport, size: int) -> None:
    """BF-RES source lint over the surfaces that open or retry network
    connections: the runtime transports, the supervisor, and every
    example/benchmark that could copy their loop shapes.  An unbounded
    reconnect loop (no retry budget or deadline) is an error — see
    :mod:`bluefog_tpu.analysis.resilience_lint`."""
    import glob

    from bluefog_tpu.analysis.resilience_lint import check_file

    root = os.path.dirname(os.path.dirname(os.path.dirname(
        os.path.abspath(__file__))))
    targets = sorted(glob.glob(os.path.join(
        root, "bluefog_tpu", "runtime", "*.py")))
    # the serving tier's readers carry their own reconnect loops — the
    # same bounded-retry discipline applies to the read path, and to
    # the relay tree's uplink re-parent loop
    targets += sorted(glob.glob(os.path.join(
        root, "bluefog_tpu", "serving", "*.py")))
    targets += sorted(glob.glob(os.path.join(
        root, "bluefog_tpu", "relay", "*.py")))
    targets.append(os.path.join(root, "bluefog_tpu", "utils", "failure.py"))
    targets += sorted(glob.glob(os.path.join(root, "examples", "*.py")))
    targets += sorted(glob.glob(os.path.join(root, "benchmarks", "*.py")))
    n = 0
    for path in targets:
        if not os.path.exists(path):
            continue
        n += 1
        report.extend(check_file(path))
    report.add(Diagnostic(
        "info", "BF-RES100",
        f"resilience-lint scanned {n} file(s) for unbounded "
        "reconnect/retry loops",
        pass_name="resilience-lint", subject="runtime"))


def tracing_pass(report: LintReport, size: int) -> None:
    """BF-TRC source lint over every span-begin surface: the whole
    package (minus ``bluefog_tpu/tracing/`` — the primitive itself)
    plus examples and benchmarks.  An explicit ``begin_span`` without a
    finally-guaranteed ``finish`` or a reasoned ``# bftrace:
    cross-thread`` waiver is an error — a wedged peer must show an OPEN
    span, never a leaked one that reports a completed phase as stuck.
    See :mod:`bluefog_tpu.analysis.tracing_lint`."""
    import glob

    from bluefog_tpu.analysis.tracing_lint import check_file

    root = os.path.dirname(os.path.dirname(os.path.dirname(
        os.path.abspath(__file__))))
    targets = sorted(glob.glob(os.path.join(
        root, "bluefog_tpu", "**", "*.py"), recursive=True))
    targets = [p for p in targets
               if os.sep + "tracing" + os.sep not in p]
    targets += sorted(glob.glob(os.path.join(root, "examples", "*.py")))
    targets += sorted(glob.glob(os.path.join(root, "benchmarks", "*.py")))
    n = 0
    for path in targets:
        if not os.path.exists(path):
            continue
        n += 1
        report.extend(check_file(path))
    report.add(Diagnostic(
        "info", "BF-TRC100",
        f"tracing-lint scanned {n} file(s) for finish-unguaranteed "
        "span begins",
        pass_name="tracing-lint", subject="tracing"))


def control_pass(report: LintReport, size: int) -> None:
    """BF-CTL source lint over the surfaces that actuate communication
    plans: the control plane itself, the runtime loops it is wired
    into, and every example/benchmark that could copy the shape.  A
    controller actuation outside a round-boundary/quiesce context is an
    error — see :mod:`bluefog_tpu.analysis.control_lint`."""
    import glob

    from bluefog_tpu.analysis.control_lint import check_file

    root = os.path.dirname(os.path.dirname(os.path.dirname(
        os.path.abspath(__file__))))
    targets = sorted(glob.glob(os.path.join(
        root, "bluefog_tpu", "control", "*.py")))
    targets += sorted(glob.glob(os.path.join(
        root, "bluefog_tpu", "runtime", "*.py")))
    # the fleet simulator actuates real CommPlans at its epoch barrier
    # — same round-boundary discipline, same lint; the relay tree
    # actuates TreePlans through RelayNode.apply_plan under the same
    # rule
    targets += sorted(glob.glob(os.path.join(
        root, "bluefog_tpu", "sim", "*.py")))
    targets += sorted(glob.glob(os.path.join(
        root, "bluefog_tpu", "relay", "*.py")))
    targets += sorted(glob.glob(os.path.join(root, "examples", "*.py")))
    targets += sorted(glob.glob(os.path.join(root, "benchmarks", "*.py")))
    n = 0
    for path in targets:
        if not os.path.exists(path):
            continue
        n += 1
        report.extend(check_file(path))
    report.add(Diagnostic(
        "info", "BF-CTL100",
        f"control-lint scanned {n} file(s) for mid-round plan actuation",
        pass_name="control-lint", subject="control"))


def fleet_pass(report: LintReport, size: int) -> None:
    """BF-FLT source lint over the surfaces that declare alert/SLO
    thresholds: the fleet plane itself, the runtime loops it wires
    into, and every example/benchmark that could copy the shape.  A
    threshold without its hysteresis twin or a declared window is an
    error — see :mod:`bluefog_tpu.analysis.fleet_lint`."""
    import glob

    from bluefog_tpu.analysis.fleet_lint import check_file

    root = os.path.dirname(os.path.dirname(os.path.dirname(
        os.path.abspath(__file__))))
    targets = sorted(glob.glob(os.path.join(
        root, "bluefog_tpu", "fleet", "*.py")))
    targets += sorted(glob.glob(os.path.join(
        root, "bluefog_tpu", "runtime", "*.py")))
    # the simulator's scenario layer constructs SLO specs too
    targets += sorted(glob.glob(os.path.join(
        root, "bluefog_tpu", "sim", "*.py")))
    targets += sorted(glob.glob(os.path.join(root, "examples", "*.py")))
    targets += sorted(glob.glob(os.path.join(root, "benchmarks", "*.py")))
    n = 0
    for path in targets:
        if not os.path.exists(path):
            continue
        n += 1
        report.extend(check_file(path))
    report.add(Diagnostic(
        "info", "BF-FLT100",
        f"fleet-lint scanned {n} file(s) for unpaired alert/SLO "
        "thresholds",
        pass_name="fleet-lint", subject="fleet"))


def sim_pass(report: LintReport, size: int) -> None:
    """Pass 12 — BF-SIM: the fleet simulator's determinism contract
    (no wall clock / no ambient RNG inside ``bluefog_tpu/sim/``) and
    the scenario-table discipline (every ``Scenario(...)`` call site
    declares ``accept=`` predicates and a bounded ``horizon_s=``) —
    see :mod:`bluefog_tpu.analysis.sim_lint` and docs/sim.md."""
    import glob

    from bluefog_tpu.analysis.sim_lint import check_file

    root = os.path.dirname(os.path.dirname(os.path.dirname(
        os.path.abspath(__file__))))
    targets = sorted(glob.glob(os.path.join(
        root, "bluefog_tpu", "sim", "*.py")))
    # scenario tables can also live in examples/benchmarks — the
    # accept/horizon rule follows the constructor there too (tests are
    # deliberately NOT swept: they construct invalid scenarios inside
    # pytest.raises on purpose; Scenario.__post_init__ still guards
    # any table a test builds for real)
    targets += sorted(glob.glob(os.path.join(root, "examples", "*.py")))
    targets += sorted(glob.glob(os.path.join(root, "benchmarks", "*.py")))
    n = 0
    for path in targets:
        if not os.path.exists(path):
            continue
        n += 1
        report.extend(check_file(path))
    report.add(Diagnostic(
        "info", "BF-SIM100",
        f"sim-lint scanned {n} file(s) for wall-clock/ambient-RNG "
        "calls and unchecked scenario entries",
        pass_name="sim-lint", subject="sim"))


def concurrency_pass(report: LintReport, size: int) -> None:
    """Pass 8 — BF-CONC: the whole-package concurrency model.  Builds
    the lock-order graph over every lock in ``bluefog_tpu/`` (cycle
    detection), the hold-and-block audit (indefinite blocking calls
    under locks that signal handlers / watchdogs / daemon threads also
    take), the thread-shared-state audit, and the condvar-predicate
    check — see :mod:`bluefog_tpu.analysis.concurrency_lint` and the
    ``bfverify-tpu`` CLI for the graph itself."""
    from bluefog_tpu.analysis.concurrency_lint import check_package

    _, diags = check_package()
    report.extend(diags)


def sharding_pass(report: LintReport, size: int) -> None:
    """Pass 9 — BF-SHD: the unified rule table vs the three leaf
    families it governs.  Coverage (BF-SHD001) of the repo's default
    tables over their reference trees, window-declaration agreement
    (BF-SHD002), and the zero-gather-on-the-hot-path invariant of the
    sharded gossip step (BF-SHD003, by jaxpr inspection) — see
    :mod:`bluefog_tpu.analysis.sharding_lint`."""
    import jax
    import jax.numpy as jnp
    import numpy as np
    import optax
    from jax.sharding import Mesh, PartitionSpec as P

    from bluefog_tpu.analysis.sharding_lint import (check_rule_coverage,
                                                    check_shard_local,
                                                    check_window_partition)
    from bluefog_tpu.models.moe import moe_param_rules
    from bluefog_tpu.ops import collectives as C
    from bluefog_tpu.ops.windows import win_create
    from bluefog_tpu.optim.optimizers import optimizer_state_specs
    from bluefog_tpu.parallel.api import shard_map
    from bluefog_tpu.parallel.tensor import tp_param_rules
    from bluefog_tpu import topology as T

    # a TP-transformer-shaped reference tree (the naming tp_param_rules
    # is written against) — shapes small, coverage is about NAMES
    params = {
        "tok": {"embedding": jnp.zeros((32, 8))},
        "block_0": {
            "qkv_kernel": jnp.zeros((8, 3, 4)),
            "qkv_bias": jnp.zeros((3, 4)),
            "proj": {"kernel": jnp.zeros((4, 8)), "bias": jnp.zeros((8,))},
            "up": {"kernel": jnp.zeros((8, 16)), "bias": jnp.zeros((16,))},
            "down": {"kernel": jnp.zeros((16, 8)), "bias": jnp.zeros((8,))},
            "ln1": {"scale": jnp.zeros((8,)), "bias": jnp.zeros((8,))},
        },
        "ln_f": {"scale": jnp.zeros((8,)), "bias": jnp.zeros((8,))},
        "lm_head": {"kernel": jnp.zeros((8, 32))},
    }
    table = tp_param_rules()
    report.extend(check_rule_coverage(table, params, name="tp_param_rules"))

    moe_tree = {"block_0": {"moe": {"router": jnp.zeros((8, 4)),
                                    "wi": jnp.zeros((4, 8, 16)),
                                    "wo": jnp.zeros((4, 16, 8))},
                            "ln1": {"scale": jnp.zeros((8,))}}}
    report.extend(check_rule_coverage(moe_param_rules(), moe_tree,
                                      name="moe_param_rules"))

    # the state-tree derivation must cover a real optimizer's state
    try:
        optimizer_state_specs(table, params, optax.adam(1e-3))
    except Exception as e:  # noqa: BLE001
        report.add(Diagnostic(
            "error", "BF-SHD001",
            f"optimizer-state spec derivation failed over tp_param_rules: "
            f"{type(e).__name__}: {e}",
            pass_name="sharding", subject="opt_state"))

    # window declared through the table must agree with the table
    sched = T.build_schedule(T.ExponentialTwoGraph(size))
    win = win_create(params, sched, _AXIS, name="lint_shd_probe",
                     rule_table=table)
    report.extend(check_window_partition(win, table))

    # the zero-gather acceptance invariant, on the traced program
    n_dev = len(jax.devices())
    if n_dev < size:
        report.add(Diagnostic(
            "warning", "BF-SHD030",
            f"sharding trace check skipped: jax exposes {n_dev} "
            f"device(s), lint mesh needs {size}",
            pass_name="sharding", subject="environment"))
        return
    mesh = Mesh(np.array(jax.devices()[:size]), (_AXIS,))
    inner = {"fsdp": 2, "tp": 2}
    specs = table.resolve_tree(params)

    def gossip_step(x):
        return C.sharded_neighbor_allreduce(
            x, sched, _AXIS, specs=specs, inner_axes=inner)

    in_spec = jax.tree_util.tree_map(lambda _: P(), params)
    step = shard_map(gossip_step, mesh=mesh,
                     in_specs=(in_spec,), out_specs=in_spec,
                     check_vma=False)
    report.extend(check_shard_local(
        step, params, inner_axes=inner,
        name="sharded_neighbor_allreduce[exp2]"))
    report.add(Diagnostic(
        "info", "BF-SHD100",
        "rule-table coverage, window declaration, and shard-local trace "
        "checked over the tp/moe default tables",
        pass_name="sharding", subject="sharding"))


def protocol_pass(report: LintReport, size: int) -> None:
    """Pass 13 — BF-WIRE: the static wire-protocol verifier.  Extracts
    the encode/decode model over the whole protocol surface (struct
    layouts cross-checked per op, status-code registry discipline,
    feature-bit gates, claimed-length allocation bounds) and runs the
    exhaustive connection-state model checker over the three stream
    machines — see :mod:`bluefog_tpu.analysis.protocol_check` and the
    ``bfwire-tpu`` CLI for the model and state graphs."""
    from bluefog_tpu.analysis.protocol_check import check_package

    _, diags = check_package()
    report.extend(diags)


def doc_pass(report: LintReport, size: int) -> None:
    """BF-DOC: docs/transport.md must list every wire v2 status code in
    the one registry (:mod:`bluefog_tpu.runtime.wire_status`) and every
    HELLO feature bit with its live ``FEATURE_*`` value,
    docs/metrics.md must agree with the live ``bf_*`` metric names,
    and docs/API.md must agree with the installed ``[project.scripts]``
    CLI entry points — all pinned both directions."""
    from bluefog_tpu.analysis.doc_lint import (check_cli_doc,
                                               check_feature_doc,
                                               check_metrics_doc,
                                               check_transport_doc)

    report.extend(check_transport_doc())
    report.extend(check_feature_doc())
    report.extend(check_metrics_doc())
    report.extend(check_cli_doc())


def profiling_pass(report: LintReport, size: int) -> None:
    """BF-PROF source lint over the continuous profiler: the sampling
    hot path (every function reachable from a ``sys._current_frames``
    caller through intra-module calls) must never acquire a lock, do
    IO, serialize, sleep, or touch metrics — the sampler observes
    threads that may hold ANY package lock, so one acquire there is a
    latent process-wide deadlock — and every deque the sampler feeds
    must be bounded.  See :mod:`bluefog_tpu.analysis.profiling_lint`."""
    import glob

    from bluefog_tpu.analysis.profiling_lint import check_file

    root = os.path.dirname(os.path.dirname(os.path.dirname(
        os.path.abspath(__file__))))
    targets = sorted(glob.glob(os.path.join(
        root, "bluefog_tpu", "profiling", "*.py")))
    n = 0
    for path in targets:
        if not os.path.exists(path):
            continue
        n += 1
        report.extend(check_file(path))
    report.add(Diagnostic(
        "info", "BF-PROF101",
        f"profiling-lint scanned {n} file(s) for hot-path lock/IO "
        "violations and unbounded rings",
        pass_name="profiling-lint", subject="profiling"))


def serving_pass(report: LintReport, size: int) -> None:
    """BF-SRV source lint over the surfaces that consume round-stamped
    snapshots: the serving tier itself plus every example/benchmark that
    could copy its read shape.  Consuming a snapshot without checking
    its round stamp / retriable status is an error — see
    :mod:`bluefog_tpu.analysis.serving_lint`."""
    import glob

    from bluefog_tpu.analysis.serving_lint import check_file

    root = os.path.dirname(os.path.dirname(os.path.dirname(
        os.path.abspath(__file__))))
    targets = sorted(glob.glob(os.path.join(
        root, "bluefog_tpu", "serving", "*.py")))
    targets += sorted(glob.glob(os.path.join(root, "examples", "*.py")))
    targets += sorted(glob.glob(os.path.join(root, "benchmarks", "*.py")))
    n = 0
    for path in targets:
        if not os.path.exists(path):
            continue
        n += 1
        report.extend(check_file(path))
    report.add(Diagnostic(
        "info", "BF-SRV100",
        f"serving-lint scanned {n} file(s) for round-stamp-blind "
        "snapshot consumers",
        pass_name="serving-lint", subject="serving"))


def relay_pass(report: LintReport, size: int) -> None:
    """BF-RLY source lint over the surfaces that re-publish received
    snapshots: the relay tree itself plus every example/benchmark that
    could copy its forwarding shape.  A re-publish hop without
    resync-anchor/cursor-gap vocabulary is an error — the
    delta-divergence twin of BF-SRV001; see
    :mod:`bluefog_tpu.analysis.relay_lint`."""
    import glob

    from bluefog_tpu.analysis.relay_lint import check_file

    root = os.path.dirname(os.path.dirname(os.path.dirname(
        os.path.abspath(__file__))))
    targets = sorted(glob.glob(os.path.join(
        root, "bluefog_tpu", "relay", "*.py")))
    targets += sorted(glob.glob(os.path.join(root, "examples", "*.py")))
    targets += sorted(glob.glob(os.path.join(root, "benchmarks", "*.py")))
    n = 0
    for path in targets:
        if not os.path.exists(path):
            continue
        n += 1
        report.extend(check_file(path))
    report.add(Diagnostic(
        "info", "BF-RLY100",
        f"relay-lint scanned {n} file(s) for guard-free snapshot "
        "re-publish hops",
        pass_name="relay-lint", subject="relay"))


_EXAMPLE_CONSTRUCTORS = (
    "ExponentialTwoGraph",
    "ExponentialGraph",
    "SymmetricExponentialGraph",
    "RingGraph",
    "MeshGrid2DGraph",
    "StarGraph",
    "FullyConnectedGraph",
)
_EXAMPLE_DYNAMIC = (
    "one_peer_exponential_two_schedules",
    "one_peer_ring_schedules",
    "one_peer_exp2_mixing_matrix",
)


def examples_pass(report: LintReport, size: int,
                  examples_dir: Optional[str] = None) -> None:
    """Scan the repo's examples for the topologies they construct and
    verify each referenced constructor/schedule at the lint mesh size —
    so a constructor regression fails the lint exactly when an example
    would train on a broken graph."""
    import glob

    from bluefog_tpu.analysis.topology_check import (
        check_dynamic_schedules, check_topology)
    from bluefog_tpu import topology as T

    if examples_dir is None:
        examples_dir = os.path.join(
            os.path.dirname(os.path.dirname(os.path.dirname(
                os.path.abspath(__file__)))), "examples")
    files = sorted(glob.glob(os.path.join(examples_dir, "*.py")))
    if not files:
        report.add(Diagnostic(
            "warning", "BF-EX001",
            f"no examples found under {examples_dir}",
            pass_name="examples", subject="examples"))
        return

    used_ctors, used_dyn, n_files = set(), set(), 0
    for path in files:
        try:
            with open(path, "r", encoding="utf-8") as f:
                src = f.read()
        except OSError:
            continue
        n_files += 1
        used_ctors.update(c for c in _EXAMPLE_CONSTRUCTORS if c in src)
        used_dyn.update(d for d in _EXAMPLE_DYNAMIC if d in src)

    for ctor in sorted(used_ctors):
        topo = getattr(T, ctor)(size)
        report.extend(check_topology(topo, name=f"examples/{ctor}"))
    if "one_peer_exponential_two_schedules" in used_dyn:
        report.extend(check_dynamic_schedules(
            T.one_peer_exponential_two_schedules(size),
            name="examples/one_peer_exp2"))
    if "one_peer_ring_schedules" in used_dyn:
        report.extend(check_dynamic_schedules(
            T.one_peer_ring_schedules(size), name="examples/one_peer_ring"))
    report.add(Diagnostic(
        "info", "BF-EX100",
        f"scanned {n_files} example(s); verified constructors "
        f"{sorted(used_ctors)} and schedules {sorted(used_dyn)}",
        pass_name="examples", subject="examples"))


# ---------------------------------------------------------------------------
# Driver
# ---------------------------------------------------------------------------


def run_all(*, size: int = 8, trace: bool = True) -> LintReport:
    """Run every pass; importable entry point for tests."""
    _ensure_host_devices(size)
    report = LintReport()
    topology_pass(report, size)
    dynamic_pass(report, size)
    collective_id_pass(report, size)
    window_pass(report, size)
    resilience_pass(report, size)
    serving_pass(report, size)
    relay_pass(report, size)
    control_pass(report, size)
    tracing_pass(report, size)
    fleet_pass(report, size)
    sim_pass(report, size)
    concurrency_pass(report, size)
    profiling_pass(report, size)
    protocol_pass(report, size)
    doc_pass(report, size)
    examples_pass(report, size)
    if trace:
        comm_lint_pass(report, size)
        sharding_pass(report, size)
    return report


def main(argv: Optional[List[str]] = None) -> int:
    ap = argparse.ArgumentParser(
        prog="python -m bluefog_tpu.analysis.lint",
        description="Statically verify bluefog_tpu communication programs "
                    "(topologies, collective-id leases, jaxpr comm-lint).")
    ap.add_argument("--size", type=int, default=8,
                    help="mesh size to verify at (default 8)")
    ap.add_argument("--verbose", action="store_true",
                    help="also print info-severity diagnostics")
    ap.add_argument("--no-trace", action="store_true",
                    help="skip the jaxpr comm-lint pass (no jax tracing; "
                    "topology/id passes only)")
    args = ap.parse_args(argv)

    report = run_all(size=args.size, trace=not args.no_trace)
    print(report.format(verbose=args.verbose))
    if report.ok:
        print("lint: OK")
        return 0
    print("lint: FAILED")
    return 1


if __name__ == "__main__":
    sys.exit(main())
