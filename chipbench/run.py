"""Run one cell of the benchmark once, in this process.

    python3 chipbench/run.py --workload <name> --seed <n> --seconds <s> --trace <0|1>

``--trace 0`` measures the end-to-end metrics with the profiler off;
``--trace 1`` splits its seconds into a steady untraced stretch, one untraced
stretch per arm the cell's per-layer metrics ask for, and a profiler trace of
``TRACE_STEPS`` steps, and reports the per-layer metrics.  The last line of
standard output is one JSON object: ``correct``, ``attempted``, ``failed``,
``metrics``, ``device`` and, traced, ``breakdown``.  Everything else goes on
earlier lines, each starting ``chipbench:``.

Finding no TPU, fewer chips than the cell asks for, or a ``device_kind``
without a row in ``peaks.py`` ends the run with a non-zero code and no result
line.  A caller that pins ``JAX_PLATFORMS=cpu`` itself gets a control-flow
check: only metrics whose ``source`` is ``program_counter`` are printed.
"""

import argparse
import collections
import json
import os
import shutil
import statistics
import sys
import time

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, REPO)

RUN_AHEAD = 2        # steps in flight behind the one being dispatched
WARMUP_STEPS = 2
TRACE_STEPS = 8
STEADY_SHARE, ARMS_SHARE = 0.5, 0.3   # of --seconds, in a traced run
OUT_DIR = os.path.join(REPO, "chipbench_out")


def process_age_s() -> float:
    """Seconds since this process was started, from the kernel's record, so
    that ``setup_s`` includes the interpreter's start and every import."""
    with open("/proc/self/stat") as f:
        fields = f.read().rsplit(")", 1)[1].split()
    started = int(fields[19]) / os.sysconf("SC_CLK_TCK")
    return time.clock_gettime(time.CLOCK_BOOTTIME) - started


def say(msg, **fields):
    print(f"chipbench: {msg}" + (" " + json.dumps(fields) if fields else ""),
          flush=True)


class CompileClock:
    """JAX's own backend-compile events (cache lookups included): their
    count and summed seconds.  Copied from ``chip_smoke.py``."""

    def __init__(self):
        import jax

        self.count, self.seconds = 0, 0.0
        jax.monitoring.register_event_duration_secs_listener(self._on_event)

    def _on_event(self, event, duration, **_):
        if event == "/jax/core/compile/backend_compile_duration":
            self.count += 1
            self.seconds += duration


def drive(step, state, ring, k, *, seconds=None, steps=None):
    """Dispatch ``step`` over the batch ring with ``RUN_AHEAD`` steps in
    flight: dispatch step i, then wait for the loss of step i - RUN_AHEAD, so
    the device queue never drains and every step has a completion time.

    With ``seconds`` the stretch ends at the first completion at or after
    that time, and the steps still in flight are drained and not counted;
    with ``steps`` exactly that many are dispatched and all are counted.
    Returns the new state and ring position and a record of the stretch.
    """
    import jax
    import numpy as np

    rec = {"done_s": [], "losses": [], "dispatch_ms": [], "attempted": 0}
    pending = collections.deque()
    closing = False
    t0 = time.perf_counter()
    while not closing or pending:
        if not closing:
            with jax.profiler.TraceAnnotation("chipbench.dispatch"):
                t = time.perf_counter()
                state, loss = step(state, ring[k % len(ring)])
                rec["dispatch_ms"].append((time.perf_counter() - t) * 1e3)
            pending.append(loss)
            k += 1
            rec["attempted"] += 1
            if steps is not None and rec["attempted"] == steps:
                closing = True
        if len(pending) > RUN_AHEAD or closing:
            with jax.profiler.TraceAnnotation("chipbench.wait"):
                loss = np.asarray(pending.popleft())
            now = time.perf_counter() - t0
            rec["losses"].append(loss)
            if steps is not None or not closing:
                rec["done_s"].append(now)
            if seconds is not None and now >= seconds:
                closing = True
    return state, k, rec


def step_ms(rec):
    """Times between consecutive completions: the steady step times (the
    first completion also holds the queue filling and is left out)."""
    d = rec["done_s"]
    return [(b - a) * 1e3 for a, b in zip(d, d[1:])]


def loss_ok(rec, ring):
    """Finite on every rank at every completion, and lower at the end than
    at the start: the last completions (up to one turn of the batch ring)
    against the first completions of the same batches."""
    import numpy as np

    losses = np.stack(rec["losses"])
    failed = int((~np.isfinite(losses).all(axis=1)).sum())
    last = range(max(ring, len(losses) - ring), len(losses))
    first = [i % ring for i in last]
    if not first:
        return failed, False, float("nan"), float("nan")
    start, end = losses[first].mean(), losses[list(last)].mean()
    return failed, bool(end < start), float(start), float(end)


def agreement(cell, state, k, report=None):
    """Three steps through the system against the plain reference, from the
    state the window left (see ``reference.py``).  The chip holds one
    training state a rank at a time, so that a state may fill the chip as a
    job's would: the reference's copy of the state waits on the host while
    the system takes its steps, and the system's result waits there while
    the reference takes its own (beside its state, its updates and its
    program's scratch, a second set of parameters did not fit: PR 27's
    probe).  The two never hold their activations at once either.

    ``report``, where given, takes what a run prints beside the verdict: the
    fullest chip's memory where the next configuration is sized from it, and
    the model's own comparison where the family brings one."""
    import numpy as np

    from chipbench import reference
    from chipbench.cell import base_optimizer

    devices = cell.devices
    report = {} if report is None else report
    memory = report.setdefault("memory", {})
    ring = [cell.ring[(k + j) % len(cell.ring)]
            for j in range(reference.STEPS)]
    params, model_state, opt_state = state
    held = reference.to_host(
        (params, model_state, opt_state.base_state), devices)
    del params, model_state, opt_state
    memory["before_steps"] = memory_reading(devices)
    got_losses = []
    for batch in ring:
        state, loss = cell.step(state, batch)
        got_losses.append(np.asarray(loss))
    got = reference.to_host(state[:2], devices)
    del state     # the system's state makes room for the reference's
    memory["after_del_state"] = memory_reading(devices)
    ref_states = reference.from_host(held, devices)
    del held

    def after_first_step():
        memory["after_reference_step"] = memory_reading(devices)

    want, want_losses = reference.run(
        cell.family, base_optimizer(cell.config), cell.config["atc"],
        reference.mixing_matrix(cell.ctx.topology, cell.traffic["comm"]),
        ref_states, [reference.per_rank(b, devices) for b in ring], devices,
        after_first_step=after_first_step)
    got = reference.from_host(got, devices)
    ok, leaves, loss_err = reference.compare(
        got, want, np.stack(got_losses), want_losses,
        cell.config["tolerance"])
    del want
    if hasattr(cell.family, "reference_loss"):
        # rank 0's first batch of the check, at the parameters the system's
        # steps ended on; the reference's state is gone by now
        batch0, = reference.per_rank(ring[0], devices[:1])
        err, want_loss, got_loss = reference.model_loss_error(
            cell.family, *got[0], batch0)
        report["model_loss"] = {"rel_err": err, "reference": want_loss,
                                "system": got_loss}
        ok = ok and err <= cell.config["tolerance"]["model_loss_rtol"]
    return ok, leaves, loss_err


MEMORY_KEYS = ("bytes_in_use", "bytes_reserved", "largest_free_block_bytes",
               "bytes_limit")


def memory_reading(devices):
    """``MEMORY_KEYS`` of the fullest chip (live buffers plus what loaded
    programs reserve) as the runtime reports them now; ``None`` on a backend
    that reports nothing (the CPU)."""
    stats = [d.memory_stats() for d in devices]
    if None in stats:
        return None
    fullest = max(stats, key=lambda s: int(s["bytes_in_use"])
                  + int(s["bytes_reserved"]))
    return {key: int(fullest[key]) for key in MEMORY_KEYS}


def device_record(devices, chips, pinned_cpu):
    """What the result names; refuses what it may not measure on."""
    from chipbench.peaks import peaks_for

    platform = devices[0].platform
    if platform != "tpu" and not pinned_cpu:
        raise SystemExit(
            f"chipbench: no TPU found (platform={platform!r}).  Pin "
            "JAX_PLATFORMS=cpu yourself for a control-flow check that "
            "reports no device metric")
    if len(devices) < chips:
        raise SystemExit(f"chipbench: the cell needs {chips} chips, JAX "
                         f"reports {len(devices)}")
    peaks = peaks_for(devices[0].device_kind) if platform == "tpu" else None
    return {"platform": platform, "kind": devices[0].device_kind,
            "count": chips}, peaks


def memory_peak_bytes(devices) -> int:
    """Peak HBM on the fullest chip, from the runtime: the peak of live
    buffers plus the peak reserved for programs' scratch, which
    ``peak_bytes_in_use`` leaves out (PR 22, on the chip: 0.41 GiB in use
    under a step that reserves 4.2 GiB; gossip landing buffers are scratch).
    0 on a backend that reports nothing (the CPU)."""
    stats = [d.memory_stats() for d in devices]
    if None in stats:
        return 0
    return max(int(s["peak_bytes_in_use"]) + int(s["peak_bytes_reserved"])
               for s in stats)


def breakdown_of(trace, top=10):
    """Where the traced window went: device operations by self time, and
    idle time by the host span that covered each gap's start and the op that
    ran last before it.  Seconds, averaged over the chips."""
    from chipbench import xplane

    ops, idle = collections.Counter(), collections.Counter()
    for events in trace.lanes.values():
        for name, ns in xplane.self_times(events):
            ops[xplane.base_name(name)] += ns
        for start, end, after in xplane.gaps(events):
            idle[f"{xplane.span_at(trace.spans, start)} after "
                 f"{xplane.base_name(after)}"] += end - start
    n = len(trace.lanes) * 1e9
    return {"device_ops": [[k, v / n] for k, v in ops.most_common(top)],
            "idle_gaps": [[k, v / n] for k, v in idle.most_common(top)]}


def traced_stretches(cell, arms, state, k, seconds):
    """A traced run's window: the cell's step untraced, each arm untraced,
    then ``TRACE_STEPS`` steps under the profiler."""
    import jax

    steady = seconds * (STEADY_SHARE if arms else STEADY_SHARE + ARMS_SHARE)
    state, k, rec = drive(cell.step, state, cell.ring, k, seconds=steady)
    arm_ms = {}
    for key, arm in arms.items():
        state, k, arm_rec = drive(arm, state, cell.ring, k,
                                  seconds=seconds * ARMS_SHARE / len(arms))
        arm_ms[key] = statistics.median(step_ms(arm_rec))
    trace_dir = os.path.join(OUT_DIR, "trace", cell.name)
    shutil.rmtree(trace_dir, ignore_errors=True)
    options = jax.profiler.ProfileOptions()
    options.python_tracer_level = 0
    jax.profiler.start_trace(trace_dir, profiler_options=options)
    state, k, _ = drive(cell.step, state, cell.ring, k, steps=TRACE_STEPS)
    jax.profiler.stop_trace()
    return state, k, rec, arm_ms, trace_dir


def read_trace(trace_dir):
    """The trace just written, or ``None`` where it holds no device lane (a
    CPU capture)."""
    from chipbench import xplane

    path = xplane.newest(trace_dir)
    trace = xplane.read(path) if path else None
    return trace if trace is not None and trace.lanes else None


def main(argv=None):
    ap = argparse.ArgumentParser()
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), required=True)
    ap.add_argument("--manifest", default=os.path.join(REPO, "BENCHMARK.json"),
                    help="the benchmark's manifest (tests bring their own)")
    args = ap.parse_args(argv)
    traced = bool(args.trace)

    age_s_at = {"start": process_age_s()}
    import jax

    import bluefog_tpu as bf
    from chipbench import cell as cells
    from chipbench import xplane

    age_s_at["imported"] = process_age_s()
    manifest = cells.Manifest.load(args.manifest)
    chips = manifest.entry("workloads", args.workload)["chips"]
    wanted = manifest.metrics_of("per_layer" if traced else "end_to_end",
                                 args.workload)
    cache_dir = bf.configure_compile_cache()
    # cache the sub-second compiles too: every run is a new process
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", -1)
    # The accelerator runtime's own start-up happens inside jax.devices(): 7
    # to 15 s from machine to machine (PR 22) and nothing a change to this
    # repo moves, so setup_s leaves it out; it is printed beside it.
    t = process_age_s()
    devices = jax.devices()
    backend_start_s = process_age_s() - t
    device, peaks = device_record(
        devices, chips,
        pinned_cpu=os.environ.get("JAX_PLATFORMS") == "cpu")
    clock = CompileClock()

    # ---- set-up: weights, the cell's executables, warm-up -----------------
    cell = cells.build_cell(manifest, args.workload, args.seed)
    age_s_at["built"] = process_age_s()
    specs = {m["name"]: cells.load_json(manifest.find("metrics", m["name"]))
             for m in wanted} if traced else {}
    arms = {}
    for spec in specs.values():
        for overrides in spec.get("arms", []):
            if cells.arm_key(overrides) not in arms:
                arms[cells.arm_key(overrides)] = cells.build_arm(
                    cell, overrides)
    state, k = cell.state, 0
    cell.state = None   # the steps donate it; keep no second handle
    for step in [cell.step, *arms.values()]:
        state, k, _ = drive(step, state, cell.ring, k, steps=WARMUP_STEPS)
    cost = cell.step.cost_analysis()
    cost = cost[0] if isinstance(cost, (list, tuple)) else cost or {}
    say("set-up", cache=cache_dir, age_s_at=age_s_at,
        backend_start_s=backend_start_s, compiles=clock.count,
        compile_s=clock.seconds,
        analytic_flops_per_step=cell.family.flops_per_item()
        * cell.family.items_per_step,
        xla_flops_per_step=cost.get("flops"))
    compiles_before = clock.count
    setup_s = process_age_s() - backend_start_s

    # ---- the measured window ----------------------------------------------
    arm_ms, trace = {}, None
    if traced:
        state, k, rec, arm_ms, trace_dir = traced_stretches(
            cell, arms, state, k, args.seconds)
    else:
        state, k, rec = drive(cell.step, state, cell.ring, k,
                              seconds=args.seconds)
    compiles_in_window = clock.count - compiles_before
    peak_bytes = memory_peak_bytes(cell.devices)
    throughput = (len(rec["done_s"]) * cell.family.items_per_step
                  / rec["done_s"][-1])
    failed, fell, loss_at_start, loss_at_end = loss_ok(rec, len(cell.ring))
    times = step_ms(rec)
    say("window", completed=len(rec["done_s"]), window_s=rec["done_s"][-1],
        median_step_ms=statistics.median(times), min_step_ms=min(times),
        max_step_ms=max(times), loss_at_start=loss_at_start,
        loss_at_end=loss_at_end, compiles_in_window=compiles_in_window,
        memory_stats=cell.devices[0].memory_stats())

    # ---- correctness, after the window ------------------------------------
    t = time.perf_counter()
    report = {}
    agrees, leaves, loss_err = agreement(cell, state, k, report)
    say("agreement", ok=agrees, seconds=time.perf_counter() - t,
        compile_s_total=clock.seconds, loss_rel_err=loss_err,
        worst_leaves=leaves[:4], **report)
    correct = bool(agrees and failed == 0 and fell
                   and compiles_in_window == 0)

    # ---- metrics ----------------------------------------------------------
    if traced:
        trace = read_trace(trace_dir)
        measured = cells.Measured(
            cell=cell, peaks=peaks, step_ms=times,
            dispatch_ms=rec["dispatch_ms"], arm_step_ms=arm_ms,
            throughput_per_chip=throughput,
            compiles_in_window=compiles_in_window, hlo=cell.step.as_text(),
            trace=trace, traced_steps=TRACE_STEPS)
        values = {name: manifest.module("reducers", spec["reducer"]).reduce(
            measured, spec["params"]) for name, spec in specs.items()}
    else:
        values = {"throughput_per_chip": throughput,
                  "peak_hbm_gib": peak_bytes / 2 ** 30, "setup_s": setup_s}
    on_chip = device["platform"] == "tpu"
    metrics = {
        m["name"]: {"value": values[m["name"]], "unit": m["unit"]}
        for m in wanted if values.get(m["name"]) is not None
        and (on_chip or m["source"] == "program_counter")}
    device["memory_peak_bytes"] = peak_bytes
    result = {"correct": correct, "attempted": rec["attempted"],
              "failed": failed, "metrics": metrics, "device": device}
    if trace is not None:
        device["busy_s"] = xplane.mean_over_lanes(trace, xplane.busy_ns) / 1e9
        device["window_s"] = xplane.mean_over_lanes(
            trace, xplane.window_ns) / 1e9
        result["breakdown"] = breakdown_of(trace)
    os.makedirs(OUT_DIR, exist_ok=True)
    with open(os.path.join(
            OUT_DIR, f"{args.workload}.seed{args.seed}.trace{args.trace}"
            ".json"), "w") as f:
        json.dump({"result": result, "step_ms": times,
                   "dispatch_ms": rec["dispatch_ms"], "arm_step_ms": arm_ms,
                   "agreement_leaves": leaves}, f)
    print(json.dumps(result), flush=True)


if __name__ == "__main__":
    main()
