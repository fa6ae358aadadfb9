"""One start of a benchmark cell, and where it went: what PERF.md section
5's table "Where a start goes" is made from.

``chipbench/run.py`` fixes ``setup_s`` after ``build_cell`` and two warm-up
steps, then spends a minute on the window, the arms and the agreement check.
This script makes the same start the same way (the harness's own functions,
in the harness's order), stops where ``setup_s`` is fixed, and prints

- one ``start_report:`` JSON line: ``setup_s``, ``age_s_at``,
  ``backend_start_s``, ``compiles`` and ``compile_s`` as the harness's
  set-up line has them, ``build_cell_s``, and, where the program keeps a
  record of its start (``bluefog_tpu/tracing/startup.py``), the seven
  ``setup_*`` metrics as their reducer reads them, the interval they account
  for (``interval_s``, from the import span's start to the cut) beside the
  same interval on the process's age (``interval_by_ages_s``:
  ``age_s_at.built`` less the import's start), the record's size, and with
  ``BLUEFOG_TPU_TRACE`` set what sending it through the armed recorder took;
- the operator's view of the same record (``bftrace-tpu startup``).

A start is warm when the cell's programs are in the compile cache
(``setup_cache_misses`` 0): run the cell twice in one call, or a side's
starts back to back with the first dropped (PERF.md section 2).  ``--repo``
runs another checkout's program and harness (the parent's, unpacked beside):
one without the record prints the harness's numbers alone.

Run on the chip, from the root of a checkout:
  chiprun -- python3 benchmarks/start_report.py \
      --workload ling3flash.t8192.solo --seed 2147492011
"""

import argparse
import json
import os
import sys
import time

UNTIL = "jit(train_step)"       # chipbench/cell.py::build_step's program
SEVEN = ("import_s", "trace_s", "lower_s", "compile_s", "cache_misses",
         "kernel_traces", "unspanned_s")


def main(argv=None):
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--repo", default=os.path.join(
        os.path.dirname(os.path.abspath(__file__)), ".."),
        help="the checkout whose program and harness run (default: this)")
    ap.add_argument("--manifest", default=None,
                    help="the benchmark's manifest (default: the checkout's)")
    args = ap.parse_args(argv)
    repo = os.path.abspath(args.repo)
    sys.path.insert(0, repo)

    import jax

    import bluefog_tpu as bf
    from chipbench import cell as cells
    from chipbench import run
    from chipbench import xplane  # noqa: F401  (run.py imports it here too)

    assert os.path.abspath(run.REPO) == repo, (run.REPO, repo)
    age_s_at = {"imported": run.process_age_s()}
    manifest = cells.Manifest.load(
        args.manifest or os.path.join(repo, "BENCHMARK.json"))
    chips = manifest.entry("workloads", args.workload)["chips"]
    cache_dir = bf.configure_compile_cache()
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", -1)
    t = run.process_age_s()
    devices = jax.devices()
    backend_start_s = run.process_age_s() - t
    device, _ = run.device_record(
        devices, chips, pinned_cpu=os.environ.get("JAX_PLATFORMS") == "cpu")
    clock = run.CompileClock()
    t = time.time()
    cell = cells.build_cell(manifest, args.workload, args.seed)
    age_s_at["built"], build_cell_s = run.process_age_s(), time.time() - t
    state, cell.state = cell.state, None
    run.drive(cell.step, state, cell.ring, 0, steps=run.WARMUP_STEPS)
    out = {"workload": args.workload, "seed": args.seed, "repo": repo,
           "device": device, "cache": cache_dir,
           "setup_s": run.process_age_s() - backend_start_s,
           "age_s_at": age_s_at, "backend_start_s": backend_start_s,
           "compiles": clock.count, "compile_s": clock.seconds,
           "build_cell_s": build_cell_s}
    view = None
    try:
        from bluefog_tpu.tracing import analyze, startup
    except ImportError:         # a program from before the record
        startup = None
    if startup is not None:
        record = startup.RECORD
        spans = record.spans()
        reducer = manifest.module("reducers", "startup_spans")
        # with the line an armed recorder would get beside the spans
        view, = analyze.startup_report(spans + [{
            "name": "bf.setup.record", "process_t0": record.process_t0,
            "dropped": record.dropped}], UNTIL)
        out.update(
            {"setup_" + value: reducer.quantity(spans, value, UNTIL)
             for value in SEVEN},
            interval_s=view["interval_s"],
            interval_by_ages_s=age_s_at["built"] - view["import_at_age_s"],
            backend_span_s=view["covered_s"].get("backend", 0.0),
            init_span_s=view["covered_s"].get("init", 0.0),
            records=len(spans), dropped=record.dropped)
        if os.environ.get("BLUEFOG_TPU_TRACE"):     # armed: what it costs
            t = time.time()
            out["exported_spans"] = record.export()
            out["export_s"] = time.time() - t
    print("start_report: " + json.dumps(out), flush=True)
    if view is not None:
        print(analyze.format_startup(view), flush=True)
    sys.stdout.flush()
    os._exit(0)     # skip the runtime's teardown: nothing more is read


if __name__ == "__main__":
    main()
