"""Multi-host launcher — the reference's ``bfrun``/``ibfrun`` re-thought for TPU.

Reference parity (upstream-relative): ``bluefog/run/run.py`` builds and execs
an ``mpirun -np N -H hosts -x ENV ...`` command line, and ``ibfrun`` starts an
interactive (Jupyter/ipyparallel) cluster (SURVEY.md §3.5, §2.2).  On TPU pods
there is no mpirun: every host runs the same program and rendezvous happens in
``jax.distributed.initialize`` against the coordinator.  This module provides

- :func:`initialize_cluster` — library-call bring-up (the ``bf.init()``-time
  process/network boundary of SURVEY.md §3.1);
- ``bfrun-tpu`` — a thin CLI that prepares the environment (coordinator
  address, env propagation à la ``mpirun -x``, timeline, **virtual-device
  simulation** for laptop debugging) and execs the training script;
- ``ibfrun-tpu`` (:func:`interactive_main`) — drops into a REPL with the
  framework initialized, the ``ibfrun`` analog for poking at topologies and
  collectives interactively.
"""

from __future__ import annotations

import argparse
import code
import os
import runpy
import sys
from typing import List, Optional

from bluefog_tpu.utils import log


def initialize_cluster(
    coordinator_address: Optional[str] = None,
    num_processes: Optional[int] = None,
    process_id: Optional[int] = None,
    initialization_timeout: Optional[int] = None,
) -> None:
    """Rendezvous all hosts (no-op on single-host).

    Mirrors ``jax.distributed.initialize`` argument conventions; on Cloud TPU
    the arguments are auto-detected from the metadata server.

    Failure policy: when the caller **asked** for a cluster (any of the
    arguments given), a rendezvous failure raises — a training job silently
    running undistributed at 1/N scale is the worst possible outcome.  Only
    the fully-auto-detected call (no arguments, e.g. a dev box without TPU
    metadata) degrades to single-process with a warning.
    """
    import jax

    if num_processes == 1:
        return
    explicit = (coordinator_address is not None or num_processes is not None
                or process_id is not None)
    kwargs = {}
    if initialization_timeout is not None:
        kwargs["initialization_timeout"] = initialization_timeout
    try:
        jax.distributed.initialize(
            coordinator_address=coordinator_address,
            num_processes=num_processes,
            process_id=process_id,
            **kwargs,
        )
        log.info("cluster initialized: process %d/%d", jax.process_index(), jax.process_count())
    except Exception as e:
        if explicit:
            raise RuntimeError(
                f"cluster rendezvous failed (coordinator="
                f"{coordinator_address}, num_processes={num_processes}, "
                f"process_id={process_id}): {e}") from e
        log.warn("jax.distributed.initialize skipped (auto-detect found no "
                 "cluster): %s", e)


def _apply_env(args) -> None:
    """Common env preparation for both CLIs (before jax import)."""
    for spec in args.env or []:
        if "=" in spec:
            key, val = spec.split("=", 1)
            os.environ[key] = val
        elif spec not in os.environ:
            raise SystemExit(f"-x {spec}: not set in the launching environment")
        # bare `-x NAME` propagates the current value — already in os.environ
    if args.timeline:
        os.environ["BLUEFOG_TPU_TIMELINE"] = args.timeline
    if args.simulate:
        # Virtual-device debug mesh (the analog of the reference's
        # mpirun-on-localhost testing mode; SURVEY.md §4): N CPU devices in
        # one process.  Env vars cover child processes; the jax.config
        # updates cover this one, where jax may already be imported.  Must
        # run before the backend is first used.
        os.environ["JAX_PLATFORMS"] = "cpu"
        flags = os.environ.get("XLA_FLAGS", "")
        os.environ["XLA_FLAGS"] = (
            f"{flags} --xla_force_host_platform_device_count={args.simulate}".strip()
        )
        import jax

        jax.config.update("jax_platforms", "cpu")
        jax.config.update("jax_num_cpu_devices", args.simulate)


def _add_common_args(ap: argparse.ArgumentParser) -> None:
    ap.add_argument("--coordinator", default=None, help="host:port of process 0")
    ap.add_argument("--num-processes", type=int, default=None)
    ap.add_argument("--process-id", type=int, default=None)
    ap.add_argument(
        "-x", dest="env", action="append", metavar="NAME[=VALUE]",
        help="propagate/set an environment variable (mpirun -x parity)")
    ap.add_argument(
        "--timeline", default=None, metavar="FILE",
        help="write a chrome-trace timeline (BLUEFOG_TPU_TIMELINE)")
    ap.add_argument(
        "--simulate", type=int, default=None, metavar="N",
        help="debug on N virtual CPU devices instead of TPU hardware")


def main(argv: Optional[List[str]] = None):
    ap = argparse.ArgumentParser(
        prog="bfrun-tpu",
        description="Launch a bluefog_tpu training script (bfrun analog; "
        "run once per host on multi-host pods)",
    )
    _add_common_args(ap)
    ap.add_argument(
        "--restart-backoff", type=float, default=2.0, metavar="SECONDS",
        help="with --supervise: initial delay before a restart (doubles "
             "per attempt with jitter, capped; 0 = restart immediately)")
    ap.add_argument(
        "--supervise", type=int, default=None, metavar="MAX_RESTARTS",
        help="run the script as a supervised subprocess, restarting it from "
        "its latest checkpoint when it dies (peer failure kills survivors "
        "via the coordination service; the hang watchdog kills wedged "
        "collectives) — up to MAX_RESTARTS times")
    ap.add_argument(
        "--incident-dir", default=None, metavar="DIR",
        help="with --supervise: directory collecting blackbox flight-"
        "recorder dumps across restarts (one incident tree for "
        "bfblackbox-tpu; the child inherits it as BLUEFOG_TPU_BLACKBOX_DIR "
        "and earlier attempts' dumps are layered into restart-N/).  "
        "Default: $BLUEFOG_TPU_BLACKBOX_DIR, else ./bf-incident")
    ap.add_argument("script")
    ap.add_argument("script_args", nargs=argparse.REMAINDER)
    args = ap.parse_args(argv)

    _apply_env(args)
    os.environ.setdefault("BLUEFOG_TPU_LAUNCHED", "1")
    if args.supervise is not None:
        if (args.coordinator is not None or args.num_processes is not None
                or args.process_id is not None):
            # The supervised child must rendezvous afresh on every restart —
            # initialize_cluster here (once, in the parent) cannot provide
            # that, and silently dropping the flags would run the job
            # undistributed at 1/N scale.  The script owns its own
            # initialize_cluster call in supervised mode.
            raise SystemExit(
                "--supervise cannot be combined with --coordinator/"
                "--num-processes/--process-id: the supervised script must "
                "call initialize_cluster itself so every restart "
                "re-rendezvouses")
        from bluefog_tpu.utils.failure import run_supervised

        incident = (args.incident_dir
                    or os.environ.get("BLUEFOG_TPU_BLACKBOX_DIR")
                    or "bf-incident")
        raise SystemExit(run_supervised(
            [sys.executable, args.script] + list(args.script_args),
            max_restarts=args.supervise, incident_dir=incident,
            restart_backoff_s=args.restart_backoff))
    if args.process_id is not None:
        # name this process's blackbox/faulthandler files by its real
        # rank BEFORE install() opens them — co-located processes with a
        # shared incident dir must not truncate each other's rank-0 files
        os.environ.setdefault("BLUEFOG_TPU_RANK", str(args.process_id))
    if args.num_processes is not None:
        os.environ.setdefault("BLUEFOG_TPU_WORLD", str(args.num_processes))
    try:
        # dump triggers armed in the launched process itself: scripts that
        # never call bf.init() (pure host runs) still leave a blackbox
        # file behind on an uncaught exception or fatal signal.  The
        # --supervise branch above deliberately skips this — the CHILD
        # arms its own triggers (via bf.init or this path on re-exec);
        # the supervisor only collects.
        from bluefog_tpu import blackbox

        blackbox.install()
    except Exception:
        pass
    from bluefog_tpu.utils.compile_cache import configure_compile_cache

    configure_compile_cache()
    initialize_cluster(args.coordinator, args.num_processes, args.process_id)
    sys.argv = [args.script] + list(args.script_args)
    runpy.run_path(args.script, run_name="__main__")


def interactive_main(argv: Optional[List[str]] = None):
    """``ibfrun-tpu``: REPL with the framework brought up (ibfrun analog)."""
    ap = argparse.ArgumentParser(
        prog="ibfrun-tpu",
        description="Interactive bluefog_tpu session (ibfrun analog)",
    )
    _add_common_args(ap)
    ap.add_argument("--topology", default="exp2",
                    choices=["exp2", "ring", "grid", "star", "full"],
                    help="initial virtual topology")
    args = ap.parse_args(argv)

    _apply_env(args)
    initialize_cluster(args.coordinator, args.num_processes, args.process_id)

    import jax

    import bluefog_tpu as bf
    from bluefog_tpu import topology as topo_lib

    bf.configure_compile_cache()

    n = len(jax.devices())
    builders = {
        "exp2": topo_lib.ExponentialTwoGraph,
        "ring": topo_lib.RingGraph,
        "grid": topo_lib.MeshGrid2DGraph,
        "star": topo_lib.StarGraph,
        "full": topo_lib.FullyConnectedGraph,
    }
    ctx = bf.init(topology=builders[args.topology](n)) if n > 1 else bf.init()
    banner = (
        f"bluefog_tpu interactive — {n} device(s), rank axis "
        f"'{ctx.axis_name}', topology={args.topology}\n"
        "Bound names: bf (the framework), jax, ctx (active context)."
    )
    code.interact(banner=banner, local={"bf": bf, "jax": jax, "ctx": ctx})


if __name__ == "__main__":
    main()
