"""Benchmark harness — ResNet-50 decentralized-SGD **images/sec/chip** and
model FLOP/s utilisation against the chip's published peak.

Runs the full decentralized train step (fwd + bwd + gossip + SGD update) as
one jitted shard_map program over all visible devices and reports throughput
per chip.  On one chip the gossip degenerates to the identity (size-1 mesh);
on several the same program gossips over ICI.

Default mode **sweeps the per-chip batch** (128 → 2048, doubling; an OOM
above the first point ends the sweep upward) and reports the
best-throughput point; ``--batch N`` pins a single batch instead.

Prints ONE JSON line that names the device it ran on:
  {"metric": "resnet50_images_per_sec_per_chip", "value": N,
   "unit": "images/sec/chip", "platform": "tpu", "device_kind": "...",
   "device_count": K, "wall_clock_step_ms": T, "mfu": M, ...}

- ``value`` / ``wall_clock_step_ms``: the host clock around ``--steps`` steps
  ended by ``block_until_ready``, profiler off.
- ``trace_device_step_ms`` (only with ``--profile DIR``): the device's own op
  time per step from a jax.profiler trace of a few extra steps at the
  reported batch — a field of its own, never a substitute for the wall clock.
- ``mfu``: achieved model FLOP/s over the published bf16 peak for the
  ``device_kind`` (:data:`NOMINAL_SPECS`).  A TPU kind missing from the table
  is an error, not a default.  Model FLOPs come from XLA's cost analysis of
  the compiled step when available, else the analytic ResNet-50 estimate.
- ``vs_baseline``: ratio against the reference's per-GPU ResNet-50
  throughput on V100 (360 img/s, the standard fp16 figure for the stack the
  reference paper benchmarked on).

Any failure exits non-zero.  Finding no TPU is a failure too, unless the
caller set ``JAX_PLATFORMS=cpu`` itself (a control-flow check): the JSON then
says ``"platform": "cpu"`` and carries no MFU.
"""

import argparse
import gc
import json
import os
import sys
import time

import jax
import jax.numpy as jnp
import optax
from jax.sharding import PartitionSpec as P

import bluefog_tpu as bf
from bluefog_tpu.models import ResNet50
from bluefog_tpu.optim import DistributedNeighborAllreduceOptimizer
from bluefog_tpu.parallel.api import shard_map
from bluefog_tpu.topology import ExponentialTwoGraph

V100_BASELINE_IMG_PER_SEC = 360.0
# Steps recorded inside the jax.profiler trace window (and the divisor that
# turns the trace's total device op time into a per-step figure).
PROFILE_STEPS = 3
# Standard analytic ResNet-50 cost at 224x224: ~4.09 GFLOP forward per image,
# training step ~= 3x forward (fwd + grad wrt activations + grad wrt weights).
RESNET50_TRAIN_FLOPS_PER_IMG_224 = 3 * 4.09e9

# Published spec sheets (bf16 dense peak TFLOP/s, HBM GB/s; Google Cloud TPU
# documentation) keyed by device_kind substring — the only MFU denominator.
NOMINAL_SPECS = {
    "v6 lite": (918.0, 1640.0), "v6e": (918.0, 1640.0),
    "v5 lite": (197.0, 819.0), "v5e": (197.0, 819.0),
    "v5p": (459.0, 2765.0),
    "v4": (275.0, 1228.0),
    "v3": (123.0, 900.0),
    "v2": (46.0, 700.0),
}


def nominal_spec(devices):
    """(bf16 peak TFLOP/s, HBM GB/s) from the public spec sheet for this
    chip; a device kind that is not in the table is an error."""
    kind = devices[0].device_kind
    for key in sorted(NOMINAL_SPECS, key=len, reverse=True):
        if key in kind.lower():
            return NOMINAL_SPECS[key]
    raise SystemExit(
        f"bench: device_kind {kind!r} is not in NOMINAL_SPECS — add its "
        "published peak (with its source) before reporting an MFU for it")


def device_record(devices):
    """What every result names: platform, device_kind, device count — and
    the spec-sheet row (None on the CPU control-flow check).  Finding no
    TPU is an error unless the caller pinned ``JAX_PLATFORMS=cpu``."""
    platform = devices[0].platform
    if platform != "tpu" and os.environ.get("JAX_PLATFORMS") != "cpu":
        raise SystemExit(
            f"bench: no TPU found (platform={platform!r}); a measurement "
            "path that finds no chip fails.  Set JAX_PLATFORMS=cpu yourself "
            "for a control-flow check that reports no device metric")
    record = {"platform": platform, "device_kind": devices[0].device_kind,
              "device_count": len(devices)}
    return record, (nominal_spec(devices) if platform == "tpu" else None)


def _cost_flops(compiled) -> float:
    """Per-invocation FLOPs of a compiled executable per XLA's cost
    analysis; 0.0 when the backend reports none.  Under SPMD this is the
    **per-device** module's count (batch images worth of work)."""
    ca = compiled.cost_analysis()
    if isinstance(ca, (list, tuple)):
        ca = ca[0]
    return float((ca or {}).get("flops", 0.0))


def run(args, batch: int, trace_dir=None):
    """One full measurement at the given per-chip batch.

    Returns ``(img_per_sec_per_chip, flops_per_step_per_chip, mem)``; the
    FLOP count is XLA's for one device's share of the step (0.0 if
    unavailable), ``mem`` the compiled step's temp/argument bytes.
    ``trace_dir`` additionally records :data:`PROFILE_STEPS` steps under the
    profiler between the warm-up and the timed loop.
    """
    n = len(jax.devices())
    ctx = bf.get_context()

    model = ResNet50(num_classes=1000, dtype=jnp.bfloat16, stem=args.stem)
    opt = DistributedNeighborAllreduceOptimizer(
        optax.sgd(0.1, momentum=0.9), topology=ctx.schedule,
        axis_name=ctx.axis_name, atc=False, backend=args.backend,
    )

    rng = jax.random.PRNGKey(0)
    x0 = jnp.zeros((batch, args.image_size, args.image_size, 3), jnp.bfloat16)
    variables = model.init(rng, x0, train=True)
    params, batch_stats = variables["params"], variables["batch_stats"]

    params = bf.rank_shard(bf.rank_stack(params))
    batch_stats = bf.rank_shard(bf.rank_stack(batch_stats))

    imgs = jax.random.normal(
        jax.random.PRNGKey(1), (n, batch, args.image_size, args.image_size, 3)
    ).astype(jnp.bfloat16)
    labels = jax.random.randint(jax.random.PRNGKey(2), (n, batch), 0, 1000)
    imgs, labels = bf.rank_shard(imgs), bf.rank_shard(labels)

    def init_opt(params_blk):
        p = jax.tree_util.tree_map(lambda t: t[0], params_blk)
        st = opt.init(p)
        return jax.tree_util.tree_map(lambda t: jnp.asarray(t)[None], st)

    opt_state = jax.jit(shard_map(
        init_opt, mesh=ctx.mesh, in_specs=(P(ctx.axis_name),),
        out_specs=P(ctx.axis_name), check_vma=False,
    ))(params)

    def train_step(params_blk, stats_blk, opt_blk, x_blk, y_blk):
        p, bs, st = jax.tree_util.tree_map(lambda t: t[0],
                                           (params_blk, stats_blk, opt_blk))
        x, y = x_blk[0], y_blk[0]

        def loss_fn(p):
            logits, mut = model.apply(
                {"params": p, "batch_stats": bs}, x, train=True,
                mutable=["batch_stats"],
            )
            loss = optax.softmax_cross_entropy_with_integer_labels(
                logits, y
            ).mean()
            return loss, mut["batch_stats"]

        (loss, new_bs), g = jax.value_and_grad(loss_fn, has_aux=True)(p)
        upd, st = opt.update(g, st, p)
        p = optax.apply_updates(p, upd)
        return (jax.tree_util.tree_map(lambda t: t[None], (p, new_bs, st))
                + (loss[None],))

    # AOT-compile once; the same executable serves cost analysis, warmup,
    # profiling, and the timed loop (no second trace/compile anywhere).
    step_fn = jax.jit(shard_map(
        train_step, mesh=ctx.mesh, in_specs=(P(ctx.axis_name),) * 5,
        out_specs=(P(ctx.axis_name),) * 4, check_vma=False,
    ), donate_argnums=(0, 1, 2)).lower(
        params, batch_stats, opt_state, imgs, labels).compile()

    flops_per_step = _cost_flops(step_fn)
    ma = step_fn.memory_analysis()
    if isinstance(ma, (list, tuple)):
        ma = ma[0]
    mem = None if ma is None else {"temp": int(ma.temp_size_in_bytes),
                                   "args": int(ma.argument_size_in_bytes)}

    for _ in range(max(args.warmup, 1)):
        params, batch_stats, opt_state, loss = step_fn(
            params, batch_stats, opt_state, imgs, labels
        )
    jax.block_until_ready(loss)

    if trace_dir:
        with jax.profiler.trace(trace_dir):
            for _ in range(PROFILE_STEPS):
                params, batch_stats, opt_state, loss = step_fn(
                    params, batch_stats, opt_state, imgs, labels
                )
            jax.block_until_ready(loss)

    t0 = time.perf_counter()
    for _ in range(args.steps):
        params, batch_stats, opt_state, loss = step_fn(
            params, batch_stats, opt_state, imgs, labels
        )
    jax.block_until_ready(loss)
    dt = time.perf_counter() - t0

    total_images = args.steps * batch * n
    return total_images / dt / n, flops_per_step, mem


def _free_device_memory() -> None:
    """Delete every live device buffer so the next compile starts against an
    empty HBM: a sweep point that failed RESOURCE_EXHAUSTED leaves its
    arguments and donated buffers resident, and run() rebuilds everything
    from scratch per call."""
    for arr in jax.live_arrays():
        if not arr.is_deleted():
            arr.delete()
    gc.collect()


def _hbm_limit_bytes() -> int:
    """Per-chip accelerator memory capacity, or 0 where the platform does
    not report it."""
    stats = jax.local_devices()[0].memory_stats()
    return int(stats.get("bytes_limit", 0)) if stats else 0


def _predicts_oom(mem, limit: int) -> bool:
    """Would doubling the batch exceed HBM?  Temp (activation) memory scales
    ~linearly with batch; arguments are mostly batch-independent params.
    Deliberately conservative (1.9x, 95% of capacity): a false 'fits' just
    pays the compile-and-fail we would have paid anyway, while a false
    'OOM' would silently drop a feasible sweep point."""
    if not mem or not limit:
        return False
    return 1.9 * mem["temp"] + mem["args"] > 0.95 * limit


def _is_oom(e: BaseException) -> bool:
    """Host OOM is MemoryError; device OOM — at compile time or at run time
    — is a ``jax.errors.JaxRuntimeError`` whose status is
    RESOURCE_EXHAUSTED."""
    if isinstance(e, MemoryError):
        return True
    return (isinstance(e, jax.errors.JaxRuntimeError)
            and "RESOURCE_EXHAUSTED" in str(e))


def mfu_fields(spec, achieved_flops, best_mem, flops_per_step,
               best_batch, best_ips) -> dict:
    """MFU against the published peak, and a bytes-moved roofline estimate.
    ``spec`` is the chip's :data:`NOMINAL_SPECS` row."""
    peak_tf, hbm_gbps = spec
    out = {
        "nominal_peak_tflops_per_sec": peak_tf,
        "mfu": round(achieved_flops / (peak_tf * 1e12), 4),
    }
    if best_mem:
        # crude per-step roofline: HBM traffic ~ activations (temp) + one
        # read of the arguments; compute bound from the published peak
        bytes_est = best_mem["temp"] + best_mem["args"]
        mem_ms = bytes_est / (hbm_gbps * 1e9) * 1e3
        comp_ms = (flops_per_step / (peak_tf * 1e12) * 1e3
                   if flops_per_step else None)
        out["roofline_estimate"] = {
            "hbm_bytes_per_step_est": int(bytes_est),
            "min_step_ms_memory": round(mem_ms, 2),
            "min_step_ms_compute": (round(comp_ms, 2)
                                    if comp_ms is not None else None),
            "measured_step_ms": round(best_batch / best_ips * 1e3, 2),
            "bound": ("memory" if comp_ms is None or mem_ms > comp_ms
                      else "compute"),
        }
    return out


def _trace_device_step_ms(trace_dir):
    """Per-step per-chip device op time (ms) from the jax.profiler trace
    captured at ``trace_dir``, or None when the trace holds no device lane
    (a CPU capture).  The trace carries one "XLA Ops" lane per local device;
    under SPMD each lane holds one chip's copy of the step, so the per-chip
    figure divides the lane-summed total by the lane count."""
    import importlib.util

    summary_py = os.path.join(os.path.dirname(os.path.abspath(__file__)),
                              "benchmarks", "profile_summary.py")
    spec = importlib.util.spec_from_file_location(
        "bftpu_profile_summary", summary_py)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    (_path, by_op, total_us, n_lanes,
     device_events) = mod.device_op_totals(trace_dir)
    if not by_op or not device_events or n_lanes <= 0:
        return None
    return total_us / 1e3 / PROFILE_STEPS / n_lanes


def sweep(args):
    """Measure per-chip batches 128, 256, ... ``--sweep-max``; returns the
    list of ``(batch, img/s/chip, flops_per_step, mem)`` points.  An OOM
    above the first point ends the sweep; any other failure, or an OOM at
    the first point, propagates."""
    results = []
    batch = min(128, args.sweep_max)
    while batch <= args.sweep_max:
        try:
            r = (batch,) + run(args, batch)
        except Exception as e:
            if not (results and _is_oom(e)):
                raise
            print(f"bench: batch {batch} exhausted memory; sweep ends",
                  file=sys.stderr)
            _free_device_memory()
            break
        print(f"bench: batch {r[0]:5d} -> {r[1]:,.0f} img/s/chip",
              file=sys.stderr)
        results.append(r)
        # Past the knee throughput declines monotonically with batch once
        # XLA starts rematerializing under HBM pressure: a point >3% below
        # the best so far means every larger one loses too.  (3% margin so
        # run-to-run noise cannot end the sweep before the real knee.)
        best_so_far = max(x[1] for x in results)
        if r[1] < 0.97 * best_so_far:
            print(f"bench: batch {r[0]} is "
                  f"{100 * (1 - r[1] / best_so_far):.1f}% below the best "
                  "point — past the knee, sweep ends", file=sys.stderr)
            break
        if batch * 2 <= args.sweep_max and _predicts_oom(
                r[3], _hbm_limit_bytes()):
            print(f"bench: batch {batch * 2} predicted to exceed HBM "
                  f"(temp {r[3]['temp'] / 2**30:.1f} GiB at {batch}); "
                  f"sweep ends", file=sys.stderr)
            break
        batch *= 2
    return results


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--batch", type=int, default=None,
                    help="pin one per-chip batch; default sweeps 128..2048 "
                         "and reports the best")
    ap.add_argument("--sweep-max", type=int, default=2048)
    ap.add_argument("--image-size", type=int, default=224)
    ap.add_argument("--steps", type=int, default=20)
    ap.add_argument("--warmup", type=int, default=5)
    ap.add_argument("--profile", default=None, metavar="DIR",
                    help="also capture a jax.profiler trace at the reported "
                         "batch and print its device time per step")
    ap.add_argument("--backend", choices=["auto", "xla", "pallas"],
                    default="auto",
                    help="gossip transport (pallas = fused RDMA kernels)")
    ap.add_argument("--stem", choices=["conv", "s2d"], default="conv",
                    help="ResNet stem: reference 7x7/s2 conv, or the "
                         "MXU-friendly space-to-depth 4x4/s1 equivalent "
                         "(exact same function class; see models/resnet.py)")
    args = ap.parse_args()
    bf.configure_compile_cache()

    devices = jax.devices()
    device, spec = device_record(devices)
    bf.init(topology=ExponentialTwoGraph(len(devices)))

    if args.batch is not None:
        results = [(args.batch,) + run(args, args.batch)]
    else:
        results = sweep(args)
    best_batch, best_ips, flops_per_step, best_mem = max(
        results, key=lambda r: r[1])

    if flops_per_step > 0:
        # cost_analysis counts the per-device SPMD module = `batch` images
        flops_per_img = flops_per_step / best_batch
    else:
        flops_per_img = RESNET50_TRAIN_FLOPS_PER_IMG_224 * (
            args.image_size / 224.0) ** 2
    achieved_flops = best_ips * flops_per_img

    out = {
        "metric": "resnet50_images_per_sec_per_chip",
        "value": round(best_ips, 2),
        "unit": "images/sec/chip",
        **device,
        "batch": best_batch,
        "backend": args.backend,
        "stem": args.stem,
        "wall_clock_step_ms": round(best_batch / best_ips * 1e3, 2),
        "vs_baseline": round(best_ips / V100_BASELINE_IMG_PER_SEC, 3),
        "sweep": [{"batch": r[0], "img_per_sec_per_chip": round(r[1], 2)}
                  for r in results],
        "model_tflops_per_sec_per_chip": round(achieved_flops / 1e12, 2),
        "flops_source": ("xla_cost_analysis" if flops_per_step > 0
                         else "analytic"),
    }
    if spec is not None:
        out.update(mfu_fields(spec, achieved_flops, best_mem, flops_per_step,
                              best_batch, best_ips))
    if args.profile:
        # a run of its own at the reported batch (the executable comes back
        # from the compile cache), so the headline above is profiler-free
        run(args, best_batch, trace_dir=args.profile)
        trace_ms = _trace_device_step_ms(args.profile)
        if trace_ms is None and device["platform"] == "tpu":
            raise SystemExit(f"bench: the trace under {args.profile} holds "
                             "no device lane")
        if trace_ms is not None:
            out["trace_device_step_ms"] = round(trace_ms, 2)
    print(json.dumps(out))


if __name__ == "__main__":
    main()
