"""chip_smoke.py — the quickest proof that bluefog-tpu still starts on the chip.

One process drives every TPU chip it finds through the entry points a user
would call, at the full width of the models the repo trains, and checks what
comes out by the repo's own means:

1. ``trainer``  — ``examples/imagenet_resnet.py``: ResNet-50, 224x224, 1,000
   classes, bf16, per-rank batch 128, synthetic source; a few gossip-SGD
   steps, one evaluation pass, one checkpoint, then ``--resume``.  Asserts a
   finite loss on every rank, every parameter leaf sharded over all devices,
   and the restored checkpoint equal to what was saved.
2. ``gossip``   — (more than one chip) ``neighbor_allreduce`` of
   rank-distinct values against the closed form ``W @ x`` in NumPy, through
   ``bf.neighbor_allreduce`` and through ``ops.neighbor_allreduce`` in a
   ``shard_map``, at 4 KiB, 1 MiB, 4 MiB and a ragged 9 MiB leaf, f32 and
   bf16; then one window round (``win_create`` / ``win_put`` /
   ``win_accumulate`` / ``win_update``) against its closed form.
3. ``gpt``      — ``examples/synthetic_benchmark.py --model gpt-small --comm
   neighbor --seq-len 2048``: two decentralized steps.
4. ``flash``    — forced ``local_attention(backend="flash")`` forward and
   backward against the dense path at bf16 tolerance, at GPT-small's heads
   (T=2048) and at latent attention's (192-wide queries and keys, 128-wide
   values, 32 heads, T=1024) against dense attention computed in f32.

Any failed phase fails the run (no handler lets one pass), a whole-run
watchdog turns a hang into a failure with stacks, and finding no TPU is a
failure.  The last line of stdout is one JSON object:
``{"ok": true, "device": {"platform": "tpu", "kind": ..., "count": ...}}``.

Run it on the chip through the chip tool: ``python chip_smoke.py``.
"""

import faulthandler
import json
import os
import shutil
import sys
import tempfile
import time

# A hung barrier handshake must end as a failure with stacks, inside the
# 1200 s the check allows — not as the tool's time limit.
WATCHDOG_S = 1100

# Full width; depth of the run (steps) cut.  A CPU dry run overrides these
# from outside (see .claude/skills/verify/SKILL.md).
TRAINER_ARGS = ["--image-size", "224", "--num-classes", "1000",
                "--batch-size", "128", "--steps-per-epoch", "3",
                "--epochs", "1", "--warmup-epochs", "1"]
GPT_ARGS = ["--model", "gpt-small", "--comm", "neighbor", "--seq-len", "2048",
            "--batch-size", "4", "--iters", "1", "--inner", "2",
            "--warmup", "0"]
FLASH_SHAPE = (2, 2048, 12, 64)  # (B, T, H, D): GPT-small's heads at T=2048
MLA_SHAPE = (2, 1024, 32, 192, 128)  # (B, T, H, D_qk, D_v): latent attention
GOSSIP_LEAF_BYTES = (4 << 10, 1 << 20, 4 << 20, (9 << 20) + 12)  # last: ragged

_REPO = os.path.dirname(os.path.abspath(__file__))


def require_tpu(devices) -> None:
    if devices[0].platform != "tpu":
        raise SystemExit(
            f"chip_smoke: needs a TPU, but JAX reports platform="
            f"{devices[0].platform!r} ({len(devices)} device(s), "
            f"JAX_PLATFORMS={os.environ.get('JAX_PLATFORMS')!r}); run it on "
            "the chip: python chip_smoke.py")


def compile_seconds():
    """JAX's own backend-compile durations so far (cache lookups included),
    from the record the program keeps of its start."""
    from bluefog_tpu.tracing import startup

    return startup.RECORD.stage_seconds["compile"]


def phase(name, fn):
    print(f"chip_smoke: [{name}] start", flush=True)
    c0, t0 = compile_seconds(), time.perf_counter()
    fn()
    print(f"chip_smoke: [{name}] ok  wall_s={time.perf_counter() - t0:.1f} "
          f"compile_s={compile_seconds() - c0:.1f}", flush=True)


def run_trainer():
    import jax
    import numpy as np

    import imagenet_resnet

    ckpt = tempfile.mkdtemp(prefix="chip_smoke_ckpt_")
    try:
        first = imagenet_resnet.main(
            TRAINER_ARGS + ["--checkpoint-dir", ckpt])
        assert first["loss"].shape == (jax.device_count(),), first["loss"]
        assert np.isfinite(first["loss"]).all(), first["loss"]
        assert first["val_top1"] is not None, "no evaluation pass ran"
        assert first["saved"] is not None, "no checkpoint was saved"
        everywhere = set(jax.devices())
        for path, leaf in jax.tree_util.tree_leaves_with_path(
                first["params"]):
            assert leaf.sharding.device_set == everywhere, (
                f"parameter {jax.tree_util.keystr(path)} lives on "
                f"{sorted(d.id for d in leaf.sharding.device_set)}, not on "
                f"all {len(everywhere)} devices")
        resumed = imagenet_resnet.main(
            TRAINER_ARGS + ["--checkpoint-dir", ckpt, "--resume"])
        assert resumed["start_epoch"] == 1, resumed["start_epoch"]
        restored = {k: resumed[k]
                    for k in ("params", "batch_stats", "opt_state")}
        same = jax.tree_util.tree_map(
            lambda a, b: bool(np.array_equal(np.asarray(a), np.asarray(b))),
            first["saved"], restored)
        assert all(jax.tree_util.tree_leaves(same)), (
            "restored checkpoint differs from what was saved")
    finally:
        shutil.rmtree(ckpt, ignore_errors=True)


def _rank_distinct(n, elems, dtype, seed):
    import jax.numpy as jnp
    import numpy as np

    x = np.random.default_rng(seed).standard_normal((n, elems), np.float32)
    x += np.arange(n, dtype=np.float32)[:, None]  # rank r is centred on r
    return jnp.asarray(x, dtype)


def _assert_close(got, want, dtype, what):
    import numpy as np

    # bf16 keeps 8 mantissa bits; the reduction itself runs in f32
    rtol, atol = (1e-5, 1e-5) if np.dtype(dtype).itemsize == 4 else (
        1e-2, 1e-2)
    np.testing.assert_allclose(
        np.asarray(got, np.float64), want, rtol=rtol, atol=atol,
        err_msg=what)


def run_gossip():
    import jax
    import jax.numpy as jnp
    import numpy as np
    from jax.sharding import PartitionSpec as P

    import bluefog_tpu as bf
    from bluefog_tpu import ops
    from bluefog_tpu.ops import pallas_gossip
    from bluefog_tpu.parallel.api import shard_map
    from bluefog_tpu.topology import ExponentialTwoGraph

    n = jax.device_count()
    ctx = bf.init(topology=ExponentialTwoGraph(n))
    print(f"chip_smoke: mesh device ids "
          f"{[d.id for d in ctx.devices]}", flush=True)
    w = np.asarray(ctx.topology.weights, np.float64)
    sched, ax = ctx.schedule, ctx.axis_name
    runners = {
        "bf": bf.neighbor_allreduce,
        "ops": jax.jit(shard_map(
            lambda xs: ops.neighbor_allreduce(xs, sched, ax),
            mesh=ctx.mesh, in_specs=(P(ax),), out_specs=P(ax),
            check_vma=False)),
    }
    seed = 0
    for dtype in (jnp.float32, jnp.bfloat16):
        for nbytes in GOSSIP_LEAF_BYTES:
            elems = nbytes // np.dtype(dtype).itemsize
            seed += 1
            x = bf.rank_shard(_rank_distinct(n, elems, dtype, seed))
            want = w @ np.asarray(x, np.float64)
            for name, run in runners.items():
                _assert_close(
                    run(x), want, dtype,
                    f"{name}.neighbor_allreduce "
                    f"dtype={np.dtype(dtype).name} bytes={nbytes}")
            print(f"chip_smoke: gossip {np.dtype(dtype).name} {nbytes} B "
                  f"== W @ x on {sorted(runners)}", flush=True)

    # one window round; at 1 MiB the deliver path takes what auto resolves to
    d, o = np.diag(np.diag(w)), w - np.diag(np.diag(w))
    x, y, z = (bf.rank_shard(_rank_distinct(n, 1 << 18, jnp.float32, s))
               for s in (101, 102, 103))
    win_backend = pallas_gossip.resolve_backend("auto", sched, x[0])
    print(f"chip_smoke: window backend auto -> {win_backend}", flush=True)
    xn, yn, zn = (np.asarray(t, np.float64) for t in (x, y, z))
    bf.win_create(x, "chip_smoke")
    bf.win_put(y, "chip_smoke")
    out1 = bf.win_update("chip_smoke")
    want1 = d @ xn + o @ yn
    _assert_close(out1, want1, jnp.float32, "win_put + win_update")
    bf.win_accumulate(z, "chip_smoke")
    out2 = bf.win_update("chip_smoke")
    _assert_close(out2, d @ want1 + o @ (yn + zn), jnp.float32,
                  "win_accumulate + win_update")
    bf.win_free("chip_smoke")
    print("chip_smoke: window round == closed form", flush=True)


def run_gpt():
    import jax

    import synthetic_benchmark

    loss = synthetic_benchmark.main(GPT_ARGS)["loss"]
    assert loss.shape == (jax.device_count(),), loss


def run_flash():
    import jax
    import jax.numpy as jnp
    import numpy as np

    from bluefog_tpu.ops.ring_attention import local_attention

    def fwd_bwd(backend, q, k, v):
        def loss(q, k, v):
            out = local_attention(q, k, v, causal=True, backend=backend)
            return jnp.sum(out.astype(jnp.float32) ** 2), out
        (_, out), grads = jax.value_and_grad(
            loss, argnums=(0, 1, 2), has_aux=True)(q, k, v)
        return (out,) + grads

    def check(what, shapes, dense_dtype):
        q, k, v = (jax.random.normal(jax.random.PRNGKey(i), shape,
                                     jnp.bfloat16)
                   for i, shape in enumerate(shapes))
        with jax.default_matmul_precision("highest"):
            dense = jax.jit(lambda: fwd_bwd(
                "dense", *(t.astype(dense_dtype) for t in (q, k, v))))()
        flash = jax.jit(lambda: fwd_bwd("flash", q, k, v))()
        for name, a, b in zip(("out", "dq", "dk", "dv"), dense, flash):
            a, b = np.asarray(a, np.float32), np.asarray(b, np.float32)
            assert a.shape == b.shape, (what, name, a.shape, b.shape)
            assert np.isfinite(b).all(), f"{what} {name} not finite"
            err = np.abs(a - b).max() / max(np.abs(a).max(), 1e-6)
            assert err < 5e-2, f"{what} {name} off dense by {err:.3g} of max"
            print(f"chip_smoke: {what} {name} vs dense: {err:.2e} of max",
                  flush=True)

    check("flash", (FLASH_SHAPE,) * 3, jnp.bfloat16)
    b, t, h, d_qk, d_v = MLA_SHAPE
    check("flash 192/128", ((b, t, h, d_qk), (b, t, h, d_qk), (b, t, h, d_v)),
          jnp.float32)


def main():
    faulthandler.dump_traceback_later(WATCHDOG_S, exit=True)
    sys.path.insert(0, _REPO)
    sys.path.insert(0, os.path.join(_REPO, "examples"))

    import jax

    import bluefog_tpu as bf
    from bluefog_tpu.runtime import native

    cache_dir = bf.configure_compile_cache()
    devices = jax.devices()
    require_tpu(devices)
    device = {"platform": devices[0].platform,
              "kind": devices[0].device_kind, "count": len(devices)}
    print(f"chip_smoke: platform={device['platform']} "
          f"device_kind={device['kind']!r} device_count={device['count']} "
          f"jax={jax.__version__} native_runtime="
          f"{native.load() is not None} compile_cache={cache_dir}",
          flush=True)

    c0, t0 = compile_seconds(), time.perf_counter()
    phase("trainer", run_trainer)
    if device["count"] > 1:
        phase("gossip", run_gossip)
    phase("gpt", run_gpt)
    phase("flash", run_flash)
    print(f"chip_smoke: all phases ok  wall_s="
          f"{time.perf_counter() - t0:.1f} "
          f"compile_s={compile_seconds() - c0:.1f}", flush=True)
    faulthandler.cancel_dump_traceback_later()
    print(json.dumps({"ok": True, "device": device}), flush=True)


if __name__ == "__main__":
    main()
