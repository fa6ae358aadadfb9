"""AOT compilation of the Pallas kernels for a REAL TPU topology.

The window RDMA transport (ops/pallas_gossip.py) is interpret-validated for
semantics here and executed on four v5e chips by ``chip_smoke.py``.  What
the CPU sandbox can prove for topologies it has no chips for: Mosaic lowers
and the XLA TPU backend **compiles** the kernels for a real v5e slice via
the PJRT topology API — barrier semaphores, remote DMAs, collective ids,
VMEM limits and all.  Skips cleanly when libtpu or the topology API is
unavailable (same policy as test_overlap_aot).

Marked ``slow`` (same reason as test_overlap_aot): the shared
session-scoped AOT topology fixture costs ~8 minutes of setup in this
container, and whichever of the two AOT modules runs first pays it — so
both are excluded from the budgeted tier-1 run together and covered by
the full suite.
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from conftest import aot_topology as _aot_topo  # single skip policy + cache

from bluefog_tpu.ops import pallas_gossip as pg
from bluefog_tpu.parallel.api import shard_map
from bluefog_tpu.topology import ExponentialTwoGraph, RingGraph
from bluefog_tpu.topology.schedule import build_schedule

pytestmark = pytest.mark.slow


@pytest.mark.parametrize("accumulate", [False, True], ids=["put", "acc"])
def test_deliver_kernel_compiles_for_v5e(accumulate, tpu_aot_topology):
    topo = tpu_aot_topology
    n = len(topo.devices)
    mesh = Mesh(np.array(topo.devices), ("bf",))
    sched = build_schedule(RingGraph(n))
    k = sched.num_slots

    fn = jax.jit(shard_map(
        lambda v, b: pg.deliver_pallas(
            v[0], b[0], sched, "bf", accumulate=accumulate)[None],
        mesh=mesh, in_specs=(P("bf"), P("bf")), out_specs=P("bf"),
        check_vma=False))
    x = jax.ShapeDtypeStruct((n, 256), jnp.float32,
                             sharding=NamedSharding(mesh, P("bf")))
    b = jax.ShapeDtypeStruct((n, k, 256), jnp.float32,
                             sharding=NamedSharding(mesh, P("bf")))
    txt = fn.lower(x, b).compile().as_text()
    assert "tpu_custom_call" in txt, "deliver kernel was not lowered"


@pytest.mark.parametrize("graph", [ExponentialTwoGraph, "full"],
                         ids=["exp2_2slots", "full_3slots"])
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16],
                         ids=["f32_wire", "bf16_wire"])
def test_deliver_kernel_at_the_cutoff_compiles_for_v5e_2x2(dtype, graph):
    """The four-chip host, snake-ordered as ``bf.init`` orders it: the
    deliver kernel at the window cutoff, the largest payload ``auto`` routes
    to it, must fit VMEM under the limit it states (before the stores were
    tiled, a two-slot f32 kernel was refused from 3.3 MiB and a bf16 one
    from 2.7 MiB)."""
    from bluefog_tpu.topology import FullyConnectedGraph
    from bluefog_tpu.topology.mapping import ici_ring_order

    devs = ici_ring_order(_aot_topo("v5e:2x2").devices)
    assert [d.id for d in devs] == [0, 2, 3, 1]
    n = len(devs)
    mesh = Mesh(np.array(devs), ("bf",))
    sched = build_schedule(
        FullyConnectedGraph(n) if graph == "full" else graph(n))
    cap = pg.DEFAULT_AUTO_MAX_BYTES
    itemsize = np.dtype(dtype).itemsize
    sharding = NamedSharding(mesh, P("bf"))

    k = sched.num_slots
    deliver = jax.jit(shard_map(
        lambda v, b: pg.deliver_pallas(
            v[0], b[0], sched, "bf", accumulate=True)[None],
        mesh=mesh, in_specs=(P("bf"), P("bf")), out_specs=P("bf"),
        check_vma=False))
    v = jax.ShapeDtypeStruct((n, cap // itemsize), dtype, sharding=sharding)
    b = jax.ShapeDtypeStruct((n, k, cap // itemsize), dtype,
                             sharding=sharding)
    assert "tpu_custom_call" in deliver.lower(v, b).compile().as_text()


# ---------------------------------------------------------------------------
# Structural evidence (round-5): not just "it lowers" — the lowered Mosaic
# module must contain the remote-DMA/semaphore machinery the kernel design
# claims, with per-slot counts.  The module ships inside the custom call as
# MLIR *bytecode*; jaxlib's MLIR bindings parse it back to text (TPU dialect
# ops surface with allow_unregistered_dialects), which makes the op-level
# structure assertable without hardware.
# ---------------------------------------------------------------------------

import base64 as _base64
import json as _json
import re as _re


def _unescape_hlo_string(s: str) -> str:
    """StableHLO string-attr escaping: backslash + two hex digits."""
    out, i = [], 0
    while i < len(s):
        if s[i] == "\\":
            nxt = s[i + 1]
            if nxt in '\\"nt':
                out.append({"\\": "\\", '"': '"', "n": "\n", "t": "\t"}[nxt])
                i += 2
            else:
                out.append(chr(int(s[i + 1:i + 3], 16)))
                i += 3
        else:
            out.append(s[i])
            i += 1
    return "".join(out)


def mosaic_modules(stablehlo_txt: str):
    """Every Mosaic kernel embedded in a lowered program, parsed back to
    MLIR text.  Returns a list (one entry per tpu_custom_call)."""
    from jax._src.lib.mlir import ir

    mods = []
    for m in _re.finditer(r'backend_config = "((?:[^"\\]|\\.)*)"',
                          stablehlo_txt):
        cfg = _json.loads(_unescape_hlo_string(m.group(1)))
        body = cfg.get("custom_call_config", {}).get("body")
        if body is None:
            continue
        raw = _base64.b64decode(body + "===")
        ctx = ir.Context()
        ctx.allow_unregistered_dialects = True
        mods.append((cfg, str(ir.Module.parse(raw, ctx))))
    return mods


@pytest.mark.parametrize("accumulate", [False, True], ids=["put", "acc"])
def test_deliver_kernel_remote_dma_structure(accumulate, tpu_aot_topology):
    """Per slot s (one ICI rotation): exactly one remote DMA enqueue and
    its send+recv wait pair; one barrier signal per in-neighbor; ONE
    barrier wait for all n_shifts signals; one get_barrier_semaphore
    (upstream mpi_controller.cc Win* is the target)."""
    topo = tpu_aot_topology
    n = len(topo.devices)
    mesh = Mesh(np.array(topo.devices), ("bf",))
    sched = build_schedule(RingGraph(n))
    s = sched.num_slots

    fn = jax.jit(shard_map(
        lambda v, b: pg.deliver_pallas(
            v[0], b[0], sched, "bf", accumulate=accumulate)[None],
        mesh=mesh, in_specs=(P("bf"), P("bf")), out_specs=P("bf"),
        check_vma=False))
    x = jax.ShapeDtypeStruct((n, 256), jnp.float32,
                             sharding=NamedSharding(mesh, P("bf")))
    b = jax.ShapeDtypeStruct((n, s, 256), jnp.float32,
                             sharding=NamedSharding(mesh, P("bf")))
    mods = mosaic_modules(fn.lower(x, b).as_text())
    assert len(mods) == 1
    _, text = mods[0]
    assert text.count("tpu.enqueue_dma") == s
    assert text.count("tpu.wait_dma") == 2 * s
    assert text.count("tpu.sem_signal") == s
    assert text.count("tpu.sem_wait") == 1
    assert text.count("tpu.sem_barrier") == 1


def _one_chip(topo):
    from jax.sharding import SingleDeviceSharding

    return SingleDeviceSharding(topo.devices[0])


def test_latent_attention_kernels_compile_for_v5e(tpu_aot_topology):
    """The splash kernels at latent attention's published head: 192-wide
    queries and keys beside 128-wide values, 32 heads, T=4096, forward and
    fused backward.  Mosaic takes the 192 as it is (no padding to 256)."""
    from bluefog_tpu.ops.ring_attention import _splash_attention

    one = _one_chip(tpu_aot_topology)
    qk = jax.ShapeDtypeStruct((2, 4096, 32, 192), jnp.bfloat16, sharding=one)
    v = jax.ShapeDtypeStruct((2, 4096, 32, 128), jnp.bfloat16, sharding=one)

    def grads(q, k, v):
        return jax.grad(lambda q, k, v: _splash_attention(
            q, k, v, causal=True, scale=192 ** -0.5).astype(
                jnp.float32).sum(), argnums=(0, 1, 2))(q, k, v)

    txt = jax.jit(grads).lower(qk, qk, v).compile().as_text()
    assert txt.count("tpu_custom_call") >= 2
    assert "flash_attention_splash_mha_fwd" in txt
    assert "flash_mha_bwd_splash_mha_dkv" in txt


def test_grouped_matmul_kernels_compile_for_v5e(tpu_aot_topology):
    """``routed_experts`` on the Pallas grouped matmul at the published
    widths: 8,192 tokens of 2,048 choosing 8 of 256 experts, 16 of them held
    of width 768, so a row buffer of 8,192 of the 65,536 sorted rows; value
    and gradient, kernels named ``gmm`` / ``tgmm`` for the trace."""
    from bluefog_tpu.ops.moe import _row_buffer, routed_experts

    one = _one_chip(tpu_aot_topology)
    assert _row_buffer(8192 * 8, 16, 256) == 8192

    def shape(dims, dtype):
        return jax.ShapeDtypeStruct(dims, dtype, sharding=one)

    def value_and_grads(x, idx, weights, wg, wu, wd):
        def total(x, weights, wg, wu, wd):
            return (routed_experts(
                x, idx, weights, wg, wu, wd, num_experts=256, held=(0, 16),
                backend="gmm")[0].astype(jnp.float32) ** 2).sum()
        return jax.value_and_grad(total, argnums=(0, 1, 2, 3, 4))(
            x, weights, wg, wu, wd)

    txt = jax.jit(value_and_grads).lower(
        shape((8192, 2048), jnp.bfloat16), shape((8192, 8), jnp.int32),
        shape((8192, 8), jnp.float32), shape((16, 2048, 768), jnp.float32),
        shape((16, 2048, 768), jnp.float32),
        shape((16, 768, 2048), jnp.float32)).compile().as_text()
    # three products in the forward's loop; in the backward's, the three
    # once more, their three row transposes and three weight transposes
    assert len(_re.findall(r"%gmm(\.\d+)? = ", txt)) == 3 + 6
    assert len(_re.findall(r"%tgmm(\.\d+)? = ", txt)) == 3
    # the kernels run at the buffer's height, and nothing is 65,536 tall
    # but the sort's own vectors
    assert "bf16[8192,768]" in txt
    assert not _re.findall(r"\[65536,\d{2,}\]", txt)


def test_the_sums_by_token_hold_no_row_wide_scatter_on_v5e(tpu_aot_topology,
                                                          monkeypatch):
    """``routed_experts`` at SmallThinker's published shapes: 16,384 tokens
    of 2,560 choosing 6 of 64 experts, 16 held of width 768, so a row buffer
    of 49,152 of the 98,304 sorted rows.  The sums by token run in
    ``bf_moe_add_rows_by_token``, twice (``y``; ``d_x`` from the gate's and
    the up projection's rows): the compiled value and gradient holds no
    scatter of rows (the scalar ``d_weights`` one and the kernels' tile
    metadata remain), the nine ``gmm`` and three ``tgmm`` it held before,
    and fewer temporaries than the scatter form at the same shapes (no f32
    copy of the buffer is made for a scatter to read), which is asked for in
    turn."""
    from bluefog_tpu.ops import moe

    one = _one_chip(tpu_aot_topology)
    assert moe._row_buffer(16384 * 6, 16, 64) == 49152
    assert moe._sums_in_vmem(16384, 2560, "gmm")

    def shape(dims, dtype):
        return jax.ShapeDtypeStruct(dims, dtype, sharding=one)

    def compiled(in_vmem):
        monkeypatch.setattr(moe, "_sums_in_vmem", lambda t, d, b: in_vmem)

        # a function of its own a compile: the form is read as it is traced
        def value_and_grads(x, idx, weights, wg, wu, wd):
            def total(x, weights, wg, wu, wd):
                return (moe.routed_experts(
                    x, idx, weights, wg, wu, wd, num_experts=64,
                    held=(0, 16), backend="gmm",
                    activation="relu")[0].astype(jnp.float32) ** 2).sum()
            return jax.value_and_grad(total, argnums=(0, 1, 2, 3, 4))(
                x, weights, wg, wu, wd)

        return jax.jit(value_and_grads).lower(
            shape((16384, 2560), jnp.bfloat16), shape((16384, 6), jnp.int32),
            shape((16384, 6), jnp.float32),
            shape((16, 2560, 768), jnp.float32),
            shape((16, 2560, 768), jnp.float32),
            shape((16, 768, 2560), jnp.float32)).compile()

    def row_scatters(txt):
        return [s for s in _re.findall(r"= (\S+) scatter\(", txt)
                if ",2560]" in s]

    kernel, scatter = compiled(True), compiled(False)
    txt = kernel.as_text()
    assert _re.findall(r" scatter\(", txt) and not row_scatters(txt)
    assert len(row_scatters(scatter.as_text())) == 2
    assert not _re.findall(r"\[98304,\d{2,}\]", txt)
    assert "bf16[49152,768]" in txt and "bf16[49152,2560]" in txt
    assert len(_re.findall(r"%gmm(\.\d+)? = ", txt)) == 3 + 6
    assert len(_re.findall(r"%tgmm(\.\d+)? = ", txt)) == 3
    assert len(_re.findall(r"%bf_moe_add_rows_by_token(\.\d+)? = ",
                           txt)) == 2
    assert (kernel.memory_analysis().temp_size_in_bytes
            < scatter.memory_analysis().temp_size_in_bytes)


@pytest.mark.parametrize("ids,rows,width,tiles", [
    (8192, 25008, 2560, 25),        # phi-4-mini-flash's slice
    (16384, 18992, 2560, 19),       # smallthinker's
    (8192, 16160, 2048, 16),        # joyai's
    (16384, 50304, 768, 0)])        # gpt2-small's
def test_the_lookup_s_gradient_holds_no_row_wide_scatter_on_v5e(
        ids, rows, width, tiles, tpu_aot_topology, monkeypatch):
    """``take_rows`` at the decoder cells' tables, as a TPU takes it (the
    backend alone is patched: the rule reads the shapes).  From 2,048 columns
    on the compiled gradient holds one ``bf_embed_add_rows_by_id`` (the
    short last tile compiles), no scatter and no f32 zero table for the
    kernel to read, and the ids' sort; GPT-2's narrower table keeps
    ``jnp.take``'s scatter-add and no kernel (``tiles`` 0)."""
    from bluefog_tpu.ops import row_sums

    one = _one_chip(tpu_aot_topology)
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    assert row_sums._lookup_form(rows, width) == (
        "vmem" if tiles else "scatter")

    def gradient(table, at, probe):
        def total(table):
            with jax.named_scope("bf.embed.lookup"):
                x = row_sums.take_rows(table, at, jnp.bfloat16)
            return (x * probe).astype(jnp.float32).sum()
        return jax.grad(total)(table)

    txt = jax.jit(gradient).lower(
        jax.ShapeDtypeStruct((rows, width), jnp.float32, sharding=one),
        jax.ShapeDtypeStruct((1, ids), jnp.int32, sharding=one),
        jax.ShapeDtypeStruct((1, ids, width), jnp.bfloat16,
                             sharding=one)).compile().as_text()
    kernels = len(_re.findall(r"%bf_embed_add_rows_by_id(\.\d+)? = ", txt))
    if not tiles:
        assert kernels == 0 and len(_re.findall(r" scatter\(", txt)) == 1
        return
    assert -(-rows // row_sums.sums_tile(rows, width)) == tiles
    assert kernels == 1 and not _re.findall(r" scatter\(", txt)
    assert not _re.findall(rf"f32\[{rows},{width}\]\S* broadcast\(", txt)
    assert _re.findall(rf"s32\[{ids}\]\S*\) sort\(", txt)
    assert "custom_call_has_side_effect=true" not in txt


def _compile_cell_step(cell, monkeypatch):
    """A benchmark cell's step, built as ``chipbench/run.py`` builds it and
    compiled for v5e (one chip; the 2x2 ring for the four-rank cell), with
    what a process on the chip answers patched in where the steps ask."""
    import importlib
    import os
    import types

    from tests._util import REPO
    from chipbench import cell as cells
    from bluefog_tpu.topology.mapping import ici_ring_order

    topo = _aot_topo("v5e:2x2")
    manifest = cells.Manifest.load(os.path.join(REPO, "BENCHMARK.json"))
    config, traffic = cells.open_cell(manifest, cell)
    n = traffic["ranks"]
    devices = ici_ring_order(topo.devices) if n == 4 else [topo.devices[0]]
    mesh = Mesh(np.array(devices), ("bf",))
    family = manifest.module("families", config["family"]).build(config,
                                                                 traffic)
    ctx = types.SimpleNamespace(
        schedule=build_schedule(ExponentialTwoGraph(n)), axis_name="bf",
        mesh=mesh)
    opt, step = cells.build_step(family, config, traffic, ctx)

    def init(key):
        params, model_state = family.init(key)
        return ((params, model_state, opt.init(params)),
                family.make_batch(key))

    # the shapes first: an init pass is too short for the chip's kernels
    shapes = jax.eval_shape(init, jax.random.PRNGKey(0))
    if cell.startswith("gpt2s"):
        monkeypatch.setattr(      # the package exports a function by
            importlib.import_module(        # the module's name
                "bluefog_tpu.ops.ring_attention"),
            "_flash_eligible", lambda *a, **k: True)
    else:
        monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    sharding = NamedSharding(mesh, P("bf"))
    state, batch = jax.tree_util.tree_map(
        lambda t: jax.ShapeDtypeStruct((n,) + t.shape, t.dtype,
                                       sharding=sharding), shapes)
    return step.lower(state, batch).compile()


@pytest.mark.parametrize("cell,kernels,parent_temp", [
    ("phi4flash.t8192.solo", 1, 3_265_160_704),
    ("smallthinker.t16384.solo", 1, 3_452_474_880),
    ("joyai.t4096.solo", 2, 2_713_568_768),
    ("gpt2s.t2048.solo", 0, 8_057_458_688),
    ("gpt2s.t8192.solo", 0, 3_754_795_520),
    ("gpt2s.t2048.exp2x4", 0, 8_667_998_720)])
def test_the_decoder_steps_sum_the_table_s_gradient_in_vmem_on_v5e(
        cell, kernels, parent_temp, monkeypatch):
    """The benchmark's six decoder steps, built as ``chipbench/run.py``
    builds them and compiled for v5e (one chip; the 2x2 ring for the
    four-rank cell): one ``bf_embed_add_rows_by_id`` a lookup of a table
    of 2,048 columns or more (the MTP model looks its table up twice), no
    scatter under ``bf.embed.`` and no side effect on the kernel; GPT-2's
    tables keep XLA's scatter-adds and no kernel.  ``temp_size_in_bytes`` within 0.5 % of what the step took
    before the lookup had a rule of its own (PR 36's tree; GPT-2's to the
    byte)."""
    compiled = _compile_cell_step(cell, monkeypatch)
    txt = compiled.as_text()
    assert len(_re.findall(r"%bf_embed_add_rows_by_id(\.\d+)? = ",
                           txt)) == kernels
    scatters = [line for line in txt.splitlines()
                if _re.search(r" scatter\(", line) and "bf.embed." in line]
    assert bool(scatters) == (kernels == 0), scatters
    sides = _re.findall(
        r"%(\S+) = [^\n]*custom_call_has_side_effect=true", txt)
    assert not [name for name in sides if name.startswith("bf_embed")]
    temp = compiled.memory_analysis().temp_size_in_bytes
    if kernels:
        assert temp <= parent_temp * 1.005, (temp, parent_temp)
    else:
        assert temp == parent_temp


# the step's temporaries with whole f32 logits (PR 45's tree), batch, rows,
# V, chunks of head_loss, head_loss calls
@pytest.mark.parametrize("cell,whole_temp,batch,rows,vocab,chunks,heads", [
    ("lfm2moe.t8192.solo", 7_175_690_752, 4, 32768, 16384, 8, 1),
    ("joyai.t4096.solo", 2_669_011_968, 2, 8192, 16160, 4, 2)])
def test_the_step_holds_no_whole_logits_on_v5e(
        cell, whole_temp, batch, rows, vocab, chunks, heads, monkeypatch):
    """The head and the loss in token chunks (``ops/head_loss.py``), in the
    fullest cell's step and in the MTP pair's, optimizer included: no f32
    array of ``rows x V`` elements in any shape, a head's three matmuls in
    the chunk loop's body (and, untied, once more for the last chunk: a
    fourth in either place would be the logits made again for the backward
    pass), and the temporaries that whole logits cost stay gone:
    ``lfm2moe``'s step under 5.2 GiB where it took 6.68."""
    compiled = _compile_cell_step(cell, monkeypatch)
    txt = compiled.as_text()
    whole = [line.strip()[:200] for line in txt.splitlines()
             if _re.search(rf"f32\[(1,)?({rows}|{batch},{rows // batch}|"
                           rf"{chunks},{rows // chunks}),{vocab}\]", line)]
    assert not whole, whole[:3]
    matmuls = [line for line in txt.splitlines()
               if _re.search(r" convolution\(", line) and "bf.head." in line]
    if cell.startswith("lfm2moe"):      # tied: every chunk in the loop
        assert len(matmuls) == 3 and all(
            "/while/body/" in line for line in matmuls), matmuls
    else:       # untied, four chunks a head: the loop of three and the last
        assert len(matmuls) == 2 * 3 * heads, matmuls
    temp = compiled.memory_analysis().temp_size_in_bytes
    assert temp < whole_temp
    if cell.startswith("lfm2moe"):
        assert temp < 5.2 * 2 ** 30, temp


def test_selective_scan_kernels_compile_for_v5e(tpu_aot_topology):
    """The selective-scan kernels at the published Mamba mixer: 8,192
    tokens, 5,120 channels in blocks of 1,024, 16 states, bf16 ``x`` beside
    f32 ``delta``; value and all six gradients.  Mosaic takes ``B`` and
    ``C`` a chunk at a time in SMEM and the chunk's 65 states of a channel
    block (4 MiB) in VMEM; the names are the ones the trace shows."""
    from bluefog_tpu.ops.selective_scan import selective_scan

    one = _one_chip(tpu_aot_topology)

    def shape(dims, dtype=jnp.float32):
        return jax.ShapeDtypeStruct(dims, dtype, sharding=one)

    wide, narrow = (1, 8192, 5120), (1, 8192, 16)
    args = (shape(wide, jnp.bfloat16), shape(wide), shape((5120, 16)),
            shape(narrow, jnp.bfloat16), shape(narrow, jnp.bfloat16),
            shape((5120,)))

    def grads(*operands):
        return jax.grad(lambda *a: selective_scan(
            *a, backend="pallas").astype(jnp.float32).sum(),
            argnums=tuple(range(6)))(*operands)

    txt = jax.jit(grads).lower(*args).compile().as_text()
    assert txt.count("tpu_custom_call") == 2
    assert "bf_selective_scan_fwd" in txt and "bf_selective_scan_bwd" in txt


@pytest.mark.parametrize("dtype", [jnp.bfloat16, jnp.float32],
                         ids=["bf16", "f32"])
def test_kda_kernels_compile_for_v5e(dtype, tpu_aot_topology):
    """The delta-rule kernels at the published KDA layer's share: 8,192
    tokens, 16 heads of 128, an f32 log-decay a channel beside bf16 (or
    f32) ``q, k, v``; value and all five gradients.  Mosaic takes the
    chunk's ``jax.vjp`` as the backward kernel's body (transposed products,
    the f32 inverse's products at ``highest``), reads the operands as
    ``(64, 128)`` blocks of ``(B, T, H * d)`` with no relayout before
    them, and the names are the ones the trace shows."""
    from bluefog_tpu.ops.kda import kda

    one = _one_chip(tpu_aot_topology)

    def shape(dims, kind=jnp.float32):
        return jax.ShapeDtypeStruct(dims, kind, sharding=one)

    wide = (1, 8192, 16, 128)
    args = (shape(wide, dtype), shape(wide, dtype), shape(wide, dtype),
            shape(wide), shape(wide[:3]))

    def grads(*operands):
        return jax.grad(lambda *a: kda(
            *a, backend="pallas").astype(jnp.float32).sum(),
            argnums=tuple(range(5)))(*operands)

    txt = jax.jit(grads).lower(*args).compile().as_text()
    assert txt.count("tpu_custom_call") == 2
    assert "bf_kda_fwd" in txt and "bf_kda_bwd_chunks" in txt
    copies = [line for line in txt.splitlines()
              if _re.search(r" (copy|transpose)\(", line)
              and "8192,16,128" in line.replace(" ", "")]
    assert not copies, copies


@pytest.mark.parametrize("window", [512, None], ids=["window", "full"])
def test_differential_attention_kernels_compile_for_v5e(window,
                                                        tpu_aot_topology):
    """The splash kernels at differential attention's published maps: 40
    heads of 64-wide queries and keys beside 128-wide values, T=8192, under
    the 512-key window (``LocalMask``) and in full, forward and fused
    backward, keys and values repeated from 20 and 10 heads."""
    from bluefog_tpu.ops.ring_attention import _repeat_heads, _splash_attention

    one = _one_chip(tpu_aot_topology)
    q = jax.ShapeDtypeStruct((1, 8192, 40, 64), jnp.bfloat16, sharding=one)
    k = jax.ShapeDtypeStruct((1, 8192, 20, 64), jnp.bfloat16, sharding=one)
    v = jax.ShapeDtypeStruct((1, 8192, 10, 128), jnp.bfloat16, sharding=one)

    def grads(q, k, v):
        return jax.grad(lambda q, k, v: _splash_attention(
            q, _repeat_heads(k, 40), _repeat_heads(v, 40), causal=True,
            scale=0.125, window=window).astype(jnp.float32).sum(),
            argnums=(0, 1, 2))(q, k, v)

    txt = jax.jit(grads).lower(q, k, v).compile().as_text()
    assert txt.count("tpu_custom_call") >= 2
    assert "flash_attention_splash_mha_fwd" in txt
    assert "flash_mha_bwd_splash_mha_dkv" in txt


@pytest.mark.parametrize("window", [4096, None], ids=["window", "full"])
def test_grouped_query_attention_kernels_compile_for_v5e(window,
                                                         tpu_aot_topology):
    """The splash kernels at the grouped-query layers' published heads: 28
    query heads over 4 key/value heads of 128, T=16,384 (the published
    context), under the 4,096-key window (``LocalMask``) and in full, forward
    and fused backward, keys and values repeated from 4 heads."""
    from bluefog_tpu.ops.ring_attention import _repeat_heads, _splash_attention

    one = _one_chip(tpu_aot_topology)
    q = jax.ShapeDtypeStruct((1, 16384, 28, 128), jnp.bfloat16, sharding=one)
    kv = jax.ShapeDtypeStruct((1, 16384, 4, 128), jnp.bfloat16, sharding=one)

    def grads(q, k, v):
        return jax.grad(lambda q, k, v: _splash_attention(
            q, _repeat_heads(k, 28), _repeat_heads(v, 28), causal=True,
            scale=128 ** -0.5, window=window).astype(jnp.float32).sum(),
            argnums=(0, 1, 2))(q, k, v)

    txt = jax.jit(grads).lower(q, kv, kv).compile().as_text()
    assert txt.count("tpu_custom_call") >= 2
    assert "flash_attention_splash_mha_fwd" in txt
    assert "flash_mha_bwd_splash_mha_dkv" in txt


def _lfm2_shape(one, dims, dtype=jnp.bfloat16):
    return jax.ShapeDtypeStruct(dims, dtype, sharding=one)


def test_full_attention_at_64_wide_grouped_heads_compiles_for_v5e(
        tpu_aot_topology):
    """``lfm2-8b-a1b``'s attention layer at its cell's shape: four sequences
    of 8,192, 32 query over 8 key/value heads of **64**, keys and values
    repeated, causal over every key, forward and fused backward."""
    from bluefog_tpu.ops.ring_attention import _repeat_heads, _splash_attention

    one = _one_chip(tpu_aot_topology)

    def grads(q, k, v):
        return jax.grad(lambda q, k, v: _splash_attention(
            q, _repeat_heads(k, 32), _repeat_heads(v, 32), causal=True,
            scale=64 ** -0.5).astype(jnp.float32).sum(),
            argnums=(0, 1, 2))(q, k, v)

    kv = _lfm2_shape(one, (4, 8192, 8, 64))
    txt = jax.jit(grads).lower(_lfm2_shape(one, (4, 8192, 32, 64)), kv,
                               kv).compile().as_text()
    assert "flash_attention_splash_mha_fwd" in txt
    assert "flash_mha_bwd_splash_mha_dkv" in txt


@pytest.mark.duration_budget(60)   # nine grouped products at 65,536 rows
def test_grouped_matmuls_at_the_deployment_s_load_compile_for_v5e(
        tpu_aot_topology):
    """``routed_experts`` at ``lfm2moe.t8192.solo``'s layer: 32,768 tokens of
    2,048 choosing 4 of 32 experts, 8 held of width **1,792** (an 896 tile,
    seven lanes' worth), so a row buffer of 65,536 of the 131,072 sorted
    rows and 32 tiles of 1,024 tokens' sums; value and gradient."""
    from bluefog_tpu.ops.moe import _gmm_tiling, _row_buffer, routed_experts
    from bluefog_tpu.ops.row_sums import sums_tile

    one = _one_chip(tpu_aot_topology)
    assert _row_buffer(32768 * 4, 8, 32) == 65536
    assert _gmm_tiling(65536, 2048, 1792) == (256, 1024, 896)
    assert sums_tile(32768, 2048) == 1024

    def value_and_grads(x, idx, weights, wg, wu, wd):
        def total(x, weights, wg, wu, wd):
            return (routed_experts(
                x, idx, weights, wg, wu, wd, num_experts=32, held=(0, 8),
                backend="gmm")[0].astype(jnp.float32) ** 2).sum()
        return jax.value_and_grad(total, argnums=(0, 1, 2, 3, 4))(
            x, weights, wg, wu, wd)

    f32 = jnp.float32
    txt = jax.jit(value_and_grads).lower(
        _lfm2_shape(one, (32768, 2048)),
        _lfm2_shape(one, (32768, 4), jnp.int32),
        _lfm2_shape(one, (32768, 4), f32),
        _lfm2_shape(one, (8, 2048, 1792), f32),
        _lfm2_shape(one, (8, 2048, 1792), f32),
        _lfm2_shape(one, (8, 1792, 2048), f32)).compile().as_text()
    assert len(_re.findall(r"%gmm(\.\d+)? = ", txt)) == 3 + 6
    assert len(_re.findall(r"%tgmm(\.\d+)? = ", txt)) == 3
    assert "bf16[65536,1792]" in txt
    assert txt.count("bf_moe_add_rows_by_token") >= 2


def test_short_convolution_kernels_compile_for_v5e(tpu_aot_topology):
    """The gate-convolution-gate kernels at the cell's shape: they read the
    in projection's ``(4, 8192, 3 * 2048)`` output as it lies (no slice of
    it is copied for them) and write one cotangent of that shape back."""
    from bluefog_tpu.ops.short_conv import gated_short_conv

    one = _one_chip(tpu_aot_topology)

    def value_and_grads(bcz, kernel):
        return jax.value_and_grad(lambda bcz, kernel: gated_short_conv(
            bcz, kernel, backend="pallas").astype(jnp.float32).sum(),
            argnums=(0, 1))(bcz, kernel)

    txt = jax.jit(value_and_grads).lower(
        _lfm2_shape(one, (4, 8192, 3 * 2048)),
        _lfm2_shape(one, (3, 2048), jnp.float32)).compile().as_text()
    assert txt.count("tpu_custom_call") == 2
    assert "bf_sconv_fwd" in txt and "bf_sconv_bwd" in txt
    copies = [line for line in txt.splitlines()
              if _re.search(r" (copy|transpose|slice)\(", line)
              and "4,8192," in line.replace(" ", "")]
    assert not copies, copies


def test_convolution_and_silu_kernels_compile_for_v5e(tpu_aot_topology):
    """``bf_cconv_fwd`` / ``bf_cconv_bwd`` at ``nemotron3nano.t8192.solo``'s
    shapes, within the default scoped VMEM (the op asks for no other): they
    read the in projection's ``(2, 8192, 10304)`` output as it lies, channels
    4,096 : 10,240 under 4 taps, and write the scan's three operands as three
    arrays, so no slice of the operand or of the result is copied."""
    from bluefog_tpu.ops.short_conv import silu_short_conv

    one = _one_chip(tpu_aot_topology)

    def value_and_grads(y, w_in, kernel, bias):
        def total(y, w_in, kernel, bias):
            # 10,304 channels are no whole number of lanes: as in the
            # model, a matmul writes them in the layout the kernels read
            return sum(
                (out.astype(jnp.float32) ** 2).sum()
                for out in silu_short_conv(
                    y @ w_in, kernel, bias, offset=4096,
                    pieces=(4096, 1024, 1024), backend="pallas"))
        return jax.value_and_grad(total, argnums=(0, 1, 2, 3))(
            y, w_in, kernel, bias)

    txt = jax.jit(value_and_grads).lower(
        _lfm2_shape(one, (2, 8192, 256)), _lfm2_shape(one, (256, 10304)),
        _lfm2_shape(one, (4, 6144), jnp.float32),
        _lfm2_shape(one, (6144,), jnp.float32)).compile().as_text()
    assert txt.count("tpu_custom_call") == 6
    assert len(_re.findall(r"%\S*bf_cconv_fwd\S* = ", txt)) == 3
    assert len(_re.findall(r"%\S*bf_cconv_bwd\S* = ", txt)) == 3
    assert "vmem_limit_bytes" not in txt
    copies = [line for line in txt.splitlines()
              if _re.search(r" (copy|transpose|slice)\(", line)
              and "2,8192," in line.replace(" ", "")]
    assert not copies, copies


def test_the_nemotron_step_holds_no_f32_convolution_on_v5e(monkeypatch):
    """``nemotron3nano.t8192.solo``'s step, optimizer included: a kernel call
    a piece, layer and pass (3 x 4 layers, the forward twice under remat),
    no f32 tensor of the convolution's ``(2, 8192, 6144)`` under
    ``bf.ssd.conv`` (as XLA compiled the ``jax.numpy`` form it held 76:
    the f32 copy of the slice, its padded form and the pre-activation, in
    every pass), and the step's temporaries under what they were with them
    (3,529,905,152 bytes at PR 48)."""
    compiled = _compile_cell_step("nemotron3nano.t8192.solo", monkeypatch)
    txt = compiled.as_text()
    assert len(_re.findall(r"%bf_cconv_fwd(\.\d+)? = ", txt)) == 3 * 4 * 2
    assert len(_re.findall(r"%bf_cconv_bwd(\.\d+)? = ", txt)) == 3 * 4
    wide = [line.strip()[:200] for line in txt.splitlines()
            if "bf.ssd.conv" in line and _re.search(       # what is written
                r" = \(?[^=]*f32\[2,819\d,\d+\][^=]* (fusion|custom-call|"
                r"copy)\(", line)]
    assert not wide, wide[:3]
    assert compiled.memory_analysis().temp_size_in_bytes < 3.0 * 2 ** 30
    # the fence: a layer's three dx (with dz and d dt) meet in ONE fusion
    # that writes the in projection's cotangent, not in both of its matmuls
    kernels = set(_re.findall(r"%(bf_cconv_bwd[.\d]*) = ", txt))
    pieces = {name for name, source in _re.findall(
        r"%(\S+) = \S+ get-tuple-element\(%(\S+)\), index=0", txt)
        if source in kernels}
    readers = [line for line in txt.splitlines()
               if _re.search(r" = .* (fusion|custom-call)\(", line)
               and pieces & set(_re.findall(r"%([\w.\-]+)", line.split(
                   " = ", 1)[1]))]
    assert len(pieces) == 12 and len(readers) == 4, readers
    assert all(_re.search(r" = bf16\[2,8192,10304\]\S* fusion\(", line)
               for line in readers), readers


def test_normalised_convolution_kernels_compile_for_v5e(tpu_aot_topology):
    """``bf_cconv_fwd`` / ``bf_cconv_bwd`` as ``ling3flash.t8192.solo``'s KDA
    layers call them: three projections' ``(1, 8192, 2048)`` bf16 outputs
    under 4 taps, q and k normalised over each head's 128 channels inside the
    kernels (the scale an operand: one body for both), v plain; within the
    default scoped VMEM, two bodies a direction, and no f32 tensor of that
    shape between the matmuls and the kernels."""
    from bluefog_tpu.ops.short_conv import silu_short_conv

    one = _one_chip(tpu_aot_topology)
    norms = ((128, 1e-6, 128 ** -0.5), (128, 1e-6, 1.0), None)

    def value_and_grads(y, w, kernels):
        def total(y, w, kernels):
            return sum(silu_short_conv(
                y @ w[i], kernels[i], jnp.zeros((2048,)), l2norm=norm,
                backend="pallas").astype(jnp.float32)[..., ::128].sum()
                for i, norm in enumerate(norms))
        return jax.value_and_grad(total, argnums=(0, 1, 2))(y, w, kernels)

    txt = jax.jit(value_and_grads).lower(
        _lfm2_shape(one, (1, 8192, 256)), _lfm2_shape(one, (3, 256, 2048)),
        _lfm2_shape(one, (3, 4, 2048), jnp.float32)).compile().as_text()
    for kernel in ("bf_cconv_fwd", "bf_cconv_bwd"):
        calls = [line for line in txt.splitlines()
                 if _re.search(rf"%\S*{kernel}\S* = ", line)]
        assert len(calls) == 3, calls
        assert len({_re.search(r'"body":"([^"]*)"', line).group(1)
                    for line in calls}) == 2
    assert txt.count("tpu_custom_call") == 6
    assert "vmem_limit_bytes" not in txt
    wide = [line.strip()[:200] for line in txt.splitlines()
            if _re.search(r" = \(?[^=]*f32\[1,8192,2048\][^=]* (fusion|"
                          r"custom-call|copy|convolution)\(", line)]
    assert not wide, wide[:3]


def test_the_ling_step_holds_no_f32_convolution_on_v5e(monkeypatch):
    """``ling3flash.t8192.solo``'s step, optimizer included: a kernel call a
    tensor, layer and pass (3 x 6 layers, the forward twice under remat) in
    two bodies a direction (normalised and plain), no f32 tensor of a
    projection's ``(1, 8192, 2048)`` written under ``bf.kda.conv`` (as XLA
    compiled the ``jax.numpy`` form 198 fusions held such ops and the
    projections wrote f32), and the step's temporaries under what they were
    with them (2,546,220,544 bytes at PR 49)."""
    compiled = _compile_cell_step("ling3flash.t8192.solo", monkeypatch)
    txt = compiled.as_text()
    bodies = set()
    for kernel, count in (("bf_cconv_fwd", 3 * 6 * 2), ("bf_cconv_bwd",
                                                         3 * 6)):
        calls = [line for line in txt.splitlines()
                 if _re.search(rf"%{kernel}(\.\d+)? = ", line)]
        assert len(calls) == count, (kernel, len(calls))
        assert all("bf.kda.conv" in line for line in calls)
        bodies |= {(kernel, _re.search(r'"body":"([^"]*)"', line).group(1))
                   for line in calls}
    assert len(bodies) <= 4
    wide = [line.strip()[:200] for line in txt.splitlines()
            if "bf.kda.conv" in line and _re.search(
                r" = \(?[^=]*f32\[1,819\d,2048\][^=]* (fusion|custom-call|"
                r"copy|convolution)\(", line)]
    assert not wide, wide[:3]
    assert compiled.memory_analysis().temp_size_in_bytes < 2_546_220_544


def test_gpt2_attention_sublayer_keeps_its_layout_copies_few_on_v5e(
        tpu_aot_topology):
    """One GPT-2 block of ``gpt2s.t2048.solo`` (batch 8, T=2048, 768 wide,
    12 heads of 64, bf16) around the splash kernels, value and gradients:
    how many stand-alone copies of an activation (25 MB) XLA schedules
    between the projections and the kernels.  With ``nn.Dense`` -> ``split``
    -> ``reshape`` -> ``transpose`` there were 14 inside the block (two
    relayouts a tensor each way, and more around the split); ``HeadDense``
    writes the fused projection channel-major and leaves 8, one a tensor
    each way and the output's two (PERF.md, PR 33: none at all is possible,
    with the heads inside the dot, and at 64-wide heads the chip then runs
    the dots at half rate).  This keeps the others from coming back.  (Two
    more sit at this function's own boundary, where a parameter and a
    result have the default layout: ``x`` in, ``dx`` out.)"""
    from bluefog_tpu.models.transformer import Block, GPTConfig
    from bluefog_tpu.ops.ring_attention import _splash_attention
    from bluefog_tpu.utils.inspect import layout_copies

    one = _one_chip(tpu_aot_topology)
    cfg = GPTConfig(dtype=jnp.bfloat16)
    block = Block(cfg)

    def attn_fn(q, k, v):
        return _splash_attention(q, k, v, causal=True, scale=64 ** -0.5)

    x = jax.ShapeDtypeStruct((8, 2048, 768), jnp.bfloat16, sharding=one)
    params = jax.tree_util.tree_map(
        lambda t: jax.ShapeDtypeStruct(t.shape, t.dtype, sharding=one),
        jax.eval_shape(lambda: block.init(
            jax.random.PRNGKey(0), jnp.zeros((1, 128, 768), jnp.bfloat16),
            lambda q, k, v: q)))

    def grads(params, x):
        return jax.grad(lambda p, x: block.apply(p, x, attn_fn).astype(
            jnp.float32).sum(), argnums=(0, 1))(params, x)

    txt = jax.jit(grads).lower(params, x).compile().as_text()
    assert "flash_attention_splash_mha_fwd" in txt
    activation = 8 * 2048 * 768 * 2
    large = [c for c in layout_copies(txt, largest=64)["largest"]
             if c[0] >= activation]
    inside = [c for c in large if "Block" in c[2]]
    assert len(inside) <= 8, inside
    assert len(large) - len(inside) <= 2, large


# tokens, width, experts, top_k, groups, kept, router: the routers of
# ling3flash, joyai, lfm2moe and smallthinker as their cells call them
_ROUTERS = {
    "ling3flash": (8192, 2560, 512, 8, 8, 4, "sigmoid"),
    "joyai": (8192, 2048, 256, 8, 1, 1, "sigmoid"),
    "lfm2moe": (32768, 2048, 32, 4, 1, 1, "sigmoid"),
    "smallthinker": (16384, 2560, 64, 6, 1, 1, "softmax"),
    "nemotron3nano": (16384, 2688, 128, 6, 1, 1, "sigmoid"),
}


@pytest.mark.parametrize("cell", sorted(_ROUTERS))
def test_the_routers_selection_is_one_kernel_and_no_larger_on_v5e(
        cell, tpu_aot_topology, monkeypatch):
    """A router call at a cell's shape, value and gradient (by ``x``, the
    router's kernel and the bias), compiled for a v5e as the chip's rule
    takes it: one ``bf_moe_select`` custom call — the group stage of
    ``ling3flash``'s 8 groups inside it — and under it no sort, no gather
    and no scatter; then in the sorted form, asked for in turn, which holds
    the row sort.  **The guard on the size**: the kernel form's serialized
    executable, what a warm start reads and loads, is within 1.5 times the
    sorted form's (PR 44's unrolled XLA rounds were 7 times it over twelve
    calls and were refused for the set-up seconds that cost, PERF.md
    section 6)."""
    from jax.experimental import serialize_executable

    from bluefog_tpu.ops import moe

    t, d, e, k, n_group, topk_group, kind = _ROUTERS[cell]
    one = _one_chip(tpu_aot_topology)
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    assert moe._select_form(t, e, k, n_group) == "kernel"

    def compiled():
        # a function of its own a compile: the form is read as it is traced
        def value_and_grads(x, w, bias, probe):
            def total(x, w, bias):
                if kind == "softmax":
                    idx, weights = moe.softmax_topk_router(x, w, top_k=k)
                else:
                    idx, weights = moe.sigmoid_topk_router(
                        x, w, bias, top_k=k, scale=2.5, n_group=n_group,
                        topk_group=topk_group)
                return jnp.sum(weights * probe), idx
            return jax.value_and_grad(total, argnums=(0, 1, 2),
                                      has_aux=True)(x, w, bias)

        def shape(dims, dtype=jnp.float32):
            return jax.ShapeDtypeStruct(dims, dtype, sharding=one)

        return jax.jit(value_and_grads).lower(
            shape((t, d), jnp.bfloat16), shape((d, e)), shape((e,)),
            shape((t, k))).compile()

    def size(executable):
        return len(serialize_executable.serialize(executable)[0])

    kernel = compiled()
    txt = kernel.as_text()
    assert len(_re.findall(r"%bf_moe_select(\.\d+)? = ", txt)) == 1
    assert _re.findall(r"bf\.moe\.route\)?/bf_moe_select", txt)   # its scope
    for op in ("sort", "gather", "scatter"):
        assert not _re.findall(rf" {op}\(", txt), op
    monkeypatch.setattr(moe, "_select_form", lambda *a, **kw: "sorted")
    sorted_form = compiled()
    assert _re.findall(r" sort\(", sorted_form.as_text())
    assert "bf_moe_select" not in sorted_form.as_text()
    assert size(kernel) <= 1.5 * size(sorted_form)
    assert (kernel.memory_analysis().temp_size_in_bytes
            <= sorted_form.memory_analysis().temp_size_in_bytes)


def test_ssd_kernels_compile_for_v5e(tpu_aot_topology):
    """The state-space kernels at the published Mamba-2 layer: 2 x 8,192
    tokens, 64 heads of 64 in slabs of two, state 128, 8 groups, bf16
    ``x``, ``B`` and ``C`` beside f32 steps; value and all six gradients.
    Mosaic takes the group chunk's ``jax.vjp`` as the backward kernel's
    body, reads the operands as ``(128, 512)`` and ``(128, 128)`` blocks of
    ``(B, T, H * P)`` and ``(B, T, G * N)`` with no relayout before them,
    and the names are the ones the trace shows."""
    from bluefog_tpu.ops.ssd import ssd

    one = _one_chip(tpu_aot_topology)

    def shape(dims, kind=jnp.float32):
        return jax.ShapeDtypeStruct(dims, kind, sharding=one)

    args = (shape((2, 8192, 64, 64), jnp.bfloat16), shape((2, 8192, 64)),
            shape((64,)), shape((2, 8192, 8, 128), jnp.bfloat16),
            shape((2, 8192, 8, 128), jnp.bfloat16), shape((64,)))

    def grads(*operands):
        return jax.grad(lambda *a: ssd(
            *a, backend="pallas").astype(jnp.float32).sum(),
            argnums=tuple(range(6)))(*operands)

    txt = jax.jit(grads).lower(*args).compile().as_text()
    assert txt.count("tpu_custom_call") == 2
    assert "bf_ssd_fwd" in txt and "bf_ssd_bwd" in txt
    copies = [line for line in txt.splitlines()
              if _re.search(r" (copy|transpose)\(", line)
              and "8192,64,64" in line.replace(" ", "")]
    assert not copies, copies


def test_full_attention_at_sixteen_queries_a_key_head_compiles_for_v5e(
        tpu_aot_topology):
    """The splash kernels at Nemotron-H's grouped heads: 32 query heads over
    2 key/value heads of 128 (sixteen a group; the other cells run 1, 4 and
    7), two sequences of 8,192, full causal, forward and fused backward,
    keys and values repeated from 2 heads."""
    from bluefog_tpu.ops.ring_attention import _repeat_heads, _splash_attention

    one = _one_chip(tpu_aot_topology)
    q = jax.ShapeDtypeStruct((2, 8192, 32, 128), jnp.bfloat16, sharding=one)
    kv = jax.ShapeDtypeStruct((2, 8192, 2, 128), jnp.bfloat16, sharding=one)

    def grads(q, k, v):
        return jax.grad(lambda q, k, v: _splash_attention(
            q, _repeat_heads(k, 32), _repeat_heads(v, 32), causal=True,
            scale=128 ** -0.5).astype(jnp.float32).sum(),
            argnums=(0, 1, 2))(q, k, v)

    txt = jax.jit(grads).lower(q, kv, kv).compile().as_text()
    assert txt.count("tpu_custom_call") >= 2
    assert "flash_attention_splash_mha_fwd" in txt
    assert "flash_mha_bwd_splash_mha_dkv" in txt


def test_ungated_grouped_matmuls_1856_wide_compile_for_v5e(tpu_aot_topology):
    """``routed_experts`` on the Pallas grouped matmul at Nemotron-H's
    experts: 16,384 tokens of 2,688 choosing 6 of 128, 8 of them held,
    **ungated** and 1,856 wide (14.5 lanes), so two leaves, a row buffer of
    12,288 of the 98,304 sorted rows and the products at 1,920 columns
    (zero-padded inside the op: every tile whole); value and gradient."""
    from bluefog_tpu.ops.moe import _row_buffer, routed_experts

    one = _one_chip(tpu_aot_topology)
    assert _row_buffer(16384 * 6, 8, 128) == 12288

    def shape(dims, dtype):
        return jax.ShapeDtypeStruct(dims, dtype, sharding=one)

    def value_and_grads(x, idx, weights, wu, wd):
        def total(x, weights, wu, wd):
            return (routed_experts(
                x, idx, weights, None, wu, wd, num_experts=128, held=(0, 8),
                backend="gmm", activation="relu2")[0].astype(
                    jnp.float32) ** 2).sum()
        return jax.value_and_grad(total, argnums=(0, 1, 2, 3))(
            x, weights, wu, wd)

    compiled = jax.jit(value_and_grads).lower(
        shape((16384, 2688), jnp.bfloat16), shape((16384, 6), jnp.int32),
        shape((16384, 6), jnp.float32), shape((8, 2688, 1856), jnp.float32),
        shape((8, 1856, 2688), jnp.float32)).compile()
    txt = compiled.as_text()
    # two products in the forward's loop; in the backward's, the two once
    # more, their two row transposes and two weight transposes
    assert len(_re.findall(r"%gmm(\.\d+)? = ", txt)) == 2 + 4
    assert len(_re.findall(r"%tgmm(\.\d+)? = ", txt)) == 2
    assert "bf16[12288,1920]" in txt and "bf16[12288,1856]" not in txt
    # the gradients come back at the leaves' own shapes
    shapes = [g.shape for g in jax.tree_util.tree_leaves(
        compiled.out_info[1])]
    assert shapes[2:] == [(8, 2688, 1856), (8, 1856, 2688)]


def test_the_looped_step_is_one_round_s_code_and_fits_a_chip(monkeypatch):
    """``ouro.t4096.solo``'s step compiled for a v5e: every leaf once in
    the arguments whatever the rounds (6.84 GiB of state); the rounds a
    loop, so one round's block passes are compiled (the attention kernel's
    forward call 8 + 8 times in the text, its fused backward 8) and the
    code stays near the size the chip machine's compile cache has kept for
    ``joyai`` (242 MiB) where the unrolled rounds were 863 MiB and never
    cached; four exits' heads; the temporaries under what leaves the
    agreement check its room beside 6.84 + 2.28 GiB (PERF.md section 6,
    PR 51: the runtime reserves less than ``temp_size_in_bytes`` says)."""
    compiled = _compile_cell_step("ouro.t4096.solo", monkeypatch)
    txt = compiled.as_text()
    memory = compiled.memory_analysis()
    assert 6.8 * 2 ** 30 < memory.argument_size_in_bytes < 6.9 * 2 ** 30
    assert memory.temp_size_in_bytes < 7.8 * 2 ** 30, (
        memory.temp_size_in_bytes)
    assert memory.generated_code_size_in_bytes < 250 * 2 ** 20, (
        memory.generated_code_size_in_bytes)
    forward = len(_re.findall(r"%(?:flash_attention|splash_mha_fwd)\S* = ",
                              txt))
    backward = len(_re.findall(r"%(?:flash_mha_bwd|splash_mha_dkv)\S* = ",
                               txt))
    assert (forward, backward) == (16, 8), (forward, backward)
    assert "bf.loop.round/" in txt
    for r in range(1, 5):
        assert _re.search(rf"exit_{r}\)?/bf\.head\.logits", txt)
    whole = [line.strip()[:200] for line in txt.splitlines()
             if _re.search(r"f32\[(1,)?4096,49152\]", line)]
    assert not whole, whole[:3]
