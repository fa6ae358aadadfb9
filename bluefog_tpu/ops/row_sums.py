"""Rows summed at an index with the sums held in VMEM, and the table lookup
whose gradient is that sum.

XLA's TPU scatter-add moves one row at a time through HBM, whatever the row
holds (PERF.md section 6, PR 35 and PR 37).  :func:`add_rows_at` is the same
sum as a Pallas kernel: ``acc[index[r]] += rows[r]`` with a tile of ``acc``
in VMEM.  Two callers: the expert layer's sums by token
(``ops/moe.py::routed_experts``: ``y`` and ``d_x``) and the gradient of a
token embedding (:func:`take_rows`: the cotangent's rows summed at their
ids).
"""

from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
from jax import lax

from bluefog_tpu.metrics import comm as metrics_comm
from bluefog_tpu.tracing import startup

__all__ = ["sums_tile", "add_rows_at", "take_rows"]

_VMEM_SUMS = 21 * 2 ** 19     # 10.5 MB of the sums in VMEM a kernel step


def sums_tile(t: int, d: int) -> Optional[int]:
    """Rows of the ``(t, d)`` f32 sums that :func:`add_rows_at` holds in
    VMEM at a time, whole rows of them, or ``None`` where no tile fits: all
    ``t`` within ``_VMEM_SUMS``, else the largest power of two of at least
    a sublane's 8 (1,024 at 2,560 and at 2,048 columns, 2,048 at 768); the
    last tile is short where that does not divide ``t``, so ``t`` has to be
    whole sublanes.  **10.5 MB, so that the kernel stays within the 16 MB
    of VMEM every kernel may take unasked**: XLA keeps the expert layer's
    input, 84 MB at 16,384 x 2,560 bf16, in VMEM across the passes, where
    the gather of its rows takes 0.39 ms against 2.0 from HBM, and a kernel
    that asks for more evicts it (PERF.md section 6, PR 35)."""
    most = _VMEM_SUMS // (4 * d)
    if t <= most:
        return t
    if most < 8 or t % 8:
        return None
    return 1 << (most.bit_length() - 1)


def add_rows_at(acc, index, live, rows, w, *, name: str,
                interpret: bool = False):
    """``acc (t, d)`` f32 with the first ``live`` rows of the buffer added
    at their indices: ``acc[index[r]] += w[r] * (rows[0][r] + rows[1][r] +
    ..)`` in f32, in the buffer's order.  ``index (c,)`` int32 below ``t``,
    ``rows`` one or two ``(c, d)`` arrays, ``w (c,)`` f32 or ``None``.
    ``acc`` may be the shape ``(t, d)`` alone: the sums then start at zero
    in VMEM, and no zero array is written to HBM and read back.

    A Pallas kernel (``name`` in the trace) in place of XLA's scatter-add.
    A step holds the sums of ``ts`` rows of ``acc`` (:func:`sums_tile`) in
    VMEM and reads the blocks of ``rb`` buffer rows that hold an index of
    its tile, one while the one before is summed: each live row of the tile
    is added to its sum by one read-modify-write of a sublane.  Where the
    buffer is sorted by index, or by group and then by index as the expert
    layer's, a tile meets a few blocks and no more; which, XLA lists
    beforehand from each block's first and last index.  Blocks past
    ``live`` are neither read nor touched, whatever they hold."""
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    from_zero = isinstance(acc, tuple)
    t, d = acc if from_zero else acc.shape
    c = index.shape[0]
    ts = sums_tile(t, d)
    tiles = -(-t // ts)
    tail = t - (tiles - 1) * ts       # the last tile's rows: ts, or fewer
    rb = 128
    while c % rb:
        rb //= 2
    if rb < 16:
        rb = c
    blocks = c // rb
    weighted = w is not None
    # every tile's blocks: those with a live row whose index it holds
    held = jnp.arange(c, dtype=jnp.int32) < live
    first = jnp.where(held, index, t).reshape(blocks, rb).min(axis=1)
    last = jnp.where(held, index, -1).reshape(blocks, rb).max(axis=1)
    edges = jnp.arange(tiles, dtype=jnp.int32)[:, None] * ts
    meets = (first < edges + ts) & (last >= edges)          # (tiles, blocks)
    todo = jnp.argsort(~meets, axis=1, stable=True).astype(jnp.int32)
    scalars = (live.reshape(1), meets.sum(axis=1, dtype=jnp.int32),
               todo.reshape(-1), index) + ((w,) if weighted else ())
    arrays = (() if from_zero else (acc,)) + tuple(rows)

    def kernel(*refs):
        live_ref, count_ref, todo_ref, at_ref = refs[:4]
        w_ref = refs[4] if weighted else None
        inputs = refs[len(scalars):-5]
        acc_ref, rows_refs = ((None, inputs) if from_zero
                              else (inputs[0], inputs[1:]))
        out_ref, sums, stage, buf, sems = refs[-5:]
        tile = pl.program_id(0)
        base = tile * ts
        count = count_ref[tile]

        def first_row(j):       # of the tile's j-th block
            return todo_ref[tile * blocks + j] * rb

        def fetch(j, slot):
            at = first_row(j)
            at = pl.ds(pl.multiple_of(at, rb) if rb % 16 == 0 else at, rb)
            return [pltpu.make_async_copy(ref.at[at], stage.at[a, slot],
                                          sems.at[a, slot])
                    for a, ref in enumerate(rows_refs)]

        def fetch_first():
            for copy in fetch(0, 0):
                copy.start()

        def one_block(j, carry):
            slot = j % 2

            @pl.when(j + 1 < count)
            def _():
                for copy in fetch(j + 1, 1 - slot):
                    copy.start()

            for copy in fetch(j, slot):
                copy.wait()
            total = stage[0, slot].astype(jnp.float32)
            for a in range(1, len(rows_refs)):
                total = total + stage[a, slot].astype(jnp.float32)
            buf[...] = total
            start = first_row(j)

            def one_row(i, carry):
                u = at_ref[start + i] - base

                @pl.when((u >= 0) & (u < ts))
                def _():
                    row = buf[pl.ds(i, 1), :]
                    if weighted:
                        row = row * w_ref[start + i]
                    sums[pl.ds(u, 1), :] = sums[pl.ds(u, 1), :] + row
                return carry

            lax.fori_loop(0, jnp.clip(live_ref[0] - start, 0, rb),
                          one_row, jnp.int32(0))
            return carry

        def sum_tile(height):   # static: ts, or the short last tile's
            mine = pl.ds(pl.multiple_of(base, 8) if ts % 8 == 0 else base,
                         height)
            here = sums if height == ts else sums.at[pl.ds(0, height)]

            def add_and_store():
                lax.fori_loop(0, count, one_block, jnp.int32(0))
                back = pltpu.make_async_copy(here, out_ref.at[mine],
                                             sems.at[0, 2])
                back.start()
                back.wait()

            if from_zero:       # every tile writes, a tile of no row zeros
                pl.when(count > 0)(fetch_first)
                sums[...] = jnp.zeros(sums.shape, jnp.float32)
                add_and_store()
                return

            @pl.when(count > 0)     # else the tile stays as it is
            def _():
                fetch_first()
                own = pltpu.make_async_copy(acc_ref.at[mine], here,
                                            sems.at[0, 2])
                own.start()
                own.wait()
                add_and_store()

        if tail == ts:
            sum_tile(ts)
        else:
            pl.when(tile < tiles - 1)(lambda: sum_tile(ts))
            pl.when(tile == tiles - 1)(lambda: sum_tile(tail))

    startup.kernel_traced(name)
    return pl.pallas_call(
        kernel,
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=len(scalars), grid=(tiles,),
            in_specs=[pl.BlockSpec(memory_space=pl.ANY)] * len(arrays),
            out_specs=pl.BlockSpec(memory_space=pl.ANY),
            scratch_shapes=[pltpu.VMEM((ts, d), jnp.float32),
                            pltpu.VMEM((len(rows), 2, rb, d), rows[0].dtype),
                            pltpu.VMEM((rb, d), jnp.float32),
                            pltpu.SemaphoreType.DMA((len(rows), 3))]),
        out_shape=jax.ShapeDtypeStruct((t, d), jnp.float32),
        input_output_aliases={} if from_zero else {len(scalars): 0},
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary",)),
        interpret=interpret,
        name=name)(*scalars, *arrays)


# f32 bytes of a table row from which the kernel form is taken.  One lookup's
# gradient on a v5e, through XLA's scatter-add against sort + gather + kernel,
# ms (benchmarks/embed_grad_bench.py; PERF.md section 6, PR 37): 16,384 ids
# into 50,304 x 768 (3,072 bytes) 0.92 against 0.88, no gain; 8,192 into
# 16,160 x 2,048 (8,192 bytes) 1.38 against 0.60; 8,192 into 25,008 x 2,560
# 10.79 against 0.87; 16,384 into 18,992 x 2,560 9.03 against 1.15.
_KERNEL_ROW_BYTES = 8192


def _lookup_form(v: int, d: int) -> str:
    """How :func:`take_rows` sums a ``(v, d)`` table's gradient: ``'vmem'``
    (:func:`add_rows_at`) on a TPU wherever :func:`sums_tile` finds a tile
    and a row's f32 sums are at least ``_KERNEL_ROW_BYTES``, ``'scatter'``
    (``jnp.take``'s own transpose) elsewhere.  The sort, the gather of the
    rows and the kernel cost a row about the same whatever it holds
    (54-107 ns); XLA's scatter-add costs by the row's width and by where
    the table lies (the instruction alone 41 ns a row at 768 columns, 143
    at 2,048, 522 and 1,241 at 2,560).  Tests ask for ``'vmem_interpret'``, the kernel in the
    Pallas interpreter, by patching this function."""
    in_vmem = (jax.default_backend() == "tpu" and sums_tile(v, d) is not None
               and 4 * d >= _KERNEL_ROW_BYTES)
    return "vmem" if in_vmem else "scatter"


@functools.partial(jax.custom_vjp, nondiff_argnums=(2,))
def take_rows(table, ids, dtype):
    """``table.astype(dtype)[ids]``: a token embedding's lookup, as
    ``flax.linen.Embed`` computes it (``jnp.take``: a negative id counts
    from the end, an id outside the table reads NaN), with a gradient rule
    of its own; its operations carry the ``jax.named_scope`` of the call.

    The gradient of ``table (v, d)`` is the cotangent's rows summed at
    their ids.  JAX transposes ``jnp.take`` to a scatter-add, which the TPU
    executes a row at a time; here (:func:`_lookup_form`) the rows are put
    in the order of their ids (a sort of the ids, a gather of the rows) and
    summed in f32 by :func:`add_rows_at` under the trace name
    ``bf_embed_add_rows_by_id``, a tile of the table's rows in VMEM at a
    time, starting from zero there.  With metrics on, every execution of
    the rule adds the ids it looked up to ``bf_embed_rows_total`` and those
    the kernel summed to ``bf_embed_vmem_rows_total``."""
    return jnp.take(table.astype(dtype), ids, axis=0)


def _take_rows_fwd(table, ids, dtype):
    return take_rows(table, ids, dtype), (table, ids)


def _take_rows_bwd(dtype, res, g):
    table, ids = res        # the table for its shape and dtype alone
    v, d = table.shape
    form = _lookup_form(v, d)
    if form == "scatter":       # what JAX transposes jnp.take to
        d_table = jnp.zeros((v, d), dtype).at[ids].add(g, mode="drop")
    else:
        at = ids.reshape(-1).astype(jnp.int32)
        at = jnp.where(at < 0, at + v, at)
        # an id outside the table read a fill: no row takes its gradient
        at = jnp.where((at >= 0) & (at < v), at, v)
        order = jnp.argsort(at)
        d_table = add_rows_at(
            (v, d), at[order], (at < v).sum(dtype=jnp.int32),
            (g.reshape(-1, d)[order],), None,
            name="bf_embed_add_rows_by_id",
            interpret=form == "vmem_interpret")
    d_table = metrics_comm.count(d_table.astype(table.dtype), [
        ("bf_embed_rows_total", float(ids.size)),
        ("bf_embed_vmem_rows_total",
         0.0 if form == "scatter" else float(ids.size))])
    return d_table, None


take_rows.defvjp(_take_rows_fwd, _take_rows_bwd)
