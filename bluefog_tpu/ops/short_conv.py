"""The gate, causal depthwise convolution and gate between the two
projections of a gated short convolution (LFM2's operator), with its backward
pass:

    s = b * z;   conv_t = sum_j k_j * s_{t - (K - 1) + j};   out = c * conv

``bcz (B, T, 3 D)`` holds ``[b; c; z]`` as the in projection leaves them,
``kernel (K, D)`` the taps (f32), zeros stand before the sequence, and the
result ``(B, T, D)`` comes back in ``bcz``'s dtype.  Everything between is
f32.  No matrix unit is involved: the work is one read of three tensors and
one write, and what it costs is how often those tensors cross HBM.

Backends (``backend=``):

- ``'xla'``: plain ``jax.numpy`` (``K`` shifted multiply-adds) and its
  autodiff.  What the CPU and CI run.  On a TPU XLA writes ``s`` and the
  convolution out as f32 tensors between its fusions, and three more in the
  backward pass (PERF.md section 6, PR 43: 20.8 ms a step where one pass a
  direction costs 9.8).
- ``'pallas'``: the TPU kernels ``bf_sconv_fwd`` / ``bf_sconv_bwd`` (the
  names a profiler trace shows, and what the benchmark's
  ``lfm2_conv_gate_roofline`` reads): one pass a direction.  A grid step holds a
  tile of tokens by a block of channels (the convolution is a channel's
  own, so channel blocks are independent) and, for the ``K - 1`` tokens
  before the tile, the last rows of the tile before it, read as a second
  small block of the same array; ``bcz`` is handed over whole and its three
  thirds are three block indices, so no slice of it is copied.  The
  backward kernel computes the convolution again, ``dc = g * conv``, runs
  the taps the other way over ``g * c`` (with the first rows of the tile
  after it) for ``ds``, and ``db = ds * z``, ``dz = ds * b``; the three
  cotangents go into one ``(B, T, 3 D)`` array, a third a grid step
  (computed on the first of the three, kept in VMEM for the other two:
  an input block whose index stays is not read again); the taps' gradient
  adds up in a VMEM block a channel block over the whole grid.
- ``'pallas_interpret'``: the same kernels in the Pallas interpreter (CPU
  tests).
- ``'auto'``: the kernels on a TPU when the shapes tile (whole 16-token
  tiles, channels a multiple of 128), else ``'xla'``.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax import lax

__all__ = ["gated_short_conv"]

BACKENDS = ("auto", "xla", "pallas", "pallas_interpret")
_EDGE = 16      # rows of a neighbouring tile a step reads: a bf16 tile's


def _tiles(t: int, d: int):
    """``(tokens, channels)`` of a grid step, or ``None`` where the shapes
    do not tile: up to 256 tokens in whole 16-row tiles by up to 1,024
    channels in whole lanes."""
    if t % _EDGE or d % 128:
        return None
    tt = next(x for x in (256, 128, 64, 32, 16) if t % x == 0)
    dc = next(x for x in (1024, 512, 256, 128) if d % x == 0)
    return tt, dc


def _resolve(backend: str, t: int, d: int, taps: int) -> str:
    if backend not in BACKENDS:
        raise ValueError(f"unknown backend {backend!r}; expected one of "
                         f"{BACKENDS}")
    fits = _tiles(t, d) is not None and taps <= _EDGE
    if backend == "auto":
        return "pallas" if fits and jax.default_backend() == "tpu" else "xla"
    if backend != "xla" and not fits:
        raise ValueError(
            f"the kernels tile whole {_EDGE}-token rows and 128-channel "
            f"lanes and reach at most {_EDGE} taps back; got {t} tokens, "
            f"{d} channels, {taps} taps")
    return backend


def gated_short_conv(bcz, kernel, *, backend: str = "auto"):
    """``c * conv(b * z)`` of ``bcz (B, T, 3 D) = [b; c; z]`` under the taps
    ``kernel (K, D)``: ``(B, T, D)`` in ``bcz.dtype`` (module docstring)."""
    d = bcz.shape[-1] // 3
    if bcz.ndim != 3 or kernel.shape[1:] != (d,) or bcz.shape[-1] != 3 * d:
        raise ValueError(f"bcz {bcz.shape} is not (B, T, 3 D) for taps "
                         f"{kernel.shape} = (K, D)")
    backend = _resolve(backend, bcz.shape[1], d, kernel.shape[0])
    if backend == "xla":
        return _plain(bcz, kernel)
    return _kernels(bcz, kernel.astype(jnp.float32),
                    backend == "pallas_interpret")


def _plain(bcz, kernel):
    b, c, z = (x.astype(jnp.float32) for x in jnp.split(bcz, 3, axis=-1))
    taps, t = kernel.shape[0], bcz.shape[1]
    padded = jnp.pad(b * z, ((0, 0), (taps - 1, 0), (0, 0)))
    conv = sum(kernel[j] * padded[:, j:j + t] for j in range(taps))
    return (c * conv).astype(bcz.dtype)


# ---- the kernels -------------------------------------------------------------

def _earlier(x, before, n):
    """Row ``r`` of the result is ``x[r - n]``, and ``before[-n + r]`` for
    the first ``n`` rows: ``x (tt, dc)``, ``before (_EDGE, dc)`` the rows
    just before it."""
    from jax.experimental.pallas import tpu as pltpu

    rolled = pltpu.roll(x, n, 0)
    edge = pltpu.roll(before, n, 0)
    row = lax.broadcasted_iota(jnp.int32, before.shape, 0)
    top = jnp.where(row < n, edge, rolled[:_EDGE])
    if x.shape[0] == _EDGE:
        return top
    return jnp.concatenate([top, rolled[_EDGE:]], axis=0)


def _later(x, after, n):
    """Row ``r`` of the result is ``x[r + n]``, and ``after[r + n - tt]`` for
    the last ``n`` rows."""
    from jax.experimental.pallas import tpu as pltpu

    tt = x.shape[0]
    rolled = pltpu.roll(x, tt - n, 0)
    edge = pltpu.roll(after, _EDGE - n, 0)
    row = lax.broadcasted_iota(jnp.int32, after.shape, 0)
    bottom = jnp.where(row >= _EDGE - n, edge, rolled[tt - _EDGE:])
    if tt == _EDGE:
        return bottom
    return jnp.concatenate([rolled[:tt - _EDGE], bottom], axis=0)


def _product(x_ref, y_ref, live=None):
    """``x * y`` of two blocks in f32; zero unless ``live`` where the block
    may stand for rows outside the sequence."""
    xy = x_ref[0].astype(jnp.float32) * y_ref[0].astype(jnp.float32)
    return xy if live is None else jnp.where(live, xy, 0.0)


def _fwd_kernel(k_ref, b_ref, c_ref, z_ref, b_before, z_before, o_ref, *,
                taps):
    from jax.experimental import pallas as pl

    s = _product(b_ref, z_ref)
    before = _product(b_before, z_before, pl.program_id(1) > 0)
    conv = k_ref[taps - 1:taps, :] * s
    for n in range(1, taps):
        conv = conv + k_ref[taps - 1 - n:taps - n, :] * _earlier(s, before, n)
    o_ref[0] = (c_ref[0].astype(jnp.float32) * conv).astype(o_ref.dtype)


def _bwd_kernel(k_ref, b_ref, c_ref, z_ref, b_before, z_before, g_ref,
                g_after, c_after, d_ref, dk_ref, thirds, *, taps, tiles):
    from jax.experimental import pallas as pl

    batch, tile, third = (pl.program_id(i) for i in (1, 2, 3))

    @pl.when((batch == 0) & (tile == 0) & (third == 0))
    def _():
        dk_ref[...] = jnp.zeros_like(dk_ref)

    @pl.when(third == 0)
    def _():
        s = _product(b_ref, z_ref)
        before = _product(b_before, z_before, tile > 0)
        d_conv = _product(g_ref, c_ref)
        after = _product(g_after, c_after, tile < tiles - 1)
        conv = k_ref[taps - 1:taps, :] * s
        d_s = k_ref[taps - 1:taps, :] * d_conv
        dk_ref[taps - 1:taps, :] += jnp.sum(d_conv * s, axis=0,
                                            keepdims=True)
        for n in range(1, taps):
            tap = k_ref[taps - 1 - n:taps - n, :]
            shifted = _earlier(s, before, n)
            conv = conv + tap * shifted
            d_s = d_s + tap * _later(d_conv, after, n)
            dk_ref[taps - 1 - n:taps - n, :] += jnp.sum(
                d_conv * shifted, axis=0, keepdims=True)
        thirds[0] = (d_s * z_ref[0].astype(jnp.float32)).astype(thirds.dtype)
        thirds[1] = (g_ref[0].astype(jnp.float32) * conv).astype(
            thirds.dtype)
        thirds[2] = (d_s * b_ref[0].astype(jnp.float32)).astype(thirds.dtype)

    d_ref[0] = thirds[third]


def _specs(t, d, order):
    """Block specs over ``bcz (B, T, 3 D)`` and ``g (B, T, D)`` for a grid
    whose indices ``order`` maps to ``(batch, tile, channel block)``: a tile
    of a third, the ``_EDGE`` rows before or after it, the taps' block."""
    from jax.experimental import pallas as pl

    tt, dc = _tiles(t, d)
    blocks, edges = d // dc, tt // _EDGE

    def spec(shape, index):
        return pl.BlockSpec(shape, lambda *grid: index(*order(*grid)))

    def tile_of(third):
        return spec((1, tt, dc), lambda bi, ti, ci: (
            bi, ti, third * blocks + ci))

    def before(third):
        return spec((1, _EDGE, dc), lambda bi, ti, ci: (
            bi, jnp.maximum(ti * edges - 1, 0), third * blocks + ci))

    def after(third):
        return spec((1, _EDGE, dc), lambda bi, ti, ci: (
            bi, jnp.minimum((ti + 1) * edges, t // _EDGE - 1),
            third * blocks + ci))

    def taps_of(taps):
        return spec((taps, dc), lambda bi, ti, ci: (0, ci))

    return tile_of, before, after, taps_of, (tt, dc, blocks)


def _forward(bcz, kernel, interpret):
    from jax.experimental import pallas as pl

    batch, t, d = bcz.shape[0], bcz.shape[1], bcz.shape[2] // 3
    taps = kernel.shape[0]
    tile_of, before, _, taps_of, (tt, dc, blocks) = _specs(
        t, d, lambda bi, ti, ci: (bi, ti, ci))
    return pl.pallas_call(
        functools.partial(_fwd_kernel, taps=taps),
        grid=(batch, t // tt, blocks),
        in_specs=[taps_of(taps), tile_of(0), tile_of(1), tile_of(2),
                  before(0), before(2)],
        out_specs=tile_of(0),
        out_shape=jax.ShapeDtypeStruct((batch, t, d), bcz.dtype),
        interpret=interpret, name="bf_sconv_fwd",
    )(kernel, bcz, bcz, bcz, bcz, bcz)


def _backward(bcz, kernel, g, interpret):
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    batch, t, d = bcz.shape[0], bcz.shape[1], bcz.shape[2] // 3
    taps = kernel.shape[0]
    # channel blocks outermost (a block's taps' gradient stays in VMEM over
    # its whole run), the three thirds of the cotangent innermost
    tile_of, before, after, taps_of, (tt, dc, blocks) = _specs(
        t, d, lambda ci, bi, ti, third: (bi, ti, ci))
    d_bcz = pl.BlockSpec((1, tt, dc), lambda ci, bi, ti, third: (
        bi, ti, third * blocks + ci))
    return pl.pallas_call(
        functools.partial(_bwd_kernel, taps=taps, tiles=t // tt),
        grid=(blocks, batch, t // tt, 3),
        in_specs=[taps_of(taps), tile_of(0), tile_of(1), tile_of(2),
                  before(0), before(2), tile_of(0), after(0), after(1)],
        out_specs=[d_bcz, taps_of(taps)],
        out_shape=[jax.ShapeDtypeStruct(bcz.shape, bcz.dtype),
                   jax.ShapeDtypeStruct(kernel.shape, jnp.float32)],
        scratch_shapes=[pltpu.VMEM((3, tt, dc), bcz.dtype)],
        interpret=interpret, name="bf_sconv_bwd",
    )(kernel, bcz, bcz, bcz, bcz, bcz, g, g, bcz)


@functools.partial(jax.custom_vjp, nondiff_argnums=(2,))
def _kernels(bcz, kernel, interpret):
    return _forward(bcz, kernel, interpret)


def _kernels_fwd(bcz, kernel, interpret):
    return _forward(bcz, kernel, interpret), (bcz, kernel)


def _kernels_bwd(interpret, residuals, g):
    bcz, kernel = residuals
    return _backward(bcz, kernel, g.astype(bcz.dtype), interpret)


_kernels.defvjp(_kernels_fwd, _kernels_bwd)
