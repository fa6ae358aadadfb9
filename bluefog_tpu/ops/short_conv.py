"""Two causal depthwise convolutions a few taps long with what stands around
them between two projections, each with its backward pass.  No matrix unit
is involved in either: the work is one read of the operands and one write,
and what it costs is how often those tensors cross HBM.

:func:`gated_short_conv`, the gate, convolution and gate of LFM2's operator:

    s = b * z;   conv_t = sum_j k_j * s_{t - (K - 1) + j};   out = c * conv

``bcz (B, T, 3 D)`` holds ``[b; c; z]`` as the in projection leaves them,
``kernel (K, D)`` the taps (f32), zeros stand before the sequence, and the
result ``(B, T, D)`` comes back in ``bcz``'s dtype.  Everything between is
f32.

:func:`silu_short_conv`, the convolution, bias and SiLU of Mamba-2's mixer:

    p_t = sum_j k_j * x_{t - (K - 1) + j} + bias;   out = p * sigmoid(p)

over the channels ``offset : offset + C`` of ``x (B, T, W)`` (the in
projection's output, handed over whole), ``kernel (K, C)`` and ``bias (C,)``
f32, the result ``(B, T, C)`` in ``x``'s dtype, everything between f32.
Its second caller is Kimi Delta Attention's mixer (``KdaMixer``: q, k and v,
a projection's output each, no bias), whose q and k are l2-normalised a
head before they are rounded; with ``l2norm=(width, eps, scale)``

    s = p * sigmoid(p);   out = scale * s / sqrt(sum_group s ** 2 + eps)

over each ``width`` consecutive channels, still f32 up to the one cast.

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
  :func:`silu_short_conv` runs as ``bf_cconv_fwd`` / ``bf_cconv_bwd`` on
  the same plan: a tile of ``x`` with the ``K - 1`` rows before it in, the
  taps and the bias a channel block in VMEM, one write of the result.  The
  backward kernel computes ``p`` again for the tile and for the first rows
  of the tile after it (whose ``p`` reaches back into this tile's last
  rows, which are at hand), ``d_p = g * sigmoid(p) (1 + p (1 -
  sigmoid(p)))``, runs the taps the other way over ``d_p`` for ``dx`` and
  adds the taps' and the bias's gradients up in one VMEM block a channel
  block.  Residuals: ``x``, the taps and the bias; no f32 tensor is saved or
  written between the two (as XLA compiles the ``jax.numpy`` form the
  convolution goes f32 in and f32 out through HBM in each of its passes:
  PERF.md section 6, PR 49).  ``dx`` comes back padded with zeros to ``x``'s
  width, one of the pieces XLA adds up to the in projection's cotangent
  (``Mamba2Mixer`` fences that sum into one pass).  The normalised form is
  the same two kernels with an epilogue: a group is whole lanes, so the sum
  of squares is a lane reduction a row of a 128-lane column slice of the
  block, and the tile's edges need nothing new (the norm is a row's own).
  Backward, ``s`` and ``r = rsqrt(sum s ** 2 + eps)`` are computed again
  with ``p``, for the tile and for the first rows of the tile after it, and
  ``d_s = scale r (g - s r ** 2 sum_group g s)`` stands where ``g`` stood.
  ``scale`` is an operand (one f32 in SMEM), so q (``head_dim ** -0.5``)
  and k (1) share a body.  The two ``pallas_call``s sit in jitted
  functions, so a model's layers and passes trace and lower each of them
  once a form: at most two forward and two backward bodies a step.
- ``'pallas_interpret'``: the same kernels in the Pallas interpreter (CPU
  tests).
- ``'auto'``: the kernels on a TPU when the shapes tile (whole 16-token
  tiles, channels and ``offset`` a multiple of 128, in every piece; a
  normalisation's groups whole lanes that divide a block of channels), else
  ``'xla'``.
"""

from __future__ import annotations

import functools
import itertools

import jax
import jax.numpy as jnp
from jax import lax

from bluefog_tpu.tracing import startup

__all__ = ["gated_short_conv", "silu_short_conv"]

BACKENDS = ("auto", "xla", "pallas", "pallas_interpret")
_EDGE = 16      # rows of a neighbouring tile a step reads: a bf16 tile's


def _tiles(t: int, d: int, offset: int = 0, group: int = 128):
    """``(tokens, channels)`` of a grid step, or ``None`` where the shapes
    do not tile: up to 256 tokens in whole 16-row tiles by up to 1,024
    channels in whole lanes, ``offset`` (the first channel, where the
    operand is wider than the convolution) a whole number of blocks and a
    block a whole number of ``group``s (the channels a normalisation sums
    over, whole lanes themselves)."""
    if t % _EDGE or d % 128 or offset % 128 or group % 128:
        return None
    tt = next(x for x in (256, 128, 64, 32, 16) if t % x == 0)
    dc = next((x for x in (1024, 512, 256, 128)
               if d % x == 0 and offset % x == 0 and x % group == 0), None)
    return None if dc is None else (tt, dc)


def _resolve(backend: str, t: int, d: int, taps: int, offset: int = 0,
             group: int = 128) -> str:
    if backend not in BACKENDS:
        raise ValueError(f"unknown backend {backend!r}; expected one of "
                         f"{BACKENDS}")
    fits = _tiles(t, d, offset, group) is not None and taps <= _EDGE
    if backend == "auto":
        return "pallas" if fits and jax.default_backend() == "tpu" else "xla"
    if backend != "xla" and not fits:
        raise ValueError(
            f"the kernels tile whole {_EDGE}-token rows and 128-channel "
            f"lanes, normalise over whole lanes that divide a block of "
            f"channels and reach at most {_EDGE} taps back; got {t} tokens, "
            f"{d} channels from channel {offset} in groups of {group}, "
            f"{taps} taps")
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


def silu_short_conv(x, kernel, bias, *, offset: int = 0, pieces=None,
                    l2norm=None, backend: str = "auto"):
    """``silu(conv(x[..., offset:offset + C]) + bias)`` under the taps
    ``kernel (K, C)``: ``(B, T, C)`` in ``x.dtype`` (module docstring).
    ``x (B, T, W)`` is handed over whole so that the kernels read their
    channels where they lie and no slice of it is copied.  With ``pieces``
    (channel counts that add up to ``C``) the result comes back cut into that
    many arrays: from the kernels a call a piece, so that a reader that wants
    them apart copies no slice of the result either.  With ``l2norm =
    (width, eps, scale)`` every ``width`` consecutive channels of the SiLU's
    result (a head's) are divided by ``sqrt(their sum of squares + eps)``
    and multiplied by ``scale``, in f32, before the one cast."""
    c = kernel.shape[1]
    widths = (c,) if pieces is None else tuple(pieces)
    whole_groups = l2norm is None or (
        l2norm[0] > 0 and not any(width % l2norm[0] for width in widths))
    if (x.ndim != 3 or kernel.ndim != 2 or bias.shape != (c,)
            or sum(widths) != c or not 0 <= offset <= x.shape[-1] - c
            or not whole_groups):
        raise ValueError(f"x {x.shape} is not (B, T, W) with channels "
                         f"{offset}:{offset + c} in pieces of {widths} for "
                         f"taps {kernel.shape} = (K, C) and bias {bias.shape}"
                         + ("" if l2norm is None else
                            f", normalised in whole groups of {l2norm[0]}"))
    edges = list(itertools.accumulate(widths, initial=0))
    backends = {_resolve(backend, x.shape[1], hi - lo, kernel.shape[0],
                         offset + lo, _group(l2norm))
                for lo, hi in zip(edges, edges[1:])}
    if "xla" in backends:
        out = _plain_silu(x, kernel, bias, offset, l2norm)
        if pieces is None:
            return out
        return tuple(out[..., lo:hi] for lo, hi in zip(edges, edges[1:]))
    taps_bias = jnp.concatenate([kernel, bias[None]]).astype(jnp.float32)
    # the scale is an operand, not part of the kernels: one body normalises
    # whatever the constant (a query's head_dim ** -0.5, a key's 1)
    norm, scale = (None, None) if l2norm is None else (
        l2norm[:2], jnp.full((1,), l2norm[2], jnp.float32))
    outs = tuple(_silu_kernels(x, taps_bias[:, lo:hi], scale, offset + lo,
                               "pallas_interpret" in backends, norm)
                 for lo, hi in zip(edges, edges[1:]))
    return outs[0] if pieces is None else outs


def _plain_silu(x, kernel, bias, offset, l2norm):
    taps, t = kernel.shape[0], x.shape[1]
    live = x[..., offset:offset + kernel.shape[1]].astype(jnp.float32)
    padded = jnp.pad(live, ((0, 0), (taps - 1, 0), (0, 0)))
    conv = sum(kernel[j] * padded[:, j:j + t] for j in range(taps)) + bias
    out = jax.nn.silu(conv)
    if l2norm is not None:
        width, eps, scale = l2norm
        heads = out.reshape(out.shape[:-1] + (-1, width))
        heads = heads * lax.rsqrt(
            jnp.sum(heads * heads, -1, keepdims=True) + eps)
        out = (heads if scale == 1.0 else heads * scale).reshape(out.shape)
    return out.astype(x.dtype)


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


def _specs(t, d, order, offset=0, group=128):
    """Block specs over ``bcz (B, T, 3 D)`` and ``g (B, T, D)`` for a grid
    whose indices ``order`` maps to ``(batch, tile, channel block)``: a tile
    of a third, the ``_EDGE`` rows before or after it, the taps' block.
    With ``offset`` the tiles are those of an operand whose channel
    ``offset`` is the convolution's first, and ``whole=True`` asks for the
    spec over that operand."""
    from jax.experimental import pallas as pl

    tt, dc = _tiles(t, d, offset, group)
    blocks, edges = d // dc, tt // _EDGE

    def spec(shape, index):
        return pl.BlockSpec(shape, lambda *grid: index(*order(*grid)))

    def first(third, whole):
        return (offset // dc if whole else 0) + third * blocks

    def tile_of(third, whole=False):
        return spec((1, tt, dc), lambda bi, ti, ci: (
            bi, ti, first(third, whole) + ci))

    def before(third, whole=False):
        return spec((1, _EDGE, dc), lambda bi, ti, ci: (
            bi, jnp.maximum(ti * edges - 1, 0), first(third, whole) + ci))

    def after(third, whole=False):
        return spec((1, _EDGE, dc), lambda bi, ti, ci: (
            bi, jnp.minimum((ti + 1) * edges, t // _EDGE - 1),
            first(third, whole) + ci))

    def taps_of(taps):
        return spec((taps, dc), lambda bi, ti, ci: (0, ci))

    return tile_of, before, after, taps_of, (tt, dc, blocks)


def _forward(bcz, kernel, interpret):
    from jax.experimental import pallas as pl

    batch, t, d = bcz.shape[0], bcz.shape[1], bcz.shape[2] // 3
    taps = kernel.shape[0]
    tile_of, before, _, taps_of, (tt, dc, blocks) = _specs(
        t, d, lambda bi, ti, ci: (bi, ti, ci))
    startup.kernel_traced("bf_sconv_fwd")
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
    startup.kernel_traced("bf_sconv_bwd")
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


# ---- convolution, bias and SiLU ---------------------------------------------

def _preactivation(kb_ref, x, before, taps):
    """``p = conv(x) + bias`` of a block ``x`` (f32) whose ``_EDGE`` earlier
    rows are ``before``, under ``kb_ref (taps + 1, dc)`` (the taps, then the
    bias); and ``x`` shifted by each tap, for the taps' gradient."""
    shifted = [x] + [_earlier(x, before, n) for n in range(1, taps)]
    p = kb_ref[taps:taps + 1, :] + kb_ref[taps - 1:taps, :] * x
    for n in range(1, taps):
        p = p + kb_ref[taps - 1 - n:taps - n, :] * shifted[n]
    return p, shifted


def _d_silu(p):
    sig = jax.nn.sigmoid(p)
    return sig * (1.0 + p * (1.0 - sig))


def _rows(ref, live=None):
    """A block in f32; zero unless ``live`` where it may stand for rows
    outside the sequence."""
    x = ref[0].astype(jnp.float32)
    return x if live is None else jnp.where(live, x, 0.0)


def _group(norm):
    """The channels a normalisation sums over; a lane tile without one."""
    return 128 if norm is None else norm[0]


def _groups(norm, *blocks):
    """The blocks' columns a group of ``norm``'s width at a time."""
    for lo in range(0, blocks[0].shape[1], norm[0]):
        yield tuple(x[:, lo:lo + norm[0]] for x in blocks)


def _normalised(s, norm, scale):
    """``scale * s / sqrt(sum of s ** 2 + eps)`` a row and group of ``norm =
    (width, eps)`` consecutive channels of the block ``s`` (f32): a group is
    whole lanes, so a lane reduction a row of a column slice."""
    return jnp.concatenate([
        group * (scale * lax.rsqrt(
            jnp.sum(group * group, axis=-1, keepdims=True) + norm[1]))
        for group, in _groups(norm, s)], axis=-1)


def _d_preactivation(p, g, norm, scale):
    """The cotangent of ``p`` where ``g`` is that of ``silu(p)``, or of its
    normalised form ``c r s`` (``s = silu(p)``, ``r = rsqrt(sum of s ** 2 +
    eps)`` a row and group): ``d_s = c r (g - s r ** 2 sum of g s)``."""
    if norm is None:
        return g * _d_silu(p)
    sig = jax.nn.sigmoid(p)
    s = p * sig
    d_s = []
    for s_h, g_h in _groups(norm, s, g):
        r = lax.rsqrt(jnp.sum(s_h * s_h, axis=-1, keepdims=True) + norm[1])
        along = jnp.sum(g_h * s_h, axis=-1, keepdims=True)
        d_s.append((scale * r) * (g_h - s_h * (r * r * along)))
    return jnp.concatenate(d_s, axis=-1) * (sig * (1.0 + p * (1.0 - sig)))


def _silu_fwd_kernel(scale_ref, kb_ref, x_ref, x_before, o_ref, *, taps,
                     norm):
    from jax.experimental import pallas as pl

    p, _ = _preactivation(kb_ref, _rows(x_ref),
                          _rows(x_before, pl.program_id(1) > 0), taps)
    s = p * jax.nn.sigmoid(p)
    if norm is not None:
        s = _normalised(s, norm, scale_ref[0])
    o_ref[0] = s.astype(o_ref.dtype)


def _silu_bwd_kernel(scale_ref, kb_ref, x_ref, x_before, x_after, g_ref,
                     g_after, dx_ref, dkb_ref, *, taps, tiles, norm):
    from jax.experimental import pallas as pl

    batch, tile = pl.program_id(1), pl.program_id(2)
    scale = None if norm is None else scale_ref[0]

    @pl.when((batch == 0) & (tile == 0))
    def _():
        dkb_ref[...] = jnp.zeros_like(dkb_ref)

    x = _rows(x_ref)
    p, shifted = _preactivation(kb_ref, x, _rows(x_before, tile > 0), taps)
    d_p = _d_preactivation(p, _rows(g_ref), norm, scale)
    # the tile after this one: its first rows' p reaches into this tile
    p_after, _ = _preactivation(kb_ref, _rows(x_after), x[-_EDGE:], taps)
    d_after = jnp.where(
        tile < tiles - 1,
        _d_preactivation(p_after, _rows(g_after), norm, scale), 0.0)
    d_x = kb_ref[taps - 1:taps, :] * d_p
    for n in range(1, taps):
        d_x = d_x + kb_ref[taps - 1 - n:taps - n, :] * _later(d_p, d_after, n)
    dx_ref[0] = d_x.astype(dx_ref.dtype)
    for n in range(taps):
        dkb_ref[taps - 1 - n:taps - n, :] += jnp.sum(
            d_p * shifted[n], axis=0, keepdims=True)
    dkb_ref[taps:taps + 1, :] += jnp.sum(d_p, axis=0, keepdims=True)


def _with_scale(kernel, scale, specs, operands):
    """The kernel, its specs and its operands behind the normalisation's
    scale (one f32 in SMEM), where there is one."""
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    if scale is None:
        return functools.partial(kernel, None), specs, operands
    return (kernel, [pl.BlockSpec(memory_space=pltpu.SMEM)] + specs,
            (scale,) + operands)


@functools.partial(jax.jit, static_argnums=(3, 4, 5))
def _silu_forward(x, taps_bias, scale, offset, interpret, norm):
    from jax.experimental import pallas as pl

    batch, t, c = x.shape[0], x.shape[1], taps_bias.shape[1]
    taps = taps_bias.shape[0] - 1
    tile_of, before, _, taps_of, (tt, _, blocks) = _specs(
        t, c, lambda bi, ti, ci: (bi, ti, ci), offset, _group(norm))
    kernel, in_specs, operands = _with_scale(
        functools.partial(_silu_fwd_kernel, taps=taps, norm=norm), scale,
        [taps_of(taps + 1), tile_of(0, True), before(0, True)],
        (taps_bias, x, x))
    startup.kernel_traced("bf_cconv_fwd")
    return pl.pallas_call(
        kernel,
        grid=(batch, t // tt, blocks),
        in_specs=in_specs,
        out_specs=tile_of(0),
        out_shape=jax.ShapeDtypeStruct((batch, t, c), x.dtype),
        interpret=interpret, name="bf_cconv_fwd",
    )(*operands)


@functools.partial(jax.jit, static_argnums=(4, 5, 6))
def _silu_backward(x, taps_bias, scale, g, offset, interpret, norm):
    from jax.experimental import pallas as pl

    batch, t, c = x.shape[0], x.shape[1], taps_bias.shape[1]
    taps = taps_bias.shape[0] - 1
    # channel blocks outermost: a block's taps' and bias's gradients stay in
    # VMEM over its whole run
    tile_of, before, after, taps_of, (tt, _, blocks) = _specs(
        t, c, lambda ci, bi, ti: (bi, ti, ci), offset, _group(norm))
    kernel, in_specs, operands = _with_scale(
        functools.partial(_silu_bwd_kernel, taps=taps, tiles=t // tt,
                          norm=norm), scale,
        [taps_of(taps + 1), tile_of(0, True), before(0, True),
         after(0, True), tile_of(0), after(0)],
        (taps_bias, x, x, x, g, g))
    startup.kernel_traced("bf_cconv_bwd")
    d_x, d_taps_bias = pl.pallas_call(
        kernel,
        grid=(blocks, batch, t // tt),
        in_specs=in_specs,
        out_specs=[tile_of(0), taps_of(taps + 1)],
        out_shape=[jax.ShapeDtypeStruct((batch, t, c), x.dtype),
                   jax.ShapeDtypeStruct(taps_bias.shape, jnp.float32)],
        interpret=interpret, name="bf_cconv_bwd",
    )(*operands)
    # the cotangent of x whole: zeros beside the channels the taps read
    beside = (offset, x.shape[-1] - offset - c, 0)
    return lax.pad(d_x, jnp.zeros((), d_x.dtype),
                   ((0, 0, 0), (0, 0, 0), beside)), d_taps_bias


@functools.partial(jax.custom_vjp, nondiff_argnums=(3, 4, 5))
def _silu_kernels(x, taps_bias, scale, offset, interpret, norm):
    return _silu_forward(x, taps_bias, scale, offset, interpret, norm)


def _silu_kernels_fwd(x, taps_bias, scale, offset, interpret, norm):
    return (_silu_forward(x, taps_bias, scale, offset, interpret, norm),
            (x, taps_bias, scale))


def _silu_kernels_bwd(offset, interpret, norm, residuals, g):
    x, taps_bias, scale = residuals
    return _silu_backward(x, taps_bias, scale, g.astype(x.dtype), offset,
                          interpret, norm) + (
        jax.tree_util.tree_map(jnp.zeros_like, scale),)


_silu_kernels.defvjp(_silu_kernels_fwd, _silu_kernels_bwd)
