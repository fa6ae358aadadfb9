"""Mamba-2's state-space layer (state-space duality, arXiv:2405.21060): a
recurrence whose decay is **one scalar a head and token**, chunked into
matmul form, with its backward pass.

    S_t = exp(dt_t a) S_{t-1} + dt_t x_t B_t^T            S_0 = 0
    y_t = S_t C_t + D x_t

``x (B, T, H, P)``, ``dt (B, T, H)`` (the step, positive), ``a (H,)``
(negative), ``b, c (B, T, G, N)`` with ``G`` dividing ``H`` (head ``h`` reads
group ``h // (H / G)``), ``d (H,)`` the skip.  The state ``S (P, N)`` a head
is f32 and is never written out a token: only the state at each chunk's
start is saved (``T / 128`` of them), and the backward pass recomputes a
chunk from it.  Unlike ``ops/selective_scan.py`` (Mamba-1: a decay a channel
and state element, no matmul form) a chunk here is four products, and
unlike ``ops/kda.py`` there is no delta rule and so no inverse.

**A chunk of 128 tokens in matmul form** (Listing 1 of the paper).  With
``L_t`` the log-decay ``dt a`` summed from the chunk's start through token
``t`` (f32, at most 0):

    scores = C B^T                              a group: its heads share it
    Y      = (scores * exp(L_t - L_s)[s <= t]) (dt * X)       within the chunk
           + exp(L_t) * (C S_0^T)                             what came before
           + D X
    S_128  = exp(L_128) S_0 + (X * dt exp(L_128 - L_t))^T B

Every exponent is a difference of a later and an earlier sum, so at most 0:
nothing overflows whatever the step (the masked upper triangle is set to
minus infinity before the ``exp``, never after).  The operands of the
products are in the inputs' dtype (bf16 where the model computes in bf16),
accumulated in f32; ``dt``, ``a``, the running sums, the decay factors and
the state are f32.

**Heads narrower than a tile's 128 lanes go side by side**: ``128 / P`` heads
make one *slab* of 128 columns of ``x`` and 128 rows of the state; each
head's masked scores multiply the slab with the other heads' columns at
zero.  A product 64 columns wide would fill half the matrix unit's output
and cost the same, and every slice of the kernels stays on a tile's edge.

The backward pass of a chunk is ``jax.vjp`` of that chunk function, taken
where the chunk is computed (inside the Pallas kernel too), walking the
chunks in reverse time with the state's cotangent carried.

Backends (``backend=``):

- ``'chunked'``: plain ``jax.numpy``, a ``lax.scan`` over chunks.  What the
  CPU and CI run.
- ``'pallas'``: the TPU kernels ``bf_ssd_fwd`` and ``bf_ssd_bwd`` (the names
  a profiler trace shows, and what the benchmark's ``nemotron_ssd_*`` metrics
  read).  Grid: batch, groups, chunks in order; a group's states (and,
  backward, their cotangents) live in VMEM scratch between chunks, and the
  128 x 128 scores never leave VMEM.  The operands are read as ``(128,
  heads a group * P)`` blocks of ``(B, T, H * P)``, the layout the
  projections write: no relayout around the kernels but the steps' and the
  running sums' ``(B, H, T)``.
- ``'pallas_interpret'``: the same kernels in the Pallas interpreter (CPU
  tests).
- ``'auto'``: the kernels on a TPU where the shapes are whole tiles (a slab
  of 128 columns, ``N`` in multiples of 128, groups of whole sublanes of 8
  heads), else ``'chunked'``.
"""

from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
from jax import lax

from bluefog_tpu.tracing import startup

__all__ = ["ssd", "CHUNK"]

BACKENDS = ("auto", "chunked", "pallas", "pallas_interpret")
CHUNK = 128
_LANES = 128


def _slab_heads(heads_a_group: int, p: int) -> int:
    """Heads side by side in one slab of the state: as many ``p``-wide
    heads as fill a tile's 128 lanes, and a divisor of the group's."""
    return math.gcd(heads_a_group, max(1, _LANES // p))


def _resolve(backend: str, heads: int, groups: int, p: int, n: int) -> str:
    if backend not in BACKENDS:
        raise ValueError(f"unknown backend {backend!r}; expected one of "
                         f"{BACKENDS}")
    hg = heads // groups
    tiled = ((_slab_heads(hg, p) * p) % _LANES == 0 and n % _LANES == 0
             and (hg % 8 == 0 or groups == 1))
    if backend == "auto":
        on_tpu = jax.default_backend() == "tpu"
        return "pallas" if on_tpu and tiled else "chunked"
    if backend == "pallas" and not tiled:
        raise ValueError(
            f"backend='pallas' needs slabs of {_LANES} columns, a state in "
            f"multiples of {_LANES} and groups of whole sublanes of 8 heads; "
            f"got {hg} heads a group of width {p}, state {n}")
    return backend


def ssd(x, dt, a, b, c, d, *, backend="auto"):
    """``y (B, T, H, P)`` in ``x``'s dtype; see the module docstring.
    Differentiable in all six operands.  Any ``T``: the tokens are padded to
    whole chunks with steps of zero, which neither decay nor write."""
    if not (x.ndim == 4 and dt.shape == x.shape[:3] and b.shape == c.shape
            and b.ndim == 4 and b.shape[:2] == x.shape[:2]
            and a.shape == d.shape == x.shape[2:3]
            and x.shape[2] % b.shape[2] == 0):
        raise ValueError(
            "ssd takes x (B, T, H, P), dt (B, T, H), a (H,), b and c "
            f"(B, T, G, N) with G dividing H, d (H,); got {x.shape}, "
            f"{dt.shape}, {a.shape}, {b.shape}, {c.shape}, {d.shape}")
    bsz, t, h, p = x.shape
    g, n = b.shape[2:]
    backend = _resolve(backend, h, g, p, n)
    pad = -t % CHUNK
    f32 = jnp.float32

    def flat(v):     # (B, T, heads, w) -> (B, chunks * CHUNK, heads * w)
        v = v.reshape(bsz, t, -1)
        return jnp.pad(v, ((0, 0), (0, pad), (0, 0))) if pad else v

    def by_head(v):  # (B, T, H) f32 -> (B, H, chunks * CHUNK)
        v = jnp.swapaxes(v, 1, 2)
        return jnp.pad(v, ((0, 0), (0, 0), (0, pad))) if pad else v

    steps = by_head(dt.astype(f32))
    # the log-decay summed from each chunk's start, in f32
    decay = jnp.cumsum((steps * a.astype(f32)[:, None]).reshape(
        bsz, h, -1, CHUNK), axis=-1).reshape(steps.shape)
    skip = jnp.repeat(d.astype(f32), p)[None]               # (1, H * P)
    y = _scan(flat(x), steps, decay, flat(b), flat(c), skip, g, p, backend)
    return y[:, :t].reshape(x.shape)


# ---- one chunk of one group ----------------------------------------------------

def _group_chunk(states, xs, b, c, dt, cum, skips, p):
    """One group's chunk.  ``states``: a tuple of slabs ``(w, N)`` f32 (``w /
    p`` heads' states one above the other); ``xs``: those heads' columns
    ``(CHUNK, w)``, a tuple alike; ``b, c (CHUNK, N)``; ``dt, cum (heads a
    group, CHUNK)`` f32, the steps and the log-decay's running sum; ``skips``
    a tuple of ``(1, w)`` f32 -> ``(ys`` f32 ``(CHUNK, w)``, the states after
    the chunk``)``, tuples alike.  Plain ``jax.numpy`` on values: the
    ``chunked`` backend maps it over batch and groups, the kernels call it
    on what they loaded.  Differentiated values are never sliced (a slice's
    transpose is a pad): a head's row comes out of ``dt`` and ``cum`` through
    a mask and a sum."""
    dtype = xs[0].dtype
    f32 = jnp.float32
    size, hg, w = b.shape[0], dt.shape[0], xs[0].shape[1]
    k = w // p

    def dot(u, v, contract):
        """Operands in the inputs' dtype, f32 accumulation."""
        precision = lax.Precision.HIGHEST if dtype == f32 else None
        return lax.dot_general(u.astype(dtype), v.astype(dtype),
                               ((contract[:1], contract[1:]), ((), ())),
                               precision=precision,
                               preferred_element_type=f32)

    rows = lax.broadcasted_iota(jnp.int32, (size, 1), 0)
    cols = lax.broadcasted_iota(jnp.int32, (1, size), 1)
    heads = lax.broadcasted_iota(jnp.int32, (hg, 1), 0)
    lane = lax.broadcasted_iota(jnp.int32, (1, w), 1) // p
    srow = lax.broadcasted_iota(jnp.int32, (w, 1), 0) // p
    causal, diagonal = rows >= cols, rows == cols

    def row(v, head):            # (hg, CHUNK) -> that head's (1, CHUNK)
        return jnp.sum(jnp.where(heads == head, v, 0.0), axis=0,
                       keepdims=True)

    def column(r):               # (1, CHUNK) -> (CHUNK, 1)
        return jnp.sum(jnp.where(diagonal, r, 0.0), axis=1, keepdims=True)

    scores = dot(c, b, (1, 1))                      # (CHUNK, CHUNK)
    ys, after = [], []
    for s, (state, x, skip) in enumerate(zip(states, xs, skips)):
        x32 = x.astype(f32)
        y = skip * x32
        through = jnp.zeros((size, w), f32)   # exp(L_t): what S_0 still gives
        write = jnp.zeros((size, w), f32)     # dt exp(L_end - L_t)
        keep = jnp.zeros((w, 1), f32)         # exp(L_end), a row of the slab
        for r in range(k):
            sum_row = row(cum, s * k + r)
            sum_col, dt_col = column(sum_row), column(row(dt, s * k + r))
            end = jnp.sum(jnp.where(cols == size - 1, sum_row, 0.0), axis=1,
                          keepdims=True)            # (1, 1)
            mine = lane == r
            within = jnp.exp(jnp.where(causal, sum_col - sum_row, -jnp.inf))
            y = y + dot(scores * within, jnp.where(mine, x32 * dt_col, 0.0),
                        (1, 0))
            through = through + jnp.where(mine, jnp.exp(sum_col), 0.0)
            write = write + jnp.where(mine, dt_col * jnp.exp(end - sum_col),
                                      0.0)
            keep = keep + jnp.where(srow == r, jnp.exp(end), 0.0)
        ys.append(y + through * dot(c, state, (1, 1)))
        after.append(keep * state + dot(x32 * write, b, (0, 0)))
    return tuple(ys), tuple(after)


# ---- the scan over chunks, with its backward --------------------------------

@functools.partial(jax.custom_vjp, nondiff_argnums=(6, 7, 8))
def _scan(x, dt, cum, b, c, skip, groups, p, backend):
    """``x (B, T, H * P)``, ``dt, cum (B, H, T)`` f32, ``b, c (B, T, G *
    N)``, ``skip (1, H * P)`` f32, ``T`` whole chunks -> ``y`` as ``x``."""
    return _scan_fwd(x, dt, cum, b, c, skip, groups, p, backend)[0]


def _scan_fwd(x, dt, cum, b, c, skip, groups, p, backend):
    if backend == "chunked":
        y, starts = _chunked_fwd(x, dt, cum, b, c, skip, groups, p)
    else:
        y, starts = _pallas_fwd(x, dt, cum, b, c, skip, groups, p,
                                backend == "pallas_interpret")
    return y, (x, dt, cum, b, c, skip, starts)


def _scan_bwd(groups, p, backend, residuals, dy):
    if backend == "chunked":
        return _chunked_bwd(*residuals, dy, groups, p)
    return _pallas_bwd(*residuals, dy, groups, p,
                       backend == "pallas_interpret")


_scan.defvjp(_scan_fwd, _scan_bwd)


# ---- 'chunked': jax.numpy ---------------------------------------------------

def _by_chunk(v, groups):
    """``(B, T, G * w) -> (T / CHUNK, B, G, CHUNK, w)``: chunks lead, for
    ``lax.scan``."""
    bsz, t, gw = v.shape
    v = v.reshape(bsz, t // CHUNK, CHUNK, groups, gw // groups)
    return jnp.transpose(v, (1, 0, 3, 2, 4))


def _from_chunks(v):
    """The inverse of :func:`_by_chunk`."""
    chunks, bsz, g, size, w = v.shape
    return jnp.transpose(v, (1, 0, 3, 2, 4)).reshape(bsz, chunks * size,
                                                     g * w)


def _heads_by_chunk(v, groups):
    """``(B, H, T) -> (T / CHUNK, B, G, H / G, CHUNK)``."""
    bsz, h, t = v.shape
    v = v.reshape(bsz, groups, h // groups, t // CHUNK, CHUNK)
    return jnp.transpose(v, (3, 0, 1, 2, 4))


def _heads_from_chunks(v):
    chunks, bsz, g, hg, size = v.shape
    return jnp.transpose(v, (1, 2, 3, 0, 4)).reshape(bsz, g * hg,
                                                     chunks * size)


def _group_step(p, w):
    """:func:`_group_chunk` on whole arrays (``state (slabs, w, N)``, ``x
    (CHUNK, slabs * w)``, ``skip (1, slabs * w)``), over groups and batch."""
    def step(state, x, b, c, dt, cum, skip):
        slabs = state.shape[0]
        ys, after = _group_chunk(
            tuple(state[s] for s in range(slabs)),
            tuple(x[:, s * w:(s + 1) * w] for s in range(slabs)), b, c, dt,
            cum, tuple(skip[:, s * w:(s + 1) * w] for s in range(slabs)), p)
        return jnp.concatenate(ys, axis=1), jnp.stack(after)

    over_groups = jax.vmap(step)
    return jax.vmap(over_groups, in_axes=(0, 0, 0, 0, 0, 0, None))


def _chunked_operands(x, dt, cum, b, c, skip, groups, p):
    hg = dt.shape[1] // groups
    w = _slab_heads(hg, p) * p
    return (_group_step(p, w), w,
            (_by_chunk(x, groups), _by_chunk(b, groups), _by_chunk(c, groups),
             _heads_by_chunk(dt, groups), _heads_by_chunk(cum, groups)),
            skip.reshape(groups, 1, hg * p))


def _chunked_fwd(x, dt, cum, b, c, skip, groups, p):
    step, w, operands, skips = _chunked_operands(x, dt, cum, b, c, skip,
                                                 groups, p)

    def one_chunk(state, inputs):
        y, after = step(state, *inputs, skips)
        return after, (y, state)

    bsz, n = x.shape[0], b.shape[2] // groups
    zero = jnp.zeros((bsz, groups, x.shape[2] // groups // w, w, n),
                     jnp.float32)
    _, (y, starts) = lax.scan(one_chunk, zero, operands)
    return _from_chunks(y).astype(x.dtype), starts


def _chunked_bwd(x, dt, cum, b, c, skip, starts, dy, groups, p):
    step, _, operands, skips = _chunked_operands(x, dt, cum, b, c, skip,
                                                 groups, p)

    def one_chunk(carry, inputs):
        d_after, d_skip = carry
        *chunk, start, d_y = inputs
        _, pull = jax.vjp(step, start, *chunk, skips)
        d_start, *grads, d_skips = pull((d_y.astype(jnp.float32), d_after))
        return (d_start, d_skip + d_skips), tuple(grads)

    (_, d_skip), (d_x, d_b, d_c, d_dt, d_cum) = lax.scan(
        one_chunk, (jnp.zeros_like(starts[0]), jnp.zeros_like(skips)),
        operands + (starts, _by_chunk(dy, groups)), reverse=True)
    return (_from_chunks(d_x), _heads_from_chunks(d_dt),
            _heads_from_chunks(d_cum), _from_chunks(d_b), _from_chunks(d_c),
            d_skip.reshape(skip.shape))


# ---- 'pallas': the TPU kernels ----------------------------------------------
# Grid (batch, group, chunk); every operand with a time axis is read as its
# chunk's block: (CHUNK, a group's columns) of (B, T, .), (a group's heads,
# CHUNK) of (B, H, T).  The chunk-start states are (B, G, T / CHUNK, slabs,
# w, N) f32.  A grid step works its group's chunk in one basic block: the
# heads' chains of products are independent, and the scheduler overlaps them.

def _slabs(ref, w):
    """The ``(rows, w)`` slabs of a ``(rows, slabs * w)`` ref, loaded."""
    return tuple(ref[:, s * w:(s + 1) * w] for s in range(ref.shape[1] // w))


def _fwd_kernel(p, x_ref, b_ref, c_ref, dt_ref, cum_ref, skip_ref, y_ref,
                start_ref, state_ref):
    from jax.experimental import pallas as pl

    @pl.when(pl.program_id(2) == 0)
    def _():
        state_ref[...] = jnp.zeros(state_ref.shape, jnp.float32)

    slabs, w, _ = state_ref.shape
    states = tuple(state_ref[s] for s in range(slabs))
    for s in range(slabs):
        start_ref[s] = states[s]
    ys, after = _group_chunk(states, _slabs(x_ref, w), b_ref[...], c_ref[...],
                             dt_ref[...], cum_ref[...], _slabs(skip_ref, w),
                             p)
    for s in range(slabs):
        y_ref[:, s * w:(s + 1) * w] = ys[s].astype(y_ref.dtype)
        state_ref[s] = after[s]


def _bwd_kernel(p, x_ref, b_ref, c_ref, dt_ref, cum_ref, skip_ref, dy_ref,
                start_ref, dx_ref, db_ref, dc_ref, ddt_ref, dcum_ref,
                dskip_ref, carried_ref):
    from jax.experimental import pallas as pl

    @pl.when(pl.program_id(2) == 0)    # the last chunk: nothing follows it
    def _():
        carried_ref[...] = jnp.zeros(carried_ref.shape, jnp.float32)
        dskip_ref[...] = jnp.zeros(dskip_ref.shape, jnp.float32)

    slabs, w, _ = carried_ref.shape
    _, pull = jax.vjp(
        lambda *operands: _group_chunk(*operands, p),
        tuple(start_ref[s] for s in range(slabs)), _slabs(x_ref, w),
        b_ref[...], c_ref[...], dt_ref[...], cum_ref[...],
        _slabs(skip_ref, w))
    d_starts, d_xs, d_b, d_c, d_dt, d_cum, d_skips = pull((
        tuple(dy.astype(jnp.float32) for dy in _slabs(dy_ref, w)),
        tuple(carried_ref[s] for s in range(slabs))))
    db_ref[...], dc_ref[...] = d_b, d_c
    ddt_ref[...], dcum_ref[...] = d_dt, d_cum
    for s in range(slabs):
        dx_ref[:, s * w:(s + 1) * w] = d_xs[s]
        dskip_ref[:, s * w:(s + 1) * w] += d_skips[s]
        carried_ref[s] = d_starts[s]


def _specs(hg, p, n, slabs, chunk_of):
    """Block specs of a group's columns of ``x``, of ``b`` and ``c``, of its
    heads' rows of ``dt`` and ``cum``, of its ``skip`` and of its chunk-start
    states; ``chunk_of(j)`` is the chunk the grid's ``j``-th step works."""
    from jax.experimental import pallas as pl

    def tokens(width):
        return pl.BlockSpec((None, CHUNK, width),
                            lambda i, g, j: (i, chunk_of(j), g))

    heads = pl.BlockSpec((None, hg, CHUNK),
                         lambda i, g, j: (i, g, chunk_of(j)))
    skip = pl.BlockSpec((1, hg * p), lambda i, g, j: (0, g))
    states = pl.BlockSpec((None, None, None, slabs, hg * p // slabs, n),
                          lambda i, g, j: (i, g, chunk_of(j), 0, 0, 0))
    return tokens(hg * p), tokens(n), heads, skip, states


def _sizes(x, dt, b, groups, p):
    bsz, t, hp = x.shape
    hg, n = dt.shape[1] // groups, b.shape[2] // groups
    slabs = hg // _slab_heads(hg, p)
    return bsz, t // CHUNK, hg, n, slabs, hp // groups // slabs


def _pallas_fwd(x, dt, cum, b, c, skip, groups, p, interpret):
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    bsz, chunks, hg, n, slabs, w = _sizes(x, dt, b, groups, p)
    wide, narrow, heads, skips, states = _specs(hg, p, n, slabs, lambda j: j)
    startup.kernel_traced("bf_ssd_fwd")
    return pl.pallas_call(
        functools.partial(_fwd_kernel, p), grid=(bsz, groups, chunks),
        in_specs=[wide, narrow, narrow, heads, heads, skips],
        out_specs=[wide, states],
        out_shape=[jax.ShapeDtypeStruct(x.shape, x.dtype),
                   jax.ShapeDtypeStruct((bsz, groups, chunks, slabs, w, n),
                                        jnp.float32)],
        scratch_shapes=[pltpu.VMEM((slabs, w, n), jnp.float32)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary")),
        interpret=interpret, name="bf_ssd_fwd",
    )(x, b, c, dt, cum, skip)


def _pallas_bwd(x, dt, cum, b, c, skip, starts, dy, groups, p, interpret):
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    bsz, chunks, hg, n, slabs, w = _sizes(x, dt, b, groups, p)
    wide, narrow, heads, skips, states = _specs(
        hg, p, n, slabs, lambda j: chunks - 1 - j)
    # a group's skip gradient, summed over its chunks in the resident block
    d_skips = pl.BlockSpec((None, None, 1, hg * p),
                           lambda i, g, j: (i, g, 0, 0))
    startup.kernel_traced("bf_ssd_bwd")
    d_x, d_b, d_c, d_dt, d_cum, d_skip = pl.pallas_call(
        functools.partial(_bwd_kernel, p), grid=(bsz, groups, chunks),
        in_specs=[wide, narrow, narrow, heads, heads, skips, wide, states],
        out_specs=[wide, narrow, narrow, heads, heads, d_skips],
        out_shape=[jax.ShapeDtypeStruct(v.shape, v.dtype)
                   for v in (x, b, c, dt, cum)]
        + [jax.ShapeDtypeStruct((bsz, groups, 1, hg * p), jnp.float32)],
        scratch_shapes=[pltpu.VMEM((slabs, w, n), jnp.float32)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary")),
        interpret=interpret, name="bf_ssd_bwd",
    )(x, b, c, dt, cum, skip, dy, starts)
    return d_x, d_dt, d_cum, d_b, d_c, d_skip.sum(axis=0).reshape(skip.shape)
