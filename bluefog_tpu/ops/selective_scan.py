"""Mamba-1's selective scan (arXiv:2312.00752 section 3): an input-dependent
diagonal recurrence over the sequence, with its backward pass.

    h_t = exp(delta_t (x) A) * h_{t-1} + (delta_t * x_t) (x) B_t,   h_0 = 0
    m_t = h_t C_t + D * x_t

``x, delta (B, T, C)``, ``A (C, N)`` (negative), ``B, C (B, T, N)``,
``D (C,)``; the state ``h`` is ``(C, N)`` a token and is never written out
whole: the decay is per channel *and* per state, so the recurrence has no
matmul form, and ``lax.associative_scan`` over the sequence would write
``T * C * N`` f32 states to HBM (2.7 GB a layer at T = 8,192, C = 5,120,
N = 16) several times over.  Both backends go **chunk by chunk**: the state
is carried from chunk to chunk, only the state at each chunk's start is
saved for the backward pass (``T / chunk`` states), and the backward pass
recomputes a chunk's states from its start and then runs the adjoint
recurrence ``g_t = C_t dm_t + a_{t+1} g_{t+1}`` through it in reverse time.
Everything is computed in f32.

Backends (``backend=``):

- ``'chunked'``: plain ``jax.numpy``; a ``lax.scan`` over chunks with a
  ``lax.associative_scan`` inside the chunk.  What the CPU and CI run.
- ``'pallas'``: the TPU kernels ``bf_selective_scan_fwd`` /
  ``bf_selective_scan_bwd`` (the names a profiler trace shows, and what the
  benchmark's ``ssm_scan_*`` metrics read).  Grid: batch, chunks of time in
  order, blocks of 1,024 channels; the state of every channel block lives in
  VMEM scratch between chunks.  Channels lie on sublanes *and* lanes (a
  block is eight ``(8, 128)`` tiles a state index), time is the leading,
  untiled axis, and ``B_t[n]``, ``C_t[n]`` are scalars read from SMEM: the
  inner loop is elementwise vector work and one ``exp`` a state element,
  with no cross-lane traffic.  The backward kernel leaves the sums over
  channels of ``dB`` and ``dC`` as 128 lane partials, which XLA adds up.
- ``'pallas_interpret'``: the same kernels in the Pallas interpreter (CPU
  tests).
- ``'auto'``: the kernels on a TPU when the channels are a multiple of 128,
  else ``'chunked'``.

``chunk`` is a function of the shapes when not given (as
``ring_attention._splash_block_sizes``): no configuration carries a knob.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax import lax

from bluefog_tpu.metrics import comm as metrics_comm
from bluefog_tpu.tracing import startup

__all__ = ["selective_scan"]

BACKENDS = ("auto", "chunked", "pallas", "pallas_interpret")
_LANES = 128
_SUBLANES = 8       # channel tiles of a kernel block: 1,024 channels


def _default_chunk(t: int) -> int:
    """Tokens a chunk.  The kernel keeps a chunk's ``chunk + 1`` states of
    one channel block in VMEM (64 KiB a token at 1,024 channels and 16
    states: 4 MiB at 64); the ``jax.numpy`` form holds ``(chunk, C, N)``
    arrays in HBM, a dozen at once in the backward pass, and its scan's
    depth is ``log2(chunk)`` passes over them."""
    return min(64, t)


def _resolve(backend: str, channels: int) -> str:
    if backend not in BACKENDS:
        raise ValueError(f"unknown backend {backend!r}; expected one of "
                         f"{BACKENDS}")
    if backend == "auto":
        on_tpu = jax.default_backend() == "tpu"
        return "pallas" if on_tpu and channels % _LANES == 0 else "chunked"
    if backend != "chunked" and channels % _LANES:
        raise ValueError(f"backend={backend!r} needs channels in multiples "
                         f"of {_LANES}, got {channels}")
    return backend


def selective_scan(x, delta, a, b, c, d, *, chunk=None, backend="auto"):
    """``m (B, T, C)`` in ``x``'s dtype; see the module docstring.
    Differentiable in all six operands."""
    if x.shape != delta.shape or b.shape != c.shape or a.shape != (
            x.shape[-1], b.shape[-1]) or d.shape != x.shape[-1:] or (
            b.shape[:2] != x.shape[:2]):
        raise ValueError(
            "selective_scan takes x, delta (B, T, C), a (C, N), b, c "
            f"(B, T, N), d (C,); got {x.shape}, {delta.shape}, {a.shape}, "
            f"{b.shape}, {c.shape}, {d.shape}")
    backend = _resolve(backend, x.shape[-1])
    t = x.shape[1]
    chunk = _default_chunk(t) if chunk is None else min(chunk, t)
    chunks = -(-t // chunk)
    m = _scan(x, delta, a, b, c, d, chunk, backend)
    return metrics_comm.count(
        m, [("bf_ssm_scan_tokens_total", float(x.shape[0] * t)),
            ("bf_ssm_scan_chunks_total", float(x.shape[0] * chunks))])


@functools.partial(jax.custom_vjp, nondiff_argnums=(6, 7))
def _scan(x, delta, a, b, c, d, chunk, backend):
    return _scan_fwd(x, delta, a, b, c, d, chunk, backend)[0]


def _padded(arrays, t, chunk):
    """Time padded up to whole chunks with zeros: a step with ``delta = 0``
    and ``x = 0`` hands the state on as it is."""
    pad = -t % chunk
    if not pad:
        return arrays
    return [jnp.pad(v, ((0, 0), (0, pad), (0, 0))) for v in arrays]


def _scan_fwd(x, delta, a, b, c, d, chunk, backend):
    t = x.shape[1]
    f32 = [v.astype(jnp.float32) for v in (x, delta, b, c)]
    xs, ds, bs, cs = _padded(f32, t, chunk)
    a32, d32 = a.astype(jnp.float32), d.astype(jnp.float32)
    if backend == "chunked":
        m, starts = _chunked_fwd(xs, ds, a32, bs, cs, d32, chunk)
    else:
        m, starts = _pallas_fwd(xs, ds, a32, bs, cs, d32, chunk,
                                backend == "pallas_interpret")
    return m[:, :t].astype(x.dtype), (x, delta, a, b, c, d, starts)


def _scan_bwd(chunk, backend, residuals, dm):
    x, delta, a, b, c, d, starts = residuals
    t = x.shape[1]
    f32 = [v.astype(jnp.float32) for v in (x, delta, b, c, dm)]
    xs, ds, bs, cs, dms = _padded(f32, t, chunk)
    a32, d32 = a.astype(jnp.float32), d.astype(jnp.float32)
    if backend == "chunked":
        grads = _chunked_bwd(xs, ds, a32, bs, cs, d32, starts, dms, chunk)
    else:
        grads = _pallas_bwd(xs, ds, a32, bs, cs, d32, starts, dms, chunk,
                            backend == "pallas_interpret")
    dx, ddelta, da, db, dc, dd = grads
    return (dx[:, :t].astype(x.dtype), ddelta[:, :t].astype(delta.dtype),
            da.astype(a.dtype), db[:, :t].astype(b.dtype),
            dc[:, :t].astype(c.dtype), dd.astype(d.dtype))


_scan.defvjp(_scan_fwd, _scan_bwd)


# ---- 'chunked': jax.numpy ------------------------------------------------

def _by_chunk(v, chunk):
    """``(B, T, ...) -> (T / chunk, B, chunk, ...)``: chunks lead, for
    ``lax.scan``."""
    shaped = v.reshape(v.shape[:1] + (-1, chunk) + v.shape[2:])
    return jnp.moveaxis(shaped, 1, 0)


def _from_chunks(v):
    """The inverse of :func:`_by_chunk`."""
    v = jnp.moveaxis(v, 0, 1)
    return v.reshape(v.shape[:1] + (-1,) + v.shape[3:])


def _compose(left, right):
    """Two steps of ``h -> a h + u`` in a row: ``left`` first."""
    (a_l, u_l), (a_r, u_r) = left, right
    return a_l * a_r, a_r * u_l + u_r


def _chunk_states(h_start, x, delta, a, b):
    """The states of one chunk from the state before it.  ``x, delta
    (B, L, C)``, ``b (B, L, N)``, ``h_start (B, C, N)`` -> the decays and the
    states, ``(B, L, C, N)`` each."""
    decay = jnp.exp(delta[..., None] * a)
    drive = (delta * x)[..., None] * b[:, :, None, :]
    prod, acc = lax.associative_scan(_compose, (decay, drive), axis=1)
    return decay, prod * h_start[:, None] + acc


def _chunked_fwd(x, delta, a, b, c, d, chunk):
    def one_chunk(h, inputs):
        xk, dk, bk, ck = inputs
        _, states = _chunk_states(h, xk, dk, a, bk)
        m = jnp.einsum("blcn,bln->blc", states, ck) + d * xk
        return states[:, -1], (m, h)

    h0 = jnp.zeros(x.shape[:1] + a.shape, jnp.float32)
    _, (m, starts) = lax.scan(
        one_chunk, h0, tuple(_by_chunk(v, chunk) for v in (x, delta, b, c)))
    return _from_chunks(m), starts


def _chunked_bwd(x, delta, a, b, c, d, starts, dm, chunk):
    def one_chunk(carry, inputs):
        # ``follow`` is a_{t+1} g_{t+1} of the first token after this chunk
        follow, da_sum = carry
        xk, dk, bk, ck, dmk, h_start = inputs
        decay, states = _chunk_states(h_start, xk, dk, a, bk)
        before = jnp.concatenate([h_start[:, None], states[:, :-1]], axis=1)
        # g_t = C_t dm_t + a_{t+1} g_{t+1}: the same recurrence in reverse
        # time, each token decayed by the *next* token's factor
        inject = dmk[..., None] * ck[:, :, None, :]
        later = jnp.concatenate(
            [decay[:, 1:], jnp.ones_like(decay[:, :1])], axis=1)
        prod, acc = lax.associative_scan(
            _compose, (later, inject), axis=1, reverse=True)
        g = prod * follow[:, None] + acc
        du = jnp.einsum("blcn,bln->blc", g, bk)
        through_decay = g * before * decay
        ddelta = du * xk + jnp.einsum("blcn,cn->blc", through_decay, a)
        dx = du * dk + d * dmk
        db = jnp.einsum("blcn,blc->bln", g, dk * xk)
        dc = jnp.einsum("blcn,blc->bln", states, dmk)
        da_sum = da_sum + jnp.einsum("blcn,blc->cn", through_decay, dk)
        return (decay[:, 0] * g[:, 0], da_sum), (dx, ddelta, db, dc)

    zero = jnp.zeros(x.shape[:1] + a.shape, jnp.float32)
    (_, da), (dx, ddelta, db, dc) = lax.scan(
        one_chunk, (zero, jnp.zeros_like(a)),
        tuple(_by_chunk(v, chunk) for v in (x, delta, b, c, dm)) + (starts,),
        reverse=True)
    dd = jnp.einsum("btc,btc->c", dm, x)
    return tuple(_from_chunks(v) for v in (dx, ddelta)) + (
        da, _from_chunks(db), _from_chunks(dc), dd)


# ---- 'pallas': the TPU kernels --------------------------------------------
# Kernel layout: channels as (C / 128, 128) tiles, blocks of ``sub`` tiles;
# time leads.  x, delta, m: (B, T, C / 128, 128); a: (N, C / 128, 128);
# chunk-start states: (B, T / chunk, N, C / 128, 128); b, c: (B, T, N) in
# SMEM, a chunk at a time.

def _tiles(channels: int):
    """``(tiles of 128 channels, tiles a block)``."""
    tiles = channels // _LANES
    return tiles, _SUBLANES if tiles % _SUBLANES == 0 else tiles


def _fwd_kernel(b_ref, c_ref, x_ref, dt_ref, a_ref, d_ref, m_ref, start_ref,
                h_ref, *, chunk, states):
    from jax.experimental import pallas as pl

    ci, k = pl.program_id(1), pl.program_id(2)

    @pl.when(ci == 0)
    def _():
        h_ref[k] = jnp.zeros(h_ref.shape[1:], jnp.float32)

    start_ref[...] = h_ref[k]
    a = [a_ref[n] for n in range(states)]
    skip = d_ref[...]

    def step(t, h):
        dt, xt = dt_ref[t], x_ref[t]
        u = dt * xt
        y = skip * xt
        new = []
        for n in range(states):
            hn = jnp.exp(dt * a[n]) * h[n] + b_ref[t, n] * u
            y = y + c_ref[t, n] * hn
            new.append(hn)
        m_ref[t] = y
        return tuple(new)

    h = lax.fori_loop(0, chunk, step,
                      tuple(h_ref[k, n] for n in range(states)))
    for n in range(states):
        h_ref[k, n] = h[n]


def _bwd_kernel(b_ref, c_ref, x_ref, dt_ref, dm_ref, a_ref, d_ref, start_ref,
                dx_ref, ddt_ref, dbp_ref, dcp_ref, da_ref, dd_ref,
                hist_ref, follow_ref, *, chunk, states, sub):
    from jax.experimental import pallas as pl

    ci, k = pl.program_id(1), pl.program_id(2)
    rows = pl.ds(pl.multiple_of(k * sub, sub), sub)

    @pl.when(ci == 0)      # the sequence's last chunk: nothing follows it
    def _():
        follow_ref[k] = jnp.zeros(follow_ref.shape[1:], jnp.float32)
        da_ref[:, rows, :] = jnp.zeros((states, sub, _LANES), jnp.float32)
        dd_ref[rows, :] = jnp.zeros((sub, _LANES), jnp.float32)

    @pl.when(k == 0)
    def _():
        dbp_ref[...] = jnp.zeros(dbp_ref.shape, jnp.float32)
        dcp_ref[...] = jnp.zeros(dcp_ref.shape, jnp.float32)

    a = [a_ref[n] for n in range(states)]

    # the chunk's states again, from its start: hist[t] is the state
    # before token t, hist[t + 1] the state after it
    hist_ref[0] = start_ref[...]

    def replay(t, h):
        dt = dt_ref[t]
        u = dt * x_ref[t]
        new = tuple(jnp.exp(dt * a[n]) * h[n] + b_ref[t, n] * u
                    for n in range(states))
        for n in range(states):
            hist_ref[t + 1, n] = new[n]
        return new

    lax.fori_loop(0, chunk, replay,
                  tuple(start_ref[n] for n in range(states)))

    skip = d_ref[...]

    def step(i, carry):
        follow, dd = carry
        t = chunk - 1 - i
        dt, xt, dmt = dt_ref[t], x_ref[t], dm_ref[t]
        u = dt * xt
        du = jnp.zeros_like(dt)
        ddt = jnp.zeros_like(dt)
        new = []
        for n in range(states):
            decay = jnp.exp(dt * a[n])
            g = c_ref[t, n] * dmt + follow[n]
            dcp_ref[t, pl.ds(n, 1), :] += jnp.sum(
                dmt * hist_ref[t + 1, n], axis=0, keepdims=True)
            dbp_ref[t, pl.ds(n, 1), :] += jnp.sum(
                g * u, axis=0, keepdims=True)
            du = du + b_ref[t, n] * g
            through = g * hist_ref[t, n] * decay
            ddt = ddt + through * a[n]
            da_ref[n, rows, :] += through * dt
            new.append(decay * g)
        ddt_ref[t] = du * xt + ddt
        dx_ref[t] = du * dt + skip * dmt
        return tuple(new), dd + dmt * xt

    follow, dd = lax.fori_loop(
        0, chunk, step,
        (tuple(follow_ref[k, n] for n in range(states)),
         jnp.zeros((sub, _LANES), jnp.float32)))
    for n in range(states):
        follow_ref[k, n] = follow[n]
    dd_ref[rows, :] += dd


def _kernel_operands(x, delta, a, d):
    tiles, _ = _tiles(x.shape[-1])
    tiled = x.shape[:2] + (tiles, _LANES)
    return (x.reshape(tiled), delta.reshape(tiled),
            a.T.reshape(a.shape[1], tiles, _LANES), d.reshape(tiles, _LANES))


def _pallas_fwd(x, delta, a, b, c, d, chunk, interpret):
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    batch, t, channels = x.shape
    states = a.shape[1]
    tiles, sub = _tiles(channels)
    chunks = t // chunk
    x4, dt4, a3, d2 = _kernel_operands(x, delta, a, d)
    scalars = pl.BlockSpec((None, chunk, states), lambda i, j, k: (i, j, 0),
                           memory_space=pltpu.SMEM)
    tokens = pl.BlockSpec((None, chunk, sub, _LANES),
                          lambda i, j, k: (i, j, k, 0))
    startup.kernel_traced("bf_selective_scan_fwd")
    m, starts = pl.pallas_call(
        functools.partial(_fwd_kernel, chunk=chunk, states=states),
        grid=(batch, chunks, tiles // sub),
        in_specs=[scalars, scalars, tokens, tokens,
                  pl.BlockSpec((states, sub, _LANES),
                               lambda i, j, k: (0, k, 0)),
                  pl.BlockSpec((sub, _LANES), lambda i, j, k: (k, 0))],
        out_specs=[tokens,
                   pl.BlockSpec((None, None, states, sub, _LANES),
                                lambda i, j, k: (i, j, 0, k, 0))],
        out_shape=[jax.ShapeDtypeStruct(x4.shape, jnp.float32),
                   jax.ShapeDtypeStruct(
                       (batch, chunks, states, tiles, _LANES), jnp.float32)],
        scratch_shapes=[pltpu.VMEM((tiles // sub, states, sub, _LANES),
                                   jnp.float32)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary", "arbitrary")),
        interpret=interpret, name="bf_selective_scan_fwd",
    )(b, c, x4, dt4, a3, d2)
    return m.reshape(x.shape), starts


def _pallas_bwd(x, delta, a, b, c, d, starts, dm, chunk, interpret):
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    batch, t, channels = x.shape
    states = a.shape[1]
    tiles, sub = _tiles(channels)
    chunks = t // chunk
    x4, dt4, a3, d2 = _kernel_operands(x, delta, a, d)
    dm4 = dm.reshape(x4.shape)

    def back(j):        # the grid walks the chunks in reverse time
        return chunks - 1 - j

    scalars = pl.BlockSpec((None, chunk, states),
                           lambda i, j, k: (i, back(j), 0),
                           memory_space=pltpu.SMEM)
    tokens = pl.BlockSpec((None, chunk, sub, _LANES),
                          lambda i, j, k: (i, back(j), k, 0))
    partials = pl.BlockSpec((None, chunk, states, _LANES),
                            lambda i, j, k: (i, back(j), 0, 0))
    state_block = (tiles // sub, states, sub, _LANES)
    startup.kernel_traced("bf_selective_scan_bwd")
    dx, ddt, dbp, dcp, da, dd = pl.pallas_call(
        functools.partial(_bwd_kernel, chunk=chunk, states=states, sub=sub),
        grid=(batch, chunks, tiles // sub),
        in_specs=[scalars, scalars, tokens, tokens, tokens,
                  pl.BlockSpec((states, sub, _LANES),
                               lambda i, j, k: (0, k, 0)),
                  pl.BlockSpec((sub, _LANES), lambda i, j, k: (k, 0)),
                  pl.BlockSpec((None, None, states, sub, _LANES),
                               lambda i, j, k: (i, back(j), 0, k, 0))],
        out_specs=[tokens, tokens, partials, partials,
                   pl.BlockSpec((None, states, tiles, _LANES),
                                lambda i, j, k: (i, 0, 0, 0)),
                   pl.BlockSpec((None, tiles, _LANES),
                                lambda i, j, k: (i, 0, 0))],
        out_shape=[jax.ShapeDtypeStruct(x4.shape, jnp.float32),
                   jax.ShapeDtypeStruct(x4.shape, jnp.float32),
                   jax.ShapeDtypeStruct((batch, t, states, _LANES),
                                        jnp.float32),
                   jax.ShapeDtypeStruct((batch, t, states, _LANES),
                                        jnp.float32),
                   jax.ShapeDtypeStruct((batch, states, tiles, _LANES),
                                        jnp.float32),
                   jax.ShapeDtypeStruct((batch, tiles, _LANES), jnp.float32)],
        scratch_shapes=[pltpu.VMEM((chunk + 1, states, sub, _LANES),
                                   jnp.float32),
                        pltpu.VMEM(state_block, jnp.float32)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary", "arbitrary")),
        interpret=interpret, name="bf_selective_scan_bwd",
    )(b, c, x4, dt4, dm4, a3, d2, starts)
    return (dx.reshape(x.shape), ddt.reshape(x.shape),
            da.sum(0).reshape(states, channels).T, dbp.sum(-1), dcp.sum(-1),
            dd.sum(0).reshape(channels))
