"""Kimi Delta Attention (Kimi Linear, arXiv:2510.26692 section 3): the delta
rule with a log-decay **per channel**, chunked into matmul form, with its
backward pass.

    S_t = (I - beta_t k_t k_t^T) Diag(exp g_t) S_{t-1} + beta_t k_t v_t^T
    o_t = S_t^T q_t,                                         S_0 = 0

``q, k, g (B, T, H, d_k)``, ``v (B, T, H, d_v)``, ``beta (B, T, H)``; ``g``
is the log-decay, at most 0 and **no less than** :data:`LOWER` **a token**
(the configuration's ``kda_lower_bound``: the bound is what makes the
chunked form computable, below).  The state ``S (d_k, d_v)`` is f32 and is
never written out a token: only the state at each chunk's start is saved
(``T / 64`` of them), and the backward pass recomputes a chunk from it.

**A chunk of 64 tokens in matmul form.**  With ``G_t`` the decay summed
from the chunk's start through token ``t``, ``kb = beta * k`` and ``w_t =
v_t - S_{t-1}^T (exp(g_t) * k_t)`` (what the delta rule writes):

    A_ts = sum_c k_tc kb_sc exp(G_tc - G_sc)   (s < t)
    B_ts = sum_c q_tc kb_sc exp(G_tc - G_sc)   (s <= t)
    (I + A) W = V - (K * exp G) S_0            (unit lower triangular)
    O = (Q * exp G) S_0 + B W
    S_C = Diag(exp G_C) S_0 + (KB * exp(G_C - G))^T W

**The decay ratios** ``exp(G_t - G_s)`` are products of a left factor
``exp(G_t - R)`` and a right factor ``exp(R - G_s)`` about a reference ``R``,
the decay at the first token of ``t``'s sub-block of 16: the left exponent
lies in ``(16 * LOWER, 0]`` and the right one is at most ``15 * |LOWER|``
wherever ``s <= t`` (75 < 88, f32's range; it is clamped at 80 where ``s >
t``, which the causal mask drops).  ``exp(-G)`` over a whole chunk would
overflow at 64 * 5 = 320.  ``(I + A)^-1`` is exact and all matmuls: the
diagonal 16-blocks ``D`` are nilpotent of order 16, so ``(I + D)^-1 = (I -
D)(I + D^2)(I + D^4)(I + D^8)``, and the block-strictly-lower rest ``F``
gives ``M = (I + D)^-1 F`` with ``M^4 = 0``, so ``(I + A)^-1 = (I + M^2)(I
- M)(I + D)^-1``.

Operands of the products with the tokens' width (the factors above, the
state as an operand, ``B`` and ``W``) are in the inputs' dtype (bf16 where
the model computes in bf16), accumulated in f32; the state, ``G``, ``A``,
its inverse and ``W`` are f32, the inverse's products at ``highest``.

The backward pass of a chunk is ``jax.vjp`` of that chunk function, taken
where the chunk is computed (inside the Pallas kernel too), walking the
chunks in reverse time with the state's cotangent carried.  One step of the
chunk carries a rule of its own: the solve ``W = (I + A)^-1 R`` has the
closed-form adjoint ``dR = (I + A)^-T dW``, ``dA = -dR W^T`` (two f32
products, which the chunk's own mask of ``A`` then masks), where autodiff
would walk back through the ten products of the inverse with twenty.  And
the inverse is not built a second time: the forward pass saves it beside the
chunk-start states (``T / 64`` matrices of 64 x 64 f32 a head) and the
backward pass hands it to the chunk function.  With bf16 operands that
leaves a chunk's backward 3 f32 products at ``highest`` (``W`` again, the
adjoint's two) beside 21 others, where autodiff alone took 33; the chunk
function is still the only place the mathematics lives.

Backends (``backend=``):

- ``'chunked'``: plain ``jax.numpy``, a ``lax.scan`` over chunks.  What the
  CPU and CI run.
- ``'pallas'``: the TPU kernels ``bf_kda_fwd`` and ``bf_kda_bwd_chunks``
  (the names a profiler trace shows, and what the benchmark's ``kda_scan_*``
  metrics read).  Grid: batch, blocks of heads, chunks in order; a head's
  state (and, backward, its cotangent) lives in VMEM scratch between chunks.
  The operands are read as ``(64, heads a block * 128)`` blocks of ``(B, T,
  H * d)``, the layout the projections write: no relayout around the
  kernels.
- ``'pallas_interpret'``: the same kernels in the Pallas interpreter (CPU
  tests).
- ``'auto'``: the kernels on a TPU when ``d_k`` and ``d_v`` are multiples
  of 128, else ``'chunked'``.
"""

from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
from jax import lax

from bluefog_tpu.metrics import comm as metrics_comm
from bluefog_tpu.tracing import startup

__all__ = ["kda", "LOWER"]

BACKENDS = ("auto", "chunked", "pallas", "pallas_interpret")
CHUNK = 64
SUB = 16            # tokens a reference point of the decay ratios serves
LOWER = -5.0        # the least log-decay a token: SUB * |LOWER| = 80 < 88
_CLAMP = SUB * -LOWER
_LANES = 128


def _resolve(backend: str, d_k: int, d_v: int) -> str:
    if backend not in BACKENDS:
        raise ValueError(f"unknown backend {backend!r}; expected one of "
                         f"{BACKENDS}")
    tiled = d_k % _LANES == 0 and d_v % _LANES == 0
    if backend == "auto":
        on_tpu = jax.default_backend() == "tpu"
        return "pallas" if on_tpu and tiled else "chunked"
    if backend != "chunked" and not tiled:
        raise ValueError(f"backend={backend!r} needs d_k and d_v in "
                         f"multiples of {_LANES}, got {d_k}, {d_v}")
    return backend


def kda(q, k, v, g, beta, *, backend="auto"):
    """``o (B, T, H, d_v)`` in ``v``'s dtype; see the module docstring.
    Differentiable in all five operands.  ``g`` below :data:`LOWER` is the
    caller's error: the ratios may then overflow."""
    if not (q.shape == k.shape == g.shape and v.shape[:3] == q.shape[:3]
            and beta.shape == q.shape[:3] and q.ndim == 4):
        raise ValueError(
            "kda takes q, k, g (B, T, H, d_k), v (B, T, H, d_v), beta "
            f"(B, T, H); got {q.shape}, {k.shape}, {g.shape}, {v.shape}, "
            f"{beta.shape}")
    b, t, h, d_k = q.shape
    backend = _resolve(backend, d_k, v.shape[-1])
    pad = -t % CHUNK
    chunks = (t + pad) // CHUNK
    kb = (k.astype(jnp.float32) * beta[..., None]).astype(k.dtype)

    def flat(x):     # (B, T, H, d) -> (B, chunks * CHUNK, H * d): no copy
        x = x.reshape(b, t, -1)
        return jnp.pad(x, ((0, 0), (0, pad), (0, 0))) if pad else x

    # the decay summed from each chunk's start, in f32; padded tokens decay
    # nothing and write nothing, so the state passes them unchanged
    decay = flat(g.astype(jnp.float32)).reshape(b, chunks, CHUNK, h * d_k)
    decay = jnp.cumsum(decay, axis=2).reshape(b, chunks * CHUNK, h * d_k)
    o = _scan(flat(q), flat(k), flat(kb), flat(v), decay, h, backend)
    o = o[:, :t].reshape(b, t, h, -1)
    return metrics_comm.count(o, [("bf_kda_chunks_total",
                                   float(b * h * chunks))])


# ---- the chunk's triangular solve, with its adjoint --------------------------

_EXACT = dict(precision=lax.Precision.HIGHEST,
              preferred_element_type=jnp.float32)


def _inverse(a):
    """``(I + a)^-1`` of a strictly lower-triangular f32 ``a (n, n)``, ``n`` a
    multiple of :data:`SUB`: ten products, exact (module docstring)."""
    n = a.shape[0]
    rows = lax.broadcasted_iota(jnp.int32, (n, 1), 0)
    cols = lax.broadcasted_iota(jnp.int32, (1, n), 1)
    eye = (rows == cols).astype(jnp.float32)
    d1 = jnp.where(rows // SUB == cols // SUB, a, 0.0)
    rest = a - d1
    d2 = jnp.dot(d1, d1, **_EXACT)
    d4 = jnp.dot(d2, d2, **_EXACT)
    d8 = jnp.dot(d4, d4, **_EXACT)
    inv = jnp.dot(eye - d1, eye + d2, **_EXACT)
    inv = jnp.dot(inv, eye + d4, **_EXACT)
    inv = jnp.dot(inv, eye + d8, **_EXACT)
    m1 = jnp.dot(inv, rest, **_EXACT)
    m2 = jnp.dot(m1, m1, **_EXACT)
    inv = inv - jnp.dot(m1, inv, **_EXACT)
    return inv + jnp.dot(m2, inv, **_EXACT)


@jax.custom_vjp
def _solve(inv, a, r):
    """``w = (I + a)^-1 r``, f32, with ``inv = (I + a)^-1`` handed in: built
    by the forward pass, read by the backward one.  Its cotangents are closed
    form, ``dr = (I + a)^-T dw`` and ``da = -dr w^T``: two products, where
    autodiff through :func:`_inverse` takes twenty.  ``inv`` takes none: it
    is no free variable, ``da`` is the whole of ``a``'s."""
    return _solve_fwd(inv, a, r)[0]


def _solve_fwd(inv, a, r):
    w = jnp.dot(inv, r, **_EXACT)
    return w, (inv, w)


def _solve_bwd(residuals, dw):
    inv, w = residuals
    dr = lax.dot_general(inv, dw, (((0,), (0,)), ((), ())), **_EXACT)
    da = -lax.dot_general(dr, w, (((1,), (1,)), ((), ())), **_EXACT)
    return None, da, dr


_solve.defvjp(_solve_fwd, _solve_bwd)


# ---- one chunk ------------------------------------------------------------

def _chunk(state, q, k, kb, v, decay, inv=None):
    """One head's chunk: ``state (d_v, d_k)`` f32 (the transpose of ``S``,
    so that the decay runs along lanes), ``q, k, kb, decay (CHUNK, d_k)``,
    ``v (CHUNK, d_v)`` -> ``(o (CHUNK, d_v)`` f32, the state after the
    chunk``)`` and ``(I + A)^-1 (CHUNK, CHUNK)`` f32, which the forward pass
    builds (``inv=None``) and saves and the backward pass hands back in.
    Plain ``jax.numpy`` on values: the ``chunked`` backend maps it over
    batch and heads, the kernels call it on what they loaded."""
    dtype = q.dtype
    f32 = jnp.float32

    def dot(a, b, contract):
        """Operands in the inputs' dtype, f32 accumulation."""
        precision = lax.Precision.HIGHEST if dtype == f32 else None
        return lax.dot_general(a.astype(dtype), b.astype(dtype),
                               ((contract[:1], contract[1:]), ((), ())),
                               precision=precision,
                               preferred_element_type=f32)

    q32, k32, kb32 = q.astype(f32), k.astype(f32), kb.astype(f32)
    n = q.shape[0]
    rows = lax.broadcasted_iota(jnp.int32, (n, 1), 0)
    cols = lax.broadcasted_iota(jnp.int32, (1, n), 1)
    block = rows // SUB            # the sub-block a token lies in

    def row(i):                    # decay[i] as (1, d_k), without a slice
        return jnp.sum(jnp.where(rows == i, decay, 0.0), axis=0,
                       keepdims=True)

    firsts = [row(i) for i in range(0, n, SUB)]
    ref = sum(jnp.where(block == j, r, 0.0) for j, r in enumerate(firsts))
    left = jnp.exp(decay - ref)
    # keys above queries: one product a reference point serves both
    both = jnp.concatenate([k32 * left, q32 * left], axis=0)
    twice = jnp.concatenate([block, block], axis=0)
    ab = jnp.zeros((2 * n, n), f32)
    for j, r in enumerate(firsts):
        right = kb32 * jnp.exp(jnp.minimum(r - decay, _CLAMP))
        ab = ab + jnp.where(twice == j, dot(both, right, (1, 1)), 0.0)
    a = jnp.where(rows > cols, ab[:n], 0.0)
    b = jnp.where(rows >= cols, ab[n:], 0.0)

    through = jnp.exp(decay)
    carried = dot(jnp.concatenate([k32 * through, q32 * through], axis=0),
                  state, (1, 1))            # what the state gives k and q
    if inv is None:
        inv = lax.stop_gradient(_inverse(a))
    w = _solve(inv, a, v.astype(f32) - carried[:n])
    o = carried[n:] + dot(b, w, (1, 0))
    last = row(n - 1)
    state = state * jnp.exp(last) + dot(w, kb32 * jnp.exp(last - decay),
                                        (0, 0))
    return (o, state), inv


# ---- the scan over chunks, with its backward --------------------------------

@functools.partial(jax.custom_vjp, nondiff_argnums=(5, 6))
def _scan(q, k, kb, v, decay, heads, backend):
    return _scan_fwd(q, k, kb, v, decay, heads, backend)[0]


def _scan_fwd(q, k, kb, v, decay, heads, backend):
    if backend == "chunked":
        o, starts, inverses = _chunked_fwd(q, k, kb, v, decay, heads)
    else:
        o, starts, inverses = _pallas_fwd(q, k, kb, v, decay, heads,
                                          backend == "pallas_interpret")
    return o, (q, k, kb, v, decay, starts, inverses)


def _scan_bwd(heads, backend, residuals, do):
    if backend == "chunked":
        return _chunked_bwd(*residuals, do, heads)
    return _pallas_bwd(*residuals, do, heads, backend == "pallas_interpret")


_scan.defvjp(_scan_fwd, _scan_bwd)


# ---- 'chunked': jax.numpy ---------------------------------------------------

def _by_chunk(x, heads):
    """``(B, T, H * d) -> (T / CHUNK, B, H, CHUNK, d)``: chunks lead, for
    ``lax.scan``."""
    b, t, hd = x.shape
    x = x.reshape(b, t // CHUNK, CHUNK, heads, hd // heads)
    return jnp.transpose(x, (1, 0, 3, 2, 4))


def _from_chunks(x):
    """The inverse of :func:`_by_chunk`."""
    c, b, h, n, d = x.shape
    return jnp.transpose(x, (1, 0, 3, 2, 4)).reshape(b, c * n, h * d)


_heads_chunk = jax.vmap(jax.vmap(_chunk))      # over batch, then heads


def _chunked_fwd(q, k, kb, v, decay, heads):
    def one_chunk(state, inputs):
        (o, after), inv = _heads_chunk(state, *inputs)
        return after, (o, state, inv)

    b, d_k, d_v = q.shape[0], q.shape[2] // heads, v.shape[2] // heads
    zero = jnp.zeros((b, heads, d_v, d_k), jnp.float32)
    _, (o, starts, inverses) = lax.scan(
        one_chunk, zero, tuple(_by_chunk(x, heads)
                               for x in (q, k, kb, v, decay)))
    return _from_chunks(o).astype(v.dtype), starts, inverses


def _chunked_bwd(q, k, kb, v, decay, starts, inverses, do, heads):
    def one_chunk(d_after, inputs):
        *operands, start, inv, d_o = inputs
        _, pull, _ = jax.vjp(lambda *a: _heads_chunk(*a, inv), start,
                             *operands, has_aux=True)
        d_start, *d_operands = pull((d_o.astype(jnp.float32), d_after))
        return d_start, tuple(d_operands)

    operands = tuple(_by_chunk(x, heads) for x in (q, k, kb, v, decay, do))
    _, grads = lax.scan(one_chunk, jnp.zeros_like(starts[0]),
                        operands[:5] + (starts, inverses, operands[5]),
                        reverse=True)
    return tuple(_from_chunks(x) for x in grads)


# ---- 'pallas': the TPU kernels ----------------------------------------------
# Grid (batch, block of heads, chunk); every operand with a time axis is read
# as the (CHUNK, heads a block * d) block of its (B, T, H * d) array, the
# chunks' inverses among them ((B, T, H * CHUNK) f32: a head's 64 columns of
# its chunk's 64 rows, so that the lanes are full); the chunk-start states
# are (B, H, T / CHUNK, d_v, d_k) f32.  A grid step works
# its heads' chunks one after the other in one basic block: the chains of
# dependent products of different heads are independent, and the scheduler
# overlaps them.

_HEADS_A_STEP = 4      # 1, 2, 4 measured at 16 heads of 128, T=8,192: value
# and gradients in 18.5, 18.0 and 17.7 ms (PERF.md section 6, PR 41)


def _heads_a_step(heads: int) -> int:
    return math.gcd(heads, _HEADS_A_STEP)


def _head(ref, j, heads):
    """Head ``j``'s ``(CHUNK, d)`` columns of a ``(CHUNK, heads * d)`` ref."""
    d = ref.shape[-1] // heads
    return ref[:, j * d:(j + 1) * d]


def _fwd_kernel(q_ref, k_ref, kb_ref, v_ref, decay_ref, o_ref, start_ref,
                inv_ref, state_ref):
    from jax.experimental import pallas as pl

    @pl.when(pl.program_id(2) == 0)
    def _():
        state_ref[...] = jnp.zeros(state_ref.shape, jnp.float32)

    heads = state_ref.shape[0]
    d_v = o_ref.shape[-1] // heads
    for j in range(heads):
        state = state_ref[j]
        start_ref[j] = state
        (o, after), inv = _chunk(state, *(_head(ref, j, heads) for ref in (
            q_ref, k_ref, kb_ref, v_ref, decay_ref)))
        o_ref[:, j * d_v:(j + 1) * d_v] = o.astype(o_ref.dtype)
        inv_ref[:, j * CHUNK:(j + 1) * CHUNK] = inv
        state_ref[j] = after


def _bwd_kernel(q_ref, k_ref, kb_ref, v_ref, decay_ref, do_ref, start_ref,
                inv_ref, dq_ref, dk_ref, dkb_ref, dv_ref, ddecay_ref,
                carried_ref):
    from jax.experimental import pallas as pl

    @pl.when(pl.program_id(2) == 0)    # the last chunk: nothing follows it
    def _():
        carried_ref[...] = jnp.zeros(carried_ref.shape, jnp.float32)

    heads = carried_ref.shape[0]
    for j in range(heads):
        inv = _head(inv_ref, j, heads)
        _, pull, _ = jax.vjp(lambda *a: _chunk(*a, inv), start_ref[j], *(
            _head(ref, j, heads) for ref in (q_ref, k_ref, kb_ref, v_ref,
                                             decay_ref)), has_aux=True)
        d_start, *grads = pull((_head(do_ref, j, heads).astype(jnp.float32),
                                carried_ref[j]))
        for ref, grad in zip((dq_ref, dk_ref, dkb_ref, dv_ref, ddecay_ref),
                             grads):
            d = ref.shape[-1] // heads
            ref[:, j * d:(j + 1) * d] = grad
        carried_ref[j] = d_start


def _specs(block, d_k, d_v, chunk_of):
    """Block specs of a ``d_k``-wide and a ``d_v``-wide operand, of the
    chunks' inverses and of the chunk-start states, ``block`` heads a grid
    step; ``chunk_of(j)`` is the chunk the grid's ``j``-th step works on."""
    from jax.experimental import pallas as pl

    def tokens(d):
        return pl.BlockSpec((None, CHUNK, block * d),
                            lambda i, h, j: (i, chunk_of(j), h))

    states = pl.BlockSpec((None, block, None, d_v, d_k),
                          lambda i, h, j: (i, h, chunk_of(j), 0, 0))
    return tokens(d_k), tokens(d_v), tokens(CHUNK), states


def _pallas_fwd(q, k, kb, v, decay, heads, interpret):
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    b, t, _ = q.shape
    d_k, d_v, chunks = q.shape[2] // heads, v.shape[2] // heads, t // CHUNK
    block = _heads_a_step(heads)
    keyed, valued, inverse, states = _specs(block, d_k, d_v, lambda j: j)
    startup.kernel_traced("bf_kda_fwd")
    return pl.pallas_call(
        _fwd_kernel, grid=(b, heads // block, chunks),
        in_specs=[keyed, keyed, keyed, valued, keyed],
        out_specs=[valued, states, inverse],
        out_shape=[jax.ShapeDtypeStruct(v.shape, v.dtype),
                   jax.ShapeDtypeStruct((b, heads, chunks, d_v, d_k),
                                        jnp.float32),
                   jax.ShapeDtypeStruct((b, t, heads * CHUNK), jnp.float32)],
        scratch_shapes=[pltpu.VMEM((block, d_v, d_k), jnp.float32)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary")),
        interpret=interpret, name="bf_kda_fwd",
    )(q, k, kb, v, decay)


def _pallas_bwd(q, k, kb, v, decay, starts, inverses, do, heads, interpret):
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    b, t, _ = q.shape
    d_k, d_v, chunks = q.shape[2] // heads, v.shape[2] // heads, t // CHUNK
    block = _heads_a_step(heads)
    keyed, valued, inverse, states = _specs(block, d_k, d_v,
                                            lambda j: chunks - 1 - j)
    startup.kernel_traced("bf_kda_bwd_chunks")
    return tuple(pl.pallas_call(
        _bwd_kernel, grid=(b, heads // block, chunks),
        in_specs=[keyed, keyed, keyed, valued, keyed, valued, states,
                  inverse],
        out_specs=[keyed, keyed, keyed, valued, keyed],
        out_shape=[jax.ShapeDtypeStruct(x.shape, x.dtype)
                   for x in (q, k, kb, v, decay)],
        scratch_shapes=[pltpu.VMEM((block, d_v, d_k), jnp.float32)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary")),
        interpret=interpret, name="bf_kda_bwd_chunks",
    )(q, k, kb, v, decay, do, starts, inverses))
