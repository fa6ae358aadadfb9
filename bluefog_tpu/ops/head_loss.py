"""The language-model head and its cross entropy a chunk of token rows at a
time, with the head's two gradients made in the same pass.

Whole f32 logits are ``rows x V`` elements, and their gradient as many
again: 2 x 2.15 GB at 32,768 rows of a 16,384-row vocabulary, the largest
temporaries of the step (PERF.md section 6, PR 46).  :func:`head_loss` is the
same mean cross entropy with no array of more than a chunk's rows by ``V``,
forward or backward, and the three matmuls the whole form runs (logits,
``d h``, ``d w``), each once: the loss is a scalar mean, so ``d logits =
(softmax - onehot) / rows`` is known as soon as a chunk's logits are, and the
backward pass is left two products with the scalar cotangent.  With
``weights`` a row (a looped model's exits, each weighted by the probability
the gate gives it) the same pass makes ``sum_i w_i CE_i``: ``d logits_i = w_i
(softmax_i - onehot_i)`` is as soon known, and the cross entropy a row goes
out beside the loss, since it is the loss's gradient in ``w_i``.  One caller,
``models/transformer.py::next_token_loss``.
"""

from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
from jax import lax

from bluefog_tpu.metrics import comm as metrics_comm

__all__ = ["chunk_rows", "head_loss"]

_CHUNK_ELEMENTS = 1 << 26     # of a chunk's logits: 256 MB in f32
_MIN_ROWS = 1024              # a chunk's matmuls stay this tall


def chunk_rows(rows: int, vocab: int) -> int:
    """Token rows a chunk of :func:`head_loss`: all ``rows`` where their
    logits fit ``_CHUNK_ELEMENTS``, else the largest power of two whose
    logits do and that cuts ``rows`` into four chunks or more, not below
    ``_MIN_ROWS`` (4,096 at 32,768 rows of a 16,384-row vocabulary, 2,048
    at 8,192 rows of 16,160 to 25,008).  Four, since an untied head's last
    chunk runs outside the loop, where XLA is free to keep its arrays as
    long as it likes: at two chunks of 4,096 ``joyai``'s step kept 1.3 GB
    of them across its MTP block (PERF.md section 6, PR 46)."""
    if rows * vocab <= _CHUNK_ELEMENTS:
        return rows
    fit = max(_CHUNK_ELEMENTS // vocab, 1)
    c = max(_MIN_ROWS, 1 << (fit.bit_length() - 1))
    while c > _MIN_ROWS and 4 * c > rows:
        c //= 2
    return min(rows, c)


def head_loss(h, w, targets, *, tied: bool = False, site: str = "main",
              weights=None):
    """The mean over all rows of the cross entropy of ``h @ w`` against
    ``targets``: ``h (..., D)`` hidden states, ``targets (...)`` ids, ``w``
    the head's leaf, ``(D, V)`` or, ``tied``, the token table ``(V, D)``.
    With ``weights (...)``, f32, a weight a row, it is the weighted **sum**
    ``sum_i weights_i CE_i`` (a mean is the caller's ``weights / rows``).
    Logits in f32 from the f32 operands at the default matmul precision,
    as ``nn.Dense(dtype=float32)`` makes them; the value and both gradients
    are those of ``optax.softmax_cross_entropy_with_integer_labels`` on
    whole logits up to the order of f32 sums.

    Differentiable in ``h`` and ``w`` by a rule of its own (reverse mode):
    the forward pass of a gradient computes ``d h`` and ``d w`` beside the
    loss, :func:`chunk_rows` rows at a time (one ``lax.scan`` body; a short
    last chunk, and an untied head's last chunk, runs after the loop), and
    keeps them, the size of ``h`` and of ``w``, for the backward pass to
    scale.  And in ``weights``, where given: the rule keeps the rows' cross
    entropies too (``d loss / d weights_i = CE_i``, f32, one a row).

    With metrics on, the gauges ``bf_head_loss_chunks`` and
    ``bf_head_loss_chunk_rows`` hold, by ``site``, what the call traced last.
    """
    rows = math.prod(h.shape[:-1])
    c = chunk_rows(rows, w.shape[0 if tied else 1])
    metrics_comm.set("bf_head_loss_chunks", -(-rows // c), site=site)
    metrics_comm.set("bf_head_loss_chunk_rows", c, site=site)
    if weights is None:
        return _head_loss(h, w, targets, tied, c)
    return _weighted_head_loss(h, w, weights.astype(jnp.float32), targets,
                               tied, c)


def _chunked(h, w, targets, tied, c, with_grads, weights=None):
    """``(loss, d h, d w, ce)`` of the mean cross entropy, ``c`` rows at a
    time, or with ``weights`` of the weighted sum; the gradients ``None``
    unless asked for, ``ce`` (the cross entropy a row, in ``weights``'
    shape) ``None`` without ``weights``."""
    rows, d = math.prod(h.shape[:-1]), h.shape[-1]
    h2, t2 = h.reshape(rows, d), targets.reshape(rows)
    weighted = weights is not None
    w2 = weights.reshape(rows) if weighted else None
    wf = w.astype(jnp.float32)      # once, not once a chunk
    v_axis = 0 if tied else 1

    def contract(a, a_axis, b, b_axis):
        return lax.dot_general(a, b, (((a_axis,), (b_axis,)), ((), ())))

    def chunk(dw, hc, tc, wc=None):
        hc = hc.astype(jnp.float32)
        with jax.named_scope("bf.head.logits"):
            logits = contract(hc, 1, wf, 1 - v_axis)
        with jax.named_scope("bf.head.loss"):
            top = logits.max(axis=-1, keepdims=True)
            e = jnp.exp(logits - top)
            z = e.sum(axis=-1, keepdims=True)
            mine = jnp.take_along_axis(logits, tc[:, None], axis=-1)
            ce = jnp.log(z) + top - mine
            loss = (wc[:, None] * ce).sum() if weighted else ce.sum()
            ce = ce[:, 0] if weighted else None
            if not with_grads:
                return dw, loss, None, ce
            hit = tc[:, None] == jnp.arange(logits.shape[-1])[None, :]
            dlogits = e / z - hit.astype(jnp.float32)
            dlogits = dlogits * wc[:, None] if weighted else dlogits / rows
        with jax.named_scope("bf.head.logits"):
            dh = contract(dlogits, 1, wf, v_axis).astype(h.dtype)
            dw = dw + (contract(dlogits, 0, hc, 0) if tied
                       else contract(hc, 0, dlogits, 0))
        return dw, loss, dh, ce

    # An untied head's last chunk runs after the loop (as any short last
    # chunk does), so that the sum of ``d w`` ends in a matmul of the step's
    # own: XLA makes the leaf's optimizer update that matmul's epilogue and
    # runs it at once, as it does with whole logits.  A sum that ends inside
    # the loop is a loop's result, whose update XLA puts two blocks later with
    # the leaf-sized f32 buffer alive till then (+ 0.33 GiB on ling3flash's
    # backward peak).  A tied table's update waits for the lookup's gradient
    # either way, and its chunks are better off all in the loop (PERF.md
    # section 6, PR 46).
    chunks = -(-rows // c)
    n = chunks if tied and not rows % c else chunks - 1
    dw = jnp.zeros(w.shape, jnp.float32) if with_grads else None
    dh = jnp.zeros((rows, d), h.dtype) if with_grads else None

    def body(carry, xs):
        dw, dh = carry
        i, hc, tc, *wc = xs
        dw, loss, dh_c, ce = chunk(dw, hc, tc, *wc)
        if with_grads:      # in place: the chunks' d h are never put together
            with jax.named_scope("bf.head.logits"):
                dh = lax.dynamic_update_slice(dh, dh_c, (i * c, 0))
        return (dw, dh), (loss, ce)

    loss, ces = 0.0, []     # ``ces``: the rows' cross entropies, by piece
    if n:
        xs = (jnp.arange(n), h2[:n * c].reshape(n, c, d),
              t2[:n * c].reshape(n, c))
        if weighted:
            xs += (w2[:n * c].reshape(n, c),)
        (dw, dh), (losses, ce) = lax.scan(body, (dw, dh), xs)
        loss = losses.sum()
        if weighted:
            ces.append(ce.reshape(n * c))
    if n < chunks:
        dw, last, dh_c, ce = chunk(dw, h2[n * c:], t2[n * c:],
                                   *((w2[n * c:],) if weighted else ()))
        loss = loss + last
        if weighted:
            ces.append(ce)
        if with_grads:
            with jax.named_scope("bf.head.logits"):
                dh = dh.at[n * c:].set(dh_c)
    if weighted:
        with jax.named_scope("bf.head.loss"):
            ce = jnp.concatenate(ces).reshape(weights.shape)
    else:
        loss, ce = loss / rows, None
    if not with_grads:
        return loss, None, None, ce
    return loss, dh.reshape(h.shape), dw.astype(w.dtype), ce


@functools.partial(jax.custom_vjp, nondiff_argnums=(3, 4))
def _head_loss(h, w, targets, tied, c):
    return _chunked(h, w, targets, tied, c, False)[0]


def _forward(h, w, targets, tied, c):
    loss, dh, dw, _ = _chunked(h, w, targets, tied, c, True)
    return loss, (dh, dw)


def _backward(tied, c, gradients, g):
    dh, dw = gradients
    with jax.named_scope("bf.head.logits"):
        return (dh * g).astype(dh.dtype), (dw * g).astype(dw.dtype), None


_head_loss.defvjp(_forward, _backward)


@functools.partial(jax.custom_vjp, nondiff_argnums=(4, 5))
def _weighted_head_loss(h, w, weights, targets, tied, c):
    return _chunked(h, w, targets, tied, c, False, weights)[0]


def _weighted_forward(h, w, weights, targets, tied, c):
    loss, *gradients = _chunked(h, w, targets, tied, c, True, weights)
    return loss, tuple(gradients)


def _weighted_backward(tied, c, gradients, g):
    dh, dw, ce = gradients
    with jax.named_scope("bf.head.logits"):
        dh, dw = (dh * g).astype(dh.dtype), (dw * g).astype(dw.dtype)
    with jax.named_scope("bf.head.loss"):
        return dh, dw, ce * g, None


_weighted_head_loss.defvjp(_weighted_forward, _weighted_backward)
