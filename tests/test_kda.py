"""Kimi Delta Attention's chunked scan (``bluefog_tpu.ops.kda``) against the
recurrence it computes, one token at a time under ``lax.scan``: forward and
the gradient of every operand, on both backends (the ``jax.numpy`` one and
the Pallas kernels in the interpreter), at lengths that are and are not
whole chunks, with the decay at its lower bound for a whole chunk (where
``exp(-G)`` would overflow) and at 0; and the closed-form adjoint of the
chunk's triangular solve against autodiff through the inverse's ten
products, with a count of the backward's f32 products that holds it there."""

import os
import sys

import jax
import jax.numpy as jnp
import pytest
from jax import lax

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if REPO not in sys.path:
    sys.path.insert(0, REPO)

from bluefog_tpu.ops import kda as kda_ops  # noqa: E402
from bluefog_tpu.ops.kda import CHUNK, LOWER, kda  # noqa: E402
# the recurrence, a token at a time: the plain reference's
from chipbench.linear_latent_moe_reference import (  # noqa: E402
    delta_rule as recurrence)

OPERANDS = ("q", "k", "v", "g", "beta")
# backend -> head width (the kernels need whole 128-lane tiles)
WIDTHS = {"chunked": 16, "pallas_interpret": 128}


@pytest.fixture(autouse=True)
def _highest_precision():
    with jax.default_matmul_precision("highest"):
        yield


def operands(t, width, decay, batch=1, heads=2, seed=0):
    """Unit keys, queries scaled as the layer scales them, ``beta`` in
    (0, 1); ``decay``: ``random`` in (LOWER, 0), ``lower`` (the bound
    itself, every token and channel) or ``zero``."""
    keys = jax.random.split(jax.random.PRNGKey(seed), 5)
    shape = (batch, t, heads, width)
    q = jax.random.normal(keys[0], shape)
    q = q / jnp.linalg.norm(q, axis=-1, keepdims=True) * width ** -0.5
    k = jax.random.normal(keys[1], shape)
    k = k / jnp.linalg.norm(k, axis=-1, keepdims=True)
    v = jax.random.normal(keys[2], shape)
    g = {"random": LOWER * jax.nn.sigmoid(
             2 * jax.random.normal(keys[3], shape)),
         "lower": jnp.full(shape, LOWER),
         "zero": jnp.zeros(shape)}[decay]
    beta = jax.nn.sigmoid(jax.random.normal(keys[4], shape[:3]))
    return q, k, v, g, beta


def relative(got, want, floor=0.0):
    return float(jnp.max(jnp.abs(got - want))
                 / (jnp.max(jnp.abs(want)) + floor))


@pytest.mark.parametrize("t", [CHUNK, 100, 2 * CHUNK + 2])
@pytest.mark.parametrize("decay", ["random", "lower", "zero"])
@pytest.mark.parametrize("backend", sorted(WIDTHS))
def test_forward_is_the_recurrence(backend, decay, t):
    args = operands(t, WIDTHS[backend], decay)
    got = kda(*args, backend=backend)
    assert got.shape == args[2].shape and got.dtype == args[2].dtype
    assert relative(got, recurrence(*args)) < 2e-5


@pytest.mark.parametrize("t", [CHUNK, 100])
@pytest.mark.parametrize("decay", ["random", "lower", "zero"])
@pytest.mark.parametrize("backend", sorted(WIDTHS))
def test_every_operand_s_gradient_is_the_recurrence_s(backend, decay, t):
    args = operands(t, WIDTHS[backend], decay, seed=3)
    probe = jax.random.normal(jax.random.PRNGKey(9), args[2].shape)
    everything = tuple(range(len(OPERANDS)))
    got = jax.grad(lambda *a: jnp.sum(kda(*a, backend=backend) * probe),
                   everything)(*args)
    want = jax.grad(lambda *a: jnp.sum(recurrence(*a) * probe),
                    everything)(*args)
    # at the bound the decay's own gradient is exp(LOWER) of the others',
    # the small difference of sums at their scale: hold it to that scale
    floor = float(jnp.max(jnp.abs(want[1]))) if decay == "lower" else 0.0
    for name, a, b in zip(OPERANDS, got, want):
        assert bool(jnp.all(jnp.isfinite(a))), name
        assert relative(a, b, floor if name == "g" else 0.0) < 5e-5, name


def test_the_lower_bound_over_a_whole_chunk_would_overflow_unfactored():
    """64 tokens at the bound sum to -320: ``exp(320)`` is not an f32, so
    the ratios cannot be formed as ``exp(G_t) * exp(-G_s)``; the kernel's
    factors about a reference 16 tokens back stay finite."""
    assert not bool(jnp.isfinite(jnp.exp(jnp.float32(-LOWER * CHUNK))))
    assert bool(jnp.isfinite(jnp.exp(jnp.float32(kda_ops._CLAMP))))
    assert kda_ops._CLAMP == -LOWER * kda_ops.SUB
    args = operands(3 * CHUNK, 16, "lower")
    got = kda(*args, backend="chunked")
    assert bool(jnp.all(jnp.isfinite(got)))
    # at exp(-5) a token the state forgets at once: o_t = beta (q . k) v
    q, k, v, _, beta = args
    own = beta[..., None] * jnp.sum(q * k, -1, keepdims=True) * v
    assert relative(got, own) < 2e-2


def test_bf16_operands_keep_an_f32_state():
    """bf16 in, bf16 out, close to the f32 recurrence on the same rounded
    operands: the state and the decay are not rounded."""
    args = operands(2 * CHUNK, 128, "random", seed=5)
    half = tuple(a.astype(jnp.bfloat16) for a in args[:3]) + args[3:]
    got = kda(*half, backend="pallas_interpret")
    assert got.dtype == jnp.bfloat16
    want = recurrence(*(a.astype(jnp.float32) for a in half))
    assert relative(got.astype(jnp.float32), want) < 3e-2


def test_the_two_backends_agree_and_padding_changes_nothing():
    args = operands(70, 128, "random", seed=7)
    a = kda(*args, backend="chunked")
    b = kda(*args, backend="pallas_interpret")
    assert relative(a, b) < 1e-5
    longer = tuple(jnp.pad(x, ((0, 0), (0, 30)) + ((0, 0),) * (x.ndim - 2))
                   for x in args)
    assert relative(kda(*longer, backend="chunked")[:, :70], a) < 1e-6


# ---- the solve's adjoint ----------------------------------------------------

def one_chunk(dtype, decay, seed=11):
    """``_chunk``'s operands for one head's chunk of 128-wide tokens (a
    state that earlier chunks left, ``q, k, kb, v`` rounded to ``dtype`` but
    held in f32, so that a gradient is read before any rounding, the decay
    summed from the chunk's start) and cotangents for its two results."""
    q, k, v, g, beta = (x[0, :, 0] for x in operands(
        CHUNK, 128, decay, heads=1, seed=seed))
    rounded = tuple(x.astype(dtype).astype(jnp.float32)
                    for x in (q, k, k * beta[:, None], v))
    keys = jax.random.split(jax.random.PRNGKey(seed + 1), 3)
    state = jax.random.normal(keys[0], (128, 128)) * 128 ** -0.5
    cotangents = (jax.random.normal(keys[1], (CHUNK, 128)),
                  jax.random.normal(keys[2], (128, 128)))
    return (state, *rounded, jnp.cumsum(g, axis=0)), cotangents


def chunk_in(dtype, inv=None):
    """``_chunk``'s two results, of operands cast to ``dtype``; ``inv``: the
    chunk's inverse handed in, as the backward pass hands it."""
    def chunk(state, q, k, kb, v, decay):
        return kda_ops._chunk(state, *(x.astype(dtype)
                                       for x in (q, k, kb, v)), decay, inv)[0]
    return chunk


def built_inverse(dtype, args):
    """The inverse the forward pass builds for ``one_chunk``'s ``args``."""
    return kda_ops._chunk(args[0], *(x.astype(dtype) for x in args[1:5]),
                          args[5])[1]


def solve_by_autodiff(inv, a, r):
    """What ``_solve`` computes, its gradient left to autodiff: through the
    product and the ten of the inverse."""
    return jnp.dot(kda_ops._inverse(a), r, **kda_ops._EXACT)


@pytest.mark.parametrize("decay", ["random", "lower", "zero"])
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16],
                         ids=["f32", "bf16"])
def test_the_solve_s_adjoint_is_autodiff_s_through_the_inverse(
        dtype, decay, monkeypatch):
    args, cotangents = one_chunk(dtype, decay)
    out, pull = jax.vjp(chunk_in(dtype, built_inverse(dtype, args)), *args)
    got = pull(cotangents)
    # the inverse built in place gives what the saved one gives
    for a, b in zip(got, jax.vjp(chunk_in(dtype), *args)[1](cotangents)):
        assert bool(jnp.all(a == b))
    monkeypatch.setattr(kda_ops, "_solve", solve_by_autodiff)
    same, pull = jax.vjp(chunk_in(dtype), *args)
    want = pull(cotangents)
    for a, b in zip(out, same):      # the forward: the same products
        assert bool(jnp.all(a == b))
    # autodiff rounds the cotangent of a bf16 product's operand to bf16, so
    # there a difference of 1e-7 now and then rounds one element of a leaf's
    # 8,192 the other way: one bf16 unit of it, up to 2e-4 of the leaf
    apart = 1e-5 if dtype == jnp.float32 else 1e-3
    names = ("state", "q", "k", "kb", "v", "decay")
    for name, a, b in zip(names, got, want):
        assert bool(jnp.all(jnp.isfinite(a))), name
        assert relative(a, b) < apart, name
        over = jnp.abs(a - b) > 1e-5 * jnp.max(jnp.abs(b))
        assert int(jnp.sum(over)) <= a.size // 512, name


def exact_products(jaxpr):
    """``dot_general``s at ``Precision.HIGHEST`` in ``jaxpr`` and whatever
    it calls."""
    def inner(value):
        if hasattr(value, "eqns"):
            yield value
        elif hasattr(value, "jaxpr"):
            yield value.jaxpr
        elif isinstance(value, (tuple, list)):
            for v in value:
                yield from inner(v)

    count = 0
    for eqn in jaxpr.eqns:
        if eqn.primitive.name == "dot_general":
            precision = eqn.params["precision"]
            count += precision is not None and all(
                p == lax.Precision.HIGHEST for p in precision)
        count += sum(exact_products(j) for value in eqn.params.values()
                     for j in inner(value))
    return count


def test_the_chunk_s_backward_holds_three_exact_products(monkeypatch):
    """With bf16 operands the f32 products at ``highest`` are the solve's
    alone: ``inv . r`` forward again and two for the adjoint where the
    inverse is handed in, ten more where it is built in place.  Autodiff
    through the inverse would make them 33, each six passes of the matrix
    unit where a bf16 product is one."""
    args, cotangents = one_chunk(jnp.bfloat16, "random")
    saved = built_inverse(jnp.bfloat16, args)

    def products(inv):
        def backward(*args):        # traced anew a call: no cached jaxpr
            # not under this file's ``highest``, which every product takes
            with jax.default_matmul_precision("default"):
                return jax.vjp(chunk_in(jnp.bfloat16, inv),
                               *args)[1](cotangents)
        return exact_products(jax.make_jaxpr(backward)(*args).jaxpr)

    assert products(saved) <= 3
    assert products(None) <= 13
    monkeypatch.setattr(kda_ops, "_solve", solve_by_autodiff)
    assert products(saved) == 33


@pytest.mark.parametrize("bad", ["shape", "backend", "width"])
def test_what_it_cannot_compute_is_refused(bad):
    q, k, v, g, beta = operands(CHUNK, 16, "zero")
    with pytest.raises(ValueError):
        if bad == "shape":
            kda(q, k, v, g, beta[..., None])
        elif bad == "backend":
            kda(q, k, v, g, beta, backend="triton")
        else:
            kda(q, k, v, g, beta, backend="pallas_interpret")


def test_the_chunks_are_counted_when_metrics_are_on():
    from bluefog_tpu.metrics import registry

    args = operands(100, 16, "random", batch=2)
    registry.metrics_stop()
    registry._STOPPED = False
    reg = registry.metrics_start()
    try:
        jax.block_until_ready(jax.jit(
            lambda *a: kda(*a, backend="chunked"))(*args))
        jax.effects_barrier()
        assert reg.snapshot()["bf_kda_chunks_total"] == 2 * 2 * 2
    finally:
        registry.metrics_stop()
        registry._STOPPED = False
