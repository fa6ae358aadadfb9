"""The head and the loss in token chunks (``ops/head_loss.py``) against
``optax``'s cross entropy on whole f32 logits, and what ``next_token_loss``
no longer materialises."""

import math

import jax
import jax.numpy as jnp
import numpy as np
import optax
import pytest

from bluefog_tpu.metrics import registry
from bluefog_tpu.models.transformer import (
    GPTConfig, TransformerLM, next_token_loss)
from bluefog_tpu.ops import head_loss as hl

D, V = 24, 50


@pytest.fixture
def small_chunks(monkeypatch):
    """Chunks of 64 rows at ``V`` = 50, so a test's few rows are many."""
    monkeypatch.setattr(hl, "_CHUNK_ELEMENTS", 64 * V)
    monkeypatch.setattr(hl, "_MIN_ROWS", 8)


def whole(h, w, targets, tied):
    """The head as ``TransformerLM`` makes logits, the loss as ``optax``."""
    h = h.astype(jnp.float32)
    logits = jnp.einsum("...d,vd->...v", h, w) if tied else h @ w
    assert logits.dtype == jnp.float32
    return optax.softmax_cross_entropy_with_integer_labels(
        logits, targets).mean()


def operands(lead, tied, dtype=jnp.float32):
    k = jax.random.split(jax.random.PRNGKey(math.prod(lead)), 3)
    h = jax.random.normal(k[0], lead + (D,)).astype(dtype)
    w = 0.3 * jax.random.normal(k[1], (V, D) if tied else (D, V))
    return h, w, jax.random.randint(k[2], lead, 0, V)


@pytest.mark.parametrize("tied", [False, True], ids=["untied", "tied"])
@pytest.mark.parametrize("lead,chunks", [
    ((2, 16), 1), ((4, 64), 4), ((5, 67), 6), ((1, 7), 1)],
    ids=["one", "many", "ragged", "short"])
def test_value_and_gradients_are_optax_s_on_whole_logits(
        lead, chunks, tied, small_chunks):
    h, w, targets = operands(lead, tied)
    c = hl.chunk_rows(math.prod(lead), V)
    assert -(-math.prod(lead) // c) == chunks
    got = jax.jit(jax.value_and_grad(
        lambda h, w: hl.head_loss(h, w, targets, tied=tied),
        argnums=(0, 1)))(h, w)
    want = jax.jit(jax.value_and_grad(
        lambda h, w: whole(h, w, targets, tied), argnums=(0, 1)))(h, w)
    np.testing.assert_allclose(got[0], want[0], rtol=2e-7)
    for g, ref in zip(got[1], want[1]):
        assert g.shape == ref.shape and g.dtype == ref.dtype
        np.testing.assert_allclose(g, ref, atol=1e-7)
    # the value alone (no gradient asked for) is the same scalar
    alone = jax.jit(lambda h, w: hl.head_loss(h, w, targets, tied=tied))(h, w)
    np.testing.assert_allclose(alone, want[0], rtol=2e-7)


@pytest.mark.parametrize("tied", [False, True], ids=["untied", "tied"])
def test_the_cotangent_scales_both_gradients(tied, small_chunks):
    """The rule keeps ``d h`` and ``d w`` of the plain mean and scales them
    by what arrives: a weighted loss (the MTP module's) gets the weight."""
    h, w, targets = operands((2, 96), tied)

    def grads(f):
        return jax.grad(lambda h, w: 0.3 * f(h, w) ** 2, argnums=(0, 1))(h, w)

    got = grads(lambda h, w: hl.head_loss(h, w, targets, tied=tied))
    want = grads(lambda h, w: whole(h, w, targets, tied))
    for g, ref in zip(got, want):
        np.testing.assert_allclose(g, ref, rtol=1e-5, atol=1e-7)


@pytest.mark.parametrize("dtype", [jnp.bfloat16, jnp.float32],
                         ids=["bf16_h", "f32_h"])
@pytest.mark.parametrize("tied", [False, True], ids=["untied", "tied"])
def test_the_logits_inside_are_f32_from_f32_operands(tied, dtype,
                                                     small_chunks):
    h, w, targets = operands((4, 64), tied, dtype)
    jaxpr = jax.make_jaxpr(jax.grad(
        lambda h, w: hl.head_loss(h, w, targets, tied=tied),
        argnums=(0, 1)))(h, w)
    dots = [e for e in equations(jaxpr.jaxpr)
            if e.primitive.name == "dot_general"]
    # the loop's body and, untied, the last chunk after it
    assert len(dots) == (3 if tied else 6)
    for e in dots:
        assert [v.aval.dtype for v in e.invars] == [jnp.float32] * 2
        assert e.outvars[0].aval.dtype == jnp.float32
        assert e.params["precision"] is None
    dh, _ = jax.grad(lambda h, w: hl.head_loss(h, w, targets, tied=tied),
                     argnums=(0, 1))(h, w)
    assert dh.dtype == dtype


# rows, vocabulary rows, chunk, chunks: the five cells that call
# next_token_loss, then shapes that fit whole and one that is all remainder
@pytest.mark.parametrize("rows,vocab,chunk,chunks", [
    (32768, 16384, 4096, 8),       # lfm2moe.t8192.solo
    (16384, 18992, 2048, 8),       # smallthinker.t16384.solo
    (8192, 25008, 2048, 4),        # phi4flash.t8192.solo
    (8192, 19648, 2048, 4),        # ling3flash.t8192.solo
    (8192, 16160, 2048, 4),        # joyai.t4096.solo
    (32, 128, 32, 1), (4096, 16384, 4096, 1), (1500, 200064, 1024, 2),
    (10000, 16384, 2048, 5)])
def test_the_chunk_is_read_off_the_shapes(rows, vocab, chunk, chunks):
    assert hl.chunk_rows(rows, vocab) == chunk
    assert -(-rows // chunk) == chunks
    assert chunk == rows or (chunk * vocab <= 1 << 26 or chunk == 1024)


def equations(jaxpr):
    """Every equation of ``jaxpr`` and of the jaxprs inside it."""
    for e in jaxpr.eqns:
        yield e
        for sub in jax.core.jaxprs_in_params(e.params):
            yield from equations(sub)


def tiny_model(vocab, mtp, tied, seq=8):
    cfg = GPTConfig(vocab_size=vocab, hidden_size=16, num_layers=1,
                    num_heads=2, max_position=max(seq, 8), dtype=jnp.float32,
                    mtp_depth=mtp, tie_head=tied)
    model = TransformerLM(cfg)
    tokens = jnp.zeros((1, 8), jnp.int32)
    params = jax.jit(lambda k: model.init(
        k, tokens, **({"next_tokens": tokens} if mtp else {})))(
            jax.random.PRNGKey(1))["params"]
    return model, params


@pytest.mark.parametrize("tied", [False, True], ids=["untied", "tied"])
@pytest.mark.parametrize("mtp", [0, 1], ids=["main", "mtp_pair"])
def test_next_token_loss_is_the_whole_logits_loss(mtp, tied, small_chunks):
    """Through the model: the MTP pair calls the head twice on one leaf, and
    the leaf's gradient is the sum (tied: the lookups' share on top)."""
    model, params = tiny_model(V, mtp, tied)
    assert ("lm_head" in params) == (not tied)
    tokens = jax.random.randint(jax.random.PRNGKey(2), (3, 70 + mtp), 0, V)
    t = 69

    def plain(params):
        def ce(logits, targets):
            assert logits.dtype == jnp.float32
            return optax.softmax_cross_entropy_with_integer_labels(
                logits, targets).mean()
        if not mtp:
            return ce(model.apply({"params": params}, tokens[:, :t]),
                      tokens[:, 1:])
        logits, more = model.apply({"params": params}, tokens[:, :t],
                                   next_tokens=tokens[:, 1:t + 1])
        return ce(logits, tokens[:, 1:t + 1]) + 0.3 * ce(more, tokens[:, 2:])

    got = jax.jit(jax.value_and_grad(lambda p: next_token_loss(
        model, p, {}, tokens, mtp_weight=0.3)))(params)
    want = jax.jit(jax.value_and_grad(plain))(params)
    np.testing.assert_allclose(got[0], want[0], rtol=1e-6)
    assert (jax.tree_util.tree_structure(got[1])
            == jax.tree_util.tree_structure(want[1]))
    for (path, g), ref in zip(jax.tree_util.tree_leaves_with_path(got[1]),
                              jax.tree_util.tree_leaves(want[1])):
        np.testing.assert_allclose(g, ref, rtol=1e-4, atol=1e-6,
                                   err_msg=jax.tree_util.keystr(path))


@pytest.mark.parametrize("tied", [False, True], ids=["untied", "tied"])
@pytest.mark.parametrize("mtp", [0, 1], ids=["main", "mtp_pair"])
def test_the_gradient_holds_no_whole_logits_and_three_matmuls_a_chunk(
        mtp, tied):
    """The jaxpr of ``grad(next_token_loss)`` at 4,096 rows of a 40,000-row
    vocabulary (four chunks of 1,024 at the module's own constants): no
    array of ``rows x V`` elements or more anywhere, and a head's three
    contractions (logits, ``d h``, ``d w``) once a chunk: a tied head's in
    the one body of a loop over the four, an untied head's in a loop over
    three and once more after it, for the last (nothing is computed a
    second time)."""
    vocab, rows, seq = 40_000, 4096, 2048
    assert hl.chunk_rows(rows, vocab) == 1024
    model, params = tiny_model(vocab, mtp, tied, seq)
    tokens = jnp.zeros((2, seq + 1 + mtp), jnp.int32)
    jaxpr = jax.make_jaxpr(jax.grad(lambda p: next_token_loss(
        model, p, {}, tokens, mtp_weight=0.3)))(params)
    largest = max((math.prod(v.aval.shape), str(v.aval))
                  for e in equations(jaxpr.jaxpr) for v in e.outvars
                  if hasattr(v.aval, "shape"))
    assert largest[0] < rows * vocab // 2, largest
    assert largest[0] >= 1024 * vocab        # a chunk's logits are there

    def over_vocab(jaxpr):
        return [e for e in equations(jaxpr)
                if e.primitive.name == "dot_general"
                and any(vocab in v.aval.shape for v in e.invars + e.outvars)]

    in_loop = 4 if tied else 3
    loops = [e for e in equations(jaxpr.jaxpr) if e.primitive.name == "scan"
             and e.params["length"] == in_loop]
    assert len(loops) == 1 + mtp
    for loop in loops:
        assert len(over_vocab(loop.params["jaxpr"].jaxpr)) == 3
    # the loops' bodies, the untied heads' last chunks, and no other matmul
    assert len(over_vocab(jaxpr.jaxpr)) == (1 + mtp) * (3 if tied else 6)
    assert all(e.outvars[0].aval.dtype == jnp.float32
               for e in over_vocab(jaxpr.jaxpr))


def test_model_apply_still_returns_logits(small_chunks):
    """Inference and every reader of logits see what they saw; ``head=False``
    hands back the hidden states the logits are the head's product of."""
    model, params = tiny_model(V, 0, False)
    tokens = jax.random.randint(jax.random.PRNGKey(2), (2, 8), 0, V)
    logits = model.apply({"params": params}, tokens)
    h = model.apply({"params": params}, tokens, head=False)
    assert logits.shape == (2, 8, V) and logits.dtype == jnp.float32
    assert h.shape == (2, 8, 16) and h.dtype == jnp.float32
    np.testing.assert_allclose(h @ params["lm_head"]["kernel"], logits,
                               rtol=1e-5, atol=1e-6)


def test_the_gauges_say_how_each_site_was_chunked(small_chunks):
    """Trace-time gauges, one pair a call site; a program traced with
    metrics off carries nothing of them."""
    model, params = tiny_model(V, 1, False)
    tokens = jnp.zeros((3, 72), jnp.int32)
    registry.metrics_stop()
    reg = registry.metrics_start()
    try:
        jax.eval_shape(lambda p: next_token_loss(
            model, p, {}, tokens, mtp_weight=0.3), params)
        snap = reg.snapshot()
    finally:
        registry.metrics_stop()
    for site in ("main", "mtp"):
        assert snap[f'bf_head_loss_chunks{{site="{site}"}}'] == 7   # 210 rows
        assert snap[f'bf_head_loss_chunk_rows{{site="{site}"}}'] == 32


# ---- weights a row: the expected loss over a looped model's exits -----------

def weighted_operands(lead, tied):
    weights = jax.random.uniform(jax.random.PRNGKey(7), lead) / math.prod(lead)
    return (*operands(lead, tied), weights)


def whole_weighted(h, w, targets, weights, tied):
    h = h.astype(jnp.float32)
    logits = jnp.einsum("...d,vd->...v", h, w) if tied else h @ w
    ce = -jnp.take_along_axis(jax.nn.log_softmax(logits), targets[..., None],
                              axis=-1)[..., 0]
    return jnp.sum(weights * ce)


@pytest.mark.parametrize("tied", [False, True], ids=["untied", "tied"])
@pytest.mark.parametrize("lead,chunks", [
    ((2, 16), 1), ((4, 64), 4), ((5, 67), 6), ((1, 7), 1)],
    ids=["one", "many", "ragged", "short"])
def test_weighted_value_and_three_gradients_are_whole_logits_arithmetic(
        lead, chunks, tied, small_chunks):
    """``sum_i w_i CE_i`` with ``d h``, ``d w`` and ``d weights = CE``."""
    h, w, targets, weights = weighted_operands(lead, tied)
    assert -(-math.prod(lead) // hl.chunk_rows(math.prod(lead), V)) == chunks
    got = jax.jit(jax.value_and_grad(
        lambda h, w, x: hl.head_loss(h, w, targets, tied=tied, weights=x),
        argnums=(0, 1, 2)))(h, w, weights)
    want = jax.jit(jax.value_and_grad(
        lambda h, w, x: whole_weighted(h, w, targets, x, tied),
        argnums=(0, 1, 2)))(h, w, weights)
    np.testing.assert_allclose(got[0], want[0], rtol=1e-6)
    for g, w_ in zip(got[1], want[1]):
        assert g.shape == w_.shape and g.dtype == w_.dtype
        np.testing.assert_allclose(g, w_, rtol=1e-5, atol=1e-7)
    alone = jax.jit(lambda h, w: hl.head_loss(
        h, w, targets, tied=tied, weights=weights))(h, w)
    np.testing.assert_allclose(alone, want[0], rtol=1e-6)


@pytest.mark.parametrize("tied", [False, True], ids=["untied", "tied"])
def test_weights_of_one_over_the_rows_give_the_mean(tied, small_chunks):
    h, w, targets = operands((4, 64), tied)
    rows = jnp.full((4, 64), 1.0 / 256)
    got = jax.jit(jax.value_and_grad(lambda h, w: hl.head_loss(
        h, w, targets, tied=tied, weights=rows), argnums=(0, 1)))(h, w)
    want = jax.jit(jax.value_and_grad(lambda h, w: hl.head_loss(
        h, w, targets, tied=tied), argnums=(0, 1)))(h, w)
    np.testing.assert_allclose(got[0], want[0], rtol=1e-6)
    for g, w_ in zip(got[1], want[1]):
        np.testing.assert_allclose(g, w_, rtol=1e-5, atol=1e-8)


@pytest.mark.parametrize("tied", [False, True], ids=["untied", "tied"])
def test_the_cotangent_scales_the_weights_gradient_too(tied, small_chunks):
    h, w, targets, weights = weighted_operands((2, 96), tied)

    def grads(f):
        return jax.grad(lambda h, w, x: 0.3 * f(h, w, x) ** 2,
                        argnums=(0, 1, 2))(h, w, weights)

    got = grads(lambda h, w, x: hl.head_loss(h, w, targets, tied=tied,
                                             weights=x))
    want = grads(lambda h, w, x: whole_weighted(h, w, targets, x, tied))
    for g, w_ in zip(got, want):
        np.testing.assert_allclose(g, w_, rtol=1e-5, atol=1e-7)


@pytest.mark.parametrize("tied", [False, True], ids=["untied", "tied"])
def test_the_weighted_gradient_holds_no_array_above_a_chunk_by_v(tied):
    """At 4,096 rows of a 40,000-row vocabulary (four chunks of 1,024): no
    array of ``rows x V`` elements anywhere, and the head's three
    contractions once a chunk, as without weights."""
    vocab, rows = 40_000, 4096
    assert hl.chunk_rows(rows, vocab) == 1024
    h = jnp.zeros((2, 2048, 16))
    w = jnp.zeros((vocab, 16) if tied else (16, vocab))
    targets = jnp.zeros((2, 2048), jnp.int32)
    weights = jnp.full((2, 2048), 1.0 / rows)

    def f(weights=None):
        return jax.make_jaxpr(jax.grad(
            lambda h, w: hl.head_loss(h, w, targets, tied=tied,
                                      weights=weights),
            argnums=(0, 1)))(h, w).jaxpr

    largest = max(math.prod(v.aval.shape) for e in equations(f(weights))
                  for v in e.outvars if hasattr(v.aval, "shape"))
    assert 1024 * vocab <= largest < rows * vocab // 2

    def dots(jaxpr):
        return sum(e.primitive.name == "dot_general" for e in equations(jaxpr))

    assert dots(f(weights)) == dots(f()) == (3 if tied else 6)


@pytest.mark.parametrize("tied", [False, True], ids=["untied", "tied"])
def test_without_weights_the_program_is_the_one_it_was(tied):
    """The unweighted call's jaxpr names no weight: the same equations as
    a call that never heard of them (``_chunked`` with ``weights=None``
    traces what it traced)."""
    h, w, targets, weights = weighted_operands((4, 64), tied)

    def text(**kw):
        return str(jax.make_jaxpr(jax.value_and_grad(
            lambda h, w: hl.head_loss(h, w, targets, tied=tied, **kw),
            argnums=(0, 1)))(h, w))

    plain = text()
    assert plain == text(weights=None)
    assert plain != text(weights=weights)
    # a division by the row count, no multiplication by a weight's column
    assert "div" in plain and plain.count("mul") < text(
        weights=weights).count("mul")
