"""``bluefog_tpu/ops/ssd.py``: Mamba-2's chunked state-space scan (the
``chunked`` ``jax.numpy`` form and the kernels in the Pallas interpreter)
against the recurrence one token at a time, forward and gradients, at
lengths that are not whole chunks; its causality and its group map.  f32,
seeded, on the CPU."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax import lax

from bluefog_tpu.ops import ssd as ssd_ops
from bluefog_tpu.ops.ssd import CHUNK, ssd

BACKENDS = ["chunked", "pallas_interpret"]
# batch, tokens, heads, head width, groups, state: two heads a slab of 16
# columns; three heads a group in slabs of one (3 and 8 share no factor); a
# head as wide as a tile
SHAPES = {"pairs": (2, 200, 4, 8, 2, 16), "odd_group": (1, 130, 6, 16, 2, 8),
          "wide": (1, 140, 2, 128, 1, 16)}


@pytest.fixture(autouse=True)
def _highest_precision():
    with jax.default_matmul_precision("highest"):
        yield


def operands(shape, seed=0):
    bsz, t, h, p, g, n = shape
    keys = jax.random.split(jax.random.PRNGKey(seed), 6)
    x = jax.random.normal(keys[0], (bsz, t, h, p))
    dt = jax.nn.softplus(jax.random.normal(keys[1], (bsz, t, h)))
    a = -jnp.exp(jax.random.uniform(keys[2], (h,), minval=0.0, maxval=2.7))
    b = 0.3 * jax.random.normal(keys[3], (bsz, t, g, n))
    c = 0.3 * jax.random.normal(keys[4], (bsz, t, g, n))
    d = jax.random.normal(keys[5], (h,))
    return x, dt, a, b, c, d


def looped(x, dt, a, b, c, d):
    """The recurrence as it is written, in float64."""
    x, dt, a, b, c, d = (np.asarray(v, np.float64)
                         for v in (x, dt, a, b, c, d))
    bsz, t, h, p = x.shape
    share = h // b.shape[2]
    y = np.zeros_like(x)
    for i in range(bsz):
        state = np.zeros((h, p, b.shape[3]))
        for s in range(t):
            for j in range(h):
                state[j] = np.exp(dt[i, s, j] * a[j]) * state[j] + (
                    dt[i, s, j] * np.outer(x[i, s, j], b[i, s, j // share]))
                y[i, s, j] = state[j] @ c[i, s, j // share] + (
                    d[j] * x[i, s, j])
    return y


@jax.jit
def scanned(x, dt, a, b, c, d):
    """The same recurrence as a ``lax.scan`` over tokens, for autodiff."""
    share = x.shape[2] // b.shape[2]

    def token(state, inputs):
        xt, dtt, bt, ct = inputs
        bt, ct = jnp.repeat(bt, share, axis=1), jnp.repeat(ct, share, axis=1)
        state = (jnp.exp(dtt * a)[..., None, None] * state
                 + (dtt[..., None] * xt)[..., None] * bt[:, :, None, :])
        return state, jnp.einsum("bhpn,bhn->bhp", state, ct) + (
            d[:, None] * xt)

    zero = jnp.zeros(x.shape[:1] + x.shape[2:] + b.shape[-1:])
    _, y = lax.scan(token, zero, tuple(jnp.moveaxis(v, 1, 0)
                                       for v in (x, dt, b, c)))
    return jnp.moveaxis(y, 0, 1)


@pytest.mark.parametrize("backend", BACKENDS)
def test_the_scan_equals_a_loop_over_tokens(backend):
    """200 tokens: one whole chunk and 72 of a second, the state carried
    across the boundary and the padding neither decaying nor writing."""
    args = operands(SHAPES["pairs"])
    got = ssd(*args, backend=backend)
    assert got.shape == args[0].shape and got.dtype == args[0].dtype
    np.testing.assert_allclose(got, looped(*args), atol=5e-5)


@pytest.mark.parametrize("shape", sorted(SHAPES))
@pytest.mark.parametrize("backend", BACKENDS)
def test_value_and_gradients_match_the_recurrence(backend, shape):
    args = operands(SHAPES[shape], seed=3)
    probe = jax.random.normal(jax.random.PRNGKey(9), args[0].shape)

    def value(fn):
        return jax.value_and_grad(
            lambda *a: jnp.sum(probe * jnp.tanh(fn(*a))),
            argnums=tuple(range(6)))(*args)

    want, want_grads = value(scanned)
    got, got_grads = value(lambda *a: ssd(*a, backend=backend))
    np.testing.assert_allclose(got, want, rtol=1e-5)
    for name, g, w in zip(("x", "dt", "a", "b", "c", "d"), got_grads,
                          want_grads):
        scale = float(jnp.max(jnp.abs(w)))
        # two f32 summation orders over 200 tokens
        assert float(jnp.max(jnp.abs(g - w))) < 2e-4 * scale, name


def test_the_kernels_equal_the_chunked_form_in_bf16():
    """bf16 operands as the model hands them: the two backends run the one
    chunk function, so they round alike."""
    x, dt, a, b, c, d = operands(SHAPES["pairs"], seed=5)
    x, b, c = (v.astype(jnp.bfloat16) for v in (x, b, c))
    outs = [ssd(x, dt, a, b, c, d, backend=backend) for backend in BACKENDS]
    assert outs[0].dtype == jnp.bfloat16
    np.testing.assert_array_equal(*(np.asarray(o, np.float32) for o in outs))
    want = looped(*(np.asarray(v, np.float32) for v in (x, dt, a, b, c, d)))
    np.testing.assert_allclose(np.asarray(outs[0], np.float32), want,
                               atol=0.05, rtol=0.05)


@pytest.mark.parametrize("at", [0, 5, CHUNK - 1, CHUNK, 199])
def test_a_change_at_a_token_moves_nothing_before_it(at):
    args = operands(SHAPES["pairs"], seed=1)
    base = ssd(*args, backend="chunked")
    for i in (0, 1, 3, 4):                       # x, dt, b, c
        moved = list(args)
        moved[i] = args[i].at[:, at].add(0.7)
        out = ssd(*moved, backend="chunked")
        np.testing.assert_array_equal(out[:, :at], base[:, :at])
        assert not np.array_equal(out[:, at], base[:, at]), i


def test_a_head_reads_its_own_group():
    """Head ``h`` reads ``B`` and ``C`` of group ``h // (H / G)``: with 4
    heads over 2 groups, group 1 serves heads 2 and 3 (``h % G`` would hand
    it heads 1 and 3)."""
    args = operands(SHAPES["pairs"], seed=2)
    base = ssd(*args, backend="chunked")
    for i in (3, 4):
        moved = list(args)
        moved[i] = args[i].at[:, :, 1].multiply(1.5)
        out = ssd(*moved, backend="chunked")
        np.testing.assert_array_equal(out[:, :, :2], base[:, :, :2])
        assert float(jnp.min(jnp.max(jnp.abs(out - base)[:, :, 2:],
                                     axis=(0, 1, 3)))) > 1e-3


def test_steps_as_large_as_the_decay_allows_stay_finite():
    """Every exponent is a later sum less an earlier one: a step that
    decays the state by ``exp(-16 x 20)`` a token overflows nothing,
    forward or backward."""
    x, dt, a, b, c, d = operands(SHAPES["pairs"], seed=4)
    dt, a = jnp.full_like(dt, 20.0), jnp.full_like(a, -16.0)
    for backend in BACKENDS:
        value, grads = jax.value_and_grad(
            lambda *v: jnp.sum(ssd(*v, backend=backend) ** 2),
            argnums=tuple(range(6)))(x, dt, a, b, c, d)
        assert np.isfinite(value)
        assert all(bool(jnp.all(jnp.isfinite(g))) for g in grads)


def test_backends_and_shapes_it_refuses(monkeypatch):
    args = operands((1, 16, 4, 8, 2, 16))
    with pytest.raises(ValueError, match="unknown backend"):
        ssd(*args, backend="mosaic")
    with pytest.raises(ValueError, match="ssd takes"):
        ssd(args[0], args[1][:, :8], *args[2:])
    with pytest.raises(ValueError, match="ssd takes"):       # 4 over 3
        ssd(args[0], args[1], args[2], args[3][:, :, :1].repeat(3, 2),
            args[4][:, :, :1].repeat(3, 2), args[5])
    with pytest.raises(ValueError, match="whole sublanes"):
        ssd(*args, backend="pallas")
    # 'auto' from the backend and the shapes alone
    assert ssd_ops._resolve("auto", 64, 8, 64, 128) == "chunked"
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    assert ssd_ops._resolve("auto", 64, 8, 64, 128) == "pallas"
    assert ssd_ops._resolve("auto", 64, 8, 64, 96) == "chunked"
    assert ssd_ops._resolve("auto", 4, 2, 8, 128) == "chunked"
    assert ssd_ops._slab_heads(8, 64) == 2
    assert ssd_ops._slab_heads(3, 16) == 1
