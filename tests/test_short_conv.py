"""``ops/short_conv.py``: the kernels ``bf_sconv_fwd`` / ``bf_sconv_bwd`` (the
gate, convolution and gate) and ``bf_cconv_fwd`` / ``bf_cconv_bwd`` (the
convolution, bias and SiLU) in the Pallas interpreter against the
``jax.numpy`` forms and their autodiff, over several tiles of tokens and
blocks of channels, at other tap counts, across a tile's edge, and with the
operand handed over wider than the convolution."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from bluefog_tpu.ops import short_conv
from bluefog_tpu.ops.short_conv import gated_short_conv, silu_short_conv


def operands(batch, t, d, dtype, taps=3):
    keys = jax.random.split(jax.random.PRNGKey(t + d), 3)
    return (jax.random.normal(keys[0], (batch, t, 3 * d)).astype(dtype),
            jax.random.uniform(keys[1], (taps, d), minval=-0.6, maxval=0.6),
            jax.random.normal(keys[2], (batch, t, d)))


def silu_operands(batch, t, d, dtype, taps=4, offset=0, beside=0):
    """``x`` with ``offset`` channels before the convolution's and
    ``beside`` after them, the taps, the bias and a probe."""
    keys = jax.random.split(jax.random.PRNGKey(t + d), 4)
    return (jax.random.normal(keys[0], (batch, t, offset + d + beside)
                              ).astype(dtype),
            jax.random.uniform(keys[1], (taps, d), minval=-0.5, maxval=0.5),
            jax.random.uniform(keys[2], (d,), minval=-0.5, maxval=0.5),
            jax.random.normal(keys[3], (batch, t, d)))


def value_and_grads(op, probe, *args):
    """``sum(probe * op(*args))`` and its gradient by every argument."""
    return jax.jit(jax.value_and_grad(lambda *args: jnp.sum(
        probe * op(*args).astype(jnp.float32)),
        argnums=tuple(range(len(args)))))(*args)


def assert_kernels_equal_the_plain_form(op, probe, args, dtype, rtol=2e-6):
    """The first argument is the operand (gradient in its dtype, within one
    bf16 rounding), the others f32 parameters."""
    want = value_and_grads(lambda *a: op("xla", *a), probe, *args)
    got = value_and_grads(lambda *a: op("pallas_interpret", *a), probe,
                          *args)
    np.testing.assert_allclose(got[0], want[0], rtol=rtol)
    unit = 2.0 ** -7 if dtype == jnp.bfloat16 else 0.0   # one bf16 rounding
    np.testing.assert_allclose(
        got[1][0].astype(jnp.float32), want[1][0].astype(jnp.float32),
        atol=2e-5, rtol=unit)
    assert got[1][0].dtype == dtype
    for mine, theirs in zip(got[1][1:], want[1][1:]):
        assert mine.dtype == jnp.float32 and mine.shape == theirs.shape
        scale = float(jnp.max(jnp.abs(theirs)))
        np.testing.assert_allclose(mine, theirs, atol=2e-6 * scale + (
            unit * scale))
    return got


def gated(backend, bcz, kernel):
    return gated_short_conv(bcz, kernel, backend=backend)


@pytest.mark.parametrize("batch,t,d,dtype,taps,tiles", [
    (2, 64, 128, jnp.float32, 3, (64, 128)),       # one tile, one block
    (1, 512, 256, jnp.float32, 3, (256, 256)),     # two tiles of tokens
    (2, 48, 384, jnp.bfloat16, 3, (16, 128)),      # 3 tiles by 3 blocks
    (1, 32, 128, jnp.float32, 2, (32, 128)),
    (1, 96, 128, jnp.float32, 4, (32, 128)),       # Mamba's and KDA's taps
], ids=["one_tile", "two_tiles", "bf16_3x3", "two_taps", "four_taps"])
def test_kernels_equal_the_plain_form_in_value_and_gradients(
        batch, t, d, dtype, taps, tiles):
    assert short_conv._tiles(t, d) == tiles
    bcz, kernel, probe = operands(batch, t, d, dtype, taps)
    assert_kernels_equal_the_plain_form(gated, probe, (bcz, kernel), dtype)
    out = gated_short_conv(bcz, kernel, backend="pallas_interpret")
    assert out.shape == (batch, t, d) and out.dtype == dtype


@pytest.mark.parametrize("batch,t,d,dtype,taps,offset,beside,tiles", [
    (2, 64, 128, jnp.float32, 4, 0, 0, (64, 128)),     # x is the operand
    (1, 512, 256, jnp.float32, 4, 256, 0, (256, 256)),  # two tiles of tokens
    (2, 48, 384, jnp.bfloat16, 4, 128, 128, (16, 128)),  # 3 tiles, 3 blocks
    (2, 32, 256, jnp.float32, 4, 256, 20, (32, 256)),  # a ragged last block
    (1, 32, 128, jnp.float32, 2, 128, 0, (32, 128)),
    (1, 96, 256, jnp.bfloat16, 3, 0, 128, (32, 256)),
], ids=["one_tile", "two_tiles_offset", "bf16_3x3_inside", "ragged_beside",
        "two_taps", "bf16_three_taps"])
def test_silu_kernels_equal_the_plain_form_in_value_and_gradients(
        batch, t, d, dtype, taps, offset, beside, tiles):
    """Value and the gradients of ``x``, the taps and the bias; ``x`` handed
    over whole with the convolution's channels inside it, whose other
    channels take a gradient of zero."""
    assert short_conv._tiles(t, d, offset) == tiles
    x, kernel, bias, probe = silu_operands(batch, t, d, dtype, taps, offset,
                                           beside)

    def silu(backend, x, kernel, bias):
        return silu_short_conv(x, kernel, bias, offset=offset,
                               backend=backend)

    # a sigmoid's last bit moves a bf16 rounding of the result now and then
    _, (d_x, _, _) = assert_kernels_equal_the_plain_form(
        silu, probe, (x, kernel, bias), dtype,
        rtol=1e-4 if dtype == jnp.bfloat16 else 2e-6)
    assert d_x.shape == x.shape
    np.testing.assert_array_equal(d_x[..., :offset], 0.0)
    np.testing.assert_array_equal(d_x[..., offset + d:], 0.0)
    out = silu("pallas_interpret", x, kernel, bias)
    assert out.shape == (batch, t, d) and out.dtype == dtype


@pytest.mark.parametrize("backend", ["xla", "pallas_interpret"])
def test_silu_pieces_are_the_whole_result_cut_along_the_channels(backend):
    """The pieces a reader wants apart: a call a piece from the kernels,
    slices of the one result from ``jax.numpy``; equal values, equal
    gradients."""
    x, kernel, bias, probe = silu_operands(2, 32, 512, jnp.float32,
                                           offset=128, beside=64)
    pieces = (256, 128, 128)

    def whole(x, kernel, bias):
        return silu_short_conv(x, kernel, bias, offset=128, backend=backend)

    def cut(x, kernel, bias):
        outs = silu_short_conv(x, kernel, bias, offset=128, pieces=pieces,
                               backend=backend)
        assert tuple(o.shape[-1] for o in outs) == pieces
        return jnp.concatenate(outs, axis=-1)

    want = value_and_grads(whole, probe, x, kernel, bias)
    got = value_and_grads(cut, probe, x, kernel, bias)
    for mine, theirs in zip(jax.tree_util.tree_leaves(got),
                            jax.tree_util.tree_leaves(want)):
        np.testing.assert_allclose(mine, theirs, rtol=1e-6, atol=1e-6)
    with pytest.raises(ValueError, match="pieces"):
        silu_short_conv(x, kernel, bias, offset=128, pieces=(256, 128),
                        backend=backend)


def _gated_run():
    bcz, kernel, _ = operands(1, 48, 128, jnp.float32)
    return bcz, jax.jit(lambda bcz: gated_short_conv(
        bcz, kernel, backend="pallas_interpret"))


def _silu_run():
    x, kernel, bias, _ = silu_operands(1, 48, 128, jnp.float32, taps=3)
    return x, jax.jit(lambda x: silu_short_conv(
        x, kernel, bias, backend="pallas_interpret"))


@pytest.mark.parametrize("make", [_gated_run, _silu_run],
                         ids=["gated", "silu"])
@pytest.mark.parametrize("at", [0, 15, 16, 30, 47])
def test_the_kernel_reaches_across_a_tile_s_edge_and_not_before_a_token(
        at, make):
    """Tiles of 16 tokens: a change at token ``at`` moves it and the two
    tokens after it, in the next tile where that is where they lie."""
    operand, run = make()
    moved = run(operand.at[0, at, :128].add(1.0))   # b, or x, of one token
    delta = np.abs(np.asarray(moved - run(operand))).max(axis=-1)[0]
    reach = list(range(at, min(at + 3, 48)))
    assert np.all(delta[reach] > 1e-5), delta
    np.testing.assert_array_equal(np.delete(delta, reach), 0.0)


def test_the_first_rows_see_zeros_before_the_sequence():
    """Token 0's pre-activation is its own tap and the bias, whatever the
    block before the first tile is read as."""
    x, kernel, bias, _ = silu_operands(2, 32, 128, jnp.float32)
    out = silu_short_conv(x, kernel, bias, backend="pallas_interpret")
    np.testing.assert_allclose(
        out[:, 0], jax.nn.silu(kernel[-1] * x[:, 0] + bias), rtol=1e-6,
        atol=1e-6)
    np.testing.assert_allclose(
        out[:, 1], jax.nn.silu(kernel[-1] * x[:, 1] + kernel[-2] * x[:, 0]
                               + bias), rtol=1e-6, atol=1e-6)


def test_backends_by_name_and_by_shape():
    bcz, kernel, _ = operands(1, 20, 64, jnp.float32)    # tiles nothing
    assert short_conv._tiles(20, 64) is None
    np.testing.assert_array_equal(
        gated_short_conv(bcz, kernel),                   # auto: the CPU's
        gated_short_conv(bcz, kernel, backend="xla"))
    with pytest.raises(ValueError, match="tile"):
        gated_short_conv(bcz, kernel, backend="pallas_interpret")
    with pytest.raises(ValueError, match="unknown backend"):
        gated_short_conv(bcz, kernel, backend="mosaic")
    with pytest.raises(ValueError, match="3 D"):
        gated_short_conv(bcz[..., :100], kernel)
    assert short_conv._tiles(8192, 2048) == (256, 1024)


@pytest.mark.parametrize("t,d,offset,pieces", [
    (20, 128, 0, None),             # tokens that are no whole tiles
    (32, 96, 0, None),              # channels that are no whole lanes
    (32, 128, 64, None),            # an offset inside a lane tile
    (32, 256, 128, (192, 64)),      # a piece that is no whole lanes
], ids=["tokens", "channels", "offset", "piece"])
def test_silu_shapes_that_do_not_tile(t, d, offset, pieces, monkeypatch):
    """``'auto'`` takes ``jax.numpy`` for them, on a TPU too; ``'pallas'``
    says what it tiles."""
    x, kernel, bias, _ = silu_operands(1, t, d, jnp.float32, offset=offset)
    call = dict(offset=offset, pieces=pieces)
    want = silu_short_conv(x, kernel, bias, backend="xla", **call)
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    got = silu_short_conv(x, kernel, bias, **call)
    for mine, theirs in zip(jax.tree_util.tree_leaves(got),
                            jax.tree_util.tree_leaves(want)):
        np.testing.assert_array_equal(mine, theirs)
    for backend in ("pallas", "pallas_interpret"):
        with pytest.raises(ValueError, match="tile"):
            silu_short_conv(x, kernel, bias, backend=backend, **call)


def test_silu_backends_by_name_and_operands_by_shape():
    x, kernel, bias, _ = silu_operands(1, 32, 128, jnp.float32, offset=128)
    np.testing.assert_array_equal(
        silu_short_conv(x, kernel, bias, offset=128),    # auto: the CPU's
        silu_short_conv(x, kernel, bias, offset=128, backend="xla"))
    with pytest.raises(ValueError, match="unknown backend"):
        silu_short_conv(x, kernel, bias, backend="mosaic")
    with pytest.raises(ValueError, match="channels 256:384"):
        silu_short_conv(x, kernel, bias, offset=256)     # past x's width
    with pytest.raises(ValueError, match="bias"):
        silu_short_conv(x, kernel, bias[:100])
    # the cell's: 6 blocks of 1,024 from the projection's fifth block on
    assert short_conv._tiles(8192, 4096, 4096) == (256, 1024)
    assert short_conv._tiles(8192, 1024, 9216) == (256, 1024)
    assert short_conv._tiles(8192, 1024, 8192 + 512) == (256, 512)
