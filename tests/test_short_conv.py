"""``ops/short_conv.py``: the kernels ``bf_sconv_fwd`` / ``bf_sconv_bwd`` (the
gate, convolution and gate) and ``bf_cconv_fwd`` / ``bf_cconv_bwd`` (the
convolution, bias and SiLU) in the Pallas interpreter against the
``jax.numpy`` forms and their autodiff, over several tiles of tokens and
blocks of channels, at other tap counts, across a tile's edge, with the
operand handed over wider than the convolution, and with the SiLU's result
normalised over groups of channels inside the kernels (a KDA head's q and
k)."""

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


@pytest.mark.parametrize("batch,t,d,dtype,taps,offset,beside,tiles,l2norm", [
    (2, 64, 128, jnp.float32, 4, 0, 0, (64, 128), None),   # x is the operand
    (1, 512, 256, jnp.float32, 4, 256, 0, (256, 256), None),  # two tiles
    (2, 48, 384, jnp.bfloat16, 4, 128, 128, (16, 128), None),  # 3 x 3 blocks
    (2, 32, 256, jnp.float32, 4, 256, 20, (32, 256), None),  # a ragged block
    (1, 32, 128, jnp.float32, 2, 128, 0, (32, 128), None),
    (1, 96, 256, jnp.bfloat16, 3, 0, 128, (32, 256), None),
    # normalised: KDA's q (a head a lane tile, scaled) and k, wider heads,
    # several tiles of tokens, several groups a block and blocks a tensor
    (2, 64, 256, jnp.float32, 4, 0, 0, (64, 256), (128, 1e-6, 128 ** -0.5)),
    (1, 512, 256, jnp.float32, 4, 256, 0, (256, 256), (256, 1e-6, 1.0)),
    (2, 48, 384, jnp.bfloat16, 4, 128, 128, (16, 128), (128, 1e-6, 1.0)),
    (1, 48, 512, jnp.bfloat16, 4, 0, 0, (16, 512), (256, 1e-6, 0.25)),
    (1, 32, 768, jnp.float32, 3, 0, 64, (32, 256), (256, 1e-6, 1.0)),
    (1, 32, 128, jnp.float32, 4, 0, 0, (32, 128), (128, 1e3, 2.0)),
], ids=["one_tile", "two_tiles_offset", "bf16_3x3_inside", "ragged_beside",
        "two_taps", "bf16_three_taps", "norm_128_scaled",
        "norm_256_two_tiles_offset", "bf16_norm_128_3x3_inside",
        "bf16_norm_256_scaled", "norm_256_three_blocks", "norm_large_eps"])
def test_silu_kernels_equal_the_plain_form_in_value_and_gradients(
        batch, t, d, dtype, taps, offset, beside, tiles, l2norm):
    """Value and the gradients of ``x``, the taps and the bias; ``x`` handed
    over whole with the convolution's channels inside it, whose other
    channels take a gradient of zero; with ``l2norm`` the SiLU's result
    normalised a group of channels and row, inside the kernels."""
    group = 128 if l2norm is None else l2norm[0]
    assert short_conv._tiles(t, d, offset, group) == tiles
    x, kernel, bias, probe = silu_operands(batch, t, d, dtype, taps, offset,
                                           beside)

    def silu(backend, x, kernel, bias):
        return silu_short_conv(x, kernel, bias, offset=offset, l2norm=l2norm,
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


def _silu_run(l2norm=None):
    x, kernel, bias, _ = silu_operands(1, 48, 128, jnp.float32, taps=3)
    return x, jax.jit(lambda x: silu_short_conv(
        x, kernel, bias, l2norm=l2norm, backend="pallas_interpret"))


def _normalised_run():
    return _silu_run((128, 1e-6, 128 ** -0.5))


@pytest.mark.parametrize("make", [_gated_run, _silu_run, _normalised_run],
                         ids=["gated", "silu", "normalised"])
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


@pytest.mark.parametrize("l2norm", [None, (128, 1e-6, 0.5)],
                         ids=["silu", "normalised"])
@pytest.mark.parametrize("at", [15, 16, 17, 33])
def test_a_cotangent_reaches_back_across_a_tile_s_edge(at, l2norm):
    """Tiles of 16 tokens: a cotangent at token ``at`` alone gives ``x`` a
    gradient at it and at the three tokens before it (4 taps), in the tile
    before where that is where they lie, and the plain form's."""
    x, kernel, bias, _ = silu_operands(1, 48, 128, jnp.float32)
    probe = jnp.zeros((1, 48, 128)).at[0, at].set(
        jax.random.normal(jax.random.PRNGKey(at), (128,)))

    def grads(backend):
        return value_and_grads(lambda *a: silu_short_conv(
            *a, l2norm=l2norm, backend=backend), probe, x, kernel, bias)[1]

    got, want = grads("pallas_interpret"), grads("xla")
    reach = list(range(at - 3, at + 1))
    rows = np.abs(np.asarray(got[0])).max(axis=-1)[0]
    assert np.all(rows[reach] > 1e-6), rows
    np.testing.assert_array_equal(np.delete(rows, reach), 0.0)
    for mine, theirs in zip(got, want):
        np.testing.assert_allclose(mine, theirs, rtol=1e-5, atol=1e-6)


@pytest.mark.parametrize("l2norm", [None, (128, 1e-6, 0.5)],
                         ids=["silu", "normalised"])
def test_the_first_rows_see_zeros_before_the_sequence(l2norm):
    """Token 0's pre-activation is its own tap and the bias, whatever the
    block before the first tile is read as."""
    x, kernel, bias, _ = silu_operands(2, 32, 128, jnp.float32)
    out = silu_short_conv(x, kernel, bias, l2norm=l2norm,
                          backend="pallas_interpret")

    def after_the_silu(p):
        s = jax.nn.silu(p)
        if l2norm is None:
            return s
        return 0.5 * s / jnp.sqrt(jnp.sum(s * s, -1, keepdims=True) + 1e-6)

    np.testing.assert_allclose(
        out[:, 0], after_the_silu(kernel[-1] * x[:, 0] + bias), rtol=1e-6,
        atol=1e-6)
    np.testing.assert_allclose(
        out[:, 1], after_the_silu(kernel[-1] * x[:, 1] + kernel[-2] * x[:, 0]
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


@pytest.mark.parametrize("t,d,offset,pieces,l2norm", [
    (20, 128, 0, None, None),           # tokens that are no whole tiles
    (32, 96, 0, None, None),            # channels that are no whole lanes
    (32, 128, 64, None, None),          # an offset inside a lane tile
    (32, 256, 128, (192, 64), None),    # a piece that is no whole lanes
    (32, 256, 0, None, (64, 1e-6, 1.0)),    # a group that is half a lane tile
    (32, 768, 0, None, (384, 1e-6, 1.0)),   # whole lanes, but no block's
    (20, 256, 0, None, (128, 1e-6, 1.0)),   # a group that tiles, tokens not
    (32, 512, 128, None, (256, 1e-6, 1.0)),  # blocks of 128 from channel 128
], ids=["tokens", "channels", "offset", "piece", "half_lane_group",
        "group_of_three_lanes", "group_but_tokens", "group_across_blocks"])
def test_silu_shapes_that_do_not_tile(t, d, offset, pieces, l2norm,
                                      monkeypatch):
    """``'auto'`` takes ``jax.numpy`` for them, on a TPU too; ``'pallas'``
    says what it tiles."""
    x, kernel, bias, _ = silu_operands(1, t, d, jnp.float32, offset=offset)
    call = dict(offset=offset, pieces=pieces, l2norm=l2norm)
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
    with pytest.raises(ValueError, match="groups of 96"):
        silu_short_conv(x, kernel, bias, offset=128, l2norm=(96, 1e-6, 1.0))
    with pytest.raises(ValueError, match="groups of 128"):   # a piece of 64
        silu_short_conv(x, kernel, bias, offset=128, pieces=(64, 64),
                        l2norm=(128, 1e-6, 1.0))
    # the cell's: 6 blocks of 1,024 from the projection's fifth block on
    assert short_conv._tiles(8192, 4096, 4096) == (256, 1024)
    assert short_conv._tiles(8192, 1024, 9216) == (256, 1024)
    assert short_conv._tiles(8192, 1024, 8192 + 512) == (256, 512)
    # a KDA layer's q and k: heads of 128 in the projection's 2,048 channels
    assert short_conv._tiles(8192, 2048, 0, 128) == (256, 1024)
    assert short_conv._tiles(8192, 2048, 0, 2048) is None


def _kernel_calls(jaxpr):
    """Every ``pallas_call`` of a traced function, nested calls included, as
    ``(name, grid, block shapes, operands, primitives of the body)``."""
    for eqn in jaxpr.eqns:
        if eqn.primitive.name == "pallas_call":
            grid = eqn.params["grid_mapping"]
            yield (eqn.params["name"], grid.grid,
                   [tuple(getattr(d, "block_size", d) for d in m.block_shape)
                    for m in grid.block_mappings], len(eqn.invars),
                   {inner.primitive.name
                    for inner in eqn.params["jaxpr"].eqns})
        for value in eqn.params.values():
            for sub in value if isinstance(value, (list, tuple)) else [value]:
                sub = getattr(sub, "jaxpr", sub)
                if hasattr(sub, "eqns"):
                    yield from _kernel_calls(sub)


def _nemotron_call(x, kernel, bias):
    return silu_short_conv(x, kernel, bias, offset=4096,
                           pieces=(4096, 1024, 1024), backend="pallas")


def _ling_call(x, kernel, bias):
    return silu_short_conv(x, kernel, bias, l2norm=(128, 1e-6, 0.5),
                           backend="pallas")


def _lfm2_call(bcz, kernel):
    return gated_short_conv(bcz, kernel, backend="pallas")


_TILE, _ROWS, _TAPS = (1, 256, 1024), (1, 16, 1024), (5, 1024)


@pytest.mark.parametrize("call,shapes,forward,backward,normalises", [
    # Mamba2Mixer in nemotron3nano.t8192.solo: no normalisation, no scale
    (_nemotron_call, ((2, 8192, 10304), (4, 6144), (6144,)),
     [((2, 32, 4), [_TAPS, _TILE, _ROWS, _TILE]),
      ((2, 32, 1), [_TAPS, _TILE, _ROWS, _TILE]),
      ((2, 32, 1), [_TAPS, _TILE, _ROWS, _TILE])],
     [((1, 2, 32), [_TAPS, _TILE, _ROWS, _ROWS, _TILE, _ROWS, _TILE, _TAPS]),
      ((1, 2, 32), [_TAPS, _TILE, _ROWS, _ROWS, _TILE, _ROWS, _TILE, _TAPS]),
      ((4, 2, 32), [_TAPS, _TILE, _ROWS, _ROWS, _TILE, _ROWS, _TILE, _TAPS])],
     False),
    # LFM2's gated convolution in lfm2moe.t8192.solo: shares _specs / _tiles
    (_lfm2_call, ((4, 8192, 3 * 2048), (3, 2048)),
     [((4, 32, 2), [(3, 1024)] + [_TILE] * 3 + [_ROWS] * 2 + [_TILE])],
     [((2, 4, 32, 3), [(3, 1024)] + [_TILE] * 3 + [_ROWS] * 2 + [_TILE]
       + [_ROWS] * 2 + [_TILE, (3, 1024)])], False),
    # KdaMixer's q in ling3flash.t8192.solo: the scale goes first, in SMEM
    (_ling_call, ((1, 8192, 2048), (4, 2048), (2048,)),
     [((1, 32, 2), [(1,), _TAPS, _TILE, _ROWS, _TILE])],
     [((2, 1, 32), [(1,), _TAPS, _TILE, _ROWS, _ROWS, _TILE, _ROWS, _TILE,
                    _TAPS])], True),
], ids=["nemotron3nano", "lfm2moe", "ling3flash"])
def test_grids_and_blocks_at_the_cells_shapes(call, shapes, forward,
                                              backward, normalises):
    """The kernels' grids, block shapes and operands as the three cells'
    layers call them (traced, not lowered).  Without ``l2norm`` they are what
    they were before the kernels learnt to normalise (256 tokens by 1,024
    channels, no scale operand, no ``rsqrt`` in a body), so the other
    callers' cells compile to the programs they had (PERF.md section 6, PR
    50, has the one-off comparison of the compiled steps); with it the same
    tiles behind one f32 in SMEM."""
    operands = [jnp.zeros(shapes[0], jnp.bfloat16)] + [
        jnp.zeros(shape, jnp.float32) for shape in shapes[1:]]

    def total(*operands):
        return sum(out.astype(jnp.float32).sum()
                   for out in jax.tree_util.tree_leaves(call(*operands)))

    found = list(_kernel_calls(jax.make_jaxpr(jax.grad(
        total, argnums=tuple(range(len(operands)))))(*operands).jaxpr))
    by_direction = {
        suffix: [(grid, blocks) for name, grid, blocks, _, _ in found
                 if name.endswith(suffix)] for suffix in ("_fwd", "_bwd")}
    assert by_direction == {"_fwd": forward, "_bwd": backward}
    for name, _, blocks, n_operands, primitives in found:
        n_outputs = 1 if name.endswith("_fwd") else 2
        assert n_operands == len(blocks) - n_outputs
        assert ("rsqrt" in primitives) == normalises, name
