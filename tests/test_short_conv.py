"""``ops/short_conv.py``: the kernels ``bf_sconv_fwd`` / ``bf_sconv_bwd`` in
the Pallas interpreter against the ``jax.numpy`` form and its autodiff, over
several tiles of tokens and blocks of channels, at other tap counts, and
across a tile's edge."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from bluefog_tpu.ops import short_conv
from bluefog_tpu.ops.short_conv import gated_short_conv


def operands(batch, t, d, dtype, taps=3):
    keys = jax.random.split(jax.random.PRNGKey(t + d), 3)
    return (jax.random.normal(keys[0], (batch, t, 3 * d)).astype(dtype),
            jax.random.uniform(keys[1], (taps, d), minval=-0.6, maxval=0.6),
            jax.random.normal(keys[2], (batch, t, d)))


def value_and_grads(backend, bcz, kernel, probe):
    return jax.jit(jax.value_and_grad(lambda bcz, kernel: jnp.sum(
        probe * gated_short_conv(bcz, kernel, backend=backend).astype(
            jnp.float32)), argnums=(0, 1)))(bcz, kernel)


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
    want = value_and_grads("xla", bcz, kernel, probe)
    got = value_and_grads("pallas_interpret", bcz, kernel, probe)
    np.testing.assert_allclose(got[0], want[0], rtol=2e-6)
    unit = 2.0 ** -7 if dtype == jnp.bfloat16 else 0.0   # one bf16 rounding
    np.testing.assert_allclose(
        got[1][0].astype(jnp.float32), want[1][0].astype(jnp.float32),
        atol=2e-5, rtol=unit)
    assert got[1][0].dtype == dtype and got[1][1].dtype == jnp.float32
    scale = float(jnp.max(jnp.abs(want[1][1])))
    np.testing.assert_allclose(got[1][1], want[1][1], atol=2e-6 * scale + (
        unit * scale))
    out = gated_short_conv(bcz, kernel, backend="pallas_interpret")
    assert out.shape == (batch, t, d) and out.dtype == dtype


@pytest.mark.parametrize("at", [0, 15, 16, 30, 47])
def test_the_kernel_reaches_across_a_tile_s_edge_and_not_before_a_token(at):
    """Tiles of 16 tokens: a change at token ``at`` moves it and the two
    tokens after it, in the next tile where that is where they lie."""
    bcz, kernel, _ = operands(1, 48, 128, jnp.float32)
    run = jax.jit(lambda bcz: gated_short_conv(bcz, kernel,
                                               backend="pallas_interpret"))
    moved = run(bcz.at[0, at, :128].add(1.0))            # b of one token
    delta = np.abs(np.asarray(moved - run(bcz))).max(axis=-1)[0]
    reach = list(range(at, min(at + 3, 48)))
    assert np.all(delta[reach] > 1e-5), delta
    np.testing.assert_array_equal(np.delete(delta, reach), 0.0)


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
