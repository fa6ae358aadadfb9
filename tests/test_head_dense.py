"""``HeadDense``: ``nn.Dense``'s leaves applied so that a projection's heads
reach (or leave) the attention kernel in few passes, at every site of
``models/transformer.py`` that uses it and in both of its forms (heads inside
the dot from 128 wide, channel-major below).  It must be indistinguishable
from ``nn.Dense`` + ``reshape`` in everything but the compiled layout: the
same leaves from the same key, the same output, the same gradients, and the
phase scope it is applied under on the forward and on both backward dots."""

import math
import re

import flax.linen as nn
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from bluefog_tpu.models.transformer import HeadDense

B, T = 2, 8
# site: (in width or None, heads, parts, inward, bias); a head of 128 or more
# takes the heads-inside-the-dot form, a narrower one the channel-major form
SITES = {
    "qkv": (32, (4, 8), 3, False, True),              # Block: fused q, k, v
    "proj": (None, (4, 8), 1, True, True),            # Block: heads in
    "q_up": (48, (2, 192), 1, False, False),          # LatentAttention
    "kv_up": (32, (2, 256), 1, False, False),
    "o": (None, (2, 128), 1, True, False),
    "q_up_narrow": (48, (4, 24), 1, False, False),    # its tiny test widths
    "qkv_wide": (32, (2, 128), 3, False, True),       # fused, wide heads
}


def _pair(site, dtype):
    width, heads, parts, inward, bias = SITES[site]
    features = 64 if inward else parts * math.prod(heads)
    head = HeadDense(features, heads, inward=inward, parts=parts,
                     use_bias=bias, dtype=dtype)
    dense = nn.Dense(features, use_bias=bias, dtype=dtype)
    shape = (B, T) + (heads if inward else (width,))
    x = jax.random.normal(jax.random.PRNGKey(1), shape, jnp.float32)
    flat = x.reshape(B, T, -1)

    def as_head(params, x):          # the parts stacked, as Dense lays them
        y = head.apply(params, x)
        return jnp.stack(y, axis=2) if parts > 1 else y

    def as_dense(params, x):
        y = dense.apply(params, x.reshape(flat.shape))
        if inward:
            return y
        return y.reshape((B, T) + ((parts,) if parts > 1 else ()) + heads)

    return head, dense, x, flat, as_head, as_dense


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16],
                         ids=["f32", "bf16"])
@pytest.mark.parametrize("site", sorted(SITES))
def test_head_dense_is_dense_and_a_reshape(site, dtype):
    head, dense, x, flat, as_head, as_dense = _pair(site, dtype)
    params = head.init(jax.random.PRNGKey(0), x)
    want = dense.init(jax.random.PRNGKey(0), flat)
    # the same leaves: paths, shapes, dtypes, values from the same key
    assert jax.tree_util.tree_structure(params) == (
        jax.tree_util.tree_structure(want))
    for a, b in zip(jax.tree_util.tree_leaves(params),
                    jax.tree_util.tree_leaves(want)):
        assert a.dtype == b.dtype == jnp.float32
        np.testing.assert_array_equal(a, b)
    if "bias" in params["params"]:       # zeros hide a misplaced bias
        bias = params["params"]["bias"]
        params = {"params": {**params["params"], "bias": jax.random.normal(
            jax.random.PRNGKey(2), bias.shape)}}
    # f32: the same products, perhaps summed in another order; bf16: one
    # rounding of the output (2**-8 of it) on top of that
    tol = 1e-6 if dtype == jnp.float32 else 2 ** -7

    def near(got, ref):
        got, ref = (np.asarray(t, np.float32) for t in (got, ref))
        assert got.shape == ref.shape
        assert np.abs(got - ref).max() <= tol * max(np.abs(ref).max(), 1e-6)

    with jax.default_matmul_precision("highest"):
        out = as_head(params, x)
        assert out.dtype == dtype
        near(out, as_dense(params, x))
        weight = jax.random.normal(jax.random.PRNGKey(3), out.shape)

        def total(fn):
            return lambda p, x: (fn(p, x).astype(jnp.float32) * weight).sum()

        got = jax.grad(total(as_head), argnums=(0, 1))(params, x)
        ref = jax.grad(total(as_dense), argnums=(0, 1))(params, x)
    for a, b in zip(jax.tree_util.tree_leaves(got),
                    jax.tree_util.tree_leaves(ref)):
        near(a, b)


@pytest.mark.parametrize("site", sorted(SITES))
def test_head_dense_keeps_the_phase_scope_forward_and_backward(site):
    """The benchmark's phase reader finds a projection by the scope it was
    applied under (``bf.mla.project``, ``bf.attn.project``): the forward
    dot and the two backward dots (input and kernel cotangents) carry it."""
    head, _, x, _, as_head, _ = _pair(site, jnp.bfloat16)
    params = head.init(jax.random.PRNGKey(0), x)

    def loss(p, x):
        with jax.named_scope("bf.mla.project"):
            return as_head(p, x).astype(jnp.float32).sum()

    def dot_scopes(fn):
        text = jax.jit(fn).lower(params, x).as_text(debug_info=True)
        names = dict(re.findall(r'(#loc\d+) = loc\("([^"]*)"', text))
        return [names.get(ref, ref) for ref in re.findall(
            r"stablehlo\.dot_general.*loc\((#loc\d+)\)", text)]

    # the forward dot feeds a plain sum, so the gradient holds two dots
    dots = dot_scopes(jax.grad(loss, argnums=(0, 1)))
    assert len(dots) == 2, dots
    assert all("bf.mla.project" in d and "transpose" in d for d in dots), dots
    dots = dot_scopes(lambda p, x: jax.vjp(loss, p, x)[0])
    assert len(dots) == 1 and "bf.mla.project" in dots[0], dots
    assert "transpose" not in dots[0]


def test_head_dense_widths_must_multiply_out():
    x = jnp.zeros((B, T, 32))
    with pytest.raises((TypeError, ValueError)):
        HeadDense(96, (4, 7), parts=3).init(jax.random.PRNGKey(0), x)
