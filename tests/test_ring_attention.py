"""Sequence parallelism: ring attention + Ulysses vs full-attention oracle.

Strategy mirrors the framework's test pyramid (SURVEY.md §4): an 8-virtual-
device CPU mesh stands in for the TPU slice, and closed-form/oracle
equivalence is asserted — here the oracle is single-device full attention on
the gathered sequence.
"""

import functools

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import Mesh, PartitionSpec as P
from bluefog_tpu.parallel.api import shard_map

from bluefog_tpu.models.transformer import GPTConfig, TransformerLM
from bluefog_tpu.ops.ring_attention import (
    all_to_all_attention,
    local_attention,
    ring_attention,
    zigzag_shard,
    zigzag_unshard,
)

N = 8
B, T_LOCAL, H, D = 2, 16, 8, 32
T = N * T_LOCAL


def _mesh():
    return Mesh(np.array(jax.devices()[:N]), ("sp",))


def _qkv(seed=0, dtype=jnp.float32):
    ks = jax.random.split(jax.random.PRNGKey(seed), 3)
    shape = (B, T, H, D)
    return tuple(jax.random.normal(k, shape, dtype) for k in ks)


def _sharded(fn):
    """Run fn over sequence-sharded q/k/v, returning the gathered output."""
    mesh = _mesh()
    return jax.jit(shard_map(
        fn, mesh=mesh,
        in_specs=(P(None, "sp"), P(None, "sp"), P(None, "sp")),
        out_specs=P(None, "sp"), check_vma=False,
    ))


@pytest.mark.parametrize("causal", [False, True])
def test_ring_attention_matches_full(causal):
    q, k, v = _qkv()
    want = local_attention(q, k, v, causal=causal)
    got = _sharded(functools.partial(ring_attention, axis_name="sp",
                                     causal=causal))(q, k, v)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=1e-5, atol=1e-5)


@pytest.mark.parametrize("causal", [False, True])
def test_all_to_all_attention_matches_full(causal):
    q, k, v = _qkv(seed=1)
    want = local_attention(q, k, v, causal=causal)
    got = _sharded(functools.partial(all_to_all_attention, axis_name="sp",
                                     causal=causal))(q, k, v)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=1e-5, atol=1e-5)


def test_ring_attention_gradients_match_full():
    q, k, v = _qkv(seed=2)

    def loss_full(q, k, v):
        return (local_attention(q, k, v, causal=True) ** 2).sum()

    ring = _sharded(functools.partial(ring_attention, axis_name="sp",
                                      causal=True))

    def loss_ring(q, k, v):
        return (ring(q, k, v) ** 2).sum()

    g_full = jax.grad(loss_full, argnums=(0, 1, 2))(q, k, v)
    g_ring = jax.grad(loss_ring, argnums=(0, 1, 2))(q, k, v)
    for gf, gr in zip(g_full, g_ring):
        np.testing.assert_allclose(np.asarray(gr), np.asarray(gf),
                                   rtol=1e-4, atol=1e-4)


@pytest.mark.parametrize("causal", [False, True])
def test_ring_attention_long_sequence_tiled(causal):
    """T = 1024 parity with the inner flash-style tiling engaged: t_local =
    128 with kv_tile = 64 forces the lax.scan tile path (2 tiles per block)
    and, for causal, the lax.switch block-skipping dispatch."""
    b, h, d, t = 1, 2, 16, 1024
    ks = jax.random.split(jax.random.PRNGKey(7), 3)
    q, k, v = (jax.random.normal(kk, (b, t, h, d)) for kk in ks)
    want = local_attention(q, k, v, causal=causal)
    ring = _sharded(functools.partial(ring_attention, axis_name="sp",
                                      causal=causal, kv_tile=64))
    got = ring(q, k, v)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=1e-5, atol=1e-5)

    # gradients flow through the tiled/remat scan and the switch branches
    g_full = jax.grad(lambda a, b_, c: (local_attention(a, b_, c,
                                                        causal=causal) ** 2).sum(),
                      argnums=(0, 1, 2))(q, k, v)
    g_ring = jax.grad(lambda a, b_, c: (ring(a, b_, c) ** 2).sum(),
                      argnums=(0, 1, 2))(q, k, v)
    for gf, gr in zip(g_full, g_ring):
        np.testing.assert_allclose(np.asarray(gr), np.asarray(gf),
                                   rtol=1e-4, atol=1e-4)


def test_zigzag_shard_roundtrip():
    x = jnp.arange(B * T * 3, dtype=jnp.float32).reshape(B, T, 3)
    z = zigzag_shard(x, N)
    assert z.shape == x.shape
    np.testing.assert_array_equal(np.asarray(zigzag_unshard(z, N)),
                                  np.asarray(x))
    # rank 0's shard = chunks 0 and 2N-1 of the global sequence
    c = T // (2 * N)
    np.testing.assert_array_equal(
        np.asarray(z[:, :2 * c]),
        np.asarray(jnp.concatenate([x[:, :c], x[:, (2 * N - 1) * c:]], 1)))


def test_ring_attention_zigzag_causal_matches_full():
    """Load-balanced causal layout: zigzag-shard in, zigzag-unshard out,
    exact parity with the full-attention oracle."""
    q, k, v = _qkv(seed=3)
    want = local_attention(q, k, v, causal=True)
    ring = _sharded(functools.partial(ring_attention, axis_name="sp",
                                     causal=True, layout="zigzag"))
    got = zigzag_unshard(
        ring(zigzag_shard(q, N), zigzag_shard(k, N), zigzag_shard(v, N)), N)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=1e-5, atol=1e-5)


def test_ring_attention_zigzag_gradients_match_full():
    q, k, v = _qkv(seed=4)

    ring = _sharded(functools.partial(ring_attention, axis_name="sp",
                                      causal=True, layout="zigzag"))

    def loss_ring(q, k, v):
        out = zigzag_unshard(
            ring(zigzag_shard(q, N), zigzag_shard(k, N), zigzag_shard(v, N)),
            N)
        return (out ** 2).sum()

    def loss_full(q, k, v):
        return (local_attention(q, k, v, causal=True) ** 2).sum()

    g_full = jax.grad(loss_full, argnums=(0, 1, 2))(q, k, v)
    g_ring = jax.grad(loss_ring, argnums=(0, 1, 2))(q, k, v)
    for gf, gr in zip(g_full, g_ring):
        np.testing.assert_allclose(np.asarray(gr), np.asarray(gf),
                                   rtol=1e-4, atol=1e-4)


def test_ring_attention_zigzag_tiled_long_sequence():
    """Zigzag with the scan-tile inner path engaged (kv_tile < chunk)."""
    b, h, d, t = 1, 2, 16, 1024
    ks = jax.random.split(jax.random.PRNGKey(11), 3)
    q, k, v = (jax.random.normal(kk, (b, t, h, d)) for kk in ks)
    want = local_attention(q, k, v, causal=True)
    ring = _sharded(functools.partial(ring_attention, axis_name="sp",
                                      causal=True, layout="zigzag",
                                      kv_tile=32))
    got = zigzag_unshard(
        ring(zigzag_shard(q, N), zigzag_shard(k, N), zigzag_shard(v, N)), N)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=1e-5, atol=1e-5)


def test_ring_attention_zigzag_bf16():
    """bf16 inputs through the zigzag ring: f32 online-softmax state keeps
    the result within bf16 tolerance of the f32 oracle."""
    q, k, v = (t.astype(jnp.bfloat16) for t in _qkv(seed=5))
    want = local_attention(q.astype(jnp.float32), k.astype(jnp.float32),
                           v.astype(jnp.float32), causal=True)
    ring = _sharded(functools.partial(ring_attention, axis_name="sp",
                                      causal=True, layout="zigzag"))
    got = zigzag_unshard(
        ring(zigzag_shard(q, N), zigzag_shard(k, N), zigzag_shard(v, N)), N)
    assert got.dtype == jnp.bfloat16
    np.testing.assert_allclose(np.asarray(got, np.float32),
                               np.asarray(want), rtol=0.05, atol=0.05)


def test_ring_attention_bf16_stable():
    q, k, v = _qkv(seed=3, dtype=jnp.bfloat16)
    got = _sharded(functools.partial(ring_attention, axis_name="sp",
                                     causal=True))(q, k, v)
    assert got.dtype == jnp.bfloat16
    assert np.isfinite(np.asarray(got, np.float32)).all()
    want = local_attention(q, k, v, causal=True)
    np.testing.assert_allclose(np.asarray(got, np.float32),
                               np.asarray(want, np.float32),
                               rtol=0.1, atol=0.1)


def test_head_count_guard():
    mesh = _mesh()
    q = k = v = jnp.zeros((B, T, 4, D))  # 4 heads < 8 devices

    def f(q, k, v):
        return all_to_all_attention(q, k, v, "sp")

    with pytest.raises(ValueError, match="not divisible"):
        shard_map(f, mesh=mesh,
                  in_specs=(P(None, "sp"),) * 3,
                  out_specs=P(None, "sp"),
                  check_vma=False)(q, k, v)


def test_transformer_lm_sequence_parallel_matches_single_device():
    """The model forward with ring attention inside shard_map equals the
    single-device full-sequence forward — long context is a drop-in."""
    cfg = GPTConfig.tiny()
    model = TransformerLM(cfg)
    tokens = jax.random.randint(jax.random.PRNGKey(0), (B, T), 0, cfg.vocab_size)
    params = model.init(jax.random.PRNGKey(1), tokens)
    want = model.apply(params, tokens)

    mesh = _mesh()

    def fwd(params, tokens):
        t_local = tokens.shape[1]
        offset = jax.lax.axis_index("sp") * t_local
        attn = functools.partial(ring_attention, axis_name="sp", causal=True)
        return model.apply(params, tokens, attn_fn=attn,
                           position_offset=offset)

    got = jax.jit(shard_map(
        fwd, mesh=mesh,
        in_specs=(P(), P(None, "sp")),
        out_specs=P(None, "sp"), check_vma=False,
    ))(params, tokens)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=2e-4, atol=2e-4)


def test_transformer_lm_ulysses_matches_single_device():
    cfg = GPTConfig.tiny()  # 4 heads — use a 4-device mesh axis
    model = TransformerLM(cfg)
    n = 4
    tokens = jax.random.randint(jax.random.PRNGKey(0), (B, n * T_LOCAL), 0,
                                cfg.vocab_size)
    params = model.init(jax.random.PRNGKey(1), tokens)
    want = model.apply(params, tokens)

    mesh = Mesh(np.array(jax.devices()[:n]), ("sp",))

    def fwd(params, tokens):
        t_local = tokens.shape[1]
        offset = jax.lax.axis_index("sp") * t_local
        attn = functools.partial(all_to_all_attention, axis_name="sp",
                                 causal=True)
        return model.apply(params, tokens, attn_fn=attn,
                           position_offset=offset)

    got = jax.jit(shard_map(
        fwd, mesh=mesh,
        in_specs=(P(), P(None, "sp")),
        out_specs=P(None, "sp"), check_vma=False,
    ))(params, tokens)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=2e-4, atol=2e-4)


def test_flash_block_sizes_clamp():
    """Tile edges must exactly divide T (kernel requirement) and default to
    512 — the edge the on-chip tune measured 3.5-5x faster than the
    library's 128 default (PROFILE.md, flash_attention_bench --tune)."""
    from bluefog_tpu.ops.ring_attention import _flash_block_sizes

    assert _flash_block_sizes(1024).block_q == 512
    assert _flash_block_sizes(4096).block_q == 512
    assert _flash_block_sizes(384).block_q == 128   # 256 does not divide 384
    assert _flash_block_sizes(128).block_q == 128
    assert _flash_block_sizes(4096, 1024).block_q == 1024
    assert _flash_block_sizes(2048, 128).block_q == 128
    for t in (128, 384, 1024, 4096):
        bs = _flash_block_sizes(t)
        assert t % bs.block_q == 0 and t % bs.block_k == 0
        assert bs.block_k <= bs.block_k_major
