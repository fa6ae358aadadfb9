"""The plain reference of the family ``conv_gqa_moe``: the whole forward pass
and loss of an LFM2-style decoder (the language model of
LiquidAI/LFM2-8B-A1B: gated short convolutions beside rotary, QK-normed
grouped-query attention; sigmoid top-k routing with a selection bias and no
shared expert; a tied head) in ``jax.numpy`` and f32.  It imports nothing of
``bluefog_tpu``: no kernel, no flax module, no bf16 cast, no sort, no grouped
matmul.  It reads the parameter tree the system trains (the names are the
only thing the two share).

``rms(x) = g * x / sqrt(mean(x^2) + eps)``.  One block over ``x (B, T, D)``,
no bias anywhere:

- ``u = rms_1(x)``;
- a ``conv`` block: ``[b; c; z] = u W_in`` (three thirds, in that order),
  ``s = b * z``, ``conv_t = sum_j k_j * s_{t - (K - 1) + j}`` a channel with
  zeros before the sequence (``K`` taps), ``o = (c * conv) W_out``.  No
  activation;
- a ``full_attention`` block: ``q = rms_q(u W_q)`` and ``k = rms_k(u W_k)``,
  each norm over one head's channels with one scale vector shared by the
  heads, ``v = u W_v``; ``H`` query over ``G`` key/value heads, query head
  ``h`` reads ``h // (H / G)``; ``q`` and ``k`` turned by rotary over the
  whole head (pair ``i`` is elements ``i`` and ``i + head_dim / 2``, angle
  ``position * theta ** (-2i / head_dim)``); scores ``q . k /
  sqrt(head_dim)``, causal over every key; ``o = concat_h(a_h) W_o``;
- ``h = x + o``; ``f = rms_2(h)``;
- the first ``dense_blocks`` blocks: ``h + W_down (silu(W_gate f) * W_up f)``;
- the others: ``l = f W_r`` over all the router's outputs, ``s =
  sigmoid(l)``, ``S`` the ``top_k`` largest of ``s + bias`` (the selection
  bias: a buffer, no gradient), ``w_i = scale * s_i / (sum_{j in S} s_j +
  weight_eps)`` for ``i`` in ``S`` and 0 outside; ``h + sum over the chosen
  i that this chip holds of w_i W_down,i (silu(W_gate,i f) * W_up,i f)``.
  Dense by mask: every held expert sees every token, weighted 0 where it
  was not chosen, a block of tokens at a time.  What the absent experts
  would add is left out (the chip's share of the deployment, as in the
  system); there is no shared expert.  Where ``train_router`` is false,
  ``w`` is a constant of the backward pass.

Logits ``rms_f(x) E^T`` with ``E`` the embedding (tied; not scaled), the loss
the mean cross entropy over the ``B * T`` positions.  Attention goes a head
and a block of queries at a time, the experts and the cross entropy in
blocks of rows (``lax.map``), so that 32,768 tokens fit beside the
parameters.

Every product is a plain ``@`` or ``einsum`` on f32 operands; the caller
computes it under ``jax.default_matmul_precision("highest")`` (the harness's
``reference.model_loss_error`` and the tests do), without which a TPU
multiplies f32 in bf16 passes.

``sizes`` is what the shapes do not say: ``kinds`` (``"conv"`` or
``"full_attention"`` a block), ``head_dim``, ``rope_theta``, ``eps``,
``dense_blocks``, ``top_k``, ``scale``, ``weight_eps``, ``held_first``,
``train_router``.
"""

import jax
import jax.numpy as jnp
from jax import lax

ROW_BLOCK = 1024      # rows of the head's logits, or of an expert, at once
QUERY_BLOCK = 512     # queries of one head scored against every key at once


def rms(x, scale, eps):
    return x * lax.rsqrt(jnp.mean(x * x, axis=-1, keepdims=True) + eps) * scale


def rotary(x, positions, theta):
    """``x (B, T, H, R)``: pair ``i`` = elements ``i`` and ``i + R / 2``, as
    a complex number turned by ``position * theta ** (-2i / R)``."""
    half = x.shape[-1] // 2
    freq = theta ** (-jnp.arange(half, dtype=jnp.float32) / half)
    angle = positions[:, None, None].astype(jnp.float32) * freq   # (T, 1, R/2)
    z = lax.complex(x[..., :half], x[..., half:]) * jnp.exp(1j * angle)
    return jnp.concatenate([z.real, z.imag], axis=-1)


def causal_conv(s, kernel):
    """``out_t = sum_j kernel[j] * s_{t - (K - 1) + j}`` a channel, zeros
    before the sequence: ``s (B, T, C)``, ``kernel (K, C)``."""
    taps = kernel.shape[0]
    out = kernel[-1] * s
    for back in range(1, taps):                # the token ``back`` places ago
        earlier = jnp.concatenate(
            [jnp.zeros_like(s[:, :back]), s[:, :-back]], axis=1)
        out = out + kernel[taps - 1 - back] * earlier
    return out


def short_conv(p, u):
    b, c, z = jnp.split(u @ p["in_proj"]["kernel"], 3, axis=-1)
    return (c * causal_conv(b * z, p["conv_kernel"])) @ p["out_proj"]["kernel"]


def attention(q, k, v):
    """``q (B, T, H, D)``, ``k, v (B, T, G, D)``, ``G`` dividing ``H`` ->
    ``(B, T, H, D)``: causal softmax attention over every key."""
    b, t, heads, dim = q.shape
    share = heads // k.shape[2]
    size = QUERY_BLOCK if t % QUERY_BLOCK == 0 else t
    keys = jnp.arange(t)[None, :]

    def one_head(args):
        h, qh = args                                       # qh (B, T, D)
        kh, vh = k[:, :, h // share], v[:, :, h // share]

        def one_block(block):
            rows, qb = block                               # (size,), (B, size, D)
            scores = jnp.einsum("bqd,bkd->bqk", qb, kh) / jnp.sqrt(
                jnp.float32(dim))
            p = jax.nn.softmax(
                jnp.where(keys <= rows[:, None], scores, -jnp.inf), axis=-1)
            return jnp.einsum("bqk,bkd->bqd", p, vh)

        blocks = lax.map(one_block, (
            jnp.arange(t).reshape(-1, size),
            jnp.moveaxis(qh.reshape(b, -1, size, dim), 1, 0)))
        return jnp.moveaxis(blocks, 0, 1).reshape(b, t, dim)

    by_head = lax.map(one_head, (jnp.arange(heads), jnp.moveaxis(q, 2, 0)))
    return jnp.moveaxis(by_head, 0, 2)


def gqa(p, u, positions, sizes):
    b, t, _ = u.shape
    dim, eps = sizes["head_dim"], sizes["eps"]
    q = rms((u @ p["q"]["kernel"]).reshape(b, t, -1, dim),
            p["q_norm"]["scale"], eps)
    k = rms((u @ p["k"]["kernel"]).reshape(b, t, -1, dim),
            p["k_norm"]["scale"], eps)
    v = (u @ p["v"]["kernel"]).reshape(b, t, -1, dim)
    q = rotary(q, positions, sizes["rope_theta"])
    k = rotary(k, positions, sizes["rope_theta"])
    return attention(q, k, v).reshape(b, t, -1) @ p["o"]["kernel"]


def route(router, bias, f, sizes):
    """``w (..., E)``: ``scale * s_i / (sum of the chosen s + weight_eps)``
    on the ``top_k`` largest ``s + bias``, 0 for the others."""
    s = jax.nn.sigmoid(f @ router)
    steer = s + bias
    kth = lax.top_k(steer, sizes["top_k"])[0][..., -1:]
    chosen = jnp.where(steer >= kth, s, 0.0)
    return sizes["scale"] * chosen / (
        jnp.sum(chosen, axis=-1, keepdims=True) + sizes["weight_eps"])


def swiglu(f, gate, up, down):
    return (jax.nn.silu(f @ gate) * (f @ up)) @ down


def held_experts(p, f, weights, first):
    """``sum_i weights[..., first + i] * E_i(f)`` over the held experts, a
    block of rows at a time."""
    count, d = p["w_gate"].shape[0], f.shape[-1]
    rows = f.reshape(-1, d)
    w_held = weights.reshape(-1, weights.shape[-1])[:, first:first + count]
    size = ROW_BLOCK if rows.shape[0] % ROW_BLOCK == 0 else rows.shape[0]

    def one_block(block):
        r, w = block                                   # (size, D), (size, count)

        def add_expert(acc, expert):
            wg, wu, wd, wi = expert                    # wi (size,): 0 unchosen
            return acc + wi[:, None] * swiglu(r, wg, wu, wd), None

        out, _ = lax.scan(add_expert, jnp.zeros_like(r),
                          (p["w_gate"], p["w_up"], p["w_down"], w.T))
        return out

    out = lax.map(one_block, (rows.reshape(-1, size, d),
                              w_held.reshape(-1, size, count)))
    return out.reshape(f.shape)


def block(p, bias, x, positions, kind, dense, sizes):
    """``bias``: the block's selection-bias buffer, ``None`` where it is
    dense."""
    eps = sizes["eps"]
    u = rms(x, p["ln1"]["scale"], eps)
    if kind == "conv":
        h = x + short_conv(p["conv"], u)
    else:
        h = x + gqa(p["attn"], u, positions, sizes)
    f = rms(h, p["ln2"]["scale"], eps)
    if dense:
        mlp = p["mlp"]
        return h + swiglu(f, mlp["gate"]["kernel"], mlp["up"]["kernel"],
                          mlp["down"]["kernel"])
    weights = route(p["moe"]["router"], lax.stop_gradient(bias), f, sizes)
    if not sizes["train_router"]:
        weights = lax.stop_gradient(weights)
    return h + held_experts(p["moe"], f, weights, sizes["held_first"])


def head_cross_entropy(h, scale, table, targets, eps):
    """Mean over all positions of the cross entropy of ``rms(h) @ table^T``
    against ``targets``, in blocks of rows."""
    rows = h.reshape(-1, h.shape[-1])
    labels = targets.reshape(-1)
    size = ROW_BLOCK if rows.shape[0] % ROW_BLOCK == 0 else rows.shape[0]

    def block_sum(args):
        r, lab = args
        logp = jax.nn.log_softmax(rms(r, scale, eps) @ table.T, axis=-1)
        return -jnp.sum(jnp.take_along_axis(logp, lab[:, None], axis=-1))

    sums = lax.map(block_sum, (rows.reshape(-1, size, rows.shape[-1]),
                               labels.reshape(-1, size)))
    return jnp.sum(sums) / rows.shape[0]


def hidden(sizes, params, model_state, tokens):
    """``tokens (B, T)`` -> the last block's output ``(B, T, D)``."""
    positions = jnp.arange(tokens.shape[1])
    x = params["tok"]["embedding"][tokens]
    for i, kind in enumerate(sizes["kinds"]):
        dense = i < sizes["dense_blocks"]
        bias = None if dense else (
            model_state["buffers"][f"block_{i}"]["moe"]["selection_bias"])
        x = block(params[f"block_{i}"], bias, x, positions, kind, dense,
                  sizes)
    return x


def logits(sizes, params, model_state, tokens):
    x = hidden(sizes, params, model_state, tokens)
    return rms(x, params["ln_f"]["scale"], sizes["eps"]) @ (
        params["tok"]["embedding"].T)


def loss(sizes, params, model_state, tokens):
    """``tokens (B, T + 1)`` -> the scalar training loss."""
    params = jax.tree_util.tree_map(lambda a: a.astype(jnp.float32), params)
    x = hidden(sizes, params, model_state, tokens[:, :-1])
    return head_cross_entropy(x, params["ln_f"]["scale"],
                              params["tok"]["embedding"], tokens[:, 1:],
                              sizes["eps"])
