"""The plain reference of the family ``mamba2_gqa_moe``: the whole forward
pass and loss of a Nemotron-H-style decoder (the language model of
nvidia/NVIDIA-Nemotron-3-Nano-30B-A3B-BF16: blocks that are a Mamba-2 mixer,
an un-positioned grouped-query attention or an expert layer **alone**;
sigmoid top-k routing with a selection bias over ungated relu-squared
experts and one shared expert; an untied head) in ``jax.numpy`` and f32.  It
imports nothing of ``bluefog_tpu``: no kernel, no flax module, no bf16 cast,
no chunked scan, no sort, no grouped matmul.  It reads the parameter tree
the system trains (the names are the only thing the two share).

``rms(x) = g * x / sqrt(mean(x^2) + eps)``.  Block ``l`` over ``x (B, T,
D)``: ``x + f_l(rms_l(x))``, one norm and one sub-layer, ``f_l`` by the
block's letter; no bias but the two named below.

- ``M``, Mamba-2 (``H`` heads of ``P`` channels, ``I = H P``, state ``N``,
  ``G`` groups): ``[z (I); xBC (I + 2 G N); dt (H)] = u W_in``; ``xBC =
  silu(conv(xBC) + b_c)``, a causal depthwise convolution of ``K`` taps with
  zeros before the sequence; ``[x (H, P); B (G, N); C (G, N)] =
  split(xBC)``; ``delta = softplus(dt + dt_bias)``; ``a = -exp(A_log)``;
  **token by token** ``S_t = exp(delta_t a) S_{t-1} + delta_t x_t B_t^T``
  (``S`` is ``P x N`` a head, zero before the sequence, head ``h`` reading
  group ``h // (H / G)``) and ``y_t = S_t C_t + D x_t``; ``y = g_n *
  GroupRMS(y * silu(z))``, the gate first, then the mean of squares over
  each of the ``G`` groups of ``I / G`` channels; ``f = y W_out``.  The
  recurrence is a ``lax.scan`` over the tokens with element-wise products
  and sums alone: no chunk, no matmul form.
- ``*``, attention: ``q = u W_q`` in ``H_q`` heads, ``k = u W_k`` and ``v =
  u W_v`` in ``H_kv``, all ``head_dim`` wide; query head ``h`` reads
  key/value head ``h // (H_q / H_kv)``; scores ``q . k / sqrt(head_dim)``,
  causal over every key, **nothing turned and no table** (the model has no
  positional encoding; the Mamba-2 layers order the tokens); ``f =
  concat_h(a_h) W_o``.
- ``E``, experts: ``l = u W_r`` over all the router's outputs, ``s =
  sigmoid(l)``, ``S`` the ``top_k`` largest of ``s + bias`` (the selection
  bias: a buffer, no gradient), ``w_i = scale * s_i / (sum_{j in S} s_j +
  weight_eps)`` for ``i`` in ``S`` and 0 outside; ``f = sum over the chosen i
  that this chip holds of w_i W_down,i relu(W_up,i u)^2 + W_down,s
  relu(W_up,s u)^2``, the last term the shared expert, every token's.
  **Ungated**: two matrices an expert.  Dense by mask: every held expert
  sees every token, weighted 0 where it was not chosen, a block of tokens at
  a time.  What the absent experts would add is left out (the chip's share
  of the deployment, as in the system).  Where ``train_router`` is false,
  ``w`` is a constant of the backward pass.

Logits ``rms_f(x) W_head`` (untied; the embedding is not scaled), the loss the
mean cross entropy over the ``B * T`` positions.  Attention goes a head and
a block of queries at a time, the experts and the cross entropy in blocks of
rows (``lax.map``), so that 16,384 tokens fit beside the parameters.

Every product is a plain ``@`` or ``einsum`` on f32 operands; the caller
computes it under ``jax.default_matmul_precision("highest")`` (the harness's
``reference.model_loss_error`` and the tests do), without which a TPU
multiplies f32 in bf16 passes.

``sizes`` is what the shapes do not say: ``kinds`` (``"M"``, ``"*"`` or
``"E"`` a block), ``head_dim`` (attention), ``eps``, ``mamba_heads``,
``mamba_groups``, ``mamba_state``, ``top_k``, ``scale``, ``weight_eps``,
``held_first``, ``train_router``.
"""

import jax
import jax.numpy as jnp
from jax import lax

ROW_BLOCK = 1024      # rows of the head's logits, or of an expert, at once
QUERY_BLOCK = 512     # queries of one head scored against every key at once
MAMBA, ATTENTION, EXPERTS = "M", "*", "E"


def rms(x, scale, eps):
    return x * lax.rsqrt(jnp.mean(x * x, axis=-1, keepdims=True) + eps) * scale


def causal_conv(s, kernel, bias):
    """``out_t = sum_j kernel[j] * s_{t - (K - 1) + j} + bias`` a channel,
    zeros before the sequence: ``s (B, T, C)``, ``kernel (K, C)``."""
    taps = kernel.shape[0]
    out = kernel[-1] * s + bias
    for back in range(1, taps):                # the token ``back`` places ago
        earlier = jnp.concatenate(
            [jnp.zeros_like(s[:, :back]), s[:, :-back]], axis=1)
        out = out + kernel[taps - 1 - back] * earlier
    return out


def recurrence(x, delta, a, b, c, skip):
    """``x (B, T, H, P)``, ``delta (B, T, H)``, ``a, skip (H,)``, ``b, c (B,
    T, G, N)`` -> ``y (B, T, H, P)``, one token at a time."""
    share = x.shape[2] // b.shape[2]

    def token(state, inputs):
        xt, dt, bt, ct = inputs                # (B, H, P), (B, H), (B, G, N)
        bt, ct = jnp.repeat(bt, share, axis=1), jnp.repeat(ct, share, axis=1)
        state = (jnp.exp(dt * a)[..., None, None] * state
                 + (dt[..., None] * xt)[..., None] * bt[:, :, None, :])
        return state, jnp.sum(state * ct[:, :, None, :], axis=-1) + (
            skip[:, None] * xt)

    zero = jnp.zeros(x.shape[:1] + x.shape[2:] + b.shape[-1:], x.dtype)
    _, y = lax.scan(token, zero, tuple(jnp.moveaxis(v, 1, 0)
                                       for v in (x, delta, b, c)))
    return jnp.moveaxis(y, 0, 1)


def gated_norm(y, z, scale, groups, eps):
    """``scale * GroupRMS(y * silu(z))``: the gate first, then the mean of
    squares over each of the ``groups`` equal runs of channels."""
    gated = y * jax.nn.silu(z)
    runs = gated.reshape(gated.shape[:-1] + (groups, -1))
    normed = runs * lax.rsqrt(
        jnp.mean(runs * runs, axis=-1, keepdims=True) + eps)
    return normed.reshape(gated.shape) * scale


def mamba2(p, u, sizes):
    bsz, t, _ = u.shape
    h, g, n = (sizes["mamba_heads"], sizes["mamba_groups"],
               sizes["mamba_state"])
    inner = p["out_proj"]["kernel"].shape[0]
    projected = u @ p["in_proj"]["kernel"]
    z, xbc, dt = (projected[..., :inner], projected[..., inner:-h],
                  projected[..., -h:])
    xbc = jax.nn.silu(causal_conv(xbc, p["conv_kernel"], p["conv_bias"]))
    x = xbc[..., :inner].reshape(bsz, t, h, inner // h)
    b = xbc[..., inner:inner + g * n].reshape(bsz, t, g, n)
    c = xbc[..., inner + g * n:].reshape(bsz, t, g, n)
    y = recurrence(x, jax.nn.softplus(dt + p["dt_bias"]), -jnp.exp(p["A_log"]),
                   b, c, p["D"])
    return gated_norm(y.reshape(bsz, t, inner), z, p["norm_scale"], g,
                      sizes["eps"]) @ p["out_proj"]["kernel"]


def attention(q, k, v):
    """``q (B, T, H, D)``, ``k, v (B, T, G, D)``, ``G`` dividing ``H`` ->
    ``(B, T, H, D)``: causal softmax attention over every key."""
    b, t, heads, dim = q.shape
    share = heads // k.shape[2]
    size = QUERY_BLOCK if t % QUERY_BLOCK == 0 else t
    keys = jnp.arange(t)[None, :]

    def one_head(args):
        h, qh = args                                       # qh (B, T, D)
        kh = jnp.take(k, h // share, axis=2)
        vh = jnp.take(v, h // share, axis=2)

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


def gqa(p, u, sizes):
    b, t, _ = u.shape
    dim = sizes["head_dim"]
    q = (u @ p["q"]["kernel"]).reshape(b, t, -1, dim)
    k = (u @ p["k"]["kernel"]).reshape(b, t, -1, dim)
    v = (u @ p["v"]["kernel"]).reshape(b, t, -1, dim)
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


def relu2_mlp(f, up, down):
    return jnp.square(jax.nn.relu(f @ up)) @ down


def expert_layer(p, f, weights, first):
    """``sum_i weights[..., first + i] * E_i(f)`` over the held experts plus
    the shared expert, a block of rows at a time."""
    count, d = p["w_up"].shape[0], f.shape[-1]
    rows = f.reshape(-1, d)
    w_held = weights.reshape(-1, weights.shape[-1])[:, first:first + count]
    size = ROW_BLOCK if rows.shape[0] % ROW_BLOCK == 0 else rows.shape[0]
    shared = p["shared"]

    def one_block(block):
        r, w = block                                   # (size, D), (size, count)

        def add_expert(acc, expert):
            wu, wd, wi = expert                        # wi (size,): 0 unchosen
            return acc + wi[:, None] * relu2_mlp(r, wu, wd), None

        out, _ = lax.scan(
            add_expert,
            relu2_mlp(r, shared["up"]["kernel"], shared["down"]["kernel"]),
            (p["w_up"], p["w_down"], w.T))
        return out

    out = lax.map(one_block, (rows.reshape(-1, size, d),
                              w_held.reshape(-1, size, count)))
    return out.reshape(f.shape)


def block(p, bias, x, kind, sizes):
    """``bias``: the block's selection-bias buffer, ``None`` where it has no
    router."""
    eps = sizes["eps"]
    if kind == MAMBA:
        return x + mamba2(p["mixer"], rms(x, p["ln1"]["scale"], eps), sizes)
    if kind == ATTENTION:
        return x + gqa(p["attn"], rms(x, p["ln1"]["scale"], eps), sizes)
    f = rms(x, p["ln2"]["scale"], eps)
    weights = route(p["moe"]["router"], lax.stop_gradient(bias), f, sizes)
    if not sizes["train_router"]:
        weights = lax.stop_gradient(weights)
    return x + expert_layer(p["moe"], f, weights, sizes["held_first"])


def head_cross_entropy(h, scale, head, targets, eps):
    """Mean over all positions of the cross entropy of ``rms(h) @ head``
    against ``targets``, in blocks of rows."""
    rows = h.reshape(-1, h.shape[-1])
    labels = targets.reshape(-1)
    size = ROW_BLOCK if rows.shape[0] % ROW_BLOCK == 0 else rows.shape[0]

    def block_sum(args):
        r, lab = args
        logp = jax.nn.log_softmax(rms(r, scale, eps) @ head, axis=-1)
        return -jnp.sum(jnp.take_along_axis(logp, lab[:, None], axis=-1))

    sums = lax.map(block_sum, (rows.reshape(-1, size, rows.shape[-1]),
                               labels.reshape(-1, size)))
    return jnp.sum(sums) / rows.shape[0]


def hidden(sizes, params, model_state, tokens):
    """``tokens (B, T)`` -> the last block's output ``(B, T, D)``."""
    x = params["tok"]["embedding"][tokens]
    for i, kind in enumerate(sizes["kinds"]):
        bias = (model_state["buffers"][f"block_{i}"]["moe"]["selection_bias"]
                if kind == EXPERTS else None)
        x = block(params[f"block_{i}"], bias, x, kind, sizes)
    return x


def logits(sizes, params, model_state, tokens):
    x = hidden(sizes, params, model_state, tokens)
    return rms(x, params["ln_f"]["scale"], sizes["eps"]) @ (
        params["lm_head"]["kernel"])


def loss(sizes, params, model_state, tokens):
    """``tokens (B, T + 1)`` -> the scalar training loss."""
    params = jax.tree_util.tree_map(lambda a: a.astype(jnp.float32), params)
    x = hidden(sizes, params, model_state, tokens[:, :-1])
    return head_cross_entropy(x, params["ln_f"]["scale"],
                              params["lm_head"]["kernel"], tokens[:, 1:],
                              sizes["eps"])
