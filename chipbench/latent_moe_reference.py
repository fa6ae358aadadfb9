"""The plain reference of the family ``latent_moe``: the whole forward pass
and loss of a DeepSeek-V3-style decoder in ``jax.numpy`` and f32, following
the published equations (arXiv:2405.04434 section 2.1 for the latent
attention, arXiv:2412.19437 sections 2.1.2 and 2.2 for the routing and the
multi-token-prediction module).  It imports nothing of ``bluefog_tpu``: no
kernel, no flax module, no bf16 cast, no sort.  It reads the parameter tree
the system trains (the names are the only thing the two share).

Per token ``x`` at position ``t`` (no bias anywhere):

- block: ``h = x + MLA(rms(x))``, ``out = h + FFN(rms(h))``; the FFN is a
  gated SiLU MLP in the leading dense blocks and the expert layer after;
- MLA: ``cq = rms(W_dq x)``; ``q = W_uq cq`` per head ``[q_nope; q_rope]``;
  ``[ckv; k_rope] = W_dkv x``; ``[k_nope; v] = W_ukv rms(ckv)`` per head;
  rotary (pairs ``(0, 1), (2, 3), ...`` as stored) on ``q_rope`` and on the
  one ``k_rope`` a token that every head shares; causal softmax of
  ``q . k / sqrt(nope + rope)``; ``W_o`` over the heads' outputs;
- expert layer: ``s = sigmoid(W_g x)``; the chosen set is the ``top_k``
  largest of ``s + b``; ``g_i = scale * s_i / sum of the chosen s``;
  ``shared(x) + sum over the chosen i that this chip holds of g_i E_i(x)``.
  Dense by mask: every held expert sees every token, weighted 0 where it was
  not chosen.  What the absent experts would add is left out (the chip's
  share of the deployment, as in the system);
- MTP: ``h' = M [rms(Emb(t_{i+1})); rms(h_i)]``, ``h_i`` the trunk's output
  before its final norm, one more expert block, the trunk's own embedding
  and head; it predicts ``t_{i+2}``;
- loss: ``CE(main, t_{i+1}) + mtp_weight * CE(mtp, t_{i+2})``, each a mean
  over the ``B * T`` positions.

Attention goes a head at a time and the head's cross entropy in blocks of
rows (``lax.map``), so that 8,192 tokens fit beside the parameters.

``sizes`` is what the shapes do not say: ``heads``, ``qk_nope``,
``qk_rope``, ``rope_theta``, ``eps``, ``top_k``, ``scale``, ``held_first``,
``mtp_weight``.
"""

import jax
import jax.numpy as jnp
from jax import lax

ROW_BLOCK = 1024    # rows of the head's logits computed at once


def rms(x, scale, eps):
    return x * lax.rsqrt(jnp.mean(x * x, axis=-1, keepdims=True) + eps) * scale


def rotary(x, positions, theta):
    """``x (..., T, R)``: pair ``i`` = elements ``2i, 2i + 1``, as a complex
    number turned by ``position * theta ** (-2i / R)``."""
    r = x.shape[-1]
    freq = theta ** (-jnp.arange(r // 2, dtype=jnp.float32) * 2.0 / r)
    angle = positions[:, None].astype(jnp.float32) * freq      # (T, R/2)
    z = lax.complex(x[..., 0::2], x[..., 1::2]) * jnp.exp(1j * angle)
    return jnp.stack([z.real, z.imag], axis=-1).reshape(x.shape)


def causal_attention(q, k, v):
    """``q, k (B, T, H, Dqk)``, ``v (B, T, H, Dv)`` -> ``(B, T, H, Dv)``."""
    t = q.shape[1]
    mask = jnp.tril(jnp.ones((t, t), bool))

    def one_head(qkv):
        qh, kh, vh = qkv                                   # (B, T, D)
        scores = jnp.einsum("bqd,bkd->bqk", qh, kh) / jnp.sqrt(
            jnp.float32(qh.shape[-1]))
        p = jax.nn.softmax(jnp.where(mask, scores, -jnp.inf), axis=-1)
        return jnp.einsum("bqk,bkd->bqd", p, vh)

    by_head = lax.map(one_head, tuple(
        jnp.moveaxis(a, 2, 0) for a in (q, k, v)))
    return jnp.moveaxis(by_head, 0, 2)


def mla(p, x, positions, sizes):
    b, t, _ = x.shape
    h, nope, rope = sizes["heads"], sizes["qk_nope"], sizes["qk_rope"]
    eps, theta = sizes["eps"], sizes["rope_theta"]
    cq = rms(x @ p["q_down"]["kernel"], p["q_norm"]["scale"], eps)
    q = (cq @ p["q_up"]["kernel"]).reshape(b, t, h, nope + rope)
    down = x @ p["kv_down"]["kernel"]
    rank = down.shape[-1] - rope
    ckv = rms(down[..., :rank], p["kv_norm"]["scale"], eps)
    kv = (ckv @ p["kv_up"]["kernel"]).reshape(b, t, h, -1)
    k_nope, v = kv[..., :nope], kv[..., nope:]
    q_rope = jnp.moveaxis(rotary(jnp.moveaxis(q[..., nope:], 1, 2),
                                 positions, theta), 2, 1)
    k_rope = rotary(down[..., rank:], positions, theta)       # (B, T, rope)
    q = jnp.concatenate([q[..., :nope], q_rope], axis=-1)
    k = jnp.concatenate(
        [k_nope, jnp.broadcast_to(k_rope[:, :, None], (b, t, h, rope))],
        axis=-1)
    out = causal_attention(q, k, v)
    return out.reshape(b, t, -1) @ p["o"]["kernel"]


def gated_mlp(p, x):
    return (jax.nn.silu(x @ p["gate"]["kernel"]) * (x @ p["up"]["kernel"])
            ) @ p["down"]["kernel"]


def expert_layer(p, bias, x, sizes):
    k, first = sizes["top_k"], sizes["held_first"]
    count = p["w_gate"].shape[0]
    s = jax.nn.sigmoid(x @ p["router"])                       # (B, T, E)
    steer = s + bias
    kth = lax.top_k(steer, k)[0][..., -1:]
    chosen = steer >= kth
    g = sizes["scale"] * s * chosen / jnp.sum(s * chosen, -1, keepdims=True)

    def add_expert(acc, expert):
        wg, wu, wd, gi = expert                     # gi (B, T): 0 if unchosen
        out = (jax.nn.silu(x @ wg) * (x @ wu)) @ wd
        return acc + gi[..., None] * out, None

    g_held = jnp.moveaxis(g[..., first:first + count], -1, 0)
    routed, _ = lax.scan(add_expert, jnp.zeros_like(x),
                         (p["w_gate"], p["w_up"], p["w_down"], g_held))
    return gated_mlp(p["shared"], x) + routed


def block(p, buffers, x, positions, sizes):
    h = x + mla(p["attn"], rms(x, p["ln1"]["scale"], sizes["eps"]),
                positions, sizes)
    y = rms(h, p["ln2"]["scale"], sizes["eps"])
    if "moe" in p:
        return h + expert_layer(p["moe"], buffers["moe"]["selection_bias"],
                                y, sizes)
    return h + gated_mlp(p["mlp"], y)


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


def loss(sizes, params, model_state, tokens):
    """``tokens (B, T + 2)`` -> the scalar training loss."""
    params = jax.tree_util.tree_map(lambda a: a.astype(jnp.float32), params)
    buffers = model_state["buffers"]
    t = tokens.shape[1] - 2
    positions = jnp.arange(t)
    eps = sizes["eps"]
    embedding = params["tok"]["embedding"]
    head = params["lm_head"]["kernel"]
    x = embedding[tokens[:, :t]]
    i = 0
    while f"block_{i}" in params:
        name = f"block_{i}"
        x = block(params[name], buffers.get(name), x, positions, sizes)
        i += 1
    main = head_cross_entropy(x, params["ln_f"]["scale"], head,
                              tokens[:, 1:t + 1], eps)
    merged = jnp.concatenate(
        [rms(embedding[tokens[:, 1:t + 1]], params["mtp_enorm"]["scale"], eps),
         rms(x, params["mtp_hnorm"]["scale"], eps)], axis=-1)
    z = block(params["mtp_block"], buffers["mtp_block"],
              merged @ params["mtp_proj"]["kernel"], positions, sizes)
    mtp = head_cross_entropy(z, params["mtp_norm"]["scale"], head,
                             tokens[:, 2:], eps)
    return main + sizes["mtp_weight"] * mtp
