"""The plain reference of the family ``linear_latent_moe``: the whole forward
pass and loss of a linear-attention / latent-attention hybrid with routed
experts (the language model of Ling-3.0-flash) in ``jax.numpy`` and f32,
following the published equations: Kimi Delta Attention, arXiv:2510.26692
section 3 (and the layer of ``fla/layers/kda.py``); latent attention,
arXiv:2405.04434 section 2.1, without a query bottleneck; the routing,
arXiv:2412.19437 section 2.1.2 with node-limited groups.  It imports nothing
of ``bluefog_tpu``: no kernel, no flax module, no bf16 cast, no chunked
form, no sort.  It reads the parameter tree the system trains (the names are
the only thing the two share) and the same share of the heads and of the
experts: what the absent ones would add to a token is left out.

Block ``i`` with the kind ``sizes["kinds"][i]``: ``h = x + mixer(rms(x))``,
``out = h + FFN(rms(h))``; the FFN is a gated SiLU MLP in the leading dense
blocks and the expert layer after.

- ``kda``, per head of width ``d``: ``q~, k~, v = silu(conv(W_q x)),
  silu(conv(W_k x)), silu(conv(W_v x))`` (causal, depthwise, ``x_t`` from
  ``x_{t-3} .. x_t``); ``q = q~ / |q~| / sqrt(d)``, ``k = k~ / |k~|``;
  ``beta = sigmoid(W_b x)`` a head; ``g = lower sigmoid(exp(A_log_h) (W_f x
  + dt_bias))`` a channel; **the recurrence, one token at a time**
  (``lax.scan``) from ``S_0 = 0``:
  ``S_t = (I - beta_t k_t k_t^T) Diag(exp g_t) S_{t-1} + beta_t k_t v_t^T``,
  ``o_t = S_t^T q_t``; output ``W_o [rms_head(o) sigmoid(W_g x)_h]``;
- ``latent_attention``: ``q = W_q x`` per head ``[q_nope; q_rope]``;
  ``[c; k_r] = W_dkv x``; ``[k_nope; v] = W_ukv rms(c)`` per head; ``q`` and
  ``[k_nope; k_r]`` each through an RMSNorm over the head's whole width;
  rotary (pairs ``(0, 1), (2, 3), ...`` as stored) on the last ``rope``
  elements of both; causal softmax of ``q . k / sqrt(nope + rope)``; the
  same head-wise gate; ``W_o``;
- expert layer: ``s = sigmoid(W_r x)``; for selection only ``s + b``; the
  experts in ``n_group`` groups in index order, a group's score the sum of
  its two largest, the ``topk_group`` best groups kept (ties to the lower
  index), the ``top_k`` largest among theirs chosen; ``g_i = scale * s_i /
  sum of the chosen s``; ``shared(x) + sum over the chosen i that this chip
  holds of g_i E_i(x)``.  Dense by mask: every held expert sees every
  token, weighted 0 where it was not chosen;
- loss: the mean cross entropy of ``rms(x) W_head`` against the next token
  over the ``B * T`` positions.

The recurrence keeps one state a head and writes none out, attention goes
a head at a time and the cross entropy in blocks of rows (``lax.map``), so
that 8,192 tokens fit beside the parameters.  Every product is a plain
``@`` or ``einsum`` on f32 operands; the caller computes it under
``jax.default_matmul_precision("highest")`` (the harness's
``reference.model_loss_error`` and the tests do).

``sizes`` is what the shapes do not say: ``kinds``, ``head_dim`` (KDA),
``lower_bound``, ``qk_nope``, ``qk_rope``, ``rope_theta``, ``eps``,
``top_k``, ``scale``, ``n_group``, ``topk_group``, ``held_first``.
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


def causal_conv(x, kernel):
    """Depthwise: ``out_t = sum_j kernel[j] * x_{t - (K - 1) + j}`` with
    ``x`` zero before the sequence.  ``x (B, T, C)``, ``kernel (K, C)``."""
    taps, t = kernel.shape[0], x.shape[1]
    padded = jnp.pad(x, ((0, 0), (taps - 1, 0), (0, 0)))
    return sum(kernel[j] * padded[:, j:j + t] for j in range(taps))


def delta_rule(q, k, v, g, beta):
    """The recurrence, every head at once: ``q, k, g (B, T, H, d_k)``,
    ``v (B, T, H, d_v)``, ``beta (B, T, H)`` -> ``o (B, T, H, d_v)``."""

    def token(state, inputs):
        qt, kt, vt, gt, bt = inputs                    # (B, H, d), (B, H)
        state = jnp.exp(gt)[..., None] * state         # (B, H, d_k, d_v)
        seen = jnp.einsum("bhk,bhkv->bhv", kt, state)
        state = state + (bt[..., None] * kt)[..., None] * (
            vt - seen)[..., None, :]
        return state, jnp.einsum("bhk,bhkv->bhv", qt, state)

    zero = jnp.zeros(q.shape[:1] + q.shape[2:] + v.shape[3:], jnp.float32)
    _, o = lax.scan(token, zero, tuple(
        jnp.moveaxis(a, 1, 0) for a in (q, k, v, g, beta)))
    return jnp.moveaxis(o, 0, 1)


def kda(p, x, sizes):
    b, t, _ = x.shape
    d, lower = sizes["head_dim"], sizes["lower_bound"]

    def heads(a):
        return a.reshape(b, t, -1, d)

    q, k, v = (heads(jax.nn.silu(causal_conv(x @ p[name]["kernel"],
                                             p[f"{name}_conv"])))
               for name in "qkv")
    q = q / jnp.sqrt(jnp.sum(q * q, -1, keepdims=True) + 1e-6) / jnp.sqrt(
        jnp.float32(d))
    k = k / jnp.sqrt(jnp.sum(k * k, -1, keepdims=True) + 1e-6)
    beta = jax.nn.sigmoid(x @ p["b"]["kernel"])                 # (B, T, H)
    g = lower * jax.nn.sigmoid(
        jnp.exp(p["A_log"])[:, None]
        * heads(x @ p["f"]["kernel"] + p["dt_bias"]))
    o = rms(delta_rule(q, k, v, g, beta), p["o_norm"]["scale"], sizes["eps"])
    gate = jax.nn.sigmoid(x @ p["head_gate"]["kernel"])
    return (o * gate[..., None]).reshape(b, t, -1) @ p["o"]["kernel"]


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
    nope, rope = sizes["qk_nope"], sizes["qk_rope"]
    eps, theta = sizes["eps"], sizes["rope_theta"]
    q = (x @ p["q"]["kernel"]).reshape(b, t, -1, nope + rope)
    h = q.shape[2]
    down = x @ p["kv_down"]["kernel"]
    rank = down.shape[-1] - rope
    kv = (rms(down[..., :rank], p["kv_norm"]["scale"], eps)
          @ p["kv_up"]["kernel"]).reshape(b, t, h, -1)
    k = jnp.concatenate(
        [kv[..., :nope],
         jnp.broadcast_to(down[:, :, None, rank:], (b, t, h, rope))], axis=-1)
    q = rms(q, p["q_head_norm"]["scale"], eps)
    k = rms(k, p["k_head_norm"]["scale"], eps)

    def turned(a):      # rotary on the last `rope` elements of (B, T, H, .)
        part = jnp.moveaxis(rotary(jnp.moveaxis(a[..., nope:], 1, 2),
                                   positions, theta), 2, 1)
        return jnp.concatenate([a[..., :nope], part], axis=-1)

    out = causal_attention(turned(q), turned(k), kv[..., nope:])
    gate = jax.nn.sigmoid(x @ p["head_gate"]["kernel"])
    return (out * gate[..., None]).reshape(b, t, -1) @ p["o"]["kernel"]


def gated_mlp(p, x):
    return (jax.nn.silu(x @ p["gate"]["kernel"]) * (x @ p["up"]["kernel"])
            ) @ p["down"]["kernel"]


def chosen_experts(steer, top_k, n_group, topk_group):
    """The 0/1 mask ``(..., E)`` of the group-limited top-k over ``steer``;
    ties go to the lower index (a stable descending order)."""

    def best(values, count):
        order = jnp.argsort(-values, axis=-1, stable=True)[..., :count]
        return jnp.any(order[..., None] == jnp.arange(values.shape[-1]),
                       axis=-2)

    if n_group > 1:
        grouped = steer.reshape(steer.shape[:-1] + (n_group, -1))
        two = -jnp.sort(-grouped, axis=-1)[..., :2]
        kept = best(two.sum(-1), topk_group)
        steer = jnp.where(kept[..., None], grouped, -jnp.inf).reshape(
            steer.shape)
    return best(steer, top_k)


def expert_layer(p, bias, x, sizes):
    first, count = sizes["held_first"], p["w_gate"].shape[0]
    s = jax.nn.sigmoid(x @ p["router"])                       # (B, T, E)
    chosen = chosen_experts(s + bias, sizes["top_k"], sizes["n_group"],
                            sizes["topk_group"])
    g = sizes["scale"] * s * chosen / jnp.sum(s * chosen, -1, keepdims=True)

    def add_expert(acc, expert):
        wg, wu, wd, gi = expert                     # gi (B, T): 0 if unchosen
        out = (jax.nn.silu(x @ wg) * (x @ wu)) @ wd
        return acc + gi[..., None] * out, None

    g_held = jnp.moveaxis(g[..., first:first + count], -1, 0)
    routed, _ = lax.scan(add_expert, jnp.zeros_like(x),
                         (p["w_gate"], p["w_up"], p["w_down"], g_held))
    return gated_mlp(p["shared"], x) + routed


def block(p, buffers, kind, x, positions, sizes):
    y = rms(x, p["ln1"]["scale"], sizes["eps"])
    h = x + (kda(p["attn"], y, sizes) if kind == "kda"
             else mla(p["attn"], y, positions, sizes))
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
    """``tokens (B, T + 1)`` -> the scalar training loss."""
    params = jax.tree_util.tree_map(lambda a: a.astype(jnp.float32), params)
    buffers = model_state["buffers"]
    t = tokens.shape[1] - 1
    positions = jnp.arange(t)
    x = params["tok"]["embedding"][tokens[:, :t]]
    for i, kind in enumerate(sizes["kinds"]):
        name = f"block_{i}"
        x = block(params[name], buffers.get(name), kind, x, positions, sizes)
    return head_cross_entropy(x, params["ln_f"]["scale"],
                              params["lm_head"]["kernel"], tokens[:, 1:],
                              sizes["eps"])
