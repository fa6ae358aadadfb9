"""The plain reference of the family ``gqa_moe``: the whole forward pass and
loss of a SmallThinker-style decoder (arXiv:2507.20984; the architecture of
PowerInfer/SmallThinker-21BA3B-Instruct) in ``jax.numpy`` and f32.  It
imports nothing of ``bluefog_tpu``: no kernel, no flax module, no bf16 cast,
no sort, no grouped matmul.  It reads the parameter tree the system trains
(the names are the only thing the two share).

One block over ``x (B, T, D)``, no bias anywhere:

- ``y = rms_1(x)``;
- **router first**, on ``y`` (the normed block input that the attention reads
  too): ``l = y W_r`` over all the router's outputs, ``S`` the ``top_k``
  largest ``l``, ``p_i = exp(l_i) / sum_{j in S} exp(l_j)`` for ``i`` in
  ``S`` and 0 outside (a softmax over all outputs renormalised over ``S`` is
  the same ``p``);
- attention: ``q = y W_q`` in ``H`` heads, ``k = y W_k`` and ``v = y W_v`` in
  ``G`` heads, all ``head_dim`` wide; query head ``h`` reads key/value head
  ``h // (H / G)``; scores ``q . k / sqrt(head_dim)``, causal.  A
  ``window_rotary_attention`` layer turns ``q`` and ``k`` by rotary over the
  whole head (pair ``i`` is elements ``i`` and ``i + head_dim / 2``, angle
  ``position * theta ** (-2i / head_dim)``) and key ``s`` is visible from
  ``t`` only while ``t - window < s <= t``; a ``full_attention`` layer turns
  nothing (**no positional encoding**) and sees every ``s <= t``;
  ``h = x + concat_h(a_h) W_o``;
- experts on ``z = rms_2(h)`` with the routing made from ``y``:
  ``out = h + sum over the chosen i that this chip holds of
  p_i W_down,i (relu(W_gate,i z) * W_up,i z)``.  Dense by mask: every held
  expert sees every token, weighted 0 where it was not chosen.  What the
  absent experts would add is left out (the chip's share of the deployment,
  as in the system).  Where ``train_router`` is false, ``p`` is a constant
  of the backward pass: the router gets no gradient, and none flows through
  it into ``y``.

Logits ``rms_f(x) W_head``, the loss the mean cross entropy over the
``B * T`` positions.  Attention goes a head and a block of queries at a time
and the cross entropy in blocks of rows (``lax.map``), so that 16,384 tokens
fit beside the parameters.

Every product is a plain ``@`` or ``einsum`` on f32 operands; the caller
computes it under ``jax.default_matmul_precision("highest")`` (the harness's
``reference.model_loss_error`` and the tests do), without which a TPU
multiplies f32 in bf16 passes.

``sizes`` is what the shapes do not say: ``kinds`` (the layer type of each
block), ``head_dim``, ``window``, ``rope_theta``, ``eps``, ``top_k``,
``held_first``, ``train_router``.
"""

import jax
import jax.numpy as jnp
from jax import lax

ROW_BLOCK = 1024      # rows of the head's logits computed at once
QUERY_BLOCK = 512     # queries of one head scored against every key at once

# layer type -> (turned by rotary, windowed)
LAYERS = {"full_attention": (False, False),
          "window_rotary_attention": (True, True)}


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


def attention(q, k, v, window):
    """``q (B, T, H, D)``, ``k, v (B, T, G, D)``, ``G`` dividing ``H`` ->
    ``(B, T, H, D)``: causal softmax attention, under a band of ``window``
    keys where it is not ``None``."""
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
            seen = keys <= rows[:, None]
            if window is not None:
                seen &= keys > rows[:, None] - window
            p = jax.nn.softmax(jnp.where(seen, scores, -jnp.inf), axis=-1)
            return jnp.einsum("bqk,bkd->bqd", p, vh)

        blocks = lax.map(one_block, (
            jnp.arange(t).reshape(-1, size),
            jnp.moveaxis(qh.reshape(b, -1, size, dim), 1, 0)))
        return jnp.moveaxis(blocks, 0, 1).reshape(b, t, dim)

    by_head = lax.map(one_head, (jnp.arange(heads), jnp.moveaxis(q, 2, 0)))
    return jnp.moveaxis(by_head, 0, 2)


def gqa(p, y, positions, kind, sizes):
    b, t, _ = y.shape
    dim = sizes["head_dim"]
    turned, windowed = LAYERS[kind]
    q = (y @ p["q"]["kernel"]).reshape(b, t, -1, dim)
    k = (y @ p["k"]["kernel"]).reshape(b, t, -1, dim)
    v = (y @ p["v"]["kernel"]).reshape(b, t, -1, dim)
    if turned:
        q = rotary(q, positions, sizes["rope_theta"])
        k = rotary(k, positions, sizes["rope_theta"])
    out = attention(q, k, v, sizes["window"] if windowed else None)
    return out.reshape(b, t, -1) @ p["o"]["kernel"]


def route(router, y, top_k):
    """``p (B, T, E)``: the softmax over the ``top_k`` largest logits of
    ``y @ router``, 0 for the others."""
    logits = y @ router
    kth = lax.top_k(logits, top_k)[0][..., -1:]
    chosen = logits >= kth
    e = jnp.exp(logits - jnp.max(logits, axis=-1, keepdims=True)) * chosen
    return e / jnp.sum(e, axis=-1, keepdims=True)


def reglu(gate, up):
    return jax.nn.relu(gate) * up


def held_experts(p, z, weights, first):
    """``sum_i weights[..., first + i] * E_i(z)`` over the held experts."""
    count = p["w_gate"].shape[0]

    def add_expert(acc, expert):
        wg, wu, wd, gi = expert                     # gi (B, T): 0 if unchosen
        return acc + gi[..., None] * (reglu(z @ wg, z @ wu) @ wd), None

    g_held = jnp.moveaxis(weights[..., first:first + count], -1, 0)
    routed, _ = lax.scan(add_expert, jnp.zeros_like(z),
                         (p["w_gate"], p["w_up"], p["w_down"], g_held))
    return routed


def block(p, x, positions, kind, sizes):
    eps = sizes["eps"]
    y = rms(x, p["ln1"]["scale"], eps)
    weights = route(p["moe"]["router"], y, sizes["top_k"])   # before attention
    if not sizes["train_router"]:
        weights = lax.stop_gradient(weights)
    h = x + gqa(p["attn"], y, positions, kind, sizes)
    z = rms(h, p["ln2"]["scale"], eps)
    return h + held_experts(p["moe"], z, weights, sizes["held_first"])


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


def loss(sizes, params, tokens):
    """``tokens (B, T + 1)`` -> the scalar training loss."""
    params = jax.tree_util.tree_map(lambda a: a.astype(jnp.float32), params)
    t = tokens.shape[1] - 1
    positions = jnp.arange(t)
    x = params["tok"]["embedding"][tokens[:, :t]]
    for i, kind in enumerate(sizes["kinds"]):
        x = block(params[f"block_{i}"], x, positions, kind, sizes)
    return head_cross_entropy(x, params["ln_f"]["scale"],
                              params["lm_head"]["kernel"], tokens[:, 1:],
                              sizes["eps"])
