"""The plain reference of the family ``sambay``: the whole forward pass and
loss of a decoder-hybrid-decoder model (SambaY, arXiv:2507.06607: the
architecture of Phi-4-mini-flash-reasoning) in ``jax.numpy`` and f32,
following the published equations (Mamba-1's selective state space,
arXiv:2312.00752 section 3; differential attention, arXiv:2410.05258
section 2).  It imports nothing of ``bluefog_tpu``: no kernel, no flax
module, no bf16 cast, no chunked scan.  It reads the parameter tree the
system trains (the names are the only thing the two share).

Published layer ``l``; every block is ``x <- x + mixer_l(LN(x))``,
``x <- x + MLP(LN(x))`` with biased LayerNorms and the gated SiLU MLP
``down(silu(gate(y)) * up(y))``.  **No positional encoding.**  Mixers:

- ``mamba``: ``[xi; z] = W_in y``; ``x = silu(conv(xi) + b_c)``, the
  convolution causal, depthwise, ``x_t`` from ``xi_{t-3} .. xi_t``;
  ``[dr; B; C] = W_x x``; ``dt = softplus(W_dt dr + b_dt)``;
  ``A = -exp(A_log)``; ``h_t = exp(dt_t A) * h_{t-1} + (dt_t x_t) B_t``
  from ``h_0 = 0``, one token at a time (``lax.scan``);
  ``m_t = h_t C_t + D x_t``; output ``W_out (m * silu(z))``.  ``m`` is the
  memory a later gated memory unit reads;
- ``diff_attention`` (``window`` keys or all): ``[q; k; v] = W_qkv y + b``
  in heads of ``head_dim``.  Query pair ``p`` is heads ``(2p, 2p + 1)``,
  key pair ``g = p // (P / G)`` likewise (``P`` query and ``G`` key pairs;
  ``p // 2`` at the published 40 and 20 heads), the pair's value its two
  value heads side by side.  ``A_j = softmax(q_{p,j} k_{g,j}^T / sqrt(head_dim) + mask)``;
  ``o_p = A_1 V_g - lam * A_2 V_g``;
  ``lam = exp(lq1 . lk1) - exp(lq2 . lk2) + lam_init``,
  ``lam_init = 0.8 - 0.6 exp(-0.3 l)``; ``o_p <- rms(o_p) * (1 - lam_init)``
  (one scale of ``2 head_dim`` shared by the pairs); output
  ``W_o concat_p(o_p) + b_o``.  Key ``s`` is visible from ``t`` iff
  ``t - window < s <= t``.  ``k`` and ``v`` are what a later cross-attention
  layer reads;
- ``gmu``: ``W_out (m * silu(W_in y))`` with ``m`` the last Mamba layer's;
- ``cross_diff_attention``: ``q = W_q y + b`` alone, ``k`` and ``v`` the last
  full attention layer's; the same two maps, its own ``lam``, sub-norm and
  ``W_o``; full causal mask.

Logits ``LN_f(x) Emb^T`` (the head is the embedding), the loss the mean
cross entropy over the ``B * T`` positions.  Attention goes a head at a time
and the cross entropy in blocks of rows (``lax.map``), so that 8,192 tokens
fit beside the parameters.

Every product is a plain ``@`` or ``einsum`` on f32 operands; the caller
computes it under ``jax.default_matmul_precision("highest")`` (the
harness's ``reference.model_loss_error`` and the tests do), without which a
TPU multiplies f32 in bf16 passes.

``sizes`` is what the shapes do not say: ``kinds`` (the mixer of each
block), ``first_layer`` (the published index of block 0), ``head_dim``,
``window``, ``d_state``, ``eps``.
"""

import jax
import jax.numpy as jnp
from jax import lax

ROW_BLOCK = 1024    # rows of the head's logits computed at once


def layer_norm(x, p, eps):
    mean = jnp.mean(x, axis=-1, keepdims=True)
    var = jnp.mean((x - mean) ** 2, axis=-1, keepdims=True)
    return (x - mean) * lax.rsqrt(var + eps) * p["scale"] + p["bias"]


def gated_mlp(p, x):
    return (jax.nn.silu(x @ p["gate"]["kernel"]) * (x @ p["up"]["kernel"])
            ) @ p["down"]["kernel"]


def causal_conv(xi, kernel, bias):
    """Depthwise: ``out_t = sum_j kernel[j] * xi_{t - (K - 1) + j} + bias``
    with ``xi`` zero before the sequence.  ``xi (B, T, C)``, ``kernel
    (K, C)``."""
    taps, t = kernel.shape[0], xi.shape[1]
    padded = jnp.pad(xi, ((0, 0), (taps - 1, 0), (0, 0)))
    return sum(kernel[j] * padded[:, j:j + t] for j in range(taps)) + bias


def recurrence(x, dt, a, b, c, d):
    """The selective scan one token at a time.  ``x, dt (B, T, C)``,
    ``a (C, N)``, ``b, c (B, T, N)``, ``d (C,)`` -> ``m (B, T, C)``."""
    def step(h, inputs):
        x_t, dt_t, b_t, c_t = inputs                 # (B, C) x2, (B, N) x2
        h = (jnp.exp(dt_t[..., None] * a) * h
             + (dt_t * x_t)[..., None] * b_t[:, None, :])
        return h, jnp.sum(h * c_t[:, None, :], axis=-1) + d * x_t

    h0 = jnp.zeros(x.shape[:1] + a.shape, jnp.float32)
    _, m = lax.scan(step, h0, tuple(
        jnp.moveaxis(v, 1, 0) for v in (x, dt, b, c)))
    return jnp.moveaxis(m, 0, 1)


def mamba(p, y, sizes):
    """-> the mixer's output and the memory ``m`` (before the gate)."""
    n = sizes["d_state"]
    xz = y @ p["in_proj"]["kernel"]
    xi, z = jnp.split(xz, 2, axis=-1)
    x = jax.nn.silu(causal_conv(xi, p["conv_kernel"], p["conv_bias"]))
    dbc = x @ p["x_proj"]["kernel"]
    rank = dbc.shape[-1] - 2 * n
    dt = jax.nn.softplus(dbc[..., :rank] @ p["dt_proj"]["kernel"]
                         + p["dt_proj"]["bias"])
    m = recurrence(x, dt, -jnp.exp(p["A_log"]), dbc[..., rank:rank + n],
                   dbc[..., rank + n:], p["D"])
    return (m * jax.nn.silu(z)) @ p["out_proj"]["kernel"], m


def lambda_init(layer):
    return 0.8 - 0.6 * jnp.exp(-0.3 * layer)


def differential_maps(p, q, k, v, layer, window, eps):
    """``q (B, T, 2P, D)``, ``k, v (B, T, 2G, D)``, ``G`` dividing ``P`` ->
    ``(B, T, P * 2D)``: the two softmax maps of every pair, their
    difference, the sub-norm."""
    b, t, heads, dim = q.shape
    pairs = heads // 2
    rows, cols = jnp.arange(t)[:, None], jnp.arange(t)[None, :]
    mask = cols <= rows
    if window is not None:
        mask &= cols > rows - window
    lam_init = lambda_init(layer)
    lam = (jnp.exp(jnp.sum(p["lambda_q1"] * p["lambda_k1"]))
           - jnp.exp(jnp.sum(p["lambda_q2"] * p["lambda_k2"])) + lam_init)

    def one_pair(args):
        qp, kg, vg = args        # (2, B, T, D), (2, B, T, D), (B, T, 2D)
        def softmax_map(j):
            scores = jnp.einsum("bqd,bkd->bqk", qp[j], kg[j]) / jnp.sqrt(
                jnp.float32(dim))
            return jax.nn.softmax(jnp.where(mask, scores, -jnp.inf), axis=-1)
        o = (jnp.einsum("bqk,bkd->bqd", softmax_map(0), vg)
             - lam * jnp.einsum("bqk,bkd->bqd", softmax_map(1), vg))
        o = o * lax.rsqrt(jnp.mean(o * o, axis=-1, keepdims=True) + eps)
        return o * p["subln"]["scale"] * (1.0 - lam_init)

    q_pairs = jnp.moveaxis(q.reshape(b, t, pairs, 2, dim), (2, 3), (0, 1))
    k_pairs = jnp.moveaxis(k.reshape(b, t, -1, 2, dim), (2, 3), (0, 1))
    v_pairs = jnp.moveaxis(v.reshape(b, t, -1, 2 * dim), 2, 0)
    group = jnp.arange(pairs) // (pairs // k_pairs.shape[0])
    out = lax.map(one_pair, (q_pairs, k_pairs[group], v_pairs[group]))
    return jnp.moveaxis(out, 0, 2).reshape(b, t, pairs * 2 * dim)


def diff_attention(p, y, layer, window, sizes):
    """-> the mixer's output and the ``(k, v)`` a cross-attention reads."""
    b, t, width = y.shape
    dim = sizes["head_dim"]
    qkv = y @ p["qkv"]["kernel"] + p["qkv"]["bias"]
    kv_width = (qkv.shape[-1] - width) // 2
    q = qkv[..., :width].reshape(b, t, -1, dim)
    k = qkv[..., width:width + kv_width].reshape(b, t, -1, dim)
    v = qkv[..., width + kv_width:].reshape(b, t, -1, dim)
    o = differential_maps(p, q, k, v, layer, window, sizes["eps"])
    return o @ p["out"]["kernel"] + p["out"]["bias"], (k, v)


def cross_diff_attention(p, y, k, v, layer, sizes):
    b, t, _ = y.shape
    q = (y @ p["q"]["kernel"] + p["q"]["bias"]).reshape(
        b, t, -1, sizes["head_dim"])
    o = differential_maps(p, q, k, v, layer, None, sizes["eps"])
    return o @ p["out"]["kernel"] + p["out"]["bias"]


def trunk(sizes, params, tokens):
    """``tokens (B, T)`` -> the last block's output ``(B, T, D)``."""
    eps = sizes["eps"]
    x = params["tok"]["embedding"][tokens]
    memory = keys_values = None
    for i, kind in enumerate(sizes["kinds"]):
        p = params[f"block_{i}"]
        layer = sizes["first_layer"] + i
        y = layer_norm(x, p["ln1"], eps)
        if kind == "mamba":
            a, memory = mamba(p["mamba"], y, sizes)
        elif kind in ("diff_attention", "diff_attention_window"):
            window = sizes["window"] if kind.endswith("window") else None
            a, kv = diff_attention(p["attn"], y, layer, window, sizes)
            if window is None:
                keys_values = kv
        elif kind == "gmu":
            g = p["gmu"]
            a = (memory * jax.nn.silu(y @ g["in_proj"]["kernel"])
                 ) @ g["out_proj"]["kernel"]
        elif kind == "cross_diff_attention":
            a = cross_diff_attention(p["attn"], y, *keys_values, layer, sizes)
        else:
            raise ValueError(f"no such mixer: {kind!r}")
        x = x + a
        x = x + gated_mlp(p["mlp"], layer_norm(x, p["ln2"], eps))
    return x


def tied_cross_entropy(h, ln_f, embedding, targets, eps):
    """Mean over all positions of the cross entropy of
    ``LN(h) @ embedding^T`` against ``targets``, in blocks of rows."""
    rows = h.reshape(-1, h.shape[-1])
    labels = targets.reshape(-1)
    size = ROW_BLOCK if rows.shape[0] % ROW_BLOCK == 0 else rows.shape[0]

    def block_sum(args):
        r, lab = args
        logp = jax.nn.log_softmax(layer_norm(r, ln_f, eps) @ embedding.T,
                                  axis=-1)
        return -jnp.sum(jnp.take_along_axis(logp, lab[:, None], axis=-1))

    sums = lax.map(block_sum, (rows.reshape(-1, size, rows.shape[-1]),
                               labels.reshape(-1, size)))
    return jnp.sum(sums) / rows.shape[0]


def loss(sizes, params, tokens):
    """``tokens (B, T + 1)`` -> the scalar training loss."""
    params = jax.tree_util.tree_map(lambda a: a.astype(jnp.float32), params)
    x = trunk(sizes, params, tokens[:, :-1])
    return tied_cross_entropy(x, params["ln_f"], params["tok"]["embedding"],
                              tokens[:, 1:], sizes["eps"])
