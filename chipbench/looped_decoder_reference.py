"""The plain reference of the family ``looped_decoder``: the whole forward
pass and loss of an Ouro-style looped language model (*Scaling Latent
Reasoning via Looped Language Models*, arXiv:2510.25741; the architecture of
ByteDance/Ouro-2.6B) in ``jax.numpy`` and f32.  It imports nothing of
``bluefog_tpu``: no kernel, no flax module, no bf16 cast, no chunked head
with a gradient rule of its own.  It reads the parameter tree the system
trains (the names are the only thing the two share).

With ``R = sizes["rounds"]`` and ``L`` blocks, every norm an RMSNorm, no
bias but the gate's::

    x_0 = Emb(t)
    round r = 1..R:   y = x_{r-1}
       block l = 1..L:   y = y + rms_1post_l( Attn_l( rms_1_l(y) ) )
                         y = y + rms_2post_l( W_down( silu(W_gate u) * W_up u ) ),   u = rms_2_l(y)
       x_r = rms_f(y)                       # the exit's state and the next round's input
       g_r = sigmoid( w_g . x_r + b_g )     # one gate, shared by the rounds
    p_r = g_r prod_{j<r} (1 - g_j)  (r < R),     p_R = prod_{j<R} (1 - g_j)
    loss = mean_i [ sum_r p_r(i) CE( W_head x_r(i), t_{i+1} )  -  beta H(p(i)) ]

The same ``L`` blocks' leaves serve every round (**the loop is a Python loop
over one list of parameters**); ``rms_f``, the gate, the head and the
embedding are one leaf each.  Attention: ``H`` query heads on ``G`` key/value
heads (``G = H`` in the source), all ``head_dim`` wide, rotary over the whole
head (pair ``i`` is elements ``i`` and ``i + head_dim / 2``, angle ``position
* theta ** (-2i / head_dim)``), causal, scores ``q . k / sqrt(head_dim)``.
``H(p) = -sum_r p_r log p_r`` with ``0 log 0 = 0``.

Attention goes a head and a block of queries at a time and each exit's
cross entropy in blocks of rows (``lax.map``), so that 4,096 tokens of a
49,152-row vocabulary fit beside the parameters.  Every product is a plain
``@`` or ``einsum`` on f32 operands; the caller computes it under
``jax.default_matmul_precision("highest")`` (the harness's
``reference.model_loss_error`` and the tests do), without which a TPU
multiplies f32 in bf16 passes.

``sizes`` is what the shapes do not say: ``rounds``, ``head_dim``,
``rope_theta``, ``eps``, ``beta``.
"""

import jax
import jax.numpy as jnp
from jax import lax

ROW_BLOCK = 1024      # rows of an exit's logits computed at once
QUERY_BLOCK = 512     # queries of one head scored against every key at once


def rms(x, scale, eps):
    return x * lax.rsqrt(jnp.mean(x * x, axis=-1, keepdims=True) + eps) * scale


def after(y, scale, eps):
    """The norm a sub-layer's output goes through before the residual sum."""
    return rms(y, scale, eps)


def rotary(x, positions, theta):
    """``x (B, T, H, R)``: pair ``i`` = elements ``i`` and ``i + R / 2``, as
    a complex number turned by ``position * theta ** (-2i / R)``."""
    half = x.shape[-1] // 2
    freq = theta ** (-jnp.arange(half, dtype=jnp.float32) / half)
    angle = positions[:, None, None].astype(jnp.float32) * freq   # (T, 1, R/2)
    z = lax.complex(x[..., :half], x[..., half:]) * jnp.exp(1j * angle)
    return jnp.concatenate([z.real, z.imag], axis=-1)


def attention(q, k, v):
    """``q (B, T, H, D)``, ``k, v (B, T, G, D)``, ``G`` dividing ``H`` ->
    ``(B, T, H, D)``: causal softmax attention."""
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


def self_attention(p, y, positions, sizes):
    b, t, _ = y.shape
    dim, theta = sizes["head_dim"], sizes["rope_theta"]
    q = rotary((y @ p["q"]["kernel"]).reshape(b, t, -1, dim), positions, theta)
    k = rotary((y @ p["k"]["kernel"]).reshape(b, t, -1, dim), positions, theta)
    v = (y @ p["v"]["kernel"]).reshape(b, t, -1, dim)
    return attention(q, k, v).reshape(b, t, -1) @ p["o"]["kernel"]


def swiglu(p, u):
    return (jax.nn.silu(u @ p["gate"]["kernel"]) * (u @ p["up"]["kernel"])
            ) @ p["down"]["kernel"]


def block(p, y, positions, sizes):
    eps = sizes["eps"]
    a = self_attention(p["attn"], rms(y, p["ln1"]["scale"], eps), positions,
                       sizes)
    y = y + after(a, p["ln1_post"]["scale"], eps)
    f = swiglu(p["mlp"], rms(y, p["ln2"]["scale"], eps))
    return y + after(f, p["ln2_post"]["scale"], eps)


def exits(sizes, params, tokens):
    """``tokens (B, T)`` -> the ``R`` exits' hidden states ``x_r (B, T, D)``
    (a list) and the gate's logits ``(R, B, T)``."""
    blocks = [params[f"block_{i}"] for i in range(
        sum(name.startswith("block_") for name in params))]
    positions = jnp.arange(tokens.shape[1])
    x = params["tok"]["embedding"][tokens]
    states, gates = [], []
    for _ in range(sizes["rounds"]):
        for p in blocks:                   # the same leaves, every round
            x = block(p, x, positions, sizes)
        x = rms(x, params["ln_f"]["scale"], sizes["eps"])
        states.append(x)
        gates.append((x @ params["exit_gate"]["kernel"])[..., 0]
                     + params["exit_gate"]["bias"][0])
    return states, jnp.stack(gates)


def exit_probabilities(gate_logits):
    """``p (R, ...)`` from the gate's logits ``(R, ...)``: leave after round
    ``r`` with probability ``g_r`` having stayed before, after the last with
    what is left."""
    g = jax.nn.sigmoid(gate_logits)
    stayed = jnp.cumprod(1.0 - g[:-1], axis=0)
    return jnp.concatenate([g[:1], g[1:-1] * stayed[:-1], stayed[-1:]])


def entropy(p):
    """``-sum_r p_r log p_r`` over the leading axis, ``0 log 0 = 0``."""
    return -jnp.sum(jnp.where(p > 0, p * jnp.log(jnp.where(p > 0, p, 1.0)),
                              0.0), axis=0)


def cross_entropies(h, head, targets):
    """The cross entropy of ``h @ head`` against ``targets`` a position,
    ``(B, T)``, in blocks of rows."""
    rows = h.reshape(-1, h.shape[-1])
    labels = targets.reshape(-1)
    size = ROW_BLOCK if rows.shape[0] % ROW_BLOCK == 0 else rows.shape[0]

    def one_block(args):
        r, lab = args
        logp = jax.nn.log_softmax(r @ head, axis=-1)
        return -jnp.take_along_axis(logp, lab[:, None], axis=-1)[:, 0]

    ce = lax.map(one_block, (rows.reshape(-1, size, rows.shape[-1]),
                             labels.reshape(-1, size)))
    return ce.reshape(targets.shape)


def logits(sizes, params, tokens):
    """Every exit's whole logits ``(R, B, T, V)`` and the gate's logits: for
    tests at a small size."""
    params = jax.tree_util.tree_map(lambda a: a.astype(jnp.float32), params)
    states, gates = exits(sizes, params, tokens)
    return jnp.stack([x @ params["lm_head"]["kernel"] for x in states]), gates


def loss(sizes, params, tokens):
    """``tokens (B, T + 1)`` -> the scalar training loss."""
    params = jax.tree_util.tree_map(lambda a: a.astype(jnp.float32), params)
    states, gates = exits(sizes, params, tokens[:, :-1])
    p = exit_probabilities(gates)
    ce = jnp.stack([cross_entropies(x, params["lm_head"]["kernel"],
                                    tokens[:, 1:]) for x in states])
    return jnp.mean(jnp.sum(p * ce, axis=0) - sizes["beta"] * entropy(p))
