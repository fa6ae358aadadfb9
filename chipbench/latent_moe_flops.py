"""Operations and bytes of the family ``latent_moe`` (latent attention, a
routed-and-shared expert layer, a multi-token-prediction module), computed
from shapes, beside ``flops.py`` and by its conventions: 2 FLOPs a
multiply-add, a training step is three forward passes, recomputation is not
model work.  Gathers, sorts, norms, rotary and the softmax carry no matrix
work and are not counted.
"""

from chipbench.flops import TRAIN_OVER_FORWARD


def mla_projection_macs(hidden, heads, q_rank, kv_rank, nope, rope, v_dim):
    """Multiply-adds a token of one block's latent-attention projections:
    query down and up, key/value down (with the shared rotary key) and up,
    and the output projection."""
    return (hidden * q_rank + q_rank * heads * (nope + rope)
            + hidden * (kv_rank + rope) + kv_rank * heads * (nope + v_dim)
            + heads * v_dim * hidden)


def causal_attention_macs(heads, seq_len, qk_dim, v_dim):
    """Multiply-adds a token of causal attention whose keys and values
    differ in width: QK^T at ``qk_dim`` and PV at ``v_dim``, halved by the
    mask."""
    return heads * seq_len * (qk_dim + v_dim) / 2


def gated_mlp_macs(hidden, width):
    return 3 * hidden * width


def forward_flops_per_token(*, hidden, heads, q_rank, kv_rank, nope, rope,
                            v_dim, seq_len, dense_blocks, expert_blocks,
                            dense_width, expert_width, shared_experts,
                            router_outputs, top_k, experts_held, vocab_rows,
                            mtp_modules):
    """One token's forward pass: ``dense_blocks + expert_blocks`` trunk
    blocks and ``mtp_modules`` further expert blocks, each with its
    projection of the doubled width and its own pass over the head.  The
    held experts are counted at the **uniform expectation**: a token sends
    ``top_k * experts_held / router_outputs`` assignments to this chip."""
    blocks = dense_blocks + expert_blocks + mtp_modules
    attention = blocks * (
        mla_projection_macs(hidden, heads, q_rank, kv_rank, nope, rope, v_dim)
        + causal_attention_macs(heads, seq_len, nope + rope, v_dim))
    held_per_token = top_k * experts_held / router_outputs
    expert_block = (hidden * router_outputs
                    + gated_mlp_macs(hidden, shared_experts * expert_width)
                    + held_per_token * gated_mlp_macs(hidden, expert_width))
    ffn = (dense_blocks * gated_mlp_macs(hidden, dense_width)
           + (expert_blocks + mtp_modules) * expert_block)
    head = (1 + mtp_modules) * hidden * vocab_rows
    mtp_projection = mtp_modules * 2 * hidden * hidden
    return 2.0 * (attention + ffn + head + mtp_projection)


def train_flops_per_token(**shapes) -> float:
    return TRAIN_OVER_FORWARD * forward_flops_per_token(**shapes)


def mla_attention_cost(batch, heads, seq_len, qk_dim, v_dim, *, layers=1,
                       forward_calls=1, itemsize=2):
    """``(flops, bytes)`` of the attention kernels for one step, forward and
    fused backward, where q and k are ``qk_dim`` wide and v is ``v_dim``:
    what the kernels' calls need, so a forward pass repeated by ``remat``
    counts (``forward_calls=2``).

    Forward: QK^T at ``qk_dim``, PV at ``v_dim``.  Backward (fused, five
    matmuls): QK^T again, dQ and dK at ``qk_dim``; dV and dP at ``v_dim``.
    Each is ``2*B*H*T*T*D`` FLOPs, halved by the causal mask.  Bytes are one
    pass over every operand and result: forward reads q, k, v and writes o
    and the f32 log-sum-exp; backward reads q, k, v, o, do and the row
    statistic and writes dq, dk, dv."""
    wide = 2.0 * batch * heads * seq_len * seq_len * qk_dim / 2
    narrow = 2.0 * batch * heads * seq_len * seq_len * v_dim / 2
    flops = layers * (forward_calls * (wide + narrow)
                      + 3 * wide + 2 * narrow)
    qk = batch * heads * seq_len * qk_dim * itemsize
    vo = batch * heads * seq_len * v_dim * itemsize
    rows = batch * heads * seq_len * 4
    nbytes = layers * (forward_calls * (2 * qk + 2 * vo + rows)
                       + 4 * qk + 4 * vo + rows)
    return flops, nbytes


def grouped_matmul_cost(rows, hidden, width, *, layers=1, forward_calls=1,
                        itemsize=2, weight_itemsize=4, experts_held=1):
    """``(flops, bytes)`` of one step's grouped matmuls over ``rows`` routed
    rows a layer (the **expectation** under uniform routing; the real count
    varies with the seed): forward three products (gate, up, down), backward
    six (each product's two transposes).  Bytes: every product reads its
    rows and the held experts' weights and writes its result once."""
    product = 2.0 * rows * hidden * width
    flops = layers * (3 * forward_calls + 6) * product
    acts = rows * (hidden + width) * itemsize
    weights = experts_held * hidden * width
    forward = 3 * (acts + weights * itemsize)
    backward = 3 * (2 * acts + weights * itemsize) + 3 * (
        acts + weights * weight_itemsize)
    return flops, layers * (forward_calls * forward + backward)
