"""Operations and bytes of the family ``linear_latent_moe`` (Kimi Delta
Attention layers beside a latent-attention layer, a routed-and-shared expert
layer under group-limited routing), computed from shapes, beside ``flops.py``
and by its conventions: 2 FLOPs a multiply-add, a training step is three
forward passes, recomputation is not model work.  Gathers, sorts, norms,
convolutions' taps, gates, rotary and the softmax are not counted.

**The delta rule is counted as the recurrence**, whatever chunking computes
it: a token and head decays its ``d_k x d_v`` state (1 operation an
element), reads it with the key (2), writes the rank-one correction (2) and
reads it with the query (2).  The chunked kernel does other arithmetic (the
intra-chunk products, a triangular inverse); that is the implementation's,
and a better chunking must not move the count.
"""

from chipbench.flops import TRAIN_OVER_FORWARD
from chipbench.latent_moe_flops import (
    causal_attention_macs, gated_mlp_macs)

DELTA_FORWARD_OPS = 7     # a state element and token: decay 1, k^T S 2,
# the rank-one update 2, S^T q 2
DELTA_BACKWARD_OPS = 22   # the states again (7) and the adjoint of each
# product and of the decay (15)


def kda_projection_macs(hidden, heads, dim):
    """Multiply-adds a token of one KDA layer's projections: queries, keys,
    values, the full-rank decay and the output at ``heads * dim``, ``beta``
    and the output gate at a scalar a head."""
    return 5 * hidden * heads * dim + 2 * hidden * heads


def mla_projection_macs(hidden, heads, kv_rank, nope, rope, v_dim):
    """Multiply-adds a token of one latent-attention layer's projections
    without a query bottleneck: ``W_q``, the down-projection with the shared
    rotary key, the up-projection, the head gate and the output."""
    return (hidden * heads * (nope + rope) + hidden * (kv_rank + rope)
            + kv_rank * heads * (nope + v_dim) + hidden * heads
            + heads * v_dim * hidden)


def forward_flops_per_token(*, kinds, hidden, heads, kda_dim, kv_rank, nope,
                            rope, v_dim, seq_len, dense_blocks, dense_width,
                            expert_width, shared_experts, router_outputs,
                            top_k, experts_held, vocab_rows):
    """One token's forward pass over the blocks ``kinds`` (the first
    ``dense_blocks`` with the dense MLP, the rest with the expert layer, the
    held experts at the **uniform expectation** of ``top_k * experts_held /
    router_outputs`` assignments a token), then the head.  ``heads`` is what
    this chip holds."""
    flops = 0.0
    for i, kind in enumerate(kinds):
        if kind == "kda":
            flops += 2.0 * kda_projection_macs(hidden, heads, kda_dim)
            flops += DELTA_FORWARD_OPS * heads * kda_dim * kda_dim
        else:
            flops += 2.0 * (
                mla_projection_macs(hidden, heads, kv_rank, nope, rope, v_dim)
                + causal_attention_macs(heads, seq_len, nope + rope, v_dim))
        if i < dense_blocks:
            flops += 2.0 * gated_mlp_macs(hidden, dense_width)
        else:
            held = top_k * experts_held / router_outputs
            flops += 2.0 * (
                hidden * router_outputs
                + gated_mlp_macs(hidden, shared_experts * expert_width)
                + held * gated_mlp_macs(hidden, expert_width))
    return flops + 2.0 * hidden * vocab_rows


def train_flops_per_token(**shapes) -> float:
    return TRAIN_OVER_FORWARD * forward_flops_per_token(**shapes)


def kda_cost(batch, seq_len, heads, d_k, d_v, *, layers=1, forward_calls=1,
             itemsize=2, decay_itemsize=4):
    """``(operations, bytes)`` of one step's delta-rule scans, **as the
    recurrence**: 7 operations a state element, token and head a forward
    pass and 22 a backward pass (module docstring), the forward twice under
    remat.  Bytes are what no kernel can avoid: forward reads ``q``, ``k``,
    ``v``, the f32 decay and ``beta`` and writes ``o``; backward reads those
    and ``do`` and writes the five gradients.  The chunk-start states are
    the kernel's own choice and are left out."""
    tokens = batch * seq_len * heads
    ops = layers * tokens * d_k * d_v * (
        forward_calls * DELTA_FORWARD_OPS + DELTA_BACKWARD_OPS)
    operands = tokens * (2 * d_k * itemsize + d_v * itemsize
                         + d_k * decay_itemsize + decay_itemsize)
    result = tokens * d_v * itemsize
    forward = operands + result
    backward = operands + result + operands
    return ops, layers * (forward_calls * forward + backward)
