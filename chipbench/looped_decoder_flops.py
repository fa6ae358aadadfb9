"""Operations and bytes of the family ``looped_decoder`` (a stack of
sandwich-normed grouped-query / SwiGLU blocks run ``rounds`` times over the
same leaves, an exit a round through one untied head, an exit gate),
computed from shapes, beside ``flops.py`` and by its conventions: 2 FLOPs a
multiply-add, a training step is three forward passes, recomputation is not
model work.  A leaf used ``rounds`` times does ``rounds`` times the work:
the counts follow the passes, not the parameters.  Norms, rotary, the
softmax, the exit distribution and its entropy carry no matrix work and are
not counted; the gate's ``hidden`` multiply-adds an exit are.
"""

from chipbench.flops import TRAIN_OVER_FORWARD
from chipbench.gqa_moe_flops import gqa_attention_cost
from chipbench.sambay_flops import visible_pairs


def block_forward_flops_per_token(*, hidden, heads, kv_heads, head_dim,
                                  ffn_width, seq_len):
    """One token through one block once: the four projections (``q`` in
    ``heads`` heads, ``k`` and ``v`` in ``kv_heads``, ``o`` back), QK^T and
    PV over the causal half of the pairs, and the three SwiGLU products."""
    projections = hidden * (heads + 2 * kv_heads) * head_dim + (
        heads * head_dim * hidden)
    scores = heads * 2 * head_dim * visible_pairs(seq_len) / seq_len
    return 2.0 * (projections + scores + 3 * hidden * ffn_width)


def forward_flops_per_token(*, rounds, layers, vocab_rows, hidden, **block):
    """One token's forward pass: ``rounds * layers`` block passes, and an
    exit a round (the untied head over ``vocab_rows`` and the gate)."""
    return (rounds * layers * block_forward_flops_per_token(
        hidden=hidden, **block)
            + rounds * 2.0 * hidden * (vocab_rows + 1))


def train_flops_per_token(**shapes) -> float:
    return TRAIN_OVER_FORWARD * forward_flops_per_token(**shapes)


def attention_cost(batch, heads, kv_heads, seq_len, head_dim, *, rounds,
                   layers, forward_calls=1, itemsize=2):
    """``(flops, bytes)`` of one step's attention kernel calls: a full
    causal layer's (``gqa_moe_flops.gqa_attention_cost``: QK^T and PV
    forward, five products backward, one pass over every operand with the
    keys and values at the ``kv_heads`` they are projected in) for each of
    the ``rounds * layers`` block passes, the forward ``forward_calls``
    times (2 under remat: as many calls recomputed as forward)."""
    return gqa_attention_cost(
        batch, heads, kv_heads, seq_len, head_dim,
        windows=[None] * (rounds * layers), forward_calls=forward_calls,
        itemsize=itemsize)
