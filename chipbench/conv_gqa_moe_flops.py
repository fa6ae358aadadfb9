"""Operations and bytes of the family ``conv_gqa_moe`` (gated short
convolutions beside rotary grouped-query attention, a leading dense SwiGLU,
routed experts without a shared one, a tied head), computed from shapes,
beside ``flops.py`` and by its conventions: 2 FLOPs a multiply-add, a
training step is three forward passes, recomputation is not model work.
Gathers, sorts, norms, rotary, the softmax, and the short convolution's two
gates and taps carry no matrix work and are not counted (:func:`gate_conv_cost`
prices their memory traffic).  The grouped matmuls are priced by
``latent_moe_flops.grouped_matmul_cost`` and the attention kernels by
``gqa_moe_flops.gqa_attention_cost`` (the same kernels over the same kind
of operands).
"""

from chipbench.flops import TRAIN_OVER_FORWARD

CONV, ATTENTION = "conv", "full_attention"


def short_conv_macs(hidden):
    """Multiply-adds a token of one gated short convolution: ``W_in``
    (``hidden x 3 hidden``) and ``W_out`` (``hidden x hidden``)."""
    return 4 * hidden * hidden


def attention_macs(hidden, heads, kv_heads, head_dim, seq_len):
    """Multiply-adds a token of one full causal grouped-query layer: ``q`` in
    ``heads`` heads, ``k`` and ``v`` in ``kv_heads``, ``o`` back, and QK^T
    and PV over the ``seq_len / 2`` keys a query sees on average."""
    projections = hidden * (heads + 2 * kv_heads) * head_dim + (
        heads * head_dim * hidden)
    return projections + heads * 2 * head_dim * seq_len / 2


def forward_flops_per_token(*, kinds, hidden, heads, kv_heads, head_dim,
                            seq_len, dense_blocks, dense_width,
                            router_outputs, top_k, experts_held,
                            expert_width, vocab_rows):
    """One token's forward pass: each block's mixer by its kind, the first
    ``dense_blocks`` blocks' SwiGLU (three ``hidden x dense_width``
    products), the other blocks' router over all its outputs and the held
    experts at the **uniform expectation** (a token sends ``top_k *
    experts_held / router_outputs`` assignments to this chip, three ``hidden
    x expert_width`` products each); then the tied head."""
    mixers = sum(short_conv_macs(hidden) if kind == CONV else attention_macs(
        hidden, heads, kv_heads, head_dim, seq_len) for kind in kinds)
    held_per_token = top_k * experts_held / router_outputs
    routed = hidden * router_outputs + held_per_token * 3 * hidden * (
        expert_width)
    ffn = dense_blocks * 3 * hidden * dense_width + (
        len(kinds) - dense_blocks) * routed
    return 2.0 * (mixers + ffn + hidden * vocab_rows)


def train_flops_per_token(**shapes) -> float:
    return TRAIN_OVER_FORWARD * forward_flops_per_token(**shapes)


def gate_conv_cost(tokens, hidden, *, layers=1, taps=3, forward_calls=1,
                   itemsize=2):
    """``(flops, bytes)`` of one step's gate-convolution-gate (``s = b * z``,
    the ``taps`` shifted multiply-adds, ``c * conv``) between the two
    projections of ``layers`` short-convolution layers, if each direction
    is one fused pass: forward reads ``b``, ``z``, ``c`` and writes the
    gated result (4 tensors of ``tokens x hidden``); backward reads those
    three and the result's cotangent and writes three cotangents (7), the
    convolution computed again on the way.  Element-wise work: 2 gates and
    ``2 taps - 1`` operations an element forward, about twice that and the
    taps' own gradient backward.  No matrix unit is involved: the bound is
    the memory one (0.655 ms a forward pass at 32,768 x 2,048 bf16 on a
    v5e)."""
    tensor = tokens * hidden * itemsize
    per_element = 2 + 2 * taps - 1
    flops = layers * tokens * hidden * per_element * (forward_calls + 2.0)
    return flops, layers * tensor * (4 * forward_calls + 7)
