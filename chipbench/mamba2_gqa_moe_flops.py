"""Operations and bytes of the family ``mamba2_gqa_moe`` (blocks that are a
Mamba-2 mixer, an un-positioned grouped-query attention or an expert layer
alone; ungated experts with one shared expert; an untied head), computed
from shapes, beside ``flops.py`` and by its conventions: 2 FLOPs a
multiply-add, a training step is three forward passes, recomputation is not
model work.  Gathers, sorts, norms, the convolution's taps, gates and the
softmax carry no matrix work and are not counted.  The attention layer's
multiply-adds are ``conv_gqa_moe_flops.attention_macs`` (a full causal
grouped-query layer is the same count with or without a rotary) and its
kernels are priced by ``gqa_moe_flops.gqa_attention_cost`` (the same kernels
over the same kind of operands).

**The state-space layer is counted as the recurrence**, whatever chunking
or backend computes it (as ``kda_flops.py`` counts the delta rule): a token
and head decays its ``P x N`` state (1 operation an element), writes the
rank-one ``dt x B^T`` into it (2) and reads it with ``C`` (2).  The chunked
form does other arithmetic (the 128 x 128 scores a group, the decay masks,
four products: about 6.5 operations an element at a chunk of 128); that is
the implementation's, and a better chunking must not move the count.
"""

from chipbench.conv_gqa_moe_flops import attention_macs  # noqa: F401
from chipbench.flops import TRAIN_OVER_FORWARD

MAMBA, ATTENTION, EXPERTS = "M", "*", "E"
SSD_FORWARD_OPS = 5     # a state element and token: decay 1, the rank-one
# write 2, the read with C 2
SSD_BACKWARD_OPS = 16   # the states again (5) and the adjoints: of the decay
# (3), of the write (dt x and B: 4) and of the read (the state and C: 4)


def mamba2_macs(hidden, heads, head_dim, state, groups):
    """Multiply-adds a token of one Mamba-2 mixer's projections: ``W_in``
    to ``z``, ``x``, ``B``, ``C`` and ``dt`` (``2 I + 2 G N + H`` outputs,
    ``I = H P``) and ``W_out`` back."""
    inner = heads * head_dim
    return hidden * (2 * inner + 2 * groups * state + heads) + inner * hidden


def ssd_forward_ops(heads, head_dim, state):
    """Operations a token of one layer's recurrence, forward."""
    return SSD_FORWARD_OPS * heads * head_dim * state


def expert_macs(hidden, router_outputs, top_k, experts_held, expert_width,
                shared_width):
    """Multiply-adds a token of one expert block: the router over all its
    outputs, the shared expert, and the held experts at the **uniform
    expectation** (``top_k * experts_held / router_outputs`` assignments a
    token); an expert is **two** ``hidden x width`` products."""
    held_per_token = top_k * experts_held / router_outputs
    return (hidden * router_outputs + 2 * hidden * shared_width
            + held_per_token * 2 * hidden * expert_width)


def forward_flops_per_token(*, kinds, hidden, mamba_heads, mamba_head_dim,
                            state, groups, heads, kv_heads, head_dim,
                            seq_len, router_outputs, top_k, experts_held,
                            expert_width, shared_width, vocab_rows):
    """One token's forward pass: each block by its letter (a block is one
    sub-layer), then the untied head."""
    per_kind = {
        MAMBA: 2.0 * mamba2_macs(hidden, mamba_heads, mamba_head_dim, state,
                                 groups)
        + ssd_forward_ops(mamba_heads, mamba_head_dim, state),
        ATTENTION: 2.0 * attention_macs(hidden, heads, kv_heads, head_dim,
                                        seq_len),
        EXPERTS: 2.0 * expert_macs(hidden, router_outputs, top_k,
                                   experts_held, expert_width, shared_width)}
    return sum(per_kind[kind] for kind in kinds) + 2.0 * hidden * vocab_rows


def train_flops_per_token(**shapes) -> float:
    return TRAIN_OVER_FORWARD * forward_flops_per_token(**shapes)


def ssd_cost(batch, seq_len, heads, head_dim, state, groups, *, layers=1,
             forward_calls=1, itemsize=2, step_itemsize=4):
    """``(operations, bytes)`` of one step's state-space scans, **as the
    recurrence**: 5 operations a state element, token and head a forward
    pass and 16 a backward pass (module docstring), the forward twice under
    remat.  Bytes are what no kernel can avoid: forward reads ``x``, ``B``,
    ``C`` (a group's, once) and the f32 step and log-decay a head, and
    writes ``y``; backward reads those and ``dy`` and writes the gradients
    of ``x``, ``B``, ``C``, the step and the log-decay.  The chunk-start
    states are the kernel's own choice and are left out."""
    tokens = batch * seq_len
    ops = layers * tokens * heads * head_dim * state * (
        forward_calls * SSD_FORWARD_OPS + SSD_BACKWARD_OPS)
    operands = tokens * (heads * head_dim * itemsize
                         + 2 * groups * state * itemsize
                         + 2 * heads * step_itemsize)
    result = tokens * heads * head_dim * itemsize
    forward = operands + result
    backward = operands + result + operands
    return ops, layers * (forward_calls * forward + backward)


def ungated_grouped_matmul_cost(rows, hidden, width, *, layers=1,
                                forward_calls=1, itemsize=2,
                                weight_itemsize=4, experts_held=1):
    """``(flops, bytes)`` of one step's grouped matmuls over ``rows`` routed
    rows a layer (the **expectation** under uniform routing; the real count
    varies with the seed) through **ungated** experts: forward two products
    (up, down), backward four (each product's two transposes), at the
    experts' own ``width`` (what the program pads it to is the program's).
    Bytes: every product reads its rows and the held experts' weights and
    writes its result once."""
    product = 2.0 * rows * hidden * width
    flops = layers * (2 * forward_calls + 4) * product
    acts = rows * (hidden + width) * itemsize
    weights = experts_held * hidden * width
    forward = 2 * (acts + weights * itemsize)
    backward = 2 * (2 * acts + weights * itemsize) + 2 * (
        acts + weights * weight_itemsize)
    return flops, layers * (forward_calls * forward + backward)
