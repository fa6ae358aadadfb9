"""Operations and bytes of the family ``gqa_moe`` (grouped-query attention
in un-positioned full layers and rotary window layers, a routed expert layer
without a shared expert, an untied head), computed from shapes, beside
``flops.py`` and by its conventions: 2 FLOPs a multiply-add, a training step
is three forward passes, recomputation is not model work.  Gathers, sorts,
norms, rotary and the softmax carry no matrix work and are not counted.  The
grouped matmuls are priced by ``latent_moe_flops.grouped_matmul_cost`` (the
same kernels over the same kind of rows) and the band under a window by
``sambay_flops.visible_pairs``.
"""

from chipbench.flops import TRAIN_OVER_FORWARD
from chipbench.sambay_flops import visible_pairs

WINDOWED = "window_rotary_attention"


def windows_of(kinds, window):
    """The band of each attention layer: ``window`` keys or ``None``."""
    return [window if kind == WINDOWED else None for kind in kinds]


def forward_flops_per_token(*, kinds, hidden, heads, kv_heads, head_dim,
                            seq_len, window, router_outputs, top_k,
                            experts_held, expert_width, vocab_rows):
    """One token's forward pass: each block's four projections (``q`` in
    ``heads`` heads, ``k`` and ``v`` in ``kv_heads``, ``o`` back), QK^T and
    PV over the pairs its mask lets through, the router over all its outputs
    and the held experts at the **uniform expectation** (a token sends
    ``top_k * experts_held / router_outputs`` assignments to this chip, three
    ``hidden x expert_width`` products each); then the untied head."""
    projections = hidden * (heads + 2 * kv_heads) * head_dim + (
        heads * head_dim * hidden)
    held_per_token = top_k * experts_held / router_outputs
    experts = hidden * router_outputs + held_per_token * 3 * hidden * (
        expert_width)
    scores = sum(heads * 2 * head_dim * visible_pairs(seq_len, w) / seq_len
                 for w in windows_of(kinds, window))
    return 2.0 * (len(kinds) * (projections + experts) + scores
                  + hidden * vocab_rows)


def train_flops_per_token(**shapes) -> float:
    return TRAIN_OVER_FORWARD * forward_flops_per_token(**shapes)


def gqa_attention_cost(batch, heads, kv_heads, seq_len, head_dim, *, windows,
                       forward_calls=1, itemsize=2):
    """``(flops, bytes)`` of one step's grouped-query attention kernel calls,
    forward and fused backward, one layer per entry of ``windows`` (``None``:
    full causal; ``w``: the band of ``w`` keys).  What the kernels' calls
    need, so a forward pass repeated by ``remat`` counts
    (``forward_calls=2``).

    Forward: QK^T and PV.  Backward (fused, five matmuls): QK^T again, dV,
    dP, dQ, dK.  Each is 2 FLOPs a visible query-key pair, head and unit of
    ``head_dim``.  Bytes are one pass over every operand and result, **the
    keys and values at the ``kv_heads`` heads they are projected in**: the
    kernel is handed them repeated up to ``heads`` (``local_attention``) and
    the backward's dK and dV are summed over a group outside it; a grouped
    kernel would need neither, so those bytes are the program's and not the
    algorithm's, and they are not charged."""
    flops = 0.0
    for window in windows:
        matmul = 2.0 * batch * heads * visible_pairs(seq_len, window) * (
            head_dim)
        flops += (2 * forward_calls + 5) * matmul
    q = batch * heads * seq_len * head_dim * itemsize
    kv = 2 * batch * kv_heads * seq_len * head_dim * itemsize
    rows = batch * heads * seq_len * 4
    # forward: q, k, v in; o and the f32 log-sum-exp out.  Backward: q, k,
    # v, o, do and the row statistic in; dq, dk, dv out
    nbytes = len(windows) * (forward_calls * (2 * q + kv + rows)
                             + 4 * q + 2 * kv + rows)
    return flops, nbytes
