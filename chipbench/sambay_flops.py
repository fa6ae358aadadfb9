"""Operations and bytes of the family ``sambay`` (Mamba mixers, differential
attention under a window and in full, a gated memory unit, cross attention
over another layer's keys and values, a head tied to the embedding), computed
from shapes, beside ``flops.py`` and by its conventions: 2 FLOPs a
multiply-add, a training step is three forward passes, recomputation is not
model work.

**Model FLOPs are matrix work.**  The selective scan is vector work (an
``exp`` and half a dozen multiply-adds a state element, no matmul form: the
decay is per channel and per state), as are the convolution's four taps, the
gates, the norms and the softmax; none of it is counted in
``train_flops_per_token`` and so none of it is in ``mfu``.  A scan that took
no time would leave ``mfu`` where the matrix work alone puts it.
``selective_scan_cost`` prices the scan for its own roofline instead.
"""

from chipbench.flops import TRAIN_OVER_FORWARD


def visible_pairs(seq_len, window=None):
    """Query-key pairs a causal mask lets through: the lower triangle with
    the diagonal counted at half, as ``flops.py`` halves the full square;
    under a window of ``w`` keys (the token itself included) the band
    ``w * T - w * w / 2``."""
    if window is None or window >= seq_len:
        return seq_len * seq_len / 2
    return window * seq_len - window * window / 2


def mixer_macs(kind, *, hidden, heads, kv_heads, inner, state, dt_rank,
               seq_len, window):
    """Multiply-adds a token of one mixer's matrix work."""
    dim = hidden // heads
    maps = heads * (dim + 2 * dim)      # QK^T at dim, PV at 2 * dim, a key
    if kind == "mamba":
        return (hidden * 2 * inner + inner * (dt_rank + 2 * state)
                + dt_rank * inner + inner * hidden)
    if kind == "gmu":
        return 2 * hidden * inner
    if kind == "cross_diff_attention":
        return 2 * hidden * hidden + maps * visible_pairs(seq_len) / seq_len
    projections = hidden * (hidden + 2 * kv_heads * dim) + hidden * hidden
    banded = window if kind == "diff_attention_window" else None
    return projections + maps * visible_pairs(seq_len, banded) / seq_len


def forward_flops_per_token(*, kinds, hidden, ffn_width, vocab_rows,
                            **mixer_shapes):
    """One token's forward pass: each block's mixer and its gated MLP
    (three ``hidden x ffn_width`` products), then the tied head."""
    macs = sum(mixer_macs(kind, hidden=hidden, **mixer_shapes)
               + 3 * hidden * ffn_width for kind in kinds)
    return 2.0 * (macs + hidden * vocab_rows)


def train_flops_per_token(**shapes) -> float:
    return TRAIN_OVER_FORWARD * forward_flops_per_token(**shapes)


def diff_attention_cost(batch, heads, kv_heads, seq_len, dim, *, windows,
                        forward_calls=1, itemsize=2):
    """``(flops, bytes)`` of one step's differential-attention kernel calls,
    forward and fused backward: ``heads`` softmax maps a layer with
    ``dim``-wide queries and keys and ``2 * dim``-wide values, one layer per
    entry of ``windows`` (``None``: full causal; ``w``: the band of ``w``
    keys).  What the kernels' calls need, so a forward pass repeated by
    ``remat`` counts (``forward_calls=2``).

    Forward: QK^T at ``dim``, PV at ``2 * dim``.  Backward (fused, five
    matmuls): QK^T again, dQ and dK at ``dim``; dV and dP at ``2 * dim``.
    Each is 2 FLOPs a visible query-key pair and unit of width.  Bytes are
    one pass over every operand and result, the keys and values at the
    ``kv_heads`` heads they are projected in (the kernel is handed them
    repeated; a grouped kernel would not need that)."""
    flops = 0.0
    for window in windows:
        pair = 2.0 * batch * heads * visible_pairs(seq_len, window)
        flops += forward_calls * pair * 3 * dim + pair * (3 * dim + 4 * dim)
    q = batch * heads * seq_len * dim * itemsize
    kv = 2 * batch * kv_heads * seq_len * dim * itemsize
    o = batch * heads * seq_len * 2 * dim * itemsize
    rows = batch * heads * seq_len * 4
    nbytes = len(windows) * (forward_calls * (q + kv + o + rows)
                             + 2 * q + 2 * kv + 2 * o + rows)
    return flops, nbytes


SCAN_FORWARD_OPS = 9     # a state element: decay (mul, exp, mul), drive
# (mul, add), read-out (mul, add), and the token's own two amortised
SCAN_BACKWARD_OPS = 22   # the chunk's states again (5) and the adjoint (17)


def selective_scan_cost(batch, seq_len, channels, states, *, layers=1,
                        forward_calls=1, itemsize=2, delta_itemsize=4):
    """``(operations, bytes)`` of one step's selective scans.  Operations:
    ``9 * T * channels * states`` a forward pass and 22 a backward pass
    (vector and exponential work: the roofline reducer divides them by the
    matrix unit's peak, which no scan can reach, so the bound that counts is
    the bytes').  Bytes are what no kernel can avoid: forward reads ``x``,
    ``delta``, ``B``, ``C`` and writes ``m``; backward reads those and
    ``dm`` and writes the five gradients that have a time axis (``dA`` and
    ``dD`` are a token's worth).  The states saved between chunks are the
    kernel's own choice and are left out."""
    elements = batch * seq_len * channels * states
    ops = layers * elements * (forward_calls * SCAN_FORWARD_OPS
                               + SCAN_BACKWARD_OPS)
    wide = batch * seq_len * channels
    narrow = batch * seq_len * states * itemsize
    forward = wide * (2 * itemsize + delta_itemsize) + 2 * narrow
    backward = (wide * (2 * itemsize + delta_itemsize) + 2 * narrow
                + wide * (itemsize + delta_itemsize) + 2 * narrow)
    return ops, layers * (forward_calls * forward + backward)
