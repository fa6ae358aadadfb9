"""Operations and bytes the algorithms need, computed from shapes.

One FLOP count per multiply and per add (2 per multiply-add).  A training
step is counted as three forward passes (forward, gradient with respect to the
activations, gradient with respect to the weights); what ``remat`` recomputes
is not model work and is not counted.  XLA's own count is printed beside these
by ``run.py`` and is never used: it includes recomputation and grows when the
compiler rematerialises under memory pressure.
"""

TRAIN_OVER_FORWARD = 3


def conv_macs(out_hw: int, kernel: int, c_in: int, c_out: int) -> int:
    """Multiply-adds of one square convolution producing ``out_hw**2``
    positions."""
    return out_hw * out_hw * kernel * kernel * c_in * c_out


def resnet_forward_macs(image_size, stage_sizes, num_filters, num_classes,
                        expansion=4) -> int:
    """Multiply-adds of one image through a bottleneck ResNet v1.5 (stride on
    the 3x3): convolutions and the classifier; batch norm, ReLU and pooling
    carry no matrix work.  ResNet-50 at 224 gives 4.09e9 — the figure often
    misquoted as FLOPs."""
    hw = image_size // 2                      # 7x7 stride 2
    macs = conv_macs(hw, 7, 3, num_filters)
    hw //= 2                                  # 3x3 max pool stride 2
    c_in = num_filters
    for i, blocks in enumerate(stage_sizes):
        width = num_filters * 2 ** i
        for j in range(blocks):
            stride = 2 if i > 0 and j == 0 else 1
            out_hw = hw // stride
            macs += conv_macs(hw, 1, c_in, width)            # 1x1 reduce
            macs += conv_macs(out_hw, 3, width, width)       # 3x3, strided
            macs += conv_macs(out_hw, 1, width, width * expansion)
            if j == 0:                                       # projection
                macs += conv_macs(out_hw, 1, c_in, width * expansion)
            c_in, hw = width * expansion, out_hw
    return macs + c_in * num_classes


def resnet_train_flops_per_image(image_size, stage_sizes, num_filters,
                                 num_classes) -> float:
    return 2.0 * TRAIN_OVER_FORWARD * resnet_forward_macs(
        image_size, stage_sizes, num_filters, num_classes)


def decoder_forward_flops_per_token(n_embd, n_layer, seq_len, vocab_rows,
                                    mlp_ratio=4) -> float:
    """Dense pre-LN decoder: per layer the fused QKV (3 d^2 multiply-adds),
    the projection (d^2) and the MLP (2 * ratio * d^2), plus causal attention
    at half of the full QK^T + PV cost (4 * T * d FLOPs a token); then the
    output head.  Embedding lookups are gathers and carry no matrix work."""
    d = n_embd
    per_layer = 2.0 * (4 + 2 * mlp_ratio) * d * d      # weight matmuls
    per_layer += 2.0 * seq_len * d                      # causal: half of 4*T*d
    return n_layer * per_layer + 2.0 * d * vocab_rows


def decoder_train_flops_per_token(n_embd, n_layer, seq_len, vocab_rows,
                                  mlp_ratio=4) -> float:
    return TRAIN_OVER_FORWARD * decoder_forward_flops_per_token(
        n_embd, n_layer, seq_len, vocab_rows, mlp_ratio)


def causal_attention_cost(batch, heads, seq_len, head_dim, *, layers=1,
                          forward_calls=1, itemsize=2):
    """``(flops, bytes)`` of flash attention, forward and backward, for one
    step: what the kernels' calls need, so a forward pass repeated by
    ``remat`` counts (``forward_calls=2``).

    Forward is two matmuls (QK^T, PV), backward five (QK^T again, dV, dP, dQ,
    dK), each ``2*B*H*T*T*D`` FLOPs, halved by the causal mask.  Bytes are one
    pass over every operand and result: forward reads q, k, v and writes o
    and the f32 row statistics l and m; backward reads q, k, v, o, do, l, m
    and writes dq, dk, dv."""
    matmul = 2.0 * batch * heads * seq_len * seq_len * head_dim / 2
    tensor = batch * heads * seq_len * head_dim * itemsize
    rows = 2 * batch * heads * seq_len * 4
    flops = layers * (2 * forward_calls + 5) * matmul
    nbytes = layers * (forward_calls * (4 * tensor + rows)
                       + 8 * tensor + rows)
    return flops, nbytes
