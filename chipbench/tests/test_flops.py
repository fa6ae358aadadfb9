"""The analytic counts against hand counts."""

import pytest

from chipbench import flops


def test_conv_macs_of_the_resnet_stem():
    # 112 x 112 outputs, 7 x 7 x 3 taps, 64 filters
    assert flops.conv_macs(112, 7, 3, 64) == 112 * 112 * 147 * 64 == 118013952


def test_resnet50_forward_is_4_09_gmac_so_8_2_gflop():
    macs = flops.resnet_forward_macs(224, [3, 4, 6, 3], 64, 1000)
    assert macs == pytest.approx(4.09e9, rel=0.005)
    train = flops.resnet_train_flops_per_image(224, [3, 4, 6, 3], 64, 1000)
    assert train == 6 * macs
    # bench.py's RESNET50_TRAIN_FLOPS_PER_IMG_224 is half of this
    assert train == pytest.approx(2 * 3 * 4.09e9, rel=0.005)


def test_resnet_first_bottleneck_by_hand():
    # one stage of one block at 8 filters on a 16 x 16 image: stem to 8 x 8,
    # pool to 4 x 4, then 1x1 8->8, 3x3 8->8, 1x1 8->32, projection 8->32
    stem = 8 * 8 * 49 * 3 * 8
    block = 16 * (8 * 8 + 9 * 8 * 8 + 8 * 32 + 8 * 32)
    assert flops.resnet_forward_macs(16, [1], 8, 10) == stem + block + 32 * 10


def test_decoder_flops_per_token_by_hand():
    d, layers, t, vocab = 768, 12, 2048, 50304
    weights = 2 * (3 * d * d + d * d + 4 * d * d + 4 * d * d)
    attention = 2 * t * d          # QK^T and PV, 2*T*d each, halved
    forward = layers * (weights + attention) + 2 * d * vocab
    assert flops.decoder_forward_flops_per_token(d, layers, t, vocab) == forward
    assert flops.decoder_train_flops_per_token(d, layers, t, vocab) == 3 * forward


def test_attention_is_half_the_flops_at_8192():
    short = flops.decoder_forward_flops_per_token(768, 12, 2048, 50304)
    long = flops.decoder_forward_flops_per_token(768, 12, 8192, 50304)
    attention = 12 * 2.0 * 8192 * 768
    assert 0.3 < attention / long < 0.5
    assert 12 * 2.0 * 2048 * 768 / short < 0.15


def test_causal_attention_cost_by_hand():
    b, h, t, d = 2, 12, 8192, 64
    matmul = 2 * b * h * t * t * d / 2
    tensor = b * h * t * d * 2
    rows = 2 * b * h * t * 4
    got = flops.causal_attention_cost(b, h, t, d, layers=12, forward_calls=2)
    assert got == (12 * 9 * matmul, 12 * (2 * (4 * tensor + rows)
                                          + 8 * tensor + rows))
    once = flops.causal_attention_cost(b, h, t, d)
    assert once[0] == 7 * matmul
