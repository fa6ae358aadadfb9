"""ResNet v1.5 — the reference's headline benchmark model
(BASELINE.json config[1]: ResNet-50/ImageNet, ExponentialTwoGraph,
DistributedNeighborAllreduceOptimizer; north-star metric images/sec/chip).

TPU-first choices: NHWC layout (XLA's native conv layout on TPU), bf16
compute with f32 BatchNorm statistics and f32 final logits, 3x3/1x1 convs
that tile cleanly onto the 128x128 MXU.
"""

from __future__ import annotations

from functools import partial
from typing import Any, Callable, Sequence, Tuple

import flax.linen as nn
import jax.numpy as jnp

from bluefog_tpu.tracing import startup

ModuleDef = Any


def space_to_depth(x, block: int = 2):
    """Fold ``block x block`` spatial tiles into channels: [N,H,W,C] ->
    [N,H/b,W/b,C*b*b].

    The TPU stem trick (used by the MLPerf ResNet submissions): the raw
    ImageNet input has C=3, so the 7x7/s2 stem conv feeds the 128-lane MXU
    at 3/128 occupancy.  Space-to-depth quadruples the contraction depth
    (12 channels) and halves the spatial extent, and the 7x7/s2 conv is
    replaced by an exactly-equivalent 4x4/s1 conv on the folded input
    (see :func:`s2d_stem_kernel_from_7x7` for the constructive proof).
    Channel order within a tile is (row a, col b, channel c) ->
    (a*block + b)*C + c.
    """
    n, h, w, c = x.shape
    x = x.reshape(n, h // block, block, w // block, block, c)
    x = x.transpose(0, 1, 3, 2, 4, 5)
    return x.reshape(n, h // block, w // block, block * block * c)


def s2d_stem_kernel_from_7x7(w7):
    """Embed a [7,7,C,K] stride-2 stem kernel as the [4,4,4C,K] stride-1
    kernel that computes the IDENTICAL function on space-to-depth(2) input
    with spatial padding (2, 1).

    Derivation: with z[p, (a*2+b)*C + c] = x[2p+a, 2q+b, c] and the s2d conv
    y[i] = sum_{dp=0..3} W'[dp, ...] z[i+dp-2], each tap reads
    x[2i + 2dp + a - 4], while the original stride-2 conv with padding 3
    reads x[2i + di - 3]; matching gives di = 2dp + a - 1, a bijection from
    (dp, a) in {0..3}x{0,1} onto di in {-1..6} — the single di = -1 slot is
    zero-filled.  Used by the equivalence test; training simply learns the
    4x4 kernel directly (a superset: the zero slot is trainable, giving an
    8x8/s2 effective receptive field).
    """
    import numpy as np

    w7 = np.asarray(w7)
    kh, kw, c, k = w7.shape
    assert (kh, kw) == (7, 7), w7.shape
    w4 = np.zeros((4, 4, 4 * c, k), w7.dtype)
    for dp in range(4):
        for a in range(2):
            di = 2 * dp + a - 1
            if not 0 <= di < 7:
                continue
            for dq in range(4):
                for b in range(2):
                    dj = 2 * dq + b - 1
                    if not 0 <= dj < 7:
                        continue
                    w4[dp, dq, (a * 2 + b) * c:(a * 2 + b) * c + c, :] = w7[di, dj]
    return w4


class ResNetBlock(nn.Module):
    """Basic block (ResNet-18/34)."""

    filters: int
    conv: ModuleDef
    norm: ModuleDef
    strides: Tuple[int, int] = (1, 1)

    @nn.compact
    def __call__(self, x):
        residual = x
        y = self.conv(self.filters, (3, 3), self.strides)(x)
        y = self.norm()(y)
        y = nn.relu(y)
        y = self.conv(self.filters, (3, 3))(y)
        y = self.norm(scale_init=nn.initializers.zeros)(y)
        if residual.shape != y.shape:
            residual = self.conv(self.filters, (1, 1), self.strides, name="conv_proj")(residual)
            residual = self.norm(name="norm_proj")(residual)
        return nn.relu(residual + y)


class BottleneckBlock(nn.Module):
    """Bottleneck block (ResNet-50/101/152)."""

    filters: int
    conv: ModuleDef
    norm: ModuleDef
    strides: Tuple[int, int] = (1, 1)

    @nn.compact
    @startup.spanned("bf.setup.trace.block", "bottleneck")
    def __call__(self, x):
        residual = x
        y = self.conv(self.filters, (1, 1))(x)
        y = self.norm()(y)
        y = nn.relu(y)
        y = self.conv(self.filters, (3, 3), self.strides)(y)
        y = self.norm()(y)
        y = nn.relu(y)
        y = self.conv(self.filters * 4, (1, 1))(y)
        # zero-init the last norm's scale: residual branch starts as identity
        y = self.norm(scale_init=nn.initializers.zeros)(y)
        if residual.shape != y.shape:
            residual = self.conv(self.filters * 4, (1, 1), self.strides, name="conv_proj")(residual)
            residual = self.norm(name="norm_proj")(residual)
        return nn.relu(residual + y)


class ResNet(nn.Module):
    stage_sizes: Sequence[int]
    block_cls: ModuleDef
    num_classes: int = 1000
    num_filters: int = 64
    dtype: jnp.dtype = jnp.bfloat16
    # "conv" = reference 7x7/s2 + maxpool (ImageNet); "s2d" = space-to-depth
    # 4x4/s1 MXU-friendly equivalent; "cifar" = 3x3/s1, no maxpool (the
    # standard small-image stem — 32x32 inputs keep a 4x4 final map after
    # the three stage strides instead of collapsing to 1x1 under the
    # ImageNet stem's extra /4)
    stem: str = "conv"

    @nn.compact
    def __call__(self, x, train: bool = True):
        conv = partial(nn.Conv, use_bias=False, dtype=self.dtype)
        norm = partial(
            nn.BatchNorm,
            use_running_average=not train,
            momentum=0.9,
            epsilon=1e-5,
            dtype=self.dtype,
            param_dtype=jnp.float32,
        )
        x = x.astype(self.dtype)
        if self.stem == "s2d":
            # MXU-friendly stem: fold 2x2 tiles into channels (3 -> 12 input
            # lanes) and convolve 4x4/s1 — same function class as the 7x7/s2
            # stem (s2d_stem_kernel_from_7x7 embeds any 7x7 kernel exactly).
            # Accepts raw [N,H,W,3] (folds here; XLA fuses the reshape) or
            # pre-folded [N,H/2,W/2,12] from the data pipeline.
            if x.shape[-1] == 3:
                if x.shape[1] % 2 or x.shape[2] % 2:
                    raise ValueError(
                        "s2d stem needs even H and W to fold 2x2 tiles; got "
                        f"{x.shape[1]}x{x.shape[2]}")
                x = space_to_depth(x, 2)
            elif x.shape[-1] != 12:
                # any other channel count would silently skip folding and run
                # the 4x4/s1 conv at full resolution — different stride and
                # receptive field than the 7x7/s2 stem it stands in for
                raise ValueError(
                    "s2d stem accepts raw [N,H,W,3] or pre-folded "
                    f"[N,H/2,W/2,12] input; got C={x.shape[-1]}")
            x = conv(self.num_filters, (4, 4), (1, 1),
                     padding=[(2, 1), (2, 1)], name="conv_init")(x)
        elif self.stem == "cifar":
            x = conv(self.num_filters, (3, 3), (1, 1),
                     padding=[(1, 1), (1, 1)], name="conv_init")(x)
        else:
            x = conv(self.num_filters, (7, 7), (2, 2), padding=[(3, 3), (3, 3)], name="conv_init")(x)
        x = norm(name="bn_init")(x)
        x = nn.relu(x)
        if self.stem != "cifar":
            x = nn.max_pool(x, (3, 3), strides=(2, 2), padding=((1, 1), (1, 1)))
        for i, block_size in enumerate(self.stage_sizes):
            for j in range(block_size):
                strides = (2, 2) if i > 0 and j == 0 else (1, 1)
                x = self.block_cls(
                    self.num_filters * 2**i, conv=conv, norm=norm, strides=strides
                )(x)
        x = jnp.mean(x, axis=(1, 2))
        x = nn.Dense(self.num_classes, dtype=jnp.float32, name="head")(x)
        return x


ResNet18 = partial(ResNet, stage_sizes=[2, 2, 2, 2], block_cls=ResNetBlock)
ResNet50 = partial(ResNet, stage_sizes=[3, 4, 6, 3], block_cls=BottleneckBlock)
ResNet101 = partial(ResNet, stage_sizes=[3, 4, 23, 3], block_cls=BottleneckBlock)
