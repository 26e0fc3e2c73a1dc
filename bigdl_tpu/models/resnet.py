"""ResNet for ImageNet and CIFAR-10.

Parity: DL/models/resnet/ResNet.scala — basic/bottleneck blocks, ImageNet
(50/101/152 via bottleneck) and CIFAR (basicBlock, depth 6n+2) variants,
optionConvolution shortcut types A/B/C, and the zero-init-of-last-BN-gamma
trick from the reference's ImageNet training recipe
(DL/models/resnet/TrainImageNet.scala). NHWC throughout; blocks are built on
the Graph container so the residual add is a CAddTable like the reference.
"""

from __future__ import annotations

from typing import Optional

import jax.numpy as jnp

import bigdl_tpu.nn as nn
from bigdl_tpu.nn.initialization import MsraFiller, Zeros


def _conv(n_in, n_out, k, stride=1, pad=None, name=None):
    if pad is None:
        pad = (k - 1) // 2
    return nn.SpatialConvolution(
        n_in, n_out, k, k, stride, stride, pad_w=pad, pad_h=pad,
        with_bias=False, weight_init=MsraFiller(), name=name)


def _bn(n, zero_gamma=False, name=None):
    bn = nn.SpatialBatchNormalization(n, name=name)
    if zero_gamma:
        # reference TrainImageNet zeroes the last BN gamma of each block so
        # residual branches start as identity
        orig_init = bn.init

        def init(rng):
            p = orig_init(rng)
            p["weight"] = jnp.zeros_like(p["weight"])
            return p

        bn.init = init
    return bn


def _shortcut(n_in, n_out, stride, shortcut_type="B"):
    if n_in != n_out or stride != 1:
        if shortcut_type in ("B", "C"):
            return (nn.Sequential()
                    .add(_conv(n_in, n_out, 1, stride, 0))
                    .add(_bn(n_out)))
        # type A: identity with zero-padded channels (CIFAR paper variant)
        return (nn.Sequential()
                .add(nn.SpatialAveragePooling(stride, stride, stride, stride))
                .add(_PadChannels(n_out - n_in)))
    return nn.Identity()


class _PadChannels(nn.Module):
    def __init__(self, extra: int, name=None):
        super().__init__(name)
        self.extra = extra

    def apply(self, params, input, ctx):
        return jnp.pad(input, ((0, 0), (0, 0), (0, 0), (0, self.extra)))


def basic_block(n_in, n_out, stride=1, shortcut_type="B", zero_gamma=True):
    main = (nn.Sequential()
            .add(_conv(n_in, n_out, 3, stride))
            .add(_bn(n_out))
            .add(nn.ReLU())
            .add(_conv(n_out, n_out, 3, 1))
            .add(_bn(n_out, zero_gamma=zero_gamma)))
    return (nn.Sequential()
            .add(nn.ConcatTable().add(main).add(_shortcut(n_in, n_out, stride, shortcut_type)))
            .add(nn.CAddTable())
            .add(nn.ReLU()))


def bottleneck(n_in, n_mid, stride=1, shortcut_type="B", zero_gamma=True,
               expansion=4):
    n_out = n_mid * expansion
    main = (nn.Sequential()
            .add(_conv(n_in, n_mid, 1, 1, 0))
            .add(_bn(n_mid))
            .add(nn.ReLU())
            .add(_conv(n_mid, n_mid, 3, stride))
            .add(_bn(n_mid))
            .add(nn.ReLU())
            .add(_conv(n_mid, n_out, 1, 1, 0))
            .add(_bn(n_out, zero_gamma=zero_gamma)))
    return (nn.Sequential()
            .add(nn.ConcatTable().add(main).add(_shortcut(n_in, n_out, stride, shortcut_type)))
            .add(nn.CAddTable())
            .add(nn.ReLU()))


_IMAGENET_CFG = {
    18: ("basic", [2, 2, 2, 2]),
    34: ("basic", [3, 4, 6, 3]),
    50: ("bottleneck", [3, 4, 6, 3]),
    101: ("bottleneck", [3, 4, 23, 3]),
    152: ("bottleneck", [3, 8, 36, 3]),
}


def ResNet(class_num: int = 1000, depth: int = 50, shortcut_type: str = "B",
           data_set: str = "ImageNet", zero_gamma: bool = True,
           remat: bool = False, s2d_stem: bool = False) -> nn.Sequential:
    """Reference ResNet.apply (DL/models/resnet/ResNet.scala).

    remat=True wraps every residual block in `nn.Remat`
    (jax.checkpoint): backward-pass activations are recomputed instead
    of stored, cutting peak HBM ~linearly in depth — enables larger
    per-chip batches on TPU at ~1.3x step FLOPs.

    s2d_stem=True computes conv1 through the 2x2 space-to-depth
    reformulation (`nn.SpaceToDepthStemConvolution`) — bit-for-bit the
    same parameter tree and the same math, restated so the 7x7/s2
    3-channel stem tiles the MXU well (the standard TPU ResNet trick)."""
    if data_set.lower() in ("cifar10", "cifar-10"):
        return _cifar_resnet(class_num, depth, shortcut_type)
    kind, reps = _IMAGENET_CFG[depth]
    widths = [64, 128, 256, 512]
    stem = (nn.SpaceToDepthStemConvolution(3, 64, 7, weight_init=MsraFiller(),
                                           name="conv1")
            if s2d_stem else _conv(3, 64, 7, 2, 3, name="conv1"))
    model = (nn.Sequential(name=f"ResNet{depth}")
             .scope("stem")
             .add(stem)
             .add(_bn(64))
             .add(nn.ReLU())
             .add(nn.SpatialMaxPooling(3, 3, 2, 2, pad_w=1, pad_h=1)))
    n_in = 64
    for stage, (w, r) in enumerate(zip(widths, reps)):
        model.scope(f"stage {stage + 1}")
        for i in range(r):
            stride = 2 if (stage > 0 and i == 0) else 1
            if kind == "bottleneck":
                block = bottleneck(n_in, w, stride, shortcut_type,
                                   zero_gamma)
                n_in = w * 4
            else:
                block = basic_block(n_in, w, stride, shortcut_type,
                                    zero_gamma)
                n_in = w
            model.add(nn.Remat(block) if remat else block)
    model.scope("classifier")
    model.add(nn.Pooler())  # global average pool -> [B, C]
    model.add(nn.Linear(n_in, class_num, name="fc"))
    model.add(nn.LogSoftMax())
    return model


def _cifar_resnet(class_num: int, depth: int, shortcut_type: str = "A"):
    assert (depth - 2) % 6 == 0, "CIFAR depth must be 6n+2"
    n = (depth - 2) // 6
    model = (nn.Sequential(name=f"ResNet{depth}-CIFAR")
             .add(_conv(3, 16, 3, 1))
             .add(_bn(16))
             .add(nn.ReLU()))
    n_in = 16
    for stage, w in enumerate([16, 32, 64]):
        for i in range(n):
            stride = 2 if (stage > 0 and i == 0) else 1
            model.add(basic_block(n_in, w, stride, shortcut_type))
            n_in = w
    model.add(nn.Pooler())
    model.add(nn.Linear(64, class_num))
    model.add(nn.LogSoftMax())
    return model


def ResNet50(class_num: int = 1000, **kw) -> nn.Sequential:
    return ResNet(class_num, depth=50, **kw)
