"""Registry-driven serialization round-trip sweep.

The reference round-trips EVERY registered module through its serializer via
a reflection-driven spec (TEST/utils/serializer/, e.g.
ModuleSerializerSpec.scala): for each class it builds an instance, runs
forward, saves, reloads, and compares. This file is that sweep for the TPU
build: `registered_modules()` is the source of truth, every name must either
round-trip here or appear in SKIP with a justification — a newly registered
module that does neither fails the sweep.
"""

import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import bigdl_tpu.nn as nn
import bigdl_tpu.ops as ops
import bigdl_tpu.keras as keras
import bigdl_tpu.interop  # registers the TF loader-internal modules
from bigdl_tpu.serialization.module_serializer import (ModuleSerializer,
                                                       registered_modules)
from bigdl_tpu.utils.table import Table

# ---------------------------------------------------------------- inputs
VEC = np.linspace(-1.0, 1.0, 8).astype(np.float32)
MAT = np.linspace(-1.0, 1.0, 8).reshape(2, 4).astype(np.float32)
POS = (np.abs(MAT) + 0.1).astype(np.float32)
SEQ = np.linspace(-1.0, 1.0, 40).reshape(2, 5, 4).astype(np.float32)
IMG = np.linspace(-1.0, 1.0, 2 * 8 * 8 * 3).reshape(2, 8, 8, 3).astype(
    np.float32)
VID = np.linspace(-1.0, 1.0, 2 * 4 * 8 * 8 * 3).reshape(2, 4, 8, 8, 3).astype(
    np.float32)
IDS = np.array([[1, 2], [3, 4]], np.float32)  # 1-based lookup ids
PAIR = Table(MAT.copy(), (MAT * 0.5 + 0.1).astype(np.float32))

CANDIDATES = [MAT, SEQ, IMG, VID, VEC, PAIR, IDS]


def _t(x):
    def conv(a):
        a = np.asarray(a)
        if a.dtype.kind in ("U", "S", "O"):
            return a  # string columns stay host-side (feature-col ops)
        return jnp.asarray(a)
    return jax.tree_util.tree_map(conv, x) if isinstance(x, Table) else conv(x)


# ------------------------------------------------- explicit constructions
# (factory, input) for classes whose ctor needs arguments. Grouped by
# family; shapes chosen small. Inputs are numpy (or Table of numpy).
SPECS = {
    # linear / embedding family
    "Linear": (lambda: nn.Linear(4, 3), MAT),
    "Bilinear": (lambda: nn.Bilinear(4, 4, 3), PAIR),
    "SparseLinear": (lambda: nn.SparseLinear(4, 3), MAT),
    "LookupTable": (lambda: nn.LookupTable(10, 4), IDS),
    "LookupTableSparse": (lambda: nn.LookupTableSparse(10, 4), IDS),
    "CMul": (lambda: nn.CMul([4]), MAT),
    "CAdd": (lambda: nn.CAdd([4]), MAT),
    "Mul": (lambda: nn.Mul(), MAT),
    "Add": (lambda: nn.Add(4), MAT),
    "Cosine": (lambda: nn.Cosine(4, 3), MAT),
    "Euclidean": (lambda: nn.Euclidean(4, 3), MAT),
    "Maxout": (lambda: nn.Maxout(4, 3, 2), MAT),
    "PReLU": (lambda: nn.PReLU(1), MAT),
    "SReLU": (lambda: nn.SReLU((4,)), MAT),
    "Highway": (lambda: nn.Highway(4), MAT),

    # convolution family (NHWC)
    "SpatialConvolution": (lambda: nn.SpatialConvolution(3, 4, 3, 3), IMG),
    "SpaceToDepthStemConvolution": (
        lambda: nn.SpaceToDepthStemConvolution(3, 4, 3), IMG),
    "SpatialShareConvolution": (
        lambda: nn.SpatialShareConvolution(3, 4, 3, 3), IMG),
    "SpatialDilatedConvolution": (
        lambda: nn.SpatialDilatedConvolution(3, 4, 3, 3, dilation_w=2,
                                             dilation_h=2), IMG),
    "SpatialFullConvolution": (
        lambda: nn.SpatialFullConvolution(3, 4, 3, 3), IMG),
    "SpatialSeparableConvolution": (
        lambda: nn.SpatialSeparableConvolution(3, 6, 2, 3, 3), IMG),
    "SpatialConvolutionMap": (
        lambda: nn.SpatialConvolutionMap(nn.SpatialConvolutionMap.full(3, 4),
                                         3, 3), IMG),
    "DepthwiseConv2D": (lambda: ops.DepthwiseConv2D(), Table(
        IMG.copy(), np.ones((3, 3, 3, 1), np.float32))),
    "TemporalConvolution": (lambda: nn.TemporalConvolution(4, 6, 2), SEQ),
    "TemporalMaxPooling": (lambda: nn.TemporalMaxPooling(2), SEQ),
    "VolumetricConvolution": (
        lambda: nn.VolumetricConvolution(3, 4, 2, 2, 2), VID),
    "VolumetricFullConvolution": (
        lambda: nn.VolumetricFullConvolution(3, 4, 2, 2, 2), VID),
    "VolumetricMaxPooling": (
        lambda: nn.VolumetricMaxPooling(2, 2, 2, 2, 2, 2), VID),
    "VolumetricAveragePooling": (
        lambda: nn.VolumetricAveragePooling(2, 2, 2, 2, 2, 2), VID),
    "Dilation2D": (lambda: ops.Dilation2D(), Table(
        IMG.copy(), np.ones((2, 2, 3), np.float32))),

    # pooling / norm
    "SpatialMaxPooling": (lambda: nn.SpatialMaxPooling(2, 2, 2, 2), IMG),
    "SpatialAveragePooling": (lambda: nn.SpatialAveragePooling(2, 2, 2, 2),
                              IMG),
    "BatchNormalization": (lambda: nn.BatchNormalization(4), MAT),
    "SpatialBatchNormalization": (lambda: nn.SpatialBatchNormalization(3),
                                  IMG),
    "LayerNormalization": (lambda: nn.LayerNormalization(4), MAT),
    "RMSNorm": (lambda: nn.RMSNorm(4), MAT),
    "SpatialCrossMapLRN": (lambda: nn.SpatialCrossMapLRN(), IMG),
    "SpatialWithinChannelLRN": (lambda: nn.SpatialWithinChannelLRN(), IMG),
    "SpatialContrastiveNormalization": (
        lambda: nn.SpatialContrastiveNormalization(3), IMG),
    "SpatialDivisiveNormalization": (
        lambda: nn.SpatialDivisiveNormalization(3), IMG),
    "SpatialSubtractiveNormalization": (
        lambda: nn.SpatialSubtractiveNormalization(3), IMG),
    "Normalize": (lambda: nn.Normalize(2.0), MAT),
    "NormalizeScale": (lambda: nn.NormalizeScale(2.0, size=(3,)), IMG),
    "Scale": (lambda: nn.Scale([4]), MAT),

    # shape ops
    "Reshape": (lambda: nn.Reshape([4]), np.ones((3, 2, 2), np.float32)),
    "View": (lambda: nn.View([4]), np.ones((3, 2, 2), np.float32)),
    "InferReshape": (lambda: nn.InferReshape([-1, 2]), MAT),
    "Transpose": (lambda: nn.Transpose([(1, 2)]), SEQ),
    "Squeeze": (lambda: nn.Squeeze(1), np.ones((2, 1, 4), np.float32)),
    "Unsqueeze": (lambda: nn.Unsqueeze(1), MAT),
    "Select": (lambda: nn.Select(1, 1), SEQ),
    "Narrow": (lambda: nn.Narrow(1, 1, 2), SEQ),
    "Index": (lambda: nn.Index(1), Table(
        MAT.copy(), np.array([1, 2], np.float32))),
    "MaskedSelect": (lambda: nn.MaskedSelect(), Table(
        MAT.copy(), (MAT > 0).astype(np.float32))),
    "Padding": (lambda: nn.Padding(1, 2, 2), MAT),
    "SpatialZeroPadding": (lambda: nn.SpatialZeroPadding(1, 1, 1, 1), IMG),
    "Replicate": (lambda: nn.Replicate(3), MAT),
    "Contiguous": (lambda: nn.Contiguous(), MAT),
    "GradientReversal": (lambda: nn.GradientReversal(), MAT),
    "Reverse": (lambda: nn.Reverse(1), SEQ),

    # table ops
    "ConcatTable": (lambda: nn.ConcatTable().add(nn.Linear(4, 2))
                    .add(nn.Identity()), MAT),
    "ParallelTable": (lambda: nn.ParallelTable().add(nn.Linear(4, 2))
                      .add(nn.Linear(4, 2)), PAIR),
    "MapTable": (lambda: nn.MapTable().add(nn.Linear(4, 2)), PAIR),
    "JoinTable": (lambda: nn.JoinTable(axis=1), PAIR),
    "SelectTable": (lambda: nn.SelectTable(1), PAIR),
    "NarrowTable": (lambda: nn.NarrowTable(1, 2), PAIR),
    "FlattenTable": (lambda: nn.FlattenTable(), PAIR),
    "SplitTable": (lambda: nn.SplitTable(1), SEQ),
    "BifurcateSplitTable": (lambda: nn.BifurcateSplitTable(1), MAT),
    "SplitAndSelect": (lambda: ops.SplitAndSelect(1, 0, 2), MAT),
    "MixtureTable": (lambda: nn.MixtureTable(), Table(
        np.abs(MAT[:, :2]) / np.abs(MAT[:, :2]).sum(1, keepdims=True),
        Table(MAT.copy(), MAT.copy()))),
    "MM": (lambda: nn.MM(), Table(MAT.copy(), MAT.T.copy())),
    "MV": (lambda: nn.MV(), Table(
        np.ones((2, 3, 4), np.float32), np.ones((2, 4), np.float32))),
    "DotProduct": (lambda: nn.DotProduct(), PAIR),
    "CosineDistance": (lambda: nn.CosineDistance(), PAIR),
    "PairwiseDistance": (lambda: nn.PairwiseDistance(), PAIR),
    "CrossProduct": (lambda: nn.CrossProduct(), Table(
        MAT.copy(), MAT.copy(), MAT.copy())),

    # containers / graph
    "Sequential": (lambda: nn.Sequential().add(nn.Linear(4, 3))
                   .add(nn.Tanh()), MAT),
    "Concat": (lambda: nn.Concat(1).add(nn.Linear(4, 2))
               .add(nn.Linear(4, 3)), MAT),
    "Bottle": (lambda: nn.Bottle(nn.Linear(4, 2)), SEQ),
    "Remat": (lambda: nn.Remat(nn.Linear(4, 2)), MAT),
    "TimeDistributed": (lambda: nn.TimeDistributed(nn.Linear(4, 2)), SEQ),

    # recurrent
    "Recurrent": (lambda: nn.Recurrent(nn.LSTMCell(4, 3)), SEQ),
    "BiRecurrent": (lambda: nn.BiRecurrent(nn.GRUCell(4, 3)), SEQ),
    "RecurrentDecoder": (
        lambda: nn.RecurrentDecoder(nn.LSTMCell(4, 4), 3), MAT),
    "RnnCell": (lambda: nn.Recurrent(nn.RnnCell(4, 3)), SEQ),
    "LSTMCell": (lambda: nn.Recurrent(nn.LSTMCell(4, 3)), SEQ),
    "LSTM": (lambda: nn.Recurrent(nn.LSTM(4, 3)), SEQ),
    "LSTM2": (lambda: nn.Recurrent(nn.LSTM2(4, 3)), SEQ),
    "GRUCell": (lambda: nn.Recurrent(nn.GRUCell(4, 3)), SEQ),
    "GRU": (lambda: nn.Recurrent(nn.GRU(4, 3)), SEQ),
    "LSTMPeephole": (lambda: nn.Recurrent(nn.LSTMPeephole(4, 3)), SEQ),
    "LSTMPeepholeCell": (
        lambda: nn.Recurrent(nn.LSTMPeepholeCell(4, 3)), SEQ),
    "MultiRNNCell": (lambda: nn.Recurrent(nn.MultiRNNCell(
        [nn.LSTMCell(4, 4), nn.GRUCell(4, 3)])), SEQ),
    "ConvLSTMPeephole": (lambda: nn.Recurrent(
        nn.ConvLSTMPeephole(3, 4)), np.ones((2, 3, 6, 6, 3), np.float32)),
    "ConvLSTMPeephole3D": (lambda: nn.Recurrent(
        nn.ConvLSTMPeephole3D(3, 4)),
        np.ones((2, 2, 4, 4, 4, 3), np.float32)),

    # attention / transformer
    "MultiHeadAttention": (
        lambda: nn.MultiHeadAttention(8, 2), np.ones((2, 5, 8), np.float32)),
    "GroupedQueryAttention": (
        lambda: nn.GroupedQueryAttention(8, 4, 2, 4, window=3,
                                         rope_base=1e4),
        np.ones((2, 5, 8), np.float32)),
    "GatedDeltaRule": (
        lambda: nn.GatedDeltaRule(8, 2, 4, 4, chunk=4),
        np.ones((2, 5, 8), np.float32)),
    "LatentAttention": (
        lambda: nn.LatentAttention(8, 2, 4, 2, 4, 6, rope_base=1e4),
        np.ones((2, 5, 8), np.float32)),
    "ShortConv": (
        lambda: nn.ShortConv(8, 3), np.ones((2, 5, 8), np.float32)),
    "ScaledDotProductAttention": (
        lambda: nn.ScaledDotProductAttention(), Table(
            np.ones((2, 2, 5, 4), np.float32), np.ones((2, 2, 5, 4), np.float32),
            np.ones((2, 2, 5, 4), np.float32))),
    "TransformerBlock": (lambda: nn.TransformerBlock(8, 2, 16),
                         np.ones((2, 5, 8), np.float32)),
    "Pooler": (lambda: nn.Pooler(), IMG),
    "Masking": (lambda: nn.Masking(0.0), SEQ),

    # tree (sentence of 2 leaves + root; test_detection.py convention)
    "TreeLSTM": (lambda: nn.BinaryTreeLSTM(4, 3), Table(
        np.ones((1, 2, 4), np.float32),
        np.array([[[0, 0, 1], [0, 0, 2], [1, 2, 0]]], np.int32))),
    "BinaryTreeLSTM": (lambda: nn.BinaryTreeLSTM(4, 3), Table(
        np.ones((1, 2, 4), np.float32),
        np.array([[[0, 0, 1], [0, 0, 2], [1, 2, 0]]], np.int32))),

    # elementwise with args
    "AddConstant": (lambda: nn.AddConstant(1.5), MAT),
    "MulConstant": (lambda: nn.MulConstant(2.0), MAT),
    "Power": (lambda: nn.Power(2.0), POS),
    "Clamp": (lambda: nn.Clamp(-0.5, 0.5), MAT),
    "HardTanh": (lambda: nn.HardTanh(), MAT),
    "Threshold": (lambda: nn.Threshold(0.0), MAT),
    "BinaryThreshold": (lambda: nn.BinaryThreshold(0.0), MAT),
    "ELU": (lambda: nn.ELU(), MAT),
    "LeakyReLU": (lambda: nn.LeakyReLU(), MAT),
    "RReLU": (lambda: nn.RReLU(), MAT),  # eval mode: deterministic
    "SoftShrink": (lambda: nn.SoftShrink(), MAT),
    "HardShrink": (lambda: nn.HardShrink(), MAT),
    "SoftMin": (lambda: nn.SoftMin(), MAT),

    # reductions with args
    "Sum": (lambda: nn.Sum(1), MAT),
    "Mean": (lambda: nn.Mean(1), MAT),
    "Max": (lambda: nn.Max(1), MAT),
    "Min": (lambda: nn.Min(1), MAT),

    # dropout / noise (eval mode => deterministic identity-ish)
    "Dropout": (lambda: nn.Dropout(0.5), MAT),
    "GaussianDropout": (lambda: nn.GaussianDropout(0.5), MAT),
    "GaussianNoise": (lambda: nn.GaussianNoise(0.5), MAT),
    "SpatialDropout1D": (lambda: nn.SpatialDropout1D(0.5), SEQ),
    "SpatialDropout2D": (lambda: nn.SpatialDropout2D(0.5), IMG),
    "SpatialDropout3D": (lambda: nn.SpatialDropout3D(0.5), VID),
    "GaussianSampler": (lambda: nn.GaussianSampler(), PAIR),

    # misc
    "Echo": (lambda: nn.Echo(), MAT),
    "RoiPooling": (lambda: nn.RoiPooling(2, 2, 1.0), Table(
        IMG.copy(), np.array([[1, 0, 0, 4, 4]], np.float32))),
    "PriorBox": (lambda: nn.PriorBox([8.0], img_h=16, img_w=16), IMG),
    "Nms": (lambda: nn.Nms(0.5), Table(
        np.array([[0, 0, 2, 2], [0, 0, 2.1, 2.1], [5, 5, 7, 7]], np.float32),
        np.array([0.9, 0.8, 0.7], np.float32))),
}

# keras-API layers (constructed standalone via input_shape=)
SPECS.update({
    "Dense": (lambda: keras.Dense(3, input_shape=(4,)), MAT),
    "Embedding": (lambda: keras.Embedding(10, 4, input_shape=(2,)), IDS),
    "Flatten": (lambda: keras.Flatten(input_shape=(5, 4)), SEQ),
    "Permute": (lambda: keras.Permute((2, 1), input_shape=(5, 4)), SEQ),
    "RepeatVector": (lambda: keras.RepeatVector(3, input_shape=(4,)), MAT),
    "ThresholdedReLU": (lambda: keras.ThresholdedReLU(0.5,
                                                      input_shape=(4,)), MAT),
    "MaxoutDense": (lambda: keras.MaxoutDense(3, input_shape=(4,)), MAT),
    "Convolution1D": (lambda: keras.Convolution1D(4, 2,
                                                  input_shape=(5, 4)), SEQ),
    "Convolution2D": (
        lambda: keras.Convolution2D(4, 3, 3, input_shape=(8, 8, 3)), IMG),
    "Convolution3D": (
        lambda: keras.Convolution3D(4, 2, 2, 2,
                                    input_shape=(4, 8, 8, 3)), VID),
    "AtrousConvolution1D": (
        lambda: keras.AtrousConvolution1D(4, 2, atrous_rate=2,
                                          input_shape=(5, 4)), SEQ),
    "AtrousConvolution2D": (
        lambda: keras.AtrousConvolution2D(4, 3, 3, atrous_rate=(2, 2),
                                          input_shape=(8, 8, 3)), IMG),
    "Deconvolution2D": (
        lambda: keras.Deconvolution2D(4, 3, 3, input_shape=(8, 8, 3)), IMG),
    "SeparableConvolution2D": (
        lambda: keras.SeparableConvolution2D(6, 3, 3,
                                             input_shape=(8, 8, 3)), IMG),
    "LocallyConnected1D": (
        lambda: keras.LocallyConnected1D(4, 2, input_shape=(5, 4)), SEQ),
    "LocallyConnected2D": (
        lambda: keras.LocallyConnected2D(4, 3, 3,
                                         input_shape=(8, 8, 3)), IMG),
    "MaxPooling1D": (lambda: keras.MaxPooling1D(input_shape=(5, 4)), SEQ),
    "MaxPooling2D": (lambda: keras.MaxPooling2D(input_shape=(8, 8, 3)), IMG),
    "MaxPooling3D": (
        lambda: keras.MaxPooling3D(input_shape=(4, 8, 8, 3)), VID),
    "AveragePooling1D": (
        lambda: keras.AveragePooling1D(input_shape=(5, 4)), SEQ),
    "AveragePooling2D": (
        lambda: keras.AveragePooling2D(input_shape=(8, 8, 3)), IMG),
    "AveragePooling3D": (
        lambda: keras.AveragePooling3D(input_shape=(4, 8, 8, 3)), VID),
    "GlobalMaxPooling1D": (
        lambda: keras.GlobalMaxPooling1D(input_shape=(5, 4)), SEQ),
    "GlobalMaxPooling2D": (
        lambda: keras.GlobalMaxPooling2D(input_shape=(8, 8, 3)), IMG),
    "GlobalMaxPooling3D": (
        lambda: keras.GlobalMaxPooling3D(input_shape=(4, 8, 8, 3)), VID),
    "GlobalAveragePooling1D": (
        lambda: keras.GlobalAveragePooling1D(input_shape=(5, 4)), SEQ),
    "GlobalAveragePooling2D": (
        lambda: keras.GlobalAveragePooling2D(input_shape=(8, 8, 3)), IMG),
    "GlobalAveragePooling3D": (
        lambda: keras.GlobalAveragePooling3D(input_shape=(4, 8, 8, 3)), VID),
    "Cropping1D": (lambda: keras.Cropping1D(input_shape=(5, 4)), SEQ),
    "UpSampling2D": (lambda: keras.UpSampling2D(input_shape=(8, 8, 3)), IMG),
    "UpSampling3D": (
        lambda: keras.UpSampling3D(input_shape=(4, 8, 8, 3)), VID),
    "ZeroPadding1D": (lambda: keras.ZeroPadding1D(input_shape=(5, 4)), SEQ),
    "ZeroPadding2D": (lambda: keras.ZeroPadding2D(input_shape=(8, 8, 3)),
                      IMG),
    "ZeroPadding3D": (
        lambda: keras.ZeroPadding3D(input_shape=(4, 8, 8, 3)), VID),
    "SimpleRNN": (lambda: keras.SimpleRNN(3, input_shape=(5, 4)), SEQ),
    "ConvLSTM2D": (lambda: keras.ConvLSTM2D(4, 3, input_shape=(3, 6, 6, 3)),
                   np.ones((2, 3, 6, 6, 3), np.float32)),
    "Bidirectional": (
        lambda: keras.Bidirectional(keras.LSTM(3), input_shape=(5, 4)), SEQ),
    "RNN": (lambda: nn.Recurrent(nn.RnnCell(4, 3)), SEQ),
})

# dotted keras.* aliases (plain name taken by the nn/torch-style class)
SPECS.update({
    "keras.BatchNormalization": (
        lambda: keras.BatchNormalization(input_shape=(4,)), MAT),
    "keras.Cropping2D": (lambda: keras.Cropping2D(input_shape=(8, 8, 3)),
                         IMG),
    "keras.Cropping3D": (
        lambda: keras.Cropping3D(input_shape=(4, 8, 8, 3)), VID),
    "keras.Dropout": (lambda: keras.Dropout(0.5, input_shape=(4,)), MAT),
    "keras.ELU": (lambda: keras.ELU(input_shape=(4,)), MAT),
    "keras.GRU": (lambda: keras.GRU(3, input_shape=(5, 4)), SEQ),
    "keras.GaussianDropout": (
        lambda: keras.GaussianDropout(0.5, input_shape=(4,)), MAT),
    "keras.GaussianNoise": (
        lambda: keras.GaussianNoise(0.5, input_shape=(4,)), MAT),
    "keras.Highway": (lambda: keras.Highway(input_shape=(4,)), MAT),
    "keras.LSTM": (lambda: keras.LSTM(3, input_shape=(5, 4)), SEQ),
    "keras.LeakyReLU": (lambda: keras.LeakyReLU(input_shape=(4,)), MAT),
    "keras.LocallyConnected1D": (
        lambda: keras.LocallyConnected1D(4, 2, input_shape=(5, 4)), SEQ),
    "keras.LocallyConnected2D": (
        lambda: keras.LocallyConnected2D(4, 3, 3,
                                         input_shape=(8, 8, 3)), IMG),
    "keras.Masking": (lambda: keras.Masking(0.0, input_shape=(5, 4)), SEQ),
    "keras.Permute": (lambda: keras.Permute((2, 1), input_shape=(5, 4)), SEQ),
    "keras.Reshape": (lambda: keras.Reshape((8,), input_shape=(2, 4)),
                      np.ones((2, 2, 4), np.float32)),
    "keras.SReLU": (lambda: keras.SReLU(input_shape=(4,)), MAT),
    "keras.SoftMax": (lambda: keras.SoftMax(input_shape=(4,)), MAT),
    "keras.SpatialDropout1D": (
        lambda: keras.SpatialDropout1D(0.5, input_shape=(5, 4)), SEQ),
    "keras.SpatialDropout2D": (
        lambda: keras.SpatialDropout2D(0.5, input_shape=(8, 8, 3)), IMG),
    "keras.SpatialDropout3D": (
        lambda: keras.SpatialDropout3D(0.5, input_shape=(4, 8, 8, 3)), VID),
    "keras.TimeDistributed": (
        lambda: keras.TimeDistributed(keras.Dense(3), input_shape=(5, 4)),
        SEQ),
    "keras.UpSampling1D": (
        lambda: keras.UpSampling1D(input_shape=(5, 4)), SEQ),
    "keras.UpSampling2D": (
        lambda: keras.UpSampling2D(input_shape=(8, 8, 3)), IMG),
    "keras.UpSampling3D": (
        lambda: keras.UpSampling3D(input_shape=(4, 8, 8, 3)), VID),
})

# TF-style ops (Table-input conventions from the tf loaders)
_INT_IDS = np.array([[1, 2], [3, 0]], np.int32)
SPECS.update({
    "Cast": (lambda: ops.Cast("int32"), MAT),
    "InTopK": (lambda: ops.InTopK(2), Table(
        MAT.copy(), np.array([1, 2], np.int32))),
    "TopK": (lambda: ops.TopK(2), MAT),
    "OneHot": (lambda: ops.OneHot(5), _INT_IDS),
    "Pad": (lambda: ops.Pad(), Table(
        MAT.copy(), np.array([[1, 1], [0, 0]], np.int32))),
    "RangeOps": (lambda: ops.RangeOps(), Table(
        np.array(0, np.int32), np.array(8, np.int32),
        np.array(1, np.int32))),
    "ResizeBilinearOps": (lambda: ops.ResizeBilinearOps(), Table(
        IMG.copy(), np.array([4, 4], np.int32))),
    "ResizeBilinear": (lambda: nn.ResizeBilinear(4, 4), IMG),
    "Slice": (lambda: ops.Slice([0, 0], [2, 2]), MAT),
    "StridedSlice": (lambda: ops.StridedSlice([0, 0], [2, 2]), MAT),
    "Tile": (lambda: ops.Tile(), Table(
        MAT.copy(), np.array([1, 2], np.int32))),
    "RandomUniform": (lambda: ops.RandomUniform(),
                      np.array([2, 3], np.int32)),
    "RandomNormal": (lambda: ops.RandomNormal(),
                     np.array([2, 3], np.int32)),
    "TruncatedNormal": (lambda: ops.TruncatedNormal(),
                        np.array([2, 3], np.int32)),
    "BucketizedCol": (lambda: ops.BucketizedCol([0.0, 0.5]), MAT),
    "CategoricalColHashBucket": (
        lambda: ops.CategoricalColHashBucket(10),
        np.array([["a", "b"], ["c", "d"]])),
    "CategoricalColVocaList": (
        lambda: ops.CategoricalColVocaList(["a", "b", "c"]),
        np.array([["a", "b"], ["z", "c"]])),
    "CrossCol": (lambda: ops.CrossCol(10), Table(
        np.array(["a", "b"]), np.array(["x", "y"]))),
    "IndicatorCol": (lambda: ops.IndicatorCol(5), _INT_IDS),
    "Kv2Tensor": (lambda: ops.Kv2Tensor(feat_len=4),
                  np.array(["0:1.0,1:2.0", "2:3.0"])),
    "SparseJoinTable": (lambda: nn.SparseJoinTable([4, 4]), Table(
        Table(np.array([[0, 1], [2, -1]], np.int32),
              np.array([[1.0, 2.0], [3.0, 0.0]], np.float32)),
        Table(np.array([[1, -1], [0, 3]], np.int32),
              np.array([[4.0, 0.0], [5.0, 6.0]], np.float32)))),
})

# TF loader-internal modules (ctor args are plain ndarrays/ints)
from bigdl_tpu.interop._tf_modules import (_TFAxisSlice, _TFConst,
                                           _TFDilation2D, _TFDynamicReshape,
                                           _TFFill,
                                           _TFMatMul, _TFPad, _TFPermute,
                                           _TFStridedSlice, _TFTableSelect,
                                           _TFUnstack)
SPECS.update({
    "_TFConst": (lambda: _TFConst(np.ones((2, 2), np.float32)), MAT),
    "_TFPad": (lambda: _TFPad([[1, 1], [0, 0]]), MAT),
    "_TFPermute": (lambda: _TFPermute([1, 0]), MAT),
    "_TFFill": (lambda: _TFFill([2, 3]), np.array(1.5, np.float32)),
    "_TFStridedSlice": (lambda: _TFStridedSlice([0, 0], [2, 2], [1, 1]), MAT),
    "_TFUnstack": (lambda: _TFUnstack(1, 0), SEQ),
    "_TFAxisSlice": (lambda: _TFAxisSlice(1, 0, 2), SEQ),
    "_TFMatMul": (lambda: _TFMatMul(), Table(MAT.copy(), MAT.T.copy())),
    "_TFTableSelect": (lambda: _TFTableSelect(1), PAIR),
    "_TFDilation2D": (lambda: _TFDilation2D(np.ones((2, 2, 3), np.float32)),
                      IMG),
    "_TFDynamicReshape": (lambda: _TFDynamicReshape(), Table(
        MAT.copy(), np.array([4, 2], np.int32))),
})

# TF gradient ops (ops/gradients.py): Table conventions follow the TF op
# signatures; structural grads need consistent primal/cotangent shapes
_CONV_DOUT = np.ones((2, 8, 8, 4), np.float32)
_POOL_DOUT = np.ones((2, 4, 4, 3), np.float32)
SPECS.update({
    "ReluGrad": (lambda: ops.ReluGrad(), PAIR),
    "Relu6Grad": (lambda: ops.Relu6Grad(), PAIR),
    "EluGrad": (lambda: ops.EluGrad(), PAIR),
    "SoftplusGrad": (lambda: ops.SoftplusGrad(), PAIR),
    "SoftsignGrad": (lambda: ops.SoftsignGrad(), PAIR),
    "SigmoidGrad": (lambda: ops.SigmoidGrad(), PAIR),
    "TanhGrad": (lambda: ops.TanhGrad(), PAIR),
    "SqrtGrad": (lambda: ops.SqrtGrad(), Table(POS.copy(), MAT.copy())),
    "RsqrtGrad": (lambda: ops.RsqrtGrad(), Table(POS.copy(), MAT.copy())),
    "InvGrad": (lambda: ops.InvGrad(), Table(POS.copy(), MAT.copy())),
    "ReciprocalGrad": (lambda: ops.ReciprocalGrad(),
                       Table(POS.copy(), MAT.copy())),
    "BiasAddGrad": (lambda: ops.BiasAddGrad(), IMG),
    "BroadcastGradientArgs": (lambda: ops.BroadcastGradientArgs(), Table(
        np.array([2, 1, 4], np.int32), np.array([4], np.int32))),
    "Conv2DBackpropInput": (lambda: ops.Conv2DBackpropInput(), Table(
        np.array([2, 8, 8, 3], np.int32),
        np.ones((3, 3, 3, 4), np.float32), _CONV_DOUT.copy())),
    "Conv2DBackpropFilter": (lambda: ops.Conv2DBackpropFilter(), Table(
        IMG.copy(), np.array([3, 3, 3, 4], np.int32), _CONV_DOUT.copy())),
    "Conv3DBackpropInput": (lambda: ops.Conv3DBackpropInput(), Table(
        np.array([2, 4, 8, 8, 3], np.int32),
        np.ones((2, 2, 2, 3, 4), np.float32),
        np.ones((2, 4, 8, 8, 4), np.float32))),
    "Conv3DBackpropFilter": (lambda: ops.Conv3DBackpropFilter(), Table(
        VID.copy(), np.array([2, 2, 2, 3, 4], np.int32),
        np.ones((2, 4, 8, 8, 4), np.float32))),
    "DepthwiseConv2dNativeBackpropInput": (
        lambda: ops.DepthwiseConv2dNativeBackpropInput(), Table(
            np.array([2, 8, 8, 3], np.int32),
            np.ones((3, 3, 3, 2), np.float32),
            np.ones((2, 8, 8, 6), np.float32))),
    "DepthwiseConv2dNativeBackpropFilter": (
        lambda: ops.DepthwiseConv2dNativeBackpropFilter(), Table(
            IMG.copy(), np.array([3, 3, 3, 2], np.int32),
            np.ones((2, 8, 8, 6), np.float32))),
    "Dilation2DBackpropInput": (lambda: ops.Dilation2DBackpropInput(),
                                Table(IMG.copy(),
                                      np.ones((2, 2, 3), np.float32),
                                      np.ones((2, 8, 8, 3), np.float32))),
    "Dilation2DBackpropFilter": (lambda: ops.Dilation2DBackpropFilter(),
                                 Table(IMG.copy(),
                                       np.ones((2, 2, 3), np.float32),
                                       np.ones((2, 8, 8, 3), np.float32))),
    "MaxPoolGrad": (lambda: ops.MaxPoolGrad(), Table(
        IMG.copy(), _POOL_DOUT.copy(), _POOL_DOUT.copy())),
    "AvgPoolGrad": (lambda: ops.AvgPoolGrad(), Table(
        np.array([2, 8, 8, 3], np.int32), _POOL_DOUT.copy())),
    "LRNGrad": (lambda: ops.LRNGrad(2), Table(
        IMG.copy(), IMG.copy(), IMG.copy())),
    "FusedBatchNormGrad": (lambda: ops.FusedBatchNormGrad(), Table(
        IMG.copy(), IMG.copy(), np.ones(3, np.float32),
        np.zeros(3, np.float32), np.ones(3, np.float32))),
    "ResizeBilinearGrad": (lambda: ops.ResizeBilinearGrad(), Table(
        _POOL_DOUT.copy(), IMG.copy())),
})

# decode/parse ops: host-side bytes in, numpy out. PIL is optional at the
# package level (ops/parsing.py imports it lazily), so the image-decode
# specs degrade to justified skips when pillow is absent rather than
# failing the whole sweep at collection.
import io as _io


def _example_bytes():
    from bigdl_tpu.interop.tfrecord import float_feature, make_example
    ex = make_example({"x": float_feature([1.0, 2.0])})
    return ex.SerializeToString()


SPECS.update({
    "DecodeRaw": (lambda: ops.DecodeRaw("float32"), np.asarray(
        np.arange(4, dtype=np.float32).tobytes(), object)),
    "ParseExample": (lambda: ops.ParseExample(1, ["float32"], [[2]]), Table(
        np.asarray([_example_bytes()], object),
        np.asarray([b""], object), np.asarray(b"x", object),
        np.zeros(2, np.float32))),
    "ParseSingleExample": (
        lambda: ops.ParseSingleExample(["x"], ["float32"], [[2]]),
        np.asarray(_example_bytes(), object)),
})

try:
    from PIL import Image as _PILImage

    _RAMP = np.linspace(0, 255, 4 * 4, dtype=np.uint8).reshape(4, 4)
    _RGB = np.stack([_RAMP] * 3, -1)

    def _img_bytes(fmt):
        buf = _io.BytesIO()
        _PILImage.fromarray(_RGB).save(buf, format=fmt)
        return np.asarray(buf.getvalue(), object)

    SPECS.update({
        "DecodeJpeg": (lambda: ops.DecodeJpeg(channels=3),
                       _img_bytes("JPEG")),
        "DecodePng": (lambda: ops.DecodePng(), _img_bytes("PNG")),
        "DecodeBmp": (lambda: ops.DecodeBmp(), _img_bytes("BMP")),
        "DecodeGif": (lambda: ops.DecodeGif(), _img_bytes("GIF")),
    })
except ImportError:  # pragma: no cover - pillow always present in CI image
    _PIL_MISSING = True
else:
    _PIL_MISSING = False

from bigdl_tpu.interop.caffe import _CaffeFlatten, _CaffeSlice
SPECS["_CaffeSlice"] = (lambda: _CaffeSlice(-1, 1, 3), MAT)
SPECS["_CaffeFlatten"] = (lambda: _CaffeFlatten(), IMG)

# quantized modules: forward after round trip must match exactly (the
# quantization tables are part of the params)
SPECS["QuantizedLinear"] = (lambda: nn.QuantizedLinear(4, 3), MAT)
SPECS["QuantizedSpatialConvolution"] = (
    lambda: nn.QuantizedSpatialConvolution(3, 4, 3, 3), IMG)
SPECS["QuantizedSpatialDilatedConvolution"] = (
    lambda: nn.QuantizedSpatialDilatedConvolution(3, 4, 3, 3), IMG)
SPECS["WeightOnlyQuantizedLinear"] = (
    lambda: nn.WeightOnlyQuantizedLinear(4, 3), MAT)
SPECS["WeightOnlyQuantizedSpatialConvolution"] = (
    lambda: nn.WeightOnlyQuantizedSpatialConvolution(3, 4, 3, 3), IMG)

# ------------------------------------------------------------- skip list
# name -> justification. Only infrastructure that is not itself a
# serializable leaf/new-instance module belongs here.
SKIP = {
    "Module": "abstract base (Module.scala analogue), never instantiated",
    "Container": "abstract base",
    "Cell": "abstract recurrent-cell base; concrete cells swept",
    "Operation": "abstract base of ops.*",
    "Activation": "keras activation factory wrapper; concrete fns swept",
    "KerasLayer": "abstract keras base",
    "KerasModel": "abstract keras base",
    "Input": "graph-input placeholder, no standalone forward",
    "keras.Input": "keras input placeholder",
    "Graph": "covered by dedicated graph round-trip tests "
             "(test_serialization.py::TestGraphRoundTrip)",
    "StaticGraph": "alias of Graph (reference StaticGraph.scala IS the "
                   "static Graph container); covered by the same tests",
    "Model": "keras functional Model; covered by test_interop functional "
             "round-trip + requires KTensor wiring not a bare ctor",
    "keras.Sequential": "keras Sequential covered by test_keras save/load",
    "Merge": "requires multi-branch KTensor wiring; covered in "
             "test_interop.py functional model tests",
    "ModuleToOperation": "adapter around an arbitrary module; the wrapped "
                         "modules are swept directly",
    "TensorModuleWrapper": "adapter for TensorOp, swept via TensorOp",
    "ControlDependency": "graph-scheduling pseudo-op, no tensor forward",
    "Assert": "side-effecting op (raises on false), exercised in "
              "test_tf_import_ops.py",
    "NoOp": "placeholder with no output contract",
    "ControlOps": "abstract control-op base (DynamicGraph)",
    "SwitchOps": "control op emitting dead tokens; needs DynamicGraph "
                 "scheduling, covered by tests/test_dynamic_graph.py",
    "MergeOps": "ditto",
    "Enter": "loop-frame marker, covered by test_dynamic_graph.py",
    "Exit": "ditto",
    "NextIteration": "ditto",
    "LoopCondOps": "ditto",
    "ControlTrigger": "control-dependency trigger, no tensor contract",
    "DynamicGraph": "needs node-DSL wiring incl. back edges; exercised by "
                    "test_dynamic_graph.py + TF control-flow import tests",
    "Proposal": "two-stage detection op requiring RPN tensors; exercised "
                "in test_detection.py",
    "DetectionOutputFrcnn": "detection post-processor with dynamic-shaped "
                            "NMS output; exercised in test_detection.py",
    "DetectionOutputSSD": "ditto",
}

if _PIL_MISSING:  # pragma: no cover
    for _n in ("DecodeJpeg", "DecodePng", "DecodeBmp", "DecodeGif"):
        SKIP[_n] = "pillow not installed in this environment"


def _registry_entries():
    reg = registered_modules()
    names = sorted(reg)
    return reg, names


_REG, _NAMES = _registry_entries()


def _heuristic_spec(name, cls):
    """Try a no-arg construction against the candidate inputs."""
    try:
        m = cls()
    except Exception:
        return None
    for x in CANDIDATES:
        try:
            m2 = cls()
            m2.ensure_params()
            m2.forward(_t(x), training=False)
            return (cls, x)
        except Exception:
            continue
    return None


def _resolve_spec(name):
    cls = _REG[name]
    if name in SPECS:
        return SPECS[name]
    short = name.split(".")[-1]
    if short in SPECS and _REG.get(short) is cls:
        return SPECS[short]
    return _heuristic_spec(name, cls)


def test_sweep_is_total():
    """Every registered module must round-trip below or be skipped with a
    reason — the sweep cannot silently lose coverage."""
    missing = []
    for name in _NAMES:
        if name in SKIP:
            continue
        if _resolve_spec(name) is None:
            missing.append(name)
    assert not missing, (
        f"{len(missing)} registered modules have no sweep spec and no "
        f"justified skip: {missing}")


@pytest.mark.parametrize("name", [n for n in _NAMES if n not in SKIP])
def test_round_trip(name, tmp_path):
    spec = _resolve_spec(name)
    if spec is None:
        pytest.fail(f"no spec for {name} (see test_sweep_is_total)")
    factory, x = spec
    m = factory()
    m.ensure_params()
    xt = _t(x)
    rng = jax.random.PRNGKey(0)  # sampler ops (RandomUniform/...) draw on it
    want = m.forward(xt, training=False, rng=rng)
    path = str(tmp_path / "m.bigdl")
    ModuleSerializer.save(m, path)
    loaded = ModuleSerializer.load(path)
    got = loaded.forward(xt, training=False, rng=rng)
    jax.tree_util.tree_map(
        lambda a, b: np.testing.assert_array_equal(np.asarray(a),
                                                   np.asarray(b)),
        want, got)
