"""bigdl_tpu.nn — the layer library (reference DL/nn parity, TPU-native)."""

from bigdl_tpu.nn.module import (Activity, ApplyContext, Module, Node,
                                 functional_apply, merge_state, param_count,
                                 topo_sort)
from bigdl_tpu.nn.containers import (Bottle, CAddTable, CAveTable, CDivTable,
                                     Remat,
                                     CMaxTable, CMinTable, CMulTable, CSubTable,
                                     Concat, ConcatTable, Container, Echo,
                                     BifurcateSplitTable, FlattenTable, Graph, Identity, Input, StaticGraph,
                                     InputNode, JoinTable, MapTable,
                                     MixtureTable, NarrowTable, ParallelTable,
                                     SelectTable, Sequential, SplitTable)
from bigdl_tpu.nn.dynamic_graph import (DEAD, ControlOps, ControlTrigger,
                                        DynamicGraph, Enter, Exit,
                                        FrameManager, LoopCondOps, MergeOps,
                                        NextIteration, Scheduler, SwitchOps,
                                        switch_port)
from bigdl_tpu.nn.linear import (Add, AddConstant, Bilinear, CAdd, CMul,
                                 Cosine, Euclidean, Highway, Linear, Maxout,
                                 Mul, MulConstant, Scale)
from bigdl_tpu.nn.conv import (LocallyConnected1D, LocallyConnected2D,
                               SpaceToDepthStemConvolution,
                               SpatialConvolution, SpatialConvolutionMap,
                               SpatialDilatedConvolution, SpatialFullConvolution,
                               SpatialSeparableConvolution,
                               SpatialShareConvolution, TemporalConvolution,
                               VolumetricConvolution, VolumetricFullConvolution)
from bigdl_tpu.nn.detection import (Anchor, DetectionOutputFrcnn,
                                    DetectionOutputSSD, Nms, PriorBox, Proposal,
                                    RoiPooling, bbox_iou, bbox_transform_inv,
                                    clip_boxes, nms_mask)
from bigdl_tpu.nn.tree import BinaryTreeLSTM, TreeLSTM
from bigdl_tpu.nn.pooling import (Pooler, ResizeBilinear, SpatialAveragePooling,
                                  SpatialCrossMapLRN, SpatialMaxPooling,
                                  TemporalMaxPooling, UpSampling1D, UpSampling2D,
                                  UpSampling3D, VolumetricAveragePooling,
                                  VolumetricMaxPooling)
from bigdl_tpu.nn.fusion import (fusible_activation, fusible_bn,
                                 fusion_enabled, fusion_scope, set_fusion)
from bigdl_tpu.nn.normalization import (BatchNormalization, LayerNormalization,
                                        Normalize, NormalizeScale, RMSNorm,
                                        SpatialBatchNormalization,
                                        SpatialContrastiveNormalization,
                                        SpatialDivisiveNormalization,
                                        SpatialSubtractiveNormalization,
                                        SpatialWithinChannelLRN)
from bigdl_tpu.nn.activation import (ELU, GELU, Abs, BinaryThreshold, Clamp,
                                     Exp, GradientReversal, HardShrink,
                                     HardSigmoid, HardTanh, LeakyReLU, Log,
                                     LogSigmoid, LogSoftMax, Negative, Power,
                                     PReLU, ReLU, ReLU6, RReLU, Sigmoid,
                                     SoftMax, SoftMin, SoftPlus, SoftShrink,
                                     SoftSign, Sqrt, Square, SReLU, Tanh,
                                     TanhShrink, Threshold)
from bigdl_tpu.nn.dropout import (Dropout, GaussianDropout, GaussianNoise,
                                  GaussianSampler, SpatialDropout1D,
                                  SpatialDropout2D, SpatialDropout3D)
from bigdl_tpu.nn.shape_ops import (MM, MV, ActivityRegularization, Contiguous,
                                    CosineDistance, Cropping2D, Cropping3D,
                                    CrossProduct, DenseToSparse, DotProduct,
                                    Index, InferReshape, Masking, MaskedSelect,
                                    Max, Mean, Min, Narrow, Pack, Padding,
                                    PairwiseDistance, Permute, Replicate,
                                    Reshape, Reverse, Select, SpatialZeroPadding,
                                    Squeeze, Sum, Tile, Transpose, Unsqueeze,
                                    View)
from bigdl_tpu.nn.embedding import (LookupTable, LookupTableSparse,
                                    SparseJoinTable, SparseLinear)
from bigdl_tpu.nn.recurrent import (BiRecurrent, Cell, ConvLSTMPeephole,
                                    ConvLSTMPeephole3D, LSTM2, GRU,
                                    GRUCell, LSTM, LSTMCell, LSTMPeephole,
                                    LSTMPeepholeCell, MultiRNNCell, Recurrent,
                                    RecurrentDecoder, RnnCell, TimeDistributed)
from bigdl_tpu.nn import criterion
from bigdl_tpu.nn.criterion import (AbsCriterion, BCECriterion,
                                    CategoricalCrossEntropy,
                                    BCECriterionWithLogits, ClassNLLCriterion,
                                    CosineDistanceCriterion,
                                    CosineEmbeddingCriterion,
                                    CosineProximityCriterion, Criterion,
                                    CrossEntropyCriterion,
                                    DiceCoefficientCriterion,
                                    DistKLDivCriterion, DotProductCriterion,
                                    FakeCriterion,
                                    GaussianCriterion, HingeEmbeddingCriterion,
                                    KLDCriterion,
                                    KullbackLeiblerDivergenceCriterion, L1Cost,
                                    L1HingeEmbeddingCriterion, L1Penalty,
                                    MarginCriterion, MarginRankingCriterion,
                                    MeanAbsolutePercentageCriterion,
                                    MeanSquaredLogarithmicCriterion,
                                    MSECriterion, MultiCriterion,
                                    MultiLabelMarginCriterion,
                                    MultiLabelSoftMarginCriterion,
                                    MultiMarginCriterion,
                                    NegativeEntropyPenalty, ParallelCriterion,
                                    PGCriterion, PoissonCriterion,
                                    SmoothL1Criterion,
                                    SmoothL1CriterionWithWeights,
                                    SoftMarginCriterion, SoftmaxWithCriterion,
                                    TimeDistributedCriterion,
                                    TimeDistributedMaskCriterion,
                                    TransformerCriterion)
from bigdl_tpu.nn.attention import (GroupedQueryAttention,
                                    MultiHeadAttention,
                                    ScaledDotProductAttention,
                                    TransformerBlock, rope)
from bigdl_tpu.nn.latent_attention import LatentAttention
from bigdl_tpu.nn.linear_attention import GatedDeltaRule
from bigdl_tpu.nn.short_conv import ShortConv
from bigdl_tpu.nn import initialization
from bigdl_tpu.nn.initialization import (BilinearFiller, ConstInitMethod,
                                         MsraFiller, Ones, RandomNormal,
                                         RandomUniform, Xavier, Zeros)
from bigdl_tpu.nn.quantized import (QuantizedLinear,
                                    QuantizedSpatialConvolution,
                                    QuantizedSpatialDilatedConvolution,
                                    Quantizer,
                                    WeightOnlyQuantizedLinear,
                                    WeightOnlyQuantizedSpatialConvolution)

# name-parity aliases (reference DL/nn/RnnCell.scala is listed as "RNN" in
# user docs; ClassSimplexCriterion export)
from bigdl_tpu.nn.recurrent import RnnCell
from bigdl_tpu.nn.criterion import ClassSimplexCriterion
RNN = RnnCell
