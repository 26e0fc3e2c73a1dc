"""bigdl_tpu.ops — compute kernels (XLA blockwise + Pallas TPU) and
TF-style stateless operations."""

from bigdl_tpu.ops.attention_kernel import (attention_state_finish,
                                            attention_state_init,
                                            blockwise_attention,
                                            flash_attention,
                                            flash_attention_forward,
                                            naive_attention)
from bigdl_tpu.ops.bn_relu_kernel import (bn_relu, bn_relu_backward,
                                          bn_relu_forward, bn_relu_pallas)
from bigdl_tpu.ops.gqa_decode_kernel import gqa_decode
from bigdl_tpu.ops.latent_decode_kernel import mla_decode
from bigdl_tpu.ops import operation
from bigdl_tpu.ops import feature_col
from bigdl_tpu.ops.operation import (Abs, Add, All, Any, ApproximateEqual,
                                     ArgMax, Assert, BatchMatMul, BiasAdd,
                                     Cast, Ceil, Compare, ControlDependency,
                                     CrossEntropy, DepthwiseConv2D, Digamma,
                                     Dilation2D, Equal, Erf, Erfc, Exp, Expm1,
                                     Floor, FloorDiv, FloorMod, Gather,
                                     Greater, GreaterEqual, InTopK, Inv,
                                     IsFinite, IsInf, IsNan, L2Loss, Less,
                                     LessEqual, Lgamma, Log1p, LogicalAnd,
                                     LogicalNot, LogicalOr, Max, Maximum,
                                     Minimum, Mod, ModuleToOperation, Mul, NoOp,
                                     NotEqual, OneHot, Operation, Pad, Pow,
                                     Prod, RandomUniform, RangeOps, Rank,
                                     RealDiv, ResizeBilinearOps, Rint, Round,
                                     Rsqrt, SegmentSum, Select, Shape, Sign,
                                     Slice, SplitAndSelect, Sqrt, Square,
                                     SquaredDifference, StridedSlice, Sub,
                                     Sum, TensorModuleWrapper, TensorOp, Tile,
                                     TopK, TruncateDiv, TruncatedNormal,
                                     RandomNormal)
from bigdl_tpu.ops.feature_col import (BucketizedCol, CategoricalColHashBucket,
                                       CategoricalColVocaList, CrossCol,
                                       IndicatorCol, Kv2Tensor, MkString,
                                       Substr)
from bigdl_tpu.ops.gradients import (AvgPoolGrad, BiasAddGrad,
                                     BroadcastGradientArgs,
                                     Conv2DBackpropFilter,
                                     Conv2DBackpropInput,
                                     Conv3DBackpropFilter,
                                     Conv3DBackpropInput,
                                     DepthwiseConv2dNativeBackpropFilter,
                                     DepthwiseConv2dNativeBackpropInput,
                                     Dilation2DBackpropFilter,
                                     Dilation2DBackpropInput, EluGrad,
                                     FusedBatchNormGrad, InvGrad, LRNGrad,
                                     MaxPoolGrad, ReciprocalGrad, Relu6Grad,
                                     ReluGrad, ResizeBilinearGrad, RsqrtGrad,
                                     SigmoidGrad, SoftplusGrad, SoftsignGrad,
                                     SqrtGrad, TanhGrad)
from bigdl_tpu.ops.parsing import (DecodeBmp, DecodeGif, DecodeJpeg,
                                   DecodePng, DecodeRaw, ParseExample,
                                   ParseSingleExample)

# round-2 review alias: the reference exposes `ops.ResizeBilinear`
# (DL/nn/ops/ResizeBilinear.scala) as well as the nn layer; same class here.
from bigdl_tpu.nn.pooling import ResizeBilinear
