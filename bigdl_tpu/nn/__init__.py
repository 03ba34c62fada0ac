"""bigdl_tpu.nn — the layer zoo.

Mirrors the reference's ``com.intel.analytics.bigdl.nn`` package surface
(SURVEY §2.6) with a pure-function core per layer.
"""

from bigdl_tpu.nn.module import (Module, Container, Sequential, Criterion,
                                 Activity)
from bigdl_tpu.nn import init
from bigdl_tpu.nn.init import (InitializationMethod, Zeros, Ones,
                               ConstInitMethod, RandomUniform, RandomNormal,
                               Xavier, MsraFiller, BilinearFiller)
from bigdl_tpu.nn.linear import (Linear, Bilinear, LookupTable, Add, Mul,
                                 CMul, CAdd, Euclidean, Cosine)
from bigdl_tpu.nn.conv import (SpatialConvolution, SpatialShareConvolution,
                               SpatialDilatedConvolution,
                               SpatialFullConvolution, TemporalConvolution,
                               VolumetricConvolution,
                               VolumetricFullConvolution,
                               SpatialConvolutionMap)
from bigdl_tpu.nn.pooling import (SpatialMaxPooling, SpatialAveragePooling,
                                  VolumetricMaxPooling, RoiPooling)
from bigdl_tpu.ops.nms import Nms, nms_mask
from bigdl_tpu.nn.attention import (MultiHeadAttention,
                                    scaled_dot_product_attention)
from bigdl_tpu.nn.latent_attention import (LatentAttention, RMSNorm,
                                           SwiGLU)
from bigdl_tpu.nn.moe import MixtureOfExperts, SigmoidRoutedExperts
from bigdl_tpu.nn.tf_ops import (Const, Fill, Shape, SplitAndSelect,
                                 StrideSlice)
from bigdl_tpu.nn.activation import (ReLU, ReLU6, LeakyReLU, ELU, PReLU,
                                     RReLU, Tanh, TanhShrink, Sigmoid,
                                     LogSigmoid, SoftMax, SoftMin, LogSoftMax,
                                     SoftPlus, SoftSign, SoftShrink,
                                     HardShrink, HardTanh, Clamp, Threshold,
                                     Power, Sqrt, Square, Abs, Log, Exp,
                                     Negative, Dropout, GaussianDropout,
                                     GaussianNoise, L1Penalty)
from bigdl_tpu.nn.normalization import (BatchNormalization,
                                        SpatialBatchNormalization,
                                        SpatialCrossMapLRN,
                                        SpatialWithinChannelLRN,
                                        SpatialContrastiveNormalization,
                                        SpatialDivisiveNormalization,
                                        SpatialSubtractiveNormalization,
                                        Normalize)
from bigdl_tpu.nn.structural import (Identity, Echo, Contiguous, Reshape,
                                     View, InferReshape, Squeeze, Unsqueeze,
                                     Transpose, Narrow, Select, Index,
                                     MaskedSelect, Max, Min, Mean, Sum,
                                     Replicate, Padding, SpatialZeroPadding,
                                     GradientReversal, Scale, Bottle, Remat,
                                     MM, MV,
                                     DotProduct, Pack, Reverse,
                                     MulConstant, AddConstant,
                                     ChannelNormalize, DeviceAugment)
from bigdl_tpu.nn.table import (Concat, ConcatTable, ParallelTable, MapTable,
                                JoinTable, SplitTable, SelectTable,
                                NarrowTable, FlattenTable, MixtureTable,
                                CAddTable, CSubTable, CMulTable, CDivTable,
                                CMaxTable, CMinTable, PairwiseDistance,
                                CosineDistance)
from bigdl_tpu.nn.criterion import (ClassNLLCriterion, CrossEntropyCriterion,
                                    MSECriterion, AbsCriterion, BCECriterion,
                                    DistKLDivCriterion,
                                    CosineEmbeddingCriterion,
                                    HingeEmbeddingCriterion,
                                    L1HingeEmbeddingCriterion,
                                    MarginCriterion, MarginRankingCriterion,
                                    MultiCriterion, ParallelCriterion,
                                    MultiLabelMarginCriterion,
                                    MultiLabelSoftMarginCriterion,
                                    MultiMarginCriterion, SmoothL1Criterion,
                                    SmoothL1CriterionWithWeights,
                                    SoftmaxWithCriterion, SoftMarginCriterion,
                                    L1Cost, CosineDistanceCriterion,
                                    DiceCoefficientCriterion,
                                    ClassSimplexCriterion,
                                    TimeDistributedCriterion)
from bigdl_tpu.nn.graph import Graph, ModuleNode, Input
from bigdl_tpu.nn.layout import (NCHWToNHWC, NHWCToNCHW, to_channels_last,
                                 apply_layout)
from bigdl_tpu.nn.fuse import fold_conv_bn
from bigdl_tpu.nn.recurrent import (Cell, RnnCell, LSTM, LSTMPeephole, GRU,
                                    ConvLSTMPeephole, ConvLSTMPeephole3D,
                                    Recurrent, BiRecurrent, TimeDistributed,
                                    BinaryTreeLSTM, TreeLSTM)
