"""bigdl_tpu.nn — layer library (parity with reference ``nn`` package;
pyspark frontend parity with ``pyspark/bigdl/nn/layer.py`` and
``criterion.py`` — same class names, positional args, snake_case kwargs)."""

from .module import Module, Container, Criterion, Node

# pyspark spelling: every layer subclasses `Layer` there (the py4j base);
# isinstance(x, Layer) in ported scripts must keep working
Layer = Module
from .init import (InitializationMethod, Zeros, Ones, ConstInit,
                   ConstInitMethod, RandomUniform,
                   RandomNormal, Xavier, MsraFiller, BilinearFiller)
from .containers import (Sequential, Concat, ConcatTable, ParallelTable,
                         MapTable, Bottle)
from .graph_container import Graph, Input
from .dynamic_graph import (StaticGraph, Model, DynamicGraph, Switch, Merge,
                            NOT_TAKEN)
from .activation import (ReLU, ReLU6, LeakyReLU, PReLU, RReLU, SReLU, ELU,
                         GELU, SoftPlus, SoftSign, Sigmoid, LogSigmoid, Tanh,
                         TanhShrink, HardTanh, Clamp, HardSigmoid, HardShrink,
                         SoftShrink, SoftMax, SoftMin, LogSoftMax, Threshold,
                         BinaryThreshold, Maxout)
from .elementwise import (Identity, Echo, Contiguous, Abs, Exp, Log, Sqrt,
                          Square, Negative, Power, AddConstant, MulConstant,
                          GradientReversal, ErrorInfo, L1Penalty)
from .linear import (Linear, Bilinear, Cosine, Euclidean, Add,
                     Mul, CMul, CAdd, Scale, Highway, LookupTable)
from .conv import (SpatialConvolution, SpatialShareConvolution,
                   SpatialDilatedConvolution, SpatialFullConvolution,
                   SpatialSeparableConvolution, SpatialConvolutionMap,
                   TemporalConvolution, VolumetricConvolution,
                   VolumetricFullConvolution, LocallyConnected1D,
                   LocallyConnected2D)
from .pool import (SpatialMaxPooling, SpatialAveragePooling,
                   TemporalMaxPooling, VolumetricMaxPooling,
                   VolumetricAveragePooling, RoiPooling)
from .norm import (BatchNormalization, SpatialBatchNormalization,
                   VolumetricBatchNormalization, LayerNormalization, RMSNorm,
                   SpatialCrossMapLRN, SpatialWithinChannelLRN, Normalize,
                   NormalizeScale, SpatialSubtractiveNormalization,
                   SpatialDivisiveNormalization,
                   SpatialContrastiveNormalization, Masking)
from .dropout import (Dropout, GaussianDropout, GaussianNoise, GaussianSampler,
                      SpatialDropout1D, SpatialDropout2D, SpatialDropout3D)
from .shape_ops import (Reshape, View, InferReshape, Squeeze, Unsqueeze,
                        Transpose, Replicate, Padding, SpatialZeroPadding,
                        Narrow, Select, Index, MaskedSelect, Max, Min, Mean,
                        Sum, Tile, ExpandSize, Cropping2D, Cropping3D, Reverse,
                        Pack, UpSampling1D, UpSampling2D, UpSampling3D,
                        ResizeBilinear)
from .sparse import (SparseTensor, SparseLinear, LookupTableSparse,
                     SparseJoinTable, DenseToSparse, sparse_dense_matmul)
from .moe import MixtureOfExperts, RoutedExperts
from .ssm import Mamba2Mixer, ssd_scan
from .table_ops import (CAddTable, CSubTable, CMulTable, CDivTable, CMaxTable,
                        CMinTable, CAveTable, JoinTable, SplitTable,
                        BifurcateSplitTable, SelectTable, NarrowTable,
                        FlattenTable, MixtureTable, DotProduct, CrossProduct,
                        MM, MV, PairwiseDistance, CosineDistance,
                        TableOperation)
from .recurrent import (Cell, RnnCell, RNN, LSTM, LSTMPeephole, GRU,
                        ConvLSTMPeephole, ConvLSTMPeephole3D, MultiRNNCell,
                        Recurrent, RecurrentDecoder, BiRecurrent,
                        TimeDistributed)
from .tree_lstm import TreeLSTM, BinaryTreeLSTM, tensor_tree
from .detection import (Anchor, Nms, PriorBox, Proposal, DetectionOutputSSD,
                        DetectionOutputFrcnn, RoiAlign, bbox_transform_inv,
                        bbox_iou_matrix, bbox_areas, clip_boxes, decode_boxes,
                        nms_mask, generate_basic_anchors, bbox_vote)
from .attention import (Attention, FeedForwardNetwork, LatentAttention,
                        SparseAttention, SublayerBlock,
                        Transformer, TransformerBlock, dot_product_attention,
                        flash_attention, position_encoding, causal_mask,
                        padding_mask, rotary_embedding)
from .speculative import speculative_generate, SpecStats
from .criterion import (ClassNLLCriterion, CrossEntropyCriterion,
                        CategoricalCrossEntropy, BCECriterion, MSECriterion,
                        AbsCriterion, SmoothL1Criterion,
                        SmoothL1CriterionWithWeights, MarginCriterion,
                        MultiLabelSoftMarginCriterion, MultiMarginCriterion,
                        MultiLabelMarginCriterion, SoftMarginCriterion,
                        DistKLDivCriterion, KullbackLeiblerDivergenceCriterion,
                        KLDCriterion, GaussianCriterion,
                        CosineEmbeddingCriterion, HingeEmbeddingCriterion,
                        L1HingeEmbeddingCriterion, MarginRankingCriterion,
                        SoftmaxWithCriterion, TimeDistributedCriterion,
                        TimeDistributedMaskCriterion, LMCriterion,
                        ParallelCriterion,
                        MultiCriterion, L1Cost, DiceCoefficientCriterion,
                        MeanAbsolutePercentageCriterion,
                        MeanSquaredLogarithmicCriterion, PoissonCriterion,
                        CosineProximityCriterion, DotProductCriterion,
                        PGCriterion, ClassSimplexCriterion,
                        CosineDistanceCriterion, ActivityRegularization,
                        NegativeEntropyPenalty, TransformerCriterion)
