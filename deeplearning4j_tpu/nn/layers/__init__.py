from .base import Layer, LayerContext, Params, State
from .attention import (
    LearnedSelfAttentionLayer,
    RecurrentAttentionLayer,
    SelfAttentionLayer,
    TransformerDecoderBlockLayer,
    dot_product_attention,
)
from .conv import (
    Convolution1DLayer,
    Convolution3DLayer,
    ConvolutionLayer,
    ConvolutionMode,
    Deconvolution2DLayer,
    DepthwiseConvolution2DLayer,
    SeparableConvolution2DLayer,
)
from .feedforward import (
    ActivationLayer,
    DenseLayer,
    DropoutLayer,
    EmbeddingLayer,
    EmbeddingSequenceLayer,
    PositionalEmbeddingLayer,
    PReLULayer,
)
from .norm import (
    BatchNormalizationLayer,
    LayerNormLayer,
    LocalResponseNormalizationLayer,
    RMSNormLayer,
    layer_norm,
    rms_norm,
)
from .output import (
    BaseOutputLayer,
    CnnLossLayer,
    LossLayer,
    MultiTokenRnnOutputLayer,
    OutputLayer,
    RnnLossLayer,
    RnnOutputLayer,
    TiedRnnOutputLayer,
)
from .pooling import (
    Cropping2DLayer,
    GlobalPoolingLayer,
    PoolingType,
    SpaceToDepthLayer,
    Subsampling1DLayer,
    Subsampling3DLayer,
    SubsamplingLayer,
    Upsampling1DLayer,
    Upsampling2DLayer,
    Upsampling3DLayer,
    ZeroPadding1DLayer,
    ZeroPaddingLayer,
)
from .preprocessors import (
    CnnToFeedForwardPreProcessor,
    CnnToRnnPreProcessor,
    FeedForwardToCnnPreProcessor,
    FeedForwardToRnnPreProcessor,
    RnnToCnnPreProcessor,
    RnnToFeedForwardPreProcessor,
)
from .cross_decoder import CrossDecoderLayer
from .decoder_block import DecoderBlockLayer, GatedFFNLayer
from .diff_attention import DifferentialAttentionLayer
from .eva import EvaDecoderBlockLayer, gated_silu_ffn, rotary_positions
from .gmu import GatedMemoryLayer
from .gqa import GroupedQueryAttentionLayer
from .longcat import LongCatBlockLayer
from .mamba import MambaMixerLayer
from .mla import LatentAttentionLayer
from .moe import ExpertShareMoELayer, MixtureOfExpertsLayer
from .mtp import MtpOutputLayer
from .samediff_layer import SameDiffLambdaLayer, SameDiffLayer
from .short_conv import ShortConvLayer
from .recurrent import (
    BidirectionalLayer,
    BidirectionalMode,
    GravesLSTMLayer,
    GRULayer,
    LSTMLayer,
    LastTimeStepLayer,
    MaskZeroLayer,
    SimpleRnnLayer,
    TimeDistributedLayer,
)

__all__ = [n for n in dir() if not n.startswith("_")]
