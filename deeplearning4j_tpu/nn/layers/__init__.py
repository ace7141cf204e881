"""Layer configs/implementations (reference ``nn/conf/layers`` + ``nn/layers``)."""
from .attention import (LatentAttention, LayerNormLayer, MultiHeadAttention,
                        NextTokenMerge, PositionalEncodingLayer,
                        RMSNormLayer, TransformerBlock)
from .base import BaseLayerConf, LayerConf
from .convolution import (Convolution1DLayer, ConvolutionLayer,
                          Subsampling1DLayer, SubsamplingLayer, Upsampling1D,
                          Upsampling2D, ZeroPaddingLayer)
from .feedforward import (ActivationLayer, CenterLossOutputLayer, DenseLayer,
                          DropoutLayer, EmbeddingLayer,
                          EmbeddingSequenceLayer, LossLayer, OutputLayer)
from .misc import FrozenLayer
from .moe import MixtureOfExpertsLayer
from .normalization import BatchNormalization, LocalResponseNormalization
from .objdetect import Yolo2OutputLayer
from .pooling import GlobalPoolingLayer
from .pretrain import AutoEncoder, RBM, VariationalAutoencoder
from .recurrent import (Bidirectional, ExitGateOutputLayer,
                        GravesBidirectionalLSTM, GravesLSTM, LastTimeStep,
                        LSTM, RnnOutputLayer, SimpleRnn)

__all__ = [
    "ActivationLayer", "AutoEncoder", "BaseLayerConf", "BatchNormalization",
    "Bidirectional", "CenterLossOutputLayer", "Convolution1DLayer",
    "ConvolutionLayer", "DenseLayer", "DropoutLayer", "EmbeddingLayer",
    "EmbeddingSequenceLayer", "ExitGateOutputLayer",
    "FrozenLayer", "GlobalPoolingLayer", "GravesBidirectionalLSTM",
    "GravesLSTM", "LastTimeStep", "LatentAttention", "LayerConf",
    "LayerNormLayer",
    "LocalResponseNormalization", "LossLayer", "LSTM",
    "MixtureOfExpertsLayer", "MultiHeadAttention", "NextTokenMerge",
    "OutputLayer", "PositionalEncodingLayer", "RBM", "RMSNormLayer", "RnnOutputLayer",
    "SimpleRnn", "TransformerBlock",
    "Subsampling1DLayer", "SubsamplingLayer", "Upsampling1D", "Upsampling2D",
    "VariationalAutoencoder", "Yolo2OutputLayer", "ZeroPaddingLayer",
]
