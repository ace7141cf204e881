"""Model zoo (reference ``deeplearning4j-zoo``): standard architectures built
on the config DSL — LeNet, SimpleCNN, AlexNet, VGG16/19, ResNet50, GoogLeNet,
InceptionResNetV1, FaceNetNN4Small2, TextGenerationLSTM.

Reference ``deeplearning4j-zoo/src/main/java/org/deeplearning4j/zoo/model/``:
``LeNet.java:35``, ``AlexNet.java``, ``VGG16.java``, ``ResNet50.java:33``
(graph built in init :82), ``GoogLeNet.java``, ``InceptionResNetV1.java``,
``FaceNetNN4Small2.java``, ``SimpleCNN.java``, ``TextGenerationLSTM.java:34``.

Architectures are the canonical published ones, NHWC, sized by
``(height, width, channels)`` so tests can instantiate miniature variants.
Pretrained-weight download (reference ``ZooModel.initPretrained`` checksum
fetch, ``ZooModel.java:40-81``) is gated on a local weights path — this
environment has no egress.
"""
from __future__ import annotations

import os
from dataclasses import dataclass
from typing import Optional, Sequence, Tuple

from ..nn.computation_graph import ComputationGraph
from ..nn.conf.computation_graph import (ElementWiseVertex, GraphBuilder,
                                         L2NormalizeVertex, MergeVertex)
from ..nn.conf.input_type import InputType
from ..nn.conf.multi_layer import NeuralNetConfiguration
from ..nn.conf.updaters import Adam, Nesterovs, UpdaterConf
from ..nn.layers.convolution import ConvolutionLayer, SubsamplingLayer
from ..nn.layers.feedforward import (ActivationLayer, DenseLayer,
                                     DropoutLayer, OutputLayer)
from ..nn.layers.normalization import (BatchNormalization,
                                       LocalResponseNormalization)
from ..nn.layers.pooling import GlobalPoolingLayer
from ..nn.layers.recurrent import LSTM, RnnOutputLayer


def _conv_block(g: GraphBuilder, name: str, inp: str, n_out: int, kernel,
                stride=(1, 1), act: Optional[str] = None,
                mode: str = "same") -> str:
    """Add a conv layer vertex; act=None inherits the builder default."""
    g.add_layer(name, ConvolutionLayer(
        n_out=n_out, kernel_size=kernel, stride=stride,
        convolution_mode=mode, activation=act), inp)
    return name


def _inception_block(g: GraphBuilder, name: str, inp: str, c1: int, c3r: int,
                     c3: int, c5r: int, c5: int, pp: int) -> str:
    """GoogLeNet-style inception module: 1x1 / 3x3 / 5x5 / pool-proj merge."""
    a = _conv_block(g, f"{name}_1x1", inp, c1, (1, 1))
    b = _conv_block(g, f"{name}_3x3r", inp, c3r, (1, 1))
    b = _conv_block(g, f"{name}_3x3", b, c3, (3, 3))
    d = _conv_block(g, f"{name}_5x5r", inp, c5r, (1, 1))
    d = _conv_block(g, f"{name}_5x5", d, c5, (5, 5))
    g.add_layer(f"{name}_pool", SubsamplingLayer(
        pooling_type="max", kernel_size=(3, 3), stride=(1, 1),
        convolution_mode="same"), inp)
    p = _conv_block(g, f"{name}_poolproj", f"{name}_pool", pp, (1, 1))
    g.add_vertex(name, MergeVertex(), a, b, d, p)
    return name


def _max_pool(g: GraphBuilder, name: str, inp: str, kernel=(3, 3),
              stride=(2, 2)) -> str:
    g.add_layer(name, SubsamplingLayer(
        pooling_type="max", kernel_size=kernel, stride=stride,
        convolution_mode="same"), inp)
    return name


@dataclass
class ZooModel:
    """Base zoo model (reference ``ZooModel.java``)."""
    model_type = "cnn"   # "cnn" | "rnn" — ModelSelector filter key
    num_classes: int = 1000
    seed: int = 123
    input_shape: Tuple[int, int, int] = (224, 224, 3)   # (h, w, c)
    updater: Optional[UpdaterConf] = None
    compute_dtype: Optional[str] = None   # 'bfloat16' = TPU fast path

    def init(self):
        raise NotImplementedError

    def pretrained(self, weights_path: Optional[str] = None):
        """Load pretrained weights (reference ``ZooModel.java:40-81``
        downloads + checksums; this environment has no egress, so the
        artifact is local).  Accepts a native checkpoint zip OR a Keras
        HDF5 file — the latter routes through the import bridge and
        transplants the weights into this zoo architecture."""
        path = weights_path or os.environ.get("DL4J_TPU_PRETRAINED_DIR")
        if not path:
            raise FileNotFoundError(
                f"no pretrained weights available for "
                f"{type(self).__name__}; pass weights_path or set "
                "DL4J_TPU_PRETRAINED_DIR")
        from ..utils import model_serializer
        if os.path.isdir(path):
            path = os.path.join(path, f"{type(self).__name__.lower()}.zip")
        with open(path, "rb") as f:
            magic = f.read(4)
        if magic == b"\x89HDF":
            return self.import_pretrained(path)
        return model_serializer.restore_model(path)

    def import_pretrained(self, keras_path: str):
        """Keras-HDF5 → zoo-architecture weight transplant (the weights-
        import bridge standing in for ``ZooModel.java``'s downloads): the
        file is imported through the Keras bridge and its parameters are
        grafted layer-for-layer onto this zoo model's own graph (so updater
        / dtype / config settings stay the zoo's)."""
        from ..modelimport.keras import import_keras_model
        imported = import_keras_model(keras_path)
        target = self.init()
        _transplant_params(imported, target,
                           what=f"{type(self).__name__} <- {keras_path}")
        return target

    def _builder(self):
        b = NeuralNetConfiguration.builder().seed(self.seed)
        if self.compute_dtype:
            b = b.compute_dtype(self.compute_dtype)
        return b


def _ordered_stateful_keys(model):
    """Keys of layers/vertices carrying params or state, in execution
    order: topological order for ComputationGraphs, layer index for
    MultiLayerNetworks."""
    has = {k for k, v in model.params.items() if v}
    has |= {k for k, v in getattr(model, "state", {}).items() if v}
    order = getattr(model.conf, "topological_order", None)
    if order:
        return [k for k in order if k in has]
    return sorted(has, key=lambda k: int(k.split("_")[-1]))


def _transplant_params(src, dst, what: str = "") -> None:
    """Copy parameters and state (e.g. BN running stats) from ``src`` onto
    ``dst`` by execution order, with shape checks — mismatches raise with
    the offending layer named rather than silently truncating.  Params and
    state ride the SAME layer pairing so a source layer missing optional
    state can never shift later layers' running stats onto the wrong
    target (state names absent on one side keep the target's values)."""
    import jax.numpy as jnp

    src_layers = _ordered_stateful_keys(src)
    dst_layers = _ordered_stateful_keys(dst)
    if len(src_layers) != len(dst_layers):
        raise ValueError(
            f"transplant {what}: source has {len(src_layers)} "
            f"param/state-bearing layers, target {len(dst_layers)} — "
            "architectures differ")
    for sk, dk in zip(src_layers, dst_layers):
        sp, dp = src.params.get(sk) or {}, dst.params.get(dk) or {}
        if set(sp) != set(dp):
            raise ValueError(f"transplant {what}: layer {dk} params "
                             f"{sorted(dp)} != source {sorted(sp)}")
        for name in sp:
            if tuple(sp[name].shape) != tuple(dp[name].shape):
                raise ValueError(
                    f"transplant {what}: {dk}.{name} shape "
                    f"{tuple(dp[name].shape)} != source "
                    f"{tuple(sp[name].shape)}")
            dp[name] = jnp.asarray(sp[name], dp[name].dtype)
        ss, ds = src.state.get(sk) or {}, dst.state.get(dk) or {}
        for name, val in ss.items():
            if name not in ds:
                continue              # optional state the target lacks
            if tuple(val.shape) != tuple(ds[name].shape):
                raise ValueError(
                    f"transplant {what}: {dk} state '{name}' shape "
                    f"{tuple(ds[name].shape)} != source {tuple(val.shape)}")
            ds[name] = jnp.asarray(val, ds[name].dtype)


@dataclass
class LeNet(ZooModel):
    """LeNet-5 (reference ``model/LeNet.java:35``)."""
    num_classes: int = 10
    input_shape: Tuple[int, int, int] = (28, 28, 1)

    def init(self):
        h, w, c = self.input_shape
        conf = (self._builder()
                .updater(self.updater or Nesterovs(learning_rate=0.01, momentum=0.9))
                .activation("relu").weight_init("xavier")
                .list()
                .layer(ConvolutionLayer(n_out=20, kernel_size=(5, 5),
                                        stride=(1, 1), convolution_mode="same"))
                .layer(SubsamplingLayer(pooling_type="max", kernel_size=(2, 2),
                                        stride=(2, 2)))
                .layer(ConvolutionLayer(n_out=50, kernel_size=(5, 5),
                                        stride=(1, 1), convolution_mode="same"))
                .layer(SubsamplingLayer(pooling_type="max", kernel_size=(2, 2),
                                        stride=(2, 2)))
                .layer(DenseLayer(n_out=500))
                .layer(OutputLayer(n_out=self.num_classes,
                                   activation="softmax", loss="mcxent"))
                # flat input + auto reshape, matching the reference LeNet's
                # InputType.convolutionalFlat (MnistDataSetIterator is flat)
                .set_input_type(InputType.convolutional_flat(h, w, c))
                .build())
        from ..nn.multilayer import MultiLayerNetwork
        return MultiLayerNetwork(conf).init()


@dataclass
class SimpleCNN(ZooModel):
    """Compact CNN (reference ``model/SimpleCNN.java``)."""
    num_classes: int = 10
    input_shape: Tuple[int, int, int] = (48, 48, 3)

    def init(self):
        h, w, c = self.input_shape
        conf = (self._builder()
                .updater(self.updater or Adam(learning_rate=1e-3))
                .activation("relu").weight_init("relu")
                .list()
                .layer(ConvolutionLayer(n_out=16, kernel_size=(3, 3),
                                        convolution_mode="same"))
                .layer(BatchNormalization())
                .layer(ConvolutionLayer(n_out=16, kernel_size=(3, 3),
                                        convolution_mode="same"))
                .layer(BatchNormalization())
                .layer(SubsamplingLayer(pooling_type="max", kernel_size=(2, 2),
                                        stride=(2, 2)))
                .layer(ConvolutionLayer(n_out=32, kernel_size=(3, 3),
                                        convolution_mode="same"))
                .layer(BatchNormalization())
                .layer(ConvolutionLayer(n_out=32, kernel_size=(3, 3),
                                        convolution_mode="same"))
                .layer(BatchNormalization())
                .layer(SubsamplingLayer(pooling_type="max", kernel_size=(2, 2),
                                        stride=(2, 2)))
                .layer(DropoutLayer(dropout=0.5))
                .layer(DenseLayer(n_out=256))
                .layer(OutputLayer(n_out=self.num_classes,
                                   activation="softmax", loss="mcxent"))
                .set_input_type(InputType.convolutional(h, w, c))
                .build())
        from ..nn.multilayer import MultiLayerNetwork
        return MultiLayerNetwork(conf).init()


@dataclass
class AlexNet(ZooModel):
    """AlexNet (reference ``model/AlexNet.java`` — one-tower variant)."""

    def init(self):
        h, w, c = self.input_shape
        conf = (self._builder()
                .updater(self.updater or Nesterovs(learning_rate=1e-2, momentum=0.9))
                .activation("relu").weight_init("relu").l2(5e-4)
                .list()
                .layer(ConvolutionLayer(n_out=96, kernel_size=(11, 11),
                                        stride=(4, 4), convolution_mode="same"))
                .layer(LocalResponseNormalization())
                .layer(SubsamplingLayer(pooling_type="max", kernel_size=(3, 3),
                                        stride=(2, 2)))
                .layer(ConvolutionLayer(n_out=256, kernel_size=(5, 5),
                                        convolution_mode="same"))
                .layer(LocalResponseNormalization())
                .layer(SubsamplingLayer(pooling_type="max", kernel_size=(3, 3),
                                        stride=(2, 2)))
                .layer(ConvolutionLayer(n_out=384, kernel_size=(3, 3),
                                        convolution_mode="same"))
                .layer(ConvolutionLayer(n_out=384, kernel_size=(3, 3),
                                        convolution_mode="same"))
                .layer(ConvolutionLayer(n_out=256, kernel_size=(3, 3),
                                        convolution_mode="same"))
                .layer(SubsamplingLayer(pooling_type="max", kernel_size=(3, 3),
                                        stride=(2, 2)))
                .layer(DenseLayer(n_out=4096, dropout=0.5))
                .layer(DenseLayer(n_out=4096, dropout=0.5))
                .layer(OutputLayer(n_out=self.num_classes,
                                   activation="softmax", loss="mcxent"))
                .set_input_type(InputType.convolutional(h, w, c))
                .build())
        from ..nn.multilayer import MultiLayerNetwork
        return MultiLayerNetwork(conf).init()


def _vgg_blocks(cfg):
    """cfg: list of (num_convs, channels)."""
    layers = []
    for n, ch in cfg:
        for _ in range(n):
            layers.append(ConvolutionLayer(n_out=ch, kernel_size=(3, 3),
                                           convolution_mode="same"))
        layers.append(SubsamplingLayer(pooling_type="max",
                                       kernel_size=(2, 2), stride=(2, 2)))
    return layers


@dataclass
class VGG16(ZooModel):
    """VGG-16 (reference ``model/VGG16.java``)."""
    BLOCKS = [(2, 64), (2, 128), (3, 256), (3, 512), (3, 512)]

    def init(self):
        h, w, c = self.input_shape
        b = (self._builder()
             .updater(self.updater or Nesterovs(learning_rate=1e-2, momentum=0.9))
             .activation("relu").weight_init("xavier")
             .list())
        for lyr in _vgg_blocks(self.BLOCKS):
            b.layer(lyr)
        b.layer(DenseLayer(n_out=4096, dropout=0.5))
        b.layer(DenseLayer(n_out=4096, dropout=0.5))
        b.layer(OutputLayer(n_out=self.num_classes, activation="softmax",
                            loss="mcxent"))
        conf = b.set_input_type(InputType.convolutional(h, w, c)).build()
        from ..nn.multilayer import MultiLayerNetwork
        return MultiLayerNetwork(conf).init()


@dataclass
class VGG19(VGG16):
    """VGG-19 (reference ``model/VGG19.java``)."""
    BLOCKS = [(2, 64), (2, 128), (4, 256), (4, 512), (4, 512)]


@dataclass
class ResNet50(ZooModel):
    """ResNet-50 (reference ``model/ResNet50.java:33``, graph in init :82):
    conv/identity bottleneck blocks as a ComputationGraph with ElementWise
    residual adds."""

    def init(self) -> ComputationGraph:
        h, w, c = self.input_shape
        defaults = {"activation": "relu", "weight_init": "relu",
                    "updater": self.updater or
                    Nesterovs(learning_rate=1e-1, momentum=0.9)}
        if self.compute_dtype:
            defaults["compute_dtype"] = self.compute_dtype
        g = GraphBuilder(defaults, seed=self.seed)
        g.add_inputs("in").set_input_types(InputType.convolutional(h, w, c))

        def conv_bn(name, inp, n_out, kernel, stride=(1, 1), act="relu",
                    mode="same"):
            x = _conv_block(g, name, inp, n_out, kernel, stride,
                            act="identity", mode=mode)
            g.add_layer(f"{name}_bn", BatchNormalization(activation=act), x)
            return f"{name}_bn"

        def bottleneck(name, inp, filters, stride, project):
            f1, f2, f3 = filters
            x = conv_bn(f"{name}_a", inp, f1, (1, 1), stride)
            x = conv_bn(f"{name}_b", x, f2, (3, 3))
            x = conv_bn(f"{name}_c", x, f3, (1, 1), act="identity")
            if project:
                sc = conv_bn(f"{name}_sc", inp, f3, (1, 1), stride,
                             act="identity")
            else:
                sc = inp
            g.add_vertex(f"{name}_add", ElementWiseVertex(op="add"), x, sc)
            g.add_layer(f"{name}_out", ActivationLayer(activation="relu"),
                        f"{name}_add")
            return f"{name}_out"

        x = conv_bn("conv1", "in", 64, (7, 7), (2, 2))
        x = _max_pool(g, "pool1", x)
        stages = [(3, (64, 64, 256), (1, 1)),
                  (4, (128, 128, 512), (2, 2)),
                  (6, (256, 256, 1024), (2, 2)),
                  (3, (512, 512, 2048), (2, 2))]
        for si, (blocks, filters, stride) in enumerate(stages):
            for bi in range(blocks):
                x = bottleneck(f"s{si}b{bi}", x, filters,
                               stride if bi == 0 else (1, 1), bi == 0)
        g.add_layer("avgpool", GlobalPoolingLayer(pooling_type="avg"), x)
        g.add_layer("out", OutputLayer(n_out=self.num_classes,
                                       activation="softmax", loss="mcxent"),
                    "avgpool")
        g.set_outputs("out")
        return ComputationGraph(g.build()).init()


@dataclass
class GoogLeNet(ZooModel):
    """GoogLeNet / Inception-v1 (reference ``model/GoogLeNet.java``)."""

    def init(self) -> ComputationGraph:
        h, w, c = self.input_shape
        g = GraphBuilder(
            {"activation": "relu", "weight_init": "relu",
             "updater": self.updater or Adam(learning_rate=1e-3)},
            seed=self.seed)
        g.add_inputs("in").set_input_types(InputType.convolutional(h, w, c))

        x = _conv_block(g, "conv1", "in", 64, (7, 7), (2, 2))
        x = _max_pool(g, "pool1", x)
        x = _conv_block(g, "conv2r", x, 64, (1, 1))
        x = _conv_block(g, "conv2", x, 192, (3, 3))
        x = _max_pool(g, "pool2", x)
        x = _inception_block(g, "i3a", x, 64, 96, 128, 16, 32, 32)
        x = _inception_block(g, "i3b", x, 128, 128, 192, 32, 96, 64)
        x = _max_pool(g, "pool3", x)
        x = _inception_block(g, "i4a", x, 192, 96, 208, 16, 48, 64)
        x = _inception_block(g, "i4b", x, 160, 112, 224, 24, 64, 64)
        x = _inception_block(g, "i4c", x, 128, 128, 256, 24, 64, 64)
        x = _inception_block(g, "i4d", x, 112, 144, 288, 32, 64, 64)
        x = _inception_block(g, "i4e", x, 256, 160, 320, 32, 128, 128)
        x = _max_pool(g, "pool4", x)
        x = _inception_block(g, "i5a", x, 256, 160, 320, 32, 128, 128)
        x = _inception_block(g, "i5b", x, 384, 192, 384, 48, 128, 128)
        g.add_layer("avgpool", GlobalPoolingLayer(pooling_type="avg"), x)
        g.add_layer("dropout", DropoutLayer(dropout=0.4), "avgpool")
        g.add_layer("out", OutputLayer(n_out=self.num_classes,
                                       activation="softmax", loss="mcxent"),
                    "dropout")
        g.set_outputs("out")
        return ComputationGraph(g.build()).init()


@dataclass
class InceptionResNetV1(ZooModel):
    """Inception-ResNet v1, compact faithful rendition (reference
    ``model/InceptionResNetV1.java`` — stem + scaled residual inception
    blocks A/B/C with reduction blocks)."""
    num_classes: int = 1000
    input_shape: Tuple[int, int, int] = (160, 160, 3)
    blocks_a: int = 5
    blocks_b: int = 10
    blocks_c: int = 5
    embedding_size: int = 128

    def init(self) -> ComputationGraph:
        h, w, c = self.input_shape
        g = GraphBuilder(
            {"activation": "relu", "weight_init": "relu",
             "updater": self.updater or Adam(learning_rate=1e-3)},
            seed=self.seed)
        g.add_inputs("in").set_input_types(InputType.convolutional(h, w, c))

        def conv(name, inp, n_out, kernel, stride=(1, 1), act="relu"):
            return _conv_block(g, name, inp, n_out, kernel, stride, act=act)

        def res_block(name, inp, branches, channels, scale=0.17):
            """Scaled residual add: out = relu(in + scale*conv(concat(branches)))."""
            outs = []
            for i, spec in enumerate(branches):
                x = inp
                for j, (n_out, kernel) in enumerate(spec):
                    x = conv(f"{name}_br{i}_{j}", x, n_out, kernel)
                outs.append(x)
            if len(outs) > 1:
                g.add_vertex(f"{name}_cat", MergeVertex(), *outs)
                cat = f"{name}_cat"
            else:
                cat = outs[0]
            up = conv(f"{name}_up", cat, channels, (1, 1), act="identity")
            from ..nn.conf.computation_graph import ScaleVertex
            g.add_vertex(f"{name}_scale", ScaleVertex(scale_factor=scale), up)
            g.add_vertex(f"{name}_add", ElementWiseVertex(op="add"),
                         inp, f"{name}_scale")
            g.add_layer(f"{name}", ActivationLayer(activation="relu"),
                        f"{name}_add")
            return name

        # stem (compact)
        x = conv("stem1", "in", 32, (3, 3), (2, 2))
        x = conv("stem2", x, 64, (3, 3))
        x = _max_pool(g, "stempool", x)
        x = conv("stem3", x, 128, (3, 3), (2, 2))
        x = conv("stem4", x, 256, (3, 3), (2, 2))
        # inception-resnet-A blocks
        for i in range(self.blocks_a):
            x = res_block(f"a{i}", x,
                          [[(32, (1, 1))],
                           [(32, (1, 1)), (32, (3, 3))],
                           [(32, (1, 1)), (32, (3, 3)), (32, (3, 3))]], 256)
        x = conv("redA", x, 384, (3, 3), (2, 2))
        for i in range(self.blocks_b):
            x = res_block(f"b{i}", x,
                          [[(128, (1, 1))],
                           [(128, (1, 1)), (128, (1, 7)), (128, (7, 1))]],
                          384, scale=0.10)
        x = conv("redB", x, 512, (3, 3), (2, 2))
        for i in range(self.blocks_c):
            x = res_block(f"c{i}", x,
                          [[(192, (1, 1))],
                           [(192, (1, 1)), (192, (1, 3)), (192, (3, 1))]],
                          512, scale=0.20)
        g.add_layer("avgpool", GlobalPoolingLayer(pooling_type="avg"), x)
        g.add_layer("bottleneck", DenseLayer(n_out=self.embedding_size,
                                             activation="identity"), "avgpool")
        g.add_vertex("embeddings", L2NormalizeVertex(), "bottleneck")
        g.add_layer("out", OutputLayer(n_out=self.num_classes,
                                       activation="softmax", loss="mcxent"),
                    "embeddings")
        g.set_outputs("out")
        return ComputationGraph(g.build()).init()


@dataclass
class FaceNetNN4Small2(ZooModel):
    """FaceNet NN4-small2 style embedding net (reference
    ``model/FaceNetNN4Small2.java``): inception-style trunk → L2-normalized
    embedding → center-loss softmax head."""
    num_classes: int = 100
    input_shape: Tuple[int, int, int] = (96, 96, 3)
    embedding_size: int = 128

    def init(self) -> ComputationGraph:
        from ..nn.layers.feedforward import CenterLossOutputLayer
        h, w, c = self.input_shape
        g = GraphBuilder(
            {"activation": "relu", "weight_init": "relu",
             "updater": self.updater or Adam(learning_rate=1e-3)},
            seed=self.seed)
        g.add_inputs("in").set_input_types(InputType.convolutional(h, w, c))

        x = _conv_block(g, "conv1", "in", 64, (7, 7), (2, 2))
        x = _max_pool(g, "pool1", x)
        x = _conv_block(g, "conv2", x, 192, (3, 3))
        x = _max_pool(g, "pool2", x)
        x = _inception_block(g, "i3a", x, 64, 96, 128, 16, 32, 32)
        x = _inception_block(g, "i3b", x, 64, 96, 128, 32, 64, 64)
        x = _max_pool(g, "pool3", x)
        x = _inception_block(g, "i4a", x, 256, 96, 192, 32, 64, 128)
        x = _inception_block(g, "i4e", x, 160, 112, 224, 24, 64, 128)
        g.add_layer("avgpool", GlobalPoolingLayer(pooling_type="avg"), x)
        g.add_layer("bottleneck", DenseLayer(n_out=self.embedding_size,
                                             activation="identity"),
                    "avgpool")
        g.add_vertex("embeddings", L2NormalizeVertex(), "bottleneck")
        g.add_layer("out", CenterLossOutputLayer(
            n_out=self.num_classes, activation="softmax", loss="mcxent",
            alpha=0.9, lambda_=5e-3), "embeddings")
        g.set_outputs("out")
        return ComputationGraph(g.build()).init()


@dataclass
class TextGenerationLSTM(ZooModel):
    """Char-level text generation LSTM (reference
    ``model/TextGenerationLSTM.java:34``)."""
    model_type = "rnn"
    num_classes: int = 26          # vocab size
    timesteps: int = 40
    hidden: int = 256

    def init(self):
        conf = (self._builder()
                .updater(self.updater or Adam(learning_rate=2e-3))
                .weight_init("xavier")
                .gradient_normalization("clipelementwiseabsolutevalue", 10.0)
                .list()
                .layer(LSTM(n_out=self.hidden, activation="tanh"))
                .layer(LSTM(n_out=self.hidden, activation="tanh"))
                .layer(RnnOutputLayer(n_out=self.num_classes,
                                      activation="softmax", loss="mcxent"))
                .set_input_type(InputType.recurrent(self.num_classes,
                                                    self.timesteps))
                .build())
        from ..nn.multilayer import MultiLayerNetwork
        return MultiLayerNetwork(conf).init()


ALL_MODELS = [LeNet, SimpleCNN, AlexNet, VGG16, VGG19, ResNet50, GoogLeNet,
              InceptionResNetV1, FaceNetNN4Small2, TextGenerationLSTM]


@dataclass
class TransformerLM(ZooModel):
    """Decoder-only transformer language model — the attention-era
    counterpart of TextGenerationLSTM (no reference equivalent; built from
    the TPU-native attention stack: pre-norm blocks, causal masking,
    flash/ring kernels selectable via attn_impl)."""
    model_type = "rnn"
    vocab_size: int = 256
    seq_len: int = 128
    embed: int = 256
    n_layers: int = 4
    n_heads: int = 8
    attn_impl: str = "auto"
    flash_min_seq: Optional[int] = None   # 'auto' crossover override
    moe_experts: int = 0    # >0: Switch-style sparse FFN blocks
    # integer-id targets [b, t] through the gather-based loss instead of
    # one-hot [b, t, V] — at V=8192 the one-hot path reads an extra
    # ~268 MB of HBM per step for the same value/gradients (measured in
    # BENCH_NOTES "transformer campaign"); LM training should use this
    sparse_labels: bool = False

    def init(self):
        from ..nn.layers.attention import (PositionalEncodingLayer,
                                           TransformerBlock)
        from ..nn.layers.feedforward import EmbeddingSequenceLayer
        from ..nn.layers.recurrent import RnnOutputLayer
        b = (self._builder()
             .updater(self.updater or Adam(learning_rate=3e-4))
             .weight_init("xavier")
             .list()
             .layer(EmbeddingSequenceLayer(n_out=self.embed))
             .layer(PositionalEncodingLayer()))
        for _ in range(self.n_layers):
            b = b.layer(TransformerBlock(n_heads=self.n_heads, causal=True,
                                         attn_impl=self.attn_impl,
                                         flash_min_seq=self.flash_min_seq,
                                         moe_experts=self.moe_experts))
        loss = "sparse_mcxent" if self.sparse_labels else "mcxent"
        conf = (b.layer(RnnOutputLayer(n_out=self.vocab_size,
                                       activation="softmax", loss=loss))
                .set_input_type(InputType.recurrent(self.vocab_size,
                                                    self.seq_len))
                .build())
        from ..nn.multilayer import MultiLayerNetwork
        return MultiLayerNetwork(conf).init()


ALL_MODELS.append(TransformerLM)


@dataclass
class EvaByteLM(ZooModel):
    """EvaByte's architecture (https://huggingface.co/EvaByte/EvaByte,
    ``model_type`` ``evabyte``): a byte-level decoder of bias-free pre-norm
    blocks — RMSNorm with a unit offset, rotary positions, a gated SiLU
    MLP, EVA chunked linearized attention (``nn/layers/attention.
    _eva_attention``) — a final RMSNorm and an untied head that predicts
    the next ``pred_heads`` bytes at every position.  Integer targets
    ``[b, t, pred_heads]`` (head ``n`` at ``t``: byte ``t + 1 + n``) with a
    label mask of the same shape that drops the targets past the sequence's
    end; the loss is their mean cross-entropy.  Under a lower
    ``compute_dtype`` the residual stream between the blocks and the final
    norm stay float32 (the model's ``fp32_skip_add``); the projections, the
    attention and the head compute in ``compute_dtype``.  The defaults are
    the published 6.5 B model's."""
    model_type = "rnn"
    vocab_size: int = 320
    seq_len: int = 32768
    embed: int = 4096
    n_layers: int = 32
    n_heads: int = 32
    head_dim: int = 128
    ffn_hidden: int = 11008
    pred_heads: int = 8
    window: int = 2048
    chunk: int = 16
    rope_theta: float = 1e5
    eps: float = 1e-5
    attn_impl: str = "auto"
    cache_mode: str = "none"   # 'remat': recompute each block in backward

    def init(self):
        from ..nn.layers.attention import RMSNormLayer, TransformerBlock
        from ..nn.layers.feedforward import EmbeddingSequenceLayer
        from ..nn.precision import PrecisionPolicy
        b = self._builder()
        if self.compute_dtype:
            # the final norm reads the float32 stream as the blocks do
            b = b.precision(PrecisionPolicy(
                compute_dtype=self.compute_dtype,
                keep_f32=("BatchNormalization", "RMSNormLayer")))
        b = (b.updater(self.updater or Adam(learning_rate=3e-4))
             .weight_init("xavier")
             .cache_mode(self.cache_mode)
             .list()
             .layer(EmbeddingSequenceLayer(n_out=self.embed)))
        for _ in range(self.n_layers):
            b = b.layer(TransformerBlock(
                n_heads=self.n_heads, head_dim=self.head_dim, causal=True,
                attn_impl=self.attn_impl, eps=self.eps, norm="rms",
                positions="rotary", rope_theta=self.rope_theta,
                ffn_hidden=self.ffn_hidden, gated=True, has_bias=False,
                attention="eva", window=self.window, chunk=self.chunk,
                residual_dtype="float32"))
        conf = (b.layer(RMSNormLayer(eps=self.eps))
                .layer(RnnOutputLayer(
                    n_out=self.pred_heads * self.vocab_size,
                    has_bias=False, activation="softmax",
                    loss="sparse_mcxent", pred_heads=self.pred_heads))
                .set_input_type(InputType.recurrent(self.vocab_size,
                                                    self.seq_len))
                .build())
        from ..nn.multilayer import MultiLayerNetwork
        return MultiLayerNetwork(conf).init()

    @staticmethod
    def targets(ids, pred_heads: int = 8):
        """``(x, y, None, label_mask)`` for ``fit`` from byte ids ``[b, t]``:
        ``y[b, t, n] = ids[b, t + 1 + n]`` and the mask 1 where that byte
        exists."""
        import numpy as np
        ids = np.asarray(ids)
        t = ids.shape[1]
        at = np.arange(t)[:, None] + 1 + np.arange(pred_heads)[None, :]
        mask = (at < t).astype(np.float32)
        y = ids[:, np.minimum(at, t - 1)].astype(np.int32)
        return ids, y, None, np.broadcast_to(mask, y.shape)


ALL_MODELS.append(EvaByteLM)


@dataclass
class TrinityLM(ZooModel):
    """Arcee Trinity's architecture (https://huggingface.co/arcee-ai/
    Trinity-Mini, ``model_type`` ``afmoe``): a decoder of bias-free blocks
    with four gain-only RMSNorms each (before and after each half), 32
    query heads over 4 K/V heads, RMSNorm over each head of q and k, a
    sigmoid gate on the attention's output, ``sliding_attention`` layers
    (the last ``window`` keys, rotary positions) among ``full_attention``
    ones (every earlier key, no positions) as ``layer_types`` lists them,
    ``dense_layers`` leading layers with a gated SiLU MLP and then layers
    whose FFN is routed: sigmoid scores over ``experts`` experts, the
    ``top_k`` largest a token, weights normalised and scaled, a shared
    expert beside them, no token dropped (``nn/layers/moe.
    RoutedExperts``).  The embedding is scaled by ``sqrt(embed)``; a final
    RMSNorm and an untied head follow.  ``experts_held = (first, count)``
    and a ``vocab_size`` that is a slice of the published one make this one
    chip's share of an expert-parallel deployment: the router keeps
    ``experts`` outputs, the chip computes its own experts' part of each
    token's result, and logits and loss are over the slice.  Integer
    targets ``[b, t]``.  The defaults are the published 26B model's."""
    model_type = "rnn"
    vocab_size: int = 200192
    seq_len: int = 131072
    embed: int = 2048
    n_heads: int = 32
    n_kv_heads: int = 4
    head_dim: int = 128
    ffn_hidden: int = 6144
    moe_hidden: int = 1024
    experts: int = 128
    experts_held: Optional[Tuple[int, int]] = None
    top_k: int = 8
    shared_experts: int = 1
    route_scale: float = 2.826
    dense_layers: int = 2
    layer_types: Tuple[str, ...] = ("sliding_attention",) * 3 + \
        ("full_attention",)
    n_layers: int = 32      # layer_types is repeated to this length
    window: int = 2048
    rope_theta: float = 1e4
    eps: float = 1e-5
    attn_impl: str = "auto"
    cache_mode: str = "none"

    def init(self):
        from ..nn.layers.attention import RMSNormLayer, TransformerBlock
        from ..nn.layers.feedforward import EmbeddingSequenceLayer
        kinds = [self.layer_types[i % len(self.layer_types)]
                 for i in range(self.n_layers)]
        b = (self._builder()
             .updater(self.updater or Adam(learning_rate=3e-4))
             .weight_init("xavier")
             .cache_mode(self.cache_mode)
             .list()
             .layer(EmbeddingSequenceLayer(n_out=self.embed,
                                           scale=float(self.embed) ** 0.5)))
        for i, kind in enumerate(kinds):
            sliding = {"sliding_attention": True,
                       "full_attention": False}[kind]
            routed = i >= self.dense_layers
            b = b.layer(TransformerBlock(
                n_heads=self.n_heads, n_kv_heads=self.n_kv_heads,
                head_dim=self.head_dim, causal=True,
                attn_impl=self.attn_impl, eps=self.eps, norm="rms",
                post_norm=True, qk_norm=True, attn_gate=True,
                gated=True, has_bias=False, ffn_hidden=self.ffn_hidden,
                attention="sliding" if sliding else "full",
                window=self.window if sliding else 0,
                positions="rotary" if sliding else "none",
                rope_theta=self.rope_theta,
                moe_experts=self.experts if routed else 0,
                moe_top_k=self.top_k if routed else 0,
                moe_scoring="sigmoid", moe_route_norm=True,
                moe_route_scale=self.route_scale,
                moe_shared=self.shared_experts if routed else 0,
                moe_hidden=self.moe_hidden,
                moe_held=self.experts_held if routed else None))
        conf = (b.layer(RMSNormLayer(eps=self.eps))
                .layer(RnnOutputLayer(n_out=self.vocab_size, has_bias=False,
                                      activation="softmax",
                                      loss="sparse_mcxent"))
                .set_input_type(InputType.recurrent(self.vocab_size,
                                                    self.seq_len))
                .build())
        from ..nn.multilayer import MultiLayerNetwork
        return MultiLayerNetwork(conf).init()


ALL_MODELS.append(TrinityLM)


@dataclass
class JoyAIFlashLM(ZooModel):
    """JoyAI-LLM-Flash's architecture (https://huggingface.co/jdopensource/
    JoyAI-LLM-Flash, ``model_type`` ``joyai_llm_flash``; DeepSeek-V3's
    design, arXiv:2412.19437): a decoder of bias-free pre-norm blocks with
    two gain-only RMSNorms each, multi-head latent attention
    (``nn/layers/attention.LatentAttention``: low-rank q, a joint latent
    for K and V, heads 192 wide in q and k and 128 in v, rotary positions
    on adjacent pairs of 64 of the 192), ``dense_layers`` leading layers
    with a gated SiLU MLP and then layers whose FFN is routed (sigmoid
    scores over ``experts``, the ``top_k`` largest, weights normalised and
    scaled, a shared expert, no token dropped), a final RMSNorm and an
    untied head; and ONE multi-token-prediction module: the embedding of
    the next token beside the trunk's last hidden state, merged
    (``NextTokenMerge``), one more routed block, a norm of its own, scored
    by the trunk's embedding and head for the token after next.

    A ``ComputationGraph``, because it has two outputs from one embedding
    and one head: the row of ``seq_len + 1`` token ids is embedded once
    and sliced into the two streams (``TimeSliceVertex``), the head runs
    once over both streams laid end to end (``TimeConcatVertex``), and the
    label mask carries the module's weight, so the step's loss is ``L_main
    + mtp_weight * L_mtp`` (``batch`` builds ids, targets and mask).  The
    module's vertices are traced under the scope ``mtp``.
    ``experts_held = (first, count)`` and a ``vocab_size`` that is a slice
    of the published one make this one chip's share of an expert-parallel
    deployment, as ``TrinityLM``'s do.  The defaults are the published
    48B-A2.7B model's."""
    model_type = "rnn"
    vocab_size: int = 129280
    seq_len: int = 131072
    embed: int = 2048
    n_heads: int = 32
    q_rank: int = 1536
    kv_rank: int = 512
    nope_dim: int = 128
    rope_dim: int = 64
    v_dim: int = 128
    ffn_hidden: int = 7168
    moe_hidden: int = 768
    experts: int = 256
    experts_held: Optional[Tuple[int, int]] = None
    top_k: int = 8
    shared_experts: int = 1
    route_scale: float = 2.5
    dense_layers: int = 1
    n_layers: int = 40
    rope_theta: float = 32e6
    eps: float = 1e-6
    attn_impl: str = "auto"
    cache_mode: str = "none"

    def _block(self, routed: bool):
        from ..nn.layers.attention import TransformerBlock
        return TransformerBlock(
            n_heads=self.n_heads, attention="latent",
            head_dim=self.nope_dim, rope_dim=self.rope_dim,
            v_head_dim=self.v_dim, latent_q_rank=self.q_rank,
            latent_kv_rank=self.kv_rank, rope_theta=self.rope_theta,
            causal=True, attn_impl=self.attn_impl, eps=self.eps, norm="rms",
            gated=True, has_bias=False, ffn_hidden=self.ffn_hidden,
            moe_experts=self.experts if routed else 0,
            moe_top_k=self.top_k if routed else 0, moe_scoring="sigmoid",
            moe_route_norm=True, moe_route_scale=self.route_scale,
            moe_shared=self.shared_experts if routed else 0,
            moe_hidden=self.moe_hidden,
            moe_held=self.experts_held if routed else None)

    def init(self) -> ComputationGraph:
        from ..nn.conf.computation_graph import (TimeConcatVertex,
                                                 TimeSliceVertex)
        from ..nn.layers.attention import NextTokenMerge, RMSNormLayer
        from ..nn.layers.feedforward import EmbeddingSequenceLayer
        g = (self._builder()
             .updater(self.updater or Adam(learning_rate=3e-4))
             .weight_init("xavier").cache_mode(self.cache_mode)
             .graph_builder())
        g.add_inputs("ids").set_input_types(
            InputType.recurrent(self.vocab_size, self.seq_len + 1))
        # one row of seq_len + 1 ids, embedded once: tokens 0..T-1 feed the
        # trunk, tokens 1..T the module
        g.add_layer("embed", EmbeddingSequenceLayer(n_out=self.embed), "ids")
        g.add_vertex("trunk_in", TimeSliceVertex(0, -1), "embed")
        g.add_vertex("next_in", TimeSliceVertex(1, None), "embed")
        h = "trunk_in"
        for i in range(self.n_layers):
            g.add_layer(f"block_{i}", self._block(i >= self.dense_layers), h)
            h = f"block_{i}"
        g.add_layer("norm", RMSNormLayer(eps=self.eps), h)
        g.add_vertex("mtp_in", MergeVertex(), "next_in", h, scope="mtp")
        g.add_layer("mtp_merge", NextTokenMerge(n_out=self.embed,
                                                eps=self.eps),
                    "mtp_in", scope="mtp")
        g.add_layer("mtp_block", self._block(True), "mtp_merge", scope="mtp")
        g.add_layer("mtp_norm", RMSNormLayer(eps=self.eps), "mtp_block",
                    scope="mtp")
        # the one head, over both streams laid end to end
        g.add_vertex("streams", TimeConcatVertex(), "norm", "mtp_norm")
        g.add_layer("head", RnnOutputLayer(n_out=self.vocab_size,
                                           has_bias=False,
                                           activation="softmax",
                                           loss="sparse_mcxent"), "streams")
        g.set_outputs("head")
        return ComputationGraph(g.build()).init()

    @staticmethod
    def batch(ids, mtp_weight: float = 0.3):
        """One training batch from rows of ``t + 1`` token ids ``[b, t +
        1]``: ``([ids], [y], None, [mask])`` with ``y [b, 2 t]`` the trunk's
        targets (the next token of positions ``0..t-1``) then the module's
        (the token after next; the last position has none) and ``mask [b, 2
        t]`` one on the trunk's positions, ``mtp_weight`` on the module's
        and nought on its last: the loss the graph then computes is
        ``L_main + mtp_weight * L_mtp``, each a sum over a row's tokens."""
        import numpy as np
        ids = np.asarray(ids)
        b, t = ids.shape[0], ids.shape[1] - 1
        y = np.concatenate([ids[:, 1:], ids[:, 2:],
                            np.zeros((b, 1), ids.dtype)], axis=1)
        mask = np.concatenate(
            [np.ones((b, t), np.float32),
             np.full((b, t - 1), mtp_weight, np.float32),
             np.zeros((b, 1), np.float32)], axis=1)
        return [ids], [y], None, [mask]


ALL_MODELS.append(JoyAIFlashLM)


@dataclass
class OuroLM(ZooModel):
    """Ouro's architecture (https://huggingface.co/ByteDance/Ouro-2.6B,
    ``model_type`` ``ouro``; "Scaling Latent Reasoning via Looped Language
    Models", arXiv:2510.25741): a dense decoder whose whole stack of layers
    is walked ``passes`` times **on one set of weights**.  Bias-free blocks
    with four gain-only RMSNorms each (before and after each half:
    ``x + N2(Attn(N1 x))``, ``x + N4(MLP(N3 x))``), ``n_heads`` heads of
    ``head_dim`` with as many K/V heads, rotary positions over the whole
    head (the same positions in every pass), a gated SiLU MLP; the final
    RMSNorm closes every pass and its output is what the next pass reads.
    The embedding is not scaled and the head is untied.

    The blocks and the final norm are one looped range of the list
    (``ListBuilder.loop``: parameters, Adam state and checkpoints hold it
    once, a weight's gradient is the sum over the passes, float32 under a
    lower ``compute_dtype``); the head is an ``ExitGateOutputLayer``: every
    pass's normed state is scored, a learned gate says how much of a
    position's prediction exits after each pass, and the loss is the
    exit-weighted cross-entropy less ``exit_beta`` times the entropy of the
    exit distribution (the paper's stage I).  ``output()`` is the last
    pass's distribution (an exit threshold of 1).  Integer targets ``[b,
    t]``.  Trains and runs forward; generation through the loop (a K/V
    cache a pass) is not written.  The defaults are the published 2.6B
    model's."""
    model_type = "rnn"
    vocab_size: int = 49152
    seq_len: int = 65536
    embed: int = 2048
    n_layers: int = 48
    n_heads: int = 16
    head_dim: int = 128
    ffn_hidden: int = 5632
    passes: int = 4
    exit_beta: float = 0.1
    rope_theta: float = 1e6
    eps: float = 1e-6
    attn_impl: str = "auto"
    cache_mode: str = "none"   # 'remat': recompute each layer-pass

    def init(self):
        from ..nn.layers.attention import RMSNormLayer, TransformerBlock
        from ..nn.layers.feedforward import EmbeddingSequenceLayer
        from ..nn.layers.recurrent import ExitGateOutputLayer
        b = (self._builder()
             .updater(self.updater or Adam(learning_rate=3e-4))
             .weight_init("xavier")
             .cache_mode(self.cache_mode)
             .list()
             .layer(EmbeddingSequenceLayer(n_out=self.embed)))
        for _ in range(self.n_layers):
            b = b.layer(TransformerBlock(
                n_heads=self.n_heads, head_dim=self.head_dim, causal=True,
                attn_impl=self.attn_impl, eps=self.eps, norm="rms",
                post_norm=True, gated=True, has_bias=False,
                ffn_hidden=self.ffn_hidden, positions="rotary",
                rope_theta=self.rope_theta))
        conf = (b.layer(RMSNormLayer(eps=self.eps))
                .loop(1, self.n_layers + 2, self.passes)
                .layer(ExitGateOutputLayer(
                    n_out=self.vocab_size, has_bias=False,
                    exits=self.passes, exit_beta=self.exit_beta))
                .set_input_type(InputType.recurrent(self.vocab_size,
                                                    self.seq_len))
                .build())
        from ..nn.multilayer import MultiLayerNetwork
        return MultiLayerNetwork(conf).init()


ALL_MODELS.append(OuroLM)


class ModelSelector:
    """Select zoo models by name/type (reference
    ``deeplearning4j-zoo/.../ModelSelector.java``: select(ZooType) returns a
    name → instance map for benchmarking sweeps over the whole zoo)."""

    @staticmethod
    def select(*names, **init_kwargs):
        """``names``: model class names (case-insensitive), a model_type
        ("cnn"/"rnn"), or "all".  Returns {name: uninitialized instance}."""
        by_name = {cls.__name__.lower(): cls for cls in ALL_MODELS}
        out = {}
        for name in names:
            key = name.lower()
            if key == "all":
                out.update({cls.__name__: cls(**init_kwargs)
                            for cls in ALL_MODELS})
            elif key in ("cnn", "rnn"):
                out.update({cls.__name__: cls(**init_kwargs)
                            for cls in ALL_MODELS
                            if cls.model_type == key})
            elif key in by_name:
                out[by_name[key].__name__] = by_name[key](**init_kwargs)
            else:
                raise ValueError(
                    f"unknown zoo model '{name}'; available: "
                    f"{sorted(by_name)} or 'all'/'cnn'/'rnn'")
        return out
