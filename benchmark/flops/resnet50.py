"""Operations of the ``resnet50`` family, from the configuration's shapes
alone.  Model FLOPs: two for every multiply-add of every convolution and of
the output matrix, forward plus backward (3x the forward), nothing
recomputed; biases, batch normalisation, ReLU, pooling, the loss and the
optimizer are not counted."""
from __future__ import annotations

from benchmark.reference import resnet50 as shapes


def forward_macs(cfg: dict) -> int:
    """Multiply-adds of one image's forward pass.  ``side`` is the side of
    the image a convolution writes: the stem halves it, the pooling halves
    it again, and a block's first convolution divides it by its stride (the
    block's projection writes that side too)."""
    side, total = cfg["image_size"], 0
    for name, kind, a in shapes.layers(cfg):
        if kind == "dense":
            total += a[0] * a[1]
            continue
        kh, kw, c_in, c_out, stride = a
        if name == "conv1" or name.endswith("_a"):
            side = -(-side // stride)
        total += side * side * kh * kw * c_in * c_out
        if name == "conv1":
            side = -(-side // 2)            # the 3x3 max pooling of stride 2
    return total


def train_step_flops(cfg: dict, batch: int) -> float:
    return 3.0 * 2.0 * forward_macs(cfg) * batch
