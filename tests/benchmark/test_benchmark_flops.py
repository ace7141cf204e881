"""The FLOP functions against numbers worked out by hand."""
import os
import sys

import pytest

REPO = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(
    __file__))))
sys.path.insert(0, REPO)

from benchmark import common  # noqa: E402


def test_gpt2_medium_step_is_6_98_tflop():
    flops = common.load_module("flops", "gpt2")
    cfg = common.load_json("configs", "gpt2-medium.json")
    # 24 blocks of 12.58 M and a head of 51.5 M matmul parameters
    assert flops.matmul_params(cfg) == 24 * 12 * 1024 ** 2 + 1024 * 50304
    assert flops.attention_flops_per_token(cfg) == pytest.approx(6.29e6,
                                                                 rel=1e-3)
    assert flops.train_step_flops(cfg, 3) == pytest.approx(6.98e12, rel=2e-3)


@pytest.mark.parametrize("kernel,products,arrays", [
    ("flash_fwd", 2, 4), ("flash_bwd_dq", 3, 6), ("flash_bwd_dkv", 4, 7)])
def test_flash_kernel_call_counts(kernel, products, arrays):
    flops = common.load_module("flops", "gpt2")
    cfg = common.load_json("configs", "gpt2-medium.json")
    f, b = flops.kernel_call(cfg, 3, kernel)
    # per head: 2*t*t*d a product, half of it under the causal mask
    assert f == products * 3 * 16 * 1024 * 1024 * 64
    assert b == arrays * 3 * 16 * 1024 * 64 * 2


def test_resnet50_forward_is_3_86_gmac():
    flops = common.load_module("flops", "resnet50")
    cfg = common.load_json("configs", "resnet50-224.json")
    # He et al. give 3.8e9 multiply-adds for the 50-layer column
    assert flops.forward_macs(cfg) == pytest.approx(3.86e9, rel=2e-3)
    # 5.93 TFLOP a step of 256; the issue's "about 6.3" took 8.2 GFLOP a
    # forward pass, the count of the variant with the stride on the 3x3
    step = flops.train_step_flops(cfg, 256)
    assert step == pytest.approx(5.926e12, rel=1e-3)
    assert step == pytest.approx(6.3e12, rel=0.07)


def test_resnet50_parameters():
    ref = common.load_module("reference", "resnet50")
    cfg = common.load_json("configs", "resnet50-224.json")
    # 25,557,032 of the published network and 26,560 convolution biases
    assert ref.n_params(cfg) == 25_557_032 + 26_560 == cfg["parameters"]


def test_gpt2_parameters():
    ref = common.load_module("reference", "gpt2")
    cfg = common.load_json("configs", "gpt2-medium.json")
    assert ref.n_params(cfg) == cfg["parameters"]
