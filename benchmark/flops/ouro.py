"""Operations and bytes of the ``ouro`` family, from the configuration's
shapes alone.  Model FLOPs: forward plus backward (3x the forward's
products), nothing recomputed (a remat run's replayed blocks are not
counted).

The layers are walked ``total_ut_steps`` times, so a token meets, in matrix
products, every layer's four attention projections and three MLP matrices
once a pass (a layer-pass), and after every pass the head over the whole
vocabulary and the exit gate's one column.  Attention counts the pairs a
query sees, half the square, once a layer-pass.  Embedding gather, RMSNorm,
rotary turns, SiLU, softmax, the exit distribution and the optimizer are
not counted."""
from __future__ import annotations


def layer_passes(cfg: dict) -> int:
    return cfg["num_hidden_layers"] * cfg["total_ut_steps"]


def matmul_params(cfg: dict) -> float:
    """Matrix-product parameters one token meets a step: every layer-pass,
    and the head and the gate at every exit."""
    e, d = cfg["hidden_size"], cfg["head_dim"]
    h, kv = cfg["num_attention_heads"], cfg["num_key_value_heads"]
    layer = 2 * e * h * d + 2 * e * kv * d + 3 * e * cfg["intermediate_size"]
    exit_ = e * cfg["vocab_size"] + e
    return layer_passes(cfg) * layer + cfg["total_ut_steps"] * exit_


def attention_flops_per_row(cfg: dict) -> float:
    """One layer-pass, one row of ``train_seq_len`` tokens, forward +
    backward: QK^T and PV, 2 * d a pair each over half the square, three
    times for the backward."""
    t = cfg["train_seq_len"]
    return 3.0 * cfg["num_attention_heads"] * 2 * 2.0 * 0.5 * t * t * \
        cfg["head_dim"]


def train_step_flops(cfg: dict, rows: int) -> float:
    tokens = rows * cfg["train_seq_len"]
    return tokens * 6.0 * matmul_params(cfg) + \
        rows * layer_passes(cfg) * attention_flops_per_row(cfg)


# ---- the attention kernels, one call each: all heads of all rows as the
# kernel's batch, [rows * 16, t, 128], causal; a step makes one call of
# each a layer-pass.  Products and arrays a call as flops/gpt2.py counts
# them.
KERNEL_PRODUCTS = {"flash_fwd": 2, "flash_bwd_dq": 3, "flash_bwd_dkv": 4}
KERNEL_ARRAYS = {"flash_fwd": 4, "flash_bwd_dq": 6, "flash_bwd_dkv": 7}


def kernel_call(cfg: dict, rows: int, kernel: str, itemsize: int = 2):
    """(flops, bytes) the algorithm needs for one call of ``kernel``."""
    t, d = cfg["train_seq_len"], cfg["head_dim"]
    heads = rows * cfg["num_attention_heads"]
    flops = KERNEL_PRODUCTS[kernel] * 2.0 * heads * 0.5 * t * t * d
    nbytes = KERNEL_ARRAYS[kernel] * heads * t * d * itemsize
    return flops, nbytes
