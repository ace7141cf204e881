"""Operations and bytes of the ``evabyte`` family, from the configuration's
shapes alone.  Model FLOPs: forward plus backward (3x the forward's
products), nothing recomputed (the cell rematerialises every block; that
forward is not counted).  The windows' causal attention counts at half of
each window's square; the summaries count what a window's queries see,
``w * window_size / chunk_size`` of them for window ``w``; the pooling of
the chunks is counted too.  Embedding gather, RMSNorm, rotary turns, SiLU,
softmax and the optimizer are not counted."""
from __future__ import annotations


def matmul_params(cfg: dict) -> int:
    e, f, n = (cfg["hidden_size"], cfg["intermediate_size"],
               cfg["num_hidden_layers"])
    hd = cfg["num_attention_heads"] * cfg["head_dim"]
    return n * (4 * e * hd + 3 * e * f) + \
        e * cfg["num_pred_heads"] * cfg["vocab_size"]


def attention_flops_per_row(cfg: dict) -> float:
    """Per layer and row of ``train_seq_len`` bytes, forward + backward."""
    t, w, c = cfg["train_seq_len"], cfg["window_size"], cfg["chunk_size"]
    h, d = cfg["num_attention_heads"], cfg["head_dim"]
    windows = t // w
    # QK^T and PV, 2*w*w*d each over the full square, half under the mask
    local = windows * 0.5 * 2 * 2.0 * w * w * d
    # window i's queries against the i*w/c summaries before it
    seen = sum(i * (w // c) for i in range(windows))
    summaries = 2 * 2.0 * w * seen * d
    # k . phi, and the two weighted sums of a chunk's keys and values
    pooling = 3 * 2.0 * t * d
    return 3.0 * h * (local + summaries + pooling)


def train_step_flops(cfg: dict, rows: int) -> float:
    tokens = rows * cfg["train_seq_len"]
    return tokens * 6.0 * matmul_params(cfg) + \
        rows * cfg["num_hidden_layers"] * attention_flops_per_row(cfg)


# ---- the attention kernels, one call each: the windows of all rows and
# heads as the kernel's batch, [rows * t / window * heads, window, head_dim],
# causal.  Products and arrays a call as flops/gpt2.py counts them.
KERNEL_PRODUCTS = {"flash_fwd": 2, "flash_bwd_dq": 3, "flash_bwd_dkv": 4}
KERNEL_ARRAYS = {"flash_fwd": 4, "flash_bwd_dq": 6, "flash_bwd_dkv": 7}


def kernel_call(cfg: dict, rows: int, kernel: str, itemsize: int = 2):
    """(flops, bytes) the algorithm needs for one call of ``kernel``."""
    w, d = cfg["window_size"], cfg["head_dim"]
    heads = rows * (cfg["train_seq_len"] // w) * cfg["num_attention_heads"]
    flops = KERNEL_PRODUCTS[kernel] * 0.5 * 2.0 * heads * w * w * d
    nbytes = KERNEL_ARRAYS[kernel] * heads * w * d * itemsize
    return flops, nbytes
