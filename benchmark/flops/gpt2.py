"""Operations and bytes of the ``gpt2`` family, from the configuration's
shapes alone.  Model FLOPs: forward plus backward (3x the forward's
matrix products), causal attention counted at half of the square, nothing
recomputed; embedding gather, LayerNorm, GELU, softmax and the optimizer
are not counted."""
from __future__ import annotations


def matmul_params(cfg: dict) -> int:
    e, f, v, n = (cfg["n_embd"], cfg["n_inner"], cfg["vocab_size"],
                  cfg["n_layer"])
    return n * (4 * e * e + 2 * e * f) + e * v


def attention_flops_per_token(cfg: dict) -> float:
    """Per layer: QK^T and PV are 2*t*e each forward over the full square,
    half of it under the causal mask, times 3 for forward + backward."""
    return 3.0 * 0.5 * 4.0 * cfg["n_positions"] * cfg["n_embd"]


def train_step_flops(cfg: dict, rows: int) -> float:
    tokens = rows * cfg["n_positions"]
    per_token = 6.0 * matmul_params(cfg) + \
        cfg["n_layer"] * attention_flops_per_token(cfg)
    return tokens * per_token


# ---- the attention kernels, one call each (all heads of all rows) --------
# products of [t, d] x [d, t] or [t, t] x [t, d] per head, 2*t*t*d each,
# halved by the causal mask: forward S and PV (2); dq recomputes S, forms dP
# and dQ (3); dkv recomputes S, forms dV, dP and dK (4).
KERNEL_PRODUCTS = {"flash_fwd": 2, "flash_bwd_dq": 3, "flash_bwd_dkv": 4}
# [rows, heads, t, d] arrays each call reads or writes once, in the compute
# type (the row statistics, t floats a head, are left out: under 2 %)
KERNEL_ARRAYS = {"flash_fwd": 4, "flash_bwd_dq": 6, "flash_bwd_dkv": 7}


def kernel_call(cfg: dict, rows: int, kernel: str, itemsize: int = 2):
    """(flops, bytes) the algorithm needs for one call of ``kernel``."""
    t, e = cfg["n_positions"], cfg["n_embd"]
    flops = KERNEL_PRODUCTS[kernel] * 0.5 * 2.0 * rows * t * t * e
    nbytes = KERNEL_ARRAYS[kernel] * rows * t * e * itemsize
    return flops, nbytes
