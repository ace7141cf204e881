"""Operations and bytes of the ``trinity`` family, from the configuration's
shapes alone.  Model FLOPs: forward plus backward (3x the forward's
products), nothing recomputed (the routed experts' buffers are rebuilt in
the backward pass; that forward is not counted).

A token meets, in matrix products: in every layer the attention's five
projections (q, output and gate at 32 heads, k and v at 4); in a dense
layer the gated MLP's three matrices; in a routed layer the router (all
``published.num_experts`` outputs), the shared expert's three, and of the
routed experts held here those it chose: ``num_experts_per_tok *
num_experts / published.num_experts`` of them **in expectation** under
even routing, 8 * 16 / 128 = 1 for the benchmark's share (a step's real
count moves with the routing; the metric holds the expectation); and the
head over the vocabulary slice.  Attention counts the pairs a query sees:
half the square on a full layer, the band ``W * T - W * W / 2`` on a
sliding one (exact to ``W / 2`` pairs).  Embedding gather, RMSNorm, rotary
turns, SiLU, sigmoid, softmax, the sort by expert and the optimizer are
not counted."""
from __future__ import annotations


def sliding_layers(cfg: dict):
    """``[sliding?, ...]``, one a layer."""
    return [kind == "sliding_attention"
            for kind in cfg["layer_types"][:cfg["num_hidden_layers"]]]


def matmul_params(cfg: dict) -> float:
    """Matrix-product parameters one token meets, all layers and the head."""
    e, d = cfg["hidden_size"], cfg["head_dim"]
    h, kv = cfg["num_attention_heads"], cfg["num_key_value_heads"]
    attention = 3 * e * h * d + 2 * e * kv * d
    dense = 3 * e * cfg["intermediate_size"]
    expert = 3 * e * cfg["moe_intermediate_size"]
    total = cfg["published"]["num_experts"]
    chosen_here = cfg["num_experts_per_tok"] * cfg["num_experts"] / total
    routed = e * total + (cfg["num_shared_experts"] + chosen_here) * expert
    n, n_dense = cfg["num_hidden_layers"], cfg["num_dense_layers"]
    return n * attention + n_dense * dense + (n - n_dense) * routed + \
        e * cfg["vocab_size"]


def pairs(t: int, window: int = None) -> float:
    """(query, key) pairs of causal attention over ``t`` positions: half
    the square, or under a window of ``W < t`` the band ``W t - W W / 2``."""
    if window is None or window >= t:
        return 0.5 * t * t
    return window * t - 0.5 * window * window


def attention_flops_per_row(cfg: dict, sliding: bool) -> float:
    """One layer, one row of ``train_seq_len`` tokens, forward + backward:
    QK^T and PV, 2 * d a pair each, three times for the backward."""
    t = cfg["train_seq_len"]
    window = cfg["sliding_window"] if sliding else None
    return 3.0 * cfg["num_attention_heads"] * 2 * 2.0 * \
        pairs(t, window) * cfg["head_dim"]


def train_step_flops(cfg: dict, rows: int) -> float:
    tokens = rows * cfg["train_seq_len"]
    return tokens * 6.0 * matmul_params(cfg) + rows * sum(
        attention_flops_per_row(cfg, s) for s in sliding_layers(cfg))


# ---- the attention kernels, one call each: all query heads of all rows as
# the kernel's batch, [rows * 32, t, 128], causal.  Products and arrays a
# call as flops/gpt2.py counts them.  Q, O and dO have 32 heads; K and V
# (and dK, dV) are counted at what the kernel reads and writes, which in
# this program is 32 heads too: the 4 K/V heads reach it expanded.
KERNEL_PRODUCTS = {"fwd": 2, "bwd_dq": 3, "bwd_dkv": 4}
KERNEL_ARRAYS = {"fwd": 4, "bwd_dq": 6, "bwd_dkv": 7}


def _call(cfg: dict, rows: int, kernel: str, window, itemsize: int):
    t, d = cfg["train_seq_len"], cfg["head_dim"]
    heads = rows * cfg["num_attention_heads"]
    flops = KERNEL_PRODUCTS[kernel] * 2.0 * heads * pairs(t, window) * d
    nbytes = KERNEL_ARRAYS[kernel] * heads * t * d * itemsize
    return flops, nbytes


def kernel_call(cfg: dict, rows: int, kernel: str, itemsize: int = 2):
    """(flops, bytes) the algorithm needs for one full call of ``kernel``
    (``flash_fwd``, ``flash_bwd_dq``, ``flash_bwd_dkv``)."""
    return _call(cfg, rows, kernel[len("flash_"):], None, itemsize)


def window_kernel_call(cfg: dict, rows: int, kernel: str, itemsize: int = 2):
    """The same for one windowed call (``flash_win_fwd``, ...): the band's
    pairs; the arrays are read and written whole all the same."""
    return _call(cfg, rows, kernel[len("flash_win_"):],
                 cfg["sliding_window"], itemsize)
