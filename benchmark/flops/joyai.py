"""Operations and bytes of the ``joyai`` family, from the configuration's
shapes alone.  Model FLOPs: forward plus backward (3x the forward's
products), nothing recomputed.

A token meets, in matrix products: in every block latent attention's five
projections (``hidden x q_lora_rank``, ``q_lora_rank x heads x (nope +
rope)``, ``hidden x (kv_lora_rank + rope)``, ``kv_lora_rank x heads x (nope
+ v)``, ``heads x v x hidden``); in a dense block the gated MLP's three
matrices; in a routed block the router (all ``published.n_routed_experts``
outputs), the shared expert's three, and of the routed experts held here
those it chose: ``num_experts_per_tok * n_routed_experts /
published.n_routed_experts`` of them **in expectation** under even routing,
8 * 16 / 256 = 0.5 for the benchmark's share (a step's real count moves
with the routing; the metric holds the expectation).  The blocks are the
``num_hidden_layers`` of the trunk and one more, routed, in each
multi-token-prediction module, which also has its merge (``2 hidden x
hidden``).  The head over the vocabulary slice is met once by the trunk's
stream and once by each module's.  Attention counts the pairs a query
sees, half the square: ``QK^T`` at ``nope + rope`` a pair, ``PV`` at ``v``.
Embedding gather, RMSNorm, rotary turns, SiLU, sigmoid, softmax, the sort
by expert and the optimizer are not counted."""
from __future__ import annotations


def widths(cfg: dict):
    """``(q/k head, v head)``: 192 and 128 as published."""
    return (cfg["qk_nope_head_dim"] + cfg["qk_rope_head_dim"],
            cfg["v_head_dim"])


def latent_params(cfg: dict) -> int:
    """Matrix-product parameters of one latent-attention layer."""
    e, h = cfg["hidden_size"], cfg["num_attention_heads"]
    d_qk, d_v = widths(cfg)
    rq, rkv = cfg["q_lora_rank"], cfg["kv_lora_rank"]
    return (e * rq + rq * h * d_qk + e * (rkv + cfg["qk_rope_head_dim"])
            + rkv * h * (cfg["qk_nope_head_dim"] + d_v) + h * d_v * e)


def routed_params(cfg: dict) -> float:
    """Matrix-product parameters one token meets in a routed FFN here."""
    e = cfg["hidden_size"]
    total = cfg["published"]["n_routed_experts"]
    expert = 3 * e * cfg["moe_intermediate_size"]
    chosen_here = cfg["num_experts_per_tok"] * cfg["n_routed_experts"] / total
    return e * total + (cfg["n_shared_experts"] + chosen_here) * expert


def blocks(cfg: dict) -> int:
    """Blocks with attention in them: the trunk's and the modules'."""
    return cfg["num_hidden_layers"] + cfg["num_nextn_predict_layers"]


def matmul_params(cfg: dict) -> float:
    """Matrix-product parameters one token meets, all blocks, the merge
    and the head (once a stream)."""
    e = cfg["hidden_size"]
    n, n_dense = cfg["num_hidden_layers"], cfg["first_k_dense_replace"]
    mtp = cfg["num_nextn_predict_layers"]
    return (blocks(cfg) * latent_params(cfg)
            + n_dense * 3 * e * cfg["intermediate_size"]
            + (n - n_dense + mtp) * routed_params(cfg)
            + mtp * 2 * e * e + (1 + mtp) * e * cfg["vocab_size"])


def pairs(t: int) -> float:
    """(query, key) pairs of causal attention over ``t`` positions."""
    return 0.5 * t * t


def attention_flops_per_row(cfg: dict) -> float:
    """One layer, one row of ``train_seq_len`` tokens, forward + backward:
    QK^T at the q/k width and PV at v's, 2 a pair and unit of width, three
    times for the backward."""
    d_qk, d_v = widths(cfg)
    return 3.0 * cfg["num_attention_heads"] * 2.0 * \
        pairs(cfg["train_seq_len"]) * (d_qk + d_v)


def train_step_flops(cfg: dict, rows: int) -> float:
    tokens = rows * cfg["train_seq_len"]
    return tokens * 6.0 * matmul_params(cfg) + \
        rows * blocks(cfg) * attention_flops_per_row(cfg)


# ---- the attention kernels, one call each: all heads of all rows as the
# kernel's batch, q and k [rows * 32, t, 192], v [rows * 32, t, 128],
# causal.  The products of a call by the width they contract or produce
# (``flops/gpt2.py`` counts the same products at one width): forward S (qk)
# and PV (v); dq recomputes S (qk), forms dP (v) and dQ (qk); dkv
# recomputes S (qk), forms dV (v), dP (v) and dK (qk).  The arrays a call
# reads or writes once: q, k (and dq, dk) at the q/k width; v, o, dO (and
# dv) at v's; the row statistics are left out.
KERNEL_PRODUCTS = {"flash_fwd": (1, 1), "flash_bwd_dq": (2, 1),
                   "flash_bwd_dkv": (2, 2)}
KERNEL_ARRAYS = {"flash_fwd": (2, 2), "flash_bwd_dq": (3, 3),
                 "flash_bwd_dkv": (3, 4)}


def kernel_call(cfg: dict, rows: int, kernel: str, itemsize: int = 2):
    """(flops, bytes) the algorithm needs for one call of ``kernel``
    (``flash_fwd``, ``flash_bwd_dq``, ``flash_bwd_dkv``), whatever the
    kernel does inside: nothing is counted at a padded width."""
    t = cfg["train_seq_len"]
    heads = rows * cfg["num_attention_heads"]
    d_qk, d_v = widths(cfg)
    at_qk, at_v = KERNEL_PRODUCTS[kernel]
    flops = 2.0 * heads * pairs(t) * (at_qk * d_qk + at_v * d_v)
    at_qk, at_v = KERNEL_ARRAYS[kernel]
    nbytes = heads * t * (at_qk * d_qk + at_v * d_v) * itemsize
    return flops, nbytes
