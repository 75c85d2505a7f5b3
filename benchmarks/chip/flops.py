"""Operations and bytes the algorithm needs, from a configuration's sizes.

The parameter and FLOP arithmetic follows ``benchmarks/roofline.py``
(``param_counts``, ``expert_flops_per_row``), copied here so that no
change to the program or its other benchmarks can move the yardstick:
2 FLOPs per multiply-add, active parameters only (each token's top-k
experts), bfloat16 weights.
"""
from __future__ import annotations

WEIGHT_BYTES = 2   # bfloat16


def expert_flops_per_row(dims) -> float:
    """One (token, expert) row: gate, up and down projections."""
    return 6.0 * dims.d_model * dims.d_expert


def expert_bytes(dims) -> float:
    """One expert's weights (w1, w3, w2), read once."""
    return 3.0 * dims.d_model * dims.d_expert * WEIGHT_BYTES


def layer_active_params(dims) -> float:
    """Attention projections, router and the top-k experts of one layer."""
    d, hd = dims.d_model, dims.head_dim
    attn = (2 * dims.n_heads + 2 * dims.n_kv_heads) * hd * d
    return attn + d * dims.n_experts + dims.top_k * 3 * d * dims.d_expert


def attention_flops(dims, context: float) -> float:
    """Scores and weighted values of one query over ``context`` keys, all
    layers."""
    return 4.0 * dims.n_layers * context * dims.n_heads * dims.head_dim


def token_flops(dims, context: float, head: bool) -> float:
    """Forward FLOPs of one token attending over ``context`` positions;
    ``head``: its logits are computed (every decoded token, a prompt's last
    one only)."""
    f = 2.0 * dims.n_layers * layer_active_params(dims) \
        + attention_flops(dims, context)
    if head:
        f += 2.0 * dims.vocab * dims.d_model
    return f
