"""Operations and bytes that the algorithm needs, from a configuration's
shapes. The yardstick's own arithmetic: the program's copy
(``models/zoo/transformer.py::gpt_train_flops_per_token``) may change, this
may not.

A multiply-add counts as 2 operations. Causal attention counts the half of the
score matrix that the mask keeps. Recomputation counts nothing: a kernel that
computes the scores again in its backward pass gets no credit for it.
"""

from __future__ import annotations

from typing import Any, Dict


def train_macs_per_token(cfg: Dict[str, Any], seq_len: int) -> float:
    """Forward multiply-adds per token of the matrix products of the model:
    QKV, attention output, the two MLP matrices, the causal score and value
    products, and the LM head (once, tied or not). Embedding gathers,
    LayerNorm, GELU, softmax and the optimizer are not model operations."""
    d, f = cfg["n_embd"], cfg["n_inner"]
    per_layer = 3 * d * d + d * d + 2 * d * f
    attn = seq_len * d  # (QK^T + PV) = 2 * seq_len * d, halved by the mask
    return cfg["n_layer"] * (per_layer + attn) + d * cfg["vocab_size"]


def train_flops_per_token(cfg: Dict[str, Any], seq_len: int) -> float:
    """Forward (2 per multiply-add) + backward (4): 6 per multiply-add."""
    return 6.0 * train_macs_per_token(cfg, seq_len)


def flash_fwd_cost(batch: int, n_head: int, seq_len: int, head_dim: int,
                   bytes_per_el: int = 2) -> Dict[str, float]:
    """Causal attention forward over ``batch * n_head`` rows: the two
    products QK^T and PV on the kept half; reads q, k, v, writes o and the
    float32 log-sum-exp row."""
    rows = batch * n_head
    flops = rows * 2 * (2.0 * seq_len * seq_len * head_dim) / 2
    bytes_ = rows * (4 * seq_len * head_dim * bytes_per_el + seq_len * 4)
    return {"flops": flops, "bytes": float(bytes_)}


def flash_bwd_cost(batch: int, n_head: int, seq_len: int, head_dim: int,
                   bytes_per_el: int = 2) -> Dict[str, float]:
    """Causal attention backward: the algorithm's four products (dV = P^T dO,
    dP = dO V^T, dQ = dS K, dK = dS^T Q) on the kept half, not the scores
    computed again; reads q, k, v, o, dO and the log-sum-exp, writes dq, dk,
    dv."""
    rows = batch * n_head
    flops = rows * 4 * (2.0 * seq_len * seq_len * head_dim) / 2
    bytes_ = rows * (8 * seq_len * head_dim * bytes_per_el + seq_len * 4)
    return {"flops": flops, "bytes": float(bytes_)}


def roofline_seconds(cost: Dict[str, float], peaks: Dict[str, float]):
    """The least time the chip could take for ``cost`` and which peak sets
    it: ``(seconds, "compute" | "memory")``."""
    t_c = cost["flops"] / peaks["bf16_flops_per_s"]
    t_m = cost["bytes"] / peaks["hbm_bytes_per_s"]
    return (t_c, "compute") if t_c >= t_m else (t_m, "memory")
