"""Operations and bytes that the hybrid state-space family's algorithm needs,
from a configuration's shapes: the yardstick's own arithmetic, as
``flops.py`` is for the GPT family.

A multiply-add counts as 2 operations. Causal attention counts the half of
the score matrix that the mask keeps, and the chunked scan the causal half of
a chunk's scores. Recomputation counts nothing: neither the scores that a
backward kernel makes again nor a block body that the step runs twice.
"""

from __future__ import annotations

from typing import Any, Dict


def _scan_macs_per_token(cfg: Dict[str, Any]) -> float:
    """The chunked scan's four products a token at the published chunk:
    ``C B^T`` once a group (the causal half of a chunk), the scores applied
    to ``x`` (the causal half) and the chunk's state made from ``x`` and
    ``B`` and read through ``C``, once a head."""
    q, n, p = cfg["mamba_chunk_size"], cfg["mamba_d_state"], cfg["mamba_d_head"]
    return cfg["mamba_n_groups"] * q * n / 2 \
        + cfg["mamba_n_heads"] * (q * p / 2 + 2 * p * n)


def train_macs_per_token(cfg: Dict[str, Any], seq_len: int) -> float:
    """Forward multiply-adds per token of the model's matrix products: every
    block's by ``layer_types`` (a Mamba-2 block's two projections and its
    scan, an attention block's four projections and the causal half of its
    scores, the gated MLP of both), and the head once (it is tied).
    Embedding gathers, norms, the convolution, gates and the optimizer are
    not model operations."""
    d, f = cfg["hidden_size"], cfg["shared_intermediate_size"]
    inner = cfg["mamba_n_heads"] * cfg["mamba_d_head"]
    gn = cfg["mamba_n_groups"] * cfg["mamba_d_state"]
    kv = cfg["num_key_value_heads"] * (d // cfg["num_attention_heads"])
    mlp = 3 * d * f
    per_kind = {
        "mamba": d * (2 * inner + 2 * gn + cfg["mamba_n_heads"]) + inner * d
                 + _scan_macs_per_token(cfg) + mlp,
        "attention": 2 * d * d + 2 * d * kv + seq_len * d + mlp,
    }
    return sum(per_kind[k] for k in cfg["layer_types"]) + d * cfg["vocab_size"]


def train_flops_per_token(cfg: Dict[str, Any], seq_len: int) -> float:
    """Forward (2 per multiply-add) + backward (4): 6 per multiply-add."""
    return 6.0 * train_macs_per_token(cfg, seq_len)


def _scan_io(cfg, batch, seq_len, bytes_per_el):
    """Elements of one pass over the scan's arrays: ``x`` and ``y`` (or their
    gradients) [b, t, h, p], ``B`` and ``C`` [b, t, g, n], ``dt`` [b, t, h]
    in float32; and the bytes of the float32 state each chunk starts from."""
    h, p = cfg["mamba_n_heads"], cfg["mamba_d_head"]
    gn = cfg["mamba_n_groups"] * cfg["mamba_d_state"]
    tokens = batch * seq_len
    xy = tokens * h * p * bytes_per_el
    bc = tokens * gn * bytes_per_el
    dt = tokens * h * 4
    states = batch * (seq_len // cfg["mamba_chunk_size"]) * h * p \
        * cfg["mamba_d_state"] * 4
    return xy, bc, dt, states


def ssd_fwd_cost(cfg: Dict[str, Any], batch: int, seq_len: int,
                 bytes_per_el: int = 2) -> Dict[str, float]:
    """One forward scan over ``batch`` rows: the four products; reads x, dt,
    B, C, writes y and the chunks' states."""
    xy, bc, dt, states = _scan_io(cfg, batch, seq_len, bytes_per_el)
    return {"flops": 2.0 * _scan_macs_per_token(cfg) * batch * seq_len,
            "bytes": float(2 * xy + 2 * bc + dt + states)}


def ssd_bwd_cost(cfg: Dict[str, Any], batch: int, seq_len: int,
                 bytes_per_el: int = 2) -> Dict[str, float]:
    """One backward scan: twice the forward's products (each product's two
    transposes), not the scores made again; reads x, dt, B, C, dy and the
    saved states, writes the gradients of x, dt, B and C."""
    xy, bc, dt, states = _scan_io(cfg, batch, seq_len, bytes_per_el)
    return {"flops": 4.0 * _scan_macs_per_token(cfg) * batch * seq_len,
            "bytes": float(3 * xy + 4 * bc + 2 * dt + states)}


def _attention_io(cfg, batch, seq_len, bytes_per_el):
    """Bytes of one pass over an attention layer's arrays: at the query
    heads' width (q, o and their gradients), at the key/value heads' width
    (k, v and theirs: each read once, the repeat to the query heads that the
    block makes is no work of the algorithm), and of the float32
    log-sum-exp; and the score matrix's multiply-adds that the mask keeps."""
    heads = cfg["num_attention_heads"]
    hd = cfg["hidden_size"] // heads
    tokens = batch * seq_len
    q = tokens * heads * hd * bytes_per_el
    kv = tokens * cfg["num_key_value_heads"] * hd * bytes_per_el
    return q, kv, tokens * heads * 4, batch * heads * seq_len * seq_len * hd / 2


def attention_fwd_cost(cfg: Dict[str, Any], batch: int, seq_len: int,
                       bytes_per_el: int = 2) -> Dict[str, float]:
    """One causal grouped-query attention forward over ``batch`` rows: the
    two products QK^T and PV on the kept half for every query head; reads q,
    k, v, writes o and the log-sum-exp."""
    q, kv, lse, macs = _attention_io(cfg, batch, seq_len, bytes_per_el)
    return {"flops": 2 * 2.0 * macs, "bytes": float(2 * q + 2 * kv + lse)}


def attention_bwd_cost(cfg: Dict[str, Any], batch: int, seq_len: int,
                       bytes_per_el: int = 2) -> Dict[str, float]:
    """Its backward: the algorithm's four products on the kept half, not the
    scores made again; reads q, k, v, o, dO and the log-sum-exp, writes the
    gradients of q, k and v."""
    q, kv, lse, macs = _attention_io(cfg, batch, seq_len, bytes_per_el)
    return {"flops": 4 * 2.0 * macs, "bytes": float(4 * q + 4 * kv + lse)}
