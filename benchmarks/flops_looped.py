"""Operations that a looped language model's algorithm needs, from a
configuration's shapes: the yardstick's own arithmetic, as ``flops.py`` is
for the GPT family.

A multiply-add counts as 2 operations. Causal attention counts the half of
the score matrix that the mask keeps. Every block of the stack is applied
``total_ut_steps`` times a token and the head scores every pass, so both count
that often. Recomputation counts nothing: neither the scores that a backward
kernel makes again nor a block body that the step runs twice.
"""

from __future__ import annotations

from typing import Any, Dict


def block_applications(cfg: Dict[str, Any]) -> int:
    """Block applications a token: each of ``n_layer`` blocks once a pass.
    Also the flash kernels' calls a step."""
    return cfg["total_ut_steps"] * cfg["n_layer"]


def train_macs_per_token(cfg: Dict[str, Any], seq_len: int) -> float:
    """Forward multiply-adds per token of the model's matrix products: an
    application's four projections (q and o at the query heads' width, k and
    v at the key/value heads'), the causal half of its scores and its gated
    MLP's three matrices, ``total_ut_steps * n_layer`` times; the head
    ``total_ut_steps`` times. Embedding gathers, norms, rotary positions,
    the gate's ``hidden_size`` multiply-adds a pass (8 thousand of 2 billion
    at the published widths), the exit distribution and the optimizer are
    not model operations."""
    d, f = cfg["hidden_size"], cfg["intermediate_size"]
    q = cfg["num_attention_heads"] * cfg["head_dim"]
    kv = cfg["num_key_value_heads"] * cfg["head_dim"]
    application = 2 * d * q + 2 * d * kv + seq_len * q + 3 * d * f
    return block_applications(cfg) * application \
        + cfg["total_ut_steps"] * d * cfg["vocab_size"]


def train_flops_per_token(cfg: Dict[str, Any], seq_len: int) -> float:
    """Forward (2 per multiply-add) + backward (4): 6 per multiply-add."""
    return 6.0 * train_macs_per_token(cfg, seq_len)
