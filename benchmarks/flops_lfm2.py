"""Operations and bytes that the LFM2 mixture-of-experts family's algorithm
needs (gated short convolutions, QK-normed grouped-query attention, routed
experts held as one chip's share), from a configuration's shapes: the
yardstick's own arithmetic, as ``flops.py`` is for the GPT family.

A multiply-add counts as 2 operations. The counts are of what the model
needs, whatever implements it: the held experts at ``k * held / E`` of an
expert a token (the rows the router sends them on average, not the padded
tiles or the static buffer), the scores on the causal half of the keys, the
head once; the convolution's 3 taps, the norms, the rotation, the router's
sort and the expert bias count nothing, and neither does recomputation.
"""

from __future__ import annotations

from typing import Any, Dict, List


def _sizes(cfg):
    d, heads = cfg["hidden_size"], cfg["num_attention_heads"]
    return d, heads, cfg["num_key_value_heads"], d // heads


def routed_layers(cfg: Dict[str, Any]) -> List[bool]:
    """For each layer of ``layer_types``: whether its feed-forward is the
    routed experts (its published index at or past ``num_dense_layers``)."""
    published = cfg.get("published_layers") or range(len(cfg["layer_types"]))
    return [i >= cfg["num_dense_layers"] for i in published]


def held_per_token(cfg: Dict[str, Any]) -> float:
    """Held experts a token runs through on average: ``k`` picks of
    ``num_routed_experts``, ``num_experts`` of them held here."""
    return (cfg["num_experts_per_tok"] * cfg["num_experts"]
            / cfg["num_routed_experts"])


def matrix_macs_per_token(kind: str, routed: bool, cfg: Dict[str, Any]) -> float:
    """Forward multiply-adds a token of one layer's matrices: the mixer's
    projections and the feed-forward (the router and the held experts'
    share, or the dense MLP)."""
    d, heads, kv, hd = _sizes(cfg)
    mixer = (3 * d * d + d * d if kind == "conv"
             else 2 * d * d + 2 * d * kv * hd)
    if routed:
        ffn = d * cfg["num_routed_experts"] \
            + held_per_token(cfg) * 3 * d * cfg["moe_intermediate_size"]
    else:
        ffn = 3 * d * cfg["intermediate_size"]
    return mixer + ffn


def attention_macs_per_token(cfg: Dict[str, Any], seq_len: int) -> float:
    """Scores and their application a token of one attention layer, on the
    causal half of the keys (itself included)."""
    _, heads, _, hd = _sizes(cfg)
    return 2 * heads * hd * (seq_len + 1) / 2


def train_macs_per_token(cfg: Dict[str, Any], seq_len: int) -> float:
    """Forward multiply-adds a token of the whole model."""
    kinds = cfg["layer_types"]
    return (sum(matrix_macs_per_token(k, r, cfg)
                for k, r in zip(kinds, routed_layers(cfg)))
            + kinds.count("full_attention") * attention_macs_per_token(cfg, seq_len)
            + cfg["hidden_size"] * cfg["vocab_size"])


def train_flops_per_token(cfg: Dict[str, Any], seq_len: int) -> float:
    """Forward (2 per multiply-add) + backward (4): 6 per multiply-add."""
    return 6.0 * train_macs_per_token(cfg, seq_len)


def _gmm(cfg: Dict[str, Any], tokens: int):
    """Rows the held experts take in a step's expert layer, the two products'
    widths, and the held matrices' elements."""
    d, f = cfg["hidden_size"], cfg["moe_intermediate_size"]
    rows = tokens * held_per_token(cfg)
    weights = cfg["num_experts"] * (d * 2 * f + f * d)
    return rows, d, f, weights


def gmm_fwd_cost(cfg: Dict[str, Any], tokens: int,
                 bytes_per_el: int = 2) -> Dict[str, float]:
    """The grouped products of a step's forward over ``tokens`` tokens, all
    its expert layers: ``[rows, d] x [d, 2f]`` and ``[rows, f] x [f, d]`` a
    layer; reads the rows and the held matrices, writes the products."""
    rows, d, f, weights = _gmm(cfg, tokens)
    layers = sum(routed_layers(cfg))
    flops = layers * 2 * rows * (d * 2 * f + f * d)
    bytes_ = layers * bytes_per_el * (rows * (d + 2 * f + f + d) + weights)
    return {"flops": float(flops), "bytes": float(bytes_)}


def gmm_bwd_cost(cfg: Dict[str, Any], tokens: int,
                 bytes_per_el: int = 2) -> Dict[str, float]:
    """Their backward: for each product the gradient of its rows (the
    matrices transposed) and of its matrices (a group's rows transposed times
    their gradient): twice the forward's operations; reads the rows, their
    gradients and the matrices, writes both gradients."""
    rows, d, f, weights = _gmm(cfg, tokens)
    layers = sum(routed_layers(cfg))
    flops = layers * 4 * rows * (d * 2 * f + f * d)
    # dx of the gate/up product reads its gradient (2f) and writes d; its dW
    # reads x (d) and the gradient (2f); the down product's dx reads dy (d)
    # and writes f, its dW reads the activation (f) and dy (d)
    per_row = (2 * f + d) + (d + 2 * f) + (d + f) + (f + d)
    bytes_ = layers * bytes_per_el * (rows * per_row + 3 * weights)
    return {"flops": float(flops), "bytes": float(bytes_)}
