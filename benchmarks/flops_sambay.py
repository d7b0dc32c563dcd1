"""Operations and bytes that the decoder-hybrid-decoder family's algorithm
needs (SambaY: Mamba-1, differential attention with a window, gated memory
units, cross-attention), from a configuration's shapes: the yardstick's own
arithmetic, as ``flops.py`` is for the GPT family.

A multiply-add counts as 2 operations. The counts are of what the model needs,
whatever implements it. Differential attention: for each pair of heads two
score maps at the head's width and each map applied to the pair's two value
heads side by side (twice the head's width), on the keys the mask keeps: the
causal half of a full or cross layer, the band of a windowed one. The
selective scan: two multiply-adds a state element a token (the decayed state
plus the input, the state read through ``C``). Recomputation counts nothing:
neither the scores that a backward kernel makes again nor a block body that
the step runs twice.
"""

from __future__ import annotations

from typing import Any, Dict

#: tokens between two states that a scan saves for its backward pass
SCAN_CHUNK = 64
ATTENTION_KINDS = ("sliding_attention", "full_attention", "cross_attention")


def _sizes(cfg):
    d = cfg["hidden_size"]
    return (d, cfg["intermediate_size"], cfg["mamba_expand"] * d,
            cfg["mamba_d_state"], cfg["mamba_dt_rank"],
            cfg["num_attention_heads"], cfg["num_key_value_heads"],
            d // cfg["num_attention_heads"])


def keys_seen(kind: str, cfg: Dict[str, Any], seq_len: int) -> float:
    """Keys a query sees on average in a layer of ``kind``: the causal half
    (itself included), or the band of ``sliding_window`` keys."""
    w = min(cfg["sliding_window"], seq_len) if kind == "sliding_attention" \
        else seq_len
    # the first w queries see 1 .. w keys, every later one w
    return (w * (w + 1) / 2 + (seq_len - w) * w) / seq_len


def attention_macs_per_token(kind: str, cfg: Dict[str, Any],
                             seq_len: int) -> float:
    """Each query head's score at the head's width and its map applied to
    values of twice that width, a key it sees."""
    _, _, _, _, _, heads, _, hd = _sizes(cfg)
    return heads * 3 * hd * keys_seen(kind, cfg, seq_len)


def scan_macs_per_token(cfg: Dict[str, Any]) -> float:
    _, _, di, n, _, _, _, _ = _sizes(cfg)
    return 2.0 * di * n


def matrix_macs_per_token(kind: str, cfg: Dict[str, Any]) -> float:
    """A block's matrix products: its mixer's and the gated MLP's three."""
    d, f, di, n, r, heads, kv, hd = _sizes(cfg)
    mixer = {
        "mamba": d * 2 * di + di * (r + 2 * n) + r * di + di * d,
        "gmu": 2 * d * di,
        "cross_attention": 2 * d * d,
    }.get(kind, d * (d + 2 * kv * hd) + d * d)
    return mixer + 3 * d * f


def train_macs_per_token(cfg: Dict[str, Any], seq_len: int) -> float:
    """Forward multiply-adds per token of the model: every block's matrix
    products by ``layer_types``, the scan of a Mamba layer, the kept scores of
    an attention layer, and the head once (it is tied). Embedding gathers,
    norms, the convolution, gates, ``lambda`` and the optimizer are not model
    operations."""
    total = cfg["hidden_size"] * cfg["vocab_size"]
    for kind in cfg["layer_types"]:
        total += matrix_macs_per_token(kind, cfg)
        if kind == "mamba":
            total += scan_macs_per_token(cfg)
        elif kind in ATTENTION_KINDS:
            total += attention_macs_per_token(kind, cfg, seq_len)
    return total


def train_flops_per_token(cfg: Dict[str, Any], seq_len: int) -> float:
    """Forward (2 per multiply-add) + backward (4): 6 per multiply-add."""
    return 6.0 * train_macs_per_token(cfg, seq_len)


def _scan_io(cfg, batch, seq_len, bytes_per_el):
    """Bytes of one pass over the scan's arrays: ``xs`` and ``y`` (or their
    gradients) [b, t, channels], ``B`` and ``C`` [b, t, states], ``dt``
    [b, t, channels] in float32, and the float32 states saved a chunk."""
    _, _, di, n, _, _, _, _ = _sizes(cfg)
    tokens = batch * seq_len
    return (tokens * di * bytes_per_el, tokens * n * bytes_per_el,
            tokens * di * 4, batch * (seq_len // SCAN_CHUNK) * di * n * 4)


def selscan_fwd_cost(cfg: Dict[str, Any], batch: int, seq_len: int,
                     bytes_per_el: int = 2) -> Dict[str, float]:
    """One forward scan over ``batch`` rows: reads xs, dt, B, C, writes y and
    the saved states."""
    xy, bc, dt, states = _scan_io(cfg, batch, seq_len, bytes_per_el)
    return {"flops": 2.0 * scan_macs_per_token(cfg) * batch * seq_len,
            "bytes": float(2 * xy + 2 * bc + dt + states)}


def selscan_bwd_cost(cfg: Dict[str, Any], batch: int, seq_len: int,
                     bytes_per_el: int = 2) -> Dict[str, float]:
    """One backward scan: the adjoint recurrence and the gradients of its
    operands, twice the forward's multiply-adds, not the states made again;
    reads xs, dt, B, C, dy and the saved states, writes the gradients of xs,
    dt, B and C."""
    xy, bc, dt, states = _scan_io(cfg, batch, seq_len, bytes_per_el)
    return {"flops": 4.0 * scan_macs_per_token(cfg) * batch * seq_len,
            "bytes": float(3 * xy + 4 * bc + 2 * dt + states)}


def _attention_io(cfg, batch, seq_len, bytes_per_el):
    """Bytes of q or o (the query heads' width), of k or v (the key/value
    heads': each read once, the spread to the query heads is no work of the
    algorithm) and of a float32 log-sum-exp a query head."""
    _, _, _, _, _, heads, kv, hd = _sizes(cfg)
    tokens = batch * seq_len
    return (tokens * heads * hd * bytes_per_el,
            tokens * kv * hd * bytes_per_el, tokens * heads * 4)


def attention_fwd_cost(kind: str, cfg: Dict[str, Any], batch: int,
                       seq_len: int, bytes_per_el: int = 2) -> Dict[str, float]:
    """One differential attention forward of a layer of ``kind``: reads q, k,
    v, writes o and the log-sum-exps."""
    q, kv, lse = _attention_io(cfg, batch, seq_len, bytes_per_el)
    macs = attention_macs_per_token(kind, cfg, seq_len) * batch * seq_len
    return {"flops": 2.0 * macs, "bytes": float(2 * q + 2 * kv + lse)}


def attention_bwd_cost(kind: str, cfg: Dict[str, Any], batch: int,
                       seq_len: int, bytes_per_el: int = 2) -> Dict[str, float]:
    """Its backward: each forward product's two transposes, not the scores
    made again; reads q, k, v, o, dO and the log-sum-exps, writes the
    gradients of q, k and v."""
    q, kv, lse = _attention_io(cfg, batch, seq_len, bytes_per_el)
    macs = attention_macs_per_token(kind, cfg, seq_len) * batch * seq_len
    return {"flops": 4.0 * macs, "bytes": float(4 * q + 4 * kv + lse)}
