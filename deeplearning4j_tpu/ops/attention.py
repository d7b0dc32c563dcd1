"""Scaled-dot-product / multi-head attention ops.

The reference predates attention entirely (SURVEY.md §2.6: no sequence
parallelism, no attention layers) — this module is a build-plan
extension (§7.7) that long-context support is built on. The full
(quadratic) form here is the single-device path and the correctness
oracle for the ring-attention sequence-parallel kernel in
``parallel/ring_attention.py``.

Shapes follow [batch, time, heads, head_dim] throughout.
"""

from __future__ import annotations

import math
from typing import Optional

import jax
import jax.numpy as jnp


def scaled_dot_product_attention(
    q: jnp.ndarray,  # [b, tq, h, d]
    k: jnp.ndarray,  # [b, tk, h, d]
    v: jnp.ndarray,  # [b, tk, h, d]
    causal: bool = False,
    mask: Optional[jnp.ndarray] = None,  # [b, tk] key validity
    window: Optional[int] = None,  # causal: the query's key and window - 1 before
) -> jnp.ndarray:
    scale = 1.0 / jnp.sqrt(jnp.asarray(q.shape[-1], q.dtype))
    scores = jnp.einsum("bqhd,bkhd->bhqk", q, k) * scale
    neg = jnp.asarray(jnp.finfo(scores.dtype).min, scores.dtype)
    if causal:
        tq, tk = q.shape[1], k.shape[1]
        causal_mask = jnp.tril(jnp.ones((tq, tk), bool), k=tk - tq)
        if window is not None:
            causal_mask &= ~jnp.tril(jnp.ones((tq, tk), bool),
                                     k=tk - tq - window)
        scores = jnp.where(causal_mask[None, None], scores, neg)
    if mask is not None:
        scores = jnp.where(mask[:, None, None, :] > 0, scores, neg)
    w = jax.nn.softmax(scores, axis=-1)
    return jnp.einsum("bhqk,bkhd->bqhd", w, v)


def rotary(x: jnp.ndarray, theta: float) -> jnp.ndarray:
    """Rotary positions on ``x`` [b, t, h, d], rotate-half over the whole
    head: lane ``i`` of the first half pairs with lane ``i + d/2`` of the
    second, and the pair at position ``p`` turns by ``p * theta ** (-2i/d)``.
    Angles, sines and the rotation itself are float32 whatever ``x`` is (at
    position 65k a bfloat16 angle is off by whole turns); the result takes
    ``x``'s dtype. Token ``p`` of the row stands at position ``p``. Applied
    to q and to k, it makes their scores depend on ``i - j`` alone."""
    t, d = x.shape[1], x.shape[-1]
    if d % 2:
        raise ValueError(f"rotary positions need an even head width, got {d}")
    half = d // 2
    freq = jnp.exp(jnp.arange(half, dtype=jnp.float32)
                   * (-2.0 * math.log(theta) / d))
    angle = jnp.arange(t, dtype=jnp.float32)[:, None] * freq[None, :]
    cos, sin = jnp.cos(angle)[:, None, :], jnp.sin(angle)[:, None, :]
    xf = x.astype(jnp.float32)
    a, b = xf[..., :half], xf[..., half:]
    return jnp.concatenate([a * cos - b * sin, b * cos + a * sin],
                           axis=-1).astype(x.dtype)


def multi_head_attention(
    x: jnp.ndarray,  # [b, t, f]
    wq: jnp.ndarray, wk: jnp.ndarray, wv: jnp.ndarray,  # [f, h*d]
    wo: jnp.ndarray,  # [h*d, f]
    num_heads: int,
    causal: bool = False,
    mask: Optional[jnp.ndarray] = None,
) -> jnp.ndarray:
    b, t, f = x.shape
    d = wq.shape[-1] // num_heads
    split = lambda z: z.reshape(b, t, num_heads, d)
    q, k, v = split(x @ wq), split(x @ wk), split(x @ wv)
    o = scaled_dot_product_attention(q, k, v, causal=causal, mask=mask)
    return o.reshape(b, t, num_heads * d) @ wo
