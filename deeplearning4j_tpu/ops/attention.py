"""Scaled-dot-product / multi-head attention ops.

The reference predates attention entirely (SURVEY.md §2.6: no sequence
parallelism, no attention layers) — this module is a build-plan
extension (§7.7) that long-context support is built on. The full
(quadratic) form here is the single-device path and the correctness
oracle for the ring-attention sequence-parallel kernel in
``parallel/ring_attention.py``.

Shapes follow [batch, time, heads, head_dim] throughout (``rotary`` takes
and returns that shape, and works on [batch, time, heads * head_dim]).
"""

from __future__ import annotations

import functools
import math
from typing import Optional

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from deeplearning4j_tpu.util.device import pallas_interpret


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
    to q and to k, it makes their scores depend on ``i - j`` alone.

    The arithmetic is done on ``x`` as [b, t, h * d], one pass forward and
    one (the inverse rotation) backward: the chip tiles that array, which
    the projections write and the packed flash kernels read, over (tokens,
    lanes) and a [b, t, h, d] one over (heads, lanes), so work on the
    second is a relayout of the first each way."""
    b, t, h, d = x.shape
    if d % 2:
        raise ValueError(f"rotary positions need an even head width, got {d}")
    return _turn(x.reshape(b, t, h * d), d, float(theta),
                 pallas_interpret()).reshape(x.shape)


@functools.partial(jax.custom_vjp, nondiff_argnums=(1, 2, 3))
def _turn(x, d, theta, interpret):
    return _rotate(x, d, theta, False, interpret)


def _turn_fwd(x, d, theta, interpret):
    return _turn(x, d, theta, interpret), None  # orthogonal: nothing to keep


def _turn_bwd(d, theta, interpret, _, g):
    return (_rotate(g, d, theta, True, interpret),)


_turn.defvjp(_turn_fwd, _turn_bwd)


def _period(width: int, d: int) -> int:
    """The lanes after which a [t, width] table of heads of ``d`` repeats,
    in whole 128-lane column blocks (``flash_attention``'s ``_packs`` rule:
    the widths the packed kernels take), 0 where there is none."""
    if d % 128 == 0:
        return d
    return 128 if 128 % d == 0 and width % 128 == 0 else 0


def _row_block(t: int, width: int, itemsize: int) -> int:
    """Rows of a [t, width] array in one program of the pass: the most
    that divide ``t`` within a MiB of ``x`` (four such buffers in flight),
    in whole tiles of the narrowest dtype; 0 where none do."""
    for rows in (1024, 512, 256, 128, 64, 32):
        if t % rows == 0 and rows * width * itemsize <= 2 ** 20:
            return rows
    return 0


@functools.partial(jax.jit, static_argnums=(1, 2, 3, 4))
def _rotate(x, d: int, theta: float, inverse: bool, interpret: bool):
    """``x`` [b, t, h * d] turned by its positions' angles (``inverse``: by
    their negatives) in one pass: ``x * cos + partner(x) * sin`` with the
    sign of the rotate-half in the sine's table, where lane ``j``'s partner
    is lane ``j + d/2`` in a head's first half and ``j - d/2`` in its second.
    A ``jax.jit``, so that a step of many blocks traces it once."""
    b, t, width = x.shape
    half = d // 2
    freq = jnp.exp(jnp.arange(half, dtype=jnp.float32)
                   * (-2.0 * math.log(theta) / d))
    angle = jnp.arange(t, dtype=jnp.float32)[:, None] * freq[None, :]
    cos, sin = jnp.cos(angle), jnp.sin(angle)
    period = _period(width, d)
    rows = _row_block(t, width, x.dtype.itemsize) if period else 0
    # [t, lanes] tables: the head's two halves side by side, head after head
    lanes = period if rows else width
    cos = jnp.tile(jnp.concatenate([cos, cos], axis=-1), (1, lanes // d))
    sin = jnp.tile(jnp.concatenate([-sin, sin], axis=-1), (1, lanes // d))
    if inverse:
        sin = -sin
    if rows:
        return _rotate_pass(x, cos, sin, d, rows, interpret)
    xf = x.astype(jnp.float32)
    first = jnp.arange(width) % d < half
    partner = jnp.where(first, jnp.roll(xf, -half, axis=-1),
                        jnp.roll(xf, half, axis=-1))
    return (xf * cos + partner * sin).astype(x.dtype)


def _rotate_kernel(x_ref, cos_ref, sin_ref, o_ref, *, d: int):
    """A block of rows, every head of them: the tables are one period wide
    and the loop walks the periods, whole 128-lane column blocks each."""
    cos, sin = cos_ref[...], sin_ref[...]
    period = cos.shape[-1]
    half = d // 2
    if period > d:  # heads that share 128 lanes
        lane = jax.lax.broadcasted_iota(jnp.int32, (1, period), 1)
        first = lane % d < half
    for at in range(0, x_ref.shape[-1], period):
        x = x_ref[0, :, at:at + period].astype(jnp.float32)
        # lane j reads lane j + half, round the period: the wrapped lanes
        # are another head's only where a period holds several
        partner = pltpu.roll(x, period - half, 1)
        if period > d:
            partner = jnp.where(first, partner, pltpu.roll(x, half, 1))
        o_ref[0, :, at:at + period] = (x * cos + partner * sin).astype(
            o_ref.dtype)


def _rotate_pass(x, cos, sin, d: int, rows: int, interpret: bool):
    """The Pallas pass over grid (row blocks, batch): a program reads and
    writes a [rows, h * d] block of ``x`` where it lies, and a block of the
    tables is fetched once for the whole batch."""
    b, t, width = x.shape
    block = pl.BlockSpec((1, rows, width), lambda i, j: (j, i, 0))
    table = pl.BlockSpec((rows, cos.shape[-1]), lambda i, j: (i, 0))
    return pl.pallas_call(
        functools.partial(_rotate_kernel, d=d),
        grid=(t // rows, b),
        in_specs=[block, table, table], out_specs=block,
        out_shape=jax.ShapeDtypeStruct(x.shape, x.dtype),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel")),
        interpret=interpret, name="rotary_turn",
    )(x, cos, sin)


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
