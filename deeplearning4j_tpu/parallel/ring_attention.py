"""Ring attention — sequence-parallel attention over the mesh ``seq`` axis.

No reference counterpart (the reference handles long sequences only by
truncated BPTT, SURVEY.md §5 "long-context"); this is the build-plan
extension that makes long-context first-class: Q/K/V are sharded over
the sequence axis, each device holds one block, and K/V blocks rotate
around the ring via ``ppermute`` (ICI neighbor exchange) while a
flash-attention-style online softmax accumulates — O(t/n) memory per
device, compute overlapped with the rotation by XLA.

Layout: [batch, time, heads, head_dim], time sharded over mesh axis
``seq``. Exact (not approximate): output matches full attention to
numerical precision (tested against ``ops/attention.py``).
"""

from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from deeplearning4j_tpu.parallel.mesh import device_collective


def _block_attend(q, k, v, scores_mask, m_prev, l_prev, acc_prev):
    """One block of online-softmax attention accumulation.

    q: [b, tq, h, d]; k/v: [b, tk, h, d]; scores_mask: [tq, tk] bool
    (True = attend). Carries: m (running max) [b, h, tq], l (running
    denominator) [b, h, tq], acc (unnormalized output) [b, tq, h, d].
    """
    scale = 1.0 / jnp.sqrt(jnp.asarray(q.shape[-1], q.dtype))
    s = jnp.einsum("bqhd,bkhd->bhqk", q, k) * scale
    neg = jnp.asarray(jnp.finfo(s.dtype).min, s.dtype)
    s = jnp.where(scores_mask[None, None], s, neg)
    m_blk = jnp.max(s, axis=-1)
    m_new = jnp.maximum(m_prev, m_blk)
    # fully-masked rows keep m = -inf-ish; exp underflows to 0 harmlessly
    p = jnp.exp(s - m_new[..., None])
    corr = jnp.exp(m_prev - m_new)
    l_new = l_prev * corr + jnp.sum(p, axis=-1)
    acc_new = acc_prev * corr.transpose(0, 2, 1)[..., None] + \
        jnp.einsum("bhqk,bkhd->bqhd", p, v)
    return m_new, l_new, acc_new


def ring_attention(
    q: jnp.ndarray, k: jnp.ndarray, v: jnp.ndarray,
    mesh: Mesh, axis: str = "seq", causal: bool = False,
    batch_axis: Optional[str] = None,
) -> jnp.ndarray:
    """Sequence-parallel exact attention. q/k/v: [b, t, h, d] with t
    divisible by the ``axis`` size; returns [b, t, h, d] sharded the
    same way. ``batch_axis`` composes DP×SP: the batch dim shards over
    that mesh axis while rings rotate within each data-parallel group
    (the ring's ppermute is over ``axis`` only, so K/V never cross the
    batch axis)."""
    n = mesh.shape[axis]
    t = q.shape[1]
    blk = t // n
    if blk * n != t:
        raise ValueError(f"sequence length {t} not divisible by {axis} axis size {n}")

    def local(qb, kb, vb):
        my = jax.lax.axis_index(axis)
        b, tq, h, d = qb.shape
        # fresh constants are typed replicated; the loop writes values
        # that vary over the sharded axes back into the same carries,
        # and shard_map's type check needs carry-in == carry-out
        # (a0 inherits qb's type through zeros_like)
        vary = (axis,) if batch_axis is None else (batch_axis, axis)
        m0, l0 = jax.lax.pcast(
            (jnp.full((b, h, tq), jnp.finfo(qb.dtype).min, qb.dtype),
             jnp.zeros((b, h, tq), qb.dtype)), vary, to="varying")
        a0 = jnp.zeros_like(qb)
        qpos = my * blk + jnp.arange(blk)

        def body(i, carry):
            m, l, acc, kk, vv = carry
            src_block = (my + i) % n  # kk currently holds block src_block
            kpos = src_block * blk + jnp.arange(blk)
            if causal:
                smask = qpos[:, None] >= kpos[None, :]
            else:
                smask = jnp.ones((blk, blk), bool)
            m, l, acc = _block_attend(qb, kk, vv, smask, m, l, acc)
            # rotate K/V to the next position around the ring
            perm = [(j, (j - 1) % n) for j in range(n)]
            kk = jax.lax.ppermute(kk, axis, perm)
            vv = jax.lax.ppermute(vv, axis, perm)
            return m, l, acc, kk, vv

        m, l, acc, _, _ = jax.lax.fori_loop(0, n, body, (m0, l0, a0, kb, vb))
        l_t = l.transpose(0, 2, 1)[..., None]  # [b, tq, h, 1]
        return acc / jnp.maximum(l_t, jnp.asarray(1e-30, l_t.dtype))

    # a genuinely per-device program (the ppermute ring schedule IS the
    # algorithm) — routed through the plane's one sanctioned shard_map
    # entry; everything jit-with-shardings-expressible must not be here
    spec = P(batch_axis, axis, None, None)
    return device_collective(local, mesh, in_specs=(spec, spec, spec),
                             out_specs=spec)(q, k, v)
