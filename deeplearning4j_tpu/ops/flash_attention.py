"""Flash attention as Pallas TPU kernels — forward AND backward.

The reference has no attention at all (SURVEY.md §2.6 — it predates
it); this is the build-plan extension (§7.7) the long-context stack
rides on, and the framework's custom-kernel slot: where the reference
dropped to cuDNN helpers (``CudnnConvolutionHelper.java:51``) for its
hot ops, the TPU build drops to Pallas for its hottest op.

Design (online-softmax blocking fitted to the MXU/VMEM):

- forward grid = (batch*heads, q_blocks, k_blocks); the k axis is the
  innermost ("arbitrary") dimension so the [block_q, d] accumulator,
  running max and running denominator live in VMEM scratch across k
  steps — the O(t²) score matrix never exists in HBM, which is the
  whole point: attention becomes compute-bound on the MXU instead of
  HBM-bound. The forward also emits the per-row logsumexp ``lse`` so
  the backward never has to replay the online softmax.
- causal masking: k-blocks entirely above the diagonal are skipped
  under ``@pl.when`` (no MXU/DMA compute); live blocks all apply the
  iota mask — a masked/unmasked branch split was measured ~2x SLOWER
  per step (duplicated conditional bodies defeat Mosaic's pipelining),
  so one masked body wins.
- the softmax scale is folded into q ONCE in XLA before the kernel
  (a per-step in-kernel multiply over [block_q, d] measured ~6x more
  expensive than the single pre-pass at 16k).
- backward = two more Pallas kernels (the TPU shape of the standard
  two-pass flash backward): a dq kernel (k innermost, dq accumulator
  in VMEM) and a dk/dv kernel (q innermost, dk+dv accumulators in
  VMEM). Both compute the score block TRANSPOSED ([block_k, block_q])
  so the per-query ``lse`` and ``delta = rowsum(dO·O)`` vectors enter
  as [1, block_q] row broadcasts — no per-step relayouts. The O(t²)
  weights are rebuilt blockwise from (q, k, lse) and never touch HBM,
  so a 32k-causal TRAINING step fits where the XLA formulation OOMs
  on the [b, h, t, t] score buffer.
- all matmuls run on the MXU in f32 accumulation
  (``preferred_element_type``) from native-bf16 operands.
- the forward is VPU-bound at ~32% MFU (16k causal, v5e) — a measured
  plateau, not a tuning gap: per k-step the online-softmax chain
  (~10M VPU elementwise ops) hides the 2 MXU matmuls. Rejected
  variants (r4, all measured on-chip): triangular live-block grid,
  scalar-prefetch index tables, precomputed D-matrix masks (f32 slow,
  i8 unsupported), masked/unmasked branch split, dead-block index
  clamping, exp2-space softmax, 2048-wide blocks (VMEM). See
  BASELINE.md "Flash-attention forward roofline". The backward's
  higher MFU is structural (7 matmuls per 2 exp chains).

CPU processes (the test mesh) run the same kernels under the Pallas
interpreter, so fwd+bwd are exercised everywhere; the TPU path
compiles via Mosaic. ``util.device.pallas_interpret`` decides which.
"""

from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from deeplearning4j_tpu.ops.attention import scaled_dot_product_attention
from deeplearning4j_tpu.util.device import pallas_interpret

_NEG_INF = -1e30  # finite sentinel: -inf scratch + exp() is nan-prone in bf16


def _pick_block(t: int, preferred: int) -> int:
    for b in (preferred, 512, 256, 128, 64, 32, 16, 8):
        if b <= preferred and t % b == 0:
            return b
    return 0


def _scratch(shape):
    return pltpu.VMEM(shape, jnp.float32)


_VMEM = dict(memory_space=pltpu.VMEM)
_GRID_SEMANTICS = pltpu.CompilerParams(
    dimension_semantics=("parallel", "parallel", "arbitrary"))


def _causal_live(offset, q0, bq, k0):
    """Whether block [q0:q0+bq) x [k0:...) intersects the causal
    triangle at all (key col c is visible to query row r iff
    r + offset >= c); dead blocks skip all compute under pl.when."""
    return k0 <= q0 + bq - 1 + offset


# --------------------------------------------------------------- forward

def _fwd_kernel(q_ref, k_ref, v_ref, o_ref, lse_ref, acc_ref, m_ref, l_ref,
                *, causal: bool, block_q: int, block_k: int, offset: int):
    qi = pl.program_id(1)
    kj = pl.program_id(2)
    nk = pl.num_programs(2)

    @pl.when(kj == 0)
    def _init():
        m_ref[:] = jnp.full_like(m_ref, _NEG_INF)
        l_ref[:] = jnp.zeros_like(l_ref)
        acc_ref[:] = jnp.zeros_like(acc_ref)

    live = _causal_live(offset, qi * block_q, block_q,
                        kj * block_k) if causal else True

    def _step():
        # q arrives pre-scaled (one XLA pass outside the kernel beats a
        # per-step in-kernel multiply ~6x at 16k); operands stay bf16
        # for the MXU — f32 accumulation via preferred_element_type
        s = jax.lax.dot_general(q_ref[0], k_ref[0], (((1,), (1,)), ((), ())),
                                preferred_element_type=jnp.float32)
        if causal:
            # one branch body, masked always: duplicating the body under
            # masked/unmasked pl.when branches measured ~2x SLOWER per
            # step than the mask passes it saves (Mosaic pipelining)
            rows = jax.lax.broadcasted_iota(jnp.int32, (block_q, block_k), 0)
            cols = jax.lax.broadcasted_iota(jnp.int32, (block_q, block_k), 1)
            ok = (qi * block_q + rows + offset) >= (kj * block_k + cols)
            s = jnp.where(ok, s, _NEG_INF)
        m_prev = m_ref[:, :1]
        l_prev = l_ref[:, :1]
        m_new = jnp.maximum(m_prev, jnp.max(s, axis=1, keepdims=True))
        p = jnp.exp(s - m_new)
        corr = jnp.exp(m_prev - m_new)
        l_new = corr * l_prev + jnp.sum(p, axis=1, keepdims=True)
        acc_ref[:] = acc_ref[:] * corr + jax.lax.dot_general(
            p.astype(v_ref.dtype), v_ref[0], (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)
        # lane-0 stores: broadcasting m/l across all 128 scratch lanes
        # measured +0.86us/step of pure VPU store traffic
        m_ref[:, :1] = m_new
        l_ref[:, :1] = l_new

    if causal:
        pl.when(live)(_step)
    else:
        _step()

    @pl.when(kj == nk - 1)
    def _final():
        denom = jnp.maximum(l_ref[:, :1], 1e-30)
        o_ref[0] = (acc_ref[:] / denom).astype(o_ref.dtype)
        lse_ref[0] = m_ref[:, :1] + jnp.log(denom)   # [block_q, 1] column


def _flash_fwd_impl(q, k, v, causal: bool, block_q: int, block_k: int,
                    interpret: bool):
    """q,k,v: [bh, t, d] (heads folded into batch) -> (o, lse[bh, t])."""
    bh, tq, d = q.shape
    tk = k.shape[1]
    q = (q * (1.0 / d ** 0.5)).astype(q.dtype)  # fold softmax scale once
    nq, nk = tq // block_q, tk // block_k
    kernel = functools.partial(
        _fwd_kernel, causal=causal, block_q=block_q,
        block_k=block_k, offset=tk - tq)
    return pl.pallas_call(
        kernel,
        grid=(bh, nq, nk),
        in_specs=[
            pl.BlockSpec((1, block_q, d), lambda b, i, j: (b, i, 0), **_VMEM),
            pl.BlockSpec((1, block_k, d), lambda b, i, j: (b, j, 0), **_VMEM),
            pl.BlockSpec((1, block_k, d), lambda b, i, j: (b, j, 0), **_VMEM),
        ],
        out_specs=[
            pl.BlockSpec((1, block_q, d), lambda b, i, j: (b, i, 0), **_VMEM),
            pl.BlockSpec((1, block_q, 1), lambda b, i, j: (b, i, 0), **_VMEM),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((bh, tq, d), q.dtype),
            jax.ShapeDtypeStruct((bh, tq, 1), jnp.float32),
        ],
        scratch_shapes=[
            _scratch((block_q, d)),
            _scratch((block_q, 128)),
            _scratch((block_q, 128)),
        ],
        compiler_params=_GRID_SEMANTICS, interpret=interpret,
        name="flash_fwd",
    )(q, k, v)


# -------------------------------------------------------------- backward
#
# Both kernels build the TRANSPOSED score block sT = (q·scale)·kᵀ as
# [block_k, block_q] so lse/delta broadcast as [1, block_q] rows.
# pT = exp(sT - lse); dPT = v·dOᵀ; dsT = pT ∘ (dPT - delta).
#   dv += pTᵀ... no: dv = Σ_i P_ij dO_i  => dv_acc += pT · dO
#   dk = Σ_i dS_ij (q_i·scale)           => dk_acc += dsT · qs
#   dq = scale · Σ_j dS_ij k_j           => dq_acc += dsTᵀ · k (contract 0,0)

def _bwd_block(q_ref, k_ref, v_ref, do_ref, lse_ref, dlt_ref,
               *, masked, q0, k0, offset, block_q, block_k):
    qs = q_ref[0]  # pre-scaled outside the kernels
    sT = jax.lax.dot_general(k_ref[0], qs, (((1,), (1,)), ((), ())),
                             preferred_element_type=jnp.float32)
    if masked:
        krow = jax.lax.broadcasted_iota(jnp.int32, (block_k, block_q), 0)
        qcol = jax.lax.broadcasted_iota(jnp.int32, (block_k, block_q), 1)
        ok = (q0 + qcol + offset) >= (k0 + krow)
        sT = jnp.where(ok, sT, _NEG_INF)
    # lse/delta arrive as [1, block_q] rows (pre-reshaped outside the
    # kernel) and broadcast across the block_k sublanes
    pT = jnp.exp(sT - lse_ref[0])                    # [block_k, block_q]
    dPT = jax.lax.dot_general(v_ref[0], do_ref[0], (((1,), (1,)), ((), ())),
                              preferred_element_type=jnp.float32)
    dsT = pT * (dPT - dlt_ref[0])
    return qs, pT.astype(v_ref.dtype), dsT.astype(q_ref.dtype)


def _dq_kernel(q_ref, k_ref, v_ref, do_ref, lse_ref, dlt_ref, dq_ref,
               acc_ref, *, scale, causal, block_q, block_k, offset):
    qi = pl.program_id(1)
    kj = pl.program_id(2)
    nk = pl.num_programs(2)

    @pl.when(kj == 0)
    def _init():
        acc_ref[:] = jnp.zeros_like(acc_ref)

    live = _causal_live(offset, qi * block_q, block_q,
                        kj * block_k) if causal else True

    def _step():
        _, _, dsT = _bwd_block(
            q_ref, k_ref, v_ref, do_ref, lse_ref, dlt_ref,
            masked=causal, q0=qi * block_q, k0=kj * block_k, offset=offset,
            block_q=block_q, block_k=block_k)
        acc_ref[:] += jax.lax.dot_general(
            dsT, k_ref[0], (((0,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)

    if causal:
        pl.when(live)(_step)
    else:
        _step()

    @pl.when(kj == nk - 1)
    def _final():
        dq_ref[0] = (acc_ref[:] * scale).astype(dq_ref.dtype)


def _dkv_kernel(k_ref, v_ref, q_ref, do_ref, lse_ref, dlt_ref,
                dk_ref, dv_ref, dk_acc, dv_acc,
                *, causal, block_q, block_k, offset):
    kj = pl.program_id(1)
    qi = pl.program_id(2)
    nq = pl.num_programs(2)

    @pl.when(qi == 0)
    def _init():
        dk_acc[:] = jnp.zeros_like(dk_acc)
        dv_acc[:] = jnp.zeros_like(dv_acc)

    live = _causal_live(offset, qi * block_q, block_q,
                        kj * block_k) if causal else True

    def _step():
        qs, pT, dsT = _bwd_block(
            q_ref, k_ref, v_ref, do_ref, lse_ref, dlt_ref,
            masked=causal, q0=qi * block_q, k0=kj * block_k, offset=offset,
            block_q=block_q, block_k=block_k)
        dv_acc[:] += jax.lax.dot_general(
            pT, do_ref[0], (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)
        dk_acc[:] += jax.lax.dot_general(
            dsT, qs, (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)

    if causal:
        pl.when(live)(_step)
    else:
        _step()

    @pl.when(qi == nq - 1)
    def _final():
        dk_ref[0] = dk_acc[:].astype(dk_ref.dtype)
        dv_ref[0] = dv_acc[:].astype(dv_ref.dtype)


def _flash_bwd_impl(q, k, v, o, lse, g, causal: bool,
                    block_q: int, block_k: int, interpret: bool):
    bh, tq, d = q.shape
    tk = k.shape[1]
    scale = 1.0 / (d ** 0.5)
    q = (q * scale).astype(q.dtype)  # pre-scale once; dq re-scales at the end
    nq, nk = tq // block_q, tk // block_k
    offset = tk - tq
    # delta = rowsum(dO ∘ O): one fused XLA pass; reshape lse/delta to
    # [bh, 1, tq] rows (free: tq stays contiguous) so the kernels
    # consume them as lane-major broadcasts without relayouts
    delta = jnp.sum(g.astype(jnp.float32) * o.astype(jnp.float32),
                    axis=-1).reshape(bh, 1, tq)
    lse = lse.reshape(bh, 1, tq)

    qspec = pl.BlockSpec((1, block_q, d), lambda b, i, j: (b, i, 0), **_VMEM)
    kspec = pl.BlockSpec((1, block_k, d), lambda b, i, j: (b, j, 0), **_VMEM)
    rowspec = pl.BlockSpec((1, 1, block_q), lambda b, i, j: (b, 0, i),
                           **_VMEM)

    dq = pl.pallas_call(
        functools.partial(_dq_kernel, scale=scale, causal=causal,
                          block_q=block_q, block_k=block_k, offset=offset),
        grid=(bh, nq, nk),
        in_specs=[qspec, kspec, kspec, qspec, rowspec, rowspec],
        out_specs=qspec,
        out_shape=jax.ShapeDtypeStruct((bh, tq, d), q.dtype),
        scratch_shapes=[_scratch((block_q, d))],
        compiler_params=_GRID_SEMANTICS, interpret=interpret,
        name="flash_dq",
    )(q, k, v, g, lse, delta)

    # dk/dv grid: (bh, k_blocks, q_blocks) — q innermost
    kspec2 = pl.BlockSpec((1, block_k, d), lambda b, j, i: (b, j, 0), **_VMEM)
    qspec2 = pl.BlockSpec((1, block_q, d), lambda b, j, i: (b, i, 0), **_VMEM)
    rowspec2 = pl.BlockSpec((1, 1, block_q), lambda b, j, i: (b, 0, i),
                            **_VMEM)
    dk, dv = pl.pallas_call(
        functools.partial(_dkv_kernel, causal=causal,
                          block_q=block_q, block_k=block_k, offset=offset),
        grid=(bh, nk, nq),
        in_specs=[kspec2, kspec2, qspec2, qspec2, rowspec2, rowspec2],
        out_specs=[kspec2, kspec2],
        out_shape=[jax.ShapeDtypeStruct((bh, tk, d), k.dtype),
                   jax.ShapeDtypeStruct((bh, tk, d), v.dtype)],
        scratch_shapes=[_scratch((block_k, d)),
                        _scratch((block_k, d))],
        compiler_params=_GRID_SEMANTICS, interpret=interpret,
        name="flash_dkv",
    )(k, v, q, g, lse, delta)
    return dq, dk, dv


@functools.partial(jax.custom_vjp, nondiff_argnums=(3, 4, 5, 6))
def _flash(q, k, v, causal, block_q, block_k, interpret):
    o, _ = _flash_fwd_impl(q, k, v, causal, block_q, block_k, interpret)
    return o


def _flash_fwd(q, k, v, causal, block_q, block_k, interpret):
    o, lse = _flash_fwd_impl(q, k, v, causal, block_q, block_k, interpret)
    return o, (q, k, v, o, lse)


def _flash_bwd(causal, block_q, block_k, interpret, res, g):
    q, k, v, o, lse = res
    # backward blocks: score blocks live in VMEM 4x over (pT/dPT/dsT
    # temporaries), so cap at 512x512. A caller-chosen forward block
    # > 512 whose length has no <=512 divisor in the candidate list
    # would make _pick_block return 0 — fall back to the forward block
    # (it ran, so it divides the length) rather than divide by zero.
    bq = _pick_block(q.shape[1], min(block_q, 512)) or block_q
    bk = _pick_block(k.shape[1], min(block_k, 512)) or block_k
    return _flash_bwd_impl(q, k, v, o, lse, g, causal, bq, bk, interpret)


_flash.defvjp(_flash_fwd, _flash_bwd)


def flash_attention(
    q: jnp.ndarray,  # [b, tq, h, d]
    k: jnp.ndarray,  # [b, tk, h, d]
    v: jnp.ndarray,  # [b, tk, h, d]
    causal: bool = False,
    mask: Optional[jnp.ndarray] = None,
    block_q: Optional[int] = None,
    block_k: Optional[int] = None,
    interpret: Optional[bool] = None,
) -> jnp.ndarray:
    """Drop-in for ``scaled_dot_product_attention`` (same [b, t, h, d]
    convention). Falls back to the XLA formulation when the kernel
    can't apply (key-validity mask, sequence lengths that no block
    size divides, or causal cross-attention with tq > tk — whose
    zero-attendable-key rows the online softmax would silently average
    over V instead of matching the oracle) — numerics match either way
    (tested).

    Both forward AND backward are Pallas kernels: training never
    materializes the O(t²) score matrix, so 32k-causal train steps fit
    where the XLA formulation OOMs on the [b, h, t, t] buffer."""
    b, tq, h, d = q.shape
    tk = k.shape[1]
    # v5e-tuned defaults: causal favors square 1024-blocks (fewer
    # diagonal crossings per live block); non-causal favors 512x1024
    if block_q is None:
        block_q = 1024 if causal else 512
    if block_k is None:
        block_k = 1024
    bq = _pick_block(tq, block_q)
    bk = _pick_block(tk, block_k)
    if mask is not None or not bq or not bk or (causal and tq > tk):
        return scaled_dot_product_attention(q, k, v, causal=causal, mask=mask)
    if interpret is None:
        interpret = pallas_interpret()
    fold = lambda z: z.transpose(0, 2, 1, 3).reshape(b * h, z.shape[1], d)
    # scoped so that the [b, t, h, d] <-> [b*h, t, d] copies have an
    # owner in the device trace, apart from the kernels
    with jax.named_scope("fold_heads"):
        q, k, v = fold(q), fold(k), fold(v)
    o = _flash(q, k, v, causal, bq, bk, interpret)
    with jax.named_scope("unfold_heads"):
        return o.reshape(b, h, tq, d).transpose(0, 2, 1, 3)
