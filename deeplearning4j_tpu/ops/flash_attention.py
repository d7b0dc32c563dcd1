"""Flash attention as Pallas TPU kernels — forward AND backward.

The reference has no attention at all (SURVEY.md §2.6 — it predates
it); this is the build-plan extension (§7.7) the long-context stack
rides on, and the framework's custom-kernel slot: where the reference
dropped to cuDNN helpers (``CudnnConvolutionHelper.java:51``) for its
hot ops, the TPU build drops to Pallas for its hottest op.

Two sets of kernels, chosen from the shapes by ``flash_path`` (a pure
function of ``(tq, tk, d, dtype, heads)``; ``dl4j_flash_path_total{path=}``
counts the choice once a traced call, and a device trace shows it as
``flash_dq_dkv`` against ``flash_dq`` + ``flash_dkv``):

**Resident** — self-attention whose row fits VMEM (up to 4k at 128 lanes
a program: 1k / head 64, 2k and 4k / head 128, 4k / a pair of heads of 64,
every shorter length): one program a row, the row's whole q,
k, v (and dO, o, lse) in VMEM, the block loop in the body. Dead blocks
are not in the loop, only diagonal blocks carry the iota mask, and the
backward is ONE kernel that makes the scores once for dq, dk and dv (5
products and 1 exp chain a block; the streamed pair makes 7 and 2) and
delta = rowsum(dO·O) itself. The softmax scale is folded into q inside
the body. Up to ``_UNROLLED_ROWS`` (2k) the loop is straight-line code
and the forward takes a plain softmax over the keys a block of queries
sees (no running max, no rescaling); longer rows (4k) run the same
programs with the loops as ``fori_loop``s over dynamic slices and a
running max in the forward ("looped": see the 4k readings below).

  A row is a COLUMN BLOCK of [b, t, features] arrays, addressed by the
  BlockSpecs (grid ``(b, column blocks)``). **Packed**
  (``path="resident_packed"``, PR 30): the arrays are the projections'
  own. Where the heads are whole 128-lane tiles of ``[b, t, h*d]`` (d a
  multiple of 128, or d a divisor of it and ``h*d % 128 == 0``) a
  program takes ``[1, t, 128]`` (one head of 128, or a PAIR of heads of
  64 side by side; a d-wide slab of 64 is not a legal Mosaic block, a
  pair is a whole tile) straight from the fused QKV projection's
  ``[b, t, 3*h*d]`` output at three column offsets
  (``flash_attention_qkv``), writes o as the ``[b, t, h*d]`` the output
  projection contracts over, and hands dq, dk, dv back as the three
  thirds of ONE ``[b, t, 3*h*d]`` array: no ``jnp.split``, no scaled
  copy of q, no ``[b, t, h, d] -> [b*h, t, d]`` fold, no unfold and no
  concatenation exist, forward or backward. Heads that share 128 lanes
  are never sliced apart (``_own_lanes``): q (forward) or k and v
  (backward) with the other head's lanes zeroed contract to this head's
  scores exactly, products against the unmasked v, dO and q are kept in
  this head's lanes by one select, and every load and store is a whole
  tile wide; the MXU passes are the folded ones (a 64-deep contraction
  or a 64-wide output fills half a 128 x 128 pass either way).
  ``flash_attention(q, k, v)`` in the [b, t, h, d] convention reaches
  the same kernels by a free reshape. **Folded** (``path="resident"``):
  head counts that do not pair (h = 3 at d = 64) keep the
  ``fold_heads`` / ``unfold_heads`` copies, and the same body runs on
  the ``[b*h, t, d]`` array's one head.

  Readings on a v5e (device self time from a trace, ms a call).
  Kernels alone on folded rows (PR 28):

    bh x t x d        streamed fwd / dq+dkv   resident fwd / dq_dkv
    128 x 1024 x 64    0.455 / 1.404           0.302 / 0.734
    24 x 2048 x 128    0.302 / 0.756           0.188 / 0.419
    512 x 256 x 64     0.629 / 0.732           0.286 / 0.435
    1024 x 128 x 64    0.618 / 1.012           0.446 / 0.607
    32 x 4096 x 128    1.397 / 4.373           0.877 / 2.323   (PR 34,
    64 x 4096 x 64     2.998 / 8.723           1.734 / 4.693    unrolled)

  One attention sublayer, ``x @ Wqkv`` -> attention -> ``@ Wo``,
  forward and backward, so that the copies round the kernels count
  (PR 30, ``scripts/profile_flash.py``; kernels fwd / dq_dkv, then
  the whole sublayer; "PR 28" is that commit's file behind the split):

    b x h x t x d       PR 28, folded           folded, this body      packed
    8 x 16 x 1024 x 64  0.303 / 0.734   2.646   0.302 / 0.737  2.575   0.337 / 0.747  2.081
    32 x 16 x 256 x 64  0.299 / 0.448   2.371   0.297 / 0.439  2.211   0.265 / 0.456  1.722
    2 x 12 x 2048 x 128 0.187 / 0.418   1.919   0.188 / 0.422  1.878   0.201 / 0.466  1.802

  The 4k rows with the UNROLLED body (PR 34; the parent's column is the
  streamed kernels with their folds, which these shapes ran until then):

    b x h x t x d       streamed, folded        folded, this body      packed
    2 x 16 x 4096 x 128 1.397 / 4.373  10.746   0.877 / 2.323  7.905   0.877 / 3.028  7.897
    2 x 32 x 4096 x 64  2.998 / 8.723  17.625   1.734 / 4.693 11.873   2.026 / 5.143 11.161

  Those are the UNROLLED bodies at 4k (36 live blocks of 512), and they
  are not what runs: Mosaic unrolls every array operation into vector
  registers, so a kernel's code grows with t squared whatever the block:
  0.72 + 1.10 MB and 8 + 11 s of compiling a call site at 4k / head 128
  (1.45 + 2.25 MB, 24 + 28 s for a pair of 64) against 0.24 + 0.32 MB
  and 1.7 + 2.6 s at 2k. A step that calls them 32 times (a looped
  model: 8 blocks x 4 passes) grew from 130 to 190 MiB as an executable
  and compiled 13-17 s longer, and the chip machines' compile cache
  holds 192 MiB: no run of it was ever warm. So rows over
  ``_UNROLLED_ROWS`` loop over their blocks (0.08 + 0.10 MB, under a
  second to compile, the step's executable 140 MiB). Kernels alone,
  three [b, t, h*d] gradients (what ``flash_attention(q, k, v)`` runs:
  the looped and the hybrid cell's calls), fwd / dq_dkv:

    b x h x t x d        streamed        unrolled        looped
    2 x 16 x 4096 x 128  1.397 / 4.373   0.865 / 2.303   1.268 / 2.508
    2 x 32 x 4096 x 64   2.998 / 8.723   2.024 / 4.304   2.520 / 4.927

  The looped forward keeps a third of what the unrolled one wins (an
  iteration's MXU and VPU phases do not overlap across the loop's
  back-edge; asking for the next block's scores before this block's
  softmax, the arrays carried through the loop, read 1.71 / 3.33 ms:
  worse), the looped backward nine tenths. Looped at block 1024 read
  1.233 / 2.471 ms (level), at 256 2.358 / 3.924. The one [b, t, 3*h*d]
  gradient in three grid steps reads 2.603 ms looped at 4k / head 128
  (3.028 unrolled) against 2.508 for three arrays.

  The pair program's kernels are 0 to 12% slower than the folded ones
  (a select a head and a block; column blocks are fetched in 4 KB
  runs) and 11% faster at 256, where half the programs is half the
  per-program cost; what the sublayer gains is the ``copy`` (0.50,
  0.49, 0.15 ms) and the ``fusion`` passes (the split, q x 1/sqrt(d),
  delta) around them. Three [b, t, h*d] gradients and XLA's
  concatenation (three ``dynamic-update-slice`` fusions) read the
  backward kernel 0.706 / 0.350 / 0.448 ms and the whole step of
  the three GPT cells 1.05 / 0.40 / 1.05 ms slower than the one
  [b, t, 3*h*d] output in three grid steps (0.4-0.75 us a program),
  which is what is built: a program computes in its first step,
  writes dq, and hands dk and dv out of VMEM in two more; its
  operands are asked for one step early, so their fetch runs under
  the previous program's computation.

  The in-body block is 512 for both kernels. Block 256 read 0.695 ms
  in the head-64 backward (5% faster) and level elsewhere (0.423 at
  2k / 128; forwards 0.324 and 0.187 against 0.333 and 0.189 before
  the lse became a row), block 128 three times slower; it unrolls
  into 10 blocks at 1k and 36 at 2k where 512 makes 3 and 10, and is
  left to a later PR. Unrolled at 4k (36 live blocks at block 512) the
  forward read 0.865 ms at block 512 against 0.995 at 1024, the backward
  2.400, 2.303 and 2.527 ms at 256, 512 and 1024 (head 128; a pair of
  64: 2.024 / 2.224 and 4.503, 4.304, 4.782): 512 stays (PR 34). The
  same body rolled into ``fori_loop``s over blocks (dynamic slices, two
  bodies in all) read 16–22% slower than the unrolled one (0.850 against
  0.732 and 0.509 against 0.418 ms at block 512), which is why rows up
  to 2k stay unrolled. The forward writes lse as the [1, t] row the backward
  reads: as a [t, 1] column (the streamed layout) every value costs a
  128-lane tile in the kernel's store and in an XLA pass that repacks
  it (0.333 against 0.302 ms, and 1.2–3.6 ms a step of ``reduce``).
  Both wrappers are ``jax.jit``s, so a model's layers share one traced
  and lowered body: unrolled bodies traced once a layer cost the first
  dispatch 3.3 s at 2k x 18 layers on the chip's host. (PR 28's
  readings, on folded rows.)

**Streamed** — everything else (cross-length calls, lengths over the
budget: 8k and up at head 128, 3k and up in float32, the 16k / 32k
long-context path), as before PR 28:

- forward grid = (batch*heads, q_blocks, k_blocks); the k axis is the
  innermost ("arbitrary") dimension so the [block_q, d] accumulator,
  running max and running denominator live in VMEM scratch across k
  steps — the O(t²) score matrix never exists in HBM, which is the
  whole point: attention becomes compute-bound on the MXU instead of
  HBM-bound. The forward also emits the per-row logsumexp ``lse`` so
  the backward never has to replay the online softmax.
- causal masking: k-blocks entirely above the diagonal are skipped
  under ``@pl.when`` (no MXU/DMA compute); live blocks all apply the
  iota mask — in the grid formulation a masked/unmasked branch split
  was measured ~2x SLOWER per step (duplicated conditional bodies
  defeat Mosaic's pipelining), so one masked body wins there. (The
  resident body has no branches: its diagonal and full blocks are
  different straight-line code.)
- backward = two more Pallas kernels (the TPU shape of the standard
  two-pass flash backward): a dq kernel (k innermost, dq accumulator
  in VMEM) and a dk/dv kernel (q innermost, dk+dv accumulators in
  VMEM). The O(t²) weights are rebuilt blockwise from (q, k, lse) and
  never touch HBM, so a 32k-causal TRAINING step fits where the XLA
  formulation OOMs on the [b, h, t, t] score buffer.
- at 16k causal on the old platform the forward read ~32% of the MXU
  peak and was VPU-bound (per k-step the online-softmax chain hides
  the 2 matmuls); rejected there, all in the grid formulation and at
  16k only: triangular live-block grid, scalar-prefetch index tables,
  precomputed D-matrix masks (f32 slow, i8 unsupported), dead-block
  index clamping, exp2-space softmax, 2048-wide blocks (VMEM). See
  BASELINE.md "Flash-attention forward roofline". None of that was
  read at 1k, 2k or 4k, where this path no longer runs.

Both paths: the softmax scale is folded into q ONCE (the streamed path
in XLA before the kernel, the resident one in the body, rounded the
same); the backward builds the score block TRANSPOSED ([keys,
queries]) so the per-query ``lse`` and ``delta = rowsum(dO·O)`` enter as
[1, queries] row broadcasts with no relayouts; all matmuls run on the
MXU in f32 accumulation (``preferred_element_type``) from native-bf16
operands, the exp and the softmax algebra in float32.

CPU processes (the test mesh) run the same kernels under the Pallas
interpreter, so fwd+bwd are exercised everywhere; the TPU path
compiles via Mosaic. ``util.device.pallas_interpret`` decides which.
"""

from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
from jax.ad_checkpoint import checkpoint_name
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from deeplearning4j_tpu.monitor import (FLASH_PATH_COUNTER,
                                        FLASH_WINDOWED_COUNTER, get_registry)
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


def _window_live(offset, q0, bq, k0, bk, window):
    """The twin of ``_causal_live`` for a window (query row r sees key col
    c iff 0 <= r + offset - c < window): whether the block's newest key is
    still inside the band of the block's oldest query."""
    return k0 + bk - 1 > q0 + offset - window


def _band(n_in: int, block_in: int, n_out: int, block_out: int,
          first_seen, last_seen):
    """For a windowed call, the blocks of the inner grid axis that a block of
    the outer one meets: ``(steps, first)``, with ``steps`` the most blocks
    any outer block meets (the inner grid's length) and ``first(i)`` the
    first inner block of outer block ``i``, traceable. ``first_seen(r)`` and
    ``last_seen(r)`` map an outer row to the first inner row of its first
    row's band and the last of its last row's."""
    spans = []
    for i in range(n_out):
        lo = max(0, first_seen(i * block_out)) // block_in
        hi = min(n_in * block_in - 1,
                 last_seen(i * block_out + block_out - 1)) // block_in
        spans.append(hi - lo + 1)
    first = lambda i: jnp.maximum(0, first_seen(i * block_out)) // block_in
    return max(spans), first


# --------------------------------------------------------------- forward

def _fwd_kernel(q_ref, k_ref, v_ref, o_ref, lse_ref, acc_ref, m_ref, l_ref,
                *, causal: bool, block_q: int, block_k: int, offset: int,
                window=None, first_k=None, n_k=None):
    qi = pl.program_id(1)
    kj = step = pl.program_id(2)
    nk = pl.num_programs(2)
    if window is not None:
        # the k axis of the grid holds the band's blocks only: step ``step``
        # of query block qi is key block first_k(qi) + step
        kj = first_k(qi) + step

    @pl.when(step == 0)
    def _init():
        m_ref[:] = jnp.full_like(m_ref, _NEG_INF)
        l_ref[:] = jnp.zeros_like(l_ref)
        acc_ref[:] = jnp.zeros_like(acc_ref)

    live = _causal_live(offset, qi * block_q, block_q,
                        kj * block_k) if causal else True
    if window is not None:
        live = live & (kj < n_k) & _window_live(
            offset, qi * block_q, block_q, kj * block_k, block_k, window)

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
            if window is not None:
                ok = ok & ((qi * block_q + rows + offset)
                           - (kj * block_k + cols) < window)
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

    @pl.when(step == nk - 1)
    def _final():
        denom = jnp.maximum(l_ref[:, :1], 1e-30)
        o_ref[0] = (acc_ref[:] / denom).astype(o_ref.dtype)
        lse_ref[0] = m_ref[:, :1] + jnp.log(denom)   # [block_q, 1] column


def _key_band(tq, tk, block_q, block_k, window):
    """``_band`` of the key blocks a query block meets, and the keys' index
    map over grid (bh, q block, step): the band's blocks, held inside the
    row (a step past the band is dead and asks for no new block)."""
    nq, nk, offset = tq // block_q, tk // block_k, tk - tq
    steps, first_k = _band(nk, block_k, nq, block_q,
                           lambda r: r + offset - window + 1,
                           lambda r: r + offset)
    kmap = lambda b, i, j: (b, jnp.minimum(first_k(i) + j, nk - 1), 0)
    return steps, kmap, dict(window=window, first_k=first_k, n_k=nk)


def _flash_fwd_impl(q, k, v, causal: bool, block_q: int, block_k: int,
                    interpret: bool, window=None):
    """q,k,v: [bh, t, d] (heads folded into batch) -> (o, lse[bh, t])."""
    bh, tq, d = q.shape
    tk = k.shape[1]
    q = (q * (1.0 / d ** 0.5)).astype(q.dtype)  # fold softmax scale once
    nq, nk = tq // block_q, tk // block_k
    kernel = functools.partial(
        _fwd_kernel, causal=causal, block_q=block_q,
        block_k=block_k, offset=tk - tq)
    kmap = lambda b, i, j: (b, j, 0)
    if window is not None:
        nk, kmap, band = _key_band(tq, tk, block_q, block_k, window)
        kernel = functools.partial(kernel, **band)
    return pl.pallas_call(
        kernel,
        grid=(bh, nq, nk),
        in_specs=[
            pl.BlockSpec((1, block_q, d), lambda b, i, j: (b, i, 0), **_VMEM),
            pl.BlockSpec((1, block_k, d), kmap, **_VMEM),
            pl.BlockSpec((1, block_k, d), kmap, **_VMEM),
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
               *, masked, q0, k0, offset, block_q, block_k, window=None):
    qs = q_ref[0]  # pre-scaled outside the kernels
    sT = jax.lax.dot_general(k_ref[0], qs, (((1,), (1,)), ((), ())),
                             preferred_element_type=jnp.float32)
    if masked:
        krow = jax.lax.broadcasted_iota(jnp.int32, (block_k, block_q), 0)
        qcol = jax.lax.broadcasted_iota(jnp.int32, (block_k, block_q), 1)
        ok = (q0 + qcol + offset) >= (k0 + krow)
        if window is not None:
            ok = ok & ((q0 + qcol + offset) - (k0 + krow) < window)
        sT = jnp.where(ok, sT, _NEG_INF)
    # lse/delta arrive as [1, block_q] rows (pre-reshaped outside the
    # kernel) and broadcast across the block_k sublanes
    pT = jnp.exp(sT - lse_ref[0])                    # [block_k, block_q]
    dPT = jax.lax.dot_general(v_ref[0], do_ref[0], (((1,), (1,)), ((), ())),
                              preferred_element_type=jnp.float32)
    dsT = pT * (dPT - dlt_ref[0])
    return qs, pT.astype(v_ref.dtype), dsT.astype(q_ref.dtype)


def _dq_kernel(q_ref, k_ref, v_ref, do_ref, lse_ref, dlt_ref, dq_ref,
               acc_ref, *, scale, causal, block_q, block_k, offset,
               window=None, first_k=None, n_k=None):
    qi = pl.program_id(1)
    kj = step = pl.program_id(2)
    nk = pl.num_programs(2)
    if window is not None:
        kj = first_k(qi) + step  # the band's blocks only, as the forward

    @pl.when(step == 0)
    def _init():
        acc_ref[:] = jnp.zeros_like(acc_ref)

    live = _causal_live(offset, qi * block_q, block_q,
                        kj * block_k) if causal else True
    if window is not None:
        live = live & (kj < n_k) & _window_live(
            offset, qi * block_q, block_q, kj * block_k, block_k, window)

    def _step():
        _, _, dsT = _bwd_block(
            q_ref, k_ref, v_ref, do_ref, lse_ref, dlt_ref,
            masked=causal, q0=qi * block_q, k0=kj * block_k, offset=offset,
            block_q=block_q, block_k=block_k, window=window)
        acc_ref[:] += jax.lax.dot_general(
            dsT, k_ref[0], (((0,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)

    if causal:
        pl.when(live)(_step)
    else:
        _step()

    @pl.when(step == nk - 1)
    def _final():
        dq_ref[0] = (acc_ref[:] * scale).astype(dq_ref.dtype)


def _dkv_kernel(k_ref, v_ref, q_ref, do_ref, lse_ref, dlt_ref,
                dk_ref, dv_ref, dk_acc, dv_acc,
                *, causal, block_q, block_k, offset,
                window=None, first_q=None, n_q=None):
    kj = pl.program_id(1)
    qi = step = pl.program_id(2)
    nq = pl.num_programs(2)
    if window is not None:
        qi = first_q(kj) + step  # the query blocks that see this key block

    @pl.when(step == 0)
    def _init():
        dk_acc[:] = jnp.zeros_like(dk_acc)
        dv_acc[:] = jnp.zeros_like(dv_acc)

    live = _causal_live(offset, qi * block_q, block_q,
                        kj * block_k) if causal else True
    if window is not None:
        live = live & (qi < n_q) & _window_live(
            offset, qi * block_q, block_q, kj * block_k, block_k, window)

    def _step():
        qs, pT, dsT = _bwd_block(
            q_ref, k_ref, v_ref, do_ref, lse_ref, dlt_ref,
            masked=causal, q0=qi * block_q, k0=kj * block_k, offset=offset,
            block_q=block_q, block_k=block_k, window=window)
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

    @pl.when(step == nq - 1)
    def _final():
        dk_ref[0] = dk_acc[:].astype(dk_ref.dtype)
        dv_ref[0] = dv_acc[:].astype(dv_ref.dtype)


def _flash_bwd_impl(q, k, v, o, lse, g, causal: bool,
                    block_q: int, block_k: int, interpret: bool, window=None):
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
    dq_kernel = functools.partial(_dq_kernel, scale=scale, causal=causal,
                                  block_q=block_q, block_k=block_k,
                                  offset=offset)
    dkv_kernel = functools.partial(_dkv_kernel, causal=causal,
                                   block_q=block_q, block_k=block_k,
                                   offset=offset)
    k_steps, q_steps = nk, nq
    # dk/dv grid: (bh, k_blocks, q_blocks) — q innermost
    kspec2 = pl.BlockSpec((1, block_k, d), lambda b, j, i: (b, j, 0), **_VMEM)
    qspec2 = pl.BlockSpec((1, block_q, d), lambda b, j, i: (b, i, 0), **_VMEM)
    rowspec2 = pl.BlockSpec((1, 1, block_q), lambda b, j, i: (b, 0, i),
                            **_VMEM)
    if window is not None:
        k_steps, kmap, band = _key_band(tq, tk, block_q, block_k, window)
        dq_kernel = functools.partial(dq_kernel, **band)
        kspec = pl.BlockSpec((1, block_k, d), kmap, **_VMEM)
        # and the query blocks that see a key block
        q_steps, first_q = _band(nq, block_q, nk, block_k,
                                 lambda c: c - offset,
                                 lambda c: c + window - 1 - offset)
        dkv_kernel = functools.partial(dkv_kernel, window=window,
                                       first_q=first_q, n_q=nq)
        qblock = lambda j, i: jnp.minimum(first_q(j) + i, nq - 1)
        qspec2 = pl.BlockSpec((1, block_q, d),
                              lambda b, j, i: (b, qblock(j, i), 0), **_VMEM)
        rowspec2 = pl.BlockSpec((1, 1, block_q),
                                lambda b, j, i: (b, 0, qblock(j, i)), **_VMEM)

    dq = pl.pallas_call(
        dq_kernel,
        grid=(bh, nq, k_steps),
        in_specs=[qspec, kspec, kspec, qspec, rowspec, rowspec],
        out_specs=qspec,
        out_shape=jax.ShapeDtypeStruct((bh, tq, d), q.dtype),
        scratch_shapes=[_scratch((block_q, d))],
        compiler_params=_GRID_SEMANTICS, interpret=interpret,
        name="flash_dq",
    )(q, k, v, g, lse, delta)

    dk, dv = pl.pallas_call(
        dkv_kernel,
        grid=(bh, nk, q_steps),
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


@functools.partial(jax.custom_vjp, nondiff_argnums=(3, 4, 5, 6, 7))
def _flash_streamed(q, k, v, causal, block_q, block_k, interpret,
                    window=None):
    o, _ = _flash_fwd_impl(q, k, v, causal, block_q, block_k, interpret,
                           window)
    return o


#: the names (``jax.ad_checkpoint.checkpoint_name``) of what a forward rule
#: hands its backward beside q, k and v: a ``jax.checkpoint`` whose policy
#: saves them does not run the forward kernel again. Named inside the rules:
#: the backward reads the rule's residuals, not the caller's copy of ``o``
FLASH_RESIDUAL_NAMES = ("flash_o", "flash_lse")


def _named_residuals(o, lse):
    return tuple(checkpoint_name(z, name)
                 for z, name in zip((o, lse), FLASH_RESIDUAL_NAMES))


def _flash_streamed_fwd(q, k, v, causal, block_q, block_k, interpret,
                        window=None):
    o, lse = _named_residuals(*_flash_fwd_impl(
        q, k, v, causal, block_q, block_k, interpret, window))
    return o, (q, k, v, o, lse)


def _flash_streamed_bwd(causal, block_q, block_k, interpret, window, res, g):
    q, k, v, o, lse = res
    # backward blocks: score blocks live in VMEM 4x over (pT/dPT/dsT
    # temporaries), so cap at 512x512. A caller-chosen forward block
    # > 512 whose length has no <=512 divisor in the candidate list
    # would make _pick_block return 0 — fall back to the forward block
    # (it ran, so it divides the length) rather than divide by zero.
    bq = _pick_block(q.shape[1], min(block_q, 512)) or block_q
    bk = _pick_block(k.shape[1], min(block_k, 512)) or block_k
    return _flash_bwd_impl(q, k, v, o, lse, g, causal, bq, bk, interpret,
                           window)


_flash_streamed.defvjp(_flash_streamed_fwd, _flash_streamed_bwd)


# ------------------------------------------------- sequence-resident path
#
# One program a row of a self-attention call (tq == tk): the row's whole q,
# k, v (and dO, lse, delta in the backward) sit in VMEM and the block loop
# is inside the kernel body. Dead blocks are not in the loop, only the
# diagonal blocks carry the iota mask, and the backward makes the scores
# once for dq, dk and dv together. Shares no kernel logic with the streamed
# path: that one wants the block loop in the grid.
#
# A row is a column block of [b, t, features] arrays, which the BlockSpecs
# address: ``heads * d`` columns from a column offset an operand,
# ``_program_lanes`` of them a program. Packed, that is the layout the
# projections make (whole 128-lane tiles: one head of 128 lanes or more, or
# 128 // d narrower heads side by side); folded, it is the one head of a
# [b*h, t, d] copy. One body serves both.

#: the in-body block, both kernels (the module docstring has the chip's
#: readings of 128, 256 and 512, and of 256, 512 and 1024 at 4k)
_RESIDENT_BLOCK = 512
#: the longest row whose block loop is straight-line code in the body.
#: Longer rows loop over their blocks: an unrolled kernel's code grows with
#: the square of the row (1.8 MB and 19 s of compiling a call site at 4k
#: against 0.2 MB and 2 s looped: the module docstring has the readings)
_UNROLLED_ROWS = 2048
#: VMEM of a v5e core
_VMEM_CORE = 128 * 2 ** 20
#: what a row's operands, accumulators and block temporaries may take of
#: VMEM for the resident kernels to be chosen. A program asks Mosaic for
#: twice the count (``_resident_call``), so this holds the request under
#: 60% of the core. By ``_resident_bytes``: 2k / head 128 takes 18.7 MiB,
#: 4k / head 128 31.4 MiB (in: the longest row the chip has read, 1.268 /
#: 2.508 ms a call where the streamed kernels take 1.397 / 4.373), 2k /
#: head 128 in float32 28.2 MiB, 3k in float32 39.3 MiB (out), 8k / head
#: 128 56.8 MiB (out: not read on the chip, and its request would be
#: nine tenths of the core)
_RESIDENT_BUDGET = 3 * _VMEM_CORE // 10
_SCOPED_VMEM_DEFAULT = 16 * 2 ** 20


def _resident_block(t: int) -> int:
    """The in-body block for a length: the whole length when short, else
    the widest divisor of at least 128 (a narrower one would unroll into
    hundreds of blocks); 0 where there is none."""
    if t <= _RESIDENT_BLOCK:
        return t if t % 8 == 0 else 0
    b = _pick_block(t, _RESIDENT_BLOCK)
    return b if b >= 128 else 0


def _resident_bytes(t: int, d: int, itemsize: int) -> int:
    """VMEM the larger of the two resident kernels holds for one program:
    inputs and outputs double-buffered, the float32 accumulators, the
    block temporaries. A head narrower than 128 lanes is padded to them,
    or shares them with its neighbours. The backward is the larger at
    every length that fits: the unrolled forward's plain softmax holds a
    block of queries against the whole key row and would pass it from
    about 2.3k on, where the looped bodies take over."""
    lanes = -(-d // 128) * 128
    block = _resident_block(t)
    slab, row = t * lanes * itemsize, 8 * t * 4
    met = t if t <= _UNROLLED_ROWS else block  # keys a query block meets
    fwd = (2 * (4 * slab + row)               # q, k, v; o; lse
           # scores, scores - max and their exponentials, and the cast
           + block * met * (3 * 4 + itemsize))
    bwd = (2 * (8 * slab + row)               # q, k, v, dO, o; dq, dk, dv; lse
           + 3 * slab                # q scaled; dk, dv awaiting a step
           + row                     # delta
           + 3 * t * lanes * 4       # dq, dk, dv in float32
           + 6 * block ** 2 * 4)     # sT, pT, dPT, dsT and their casts
    return max(fwd, bwd)


def _packs(heads: int, d: int) -> bool:
    """Whether ``heads`` heads of width ``d`` side by side are whole
    128-lane column blocks of a [b, t, heads * d] array."""
    return d % 128 == 0 or (128 % d == 0 and (heads * d) % 128 == 0)


def _program_lanes(heads: int, d: int) -> int:
    """Columns one program holds: a head of a multiple of 128 lanes, 128
    lanes of narrower heads, or the one head of a folded array."""
    assert heads == 1 or _packs(heads, d), (heads, d)
    return d if heads == 1 or d % 128 == 0 else 128


def flash_path(tq: int, tk: int, d: int, dtype, heads: int = 0,
               window: Optional[int] = None) -> str:
    """Which kernels ``flash_attention`` runs at these shapes, a pure
    function of them. The resident kernels for self-attention lengths that
    split into in-body blocks and whose row fits the VMEM budget: on the
    projections' own layout ("resident_packed") where ``heads`` heads of
    ``d`` are whole 128-lane column blocks, on folded [b*h, t, d] copies
    ("resident") where they are not or ``heads`` is not given. "streamed"
    for everything else (cross-length calls, 8k and longer), as before, and
    for every call with a ``window``: the resident kernels have none."""
    if window is not None:
        return "streamed"
    fits = tq == tk and _resident_block(tq) and _resident_bytes(
        tq, d, jnp.dtype(dtype).itemsize) <= _RESIDENT_BUDGET
    if not fits:
        return "streamed"
    return "resident_packed" if heads and _packs(heads, d) else "resident"


def _resident_call(kernel, name, operands, cols, rows, outs, heads, d,
                   scratch, interpret):
    """One resident kernel over grid (b, column blocks): ``operands`` are
    [b, t, features] arrays whose ``heads * d`` columns start at ``cols``,
    ``rows`` [b, heads, 1, t] float32 arrays (lse), ``outs`` what it
    returns: "slab" for a [b, t, heads * d] array, "rows" for such rows,
    or "thirds" alone for ONE [b, t, 3 * heads * d] array whose three
    thirds a program writes in three grid steps of their own."""
    n, t = operands[0].shape[:2]
    dtype = operands[0].dtype
    lanes = _program_lanes(heads, d)
    blocks = heads * d // lanes
    thirds = outs == "thirds"

    def program(i, j, *step):
        """The program whose operands grid step (i, j[, step]) wants."""
        if not step:
            return i, j
        # a program computes in its first step and hands a third out in
        # each: its operands are asked for from the step after the program
        # before computed, so their fetch runs under that computation and
        # not in the two short steps that only copy
        ahead = jnp.minimum(i * blocks + j + (step[0] > 0), n * blocks - 1)
        return ahead // blocks, ahead % blocks

    def slab(col):
        def index(*ids):
            i, j = program(*ids)
            return i, 0, col // lanes + j
        return pl.BlockSpec((1, t, lanes), index, **_VMEM)

    rowspec = pl.BlockSpec((1, lanes // d, 1, t),
                           lambda *ids: (*program(*ids), 0, 0), **_VMEM)
    if thirds:
        out_specs = pl.BlockSpec(
            (1, t, lanes), lambda i, j, step: (i, 0, step * blocks + j),
            **_VMEM)
        out_shape = jax.ShapeDtypeStruct((n, t, 3 * heads * d), dtype)
    else:
        kinds = {"slab": (slab(0), jax.ShapeDtypeStruct((n, t, heads * d),
                                                        dtype)),
                 "rows": (rowspec, jax.ShapeDtypeStruct((n, heads, 1, t),
                                                        jnp.float32))}
        out_specs, out_shape = zip(*(kinds[out] for out in outs))
    # programs are independent, and the scoped VMEM limit follows the same
    # arithmetic as the rule
    need = 2 * _resident_bytes(t, d, jnp.dtype(dtype).itemsize)
    return pl.pallas_call(
        kernel,
        grid=(n, blocks) + (3,) * thirds,
        in_specs=[slab(col) for col in cols] + [rowspec] * len(rows),
        out_specs=out_specs, out_shape=out_shape, scratch_shapes=scratch,
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel")
            + ("arbitrary",) * thirds,
            vmem_limit_bytes=max(need, _SCOPED_VMEM_DEFAULT)),
        interpret=interpret, name=name,
    )(*operands, *rows)


_NT = (((1,), (1,)), ((), ()))   # a · bᵀ
_NN = (((1,), (0,)), ((), ()))   # a · b
_TN = (((0,), (0,)), ((), ()))   # aᵀ · b


def _dot(a, b, dims):
    return jax.lax.dot_general(a, b, dims, preferred_element_type=jnp.float32)


def _diagonal_keep(block, q_dim):
    """The causal mask of a diagonal block whose queries run along
    ``q_dim``: the same for every such block."""
    qi = jax.lax.broadcasted_iota(jnp.int32, (block, block), q_dim)
    ki = jax.lax.broadcasted_iota(jnp.int32, (block, block), 1 - q_dim)
    return qi >= ki


def _own_lanes(lanes, d):
    """For each head of a program the [1, lanes] mask of its lanes, None
    for a head that has the program to itself. Heads that share 128 lanes
    are never sliced apart: an operand with the other heads' lanes zeroed
    contracts to this head's product exactly (the MXU pass is as deep as a
    64-wide one), a product with such an operand on the right lands in this
    head's lanes and leaves the others zero, and every load and store is a
    whole tile wide."""
    if lanes == d:
        return [None]
    lane = jax.lax.broadcasted_iota(jnp.int32, (1, lanes), 1)
    return [(lane >= a * d) & (lane < (a + 1) * d) for a in range(lanes // d)]


def _only(own, x):
    return x if own is None else jnp.where(own, x, jnp.zeros_like(x))


def _resident_fwd_kernel(q_ref, k_ref, v_ref, o_ref, lse_ref,
                         *, scale, causal, block, d):
    t, lanes = q_ref.shape[1:]
    for q0 in range(0, t, block):
        rows = slice(q0, q0 + block)
        # the softmax scale folded into q once, rounded as a pass of XLA's
        # before the kernel would round it
        qs = (q_ref[0, rows, :] * scale).astype(q_ref.dtype)
        out = None
        for a, own in enumerate(_own_lanes(lanes, d)):
            q = _only(own, qs)
            # causal: the keys before this block of queries need no mask,
            # the diagonal block does; otherwise every key, unmasked
            parts = []
            if q0 or not causal:
                cols = slice(0, q0 if causal else t)
                parts.append((cols, _dot(q, k_ref[0, cols, :], _NT)))
            if causal:
                s = _dot(q, k_ref[0, rows, :], _NT)
                parts.append((rows, jnp.where(_diagonal_keep(block, 0), s,
                                              _NEG_INF)))
            # the whole key row is here: a plain softmax, no running max
            m = functools.reduce(jnp.maximum, [
                jnp.max(s, axis=1, keepdims=True) for _, s in parts])
            ps = [(cols, jnp.exp(s - m)) for cols, s in parts]
            denom = jnp.maximum(
                sum(jnp.sum(p, axis=1, keepdims=True) for _, p in ps), 1e-30)
            # against the unmasked v: this head's lanes are its output
            acc = sum(_dot(p.astype(v_ref.dtype), v_ref[0, cols, :], _NN)
                      for cols, p in ps)
            out = acc / denom if out is None else jnp.where(
                own, acc / denom, out)
            # as a lane-major row, the layout the backward reads: a [t, 1]
            # column costs a 128-lane tile a value, in the kernel's store
            # and again in the XLA pass that has to repack it (.T, not a
            # reshape: Mosaic's relayout for that one read 0.06 ms a call
            # slower)
            lse_ref[0, a, :, rows] = (m + jnp.log(denom)).T
        o_ref[0, rows, :] = out.astype(o_ref.dtype)


def _block_at(i, block):
    """Rows [i * block, (i + 1) * block) of a ref, ``i`` a loop index."""
    return pl.ds(pl.multiple_of(i * block, block), block)


def _resident_fwd_looped_kernel(q_ref, k_ref, v_ref, o_ref, lse_ref,
                                *, scale, causal, block, d):
    """The forward for rows over ``_UNROLLED_ROWS``: the same program a row
    and the same operands in VMEM, the block loops as loops. A block of
    queries meets its key blocks one at a time, so the softmax keeps a
    running max and rescales (the streamed kernel's algebra); dead blocks
    are not in the loop and only the diagonal block is masked."""
    t, lanes = q_ref.shape[1:]
    blocks = t // block

    def q_block(i, _):
        rows = _block_at(i, block)
        qs = (q_ref[0, rows, :] * scale).astype(q_ref.dtype)
        out = None
        for a, own in enumerate(_own_lanes(lanes, d)):
            q = _only(own, qs)

            def meet(cols, carry, masked):
                m, l, acc = carry
                s = _dot(q, k_ref[0, cols, :], _NT)
                if masked:
                    s = jnp.where(_diagonal_keep(block, 0), s, _NEG_INF)
                m_new = jnp.maximum(m, jnp.max(s, axis=1, keepdims=True))
                p, corr = jnp.exp(s - m_new), jnp.exp(m - m_new)
                return (m_new, corr * l + jnp.sum(p, axis=1, keepdims=True),
                        corr * acc + _dot(p.astype(v_ref.dtype),
                                          v_ref[0, cols, :], _NN))
            carry = (jnp.full((block, 1), _NEG_INF, jnp.float32),
                     jnp.zeros((block, 1), jnp.float32),
                     jnp.zeros((block, lanes), jnp.float32))
            # causal: the key blocks before this one, then the diagonal
            carry = jax.lax.fori_loop(
                0, i if causal else blocks,
                lambda j, c: meet(_block_at(j, block), c, False), carry)
            m, l, acc = meet(rows, carry, True) if causal else carry
            denom = jnp.maximum(l, 1e-30)
            out = acc / denom if out is None else jnp.where(
                own, acc / denom, out)
            lse_ref[0, a, :, rows] = (m + jnp.log(denom)).T
        o_ref[0, rows, :] = out.astype(o_ref.dtype)

    jax.lax.fori_loop(0, blocks, q_block, None)


# jitted, both wrappers: every layer of a model calls with the same shapes,
# so the unrolled body is traced and lowered once a program, not once a
# layer; XLA inlines the calls
@functools.partial(jax.jit, static_argnums=(3, 4, 5, 6, 7, 8))
def _resident_fwd(q, k, v, cols, heads: int, d: int, causal: bool,
                  block: int, interpret: bool):
    """q, k, v: [b, t, features] arrays (one fused projection three times,
    or three arrays) with ``heads`` heads of ``d`` from the column offsets
    ``cols`` -> (o [b, t, heads * d], lse [b, heads, 1, t])."""
    kernel = functools.partial(
        _resident_fwd_kernel if q.shape[1] <= _UNROLLED_ROWS
        else _resident_fwd_looped_kernel,
        scale=1.0 / d ** 0.5, causal=causal, block=block, d=d)
    return _resident_call(kernel, "flash_fwd", (q, k, v), cols, (),
                          ("slab", "rows"), heads, d, (), interpret)


def _resident_bwd_kernel(q_ref, k_ref, v_ref, do_ref, o_ref, lse_ref,
                         dq_ref, dk_ref, dv_ref, qs_ref, dlt_ref, dq_acc,
                         *, scale, causal, block, d):
    """dq, dk and dv of one program's heads in one pass: per live block the
    scores once, five products and one exp chain (the streamed pair makes
    seven and two). Same algebra and precision as ``_bwd_block``."""
    t, lanes = q_ref.shape[1:]
    heads = _own_lanes(lanes, d)
    # q with the scale folded in, rounded as the forward rounds it: every
    # key block reads it again
    qs_ref[:] = (q_ref[0] * scale).astype(qs_ref.dtype)
    dq_acc[:] = jnp.zeros_like(dq_acc)
    # delta = rowsum(dO ∘ O) a head, as the [1, t] row lse is: made here, a
    # pass of XLA's over dO and O would have to transpose its sums
    for q0 in range(0, t, block):
        rows = slice(q0, q0 + block)
        prod = do_ref[0, rows, :].astype(jnp.float32) \
            * o_ref[0, rows, :].astype(jnp.float32)
        for a, own in enumerate(heads):
            dlt_ref[a, :, rows] = jnp.sum(_only(own, prod), axis=1,
                                          keepdims=True).T
    for k0 in range(0, t, block):
        keys = slice(k0, k0 + block)
        dk_out = dv_out = None
        for a, own in enumerate(heads):
            # this head's k and v alone: the scores and dP contract over
            # its lanes, and dq lands in them
            k, v = _only(own, k_ref[0, keys, :]), _only(own, v_ref[0, keys, :])
            dk = dv = jnp.zeros((block, lanes), jnp.float32)
            # causal: the diagonal block, then the queries after it
            for q0 in range(k0 if causal else 0, t, block):
                rows = slice(q0, q0 + block)
                qs, do = qs_ref[rows, :], do_ref[0, rows, :]
                sT = _dot(k, qs, _NT)                    # [keys, queries]
                if causal and q0 == k0:
                    sT = jnp.where(_diagonal_keep(block, 1), sT, _NEG_INF)
                # lse and delta are [1, t] rows: they broadcast over the keys
                pT = jnp.exp(sT - lse_ref[0, a, :, rows])
                dsT = pT * (_dot(v, do, _NT) - dlt_ref[a, :, rows])
                pT, dsT = pT.astype(do.dtype), dsT.astype(qs.dtype)
                # against the unmasked dO and q: this head's lanes are its
                # dv and dk
                dv += _dot(pT, do, _NN)
                dk += _dot(dsT, qs, _NN)
                dq_acc[rows, :] += _dot(dsT, k, _TN)
            dk_out = dk if dk_out is None else jnp.where(own, dk, dk_out)
            dv_out = dv if dv_out is None else jnp.where(own, dv, dv_out)
        dk_ref[0, keys, :] = dk_out.astype(dk_ref.dtype)
        dv_ref[0, keys, :] = dv_out.astype(dv_ref.dtype)
    dq_ref[0] = (dq_acc[:] * scale).astype(dq_ref.dtype)


def _resident_bwd_looped_kernel(q_ref, k_ref, v_ref, do_ref, o_ref, lse_ref,
                                dq_ref, dk_ref, dv_ref, qs_ref, dlt_ref,
                                dq_acc, *, scale, causal, block, d):
    """The same pass for rows over ``_UNROLLED_ROWS``, the block loops as
    loops: per live block the same five products and one exp chain, dk and
    dv carried through the loop over the queries a key block meets."""
    t, lanes = q_ref.shape[1:]
    blocks = t // block
    heads = _own_lanes(lanes, d)
    qs_ref[:] = (q_ref[0] * scale).astype(qs_ref.dtype)
    dq_acc[:] = jnp.zeros_like(dq_acc)

    def delta(i, _):
        rows = _block_at(i, block)
        prod = do_ref[0, rows, :].astype(jnp.float32) \
            * o_ref[0, rows, :].astype(jnp.float32)
        for a, own in enumerate(heads):
            dlt_ref[a, :, rows] = jnp.sum(_only(own, prod), axis=1,
                                          keepdims=True).T
    jax.lax.fori_loop(0, blocks, delta, None)

    def k_block(j, _):
        keys = _block_at(j, block)
        dk_out = dv_out = None
        for a, own in enumerate(heads):
            k, v = _only(own, k_ref[0, keys, :]), _only(own, v_ref[0, keys, :])

            def meet(i, carry, masked):
                dk, dv = carry
                rows = _block_at(i, block)
                qs, do = qs_ref[rows, :], do_ref[0, rows, :]
                sT = _dot(k, qs, _NT)                    # [keys, queries]
                if masked:
                    sT = jnp.where(_diagonal_keep(block, 1), sT, _NEG_INF)
                pT = jnp.exp(sT - lse_ref[0, a, :, rows])
                dsT = pT * (_dot(v, do, _NT) - dlt_ref[a, :, rows])
                pT, dsT = pT.astype(do.dtype), dsT.astype(qs.dtype)
                dq_acc[rows, :] += _dot(dsT, k, _TN)
                return dk + _dot(dsT, qs, _NN), dv + _dot(pT, do, _NN)
            carry = (jnp.zeros((block, lanes), jnp.float32),) * 2
            # causal: the diagonal block, then the queries after it
            if causal:
                carry = meet(j, carry, True)
            dk, dv = jax.lax.fori_loop(
                j + 1 if causal else 0, blocks,
                lambda i, c: meet(i, c, False), carry)
            dk_out = dk if dk_out is None else jnp.where(own, dk, dk_out)
            dv_out = dv if dv_out is None else jnp.where(own, dv, dv_out)
        dk_ref[0, keys, :] = dk_out.astype(dk_ref.dtype)
        dv_ref[0, keys, :] = dv_out.astype(dv_ref.dtype)

    jax.lax.fori_loop(0, blocks, k_block, None)
    dq_ref[0] = (dq_acc[:] * scale).astype(dq_ref.dtype)


def _resident_bwd_thirds_kernel(q_ref, k_ref, v_ref, do_ref, o_ref, lse_ref,
                                out_ref, dk_ref, dv_ref, *scratch, **static):
    """The same pass with dq, dk and dv as the three thirds of one
    [b, t, 3 * heads * d] array, the layout the fused projection's backward
    contracts over: computed in the program's first grid step, dq to the
    output and dk and dv to VMEM, which the next two steps hand out."""
    step = pl.program_id(2)

    @pl.when(step == 0)
    def _compute():
        _resident_bwd_body(q_ref.shape[1])(
            q_ref, k_ref, v_ref, do_ref, o_ref, lse_ref, out_ref, dk_ref,
            dv_ref, *scratch, **static)

    @pl.when(step == 1)
    def _dk():
        out_ref[:] = dk_ref[:]

    @pl.when(step == 2)
    def _dv():
        out_ref[:] = dv_ref[:]


def _resident_bwd_body(t: int):
    return (_resident_bwd_kernel if t <= _UNROLLED_ROWS
            else _resident_bwd_looped_kernel)


@functools.partial(jax.jit, static_argnums=(6, 7, 8, 9, 10, 11))
def _resident_bwd(q, k, v, o, lse, g, cols, heads: int, causal: bool,
                  block: int, interpret: bool, thirds: bool = False):
    """-> (dq, dk, dv), each [b, t, heads * d], or with ``thirds`` the one
    [b, t, 3 * heads * d] array that holds them side by side."""
    t, d = o.shape[1], o.shape[2] // heads
    lanes = _program_lanes(heads, d)
    static = dict(scale=1.0 / d ** 0.5, causal=causal, block=block, d=d)
    scratch = (pltpu.VMEM((t, lanes), q.dtype), _scratch((lanes // d, 1, t)),
               _scratch((t, lanes)))
    if thirds:
        kernel = functools.partial(_resident_bwd_thirds_kernel, **static)
        scratch = (pltpu.VMEM((1, t, lanes), q.dtype),) * 2 + scratch
    else:
        kernel = functools.partial(_resident_bwd_body(t), **static)
    return _resident_call(
        kernel, "flash_dq_dkv", (q, k, v, g, o), cols + (0, 0), (lse,),
        "thirds" if thirds else ("slab",) * 3, heads, d, scratch, interpret)


@functools.partial(jax.custom_vjp, nondiff_argnums=(3, 4, 5))
def _flash_resident(q, k, v, heads, causal, interpret):
    """q, k, v: [b, t, heads * d] each (heads = 1: folded [b*h, t, d])."""
    return _flash_resident_fwd(q, k, v, heads, causal, interpret)[0]


def _flash_resident_fwd(q, k, v, heads, causal, interpret):
    o, lse = _named_residuals(*_resident_fwd(
        q, k, v, (0, 0, 0), heads, q.shape[2] // heads, causal,
        _resident_block(q.shape[1]), interpret))
    return o, (q, k, v, o, lse)


def _flash_resident_bwd(heads, causal, interpret, res, g):
    q, k, v, o, lse = res
    return tuple(_resident_bwd(q, k, v, o, lse, g, (0, 0, 0), heads, causal,
                               _resident_block(q.shape[1]), interpret))


_flash_resident.defvjp(_flash_resident_fwd, _flash_resident_bwd)


def _qkv_cols(qkv):
    width = qkv.shape[2] // 3
    return (0, width, 2 * width)


@functools.partial(jax.custom_vjp, nondiff_argnums=(1, 2, 3))
def _flash_resident_qkv(qkv, heads, causal, interpret):
    """qkv: a fused projection's [b, t, 3 * heads * d] output, read where it
    lies: q, k and v are its three column offsets, and no split exists."""
    return _flash_resident_qkv_fwd(qkv, heads, causal, interpret)[0]


def _flash_resident_qkv_fwd(qkv, heads, causal, interpret):
    # o and lse are not named here: no recomputable block calls this rule,
    # and a name renumbers the private functions of the lowered GPT step
    o, lse = _resident_fwd(qkv, qkv, qkv, _qkv_cols(qkv), heads,
                           qkv.shape[2] // (3 * heads), causal,
                           _resident_block(qkv.shape[1]), interpret)
    return o, (qkv, o, lse)


def _flash_resident_qkv_bwd(heads, causal, interpret, res, g):
    qkv, o, lse = res
    return (_resident_bwd(qkv, qkv, qkv, o, lse, g, _qkv_cols(qkv), heads,
                          causal, _resident_block(qkv.shape[1]), interpret,
                          True),)


_flash_resident_qkv.defvjp(_flash_resident_qkv_fwd, _flash_resident_qkv_bwd)


def flash_attention(
    q: jnp.ndarray,  # [b, tq, h, d]
    k: jnp.ndarray,  # [b, tk, h, d]
    v: jnp.ndarray,  # [b, tk, h, d]
    causal: bool = False,
    mask: Optional[jnp.ndarray] = None,
    block_q: Optional[int] = None,
    block_k: Optional[int] = None,
    interpret: Optional[bool] = None,
    window: Optional[int] = None,
) -> jnp.ndarray:
    """Drop-in for ``scaled_dot_product_attention`` (same [b, t, h, d]
    convention). With ``window`` (causal calls only) a query sees itself and
    the ``window - 1`` keys before it: the streamed kernels then visit only
    the key blocks of a query block's band (the k axis of their grid is the
    band, and the two edge blocks are masked), so the work is ``t * window``
    and not ``t * t / 2``. Falls back to the XLA formulation when the kernel
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
    if window is not None:
        if not causal or window < 1:
            raise ValueError("a window is for causal calls, and holds at "
                             f"least the query's own key: got {window}")
        get_registry().counter(
            FLASH_WINDOWED_COUNTER, "flash_attention calls traced with a "
            "window").inc()
        if window >= tk:
            window = None  # every earlier key is inside it: plain causal
    # v5e-tuned defaults: causal favors square 1024-blocks (fewer
    # diagonal crossings per live block); non-causal favors 512x1024
    if block_q is None:
        block_q = 1024 if causal else 512
    if block_k is None:
        block_k = 1024
    if window is not None:
        # a block no wider than the window: a query block then meets the
        # diagonal block and the one or two before it
        block_q, block_k = (min(z, max(128, window)) for z in (block_q, block_k))
    bq = _pick_block(tq, block_q)
    bk = _pick_block(tk, block_k)
    if mask is not None or not bq or not bk or (causal and tq > tk):
        return scaled_dot_product_attention(q, k, v, causal=causal, mask=mask,
                                            window=window)
    if interpret is None:
        interpret = pallas_interpret()
    path = flash_path(tq, tk, d, q.dtype, heads=h, window=window)
    _count_path(path)
    if path == "resident_packed":
        # the projections' layout is the kernels': free reshapes, no copy
        pack = lambda z: z.reshape(b, tq, h * d)
        return _flash_resident(pack(q), pack(k), pack(v), h, causal,
                               interpret).reshape(b, tq, h, d)
    fold = lambda z: z.transpose(0, 2, 1, 3).reshape(b * h, z.shape[1], d)
    # scoped so that the [b, t, h, d] <-> [b*h, t, d] copies have an
    # owner in the device trace, apart from the kernels
    with jax.named_scope("fold_heads"):
        q, k, v = fold(q), fold(k), fold(v)
    if path == "resident":
        o = _flash_resident(q, k, v, 1, causal, interpret)
    else:
        o = _flash_streamed(q, k, v, causal, bq, bk, interpret, window)
    with jax.named_scope("unfold_heads"):
        return o.reshape(b, h, tq, d).transpose(0, 2, 1, 3)


def _count_path(path: str) -> None:
    # decided while tracing, so counted there: nothing in the compiled step
    get_registry().counter(
        FLASH_PATH_COUNTER, "flash_attention calls traced, by the kernels "
        "their shapes chose", path=path).inc()


def flash_attention_qkv(
    qkv: jnp.ndarray,  # [b, t, 3 * heads * d]: q, k, v side by side
    heads: int,
    causal: bool = False,
    interpret: Optional[bool] = None,
) -> jnp.ndarray:
    """Self-attention straight from a fused projection's output, to the
    [b, t, heads * d] array the output projection contracts over. Where the
    shapes choose the packed resident kernels (``flash_path``) they read q,
    k and v where they lie and no split, fold or unfold copy is made;
    everywhere else this is ``flash_attention`` of the three thirds."""
    b, t, features = qkv.shape
    d = features // (3 * heads)
    if flash_path(t, t, d, qkv.dtype, heads=heads) == "resident_packed":
        _count_path("resident_packed")
        if interpret is None:
            interpret = pallas_interpret()
        return _flash_resident_qkv(qkv, heads, causal, interpret)
    q, k, v = (z.reshape(b, t, heads, d) for z in jnp.split(qkv, 3, axis=-1))
    return flash_attention(q, k, v, causal=causal,
                           interpret=interpret).reshape(b, t, heads * d)
