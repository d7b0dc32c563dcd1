"""SequenceVectors — the generic embedding trainer.

Parity: ``models/sequencevectors/SequenceVectors.java:48`` (fit
:159-280) with the learning algorithms of
``models/embeddings/learning/impl/elements/`` (SkipGram :31, CBOW) and
``.../sequence/`` (DBOW, DM for paragraph vectors).

TPU-first reformulation (SURVEY.md §7.9): the reference trains via
Hogwild — an ``AsyncSequencer`` feeding N lock-free
``VectorCalculationsThread``s doing one-row axpy updates (:914, :1008).
That design is pure host-side pointer chasing and cannot feed a matrix
unit. Here training-pair generation stays on the host (numpy,
vectorized) and the math runs as BATCHED device steps:

- one jitted step consumes [B] centers, [B] contexts, [B,K] negatives
  (and/or padded Huffman codes/points) and applies sparse
  ``.at[idx].add`` scatter updates to syn0/syn1 — thousands of
  reference "iterations" per XLA dispatch,
- identical math to word2vec SGNS/HS: the batch IS the Hogwild razor —
  within-batch index collisions accumulate (scatter-add) instead of
  racing, which is the deterministic version of what Hogwild converges
  to stochastically,
- linear lr decay over total expected pairs, computed host-side per
  batch (scalar input, no retrace).
"""

from __future__ import annotations

import functools
from typing import Callable, Iterable, List, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from deeplearning4j_tpu.util.jit import cpu_safe_jit
from deeplearning4j_tpu.models.embeddings.lookup_table import InMemoryLookupTable, WordVectors
from deeplearning4j_tpu.models.word2vec.vocab import Huffman, VocabCache


# --------------------------------------------------------------- device steps

# Below this vocab size the SGNS table update runs as dense one-hotᵀ
# matmuls on the MXU instead of row scatters; measured 1.8x faster at
# V=2000/B=32k/d=128 on v5e (the matmul cost grows linearly with V,
# the scatter cost doesn't — past ~16k rows the scatter wins back).
_DENSE_UPDATE_MAX_VOCAB = 16384

# Per-row in-batch accumulation cap (see _sgns_math): rows occurring
# more than this many times per batch get cap * mean(grad) instead of
# sum(grad). 64 keeps exact-sum parity for >99% of vocab rows on
# zipf-distributed text at 32k batches while bounding head-word
# movement at ~cap*lr per step (the sequential reference's saturating
# trajectory does the same).
_ROW_UPDATE_CAP = 64.0


def _row_denom(n_rows: int, idx, w, dtype, psum_axis=None):
    """[n_rows] per-row divisor for capped accumulation: occurrence
    weight summed per row (globally, when ``psum_axis`` names a mesh
    axis inside shard_map), divided by the cap, floored at 1."""
    cnt = jnp.zeros(n_rows, dtype).at[idx.reshape(-1)].add(w.reshape(-1))
    if psum_axis is not None:
        cnt = jax.lax.psum(cnt, psum_axis)
    return jnp.maximum(cnt / jnp.asarray(_ROW_UPDATE_CAP, dtype), 1.0)


def _sgns_math(syn0, syn1neg, centers, contexts, negatives, lr, weights,
               dense):
    """Shared SGNS batch-update math (SkipGram.iterateSample :204
    neg-sampling branch, batched). ``weights`` [B]: per-pair weight
    (0 = padding). ``dense``: accumulate the table updates as
    one-hotᵀ@grad matmuls (MXU) instead of scatter-adds — identical
    accumulation semantics (duplicates sum), measured 1.8x faster at
    V=2k/B=32k on v5e; TPU f32 matmul default precision makes updates
    agree with the scatter path to ~1e-3 relative, which is far below
    SGD noise for embedding training."""
    v = syn0[centers]                       # [B, d]
    u_pos = syn1neg[contexts]               # [B, d]
    u_neg = syn1neg[negatives]              # [B, K, d]
    s_pos = jnp.sum(v * u_pos, axis=-1)     # [B]
    s_neg = jnp.einsum("bd,bkd->bk", v, u_neg)
    # negatives that collide with the true context are skipped (word2vec
    # semantics: a sampled negative equal to the target is discarded)
    neg_ok = (negatives != contexts[:, None]).astype(s_neg.dtype)
    # maximize log σ(s_pos) + Σ log σ(-s_neg)
    g_pos = (1.0 - jax.nn.sigmoid(s_pos)) * weights
    g_neg = -jax.nn.sigmoid(s_neg) * neg_ok * weights[:, None]
    dv = g_pos[:, None] * u_pos + jnp.einsum("bk,bkd->bd", g_neg, u_neg)
    du_pos = g_pos[:, None] * v
    du_neg = g_neg[..., None] * v[:, None, :]
    # CAPPED accumulation: a row that occurs m times in the batch
    # receives lr * sum(grads) for m <= _ROW_UPDATE_CAP, and
    # lr * cap * mean(grads) beyond. The reference's sequential
    # per-pair axpy is self-limiting (each update moves the logit,
    # saturating the next sigmoid) so its cumulative movement grows
    # roughly linearly then flattens; a batched SUM is linear forever —
    # a zipf head word appearing thousands of times per 32k batch gets
    # an effective lr thousands of times larger and the tables
    # measurably diverge to inf (both scatter and dense paths, any
    # batch >~1k on natural-text frequencies). A pure MEAN is the
    # opposite failure: head rows take ONE bounded step per batch where
    # the reference takes thousands of micro-steps, and nothing trains.
    # sum-until-cap is exact-sum parity for all but the few head rows
    # and reproduces the saturating trajectory for those.
    # the two tables can differ in row count (ParagraphVectors trains
    # doc vectors in syn0 against the WORD output table in syn1neg), so
    # each side's counts/one-hots are sized by its own table
    V0 = syn0.shape[0]
    V1 = syn1neg.shape[0]
    d = syn0.shape[1]
    idx_all = jnp.concatenate([contexts[:, None], negatives],
                              axis=1).reshape(-1)                 # [B(K+1)]
    du_all = jnp.concatenate([du_pos[:, None], du_neg],
                             axis=1).reshape(-1, d)
    w_all = jnp.broadcast_to(weights[:, None],
                             (weights.shape[0], negatives.shape[1] + 1)
                             ).reshape(-1)
    if dense:
        cap = jnp.asarray(_ROW_UPDATE_CAP, syn0.dtype)
        oh_c = jax.nn.one_hot(centers, V0, dtype=syn0.dtype)      # [B, V0]
        den_c = jnp.maximum((oh_c.T @ weights) / cap, 1.0)        # [V0]
        syn0 = syn0 + lr * jnp.einsum("bv,bd->vd", oh_c, dv) / den_c[:, None]
        oh_u = jax.nn.one_hot(idx_all, V1, dtype=syn0.dtype)
        den_u = jnp.maximum((oh_u.T @ w_all) / cap, 1.0)
        syn1neg = syn1neg + lr * jnp.einsum("bv,bd->vd", oh_u, du_all) \
            / den_u[:, None]
    else:
        den_c = _row_denom(V0, centers, weights, syn0.dtype)
        syn0 = syn0.at[centers].add(lr * dv / den_c[centers][:, None])
        den_u = _row_denom(V1, idx_all, w_all, syn0.dtype)
        syn1neg = syn1neg.at[idx_all].add(lr * du_all
                                          / den_u[idx_all][:, None])
    n_real = jnp.maximum(jnp.sum(weights), 1.0)
    loss = -jnp.sum((jnp.log(jax.nn.sigmoid(s_pos) + 1e-10)
                     + jnp.sum(jnp.log(jax.nn.sigmoid(-s_neg) + 1e-10) * neg_ok,
                               axis=-1)) * weights) / n_real
    return syn0, syn1neg, loss


@cpu_safe_jit(donate_argnums=(0, 1), static_argnames=("dense",))
def _sgns_step(syn0, syn1neg, centers, contexts, negatives, lr, weights,
               dense=False):
    """One host-fed SGNS batch (the fallback path; the hot path is
    ``_sgns_scan_program`` which never leaves the device)."""
    return _sgns_math(syn0, syn1neg, centers, contexts, negatives, lr,
                      weights, dense)


def _device_pairs(flat, pos, slen, n_tokens, idx, kb, offs, bp, n2w, N):
    """On-device window generation for one batch of stream positions —
    the ONE implementation every scan program shares (reduced-window
    draw, same-sentence bounds, padding guard). Returns the UNflattened
    (centers [bp], contexts [bp, 2w], ok [bp, 2w] float mask): the
    skip-gram callers flatten to a pair stream, CBOW consumes the
    window matrix directly."""
    centers = flat[idx]
    p, L = pos[idx], slen[idx]
    window = n2w // 2
    b = jax.random.randint(jax.random.fold_in(kb, 0), (bp,), 1, window + 1)
    cpos = p[:, None] + offs[None, :]                             # [bp, 2w]
    ok = ((jnp.abs(offs)[None, :] <= b[:, None])
          & (cpos >= 0) & (cpos < L[:, None])
          & (idx[:, None] < n_tokens))
    contexts = flat[jnp.clip(idx[:, None] + offs[None, :], 0, N - 1)]
    return centers, contexts, ok.astype(jnp.float32)


def _flat_pairs(centers, contexts, ok, bp, n2w):
    """[bp]-windows → the flattened (center, context, weight) pair
    stream the skip-gram objectives consume."""
    c2 = jnp.broadcast_to(centers[:, None], (bp, n2w)).reshape(-1)
    return c2, contexts.reshape(-1), ok.reshape(-1)


@cpu_safe_jit(donate_argnums=(0, 1),
              static_argnames=("window", "K", "bp", "n_steps", "dense"))
def _sgns_scan_program(syn0, syn1neg, flat, pos, slen, neg_table, key,
                       lr0, min_lr, n_tokens, step0, total_steps, *,
                       window, K, bp, n_steps, dense):
    """ONE EPOCH of SGNS training as ONE compiled program.

    A per-batch host loop spends its wall clock on host↔device traffic
    (pair/negative uploads each step + loss fetches), not on the chip.
    Here the token stream is uploaded once and everything else happens
    in a ``lax.scan``:

    - pair generation on device: for each batch of ``bp`` stream
      positions, the 2*window offset slots are materialized with a 0/1
      weight (reduced-window b ~ U[1, window] per center, same-sentence
      bounds) — the same (center, context, weight) stream
      ``skipgram_pairs`` builds, in the reference's sentence order
      (``SequenceVectors.java`` :914 feeds sentences in stream order;
      no global pair shuffle exists there either),
    - negative sampling on device from the unigram^0.75 quantized
      table (``InMemoryLookupTable.java:66-74``'s own design: one
      randint + one gather per sample; an exact searchsorted
      inverse-CDF measured 8x slower on v5e), strided down to <=128k
      entries so the one-time upload stays small,
    - linear lr decay from the scan step counter.

    flat/pos/slen: [N] padded token stream, within-sentence position,
    sentence length. ``n_tokens``: real (unpadded) token count.
    ``step0``/``total_steps``: DYNAMIC global step offset and lr-decay
    horizon, so the compile depends only on the corpus shape — running
    more epochs re-dispatches this same executable with a new offset
    and key instead of recompiling. Returns
    (syn0', syn1neg', losses[n_steps]).
    """
    offs = jnp.asarray([d for d in range(-window, window + 1) if d != 0],
                       jnp.int32)                                 # [2w]
    n2w = 2 * window
    N = flat.shape[0]
    total = total_steps.astype(jnp.float32)

    def body(carry, i):
        syn0, syn1neg = carry
        base = (i % (N // bp)) * bp
        idx = base + jnp.arange(bp, dtype=jnp.int32)              # [bp]
        kb = jax.random.fold_in(key, step0 + i)
        c2, x2, w2 = _flat_pairs(*_device_pairs(
            flat, pos, slen, n_tokens, idx, kb, offs, bp, n2w, N), bp, n2w)
        negs = neg_table[jax.random.randint(
            jax.random.fold_in(kb, 1), (bp * n2w, K), 0,
            neg_table.shape[0])]
        g_step = (step0 + i).astype(jnp.float32)
        lr = jnp.maximum(min_lr, lr0 * (1.0 - g_step / total))
        syn0, syn1neg, loss = _sgns_math(syn0, syn1neg, c2, x2, negs, lr,
                                         w2, dense)
        return (syn0, syn1neg), loss

    (syn0, syn1neg), losses = jax.lax.scan(
        body, (syn0, syn1neg), jnp.arange(n_steps, dtype=jnp.int32))
    return syn0, syn1neg, losses


def _hs_math(syn0, syn1, centers, codes, points, code_mask, lr, weights):
    """Shared hierarchical-softmax batch update (SkipGram.iterateSample
    :204 HS branch, batched over padded Huffman paths)."""
    v = syn0[centers]                       # [B, d]
    u = syn1[points]                        # [B, L, d]
    s = jnp.einsum("bd,bld->bl", v, u)      # [B, L]
    # label = 1 - code; g = (label - σ(s)) masked
    code_mask = code_mask * weights[:, None]
    g = (1.0 - codes - jax.nn.sigmoid(s)) * code_mask
    dv = jnp.einsum("bl,bld->bd", g, u)
    du = g[..., None] * v[:, None, :]
    # capped accumulation (see _sgns_math): Huffman-internal nodes near
    # the root occur in almost every path — an unbounded sum diverges
    den_c = _row_denom(syn0.shape[0], centers, weights, syn0.dtype)
    syn0 = syn0.at[centers].add(lr * dv / den_c[centers][:, None])
    den_p = _row_denom(syn1.shape[0], points, code_mask, syn1.dtype)
    syn1 = syn1.at[points].add(lr * du / den_p[points][..., None])
    p = jax.nn.sigmoid(jnp.where(codes > 0, -s, s))
    loss = -jnp.sum(jnp.log(p + 1e-10) * code_mask) / jnp.maximum(jnp.sum(code_mask), 1.0)
    return syn0, syn1, loss


@cpu_safe_jit(donate_argnums=(0, 1))
def _hs_step(syn0, syn1, centers, codes, points, code_mask, lr, weights):
    """One host-fed HS batch (fallback path; the hot path is
    ``_hs_scan_program``)."""
    return _hs_math(syn0, syn1, centers, codes, points, code_mask, lr,
                    weights)


@cpu_safe_jit(donate_argnums=(0, 1),
              static_argnames=("window", "bp", "n_steps"))
def _hs_scan_program(syn0, syn1, flat, pos, slen, codes_tab, points_tab,
                     cmask_tab, key, lr0, min_lr, n_tokens, step0,
                     total_steps, *, window, bp, n_steps):
    """ONE EPOCH of hierarchical-softmax skip-gram as ONE compiled
    program — the HS twin of ``_sgns_scan_program`` (same device
    pair generation; the Huffman code/point/mask tables are uploaded
    once and gathered by context id on device)."""
    offs = jnp.asarray([d for d in range(-window, window + 1) if d != 0],
                       jnp.int32)
    n2w = 2 * window
    N = flat.shape[0]
    total = total_steps.astype(jnp.float32)

    def body(carry, i):
        syn0, syn1 = carry
        base = (i % (N // bp)) * bp
        idx = base + jnp.arange(bp, dtype=jnp.int32)
        kb = jax.random.fold_in(key, step0 + i)
        c2, x2, w2 = _flat_pairs(*_device_pairs(
            flat, pos, slen, n_tokens, idx, kb, offs, bp, n2w, N), bp, n2w)
        g_step = (step0 + i).astype(jnp.float32)
        lr = jnp.maximum(min_lr, lr0 * (1.0 - g_step / total))
        syn0, syn1, loss = _hs_math(
            syn0, syn1, c2, codes_tab[x2], points_tab[x2], cmask_tab[x2],
            lr, w2)
        return (syn0, syn1), loss

    (syn0, syn1), losses = jax.lax.scan(
        body, (syn0, syn1), jnp.arange(n_steps, dtype=jnp.int32))
    return syn0, syn1, losses


def _huffman_device_tables(huffman):
    """Device copies of the Huffman code/point tables + the padded-path
    float mask — the ONE staging used by both the per-batch fallback
    and the HS scan path."""
    codes = jnp.asarray(huffman.codes)
    points = jnp.asarray(huffman.points)
    lens = huffman.code_lengths
    cmask = jnp.asarray((np.arange(codes.shape[1])[None, :]
                         < lens[:, None]).astype(np.float32))
    return codes, points, cmask


# ------------------------------------------------------------------- sampling

def _pad_np(arr, target: int) -> np.ndarray:
    """Zero-pad the leading dim to ``target`` (paired with a 0 weight)."""
    arr = np.asarray(arr)
    if len(arr) == target:
        return arr
    padding = np.zeros((target - len(arr),) + arr.shape[1:], arr.dtype)
    return np.concatenate([arr, padding])


def skipgram_pairs(sentences_idx: List[np.ndarray], window: int,
                   rng: np.random.Generator) -> Tuple[np.ndarray, np.ndarray]:
    """(center, context) pair generation with the reference's
    reduced-window sampling (random b in [1, window] per center).

    Fully numpy-vectorized over the concatenated corpus: sentences are
    flattened with position indices, and for each offset d in
    [-window, window] a boolean mask selects centers whose sampled
    window covers d AND whose context stays inside the same sentence —
    no Python loop per token (the engine's host half runs on one core;
    the reference amortized this across Hogwild threads)."""
    sents = [np.asarray(s) for s in sentences_idx if len(s) >= 2]
    if not sents:
        return np.zeros(0, np.int32), np.zeros(0, np.int32)
    flat = np.concatenate(sents).astype(np.int32)
    lens = np.array([len(s) for s in sents])
    pos = np.concatenate([np.arange(n) for n in lens])        # within-sentence
    slen = np.repeat(lens, lens)                              # sentence length
    b = rng.integers(1, window + 1, len(flat))
    idx_parts, xs_parts = [], []
    dmax = min(window, int(lens.max()) - 1)  # longer offsets can't pair
    for d in range(-dmax, dmax + 1):
        if d == 0:
            continue
        ok = (np.abs(d) <= b) & (pos + d >= 0) & (pos + d < slen)
        idx = np.nonzero(ok)[0]
        idx_parts.append(idx)
        xs_parts.append(flat[idx + d])
    center_idx = np.concatenate(idx_parts)
    xs = np.concatenate(xs_parts)
    # center-major order, contexts by ascending offset — the same
    # (center, context) sequence the per-token loop produced
    order = np.argsort(center_idx, kind="stable")
    return flat[center_idx[order]], xs[order]  # already int32


def cbow_pairs(sentences_idx, window, rng, pad_idx):
    """(context-window [B, 2w], center [B]) with pad for short windows."""
    ctxs, cs, masks = [], [], []
    W = 2 * window
    for s in sentences_idx:
        n = len(s)
        if n < 2:
            continue
        b = rng.integers(1, window + 1, n)
        for i in range(n):
            lo, hi = max(0, i - b[i]), min(n, i + b[i] + 1)
            ctx = [s[j] for j in range(lo, hi) if j != i]
            if not ctx:
                continue
            pad = W - len(ctx)
            ctxs.append(ctx + [pad_idx] * pad)
            masks.append([1.0] * len(ctx) + [0.0] * pad)
            cs.append(s[i])
    if not cs:
        z = np.zeros((0, W))
        return z.astype(np.int32), np.zeros(0, np.int32), z.astype(np.float32)
    return (np.asarray(ctxs, np.int32), np.asarray(cs, np.int32),
            np.asarray(masks, np.float32))


def _cbow_math(syn0, syn1neg, ctx, ctx_mask, centers, negatives, lr,
               weights):
    """Shared CBOW + negative-sampling update (CBOW.java batched):
    mean of context vectors predicts the center."""
    vc = syn0[ctx] * ctx_mask[..., None]            # [B, W, d]
    denom = jnp.maximum(jnp.sum(ctx_mask, axis=1, keepdims=True), 1.0)
    h = jnp.sum(vc, axis=1) / denom                 # [B, d]
    u_pos = syn1neg[centers]
    u_neg = syn1neg[negatives]
    s_pos = jnp.sum(h * u_pos, axis=-1)
    s_neg = jnp.einsum("bd,bkd->bk", h, u_neg)
    neg_ok = (negatives != centers[:, None]).astype(s_neg.dtype)
    g_pos = (1.0 - jax.nn.sigmoid(s_pos)) * weights
    g_neg = -jax.nn.sigmoid(s_neg) * neg_ok * weights[:, None]
    dh = g_pos[:, None] * u_pos + jnp.einsum("bk,bkd->bd", g_neg, u_neg)
    dctx = (dh / denom)[:, None, :] * ctx_mask[..., None]
    # capped accumulation (see _sgns_math)
    wc = ctx_mask * weights[:, None]
    den_ctx = _row_denom(syn0.shape[0], ctx, wc, syn0.dtype)
    syn0 = syn0.at[ctx].add(lr * dctx / den_ctx[ctx][..., None])
    idx_all = jnp.concatenate([centers[:, None], negatives], axis=1)
    w_all = jnp.broadcast_to(weights[:, None], idx_all.shape)
    den_u = _row_denom(syn1neg.shape[0], idx_all, w_all, syn1neg.dtype)
    syn1neg = syn1neg.at[centers].add(
        lr * (g_pos[:, None] * h) / den_u[centers][:, None])
    syn1neg = syn1neg.at[negatives].add(
        lr * (g_neg[..., None] * h[:, None, :]) / den_u[negatives][..., None])
    n_real = jnp.maximum(jnp.sum(weights), 1.0)
    loss = -jnp.sum((jnp.log(jax.nn.sigmoid(s_pos) + 1e-10)
                     + jnp.sum(jnp.log(jax.nn.sigmoid(-s_neg) + 1e-10) * neg_ok,
                               axis=-1)) * weights) / n_real
    return syn0, syn1neg, loss


def _cbow_hs_math(syn0, syn1, ctx, ctx_mask, codes, points, code_mask,
                  lr, weights):
    """CBOW with hierarchical softmax (CBOW.java HS branch, batched):
    the masked MEAN of the context vectors walks the CENTER word's
    Huffman path. codes/points/code_mask are the center's [B, L]
    tables."""
    m = ctx_mask[..., None]
    denom = jnp.maximum(jnp.sum(ctx_mask, axis=-1, keepdims=True), 1.0)
    h = jnp.sum(syn0[ctx] * m, axis=1) / denom          # [B, d]
    u = syn1[points]                                    # [B, L, d]
    s = jnp.einsum("bd,bld->bl", h, u)
    cm = code_mask * weights[:, None]
    g = (1.0 - codes - jax.nn.sigmoid(s)) * cm
    dh = jnp.einsum("bl,bld->bd", g, u)
    du = g[..., None] * h[:, None, :]
    dctx = (dh / denom)[:, None, :] * m
    # capped accumulation (see _sgns_math)
    wc = ctx_mask * weights[:, None]
    den_ctx = _row_denom(syn0.shape[0], ctx, wc, syn0.dtype)
    syn0 = syn0.at[ctx].add(lr * dctx / den_ctx[ctx][..., None])
    den_p = _row_denom(syn1.shape[0], points, cm, syn1.dtype)
    syn1 = syn1.at[points].add(lr * du / den_p[points][..., None])
    p = jax.nn.sigmoid(jnp.where(codes > 0, -s, s))
    loss = -jnp.sum(jnp.log(p + 1e-10) * cm) / jnp.maximum(jnp.sum(cm), 1.0)
    return syn0, syn1, loss


@cpu_safe_jit(donate_argnums=(0, 1))
def _cbow_hs_step(syn0, syn1, ctx, ctx_mask, codes, points, code_mask, lr,
                  weights):
    return _cbow_hs_math(syn0, syn1, ctx, ctx_mask, codes, points,
                         code_mask, lr, weights)


@cpu_safe_jit(donate_argnums=(0, 1))
def _cbow_sgns_step(syn0, syn1neg, ctx, ctx_mask, centers, negatives, lr,
                    weights):
    """One host-fed CBOW batch (fallback path; the hot path is
    ``_cbow_scan_program``)."""
    return _cbow_math(syn0, syn1neg, ctx, ctx_mask, centers, negatives, lr,
                      weights)


@cpu_safe_jit(donate_argnums=(0, 1),
              static_argnames=("window", "K", "bp", "n_steps"))
def _cbow_scan_program(syn0, syn1neg, flat, pos, slen, neg_table, key,
                       lr0, min_lr, n_tokens, step0, total_steps, *,
                       window, K, bp, n_steps):
    """ONE EPOCH of CBOW + negative sampling as ONE compiled program —
    the device pair generation yields exactly CBOW's [bp, 2w] context
    window (same reduced-window/sentence-bounds mask as the skip-gram
    scans; one center per stream position)."""
    offs = jnp.asarray([d for d in range(-window, window + 1) if d != 0],
                       jnp.int32)
    N = flat.shape[0]
    total = total_steps.astype(jnp.float32)

    n2w = 2 * window

    def body(carry, i):
        syn0, syn1neg = carry
        base = (i % (N // bp)) * bp
        idx = base + jnp.arange(bp, dtype=jnp.int32)
        kb = jax.random.fold_in(key, step0 + i)
        centers, ctx, cmask = _device_pairs(
            flat, pos, slen, n_tokens, idx, kb, offs, bp, n2w, N)
        w = (jnp.sum(cmask, axis=1) > 0).astype(jnp.float32)
        negs = neg_table[jax.random.randint(
            jax.random.fold_in(kb, 1), (bp, K), 0, neg_table.shape[0])]
        g_step = (step0 + i).astype(jnp.float32)
        lr = jnp.maximum(min_lr, lr0 * (1.0 - g_step / total))
        syn0, syn1neg, loss = _cbow_math(syn0, syn1neg, ctx, cmask,
                                         centers, negs, lr, w)
        return (syn0, syn1neg), loss

    (syn0, syn1neg), losses = jax.lax.scan(
        body, (syn0, syn1neg), jnp.arange(n_steps, dtype=jnp.int32))
    return syn0, syn1neg, losses


# --------------------------------------------------------------------- engine

class SequenceVectors:
    """Generic embedding trainer over tokenized sequences.

    elements_learning_algorithm: "skipgram" | "cbow";
    use_hierarchic_softmax / negative (sample count) select the
    objective, mirroring the reference builder knobs.
    """

    def __init__(self, vector_length: int = 100, window: int = 5,
                 min_word_frequency: int = 1, epochs: int = 1,
                 learning_rate: float = 0.025, min_learning_rate: float = 1e-4,
                 negative: int = 5, use_hierarchic_softmax: bool = False,
                 subsampling: float = 0.0, batch_size: int = 4096,
                 elements_learning_algorithm: str = "skipgram", seed: int = 123,
                 device_pairgen: bool = True,
                 mesh=None, data_axis: str = "data", model_axis: str = "model"):
        self.vector_length = vector_length
        self.window = window
        self.min_word_frequency = min_word_frequency
        self.epochs = epochs
        self.learning_rate = learning_rate
        self.min_learning_rate = min_learning_rate
        self.negative = negative
        self.use_hs = use_hierarchic_softmax
        self.subsampling = subsampling
        self.batch_size = batch_size
        self.algo = elements_learning_algorithm
        self.seed = seed
        # device_pairgen: allow the all-epochs-on-device scan path (the
        # hot path on a real TPU). Off = the host per-batch loop, which
        # the sharded steps and the sharded-vs-single equivalence tests
        # use (identical pair stream on both sides).
        self.device_pairgen = device_pairgen
        # mesh-sharded training (the Spark-NLP distributed word2vec role):
        # pair stream over data_axis, embedding dim over model_axis
        self.mesh = mesh
        self.data_axis = data_axis
        self.model_axis = model_axis
        if mesh is not None and data_axis not in mesh.shape:
            raise ValueError(
                f"mesh {dict(mesh.shape)} has no '{data_axis}' axis; the pair "
                f"stream needs one — for pure embedding-dim sharding use "
                f"{{'{data_axis}': 1, '{model_axis}': N}}")
        self.vocab: Optional[VocabCache] = None
        self.lookup_table: Optional[InMemoryLookupTable] = None
        self.huffman: Optional[Huffman] = None
        self._loss_history: List[float] = []

    # -- vocab --

    def build_vocab(self, token_lists: Iterable[List[str]]):
        self.vocab = VocabCache.build_from_sentences(token_lists, self.min_word_frequency)
        self.lookup_table = InMemoryLookupTable(self.vocab, self.vector_length, self.seed)
        self.lookup_table.reset_weights()
        if self.use_hs:
            self.huffman = Huffman(self.vocab)

    def _to_indices(self, token_lists: Sequence[List[str]],
                    rng: np.random.Generator) -> List[np.ndarray]:
        out = []
        total = max(self.vocab.total_word_count(), 1)
        freqs = self.vocab.word_frequencies() / total
        for toks in token_lists:
            idx = [self.vocab.index_of(t) for t in toks]
            idx = [i for i in idx if i >= 0]
            if self.subsampling > 0:
                # reference subsampling: P(keep) = sqrt(t/f) + t/f
                keep = []
                for i in idx:
                    f = freqs[i]
                    p = min(1.0, (np.sqrt(f / self.subsampling) + 1) * self.subsampling / f)
                    if rng.random() < p:
                        keep.append(i)
                idx = keep
            out.append(np.asarray(idx, np.int32))
        return out

    # -- training --

    def fit(self, token_lists: Sequence[List[str]]):
        if self.vocab is None:
            self.build_vocab(token_lists)
        lt = self.lookup_table
        rng = np.random.default_rng(self.seed)
        sharded = self.mesh is not None
        if sharded and self.algo == "cbow" and self.use_hs:
            # fail BEFORE any device placement happens below
            raise NotImplementedError(
                "mesh-sharded CBOW with hierarchical softmax is not "
                "implemented; use negative sampling or the single-device "
                "path")
        if sharded:
            from deeplearning4j_tpu.models.sequencevectors.distributed import (
                make_sharded_cbow_step, make_sharded_hs_step,
                make_sharded_sgns_step, place_tables)
            dsize = self.mesh.shape[self.data_axis]
            syn0, syn1 = place_tables(
                self.mesh, lt.syn0, lt.syn1 if self.use_hs else lt.syn1neg,
                self.model_axis)
            kw = dict(data_axis=self.data_axis, model_axis=self.model_axis)
            if self.algo == "cbow":
                sh_step = make_sharded_cbow_step(self.mesh, **kw)
            elif self.use_hs:
                sh_step = make_sharded_hs_step(self.mesh, **kw)
            else:
                sh_step = make_sharded_sgns_step(self.mesh, **kw)
            pad = _pad_np
        else:
            syn0 = jnp.asarray(lt.syn0)
            syn1 = jnp.asarray(lt.syn1) if self.use_hs else jnp.asarray(lt.syn1neg)
        # the scan hot path (skip-gram SGNS/HS and CBOW-SGNS) builds
        # its own device tables — do the (potentially megabytes of)
        # host table setup only for the per-batch fallback paths
        scan_path = (not sharded and self.subsampling == 0
                     and self.device_pairgen
                     and (self.algo == "skipgram"
                          or (self.algo == "cbow" and not self.use_hs)))
        neg_table = (lt.negative_table()
                     if not self.use_hs and not scan_path else None)
        if self.use_hs and not scan_path:
            codes, points, cmask = _huffman_device_tables(self.huffman)

        # estimated total steps for linear lr decay
        sentences = list(token_lists)
        est_pairs_per_epoch = max(1, sum(len(s) for s in sentences) * self.window)
        total_steps = max(1, (est_pairs_per_epoch * self.epochs) // self.batch_size)
        step_i = 0
        # dense MXU table updates for small vocabs (single-device SGNS
        # only; the sharded steps keep their scatter formulation)
        dense = (not sharded and self.algo != "cbow" and not self.use_hs
                 and self.vocab.num_words() <= _DENSE_UPDATE_MAX_VOCAB)
        device_losses: List[jnp.ndarray] = []

        # hot path: SGNS/HS skip-gram and CBOW-SGNS with no subsampling
        # run each epoch as one device program (zero per-step host
        # traffic; see the *_scan_program trio). Subsampling re-draws
        # the kept tokens per epoch host-side, so it stays on the
        # per-batch path.
        if scan_path:
            self._fit_scan(sentences, syn0, syn1, rng)
            return

        for _ in range(self.epochs):
            idx_lists = self._to_indices(sentences, rng)
            if self.algo == "cbow":
                ctx, centers, cmask_b = cbow_pairs(idx_lists, self.window, rng, 0)
                order = rng.permutation(len(centers))
                ctx, centers, cmask_b = ctx[order], centers[order], cmask_b[order]
            else:
                centers, contexts = skipgram_pairs(idx_lists, self.window, rng)
                order = rng.permutation(len(centers))
                centers, contexts = centers[order], contexts[order]
            B = self.batch_size
            for s in range(0, len(centers), B):
                lr = max(self.min_learning_rate,
                         self.learning_rate * (1.0 - step_i / total_steps))
                lr = jnp.float32(lr)
                cb = centers[s:s + B]
                if len(cb) == 0:
                    continue
                # pad EVERY batch to one static shape (tail included) and
                # weight the padding to 0: one compile per stream instead
                # of one per distinct tail size (padding also keeps the
                # sharded batch divisible over the data axis)
                if sharded:
                    from deeplearning4j_tpu.models.sequencevectors.distributed import pad_to_multiple
                    tgt = pad_to_multiple(B, dsize)
                else:
                    tgt = B
                w = np.zeros(tgt, np.float32)
                w[:len(cb)] = 1.0
                w = jnp.asarray(w)
                if self.algo == "cbow" and self.use_hs:
                    cj = jnp.asarray(_pad_np(cb, tgt))
                    syn0, syn1, loss = _cbow_hs_step(
                        syn0, syn1, jnp.asarray(_pad_np(ctx[s:s + B], tgt)),
                        jnp.asarray(_pad_np(cmask_b[s:s + B], tgt)),
                        codes[cj], points[cj], cmask[cj], lr, w)
                elif self.algo == "cbow":
                    negs = rng.choice(neg_table, (len(cb), self.negative))
                    if sharded:
                        syn0, syn1, loss = sh_step(
                            syn0, syn1,
                            jnp.asarray(pad(ctx[s:s + B], tgt)),
                            jnp.asarray(pad(cmask_b[s:s + B], tgt)),
                            jnp.asarray(pad(cb, tgt)),
                            jnp.asarray(pad(negs, tgt), jnp.int32), w, lr)
                    else:
                        syn0, syn1, loss = _cbow_sgns_step(
                            syn0, syn1, jnp.asarray(_pad_np(ctx[s:s + B], tgt)),
                            jnp.asarray(_pad_np(cmask_b[s:s + B], tgt)),
                            jnp.asarray(_pad_np(cb, tgt)),
                            jnp.asarray(_pad_np(negs, tgt), jnp.int32), lr, w)
                elif self.use_hs:
                    xb = contexts[s:s + B]
                    if sharded:
                        xj = jnp.asarray(pad(xb, tgt))
                        syn0, syn1, loss = sh_step(
                            syn0, syn1, jnp.asarray(pad(cb, tgt)), codes[xj],
                            points[xj], cmask[xj], w, lr)
                    else:
                        xj = jnp.asarray(_pad_np(xb, tgt))
                        syn0, syn1, loss = _hs_step(
                            syn0, syn1, jnp.asarray(_pad_np(cb, tgt)),
                            codes[xj], points[xj], cmask[xj], lr, w)
                else:
                    negs = rng.choice(neg_table, (len(cb), self.negative))
                    if sharded:
                        syn0, syn1, loss = sh_step(
                            syn0, syn1, jnp.asarray(pad(cb, tgt)),
                            jnp.asarray(pad(contexts[s:s + B], tgt)),
                            jnp.asarray(pad(negs, tgt), jnp.int32), w, lr)
                    else:
                        syn0, syn1, loss = _sgns_step(
                            syn0, syn1, jnp.asarray(_pad_np(cb, tgt)),
                            jnp.asarray(_pad_np(contexts[s:s + B], tgt)),
                            jnp.asarray(_pad_np(negs, tgt), jnp.int32), lr, w,
                            dense=dense)
                step_i += 1
                if step_i % 10 == 0:
                    # device scalar, NOT float(loss): a host fetch here
                    # would wait out every queued step and leave the
                    # chip idle until the next dispatch; one stacked
                    # fetch happens after the loop
                    device_losses.append(loss)
        if device_losses:
            self._loss_history.extend(
                np.asarray(jnp.stack(device_losses)).tolist())
        lt.syn0 = np.asarray(syn0)
        if self.use_hs:
            lt.syn1 = np.asarray(syn1)
        else:
            lt.syn1neg = np.asarray(syn1)

    def _fit_scan(self, sentences, syn0, syn1,
                  rng: np.random.Generator):
        """Stage the token stream once and run every epoch inside one
        of the scan programs (SGNS / HS / CBOW) — the only host↔device
        traffic is the initial upload and one final table/loss
        fetch."""
        lt = self.lookup_table
        idx_lists = self._to_indices(sentences, rng)
        sents = [s for s in idx_lists if len(s) >= 2]
        if not sents:
            return
        flat = np.concatenate(sents).astype(np.int32)
        lens = np.array([len(s) for s in sents])
        pos = np.concatenate([np.arange(n) for n in lens]).astype(np.int32)
        slen = np.repeat(lens, lens).astype(np.int32)
        n_tokens = len(flat)

        n2w = 2 * self.window
        # positions per scan step: skip-gram expands each position into
        # 2w pairs, so bp*2w ~ batch_size pairs; CBOW trains ONE
        # example per position, so bp = batch_size outright
        bp = (self.batch_size if self.algo == "cbow"
              else max(8, self.batch_size // n2w))
        n_batches = -(-n_tokens // bp)
        pad = n_batches * bp - n_tokens
        if pad:
            z = lambda a: np.concatenate([a, np.zeros(pad, np.int32)])
            flat, pos, slen = z(flat), z(pos), z(slen)
        total_steps = n_batches * self.epochs

        key = jax.random.PRNGKey(int(rng.integers(2**31)))
        flat_d, pos_d, slen_d = (jnp.asarray(flat), jnp.asarray(pos),
                                 jnp.asarray(slen))
        common = dict(window=self.window, bp=bp, n_steps=n_batches)
        scal = lambda e: (jnp.float32(self.learning_rate),
                          jnp.float32(self.min_learning_rate),
                          jnp.int32(n_tokens), jnp.int32(e * n_batches),
                          jnp.int32(total_steps))
        loss_chunks = []
        # device unigram^0.75 table (SGNS objectives), built at device
        # size rather than striding the big host table (a stride would
        # drop most tail words); min-one-slot means the actual length
        # is max(128k, vocab words) — ~0.5MB once for typical vocabs
        neg_table = (jnp.asarray(lt.negative_table(size=131072))
                     if not self.use_hs else None)
        if self.algo == "cbow":
            for e in range(self.epochs):
                syn0, syn1, losses = _cbow_scan_program(
                    syn0, syn1, flat_d, pos_d, slen_d, neg_table, key,
                    *scal(e), K=self.negative, **common)
                loss_chunks.append(losses)
            lt.syn0 = np.asarray(syn0)
            lt.syn1neg = np.asarray(syn1)
        elif self.use_hs:
            codes_tab, points_tab, cmask_tab = _huffman_device_tables(
                self.huffman)
            for e in range(self.epochs):
                syn0, syn1, losses = _hs_scan_program(
                    syn0, syn1, flat_d, pos_d, slen_d, codes_tab,
                    points_tab, cmask_tab, key, *scal(e), **common)
                loss_chunks.append(losses)
            lt.syn0 = np.asarray(syn0)
            lt.syn1 = np.asarray(syn1)
        else:
            dense = self.vocab.num_words() <= _DENSE_UPDATE_MAX_VOCAB
            for e in range(self.epochs):
                # one executable per corpus shape; epochs re-dispatch it
                # with a new step offset — no host-device traffic
                # between epochs beyond these scalars
                syn0, syn1, losses = _sgns_scan_program(
                    syn0, syn1, flat_d, pos_d, slen_d, neg_table, key,
                    *scal(e), K=self.negative, dense=dense, **common)
                loss_chunks.append(losses)
            lt.syn0 = np.asarray(syn0)
            lt.syn1neg = np.asarray(syn1)
        self._loss_history.extend(
            np.asarray(jnp.concatenate(loss_chunks))[::10].tolist())

    def word_vectors(self) -> WordVectors:
        return WordVectors(self.vocab, self.lookup_table.syn0)
