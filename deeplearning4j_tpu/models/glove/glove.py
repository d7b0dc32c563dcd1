"""GloVe: co-occurrence counting + weighted least-squares embedding.

Parity: ``models/glove/Glove.java:31`` + ``AbstractCoOccurrences``
(window-weighted co-occurrence counts; 1/distance weighting) trained
with per-element AdaGrad exactly as the reference (which used the
lookup table's AdaGrad, ``InMemoryLookupTable`` :118).

TPU formulation: the nonzero co-occurrence list is the training set;
each jitted step consumes a [B] slice of (i, j, log X_ij, f(X_ij)) and
scatter-updates vectors, biases and AdaGrad history in one program.
"""

from __future__ import annotations

import functools
from typing import Dict, Iterable, List, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from deeplearning4j_tpu.util.jit import cpu_safe_jit
from deeplearning4j_tpu.models.embeddings.lookup_table import WordVectors
from deeplearning4j_tpu.models.word2vec.vocab import VocabCache
from deeplearning4j_tpu.text.tokenization import DefaultTokenizerFactory


@cpu_safe_jit(donate_argnums=(0, 1, 2, 3, 4, 5, 6, 7))
def _glove_step(w, wc, b, bc, hw, hwc, hb, hbc, ii, jj, logx, fx, lr, eps=1e-8):
    """One AdaGrad batch on the GloVe objective."""
    wi = w[ii]
    wj = wc[jj]
    diff = jnp.sum(wi * wj, axis=-1) + b[ii] + bc[jj] - logx   # [B]
    g = fx * diff                                              # [B]
    gwi = g[:, None] * wj
    gwj = g[:, None] * wi
    gbi = g
    gbj = g
    loss = 0.5 * jnp.mean(fx * diff * diff)

    hw = hw.at[ii].add(gwi * gwi)
    w = w.at[ii].add(-lr * gwi / jnp.sqrt(hw[ii] + eps))
    hwc = hwc.at[jj].add(gwj * gwj)
    wc = wc.at[jj].add(-lr * gwj / jnp.sqrt(hwc[jj] + eps))
    hb = hb.at[ii].add(gbi * gbi)
    b = b.at[ii].add(-lr * gbi / jnp.sqrt(hb[ii] + eps))
    hbc = hbc.at[jj].add(gbj * gbj)
    bc = bc.at[jj].add(-lr * gbj / jnp.sqrt(hbc[jj] + eps))
    return w, wc, b, bc, hw, hwc, hb, hbc, loss


class CoOccurrences:
    """``AbstractCoOccurrences`` — symmetric, 1/distance-weighted counts."""

    def __init__(self, vocab: VocabCache, window: int = 15, symmetric: bool = True):
        self.vocab = vocab
        self.window = window
        self.symmetric = symmetric
        self.counts: Dict[Tuple[int, int], float] = {}

    def fit(self, token_lists: Iterable[List[str]]):
        for toks in token_lists:
            idx = [self.vocab.index_of(t) for t in toks]
            idx = [i for i in idx if i >= 0]
            for p, wi in enumerate(idx):
                for off in range(1, self.window + 1):
                    q = p + off
                    if q >= len(idx):
                        break
                    wj = idx[q]
                    weight = 1.0 / off
                    self.counts[(wi, wj)] = self.counts.get((wi, wj), 0.0) + weight
                    if self.symmetric:
                        self.counts[(wj, wi)] = self.counts.get((wj, wi), 0.0) + weight

    def arrays(self) -> Tuple[np.ndarray, np.ndarray, np.ndarray]:
        ii = np.fromiter((k[0] for k in self.counts), np.int32, len(self.counts))
        jj = np.fromiter((k[1] for k in self.counts), np.int32, len(self.counts))
        xx = np.fromiter(self.counts.values(), np.float32, len(self.counts))
        return ii, jj, xx


class Glove:
    def __init__(self, layer_size: int = 100, window: int = 15,
                 min_word_frequency: int = 1, epochs: int = 25,
                 learning_rate: float = 0.05, x_max: float = 100.0,
                 alpha: float = 0.75, batch_size: int = 8192,
                 symmetric: bool = True, seed: int = 123):
        self.layer_size = layer_size
        self.window = window
        self.min_word_frequency = min_word_frequency
        self.epochs = epochs
        self.learning_rate = learning_rate
        self.x_max = x_max
        self.alpha = alpha
        self.batch_size = batch_size
        self.symmetric = symmetric
        self.seed = seed
        self.vocab: Optional[VocabCache] = None
        self.vectors: Optional[np.ndarray] = None
        self.tokenizer_factory = DefaultTokenizerFactory()
        self.loss_history: List[float] = []

    def fit(self, corpus: Sequence):
        token_lists = []
        for s in corpus:
            token_lists.append(self.tokenizer_factory.create(s).get_tokens()
                               if isinstance(s, str) else list(s))
        self.vocab = VocabCache.build_from_sentences(token_lists, self.min_word_frequency)
        co = CoOccurrences(self.vocab, self.window, self.symmetric)
        co.fit(token_lists)
        ii, jj, xx = co.arrays()
        if len(ii) == 0:
            raise ValueError("empty co-occurrence matrix")
        logx = np.log(xx)
        fx = np.minimum(1.0, (xx / self.x_max) ** self.alpha).astype(np.float32)

        rng = np.random.default_rng(self.seed)
        V, d = self.vocab.num_words(), self.layer_size
        init = lambda shape: jnp.asarray(((rng.random(shape) - 0.5) / d).astype(np.float32))
        w, wc = init((V, d)), init((V, d))
        b, bc = jnp.zeros(V, jnp.float32), jnp.zeros(V, jnp.float32)
        hw, hwc = jnp.full((V, d), 1e-8), jnp.full((V, d), 1e-8)
        hb, hbc = jnp.full(V, 1e-8), jnp.full(V, 1e-8)
        lr = jnp.float32(self.learning_rate)
        B = self.batch_size
        epoch_losses = []  # device scalars; ONE fetch after the loop — a
        for _ in range(self.epochs):  # per-batch float(loss) would drain
            order = rng.permutation(len(ii))  # the dispatch queue every
            batch_losses = []                 # step (engine.py note)
            for s in range(0, len(order), B):
                sel = order[s:s + B]
                w, wc, b, bc, hw, hwc, hb, hbc, loss = _glove_step(
                    w, wc, b, bc, hw, hwc, hb, hbc,
                    jnp.asarray(ii[sel]), jnp.asarray(jj[sel]),
                    jnp.asarray(logx[sel]), jnp.asarray(fx[sel]), lr)
                batch_losses.append(loss)
            epoch_losses.append(jnp.mean(jnp.stack(batch_losses)))
        if epoch_losses:  # epochs=0: vocab/co-occurrence build only
            self.loss_history.extend(
                np.asarray(jnp.stack(epoch_losses)).tolist())
        # final vectors = w + wc (GloVe convention; the reference sums)
        self.vectors = np.asarray(w) + np.asarray(wc)

    def word_vectors(self) -> WordVectors:
        return WordVectors(self.vocab, self.vectors)

    def similarity(self, a: str, b: str) -> float:
        return self.word_vectors().similarity(a, b)
