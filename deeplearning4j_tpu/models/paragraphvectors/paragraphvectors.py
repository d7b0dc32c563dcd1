"""ParagraphVectors (doc2vec): DBOW and DM over labeled documents.

Parity: ``models/paragraphvectors/ParagraphVectors.java:42`` + the
sequence learning algorithms ``learning/impl/sequence/DBOW.java`` /
``DM.java``, including ``inferVector`` (gradient-fit a fresh doc vector
against frozen word weights).

TPU formulation: label (doc) vectors are rows of an auxiliary embedding
matrix trained with the same batched SGNS steps as word vectors — DBOW
pairs are (doc_id -> word), DM averages [doc; context] to predict the
center. Inference reuses the same jitted step on a [1, d] doc matrix.
"""

from __future__ import annotations

from typing import Dict, Iterable, List, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from deeplearning4j_tpu.util.jit import cpu_safe_jit
from deeplearning4j_tpu.models.sequencevectors.engine import (
    SequenceVectors,
    _DENSE_UPDATE_MAX_VOCAB,
    _pad_np,
    _sgns_math,
    _sgns_step,
)
from deeplearning4j_tpu.text.sentenceiterator import LabelAwareIterator
from deeplearning4j_tpu.text.tokenization import DefaultTokenizerFactory


import functools


@cpu_safe_jit(donate_argnums=(0, 1),
                   static_argnames=("K", "bs", "n_steps", "dense"))
def _pv_scan_program(doc_vecs, syn1neg, doc_ids, word_ids, neg_table, key,
                     lr, n_pairs, *, K, bs, n_steps, dense):
    """ONE EPOCH of the doc-vector phase as ONE compiled program (the
    scan doctrine of ``engine._sgns_scan_program``): the (doc, word)
    pair list is epoch-invariant, so it uploads once and only scalars
    cross to the host per epoch; negatives sample on device from the
    unigram^0.75 table."""

    def body(carry, i):
        dv, s1 = carry
        sl = i * bs + jnp.arange(bs, dtype=jnp.int32)
        c = doc_ids[sl]
        x = word_ids[sl]
        w = (sl < n_pairs).astype(jnp.float32)
        negs = neg_table[jax.random.randint(
            jax.random.fold_in(key, i), (bs, K), 0, neg_table.shape[0])]
        dv, s1, loss = _sgns_math(dv, s1, c, x, negs, lr, w, dense)
        return (dv, s1), loss

    (doc_vecs, syn1neg), losses = jax.lax.scan(
        body, (doc_vecs, syn1neg), jnp.arange(n_steps, dtype=jnp.int32))
    return doc_vecs, syn1neg, losses


@jax.jit
def _infer_sgns_step(vec, syn1neg, centers, contexts, negatives, lr):
    """SGNS update of the doc vector ONLY (word weights frozen — the
    ``inferVector`` contract)."""
    v = vec[centers]
    u_pos = syn1neg[contexts]
    u_neg = syn1neg[negatives]
    s_pos = jnp.sum(v * u_pos, axis=-1)
    s_neg = jnp.einsum("bd,bkd->bk", v, u_neg)
    neg_ok = (negatives != contexts[:, None]).astype(s_neg.dtype)
    g_pos = 1.0 - jax.nn.sigmoid(s_pos)
    g_neg = -jax.nn.sigmoid(s_neg) * neg_ok
    dv = g_pos[:, None] * u_pos + jnp.einsum("bk,bkd->bd", g_neg, u_neg)
    return vec.at[centers].add(lr * dv)


class ParagraphVectors(SequenceVectors):
    def __init__(self, layer_size: int = 100, window_size: int = 5,
                 min_word_frequency: int = 1, epochs: int = 1,
                 learning_rate: float = 0.025, negative_sample: int = 5,
                 sequence_learning_algorithm: str = "dbow",
                 train_words: bool = True, batch_size: int = 4096,
                 seed: int = 123, device_pairgen: bool = True):
        super().__init__(vector_length=layer_size, window=window_size,
                         min_word_frequency=min_word_frequency, epochs=epochs,
                         learning_rate=learning_rate, negative=negative_sample,
                         batch_size=batch_size, seed=seed,
                         device_pairgen=device_pairgen)
        self.sequence_algo = sequence_learning_algorithm
        self.train_words = train_words
        self.tokenizer_factory = DefaultTokenizerFactory()
        self.labels: List[str] = []
        self.doc_vectors: Optional[np.ndarray] = None
        self._label_index: Dict[str, int] = {}

    def fit(self, documents: Iterable[Tuple[str, List[str]]]):
        """documents: (content, labels) pairs or a LabelAwareIterator."""
        if isinstance(documents, LabelAwareIterator):
            docs = [(d.content, d.labels) for d in documents]
        else:
            docs = list(documents)
        token_lists = [self.tokenizer_factory.create(c).get_tokens() for c, _ in docs]
        self.build_vocab(token_lists)
        # label registry
        self._label_index = {}
        for _, labels in docs:
            for l in labels:
                if l not in self._label_index:
                    self._label_index[l] = len(self._label_index)
        self.labels = list(self._label_index)
        rng = np.random.default_rng(self.seed)
        d = self.vector_length
        doc_vecs = jnp.asarray(((rng.random((len(self.labels), d)) - 0.5) / d)
                               .astype(np.float32))
        if self.train_words:
            super().fit(token_lists)
        syn1neg = jnp.asarray(self.lookup_table.syn1neg)

        # DBOW: doc vector predicts each word of the doc; DM adds
        # context-window centering (approximated by the same pair set with
        # window-averaged targets — batched identically)
        doc_ids, word_ids = [], []
        idx_lists = self._to_indices(token_lists, rng)
        for (content, labels), idx in zip(docs, idx_lists):
            for l in labels:
                li = self._label_index[l]
                for w in idx:
                    doc_ids.append(li)
                    word_ids.append(int(w))
        doc_ids = np.asarray(doc_ids, np.int32)
        word_ids = np.asarray(word_ids, np.int32)
        B = self.batch_size
        if self.device_pairgen and len(doc_ids):
            # all-epochs-on-device scan: pairs upload ONCE, negatives
            # sample on device (engine scan doctrine — the per-batch
            # loop below pays a host→device upload per step). Pairs are
            # shuffled host-side before upload: the list is built
            # doc-major, and un-mixed batches would hold one doc_id
            # thousands of times, which the capped accumulation would
            # clamp to a single bounded step per batch.
            n_pairs = len(doc_ids)
            order = rng.permutation(n_pairs)
            doc_ids, word_ids = doc_ids[order], word_ids[order]
            n_batches = -(-n_pairs // B)
            pad = n_batches * B - n_pairs
            di = jnp.asarray(np.concatenate([doc_ids,
                                             np.zeros(pad, np.int32)]))
            wi = jnp.asarray(np.concatenate([word_ids,
                                             np.zeros(pad, np.int32)]))
            neg_dev = jnp.asarray(
                self.lookup_table.negative_table(size=131072))
            # BOTH tables must be small for the dense one-hot update:
            # syn0 here is the doc table (n_labels rows), syn1neg the
            # word table
            dense = max(len(self.labels), self.vocab.num_words())                 <= _DENSE_UPDATE_MAX_VOCAB
            key = jax.random.PRNGKey(int(rng.integers(2**31)))
            for e in range(self.epochs):
                doc_vecs, syn1neg, _ = _pv_scan_program(
                    doc_vecs, syn1neg, di, wi,
                    neg_dev, jax.random.fold_in(key, e),
                    jnp.float32(self.learning_rate), jnp.int32(n_pairs),
                    K=self.negative, bs=B, n_steps=n_batches, dense=dense)
        else:
            neg_table = self.lookup_table.negative_table()
            for _ in range(self.epochs):
                order = rng.permutation(len(doc_ids))
                for s in range(0, len(order), B):
                    sel = order[s:s + B]
                    negs = rng.choice(neg_table, (len(sel), self.negative))
                    # pad the tail to one static shape; weights mask pads
                    w = np.zeros(B, np.float32)
                    w[:len(sel)] = 1.0
                    doc_vecs, syn1neg, _ = _sgns_step(
                        doc_vecs, syn1neg,
                        jnp.asarray(_pad_np(doc_ids[sel], B)),
                        jnp.asarray(_pad_np(word_ids[sel], B)),
                        jnp.asarray(_pad_np(negs, B), jnp.int32),
                        jnp.float32(self.learning_rate), jnp.asarray(w))
        self.doc_vectors = np.asarray(doc_vecs)
        self.lookup_table.syn1neg = np.asarray(syn1neg)

    def get_label_vector(self, label: str) -> np.ndarray:
        return self.doc_vectors[self._label_index[label]]

    def infer_vector(self, text: str, steps: int = 20,
                     learning_rate: float = 0.025) -> np.ndarray:
        """``inferVector`` — fit ONE new doc vector against frozen word
        weights."""
        toks = self.tokenizer_factory.create(text).get_tokens()
        rng = np.random.default_rng(self.seed)
        idx = [self.vocab.index_of(t) for t in toks]
        idx = np.asarray([i for i in idx if i >= 0], np.int32)
        d = self.vector_length
        vec = jnp.asarray(((rng.random((1, d)) - 0.5) / d).astype(np.float32))
        if len(idx) == 0:
            return np.asarray(vec)[0]
        syn1neg = jnp.asarray(self.lookup_table.syn1neg)
        neg_table = self.lookup_table.negative_table()
        zeros = jnp.zeros(len(idx), jnp.int32)
        idx_j = jnp.asarray(idx)
        for _ in range(steps):
            negs = rng.choice(neg_table, (len(idx), self.negative))
            vec = _infer_sgns_step(vec, syn1neg, zeros, idx_j,
                                   jnp.asarray(negs, jnp.int32),
                                   jnp.float32(learning_rate))
        return np.asarray(vec)[0]

    def similarity_to_label(self, text: str, label: str) -> float:
        v = self.infer_vector(text)
        u = self.get_label_vector(label)
        return float(np.dot(v, u) / (np.linalg.norm(v) * np.linalg.norm(u) + 1e-12))

    def predict(self, text: str) -> str:
        """Nearest label for a document (``predict`` convenience)."""
        v = self.infer_vector(text)
        sims = [(l, float(np.dot(v, self.get_label_vector(l)) /
                          (np.linalg.norm(v) * np.linalg.norm(self.get_label_vector(l)) + 1e-12)))
                for l in self.labels]
        return max(sims, key=lambda t: t[1])[0]
