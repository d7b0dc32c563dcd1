"""ctypes bindings for the native IO kernels, with pure-Python fallback.

The shared library is compiled on first use (g++, baked into the image)
and cached next to the source under a name keyed on the CONTENT of
``io_kernels.cpp`` — what loads is always built from the source that is
there, whatever copied or checked out the tree and whenever.
Environments without a toolchain fall back to NumPy implementations —
the helper-SPI "graceful CPU fallback" doctrine of the reference's
accelerator seam (``ConvolutionLayer.java:60-67``) applied to the data
plane; :func:`data_plane` says which one is in use.
"""

from __future__ import annotations

import ctypes
import hashlib
import logging
import os
import subprocess
import tempfile
import threading
from typing import Optional

import numpy as np

logger = logging.getLogger("deeplearning4j_tpu")

_HERE = os.path.dirname(os.path.abspath(__file__))
_SRC = os.path.join(_HERE, "io_kernels.cpp")
_lock = threading.Lock()
_lib: Optional[ctypes.CDLL] = None
_tried = False

_IDX_DTYPES = {0x08: np.uint8, 0x09: np.int8, 0x0B: np.dtype(">i2"),
               0x0C: np.dtype(">i4"), 0x0D: np.dtype(">f4"), 0x0E: np.dtype(">f8")}


def _lib_path() -> str:
    with open(_SRC, "rb") as f:
        digest = hashlib.sha256(f.read()).hexdigest()[:16]
    return os.path.join(_HERE, f"libdl4jtpu_io.{digest}.so")


def _build(lib_path: str) -> bool:
    # build beside the target and rename: a concurrent process never
    # loads a half-written library
    fd, tmp = tempfile.mkstemp(suffix=".so", dir=_HERE)
    os.close(fd)
    cmd = ["g++", "-O3", "-shared", "-fPIC", "-pthread", "-std=c++17",
           "-o", tmp, _SRC]
    try:
        try:
            proc = subprocess.run(cmd, capture_output=True, text=True,
                                  timeout=240)
        except (FileNotFoundError, subprocess.TimeoutExpired) as e:
            logger.info("native io build unavailable (%s); using python "
                        "fallback", e)
            return False
        if proc.returncode != 0:
            logger.warning("native io build failed, using python "
                           "fallback:\n%s", proc.stderr[-1000:])
            return False
        os.replace(tmp, lib_path)
        return True
    finally:
        if os.path.exists(tmp):
            os.remove(tmp)


def get_lib() -> Optional[ctypes.CDLL]:
    """Load (building if needed) the native library; None → fallback."""
    global _lib, _tried
    with _lock:
        if _lib is not None or _tried:
            return _lib
        _tried = True
        lib_path = _lib_path()
        if not os.path.exists(lib_path) and not _build(lib_path):
            return None
        try:
            lib = ctypes.CDLL(lib_path)
        except OSError as e:
            logger.warning("native io load failed (%s); python fallback", e)
            return None
        lib.dl4j_csv_shape.argtypes = [ctypes.c_char_p, ctypes.c_int64,
                                       ctypes.POINTER(ctypes.c_int64),
                                       ctypes.POINTER(ctypes.c_int64)]
        lib.dl4j_csv_parse.argtypes = [ctypes.c_char_p, ctypes.c_int64,
                                       ctypes.POINTER(ctypes.c_float),
                                       ctypes.c_int64, ctypes.c_int64, ctypes.c_int]
        lib.dl4j_csv_parse.restype = ctypes.c_int64
        lib.dl4j_idx_header.argtypes = [ctypes.c_char_p,
                                        ctypes.POINTER(ctypes.c_int),
                                        ctypes.POINTER(ctypes.c_int),
                                        ctypes.POINTER(ctypes.c_int64)]
        lib.dl4j_idx_read.argtypes = [ctypes.c_char_p,
                                      ctypes.POINTER(ctypes.c_ubyte), ctypes.c_int64]
        _lib = lib
        return _lib


def data_plane() -> str:
    """``"native"`` when the C++ kernels loaded, ``"numpy"`` when the
    fallbacks are serving."""
    return "native" if get_lib() is not None else "numpy"


def csv_read_floats(path: str, skip_rows: int = 0, threads: int = 0,
                    strict: bool = False) -> np.ndarray:
    """Parse a numeric CSV file to a float32 [rows, cols] array via the
    multithreaded native parser; NumPy fallback when unavailable.

    Semantics (identical in both paths): ``skip_rows`` counts physical
    lines, whitespace-only lines are dropped, cells may be quoted.
    Non-numeric cells parse as 0.0 — unless ``strict=True``, which
    raises so mis-pointed files fail loudly instead of training on
    silently-zeroed features."""
    lib = get_lib()
    if lib is None:
        return _csv_read_floats_py(path, skip_rows, strict)
    rows = ctypes.c_int64()
    cols = ctypes.c_int64()
    rc = lib.dl4j_csv_shape(path.encode(), skip_rows,
                            ctypes.byref(rows), ctypes.byref(cols))
    if rc != 0:
        raise IOError(f"csv shape scan failed rc={rc}: {path}")
    out = np.empty((rows.value, cols.value), np.float32)
    bad = lib.dl4j_csv_parse(
        path.encode(), skip_rows,
        out.ctypes.data_as(ctypes.POINTER(ctypes.c_float)),
        rows.value, cols.value, threads)
    if bad < 0:
        raise IOError(f"csv parse failed rc={bad}: {path}")
    if strict and bad > 0:
        raise ValueError(f"{bad} non-numeric cell(s) in {path}; "
                         f"use strict=False to zero-fill them")
    return out


def _csv_read_floats_py(path: str, skip_rows: int,
                        strict: bool = False) -> np.ndarray:
    rows = []
    bad = 0
    with open(path) as f:
        for i, line in enumerate(f):
            if i < skip_rows or not line.strip():
                continue
            vals = []
            for cell in line.rstrip("\n").split(","):
                cell = cell.strip().strip('"')
                try:
                    vals.append(float(cell))
                except ValueError:
                    vals.append(0.0)
                    bad += 1
            rows.append(vals)
    if strict and bad > 0:
        raise ValueError(f"{bad} non-numeric cell(s) in {path}; "
                         f"use strict=False to zero-fill them")
    return np.asarray(rows, np.float32)


def idx_read(path: str) -> Optional[np.ndarray]:
    """Read an (uncompressed) IDX file natively; None → caller falls
    back to its own parser (gz files are not handled here)."""
    lib = get_lib()
    if lib is None or path.endswith(".gz"):
        return None
    dtype = ctypes.c_int()
    ndim = ctypes.c_int()
    dims = (ctypes.c_int64 * 8)()
    rc = lib.dl4j_idx_header(path.encode(), ctypes.byref(dtype),
                             ctypes.byref(ndim), dims)
    if rc != 0:
        return None
    np_dtype = _IDX_DTYPES.get(dtype.value)
    if np_dtype is None:
        return None
    shape = tuple(dims[i] for i in range(ndim.value))
    nbytes = int(np.prod(shape)) * np.dtype(np_dtype).itemsize
    out = np.empty(nbytes, np.uint8)
    rc = lib.dl4j_idx_read(path.encode(),
                           out.ctypes.data_as(ctypes.POINTER(ctypes.c_ubyte)),
                           nbytes)
    if rc != 0:
        return None
    arr = out.view(np_dtype).reshape(shape)
    # normalize big-endian multi-byte types to native order
    if np.dtype(np_dtype).byteorder == ">":
        arr = arr.astype(np.dtype(np_dtype).newbyteorder("="))
    return arr
