"""ctypes loader for the native host-side graph/data pipeline.

Compiles graph_builder.cpp on first use (the shared library next to the
source is a build output, never committed: a fresh checkout builds it,
and an edited source rebuilds it). Every entry point has a NumPy
fallback, so the framework works even without a toolchain — the native
path just keeps the TPU from waiting on host-side batch prep;
`native_available()` says which of the two is in effect.
"""
from __future__ import annotations

import ctypes
import os
import subprocess
import threading
from typing import Optional, Tuple

import numpy as np

_HERE = os.path.dirname(os.path.abspath(__file__))
_SRC = os.path.join(_HERE, 'graph_builder.cpp')
_LIB = os.path.join(_HERE, 'libse3graph.so')
_lock = threading.Lock()
_lib: Optional[ctypes.CDLL] = None
_tried = False


def _build() -> bool:
    cmd = ['g++', '-O3', '-shared', '-fPIC', _SRC, '-o', _LIB + '.tmp']
    try:
        subprocess.run(cmd, check=True, capture_output=True, timeout=120)
        os.replace(_LIB + '.tmp', _LIB)
        return True
    except (OSError, subprocess.SubprocessError):
        return False


def get_lib() -> Optional[ctypes.CDLL]:
    """The compiled library, building it if needed; None if unavailable."""
    global _lib, _tried
    with _lock:
        if _lib is not None or _tried:
            return _lib
        _tried = True
        try:
            needs_build = (not os.path.exists(_LIB)
                           or os.path.getmtime(_LIB) < os.path.getmtime(_SRC))
            if needs_build and not _build():
                return None
            lib = ctypes.CDLL(_LIB)
        except OSError:
            return None

        i8p = np.ctypeslib.ndpointer(np.uint8, flags='C_CONTIGUOUS')
        i32p = np.ctypeslib.ndpointer(np.int32, flags='C_CONTIGUOUS')
        f32p = np.ctypeslib.ndpointer(np.float32, flags='C_CONTIGUOUS')
        i32 = ctypes.c_int32

        lib.chain_adjacency.argtypes = [i32, i8p]
        lib.expand_adjacency.argtypes = [i32, i32, i8p, i32p]
        lib.knn_graph.argtypes = [f32p, i32, i32, i32, ctypes.c_float,
                                  i32p, f32p, i8p]
        lib.pad_token_batch.argtypes = [i32p, i32p, i32, i32, i32, i32p, i8p]
        lib.pad_coord_batch.argtypes = [f32p, i32p, i32, i32, f32p]
        _lib = lib
        return _lib


def native_available() -> bool:
    return get_lib() is not None


def chain_adjacency(n: int) -> np.ndarray:
    lib = get_lib()
    out = np.zeros((n, n), np.uint8)
    if lib is not None:
        lib.chain_adjacency(n, out)
    else:
        i = np.arange(n)
        out = (np.abs(i[:, None] - i[None, :]) == 1).astype(np.uint8)
    return out.astype(bool)


def expand_adjacency(adj: np.ndarray, num_degrees: int
                     ) -> Tuple[np.ndarray, np.ndarray]:
    """Expanded adjacency + hop-count ring labels (host-side counterpart of
    ops.neighbors.expand_adjacency)."""
    n = adj.shape[-1]
    lib = get_lib()
    if lib is not None and adj.ndim == 2:
        # explicit copy: the C function expands its argument in place, and
        # ascontiguousarray would alias an already-uint8 caller array
        a = np.array(adj, dtype=np.uint8, copy=True, order='C')
        labels = np.zeros((n, n), np.int32)
        lib.expand_adjacency(n, num_degrees, a, labels)
        return a.astype(bool), labels
    # numpy fallback (also the batched path)
    a = adj.astype(bool)
    labels = a.astype(np.int32)
    cur = a
    for d in range(2, num_degrees + 1):
        nxt = (cur.astype(np.float32) @ cur.astype(np.float32)) > 0
        labels = np.where(nxt & ~cur & (labels == 0), d, labels)
        cur = nxt
    return cur, labels


def knn_graph(coords: np.ndarray, k: int, radius: float = np.inf
              ) -> Tuple[np.ndarray, np.ndarray, np.ndarray]:
    """Exact batched kNN excluding self. coords [b, n, 3] float32 ->
    (idx [b,n,k] i32, dist [b,n,k] f32, mask [b,n,k] bool)."""
    coords = np.ascontiguousarray(coords, np.float32)
    b, n, _ = coords.shape
    k = int(min(k, n - 1)) if n > 1 else 0
    lib = get_lib()
    idx = np.zeros((b, n, k), np.int32)
    dist = np.zeros((b, n, k), np.float32)
    mask = np.zeros((b, n, k), np.uint8)
    if k == 0:
        return idx, dist, mask.astype(bool)
    if lib is not None:
        r = np.float32(radius if np.isfinite(radius) else np.finfo(np.float32).max)
        lib.knn_graph(coords, b, n, k, r, idx, dist, mask)
        return idx, dist, mask.astype(bool)
    # numpy fallback
    d2 = ((coords[:, :, None, :] - coords[:, None, :, :]) ** 2).sum(-1)
    ii = np.arange(n)
    d2[:, ii, ii] = np.inf
    idx = np.argsort(d2, axis=-1)[..., :k].astype(np.int32)
    dist = np.sqrt(np.take_along_axis(d2, idx, axis=-1)).astype(np.float32)
    return idx, dist, dist <= radius


def pad_to_bucket(token_seqs, coord_seqs, bucket_len: int,
                  batch_size: Optional[int] = None, pad_value: int = 0):
    """THE pad-to-bucket implementation, shared by training
    (`training/dataset.py:batches`) and serving
    (`inference/batching.py:MicroBatcher`) so the two sides cannot drift:
    a sequence padded for a serving bucket is bit-identical to the same
    sequence padded for the training bucket.

    Truncates each ragged sequence to `bucket_len`, pads to
    tokens [B, bucket_len] / coords [B, bucket_len, 3] / mask
    [B, bucket_len], and — when `batch_size` exceeds the number of
    sequences — appends all-padding rows (mask False everywhere) so the
    batch matches a fixed-shape compiled executable.
    """
    assert batch_size is None or len(token_seqs) <= batch_size, (
        f'{len(token_seqs)} sequences do not fit a batch of {batch_size}')
    toks = [np.asarray(t)[:bucket_len] for t in token_seqs]
    crds = [np.asarray(c, np.float32).reshape(-1, 3)[:bucket_len]
            for c in coord_seqs]
    tokens, coords, mask = pad_batch(toks, crds, max_len=bucket_len,
                                     pad_value=pad_value)
    if batch_size is not None and tokens.shape[0] < batch_size:
        extra = batch_size - tokens.shape[0]
        tokens = np.concatenate(
            [tokens, np.full((extra, bucket_len), pad_value, np.int32)])
        coords = np.concatenate(
            [coords, np.zeros((extra, bucket_len, 3), np.float32)])
        mask = np.concatenate(
            [mask, np.zeros((extra, bucket_len), bool)])
    return tokens, coords, mask


def pad_batch(token_seqs, coord_seqs, max_len: Optional[int] = None,
              pad_value: int = 0):
    """Ragged (tokens, coords) sequences -> padded [b, L] / [b, L, 3] batch
    with mask. Host-side equivalent of the reference's per-sequence
    truncation loop (denoise.py:57-68)."""
    b = len(token_seqs)
    lengths = np.asarray([len(t) for t in token_seqs], np.int32)
    L = int(max_len if max_len is not None else lengths.max())
    lib = get_lib()
    tokens_out = np.full((b, L), pad_value, np.int32)
    mask = np.zeros((b, L), np.uint8)
    coords_out = np.zeros((b, L, 3), np.float32)
    if lib is not None:
        flat_t = np.ascontiguousarray(
            np.concatenate([np.asarray(t, np.int32) for t in token_seqs]))
        flat_c = np.ascontiguousarray(
            np.concatenate([np.asarray(c, np.float32).reshape(-1, 3)
                            for c in coord_seqs]))
        lib.pad_token_batch(flat_t, lengths, b, L, pad_value, tokens_out,
                            mask)
        lib.pad_coord_batch(flat_c, lengths, b, L, coords_out)
    else:
        for i, (t, c) in enumerate(zip(token_seqs, coord_seqs)):
            Li = min(len(t), L)
            tokens_out[i, :Li] = np.asarray(t[:Li], np.int32)
            coords_out[i, :Li] = np.asarray(c[:Li], np.float32)
            mask[i, :Li] = 1
    return tokens_out, coords_out, mask.astype(bool)
