"""horovod_tpu.native — C++ host runtime (ctypes-bound).

Reference parity (SURVEY.md §2.1): the pieces of the reference's native
core that still belong on the host under SPMD — thread pool
(thread_pool.cc), timeline writer thread (timeline.cc), and the
prefetch/memcpy machinery (the fusion buffer's MEMCPY_IN role) applied to
the TPU's real host bottleneck: the input pipeline. See
``src/hvd_runtime.cc``.

If no C++ compiler is present at all, :func:`available` is False and
:class:`RecordPipeline` uses the pure-numpy path with identical semantics
(the tests run both). A compiler that is present and fails to build the
library is an error at load (``build.py``), not a quiet downgrade.
"""

from __future__ import annotations

import ctypes
import os
import threading
from typing import List, Optional, Sequence, Tuple

import numpy as np

_lib = None
_lib_lock = threading.Lock()
_tried = False


def _load() -> Optional[ctypes.CDLL]:
    global _lib, _tried
    with _lib_lock:
        if _lib is not None or _tried:
            return _lib
        if os.environ.get("HOROVOD_DISABLE_NATIVE", "").lower() in (
                "1", "true", "yes", "on"):
            _tried = True
            return None
        from .build import build
        path = build()  # raises when a compiler is present and fails
        _tried = True
        if path is None:
            return None
        lib = ctypes.CDLL(path)
        lib.hvd_runtime_abi_version.restype = ctypes.c_int
        if lib.hvd_runtime_abi_version() != 3:
            return None
        # signatures
        lib.hvd_pool_create.restype = ctypes.c_void_p
        lib.hvd_pool_create.argtypes = [ctypes.c_int]
        lib.hvd_pool_counter_add.argtypes = [
            ctypes.c_void_p, ctypes.POINTER(ctypes.c_longlong),
            ctypes.c_longlong]
        lib.hvd_pool_destroy.argtypes = [ctypes.c_void_p]
        lib.hvd_timeline_open.restype = ctypes.c_void_p
        lib.hvd_timeline_open.argtypes = [ctypes.c_char_p]
        lib.hvd_timeline_event.argtypes = [
            ctypes.c_void_p, ctypes.c_char_p, ctypes.c_char_p,
            ctypes.c_char, ctypes.c_int, ctypes.c_int]
        lib.hvd_timeline_close.argtypes = [ctypes.c_void_p]
        lib.hvd_pipeline_create.restype = ctypes.c_void_p
        lib.hvd_pipeline_create.argtypes = [
            ctypes.POINTER(ctypes.c_char_p), ctypes.c_int,
            ctypes.c_longlong, ctypes.c_longlong, ctypes.c_int,
            ctypes.c_int, ctypes.c_ulonglong, ctypes.c_int, ctypes.c_int]
        lib.hvd_pipeline_next.restype = ctypes.c_longlong
        lib.hvd_pipeline_next.argtypes = [ctypes.c_void_p,
                                          ctypes.POINTER(ctypes.c_uint8)]
        lib.hvd_pipeline_error.restype = ctypes.c_char_p
        lib.hvd_pipeline_error.argtypes = [ctypes.c_void_p]
        lib.hvd_pipeline_destroy.argtypes = [ctypes.c_void_p]
        lib.hvd_parallel_gather.argtypes = [
            ctypes.POINTER(ctypes.c_uint8),
            ctypes.POINTER(ctypes.c_longlong), ctypes.c_longlong,
            ctypes.c_longlong, ctypes.POINTER(ctypes.c_uint8),
            ctypes.c_int]
        _lib = lib
        return _lib


def available() -> bool:
    """True when the native runtime built & loaded."""
    return _load() is not None


class NativeTimeline:
    """C++ writer-thread Chrome-trace timeline (drop-in for the hot path;
    same file format as tools.timeline.Timeline)."""

    def __init__(self, path: str):
        lib = _load()
        if lib is None:
            raise RuntimeError("native runtime unavailable")
        self._lib = lib
        d = os.path.dirname(path)
        if d:
            os.makedirs(d, exist_ok=True)
        self._h = lib.hvd_timeline_open(path.encode())
        if not self._h:
            raise OSError(f"cannot open timeline file {path}")

    def activity_start(self, name: str, activity: str, rank: int = 0) -> None:
        self._lib.hvd_timeline_event(self._h, activity.encode(),
                                     name.encode(), b"B", rank, 0)

    def activity_end(self, name: str, activity: str, rank: int = 0) -> None:
        self._lib.hvd_timeline_event(self._h, activity.encode(),
                                     name.encode(), b"E", rank, 0)

    def marker(self, name: str, rank: int = 0) -> None:
        self._lib.hvd_timeline_event(self._h, name.encode(), b"", b"i",
                                     rank, 0)

    def close(self) -> None:
        if self._h:
            self._lib.hvd_timeline_close(self._h)
            self._h = None


def _splitmix64_shuffle(items, seed: int) -> None:
    """Fisher-Yates with a SplitMix64 stream — bit-for-bit the shuffle in
    native/src/hvd_runtime.cc, so native and fallback pipelines yield the
    SAME batches for the same seed (the documented contract)."""
    mask = (1 << 64) - 1
    state = seed & mask

    def next_u64():
        nonlocal state
        state = (state + 0x9E3779B97F4A7C15) & mask
        z = state
        z = ((z ^ (z >> 30)) * 0xBF58476D1CE4E5B9) & mask
        z = ((z ^ (z >> 27)) * 0x94D049BB133111EB) & mask
        return z ^ (z >> 31)

    for i in range(len(items) - 1, 0, -1):
        j = next_u64() % (i + 1)
        items[i], items[j] = items[j], items[i]


class RecordPipeline:
    """Prefetching batch reader over fixed-size-record binary files.

    Yields ``np.ndarray`` batches of shape ``(batch_size, *record_shape)``.
    Native path: multithreaded C++ readers with a bounded prefetch queue.
    Fallback path: single-threaded numpy with identical ordering semantics
    (same seed ⇒ same batches).
    """

    def __init__(self, paths: Sequence[str], record_shape: Tuple[int, ...],
                 dtype, batch_size: int, shuffle: bool = True, seed: int = 0,
                 n_threads: int = 4, prefetch: int = 4,
                 drop_remainder: bool = True,
                 force_fallback: bool = False):
        self.paths = [os.path.abspath(p) for p in paths]
        self.record_shape = tuple(record_shape)
        self.dtype = np.dtype(dtype)
        self.batch_size = int(batch_size)
        self.shuffle = shuffle
        self.seed = seed
        self.record_bytes = int(np.prod(self.record_shape)) * \
            self.dtype.itemsize
        self.drop_remainder = drop_remainder
        self._n_threads = n_threads
        self._prefetch = prefetch
        self._lib = None if force_fallback else _load()
        self._h = None
        self._fallback_iter = None
        self._start()

    @property
    def native(self) -> bool:
        return self._lib is not None

    def _start(self) -> None:
        if self._lib is not None:
            arr = (ctypes.c_char_p * len(self.paths))(
                *[p.encode() for p in self.paths])
            self._h = self._lib.hvd_pipeline_create(
                arr, len(self.paths), self.record_bytes, self.batch_size,
                self._n_threads, self._prefetch, self.seed,
                1 if self.shuffle else 0, 1 if self.drop_remainder else 0)
            err = self._lib.hvd_pipeline_error(self._h).decode()
            if err:
                self.close()
                raise OSError(f"pipeline init failed: {err}")
        else:
            self._fallback_iter = self._fallback_batches()

    # -- fallback (identical semantics, pure numpy) --------------------------

    def _fallback_batches(self):
        index: List[Tuple[str, int]] = []
        for p in self.paths:
            sz = os.path.getsize(p)
            if sz % self.record_bytes:
                raise OSError(f"{p} size not a multiple of record_bytes")
            index.extend((p, i) for i in range(sz // self.record_bytes))
        if self.shuffle:
            _splitmix64_shuffle(index, self.seed)
        files = {p: open(p, "rb") for p in self.paths}
        try:
            n_full = len(index) // self.batch_size
            total = n_full if self.drop_remainder else \
                -(-len(index) // self.batch_size)
            for b in range(total):
                chunk = index[b * self.batch_size:(b + 1) * self.batch_size]
                out = np.empty((len(chunk), self.record_bytes), np.uint8)
                for j, (p, rec) in enumerate(chunk):
                    f = files[p]
                    f.seek(rec * self.record_bytes)
                    out[j] = np.frombuffer(f.read(self.record_bytes),
                                           np.uint8)
                yield out
        finally:
            for f in files.values():
                f.close()

    # -- iteration -----------------------------------------------------------

    def next_batch(self) -> Optional[np.ndarray]:
        """Next batch, or None at end of data."""
        if self._lib is not None:
            buf = np.empty(self.batch_size * self.record_bytes, np.uint8)
            n = self._lib.hvd_pipeline_next(
                self._h, buf.ctypes.data_as(ctypes.POINTER(ctypes.c_uint8)))
            if n < 0:
                raise OSError("pipeline error: "
                              + self._lib.hvd_pipeline_error(self._h)
                              .decode())
            if n == 0:
                return None
            raw = buf[: n * self.record_bytes]
        else:
            try:
                raw = next(self._fallback_iter)
            except StopIteration:
                return None
            n = raw.shape[0]
            raw = raw.reshape(-1)
        return raw.view(self.dtype).reshape((n,) + self.record_shape)

    def __iter__(self):
        while True:
            b = self.next_batch()
            if b is None:
                return
            yield b

    def close(self) -> None:
        if self._h:
            self._lib.hvd_pipeline_destroy(self._h)
            self._h = None

    def __enter__(self):
        return self

    def __exit__(self, *exc):
        self.close()

    def __del__(self):
        try:
            self.close()
        except Exception:
            pass


__all__ = ["NativeTimeline", "RecordPipeline", "available",
           "parallel_gather"]


def parallel_gather(src: np.ndarray, indices: np.ndarray,
                    out: Optional[np.ndarray] = None,
                    threads: int = 0) -> np.ndarray:
    """``src[indices]`` along axis 0 (1-D integer ``indices``) with native
    threaded memcpy.

    The batch-assembly hot op of the input pipeline (the reference's
    MEMCPY_IN role): ctypes releases the GIL, so gathering the next batch
    overlaps device compute inside :class:`~horovod_tpu.data.Prefetcher`.
    Falls back to numpy fancy indexing when the native lib is unavailable,
    ``src`` is not plain C-contiguous numeric data, or ``indices`` uses
    numpy-only semantics (negative values) — identical results either way.
    """
    indices = np.asarray(indices)
    if indices.ndim != 1:
        raise ValueError(f"indices must be 1-D, got shape {indices.shape}")
    if not np.issubdtype(indices.dtype, np.integer):
        raise TypeError(f"indices must be integers, got {indices.dtype}")
    idx = np.ascontiguousarray(indices, dtype=np.int64)
    n = src.shape[0] if src.ndim else 0
    if idx.size and (int(idx.max()) >= n or int(idx.min()) < -n):
        raise IndexError(
            f"index out of bounds for axis 0 with size {n}")
    lib = _load()
    use_native = (lib is not None and src.ndim >= 1
                  and src.flags.c_contiguous and not src.dtype.hasobject
                  and (not idx.size or int(idx.min()) >= 0))
    if not use_native:
        result = src[idx]
        if out is not None:
            out[...] = result
            return out
        return result
    row_bytes = src.dtype.itemsize
    for d in src.shape[1:]:
        row_bytes *= d
    want_shape = (idx.shape[0],) + src.shape[1:]
    if out is None:
        out = np.empty(want_shape, dtype=src.dtype)
    elif (out.shape != want_shape or out.dtype != src.dtype
          or not out.flags.c_contiguous):
        raise ValueError(
            f"out must be C-contiguous {want_shape} {src.dtype}, got "
            f"{out.shape} {out.dtype}")
    if threads <= 0:
        threads = min(8, os.cpu_count() or 1)
    lib.hvd_parallel_gather(
        src.ctypes.data_as(ctypes.POINTER(ctypes.c_uint8)),
        idx.ctypes.data_as(ctypes.POINTER(ctypes.c_longlong)),
        idx.shape[0], row_bytes,
        out.ctypes.data_as(ctypes.POINTER(ctypes.c_uint8)), threads)
    return out
