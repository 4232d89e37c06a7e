"""Native (C) input-pipeline kernels + background prefetch.

The reference leans on torch's C++ DataLoader workers for its input pipeline
(num_workers in /root/reference/train.py:96-107); the TPU build's equivalent
is this module: a small C kernel — compiled on demand with the system gcc,
loaded via ctypes (no pybind11 in this environment) — that fuses the CIFAR
augmentation (zero-pad + random crop + horizontal flip) with uint8->f32
normalization in ONE pass over the batch, OpenMP-parallel across images,
plus a background-thread prefetcher that overlaps host batch preparation
with device steps.

Per-image Python loops cost milliseconds per batch — an order of magnitude
more than the ~0.25 ms train step they feed. The fused C kernel reads the
source image directly (implicit zero padding, flip folded into the column
index) and writes normalized floats: no padded intermediate, no second
normalization pass. A vectorized-numpy fallback keeps every machine working
when no C toolchain is present; both are tested against the same oracle.
"""

import ctypes
import itertools
import os
import queue
import subprocess
import tempfile
import threading
from typing import Iterator, Optional, Tuple

import numpy as np

from dgc_tpu.telemetry import trace as _trace

__all__ = ["crop_flip_normalize", "native_available", "Prefetcher",
           "stage_ahead"]

_C_SOURCE = r"""
#include <stdint.h>

void crop_flip_normalize(
    const uint8_t* in, float* out,
    const int32_t* ys, const int32_t* xs, const uint8_t* flips,
    int64_t n, int64_t h, int64_t w, int64_t pad,
    const float* scale, const float* bias)
{
    #pragma omp parallel for schedule(static)
    for (int64_t i = 0; i < n; ++i) {
        const uint8_t* src = in + i * h * w * 3;
        float* dst = out + i * h * w * 3;
        const int64_t oy = (int64_t)ys[i] - pad;
        const int64_t ox = (int64_t)xs[i] - pad;
        const int flip = flips[i];
        for (int64_t y = 0; y < h; ++y) {
            const int64_t sy = y + oy;
            const int in_y = (sy >= 0 && sy < h);
            for (int64_t x = 0; x < w; ++x) {
                const int64_t xcol = flip ? (w - 1 - x) : x;
                const int64_t sx = xcol + ox;
                float* o = dst + (y * w + x) * 3;
                if (in_y && sx >= 0 && sx < w) {
                    const uint8_t* s = src + (sy * w + sx) * 3;
                    o[0] = s[0] * scale[0] + bias[0];
                    o[1] = s[1] * scale[1] + bias[1];
                    o[2] = s[2] * scale[2] + bias[2];
                } else {
                    o[0] = bias[0];
                    o[1] = bias[1];
                    o[2] = bias[2];
                }
            }
        }
    }
}
"""

_lib = None
_tried = False


def _build() -> Optional[ctypes.CDLL]:
    """Compile the kernel into a cached .so; None when no toolchain.

    The cache name is keyed on the source hash (stale binaries never load
    after a kernel edit) and the uid (predictable world-writable /tmp
    path); the build lands atomically via rename so a killed compile or a
    concurrent builder can never leave a truncated library behind. ANY
    failure degrades to the numpy fallback."""
    import hashlib
    import stat
    tag = hashlib.sha256(_C_SOURCE.encode()).hexdigest()[:16]
    try:
        cache = os.path.join(tempfile.gettempdir(),
                             f"dgc_tpu_native_{os.getuid()}")
        os.makedirs(cache, mode=0o700, exist_ok=True)
        # never load a library from a directory anyone else could have
        # pre-planted or can write to at this predictable path
        st = os.stat(cache)
        if st.st_uid != os.getuid() or (
                st.st_mode & (stat.S_IWOTH | stat.S_IWGRP)):
            return None
        so_path = os.path.join(cache, f"libdgcdata_{tag}.so")
        if not os.path.exists(so_path):
            c_path = os.path.join(cache, f"dgcdata_{tag}.c")
            with open(c_path, "w") as f:
                f.write(_C_SOURCE)
            tmp_so = so_path + f".tmp{os.getpid()}"
            subprocess.run(
                ["gcc", "-O3", "-fopenmp", "-shared", "-fPIC",
                 c_path, "-o", tmp_so],
                check=True, capture_output=True, timeout=60)
            # replace, not rename: a racing builder (two loaders on one
            # host) or a crashed-then-retried build must not wedge on an
            # existing target
            os.replace(tmp_so, so_path)
        lib = ctypes.CDLL(so_path)
    except (OSError, subprocess.SubprocessError):
        return None
    lib.crop_flip_normalize.argtypes = [
        ctypes.POINTER(ctypes.c_uint8), ctypes.POINTER(ctypes.c_float),
        ctypes.POINTER(ctypes.c_int32), ctypes.POINTER(ctypes.c_int32),
        ctypes.POINTER(ctypes.c_uint8),
        ctypes.c_int64, ctypes.c_int64, ctypes.c_int64, ctypes.c_int64,
        ctypes.POINTER(ctypes.c_float), ctypes.POINTER(ctypes.c_float)]
    lib.crop_flip_normalize.restype = None
    return lib


def native_available() -> bool:
    global _lib, _tried
    if not _tried:
        _tried = True
        _lib = _build()
    return _lib is not None


def _numpy_path(images_u8, ys, xs, flips, pad, scale, bias):
    """Vectorized fallback: one fancy-indexed gather, no per-image loop."""
    n, h, w, c = images_u8.shape
    padded = np.zeros((n, h + 2 * pad, w + 2 * pad, c), images_u8.dtype)
    padded[:, pad:pad + h, pad:pad + w] = images_u8
    iy = ys[:, None] + np.arange(h)[None, :]
    ix = xs[:, None] + np.arange(w)[None, :]
    out = padded[np.arange(n)[:, None, None], iy[:, :, None],
                 ix[:, None, :]]
    fl = flips.astype(bool)
    out[fl] = out[fl][:, :, ::-1]
    return out.astype(np.float32) * scale + bias


def crop_flip_normalize(images_u8: np.ndarray, ys: np.ndarray,
                        xs: np.ndarray, flips: np.ndarray, pad: int,
                        mean: np.ndarray, std: np.ndarray) -> np.ndarray:
    """Fused augment+normalize: crop offsets ``(ys, xs)`` index the
    zero-padded image, ``flips`` mirrors horizontally, output is
    ``(u8/255 - mean)/std`` f32 NHWC."""
    scale = (1.0 / (255.0 * std)).astype(np.float32)
    bias = (-mean / std).astype(np.float32)
    if not native_available():
        return _numpy_path(images_u8, ys, xs, flips, pad, scale, bias)
    n, h, w, c = images_u8.shape
    assert c == 3
    images_u8 = np.ascontiguousarray(images_u8)
    out = np.empty((n, h, w, 3), np.float32)
    ys32 = np.ascontiguousarray(ys, np.int32)
    xs32 = np.ascontiguousarray(xs, np.int32)
    fl8 = np.ascontiguousarray(flips, np.uint8)

    def p(a, t):
        return a.ctypes.data_as(ctypes.POINTER(t))

    _lib.crop_flip_normalize(
        p(images_u8, ctypes.c_uint8), p(out, ctypes.c_float),
        p(ys32, ctypes.c_int32), p(xs32, ctypes.c_int32),
        p(fl8, ctypes.c_uint8),
        n, h, w, pad, p(scale, ctypes.c_float), p(bias, ctypes.c_float))
    return out


def _nbytes(item) -> int:
    parts = item if isinstance(item, (tuple, list)) else (item,)
    return sum(getattr(a, "nbytes", 0) for a in parts)


def stage_ahead(iterator, stage, depth: int = 1):
    """Keep ``depth`` staged items in flight ahead of the consumer.

    ``stage`` is called on each item as soon as it is pulled (e.g. an async
    ``device_put``); the consumer receives items in order, so while it works
    on item k the transfers for k+1..k+depth are already issued — host->
    device copies overlap device compute instead of serializing with it."""
    from collections import deque
    pending = deque()
    for item in iterator:
        with _trace.span("input.stage", seq=getattr(item, "seq", None),
                         bytes=_nbytes(item)):
            pending.append(stage(item))
        if len(pending) > depth:
            yield pending.popleft()
    while pending:
        yield pending.popleft()


class _Batch(tuple):
    """A batch as ``Prefetcher`` hands it on: the split's tuple, plus the
    ``seq`` its producer gave it (``stage_ahead`` reads it, so one batch's
    spans link from ``get_batch`` through the queue to staging)."""
    seq = None


#: batch ids, unique in the process (a run makes a ``Prefetcher`` per epoch)
_SEQ = itertools.count()


class Prefetcher:
    """Background-thread batch preparation (the DataLoader-worker role):
    the host assembles/augments batch k+1..k+depth while the device runs
    step k."""

    def __init__(self, split, index_iter: Iterator[np.ndarray],
                 depth: int = 2):
        self._q: "queue.Queue" = queue.Queue(maxsize=depth)
        self._stop = threading.Event()
        self._thread = threading.Thread(
            target=self._fill, args=(split, index_iter), daemon=True)
        self._thread.start()

    def _put(self, item) -> bool:
        while not self._stop.is_set():
            try:
                self._q.put(item, timeout=0.1)
                return True
            except queue.Full:
                continue
        return False

    def _fill(self, split, index_iter):
        try:
            for idx in index_iter:
                seq = next(_SEQ)
                with _trace.carry(seq=seq):
                    batch = _Batch(split.get_batch(idx))
                batch.seq = seq
                if self._stop.is_set() or not self._put(("item", batch)):
                    return
        except BaseException as e:  # surface worker errors to the consumer
            self._put(("error", e))
            return
        self._put(("end", None))

    def close(self):
        """Release the worker thread and its buffered batches; safe to call
        any time (consumers abandoning iteration early MUST call this or
        the bounded queue pins the thread and several batches forever)."""
        self._stop.set()
        try:
            while True:
                self._q.get_nowait()
        except queue.Empty:
            pass
        self._thread.join(timeout=5)

    def __iter__(self) -> Iterator[Tuple[np.ndarray, np.ndarray]]:
        while True:
            with _trace.span("input.queue_wait") as wait:
                # what the consumer finds: 0 means it outran the producer
                _trace.count("input.queue_depth", self._q.qsize())
                kind, payload = self._q.get()
                wait.set(seq=getattr(payload, "seq", None))
            if kind == "error":
                raise payload
            if kind == "end":
                return
            yield payload
