"""Native C++ runtime bindings (ctypes — the reference loads libmxnet.so the
same way, ``python/mxnet/base.py`` SURVEY.md §2.2).

Components (see ``cpp/src/``):
- dependency engine: host-side task scheduler with read/write variable
  ordering (reference ThreadedEngine, N1 — scoped to host work since
  XLA/PjRt owns device ordering);
- RecordIO native reader: engine-driven prefetching batch reader with pooled
  arenas (reference ImageRecordIOParser2 + pooled storage, N21/N3).

Builds on demand with g++ (``make -C cpp``); everything degrades to the
Python implementations when the library is unavailable.
"""
from __future__ import annotations

import ctypes
import os
import subprocess

_LIB = None
_TRIED = False

_HERE = os.path.dirname(os.path.abspath(__file__))
_LIB_PATH = os.path.join(_HERE, "libmxt_runtime.so")
_CPP_DIR = os.path.normpath(os.path.join(_HERE, "..", "..", "cpp"))


def _build():
    """``make -C cpp``; a failed build is reported with the compiler's
    message (the Python implementations then serve), never dropped."""
    try:
        subprocess.run(["make", "-C", _CPP_DIR], check=True,
                       capture_output=True, text=True, timeout=120)
        return True
    except (OSError, subprocess.SubprocessError) as e:
        import warnings
        warnings.warn(f"native runtime build failed, using the Python "
                      f"implementations: {e}: "
                      f"{(getattr(e, 'stderr', '') or '')[-2000:]}")
        return False


def get_lib():
    """Load (building if needed) the native runtime; None if unavailable."""
    global _LIB, _TRIED
    if _LIB is not None or _TRIED:
        return _LIB
    _TRIED = True
    if not os.path.exists(_LIB_PATH) and os.path.isdir(_CPP_DIR):
        _build()
    if not os.path.exists(_LIB_PATH):
        return None
    lib = ctypes.CDLL(_LIB_PATH)
    # stale .so from an older source tree: rebuild once, else load what works
    if not hasattr(lib, "mxt_augment_batch") and _build():
        lib = ctypes.CDLL(_LIB_PATH)
    lib.mxt_reader_open.restype = ctypes.c_void_p
    lib.mxt_reader_open.argtypes = [ctypes.c_char_p, ctypes.c_int,
                                    ctypes.c_int, ctypes.c_int]
    lib.mxt_reader_num_records.restype = ctypes.c_longlong
    lib.mxt_reader_num_records.argtypes = [ctypes.c_void_p]
    lib.mxt_reader_reset.argtypes = [ctypes.c_void_p, ctypes.c_int,
                                     ctypes.c_ulonglong, ctypes.c_int,
                                     ctypes.c_int]
    lib.mxt_reader_next.restype = ctypes.c_int
    lib.mxt_reader_next.argtypes = [
        ctypes.c_void_p, ctypes.POINTER(ctypes.POINTER(ctypes.c_ubyte)),
        ctypes.POINTER(ctypes.POINTER(ctypes.c_ulonglong))]
    lib.mxt_reader_close.argtypes = [ctypes.c_void_p]
    lib.mxt_reader_engine_ops.restype = ctypes.c_ulonglong
    lib.mxt_reader_engine_ops.argtypes = [ctypes.c_void_p]
    lib.mxt_engine_create.restype = ctypes.c_void_p
    lib.mxt_engine_create.argtypes = [ctypes.c_int]
    lib.mxt_engine_destroy.argtypes = [ctypes.c_void_p]
    lib.mxt_engine_new_var.restype = ctypes.c_void_p
    lib.mxt_engine_new_var.argtypes = [ctypes.c_void_p]
    lib.mxt_engine_push_axpy.argtypes = [
        ctypes.c_void_p, ctypes.POINTER(ctypes.c_double), ctypes.c_double,
        ctypes.POINTER(ctypes.c_void_p), ctypes.c_int,
        ctypes.POINTER(ctypes.c_void_p), ctypes.c_int, ctypes.c_int]
    lib.mxt_engine_push_scale.argtypes = lib.mxt_engine_push_axpy.argtypes
    lib.mxt_engine_wait_var.argtypes = [ctypes.c_void_p, ctypes.c_void_p]
    lib.mxt_engine_wait_all.argtypes = [ctypes.c_void_p]
    lib.mxt_engine_num_executed.restype = ctypes.c_ulonglong
    lib.mxt_engine_num_executed.argtypes = [ctypes.c_void_p]
    if hasattr(lib, "mxt_augment_batch"):
        lib.mxt_augment_batch.argtypes = [
            ctypes.POINTER(ctypes.POINTER(ctypes.c_ubyte)),
            ctypes.POINTER(ctypes.c_int), ctypes.POINTER(ctypes.c_int),
            ctypes.c_int, ctypes.c_int, ctypes.c_int, ctypes.c_int,
            ctypes.POINTER(ctypes.c_float), ctypes.POINTER(ctypes.c_float),
            ctypes.c_int, ctypes.c_int, ctypes.c_ulonglong, ctypes.c_int,
            ctypes.POINTER(ctypes.c_float)]
    _LIB = lib
    return _LIB


def available() -> bool:
    return get_lib() is not None


class NativeEngine:
    """Python handle on the C++ dependency engine."""

    def __init__(self, num_workers=4):
        lib = get_lib()
        if lib is None:
            raise RuntimeError("native runtime unavailable")
        self._lib = lib
        self._h = lib.mxt_engine_create(num_workers)

    def new_var(self):
        return self._lib.mxt_engine_new_var(self._h)

    def _varr(self, vars_):
        arr = (ctypes.c_void_p * len(vars_))(*vars_)
        return arr, len(vars_)

    def push_axpy(self, target, addend, reads=(), writes=(), sleep_us=0):
        r, nr = self._varr(list(reads))
        w, nw = self._varr(list(writes))
        self._lib.mxt_engine_push_axpy(self._h, target, addend, r, nr, w, nw,
                                       sleep_us)

    def push_scale(self, target, mul, reads=(), writes=(), sleep_us=0):
        r, nr = self._varr(list(reads))
        w, nw = self._varr(list(writes))
        self._lib.mxt_engine_push_scale(self._h, target, mul, r, nr, w, nw,
                                        sleep_us)

    def wait_var(self, var):
        self._lib.mxt_engine_wait_var(self._h, var)

    def wait_all(self):
        self._lib.mxt_engine_wait_all(self._h)

    @property
    def num_executed(self):
        return self._lib.mxt_engine_num_executed(self._h)

    def close(self):
        if self._h:
            self._lib.mxt_engine_destroy(self._h)
            self._h = None

    def __del__(self):
        try:
            self.close()
        except Exception:
            pass


class NativeRecordReader:
    """Prefetching batched RecordIO reader backed by the C++ engine."""

    def __init__(self, path, batch_size, num_threads=4, prefetch=4):
        lib = get_lib()
        if lib is None:
            raise RuntimeError("native runtime unavailable")
        self._lib = lib
        self._h = lib.mxt_reader_open(path.encode(), batch_size, num_threads,
                                      prefetch)
        if not self._h:
            raise IOError(f"cannot open record file {path}")

    def __len__(self):
        return int(self._lib.mxt_reader_num_records(self._h))

    def reset(self, shuffle=False, seed=0, part_index=0, num_parts=1):
        self._lib.mxt_reader_reset(self._h, int(shuffle), seed, part_index,
                                   num_parts)

    def next_batch(self):
        """Returns list[bytes] for the next batch ([] at epoch end)."""
        arena = ctypes.POINTER(ctypes.c_ubyte)()
        offsets = ctypes.POINTER(ctypes.c_ulonglong)()
        n = self._lib.mxt_reader_next(self._h, ctypes.byref(arena),
                                      ctypes.byref(offsets))
        out = []
        for i in range(n):
            lo, hi = offsets[i], offsets[i + 1]
            out.append(ctypes.string_at(
                ctypes.addressof(arena.contents) + lo, hi - lo))
        return out

    @property
    def engine_ops(self):
        return int(self._lib.mxt_reader_engine_ops(self._h))

    def close(self):
        if self._h:
            self._lib.mxt_reader_close(self._h)
            self._h = None

    def __del__(self):
        try:
            self.close()
        except Exception:
            pass


def augment_batch(images, out_hw, mean=None, std=None, rand_crop=False,
                  rand_mirror=False, seed=0, num_threads=4):
    """Native fused resize+crop+mirror+normalize -> float32 NCHW batch.

    ``images``: list of uint8 HWC numpy arrays (any per-image sizes).
    Reference analogue: ImageRecordIOParser2::ProcessImage batch assembly.
    Returns an (N, C, out_h, out_w) float32 numpy array."""
    import numpy as onp
    lib = get_lib()
    if lib is None or not hasattr(lib, "mxt_augment_batch"):
        raise RuntimeError("native augment kernel unavailable "
                           "(rebuild: make -C cpp)")
    n = len(images)
    if n == 0:
        raise ValueError("empty batch")
    if images[0].ndim != 3:
        raise ValueError(f"augment_batch: image 0 has shape "
                         f"{images[0].shape}; images must be HWC")
    c = images[0].shape[2]
    for i, im in enumerate(images):
        if im.ndim != 3 or im.shape[2] != c:
            raise ValueError(
                f"augment_batch: image {i} has shape {im.shape}; every "
                f"image must be HWC with {c} channels")
    out_h, out_w = out_hw
    # keep contiguous uint8 views alive for the call
    holds = [onp.ascontiguousarray(im, dtype=onp.uint8) for im in images]
    ptrs = (ctypes.POINTER(ctypes.c_ubyte) * n)(*[
        h.ctypes.data_as(ctypes.POINTER(ctypes.c_ubyte)) for h in holds])
    hs = (ctypes.c_int * n)(*[h.shape[0] for h in holds])
    ws = (ctypes.c_int * n)(*[h.shape[1] for h in holds])

    def fbuf(v):
        if v is None:
            return None
        a = onp.ascontiguousarray(v, dtype=onp.float32)
        return a, a.ctypes.data_as(ctypes.POINTER(ctypes.c_float))
    mh = fbuf(mean)
    sh = fbuf(std)
    out = onp.empty((n, c, out_h, out_w), onp.float32)
    lib.mxt_augment_batch(
        ptrs, hs, ws, c, n, out_h, out_w,
        mh[1] if mh else None, sh[1] if sh else None,
        int(bool(rand_crop)), int(bool(rand_mirror)),
        int(seed), int(num_threads),
        out.ctypes.data_as(ctypes.POINTER(ctypes.c_float)))
    return out


def jpeg_probe(payload):
    """Return (w, h) if ``payload`` parses as a JPEG header, else None."""
    lib = get_lib()
    if lib is None or not hasattr(lib, "mxt_jpeg_probe"):
        return None
    buf = (ctypes.c_ubyte * len(payload)).from_buffer_copy(payload)
    w = ctypes.c_int()
    h = ctypes.c_int()
    if lib.mxt_jpeg_probe(buf, ctypes.c_ulonglong(len(payload)),
                          ctypes.byref(w), ctypes.byref(h)):
        return w.value, h.value
    return None


def decode_augment_batch(payloads, out_hw, mean=None, std=None,
                         rand_crop=False, rand_mirror=False, seed=0,
                         num_threads=4):
    """Native fused JPEG-decode + resize/crop/mirror/normalize.

    ``payloads``: list of JPEG byte strings (or buffers). Returns an
    (N, 3, out_h, out_w) float32 numpy array, or None if any image failed
    to decode (caller should fall back to the python path). Reference
    analogue: ImageRecordIOParser2 decode + ProcessImage on C++ threads
    (src/io/iter_image_recordio_2.cc)."""
    import numpy as onp
    lib = get_lib()
    if lib is None or not hasattr(lib, "mxt_decode_augment_batch"):
        raise RuntimeError("native jpeg pipeline unavailable "
                           "(rebuild: make -C cpp)")
    n = len(payloads)
    if n == 0:
        raise ValueError("empty batch")
    # zero-copy: the C side only reads, so pass pointers into the (kept
    # alive) python byte buffers directly instead of memcpy'ing ~MBs of
    # compressed data per batch
    holds = [p if isinstance(p, bytes) else bytes(p) for p in payloads]
    ptrs = (ctypes.POINTER(ctypes.c_ubyte) * n)(*[
        ctypes.cast(ctypes.c_char_p(h), ctypes.POINTER(ctypes.c_ubyte))
        for h in holds])
    lens = (ctypes.c_ulonglong * n)(*[len(h) for h in holds])

    def fbuf(v):
        if v is None:
            return None
        a = onp.ascontiguousarray(v, dtype=onp.float32)
        return a, a.ctypes.data_as(ctypes.POINTER(ctypes.c_float))
    mh = fbuf(mean)
    sh = fbuf(std)
    out_h, out_w = out_hw
    out = onp.empty((n, 3, out_h, out_w), onp.float32)
    rc = lib.mxt_decode_augment_batch(
        ptrs, lens, n, out_h, out_w,
        mh[1] if mh else None, sh[1] if sh else None,
        int(bool(rand_crop)), int(bool(rand_mirror)),
        ctypes.c_ulonglong(int(seed)), int(num_threads),
        out.ctypes.data_as(ctypes.POINTER(ctypes.c_float)))
    if rc:
        return None
    return out


class Feature:
    def __init__(self, name, enabled):
        self.name = name
        self.enabled = enabled

    def __repr__(self):
        return f"{'✔' if self.enabled else '✖'} {self.name}"


class Features(dict):
    """Build/runtime feature flags (reference: mx.runtime.Features() listing
    CUDA/CUDNN/MKLDNN/...; here the TPU-relevant set)."""

    def __init__(self):
        import jax
        feats = {
            "TPU": any(d.platform != "cpu" for d in jax.devices()),
            "XLA": True,
            "PALLAS": True,
            "NATIVE_RUNTIME": available(),
            "NATIVE_IMAGE_AUG": available() and
                hasattr(get_lib(), "mxt_augment_batch"),
            "JPEG": available() and
                hasattr(get_lib(), "mxt_decode_augment_batch"),
            "DISTRIBUTED": True,
            "INT8_MXU": True,
            "BF16": True,
            "CUDA": False, "CUDNN": False, "NCCL": False,
            "MKLDNN": False, "TENSORRT": False, "OPENCV": False,
        }
        super().__init__({k: Feature(k, v) for k, v in feats.items()})

    def is_enabled(self, name):
        f = self.get(name.upper())
        return bool(f and f.enabled)
