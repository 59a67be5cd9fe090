"""ctypes wrapper for the native JPEG batch decoder (ref role:
src/io/iter_image_recordio_2.cc decode threads; see
src/imgdec/imgdec.cc).  Self-builds like the recordio backend; falls
back cleanly (``available() == False``) when g++/libjpeg are absent —
callers then use the PIL path."""
import ctypes
import os
import subprocess

import numpy as np

__all__ = ["available", "decode_batch"]

_LIB = None
_TRIED = False


def _build(src, so):
    """Compile to a temp file and os.rename into place: the rename is
    atomic on the same filesystem, so a concurrent process (multi-
    process launch, pytest-xdist) can never dlopen a half-written .so
    and two builders cannot corrupt each other (advisor r4)."""
    os.makedirs(os.path.dirname(so), exist_ok=True)
    tmp = f"{so}.tmp.{os.getpid()}"
    try:
        subprocess.run(
            ["g++", "-O2", "-fPIC", "-std=c++17", "-shared",
             "-o", tmp, src, "-ljpeg", "-lpthread"],
            check=True, capture_output=True, timeout=180)
        os.rename(tmp, so)
        return True
    except Exception:
        try:
            os.remove(tmp)
        except OSError:
            pass
        return False


def _native_lib():
    global _LIB, _TRIED
    if _TRIED:
        return _LIB
    _TRIED = True
    here = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    # ABI-versioned filename: bumping the suffix on an ABI change makes
    # a stale cached build simply not found, instead of relying on a
    # same-path reload (glibc dedups dlopen by pathname, so re-loading
    # a rebuilt .so at the SAME path returns the old mapping)
    # v3: fork-safe thread pool (pthread_atfork re-arm) for the
    # multi-process data service's forked decode workers
    so = os.path.join(here, "lib", "libmxtpu_imgdec.v3.so")
    src = os.path.join(os.path.dirname(here), "src", "imgdec",
                       "imgdec.cc")
    if os.path.exists(src) and (
            not os.path.exists(so)
            or os.path.getmtime(so) < os.path.getmtime(src)):
        # missing, or older than its source
        if not _build(src, so):
            return None
    if not os.path.exists(so):
        return None
    try:
        lib = ctypes.CDLL(so)
        lib.imgdec_last_error.restype = ctypes.c_char_p
        lib.imgdec_batch_err.restype = ctypes.c_int
        lib.imgdec_batch_err.argtypes = [
            ctypes.POINTER(ctypes.c_void_p),
            ctypes.POINTER(ctypes.c_int64), ctypes.c_int,
            ctypes.c_int, ctypes.c_int, ctypes.c_int,
            ctypes.c_void_p, ctypes.c_void_p, ctypes.c_void_p,
            ctypes.POINTER(ctypes.c_float), ctypes.c_int,
            ctypes.c_char_p, ctypes.c_int]
        _LIB = lib
    except (OSError, AttributeError):
        # AttributeError: symbol missing (a foreign/corrupt .so at the
        # versioned path) — degrade to the PIL fallback, never crash
        return None
    return _LIB


def available():
    return _native_lib() is not None


def decode_batch(raws, out_hw, resize_short=0, mirror=None,
                 mean=None, std=None, nthreads=8, out=None):
    """Decode a list of JPEG byte strings into (n, 3, H, W) float32.

    mirror: optional per-image bool array; mean/std: optional
    3-vectors applied as (px - mean) / std.  Raises on any decode
    failure (fail loudly: a corrupt record must not train as zeros).
    """
    lib = _native_lib()
    if lib is None:
        raise RuntimeError("native image decoder unavailable")
    n = len(raws)
    oh, ow = out_hw
    if out is None:
        out = np.empty((n, 3, oh, ow), np.float32)
    bufs = (ctypes.c_void_p * n)(
        *[ctypes.cast(ctypes.c_char_p(r), ctypes.c_void_p)
          for r in raws])
    sizes = (ctypes.c_int64 * n)(*[len(r) for r in raws])
    mir = None
    if mirror is not None:
        mirror = np.ascontiguousarray(mirror, np.uint8)
        mir = mirror.ctypes.data_as(ctypes.c_void_p)
    mvec = svec = None
    if mean is not None:
        mean = np.ascontiguousarray(mean, np.float32)
        mvec = mean.ctypes.data_as(ctypes.c_void_p)
    if std is not None:
        std = np.ascontiguousarray(std, np.float32)
        svec = std.ctypes.data_as(ctypes.c_void_p)
    # per-call error buffer: a concurrent iterator's next batch can't
    # clobber this batch's message (unlike the imgdec_last_error()
    # global)
    err = ctypes.create_string_buffer(512)
    failed = lib.imgdec_batch_err(
        bufs, sizes, n, oh, ow, int(resize_short), mir, mvec, svec,
        out.ctypes.data_as(ctypes.POINTER(ctypes.c_float)),
        int(nthreads), err, len(err))
    if failed:
        raise ValueError(
            f"native decode failed for {failed}/{n} images: "
            f"{err.value.decode(errors='replace')}")
    return out
