"""ctypes bindings for libmxtpu (see ``src/libmxtpu.cc``) — the native
runtime components (RecordIO reader, JPEG decode, threaded decode
pipeline; the rebuild of the reference's C++ ``src/io`` stack).

The library builds with g++ on first use (no pybind11 in the
environment — plain C ABI + ctypes per SURVEY.md environment notes),
from the source git holds: its file name carries a hash of
``libmxtpu.cc``, so a binary built from other source is never loaded.
A component asked for by name (:class:`NativeRecordReader`,
:func:`jpeg_decode`, :class:`NativePipeline`) raises when the build
fails; :func:`available` is for callers with a documented Python
alternative (``mxtpu.io.ImageRecordIter``) and logs the reason once.
"""
from __future__ import annotations

import ctypes
import hashlib
import logging
import os
import subprocess
import threading

import numpy as onp

_LIB = None
_LIB_ERROR = None
_LOCK = threading.Lock()
_SRC_DIR = os.path.join(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))), "src")


def _build() -> str:
    """Path of the library built from the current ``libmxtpu.cc``,
    compiling it when no file with that content hash exists yet."""
    src = os.path.join(_SRC_DIR, "libmxtpu.cc")
    with open(src, "rb") as f:
        tag = hashlib.sha256(f.read()).hexdigest()[:16]
    so = os.path.join(_SRC_DIR, f"libmxtpu-{tag}.so")
    if not os.path.exists(so):
        tmp = f"{so}.{os.getpid()}.tmp"     # concurrent builders race
        try:                                # on the rename, not the file
            subprocess.run(
                ["g++", "-O3", "-std=c++17", "-fPIC", "-Wall", "-shared",
                 src, "-o", tmp, "-ljpeg", "-lpthread"],
                check=True, capture_output=True, text=True, timeout=120)
            os.replace(tmp, so)
        except subprocess.CalledProcessError as e:
            raise RuntimeError(
                f"libmxtpu build failed:\n{e.stderr[-2000:]}") from e
        finally:
            if os.path.exists(tmp):
                os.remove(tmp)
    return so


def get_lib():
    """Load (building if needed) libmxtpu. Raises RuntimeError when it
    cannot be built or loaded; the failure is remembered, so the
    compiler runs at most once per process."""
    global _LIB, _LIB_ERROR
    with _LOCK:
        if _LIB is not None:
            return _LIB
        if _LIB_ERROR is not None:
            raise RuntimeError(_LIB_ERROR)
        try:
            lib = ctypes.CDLL(_build())
        except (OSError, RuntimeError,
                subprocess.TimeoutExpired) as e:
            _LIB_ERROR = f"libmxtpu unavailable: {e}"
            raise RuntimeError(_LIB_ERROR) from e
        lib.mxtpu_rec_open.restype = ctypes.c_void_p
        lib.mxtpu_rec_open.argtypes = [ctypes.c_char_p]
        lib.mxtpu_rec_count.restype = ctypes.c_long
        lib.mxtpu_rec_count.argtypes = [ctypes.c_void_p]
        lib.mxtpu_rec_read.restype = ctypes.c_long
        lib.mxtpu_rec_read.argtypes = [
            ctypes.c_void_p, ctypes.c_long,
            ctypes.POINTER(ctypes.POINTER(ctypes.c_ubyte))]
        lib.mxtpu_rec_close.argtypes = [ctypes.c_void_p]
        lib.mxtpu_jpeg_decode.restype = ctypes.c_long
        lib.mxtpu_jpeg_decode.argtypes = [
            ctypes.POINTER(ctypes.c_ubyte), ctypes.c_ulong, ctypes.c_int,
            ctypes.POINTER(ctypes.c_ubyte), ctypes.POINTER(ctypes.c_int),
            ctypes.POINTER(ctypes.c_int), ctypes.POINTER(ctypes.c_int)]
        lib.mxtpu_pipe_create.restype = ctypes.c_void_p
        lib.mxtpu_pipe_create.argtypes = [
            ctypes.c_char_p, ctypes.c_int, ctypes.c_int, ctypes.c_int,
            ctypes.c_int, ctypes.c_uint, ctypes.c_int, ctypes.c_int]
        lib.mxtpu_pipe_next_u8.restype = ctypes.c_long
        lib.mxtpu_pipe_next_u8.argtypes = [
            ctypes.c_void_p, ctypes.c_long,
            ctypes.POINTER(ctypes.c_ubyte),
            ctypes.POINTER(ctypes.c_float)]
        lib.mxtpu_pipe_next.restype = ctypes.c_long
        lib.mxtpu_pipe_next.argtypes = [
            ctypes.c_void_p, ctypes.c_long,
            ctypes.POINTER(ctypes.c_float), ctypes.POINTER(ctypes.c_float)]
        lib.mxtpu_pipe_reset.argtypes = [ctypes.c_void_p]
        lib.mxtpu_pipe_destroy.argtypes = [ctypes.c_void_p]
        _LIB = lib
        return lib


def available() -> bool:
    """True when libmxtpu loads. For callers that have a Python path
    to fall back to; the reason it does not load is logged once."""
    first = _LIB is None and _LIB_ERROR is None
    try:
        get_lib()
        return True
    except RuntimeError as e:
        if first:
            logging.getLogger(__name__).warning(
                "%s — using the Python input path", e)
        return False


class NativeRecordReader:
    """Random-access RecordIO reader over the native offset index."""

    def __init__(self, path: str):
        lib = get_lib()
        self._lib = lib
        self._h = lib.mxtpu_rec_open(path.encode())
        if not self._h:
            raise IOError(f"cannot open {path}")

    def __len__(self) -> int:
        return int(self._lib.mxtpu_rec_count(self._h))

    def read(self, i: int) -> bytes:
        ptr = ctypes.POINTER(ctypes.c_ubyte)()
        n = self._lib.mxtpu_rec_read(self._h, i, ctypes.byref(ptr))
        if n < 0:
            raise IndexError(i)
        return bytes(ctypes.cast(
            ptr, ctypes.POINTER(ctypes.c_ubyte * n)).contents)

    def close(self):
        if self._h:
            self._lib.mxtpu_rec_close(self._h)
            self._h = None

    def __del__(self):
        try:
            self.close()
        except Exception:
            pass


def jpeg_decode(buf: bytes, channels: int = 3) -> onp.ndarray:
    """Native JPEG decode → HWC uint8."""
    lib = get_lib()
    arr = (ctypes.c_ubyte * len(buf)).from_buffer_copy(buf)
    w = ctypes.c_int()
    h = ctypes.c_int()
    c = ctypes.c_int()
    n = lib.mxtpu_jpeg_decode(arr, len(buf), channels, None,
                              ctypes.byref(w), ctypes.byref(h),
                              ctypes.byref(c))
    if n < 0:
        raise ValueError("JPEG decode failed")
    out = onp.empty(n, onp.uint8)
    lib.mxtpu_jpeg_decode(
        arr, len(buf), channels,
        out.ctypes.data_as(ctypes.POINTER(ctypes.c_ubyte)),
        ctypes.byref(w), ctypes.byref(h), ctypes.byref(c))
    return out.reshape(h.value, w.value, c.value)


class NativePipeline:
    """Threaded read+decode+resize pipeline (the reference's C++
    ImageRecordIOParser2 + prefetcher, rebuilt)."""

    def __init__(self, rec_path: str, height: int, width: int,
                 channels: int = 3, shuffle: bool = False, seed: int = 0,
                 threads: int = 2, out_u8: bool = False):
        lib = get_lib()
        self._lib = lib
        self._hwc = (height, width, channels)
        self._u8 = bool(out_u8)
        self._h = lib.mxtpu_pipe_create(rec_path.encode(), height, width,
                                        channels, int(shuffle), seed,
                                        threads, int(out_u8))
        if not self._h:
            raise IOError(f"cannot open {rec_path}")

    def next_batch(self, batch_size: int):
        """Returns (data (n,h,w,c), labels (n,)) with n ≤ batch_size;
        n==0 means the epoch is exhausted. Data is float32, or uint8
        when built with ``out_u8`` (quarter the host→device bytes —
        convert/normalize on the accelerator)."""
        h, w, c = self._hwc
        labels = onp.empty((batch_size,), onp.float32)
        lp = labels.ctypes.data_as(ctypes.POINTER(ctypes.c_float))
        if self._u8:
            data = onp.empty((batch_size, h, w, c), onp.uint8)
            n = self._lib.mxtpu_pipe_next_u8(
                self._h, batch_size,
                data.ctypes.data_as(ctypes.POINTER(ctypes.c_ubyte)), lp)
        else:
            data = onp.empty((batch_size, h, w, c), onp.float32)
            n = self._lib.mxtpu_pipe_next(
                self._h, batch_size,
                data.ctypes.data_as(ctypes.POINTER(ctypes.c_float)), lp)
        if n < 0:
            raise RuntimeError("pipe output-mode mismatch (out_u8 flag "
                               "does not match the create() mode)")
        return data[:n], labels[:n]

    def reset(self):
        self._lib.mxtpu_pipe_reset(self._h)

    def close(self):
        if self._h:
            self._lib.mxtpu_pipe_destroy(self._h)
            self._h = None

    def __del__(self):
        try:
            self.close()
        except Exception:
            pass
