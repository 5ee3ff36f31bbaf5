"""ctypes bindings for the in-tree C++ image runtime (native/imagebridge.cc).

Reference analogue: the reference's native execution surface lived in its
dependencies — TensorFrames' JNI bridge moved partition data into
libtensorflow, PIL/libjpeg decoded images, ImageUtils.scala resized them on
executors (SURVEY.md §3.1). Here the equivalent is an in-tree C++ library
doing decode (libjpeg/libpng), bilinear resize, and multithreaded NHWC
batch assembly, bound via ctypes (no pybind11 in the environment).

Every entry point has a pure-Python/PIL fallback; ``available()`` says
whether the fast path is active. The library is built on demand with
``make -C native`` and cached; a build that fails warns with the
compiler's message before the fallback takes over. Set
``SPARKDL_TPU_NO_NATIVE=1`` to force the fallback (used by parity tests).
"""

from __future__ import annotations

import ctypes
import os
import shutil
import subprocess
import warnings
from typing import List, Optional, Sequence, Tuple

import numpy as np

from sparkdl_tpu.runtime import knobs, locksmith

_REPO_ROOT = os.path.dirname(
    os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
)
_NATIVE_DIR = os.path.join(_REPO_ROOT, "native")
_SO_PATH = os.path.join(_NATIVE_DIR, "build", "libimagebridge.so")

_lock = locksmith.lock("sparkdl_tpu/runtime/native.py::_lock")
_lib: Optional[ctypes.CDLL] = None
_load_failed = False


def build(clean: bool = False) -> None:
    """Build the shared library from ``native/imagebridge.cc`` with make;
    raises with the compiler's output when it fails. ``clean=True``
    removes ``native/build/`` first, so what loads afterwards was
    compiled from the source on disk (``chip_smoke.py``)."""
    if clean:
        shutil.rmtree(os.path.dirname(_SO_PATH), ignore_errors=True)
    r = subprocess.run(
        ["make", "-C", _NATIVE_DIR],
        capture_output=True,
        text=True,
        timeout=300,
    )
    if r.returncode != 0 or not os.path.exists(_SO_PATH):
        raise RuntimeError(
            f"make -C {_NATIVE_DIR} failed (rc={r.returncode}): "
            + (r.stderr or r.stdout).strip()[-2000:]
        )


def _load() -> Optional[ctypes.CDLL]:
    global _lib, _load_failed
    if _lib is not None or _load_failed:
        return _lib
    with _lock:
        if _lib is not None or _load_failed:
            return _lib
        if knobs.get_flag("SPARKDL_TPU_NO_NATIVE"):
            _load_failed = True
            return None
        src = os.path.join(_NATIVE_DIR, "imagebridge.cc")
        needs_build = not os.path.exists(_SO_PATH) or (
            os.path.exists(src)
            and os.path.getmtime(src) > os.path.getmtime(_SO_PATH)
        )
        if needs_build:
            try:
                build()
            except (RuntimeError, subprocess.SubprocessError, OSError) as e:
                # a host that lands on the slower PIL path must be able
                # to see why
                warnings.warn(
                    f"native image bridge unavailable, using PIL: {e}"
                )
                _load_failed = True
                return None
        try:
            lib = ctypes.CDLL(_SO_PATH)
        except OSError:
            _load_failed = True
            return None
        u8p = ctypes.POINTER(ctypes.c_uint8)
        i32p = ctypes.POINTER(ctypes.c_int)
        lib.ib_version.restype = ctypes.c_int
        lib.ib_free.argtypes = [u8p]
        lib.ib_decode.restype = u8p
        lib.ib_decode.argtypes = [
            ctypes.c_char_p,
            ctypes.c_size_t,
            i32p,
            i32p,
            i32p,
        ]
        lib.ib_resize_bilinear.argtypes = [
            u8p,
            ctypes.c_int,
            ctypes.c_int,
            ctypes.c_int,
            u8p,
            ctypes.c_int,
            ctypes.c_int,
        ]
        lib.ib_assemble_batch.argtypes = [
            ctypes.POINTER(u8p),
            i32p,
            i32p,
            i32p,
            ctypes.c_int,
            u8p,
            ctypes.c_int,
            ctypes.c_int,
            ctypes.c_int,
            u8p,
            ctypes.c_int,
            ctypes.c_int,
        ]
        lib.ib_decode_resize_batch.argtypes = [
            ctypes.POINTER(ctypes.c_char_p),
            ctypes.POINTER(ctypes.c_size_t),
            ctypes.c_int,
            u8p,
            ctypes.c_int,
            ctypes.c_int,
            ctypes.c_int,
            u8p,
            ctypes.c_int,
            ctypes.c_int,
        ]
        if lib.ib_version() != 2:
            _load_failed = True
            return None
        _lib = lib
        return _lib


def available() -> bool:
    return _load() is not None


def _as_u8p(arr: np.ndarray):
    return arr.ctypes.data_as(ctypes.POINTER(ctypes.c_uint8))


def decode(raw: bytes) -> Optional[np.ndarray]:
    """Decode JPEG/PNG bytes -> HWC uint8 numpy array (1 or 3 channels), or
    None if undecodable."""
    lib = _load()
    if lib is None:
        raise RuntimeError("native bridge unavailable")
    h = ctypes.c_int()
    w = ctypes.c_int()
    c = ctypes.c_int()
    ptr = lib.ib_decode(
        raw, len(raw), ctypes.byref(h), ctypes.byref(w), ctypes.byref(c)
    )
    if not ptr:
        return None
    try:
        n = h.value * w.value * c.value
        arr = np.ctypeslib.as_array(ptr, shape=(n,)).copy()
        return arr.reshape(h.value, w.value, c.value)
    finally:
        lib.ib_free(ptr)


def resize_bilinear(arr: np.ndarray, height: int, width: int) -> np.ndarray:
    """HWC uint8 -> (height, width, C) uint8, bilinear (half-pixel
    centers)."""
    lib = _load()
    if lib is None:
        raise RuntimeError("native bridge unavailable")
    arr = np.ascontiguousarray(arr, dtype=np.uint8)
    h, w, c = arr.shape
    out = np.empty((height, width, c), dtype=np.uint8)
    lib.ib_resize_bilinear(_as_u8p(arr), h, w, c, _as_u8p(out), height, width)
    return out


def assemble_batch(
    arrays: Sequence[Optional[np.ndarray]],
    height: int,
    width: int,
    n_channels: int = 3,
    max_threads: int = 0,
    chw: bool = False,
) -> Tuple[np.ndarray, np.ndarray]:
    """List of HWC uint8 arrays (or None) -> (uint8 batch, bool mask),
    multithreaded in C++. Channel adaptation: gray->3, RGBA->3, RGB->1.
    ``chw=True`` packs slots channel-major — batch shape (n, C, H, W) —
    the TPU flat-feed layout, transposed inside the C++ thread pool."""
    lib = _load()
    if lib is None:
        raise RuntimeError("native bridge unavailable")
    n = len(arrays)
    shape = (
        (n, n_channels, height, width) if chw
        else (n, height, width, n_channels)
    )
    batch = np.zeros(shape, dtype=np.uint8)
    ok = np.zeros((n,), dtype=np.uint8)
    if n == 0:
        return batch, ok.astype(bool)
    srcs = (ctypes.POINTER(ctypes.c_uint8) * n)()
    hs = (ctypes.c_int * n)()
    ws = (ctypes.c_int * n)()
    cs = (ctypes.c_int * n)()
    keep: List[np.ndarray] = []  # hold refs so buffers outlive the call
    for i, a in enumerate(arrays):
        if a is None:
            continue
        a = np.ascontiguousarray(a, dtype=np.uint8)
        if a.ndim == 2:
            a = a[:, :, None]
        if a.ndim != 3:
            continue
        keep.append(a)
        srcs[i] = _as_u8p(a)
        hs[i], ws[i], cs[i] = a.shape
    lib.ib_assemble_batch(
        srcs,
        hs,
        ws,
        cs,
        n,
        _as_u8p(batch),
        height,
        width,
        n_channels,
        ok.ctypes.data_as(ctypes.POINTER(ctypes.c_uint8)),
        max_threads,
        int(chw),
    )
    return batch, ok.astype(bool)


def decode_resize_batch(
    blobs: Sequence[Optional[bytes]],
    height: int,
    width: int,
    n_channels: int = 3,
    max_threads: int = 0,
    chw: bool = False,
) -> Tuple[np.ndarray, np.ndarray]:
    """Raw image file bytes -> (uint8 batch, bool mask) in ONE
    multithreaded C++ pass (decode + channel adapt + resize + pack). The
    filesToDF -> featurizer hot loop. ``chw=True`` packs channel-major
    (n, C, H, W) — the TPU flat-feed layout."""
    lib = _load()
    if lib is None:
        raise RuntimeError("native bridge unavailable")
    n = len(blobs)
    shape = (
        (n, n_channels, height, width) if chw
        else (n, height, width, n_channels)
    )
    batch = np.zeros(shape, dtype=np.uint8)
    ok = np.zeros((n,), dtype=np.uint8)
    if n == 0:
        return batch, ok.astype(bool)
    ptrs = (ctypes.c_char_p * n)()
    lens = (ctypes.c_size_t * n)()
    for i, b in enumerate(blobs):
        if b:
            ptrs[i] = b
            lens[i] = len(b)
    lib.ib_decode_resize_batch(
        ptrs,
        lens,
        n,
        _as_u8p(batch),
        height,
        width,
        n_channels,
        ok.ctypes.data_as(ctypes.POINTER(ctypes.c_uint8)),
        max_threads,
        int(chw),
    )
    return batch, ok.astype(bool)
