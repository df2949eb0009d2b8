"""Native host shim: C++ resize/pack with transparent Python fallback.

The reference's host hot path was native (JVM resize + TensorFrames/JNI
libtensorflow, SURVEY §2.3); this package is the TPU build's
counterpart. The C++ source (``sparkdl_host.cpp``) is compiled on first
use with the ambient ``g++`` (``-O3 -fopenmp``) into a shared library
next to the source and bound via ctypes — no pybind11 (not in the env),
no build step at install time, and every call site falls back to the
PIL/numpy path when the toolchain is absent.

The binary is keyed on its source: it is named
``_sparkdl_host.<sha>.so`` after a hash of ``sparkdl_host.cpp``'s
bytes, so a library built from any other source is never loaded —
whatever a copy did to file times — and editing the source rebuilds.
:func:`build_info` reports the hash and whether libjpeg was linked.

Set ``SPARKDL_TPU_NO_NATIVE=1`` to force the Python path.
"""

from __future__ import annotations

import ctypes
import glob
import hashlib
import logging
import os
import subprocess
import threading
from typing import List, Optional, Sequence

import numpy as np

logger = logging.getLogger(__name__)

_DIR = os.path.dirname(os.path.abspath(__file__))
_SRC = os.path.join(_DIR, "sparkdl_host.cpp")

_lock = threading.Lock()
_lib: Optional[ctypes.CDLL] = None
_tried = False


def source_sha() -> Optional[str]:
    """Hash of the shim's C++ source (the binary's key); None when the
    source is absent."""
    try:
        with open(_SRC, "rb") as f:
            return hashlib.sha256(f.read()).hexdigest()[:16]
    except FileNotFoundError:
        return None


def _lib_path(sha: str) -> str:
    return os.path.join(os.path.dirname(_SRC), f"_sparkdl_host.{sha}.so")


def _build(lib_path: str) -> bool:
    # Compile to a temp path and rename into place: rename is atomic, so
    # a concurrent process never dlopens a partially written .so. First
    # try with libjpeg (wherever the toolchain's search paths find it);
    # on failure retry without JPEG support rather than probing one
    # hardcoded header location.
    tmp = f"{lib_path}.{os.getpid()}.tmp"
    base = ["g++", "-O3", "-shared", "-fPIC", "-fopenmp", "-std=c++17",
            _SRC, "-o", tmp]
    attempts = [base[:1] + ["-DSDL_HAVE_JPEG"] + base[1:] + ["-ljpeg"],
                base]
    err: Optional[Exception] = None
    for cmd in attempts:
        try:
            subprocess.run(cmd, check=True, capture_output=True,
                           timeout=120)
            os.replace(tmp, lib_path)
        except (OSError, subprocess.SubprocessError) as e:
            err = e
            continue
        # binaries keyed on an older source are dead weight
        for old in glob.glob(_lib_path("*")):
            if old != lib_path:
                try:
                    os.unlink(old)
                except OSError:
                    pass
        return True
    logger.warning("native shim build failed (%s); using Python host "
                   "path", err)
    try:
        os.unlink(tmp)
    except OSError:
        pass
    return False


def _bind(lib: ctypes.CDLL) -> ctypes.CDLL:
    # every symbol is bound unconditionally: the binary was built from
    # THIS source (hash-keyed name), so none can be missing. A build
    # without libjpeg still exports the JPEG entry points as stubs and
    # says so through sdl_has_jpeg().
    _pp = ctypes.POINTER(ctypes.c_void_p)
    _pi64 = ctypes.POINTER(ctypes.c_int64)
    _pi32 = ctypes.POINTER(ctypes.c_int32)
    _pu8 = ctypes.POINTER(ctypes.c_uint8)
    lib.sdl_resize_pack_batch.restype = ctypes.c_int
    lib.sdl_resize_pack_batch.argtypes = [
        _pp,                                              # srcs
        _pi32, _pi32, _pi32,                              # src_h/w/c
        ctypes.c_int64,                                   # n
        ctypes.c_void_p,                                  # dst
        ctypes.c_int32, ctypes.c_int32, ctypes.c_int32,   # H, W, C
        ctypes.c_int32,                                   # num_threads
    ]
    lib.sdl_version.restype = ctypes.c_int
    lib.sdl_has_jpeg.restype = ctypes.c_int
    lib.sdl_jpeg_batch_dims.restype = ctypes.c_int
    lib.sdl_jpeg_batch_dims.argtypes = [
        _pp, _pi64, ctypes.c_int64, _pi32, _pi32, _pi32,
        ctypes.c_int32]
    lib.sdl_jpeg_batch_decode.restype = ctypes.c_int
    lib.sdl_jpeg_batch_decode.argtypes = [
        _pp, _pi64, ctypes.c_int64, _pp, _pi32, _pi32, _pu8,
        ctypes.c_int32]
    # the trailing int32 is the DCT-prescale ``scaled`` flag
    lib.sdl_decode_resize_pack_v3.restype = ctypes.c_int
    lib.sdl_decode_resize_pack_v3.argtypes = [
        _pp, _pi64, ctypes.c_int64, ctypes.c_void_p,
        ctypes.c_int32, ctypes.c_int32, ctypes.c_int32, _pu8,
        ctypes.c_int32, ctypes.c_int32]
    lib.sdl_decode_resize_pack_420_v3.restype = ctypes.c_int
    lib.sdl_decode_resize_pack_420_v3.argtypes = [
        _pp, _pi64, ctypes.c_int64, ctypes.c_void_p,
        ctypes.c_int32, ctypes.c_int32, _pu8,
        ctypes.c_int32, ctypes.c_int32]
    return lib


def disabled_by_env() -> bool:
    """Whether SPARKDL_TPU_NO_NATIVE disables the shim. "0"/"false"/""
    mean NOT disabled — a truthy-string check would silently disable
    for SPARKDL_TPU_NO_NATIVE=0. (Shared with the test skip-gate so the
    accepted spellings can't drift.)"""
    return os.environ.get("SPARKDL_TPU_NO_NATIVE", "").lower() \
        not in ("", "0", "false")


def get_lib() -> Optional[ctypes.CDLL]:
    """The loaded native library, building it on first call; None when
    disabled or unavailable (no source, no toolchain)."""
    global _lib, _tried
    if disabled_by_env():
        return None
    with _lock:
        if _tried:
            return _lib
        _tried = True
        # sparkdl-lint: allow[H8] -- part of the same one-shot resolution as the build below: the source is hashed once per process, under the lock every caller must wait on anyway
        sha = source_sha()
        if sha is None:
            return None
        lib_path = _lib_path(sha)
        # sparkdl-lint: allow[H8] -- one-shot g++ build under the load lock is the point: every caller must wait for (and share) THE library; a second unlocked builder would race the .so write
        if not os.path.exists(lib_path) and not _build(lib_path):
            return None
        try:
            _lib = _bind(ctypes.CDLL(lib_path))
            _lib.source_sha = sha
        except OSError as e:
            logger.warning("native shim load failed (%s); using Python "
                           "host path", e)
            _lib = None
        return _lib


def build_info() -> dict:
    """What this process's decode path runs on: the source hash the
    loaded binary was built from (None when the shim is unavailable —
    the PIL path is in use) and whether it links libjpeg."""
    return {"source_sha": getattr(get_lib(), "source_sha", None),
            "jpeg": has_jpeg()}


def available() -> bool:
    return get_lib() is not None


# Matches PIL's decompression-bomb threshold order of magnitude: refuse
# to trust a header claiming more pixels than this.
MAX_DECODE_PIXELS = 100_000_000


def has_jpeg() -> bool:
    lib = get_lib()
    return bool(lib and lib.sdl_has_jpeg())


def _blob_ptrs(blobs: Sequence[bytes]):
    n = len(blobs)
    ptrs = (ctypes.c_void_p * n)()
    lens = np.empty(n, np.int64)
    refs = []
    for i, b in enumerate(blobs):
        buf = np.frombuffer(b, np.uint8)
        refs.append(buf)
        ptrs[i] = buf.ctypes.data
        lens[i] = len(b)
    return ptrs, lens, refs


def decode_jpeg_batch(blobs: Sequence[bytes]
                      ) -> Optional[List[Optional[np.ndarray]]]:
    """Decode COLOR JPEG byte blobs to RGB HWC uint8 arrays in one
    native call (OpenMP over images, GIL released). Per-image failures —
    parse errors, header dims over :data:`MAX_DECODE_PIXELS`, and
    grayscale sources (left to the PIL path so the image struct's
    nChannels stays identical with and without the shim) — come back as
    None; returns None overall when the native path or libjpeg is
    unavailable."""
    if not has_jpeg():
        return None
    lib = get_lib()
    n = len(blobs)
    if n == 0:
        return []
    ptrs, lens, refs = _blob_ptrs(blobs)
    hs = np.empty(n, np.int32)
    ws = np.empty(n, np.int32)
    cs = np.empty(n, np.int32)
    lib.sdl_jpeg_batch_dims(
        ptrs, lens.ctypes.data_as(ctypes.POINTER(ctypes.c_int64)), n,
        hs.ctypes.data_as(ctypes.POINTER(ctypes.c_int32)),
        ws.ctypes.data_as(ctypes.POINTER(ctypes.c_int32)),
        cs.ctypes.data_as(ctypes.POINTER(ctypes.c_int32)), 0)
    outs: List[Optional[np.ndarray]] = [None] * n
    dsts = (ctypes.c_void_p * n)()
    for i in range(n):
        if (hs[i] > 0 and ws[i] > 0 and cs[i] == 3
                and int(hs[i]) * int(ws[i]) <= MAX_DECODE_PIXELS):
            arr = np.empty((hs[i], ws[i], 3), np.uint8)
            dsts[i] = arr.ctypes.data
            outs[i] = arr
        else:
            hs[i] = -1  # tell the decode pass to skip this row
            dsts[i] = None
    ok = np.zeros(n, np.uint8)
    lib.sdl_jpeg_batch_decode(
        ptrs, lens.ctypes.data_as(ctypes.POINTER(ctypes.c_int64)), n,
        dsts, hs.ctypes.data_as(ctypes.POINTER(ctypes.c_int32)),
        ws.ctypes.data_as(ctypes.POINTER(ctypes.c_int32)),
        ok.ctypes.data_as(ctypes.POINTER(ctypes.c_uint8)), 0)
    return [outs[i] if ok[i] else None for i in range(n)]


def decode_resize_pack(blobs: Sequence[bytes], height: int, width: int,
                       nChannels: int = 3, num_threads: int = 0,
                       scaled_decode: bool = False) -> Optional[tuple]:
    """Fused infeed path: JPEG decode → bilinear resize → channel
    convert → contiguous [N,H,W,C] uint8, one native call (the product
    consumer is ``imageIO.readImagesPacked``). ``scaled_decode`` enables
    libjpeg's DCT-domain prescale — decode lands at the smallest M/8 of
    the source still covering (H, W), so most IDCT work is skipped on
    shrink and the following bilinear step never shrinks by ≥2x (which
    also anti-aliases better than bilinear from full res). Pixel output
    differs from the unscaled path on downscale. Returns
    ``(batch, ok_mask)`` or None when unavailable."""
    if not has_jpeg():
        return None
    lib = get_lib()
    n = len(blobs)
    out = np.zeros((n, height, width, nChannels), np.uint8)
    ok = np.zeros(n, np.uint8)
    if n == 0:
        return out, ok.astype(bool)
    ptrs, lens, refs = _blob_ptrs(blobs)
    lib.sdl_decode_resize_pack_v3(
        ptrs, lens.ctypes.data_as(ctypes.POINTER(ctypes.c_int64)),
        n, out.ctypes.data, height, width, nChannels,
        ok.ctypes.data_as(ctypes.POINTER(ctypes.c_uint8)),
        num_threads, int(bool(scaled_decode)))
    return out, ok.astype(bool)


def yuv420_packed_size(height: int, width: int) -> int:
    """Bytes per image of the planar 4:2:0 payload: Y[H*W] ++
    Cb[H/2*W/2] ++ Cr[H/2*W/2]. H and W must be positive and even."""
    if height <= 0 or width <= 0 or height % 2 or width % 2:
        raise ValueError(
            f"yuv420 packing needs positive even dims, got "
            f"{height}x{width}")
    return height * width + 2 * (height // 2) * (width // 2)


def decode_resize_pack_420(blobs: Sequence[bytes], height: int,
                           width: int, num_threads: int = 0,
                           scaled_decode: bool = False
                           ) -> Optional[tuple]:
    """Fused 4:2:0 infeed (VERDICT r4 next #1): JPEG decode → per-plane
    bilinear resize → packed planar YCbCr 4:2:0 ``[N, H*W*3/2]`` uint8,
    one native call. Standard 4:2:0 sources come out of libjpeg raw
    (chroma never upsampled on host); the device op
    ``ops.fused_yuv420_resize_normalize`` reconstructs RGB fused into
    the model program. ``scaled_decode`` enables the DCT-domain
    prescale (power-of-two M/8 covering (H, W)): the Y IDCT emits a
    quarter the samples at 1/2 scale while stored-half-res chroma stays
    unscaled; pixel output differs from the unscaled path on downscale.
    Returns ``(packed, ok_mask)`` or None when the native path or
    libjpeg is unavailable."""
    if not has_jpeg():
        return None
    lib = get_lib()
    row = yuv420_packed_size(height, width)
    n = len(blobs)
    out = np.zeros((n, row), np.uint8)
    ok = np.zeros(n, np.uint8)
    if n == 0:
        return out, ok.astype(bool)
    ptrs, lens, refs = _blob_ptrs(blobs)
    rc = lib.sdl_decode_resize_pack_420_v3(
        ptrs, lens.ctypes.data_as(ctypes.POINTER(ctypes.c_int64)),
        n, out.ctypes.data, height, width,
        ok.ctypes.data_as(ctypes.POINTER(ctypes.c_uint8)),
        num_threads, int(bool(scaled_decode)))
    if rc != 0:
        raise ValueError(f"native 4:2:0 decode/pack failed (rc={rc})")
    return out, ok.astype(bool)


def resize_pack_buffers(values: np.ndarray, offsets: np.ndarray,
                        heights: np.ndarray, widths: np.ndarray,
                        channels: np.ndarray, height: int, width: int,
                        nChannels: int = 3,
                        num_threads: int = 0) -> Optional[np.ndarray]:
    """Zero-copy variant of :func:`resize_pack_batch`: sources are given
    as one shared uint8 buffer plus per-row offsets/dims (numpy views
    over an Arrow binary column — see ``imageIO.imageColumnViews``), so
    no per-row Python objects or copies are made; the pointer table is
    computed vectorized as ``base + offsets``. Returns None when the
    native path is unavailable."""
    lib = get_lib()
    if lib is None:
        return None
    n = len(heights)
    out = np.empty((n, height, width, nChannels), dtype=np.uint8)
    if n == 0:
        return out
    values = np.ascontiguousarray(values)
    expected = (heights.astype(np.int64) * widths.astype(np.int64)
                * channels.astype(np.int64))
    sizes = np.asarray(offsets[1:]) - np.asarray(offsets[:-1])
    if not (sizes == expected).all():
        i = int(np.flatnonzero(sizes != expected)[0])
        raise ValueError(
            f"row {i}: data size {int(sizes[i])} != h*w*c = "
            f"{int(expected[i])}")
    if int(offsets[-1]) > values.size:
        raise ValueError("offsets overrun the shared data buffer")
    ptr_table = (np.asarray(offsets[:-1], np.uint64)
                 + np.uint64(values.ctypes.data))
    hs = np.ascontiguousarray(heights, np.int32)
    ws = np.ascontiguousarray(widths, np.int32)
    cs = np.ascontiguousarray(channels, np.int32)
    rc = lib.sdl_resize_pack_batch(
        ptr_table.ctypes.data_as(ctypes.POINTER(ctypes.c_void_p)),
        hs.ctypes.data_as(ctypes.POINTER(ctypes.c_int32)),
        ws.ctypes.data_as(ctypes.POINTER(ctypes.c_int32)),
        cs.ctypes.data_as(ctypes.POINTER(ctypes.c_int32)),
        n, out.ctypes.data, height, width, nChannels, num_threads)
    if rc != 0:
        raise ValueError(
            "native resize/pack failed: unsupported channel conversion "
            f"in batch (target {nChannels} channels)")
    return out


def resize_pack_batch(images: Sequence[np.ndarray], height: int,
                      width: int, nChannels: int = 3,
                      num_threads: int = 0) -> Optional[np.ndarray]:
    """Resize+convert+pack HWC uint8 images into [N,H,W,C] uint8 in one
    native call (OpenMP over rows, GIL released). Returns None when the
    native path is unavailable; raises ValueError for unsupported
    channel conversions (matching the Python path's behavior)."""
    lib = get_lib()
    if lib is None:
        return None
    n = len(images)
    out = np.empty((n, height, width, nChannels), dtype=np.uint8)
    if n == 0:
        return out
    ptrs = (ctypes.c_void_p * n)()
    hs = np.empty(n, np.int32)
    ws = np.empty(n, np.int32)
    cs = np.empty(n, np.int32)
    refs: List[np.ndarray] = []  # keep source buffers alive over the call
    for i, img in enumerate(images):
        arr = np.ascontiguousarray(img)
        if arr.ndim == 2:
            arr = arr[:, :, None]
        if arr.ndim != 3 or arr.dtype != np.uint8:
            raise ValueError(
                f"image {i}: expected HWC uint8, got shape "
                f"{arr.shape} dtype {arr.dtype}")
        refs.append(arr)
        ptrs[i] = arr.ctypes.data
        hs[i], ws[i], cs[i] = arr.shape
    rc = lib.sdl_resize_pack_batch(
        ptrs,
        hs.ctypes.data_as(ctypes.POINTER(ctypes.c_int32)),
        ws.ctypes.data_as(ctypes.POINTER(ctypes.c_int32)),
        cs.ctypes.data_as(ctypes.POINTER(ctypes.c_int32)),
        n, out.ctypes.data, height, width, nChannels, num_threads)
    if rc != 0:
        raise ValueError(
            "native resize/pack failed: unsupported channel conversion "
            f"in batch (target {nChannels} channels)")
    return out
