"""Native runtime components: build-on-first-use C++ via ctypes.

The reference keeps all of its own code in Python and leans on each
worker's CUDA substrate for performance (SURVEY.md §2: zero native code in
the repo). Here the serving path has real host-side work — PNG encoding of
finished images, with the device idle and the client waiting — done
natively (native/png_encoder.cpp: zlib, each image deflated as strips of
scanlines on the host's idle cores and stitched into one stream; the
library picks the number of strips from the image's bytes and the cores
this process may run on, and reports it) with a PIL fallback when no
toolchain is available; the fallback is logged once and
:func:`active_encoder` names which one serves. The library is compiled
once per machine into ``native/build/`` (git-ignored) and memoized.
"""

from __future__ import annotations

import ctypes
import os
import subprocess
import threading
from typing import Optional, Tuple

import numpy as np

_lock = threading.Lock()
_lib: Optional[ctypes.CDLL] = None
_lib_failed = False


def _native_dir() -> str:
    return os.path.join(os.path.dirname(os.path.dirname(
        os.path.dirname(os.path.abspath(__file__)))), "native")


def _warn_fallback(why: str) -> None:
    from stable_diffusion_webui_distributed_tpu.runtime.logging import (
        get_logger,
    )

    get_logger().warning(
        "native png encoder unavailable (%s); PNGs are encoded by PIL", why)


def _build_library() -> Optional[str]:
    src = os.path.join(_native_dir(), "png_encoder.cpp")
    if not os.path.exists(src):
        _warn_fallback(f"{src} missing")
        return None
    build_dir = os.path.join(_native_dir(), "build")
    out = os.path.join(build_dir, "libsdtpu_png.so")
    if os.path.exists(out) and os.path.getmtime(out) >= os.path.getmtime(src):
        return out
    os.makedirs(build_dir, exist_ok=True)
    cmd = ["g++", "-O3", "-shared", "-fPIC", "-pthread", src, "-lz", "-o",
           out]
    try:
        proc = subprocess.run(cmd, capture_output=True, timeout=120)
    except (OSError, subprocess.TimeoutExpired) as e:
        _warn_fallback(f"g++ did not run: {e}")
        return None
    if proc.returncode != 0:
        _warn_fallback("build failed: "
                       + proc.stderr.decode(errors="replace")[:400])
        return None
    return out


def _get_lib() -> Optional[ctypes.CDLL]:
    global _lib, _lib_failed
    with _lock:
        if _lib is not None or _lib_failed:
            return _lib
        path = _build_library()
        if path is None:
            _lib_failed = True
            return None
        try:
            lib = ctypes.CDLL(path)
            lib.sdtpu_encode_png.restype = ctypes.c_long
            lib.sdtpu_encode_png.argtypes = [
                ctypes.c_void_p, ctypes.c_int, ctypes.c_int, ctypes.c_int,
                ctypes.c_ssize_t, ctypes.c_ssize_t, ctypes.c_ssize_t,
                ctypes.c_int, ctypes.c_void_p, ctypes.c_long,
                ctypes.POINTER(ctypes.c_int),
            ]
            _lib = lib
        except OSError as e:
            _warn_fallback(f"{path} did not load: {e}")
            _lib_failed = True
        return _lib


def active_encoder() -> str:
    """``"native"`` when the C++ encoder built and loaded, else ``"pil"`` —
    the encoder :func:`pipeline.payload.array_to_b64png` serves with."""
    return "native" if _get_lib() is not None else "pil"


def warm_up(background: bool = True) -> None:
    """Build/load the native library ahead of the first request so the
    compile (up to ~2 min cold) never lands on the serving path."""
    if background:
        threading.Thread(target=_get_lib, name="native-warmup",
                         daemon=True).start()
    else:
        _get_lib()


def encode_png(img: np.ndarray, compression_level: int = 6
               ) -> Optional[Tuple[bytes, int]]:
    """(H, W, 3|4) uint8 -> (PNG bytes, strips the image was deflated as)
    via the native encoder, or None when the native path is unavailable
    (caller falls back to PIL). The array goes to the library as it lies,
    strides and all: the image a TPU hands back is three planes, and the
    strips' threads interleave them."""
    lib = _get_lib()
    if lib is None:
        return None
    if img.dtype != np.uint8 or img.ndim != 3 or img.shape[2] not in (3, 4):
        return None
    h, w, c = img.shape
    strips = ctypes.c_int(0)
    cap = h * (w * c + 1) + (h * w * c >> 10) + 4096
    for _ in range(2):
        buf = np.empty(cap, np.uint8)   # the library writes the file here
        n = lib.sdtpu_encode_png(
            img.ctypes.data, w, h, c, *img.strides, compression_level,
            buf.ctypes.data, cap, ctypes.byref(strips))
        if n >= 0:
            break
        cap = -n    # undersized buffer: once more at the reported size
    if n <= 0:
        return None
    return buf[:n].tobytes(), strips.value
