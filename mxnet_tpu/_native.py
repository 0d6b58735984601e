"""Native (C++) component loader.

The reference keeps its runtime IO/serving hot paths in C++ (src/io/,
src/c_api/); this build does the same, compiling the sources under
``src/native/`` into a shared library consumed via ctypes (pybind11 is
not in this image — the flat C ABI mirrors the reference's c_api.h
approach anyway). The library is built on demand with g++ from the
tracked sources and cached under the git-ignored ``build/native``;
callers must handle ``None`` (pure-Python fallback) when the build
fails — the failure is logged once, with the compiler's output.
"""
from __future__ import annotations

import ctypes
import logging
import os
import subprocess
import threading

_log = logging.getLogger(__name__)
_lock = threading.Lock()
_cache = {}

_ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
_SRC = os.path.join(_ROOT, "src", "native")
_OUT = os.path.join(_ROOT, "build", "native")


def _build(name, sources, flags=()):
    os.makedirs(_OUT, exist_ok=True)
    lib_path = os.path.join(_OUT, "lib%s.so" % name)
    srcs = [os.path.join(_SRC, s) for s in sources]
    if os.path.exists(lib_path) and all(
            os.path.getmtime(lib_path) >= os.path.getmtime(s) for s in srcs):
        return lib_path
    cmd = ["g++", "-O2", "-std=c++17", "-shared", "-fPIC", "-o", lib_path] \
        + srcs + list(flags)
    subprocess.run(cmd, check=True, capture_output=True)
    return lib_path


def load(name, sources, flags=()):
    """Build (if needed) + dlopen lib<name>.so from src/native sources.
    Returns the ctypes CDLL, or None when it cannot be built or loaded
    (no g++, a compile error, a missing system library): logged once —
    the result is cached — with the compiler's output."""
    with _lock:
        if name in _cache:
            return _cache[name]
        try:
            lib = ctypes.CDLL(_build(name, sources, flags))
        except (OSError, subprocess.CalledProcessError) as e:
            stderr = getattr(e, "stderr", None) or b""
            _log.warning(
                "native lib%s unavailable (%s); callers fall back to "
                "their pure-Python path. Compiler output:\n%s",
                name, e, stderr.decode(errors="replace").strip()
                or "(none)")
            lib = None
        _cache[name] = lib
        return lib


def recordio_lib():
    lib = load("recordio", ["recordio.cc"])
    if lib is not None and not getattr(lib, "_rio_typed", False):
        lib.rio_open.restype = ctypes.c_void_p
        lib.rio_open.argtypes = [ctypes.c_char_p, ctypes.c_int]
        lib.rio_close.argtypes = [ctypes.c_void_p]
        lib.rio_write.restype = ctypes.c_longlong
        lib.rio_write.argtypes = [ctypes.c_void_p, ctypes.c_char_p,
                                  ctypes.c_uint64]
        lib.rio_read.restype = ctypes.c_int
        lib.rio_read.argtypes = [ctypes.c_void_p,
                                 ctypes.POINTER(ctypes.c_char_p),
                                 ctypes.POINTER(ctypes.c_uint64)]
        lib.rio_seek.restype = ctypes.c_int
        lib.rio_seek.argtypes = [ctypes.c_void_p, ctypes.c_uint64]
        lib.rio_tell.restype = ctypes.c_longlong
        lib.rio_tell.argtypes = [ctypes.c_void_p]
        lib.rio_free.argtypes = [ctypes.c_char_p]
        lib._rio_typed = True
    return lib


def imagedec_lib():
    """Parallel JPEG decode+augment pool (src/native/imagedec.cc; the
    analog of the reference's OMP ParseChunk hot path). Needs the
    system OpenCV C++ libs; returns None when they're absent."""
    lib = load("imagedec", ["imagedec.cc"],
               flags=["-I/usr/include/opencv4", "-pthread",
                      "-lopencv_core", "-lopencv_imgcodecs",
                      "-lopencv_imgproc"])
    if lib is not None and not getattr(lib, "_img_typed", False):
        u8pp = ctypes.POINTER(ctypes.c_char_p)
        lib.imgdec_create.restype = ctypes.c_void_p
        lib.imgdec_create.argtypes = [
            ctypes.c_int, ctypes.c_int, ctypes.c_int, ctypes.c_int,
            ctypes.c_int, ctypes.c_int, ctypes.c_int,
            ctypes.POINTER(ctypes.c_float), ctypes.POINTER(ctypes.c_float),
            ctypes.c_uint64]
        lib.imgdec_decode_batch.restype = ctypes.c_int
        lib.imgdec_decode_batch.argtypes = [
            ctypes.c_void_p, ctypes.c_int, u8pp,
            ctypes.POINTER(ctypes.c_int64), ctypes.c_uint64,
            ctypes.POINTER(ctypes.c_float)]
        lib.imgdec_last_error.restype = ctypes.c_char_p
        lib.imgdec_last_error.argtypes = [ctypes.c_void_p]
        lib.imgdec_destroy.argtypes = [ctypes.c_void_p]
        lib._img_typed = True
    return lib
