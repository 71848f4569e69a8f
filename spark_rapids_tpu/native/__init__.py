"""Native (C++) runtime bindings.

The reference keeps its hot host-side runtime in native code
(spark-rapids-jni: kudo serializer, RmmSpark allocator surface — SURVEY.md
§2.11). Here the equivalents live in ``native/*.cpp``, compiled on demand
with g++ into one shared library and bound via ctypes (no pybind11 in this
environment). Every native entry point has a pure-Python fallback at its
call site, so the framework works (slower) when no toolchain is present —
``available()`` reports which path is active.
"""

from __future__ import annotations

import ctypes
import os
import subprocess
import threading

_SRC_DIR = os.path.join(os.path.dirname(__file__), "..", "..", "native")
_LIB_NAME = "libsparkrapids_tpu.so"
_lock = threading.Lock()
_lib = None
_tried = False


def _build(out_path: str) -> bool:
    srcs = [os.path.join(_SRC_DIR, f) for f in ("kudo.cpp", "hostpool.cpp")]
    if not all(os.path.exists(s) for s in srcs):
        return False
    # compile to a private temp path and os.replace into place: concurrent
    # processes must never dlopen a half-written .so or interleave linker
    # output on the shared cache path
    tmp = f"{out_path}.{os.getpid()}.tmp"
    # no -march=native: the tree (and this build product, were it not
    # rebuilt) travels to machines with a different host CPU
    cmd = ["g++", "-O3", "-shared", "-fPIC", "-std=c++17", "-o", tmp] + srcs
    try:
        subprocess.run(cmd, check=True, capture_output=True, timeout=120)
        os.replace(tmp, out_path)
        return True
    except (subprocess.SubprocessError, FileNotFoundError, OSError):
        try:
            os.unlink(tmp)
        except OSError:
            pass
        return False


def _bind(lib):
    c = ctypes
    u8p = c.POINTER(c.c_uint8)
    lib.kudo_pack_validity.argtypes = [u8p, c.c_size_t, u8p]
    lib.kudo_unpack_validity.argtypes = [u8p, c.c_size_t, u8p]
    lib.kudo_serialize_size.restype = c.c_size_t
    lib.kudo_serialize_size.argtypes = [
        c.c_uint32, c.c_uint32, c.POINTER(c.c_size_t),
        c.POINTER(u8p), c.POINTER(u8p)]
    lib.kudo_serialize_fill.restype = c.c_size_t
    lib.kudo_serialize_fill.argtypes = [
        c.c_uint32, c.c_uint32, c.POINTER(u8p), c.POINTER(c.c_size_t),
        c.POINTER(u8p), c.POINTER(u8p), u8p, u8p]
    lib.kudo_merge_sizes.restype = c.c_longlong
    lib.kudo_merge_sizes.argtypes = [
        c.POINTER(u8p), c.POINTER(c.c_size_t), c.c_int, c.c_uint32,
        c.POINTER(c.c_ulonglong)]
    lib.kudo_merge_fill.restype = c.c_int
    lib.kudo_merge_fill.argtypes = [
        c.POINTER(u8p), c.POINTER(c.c_size_t), c.c_int, c.c_uint32,
        c.POINTER(u8p), c.POINTER(u8p), c.POINTER(c.POINTER(c.c_int32))]
    lib.hostpool_create.restype = c.c_void_p
    lib.hostpool_create.argtypes = [c.c_uint64]
    lib.hostpool_destroy.argtypes = [c.c_void_p]
    lib.hostpool_alloc.restype = c.c_void_p
    lib.hostpool_alloc.argtypes = [c.c_void_p, c.c_uint64]
    lib.hostpool_free.argtypes = [c.c_void_p, c.c_void_p]
    for f in ("hostpool_in_use", "hostpool_high_watermark",
              "hostpool_capacity"):
        getattr(lib, f).restype = c.c_uint64
        getattr(lib, f).argtypes = [c.c_void_p]
    return lib


def get_lib():
    """The loaded native library, building it on first use; None if the
    toolchain/sources are unavailable."""
    global _lib, _tried
    with _lock:
        if _tried:
            return _lib
        _tried = True
        cache_dir = os.path.join(os.path.dirname(__file__), "_build")
        os.makedirs(cache_dir, exist_ok=True)
        path = os.path.join(cache_dir, _LIB_NAME)
        srcs = [os.path.join(_SRC_DIR, f)
                for f in ("kudo.cpp", "hostpool.cpp")]
        stale = (not os.path.exists(path)
                 or any(os.path.exists(s)
                        and os.path.getmtime(s) > os.path.getmtime(path)
                        for s in srcs))
        if stale and not _build(path):
            return None
        try:
            _lib = _bind(ctypes.CDLL(path))
        except OSError:
            _lib = None
        return _lib


def available() -> bool:
    return get_lib() is not None
