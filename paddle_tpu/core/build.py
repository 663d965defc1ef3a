"""On-demand native build: g++ → cached shared library → ctypes.

Parity note: the reference ships compiled C++ in its wheel; this build
compiles its (small) native core at first use — same pattern as
paddle.utils.cpp_extension's JIT path (python/paddle/utils/cpp_extension/).
"""
from __future__ import annotations

import ctypes
import hashlib
import os
import subprocess
import threading
from typing import Optional

_SRC_DIR = os.path.join(os.path.dirname(__file__), "csrc")
_SOURCES = ["tcp_store.cpp", "shm_queue.cpp"]
# -lrt: shm_open/shm_unlink live in librt before glibc 2.34 (the symbol
# is in libc proper afterwards, where the flag is a harmless no-op) —
# without it the .so builds fine and then fails at dlopen with
# "undefined symbol: shm_open" on older glibc
_LINK_FLAGS = ["-lrt"]
_LOCK = threading.Lock()
_LIB: Optional[ctypes.CDLL] = None


def _cache_dir() -> str:
    d = os.environ.get("PADDLE_TPU_CACHE",
                       os.path.expanduser("~/.cache/paddle_tpu"))
    os.makedirs(d, exist_ok=True)
    return d


def _src_digest() -> str:
    h = hashlib.sha256()
    for s in _SOURCES:
        with open(os.path.join(_SRC_DIR, s), "rb") as f:
            h.update(f.read())
    # link flags are part of the identity: a cached .so built WITHOUT
    # -lrt would otherwise shadow the fixed build forever
    h.update(" ".join(_LINK_FLAGS).encode())
    return h.hexdigest()[:16]


def build_native(verbose: bool = False) -> str:
    """Compile the native core if needed; returns the .so path."""
    so = os.path.join(_cache_dir(), f"libpaddle_tpu_core_{_src_digest()}.so")
    if os.path.exists(so):
        return so
    srcs = [os.path.join(_SRC_DIR, s) for s in _SOURCES]
    tmp = so + f".build.{os.getpid()}"
    cmd = ["g++", "-O2", "-std=c++17", "-shared", "-fPIC", "-pthread",
           "-o", tmp, *srcs, *_LINK_FLAGS]
    try:
        subprocess.run(cmd, check=True, capture_output=not verbose)
    except (subprocess.CalledProcessError, FileNotFoundError) as e:
        # the compiler's own words, not just its exit code: this runs on
        # first use on every new machine, where they are the diagnosis
        detail = getattr(e, "stderr", None)
        detail = detail.decode(errors="replace").strip() if detail else ""
        raise RuntimeError(
            f"native core build failed ({' '.join(cmd)}): {e}"
            + (f"\n{detail}" if detail else "")) from e
    os.replace(tmp, so)
    return so


def load_native() -> ctypes.CDLL:
    """Build (if needed) and load the native core library."""
    global _LIB
    with _LOCK:
        if _LIB is None:
            lib = ctypes.CDLL(build_native())  # pdlint: disable=thread-blocking-under-lock -- deliberate: the one-time native cc build runs under the load lock so concurrent importers wait for ONE compile instead of racing N
            # TCP store
            lib.pd_store_server_start.restype = ctypes.c_void_p
            lib.pd_store_server_start.argtypes = [ctypes.c_int]
            lib.pd_store_server_port.restype = ctypes.c_int
            lib.pd_store_server_port.argtypes = [ctypes.c_void_p]
            lib.pd_store_server_stop.argtypes = [ctypes.c_void_p]
            lib.pd_store_client_connect.restype = ctypes.c_void_p
            lib.pd_store_client_connect.argtypes = [
                ctypes.c_char_p, ctypes.c_int, ctypes.c_double]
            lib.pd_store_client_set.restype = ctypes.c_int
            lib.pd_store_client_set.argtypes = [
                ctypes.c_void_p, ctypes.c_char_p, ctypes.c_uint32,
                ctypes.c_char_p, ctypes.c_uint32]
            lib.pd_store_client_get.restype = ctypes.c_int
            lib.pd_store_client_get.argtypes = [
                ctypes.c_void_p, ctypes.c_char_p, ctypes.c_uint32,
                ctypes.POINTER(ctypes.POINTER(ctypes.c_char)),
                ctypes.POINTER(ctypes.c_uint32), ctypes.c_double]
            lib.pd_store_client_add.restype = ctypes.c_longlong
            lib.pd_store_client_add.argtypes = [
                ctypes.c_void_p, ctypes.c_char_p, ctypes.c_uint32,
                ctypes.c_longlong]
            lib.pd_store_client_del.restype = ctypes.c_int
            lib.pd_store_client_del.argtypes = [
                ctypes.c_void_p, ctypes.c_char_p, ctypes.c_uint32]
            lib.pd_store_client_close.argtypes = [ctypes.c_void_p]
            lib.pd_store_free.argtypes = [ctypes.POINTER(ctypes.c_char)]
            # shm queue
            lib.pd_shmq_create.restype = ctypes.c_void_p
            lib.pd_shmq_create.argtypes = [ctypes.c_char_p, ctypes.c_uint64]
            lib.pd_shmq_open.restype = ctypes.c_void_p
            lib.pd_shmq_open.argtypes = [ctypes.c_char_p]
            lib.pd_shmq_push.restype = ctypes.c_int
            lib.pd_shmq_push.argtypes = [
                ctypes.c_void_p, ctypes.c_char_p, ctypes.c_uint64,
                ctypes.c_double]
            lib.pd_shmq_pop.restype = ctypes.c_int64
            lib.pd_shmq_pop.argtypes = [
                ctypes.c_void_p, ctypes.POINTER(ctypes.POINTER(ctypes.c_char)),
                ctypes.c_double]
            lib.pd_shmq_count.restype = ctypes.c_uint64
            lib.pd_shmq_count.argtypes = [ctypes.c_void_p]
            lib.pd_shmq_close_writers.argtypes = [ctypes.c_void_p]
            lib.pd_shmq_free.argtypes = [ctypes.POINTER(ctypes.c_char)]
            lib.pd_shmq_close.argtypes = [ctypes.c_void_p]
            _LIB = lib
    return _LIB
