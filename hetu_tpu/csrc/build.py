"""Build + load the native cores (ctypes, cached .so).

The reference ships its solver as a pybind11 extension
(``tools/Galvatron/csrc/dp_core.cpp``); here we compile a plain C-ABI
shared library with g++ at first use and bind it with ctypes — no
pybind11 needed.  The ``.so`` is keyed on a hash of its sources (in the
file name), so a binary built from other sources — a stale one copied
along with the tree, whatever its mtime — is never picked up.

Callers that merely *prefer* the native path get ``None`` when it cannot
be built and take their pure-Python implementation; callers that were
*asked* for it pass ``required=True`` and get :class:`NativeBuildError`
carrying g++'s stderr.
"""
from __future__ import annotations

import ctypes
import hashlib
import os
import subprocess
import threading
from typing import Optional

_SRC_DIR = os.path.dirname(os.path.abspath(__file__))
_BUILD_DIR = os.path.join(_SRC_DIR, "_build")
_CXX = ["g++", "-O2", "-std=c++17", "-shared", "-fPIC", "-pthread"]
_LOCK = threading.Lock()
_CACHE: dict = {}


class NativeBuildError(RuntimeError):
    """A native core could not be compiled or loaded."""


def _compile(name: str, sources) -> str:
    srcs = [os.path.join(_SRC_DIR, s) for s in sources]
    h = hashlib.sha256(" ".join(_CXX).encode())
    for src in srcs:
        with open(src, "rb") as f:
            h.update(f.read())
    so_path = os.path.join(_BUILD_DIR, f"lib{name}-{h.hexdigest()[:16]}.so")
    if os.path.exists(so_path):
        return so_path
    os.makedirs(_BUILD_DIR, exist_ok=True)
    tmp = f"{so_path}.{os.getpid()}.tmp"    # concurrent builders never
    try:                                     # expose a half-written .so
        proc = subprocess.run([*_CXX, "-o", tmp, *srcs],
                              capture_output=True, text=True, timeout=120)
    except (OSError, subprocess.TimeoutExpired) as e:
        raise NativeBuildError(f"g++ did not run for lib{name}: {e}") from e
    if proc.returncode != 0:
        raise NativeBuildError(
            f"g++ failed for lib{name} (rc={proc.returncode}):\n"
            f"{proc.stderr}")
    os.replace(tmp, so_path)
    return so_path


def load_native(name: str, sources,
                required: bool = False) -> Optional[ctypes.CDLL]:
    """Build-if-missing and dlopen ``lib<name>-<source hash>.so``.  On
    failure: ``None``, or :class:`NativeBuildError` when ``required``."""
    with _LOCK:
        if name not in _CACHE:
            try:
                _CACHE[name] = ctypes.CDLL(_compile(name, sources))
            except NativeBuildError as e:
                _CACHE[name] = e
            except OSError as e:
                _CACHE[name] = NativeBuildError(
                    f"cannot load lib{name}: {e}")
        lib = _CACHE[name]
    if isinstance(lib, NativeBuildError):
        if required:
            raise lib
        return None
    return lib


def load_dataloader_core(required: bool = False) -> Optional[ctypes.CDLL]:
    lib = load_native("hetu_dataloader", ["dataloader.cc"], required)
    if lib is not None and not getattr(lib, "_hetu_sigs_set", False):
        lib.hetu_loader_create.restype = ctypes.c_void_p
        lib.hetu_loader_create.argtypes = [
            ctypes.c_void_p, ctypes.c_int64, ctypes.c_int64, ctypes.c_int32,
            ctypes.c_int32, ctypes.c_int32, ctypes.c_uint64, ctypes.c_int32,
            ctypes.c_int32, ctypes.c_int32]
        lib.hetu_loader_num_batches.restype = ctypes.c_int64
        lib.hetu_loader_num_batches.argtypes = [ctypes.c_void_p]
        lib.hetu_loader_next.restype = ctypes.c_int32
        lib.hetu_loader_next.argtypes = [ctypes.c_void_p, ctypes.c_void_p]
        lib.hetu_loader_reset.restype = None
        lib.hetu_loader_reset.argtypes = [ctypes.c_void_p, ctypes.c_uint64]
        lib.hetu_loader_destroy.restype = None
        lib.hetu_loader_destroy.argtypes = [ctypes.c_void_p]
        lib._hetu_sigs_set = True
    return lib


def load_embed_cache_core() -> Optional[ctypes.CDLL]:
    lib = load_native("hetu_embed_cache", ["embed_cache.cc"])
    if lib is not None and not getattr(lib, "_hetu_sigs_set", False):
        i64p = ctypes.POINTER(ctypes.c_int64)
        u8p = ctypes.POINTER(ctypes.c_uint8)
        lib.hetu_cache_create.restype = ctypes.c_void_p
        lib.hetu_cache_create.argtypes = [ctypes.c_int32, ctypes.c_int64]
        lib.hetu_cache_destroy.restype = None
        lib.hetu_cache_destroy.argtypes = [ctypes.c_void_p]
        lib.hetu_cache_size.restype = ctypes.c_int64
        lib.hetu_cache_size.argtypes = [ctypes.c_void_p]
        lib.hetu_cache_lookup.restype = ctypes.c_int64
        lib.hetu_cache_lookup.argtypes = [
            ctypes.c_void_p, i64p, ctypes.c_int64, i64p, u8p, i64p, i64p]
        lib._hetu_sigs_set = True
    return lib


def load_dp_core() -> Optional[ctypes.CDLL]:
    lib = load_native("hetu_dp_core", ["dp_core.cc"])
    if lib is not None and not getattr(lib, "_hetu_sigs_set", False):
        i32p = ctypes.POINTER(ctypes.c_int32)
        f64p = ctypes.POINTER(ctypes.c_double)
        lib.hetu_dp_strategy_solve.restype = ctypes.c_double
        lib.hetu_dp_strategy_solve.argtypes = [
            ctypes.c_int32, ctypes.c_int32, ctypes.c_int32,
            i32p, f64p, f64p, i32p]
        lib.hetu_dp_pipeline_partition.restype = ctypes.c_double
        lib.hetu_dp_pipeline_partition.argtypes = [
            ctypes.c_int32, ctypes.c_int32, f64p, f64p, i32p]
        lib._hetu_sigs_set = True
    return lib
