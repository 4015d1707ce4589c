"""Build/load the native library (no pybind11 in this image — plain C ABI
via ctypes; g++ is in the base toolchain).

Usage:
    python -m dotaclient_tpu.native.build        # compile libdota_native.so
"""

from __future__ import annotations

import ctypes
import os
import subprocess
import sys
import threading
from typing import Optional

_DIR = os.path.dirname(os.path.abspath(__file__))
_SRC = os.path.join(_DIR, "rollout_codec.cc")
_LIB = os.path.join(_DIR, "libdota_native.so")
_lock = threading.Lock()
_lib: Optional[ctypes.CDLL] = None
_load_failed = False


def build(force: bool = False, out: Optional[str] = None) -> str:
    """Compile the shared library if missing/stale; returns its path."""
    out = out or _LIB
    with _lock:
        if (
            not force
            and os.path.exists(out)
            and os.path.getmtime(out) >= os.path.getmtime(_SRC)
        ):
            return out
        # compile beside the target and rename into place: the library is
        # not in git, so on a fresh checkout a learner and its actors all
        # build at once and none may dlopen a half-written file
        tmp = f"{out}.build.{os.getpid()}"
        cmd = [
            "g++", "-O2", "-shared", "-fPIC", "-std=c++17",
            "-o", tmp, _SRC,
        ]
        try:
            subprocess.run(cmd, check=True, capture_output=True)
            os.replace(tmp, out)
        finally:
            if os.path.exists(tmp):
                os.unlink(tmp)
        return out


class TensorEntry(ctypes.Structure):
    _fields_ = [
        ("name_off", ctypes.c_uint32), ("name_len", ctypes.c_uint32),
        ("dtype_off", ctypes.c_uint32), ("dtype_len", ctypes.c_uint32),
        ("data_off", ctypes.c_uint32), ("data_len", ctypes.c_uint32),
        ("shape", ctypes.c_int32 * 8), ("ndim", ctypes.c_int32),
    ]


class RolloutHeader(ctypes.Structure):
    _fields_ = [
        ("model_version", ctypes.c_int32), ("env_id", ctypes.c_int32),
        ("rollout_id", ctypes.c_uint64), ("length", ctypes.c_int32),
        ("total_reward", ctypes.c_float),
    ]


class EncodeTensor(ctypes.Structure):
    _fields_ = [
        ("name_off", ctypes.c_uint32), ("name_len", ctypes.c_uint32),
        ("dtype_off", ctypes.c_uint32), ("dtype_len", ctypes.c_uint32),
        ("data_ptr", ctypes.c_uint64), ("data_len", ctypes.c_uint64),
        ("shape", ctypes.c_int32 * 8), ("ndim", ctypes.c_int32),
    ]


def load_library(auto_build: bool = True) -> Optional[ctypes.CDLL]:
    """Load (building if needed) the native library; None if unavailable —
    the callers then use the Python codec, and the reason (the compiler's
    own error for a failed build) is printed once."""
    global _lib, _load_failed
    if _lib is not None or _load_failed:
        return _lib
    try:
        if auto_build:
            build()
        lib = ctypes.CDLL(_LIB)
        if auto_build and not hasattr(lib, "dota_encode_rollout"):
            # Stale artifact with equal mtimes (image COPY, tarball): the
            # mtime check skipped the rebuild but the symbol set is old —
            # and dlopen caches by file, so rebuilding onto the SAME path
            # cannot refresh this process's handle. Compile to a fresh
            # path, load that, and promote it for future processes; if the
            # rebuild fails, keep the stale handle (decode still works —
            # the encode wrapper probes for its symbol before use).
            fresh = f"{_LIB}.fresh.{os.getpid()}"
            try:
                build(force=True, out=fresh)
                lib = ctypes.CDLL(fresh)
                os.replace(fresh, _LIB)
            except (OSError, subprocess.CalledProcessError):
                try:
                    os.unlink(fresh)
                except OSError:
                    pass
        lib.dota_decode_rollout.restype = ctypes.c_int32
        lib.dota_decode_rollout.argtypes = [
            # void* (not char*): callers pass bytes directly OR a raw
            # pointer into a memoryview (the shm lane's zero-copy frames)
            ctypes.c_void_p, ctypes.c_uint64,
            ctypes.POINTER(RolloutHeader),
            ctypes.POINTER(TensorEntry), ctypes.c_int32,
        ]
        if hasattr(lib, "dota_encode_rollout"):  # absent on a stale handle
            lib.dota_encode_rollout.restype = ctypes.c_int64
            lib.dota_encode_rollout.argtypes = [
                ctypes.POINTER(RolloutHeader), ctypes.c_char_p,
                ctypes.POINTER(EncodeTensor), ctypes.c_int32,
                ctypes.c_void_p, ctypes.c_uint64,
            ]
        _lib = lib
    except (
        OSError,   # no g++ (FileNotFoundError) or an unloadable library
        subprocess.CalledProcessError,
        AttributeError,  # unbuildable stale library missing a symbol
    ) as e:
        _load_failed = True   # latched: the report below prints once
        _lib = None
        stderr = getattr(e, "stderr", None) or b""
        print(
            f"dotaclient_tpu.native: C++ rollout codec unavailable, using "
            f"the Python codec ({type(e).__name__}: {e})\n"
            f"{stderr.decode(errors='replace').rstrip()}",
            file=sys.stderr, flush=True,
        )
    return _lib


if __name__ == "__main__":
    print(build(force=True))
