"""Native runtime loader: builds (once) and binds csrc/ via ctypes.

The reference's native runtime is compiled into libpaddle; here the native
pieces (TCPStore rendezvous, DataLoader batch assembly) compile on first use
with the system toolchain and load with ctypes — no pybind11 in this image.
Everything gates gracefully: ``available()`` is False when no compiler
exists, and every consumer has a pure-Python fallback.
"""
from __future__ import annotations

import ctypes
import os
import subprocess
import threading
from ..analysis import locksan

_LOCK = locksan.Lock("native.load")
_LIB = None
_TRIED = False

_CSRC = os.path.join(os.path.dirname(os.path.dirname(
    os.path.dirname(os.path.abspath(__file__)))), "csrc")
_SO = os.path.join(_CSRC, "build", "libpaddle_tpu_native.so")


def _build():
    r = subprocess.run(["make", "-C", _CSRC], capture_output=True, text=True,
                       timeout=300)
    if r.returncode != 0:
        raise RuntimeError(f"native build failed:\n{r.stdout}\n{r.stderr}")


def load():
    """The bound library, or None if it can't be built here."""
    global _LIB, _TRIED
    with _LOCK:
        if _LIB is not None or _TRIED:
            return _LIB
        _TRIED = True
        try:
            # always through make: its dependency check is cheap, and a
            # binary this checkout did not build is never loaded
            _build()
            lib = ctypes.CDLL(_SO)
            _bind(lib)  # AttributeError here = stale-ABI binary
        except (OSError, RuntimeError, subprocess.SubprocessError,
                AttributeError):
            return None
        _LIB = lib
        return _LIB


def available() -> bool:
    return load() is not None


def _bind(lib):
    c = ctypes
    lib.ts_server_start.restype = c.c_void_p
    lib.ts_server_start.argtypes = [c.c_int]
    lib.ts_server_port.restype = c.c_int
    lib.ts_server_port.argtypes = [c.c_void_p]
    lib.ts_server_stop.argtypes = [c.c_void_p]
    lib.ts_connect.restype = c.c_int
    lib.ts_connect.argtypes = [c.c_char_p, c.c_int, c.c_int]
    lib.ts_set.restype = c.c_int
    lib.ts_set.argtypes = [c.c_int, c.c_char_p, c.c_uint32, c.c_char_p, c.c_uint32]
    lib.ts_get.restype = c.c_int
    lib.ts_get.argtypes = [c.c_int, c.c_char_p, c.c_uint32, c.c_char_p, c.c_uint32]
    lib.ts_add.restype = c.c_int64
    lib.ts_add.argtypes = [c.c_int, c.c_char_p, c.c_uint32, c.c_int64]
    lib.ts_wait.restype = c.c_int
    lib.ts_wait.argtypes = [c.c_int, c.c_char_p, c.c_uint32, c.c_int64]
    lib.ts_delete.restype = c.c_int
    lib.ts_delete.argtypes = [c.c_int, c.c_char_p, c.c_uint32]
    lib.ts_close.argtypes = [c.c_int]

    lib.bt_create.restype = c.c_void_p
    lib.bt_create.argtypes = [c.c_int64, c.c_int, c.c_int64]
    lib.bt_add_source.argtypes = [c.c_void_p, c.c_char_p, c.c_uint64]
    lib.bt_start.argtypes = [c.c_void_p, c.POINTER(c.c_int64), c.c_int64]
    lib.bt_num_batches.restype = c.c_int64
    lib.bt_num_batches.argtypes = [c.c_void_p]
    lib.bt_next.restype = c.c_int64
    lib.bt_next.argtypes = [c.c_void_p, c.POINTER(c.c_char_p), c.c_uint64]
    lib.bt_destroy.argtypes = [c.c_void_p]
