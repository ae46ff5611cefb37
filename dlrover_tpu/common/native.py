"""Loader for the C++ native runtime pieces (built from ``native/``).

Every load asks ``make`` whether the ``.so`` matches its tracked ``.cc``
source (a no-op when it does), so a stale binary left in the tree is never
used.  Consumers that have a pure-Python equivalent fall back to it when
the toolchain is missing — the load says which backend is in use.
"""

from __future__ import annotations

import ctypes
import fcntl
import os
import subprocess
import threading
from typing import Optional

from dlrover_tpu.common.log import logger

_NATIVE_DIR = os.path.abspath(
    os.path.join(os.path.dirname(__file__), "..", "..", "native")
)
_LOCK = threading.Lock()
_LIBS: dict = {}


def _build(lib: str) -> Optional[str]:
    """``make <lib>``: rebuilds only when the source is newer.  Concurrent
    processes (agent + workers) may race here; g++ writes the output in
    place, so serialize on a lock file beside the Makefile."""
    path = os.path.join(_NATIVE_DIR, lib)
    try:
        with open(os.path.join(_NATIVE_DIR, ".build.lock"), "w") as lock:
            fcntl.flock(lock, fcntl.LOCK_EX)
            subprocess.run(
                ["make", "-C", _NATIVE_DIR, lib],
                check=True,
                capture_output=True,
                timeout=120,
            )
    except subprocess.CalledProcessError as e:
        logger.warning(
            "native build of %s failed: %s", lib,
            e.stderr.decode(errors="replace")[-400:],
        )
        return None
    except (subprocess.SubprocessError, OSError) as e:
        logger.warning("native build of %s failed: %s", lib, e)
        return None
    return path if os.path.exists(path) else None


def load_library(lib: str) -> Optional[ctypes.CDLL]:
    with _LOCK:
        if lib in _LIBS:
            return _LIBS[lib]
        path = _build(lib)
        handle = None
        if path:
            try:
                handle = ctypes.CDLL(path)
            except OSError as e:
                logger.warning("loading %s failed: %s", path, e)
        logger.info(
            "native %s: %s", lib,
            "loaded (built from native/ by make)" if handle is not None
            else "UNAVAILABLE, using the pure-Python backend",
        )
        _LIBS[lib] = handle
        return handle


def shm_lib() -> Optional[ctypes.CDLL]:
    lib = load_library("libshm_arena.so")
    if lib is not None and not getattr(lib, "_sigs_set", False):
        lib.shm_arena_create.restype = ctypes.c_int
        lib.shm_arena_create.argtypes = [ctypes.c_char_p, ctypes.c_uint64]
        lib.shm_arena_open.restype = ctypes.c_int
        lib.shm_arena_open.argtypes = [ctypes.c_char_p]
        lib.shm_arena_size.restype = ctypes.c_int64
        lib.shm_arena_size.argtypes = [ctypes.c_int]
        lib.shm_arena_map.restype = ctypes.c_void_p
        lib.shm_arena_map.argtypes = [ctypes.c_int, ctypes.c_uint64]
        lib.shm_arena_unmap.restype = ctypes.c_int
        lib.shm_arena_unmap.argtypes = [ctypes.c_void_p, ctypes.c_uint64]
        lib.shm_arena_unlink.restype = ctypes.c_int
        lib.shm_arena_unlink.argtypes = [ctypes.c_char_p]
        lib.shm_arena_close.restype = ctypes.c_int
        lib.shm_arena_close.argtypes = [ctypes.c_int]
        lib.shm_crc32.restype = ctypes.c_uint32
        lib.shm_crc32.argtypes = [ctypes.c_void_p, ctypes.c_uint64, ctypes.c_uint32]
        lib._sigs_set = True
    return lib


def packer_lib() -> Optional[ctypes.CDLL]:
    """Native first-fit sequence packer (``native/packer.cc``)."""
    lib = load_library("libpacker.so")
    if lib is not None and not getattr(lib, "_sigs_set", False):
        import numpy as np

        i64 = ctypes.c_int64
        i64p = np.ctypeslib.ndpointer(np.int64, flags="C_CONTIGUOUS")
        i32p = np.ctypeslib.ndpointer(np.int32, flags="C_CONTIGUOUS")
        lib.pack_first_fit.restype = i64
        lib.pack_first_fit.argtypes = [i64p, i64, i64, i32p, i32p, i32p]
        lib._sigs_set = True
    return lib
