"""ctypes surface over the C++ transfer agent (native/transfer/agent.cpp).

The library builds on demand with `make -C native` (g++ is in the image;
pybind11 is not, hence the C ABI + ctypes). `make` runs once per process
BEFORE the library is loaded, whether or not a binary is already on disk —
the Makefile decides staleness, so what runs is built from
native/transfer/agent.cpp as it stands, never a leftover
native/build/*.so (the directory is git-ignored but travels with a copied
working tree). Everything degrades gracefully:
``native_available()`` is False when the toolchain or build is missing and
callers fall back to the Python request-plane transfer path.

Blocking native calls (`dtpu_fetch`) release the GIL for their full duration
(ctypes does this for foreign calls), so multi-MB fetches run concurrently
with the engine loop.
"""

from __future__ import annotations

import ctypes
import os
import subprocess
import threading
from typing import Optional, Sequence

import numpy as np

from ..runtime.faults import FAULTS
from ..runtime.logging import get_logger

log = get_logger("transfer.native")

_REPO_ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
_NATIVE_DIR = os.path.join(_REPO_ROOT, "native")
_LIB_PATH = os.path.join(_NATIVE_DIR, "build", "libdtpu_transfer.so")

_lib = None
_lib_lock = threading.Lock()
_built = False          # `make -C native` succeeded in this process
_build_failed = False
_build_thread: Optional[threading.Thread] = None
# arenas whose agent teardown leaked its threads: kept alive forever so the
# leaked writev path can never read freed memory
_LEAKED_ARENAS: list = []


def _build() -> bool:
    global _built, _build_failed
    try:
        subprocess.run(
            ["make", "-C", _NATIVE_DIR],
            check=True, capture_output=True, timeout=120,
        )
        _built = True
        return True
    except Exception as e:
        log.warning("native transfer build failed (%s); using python path", e)
        _build_failed = True
        return False


def _load(build: bool = True) -> Optional[ctypes.CDLL]:
    global _lib, _build_failed
    if _lib is not None:
        return _lib
    with _lib_lock:
        if _lib is not None or _build_failed:
            return _lib
        if not _built and (not build or not _build()):
            return None
        try:
            lib = ctypes.CDLL(_LIB_PATH)
        except OSError as e:
            log.warning("native transfer load failed (%s); using python path", e)
            _build_failed = True
            return None
        lib.dtpu_agent_new.restype = ctypes.c_void_p
        lib.dtpu_agent_new.argtypes = [ctypes.c_char_p, ctypes.c_int]
        lib.dtpu_agent_port.restype = ctypes.c_int
        lib.dtpu_agent_port.argtypes = [ctypes.c_void_p]
        lib.dtpu_agent_register.restype = ctypes.c_int
        lib.dtpu_agent_register.argtypes = [
            ctypes.c_void_p, ctypes.c_uint64, ctypes.c_void_p,
            ctypes.c_uint64, ctypes.c_uint64,
        ]
        lib.dtpu_agent_unregister.restype = ctypes.c_int
        lib.dtpu_agent_unregister.argtypes = [ctypes.c_void_p, ctypes.c_uint64]
        lib.dtpu_agent_free.restype = ctypes.c_int  # 0 freed, 1 leaked
        lib.dtpu_agent_free.argtypes = [ctypes.c_void_p]
        lib.dtpu_fetch.restype = ctypes.c_longlong
        lib.dtpu_fetch.argtypes = [
            ctypes.c_char_p, ctypes.c_int, ctypes.c_uint64,
            ctypes.POINTER(ctypes.c_uint64), ctypes.c_uint64,
            ctypes.c_void_p, ctypes.c_uint64,
        ]
        _lib = lib
        return _lib


def native_available() -> bool:
    """True iff the native library is usable NOW. Never blocks the caller on
    a compile: until `make` has run in this process, it is kicked off on a
    daemon thread and this returns False until it lands (async paths — the
    engine loop, request handlers — must not stall ~seconds on `make`)."""
    global _build_thread
    if _load(build=False) is not None:
        return True
    if _build_failed:
        return False
    with _lib_lock:
        if _build_thread is None or not _build_thread.is_alive():
            _build_thread = threading.Thread(target=_build, daemon=True)
            _build_thread.start()
    return False


def ensure_native(timeout_s: float = 120.0) -> bool:
    """Blocking variant for process startup / tests: build + load."""
    del timeout_s
    return _load(build=True) is not None


class NativeAgent:
    """Serving side: registered host arenas exposed over raw TCP.

    An arena is a contiguous numpy buffer sliced into equal-size blocks; the
    agent serves scatter/gather reads of named block indices. The caller must
    keep registered arrays alive until close()."""

    def __init__(self, host: str = "0.0.0.0", port: int = 0):
        lib = _load()
        if lib is None:
            raise RuntimeError("native transfer library unavailable")
        self._lib = lib
        self._handle = lib.dtpu_agent_new(host.encode(), port)
        if not self._handle:
            raise RuntimeError(f"failed to bind transfer agent on {host}:{port}")
        self.port = lib.dtpu_agent_port(self._handle)
        self._regions = {}  # region_id -> ndarray (keepalive)

    def register(self, region_id: int, arena: np.ndarray, block_bytes: int) -> None:
        if not arena.flags["C_CONTIGUOUS"]:
            raise ValueError("arena must be C-contiguous")
        if arena.nbytes % block_bytes:
            raise ValueError("arena size must be a multiple of block_bytes")
        rc = self._lib.dtpu_agent_register(
            self._handle, region_id,
            arena.ctypes.data_as(ctypes.c_void_p),
            block_bytes, arena.nbytes // block_bytes,
        )
        if rc != 0:
            raise RuntimeError("region registration failed")
        self._regions[region_id] = arena

    def unregister(self, region_id: int) -> None:
        self._lib.dtpu_agent_unregister(self._handle, region_id)
        self._regions.pop(region_id, None)

    def close(self) -> None:
        if self._handle:
            rc = self._lib.dtpu_agent_free(self._handle)
            self._handle = None
            if rc == 1:
                # teardown leaked the agent: its connection threads may still
                # writev from our arenas, so the buffers must outlive us —
                # park them for the process lifetime instead of freeing
                log.warning(
                    "native agent leaked on close; pinning %d arena(s) for "
                    "process lifetime", len(self._regions),
                )
                _LEAKED_ARENAS.append(dict(self._regions))
            self._regions.clear()

    def __del__(self):
        try:
            self.close()
        except Exception:
            pass


def native_fetch(
    host: str,
    port: int,
    region_id: int,
    block_ids: Sequence[int],
    block_bytes: int,
) -> np.ndarray:
    """Client side: gather remote blocks into one contiguous buffer.
    Returns a uint8 array of shape [n, block_bytes]. Raises on failure.
    Runs on executor threads — the sync fault point is safe here."""
    FAULTS.inject("transfer.native_fetch")
    lib = _load()
    if lib is None:
        raise RuntimeError("native transfer library unavailable")
    n = len(block_ids)
    ids = np.asarray(block_ids, np.uint64)
    out = np.empty((n, block_bytes), np.uint8)
    got = lib.dtpu_fetch(
        host.encode(), port, region_id,
        ids.ctypes.data_as(ctypes.POINTER(ctypes.c_uint64)), n,
        out.ctypes.data_as(ctypes.c_void_p), out.nbytes,
    )
    if got != out.nbytes:
        raise RuntimeError(f"native fetch failed: rc={got}, expected {out.nbytes}")
    return out
