"""ctypes binding to the native disk spill store (native/spill_store.cpp
— the RapidsDiskStore/RapidsDiskBlockManager analog).

Spilled batches append into large slab files through a C++ block store
with CRC32 verification on read-back; one store per spill directory,
shared by every MemoryManager pointing at it. Falls back to None (with a
logged warning, mem/native.build_shared_lib) when the build fails —
SpillableBatch then uses per-batch Arrow IPC files (the pure-Python
tier).
"""
from __future__ import annotations

import ctypes
import os
import threading
from typing import Dict, Optional

from .native import build_shared_lib

__all__ = ["NativeSpillStore", "get_store"]

_LOCK = threading.Lock()
_lib = None          # tpulint: guarded-by _LOCK
_tried = False       # tpulint: guarded-by _LOCK
_stores: Dict[str, "NativeSpillStore"] = {}  # tpulint: guarded-by _LOCK


def _load_lib():
    global _lib, _tried
    with _LOCK:
        if _tried:
            return _lib
        _tried = True
        so = build_shared_lib("spill_store")
        if so is None:
            return None
        lib = ctypes.CDLL(so)
        lib.sp_open.restype = ctypes.c_void_p
        lib.sp_open.argtypes = [ctypes.c_char_p, ctypes.c_int64]
        lib.sp_write.restype = ctypes.c_int64
        lib.sp_write.argtypes = [ctypes.c_void_p, ctypes.c_char_p,
                                 ctypes.c_int64]
        lib.sp_block_size.restype = ctypes.c_int64
        lib.sp_block_size.argtypes = [ctypes.c_void_p, ctypes.c_int64]
        lib.sp_read.restype = ctypes.c_int64
        lib.sp_read.argtypes = [ctypes.c_void_p, ctypes.c_int64,
                                ctypes.c_char_p, ctypes.c_int64]
        lib.sp_free.restype = ctypes.c_int
        lib.sp_free.argtypes = [ctypes.c_void_p, ctypes.c_int64]
        lib.sp_stats.argtypes = [ctypes.c_void_p,
                                 ctypes.POINTER(ctypes.c_int64 * 4)]
        lib.sp_close.argtypes = [ctypes.c_void_p]
        _lib = lib
        return _lib


class NativeSpillStore:
    """One slab-file block store rooted at a spill directory."""

    def __init__(self, lib, handle):
        self._lib = lib
        self._h = handle
        self._lock = threading.Lock()

    def write(self, data: bytes) -> int:
        with self._lock:
            bid = self._lib.sp_write(self._h, data, len(data))
        if bid < 0:
            raise IOError("native spill write failed")
        return int(bid)

    def read(self, block_id: int) -> bytes:
        n = self._lib.sp_block_size(self._h, block_id)
        if n < 0:
            raise KeyError(f"unknown spill block {block_id}")
        buf = ctypes.create_string_buffer(int(n))
        with self._lock:
            got = self._lib.sp_read(self._h, block_id, buf, n)
        if got == -2:
            raise IOError(
                f"spill block {block_id} failed CRC verification "
                "(disk corruption)")
        if got != n:
            raise IOError(f"short read of spill block {block_id}")
        return buf.raw

    def free(self, block_id: int) -> None:
        with self._lock:
            self._lib.sp_free(self._h, block_id)

    def stats(self) -> dict:
        out = (ctypes.c_int64 * 4)()
        self._lib.sp_stats(self._h, ctypes.byref(out))
        return {"live_blocks": out[0], "live_bytes": out[1],
                "slab_files": out[2], "file_bytes": out[3]}


def _close_all():
    with _LOCK:
        for st in _stores.values():
            try:
                st._lib.sp_close(st._h)
            except Exception:
                pass
        _stores.clear()


def get_store(spill_dir: str) -> Optional[NativeSpillStore]:
    """Shared store per spill directory, or None without a toolchain.
    Slab files are pid-unique (safe for shared directories) and removed
    by sp_close at interpreter exit; files left by a CRASHED process are
    dead weight the operator reclaims by clearing the spill dir (same
    contract as the reference's disk block manager)."""
    lib = _load_lib()
    if lib is None:
        return None
    with _LOCK:
        first = not _stores
        st = _stores.get(spill_dir)
        if st is None:
            os.makedirs(spill_dir, exist_ok=True)
            h = lib.sp_open(spill_dir.encode(), 0)
            if not h:
                return None
            st = NativeSpillStore(lib, h)
            _stores[spill_dir] = st
            if first:
                import atexit
                atexit.register(_close_all)
        return st
