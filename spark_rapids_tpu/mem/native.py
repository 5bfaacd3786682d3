"""ctypes binding to the native OOM state machine (native/oom_state.cpp).

Builds the shared library on demand with g++ from the committed source
(the ``.so`` is git-ignored, never shipped); `load()` returns None when the
build fails so the Python twin in manager.py keeps working — same pattern
as the reference where RmmSpark is mandatory native but our runtime
degrades. The degradation is logged with the compiler's words, never
silent: ``MemoryManager.state_machine`` says which twin is in use.
"""
from __future__ import annotations

import ctypes
import logging
import os
import subprocess
import threading
from typing import Optional, Sequence

__all__ = ["load", "NativeOomState", "build_shared_lib"]

log = logging.getLogger(__name__)

_NATIVE_DIR = os.path.abspath(os.path.join(os.path.dirname(__file__),
                                           "..", "native"))
_LOCK = threading.Lock()
_lib = None          # tpulint: guarded-by _LOCK
_tried = False       # tpulint: guarded-by _LOCK


def build_shared_lib(stem: str, extra_flags: Sequence[str] = ()
                     ) -> Optional[str]:
    """``native/<stem>.cpp`` -> ``native/lib<stem>.so`` (rebuilt when the
    source is newer). The compile lands under a per-process name and is
    renamed into place, so concurrent first users (pytest workers) never
    load a half-written library. Returns None, with a logged warning
    carrying the compiler's output, when the build fails."""
    src = os.path.join(_NATIVE_DIR, stem + ".cpp")
    so = os.path.join(_NATIVE_DIR, f"lib{stem}.so")
    if os.path.exists(so) and os.path.getmtime(so) >= os.path.getmtime(src):
        return so
    tmp = f"{so}.{os.getpid()}.tmp"
    try:
        subprocess.run(["g++", "-O2", "-fPIC", "-shared", "-std=c++17",
                        *extra_flags, src, "-o", tmp], check=True,
                       capture_output=True, timeout=120)
        os.replace(tmp, so)
        return so
    except (OSError, subprocess.SubprocessError) as e:
        detail = getattr(e, "stderr", b"") or b""
        log.warning("native build of %s failed (%s: %s)%s; the Python "
                    "twin is used instead", src, type(e).__name__, e,
                    ": " + detail.decode("utf-8", "replace")[-2000:]
                    if detail else "")
        try:
            os.remove(tmp)
        except OSError:
            pass
        return None


def load():
    global _lib, _tried
    with _LOCK:
        if _tried:
            return _lib
        _tried = True
        so = build_shared_lib("oom_state", ("-pthread",))
        if so is None:
            return None
        lib = ctypes.CDLL(so)
        i64, lng = ctypes.c_int64, ctypes.c_long
        lib.oom_init.argtypes = [i64]
        lib.oom_set_budget.argtypes = [i64]
        lib.oom_register_thread.argtypes = [i64, lng]
        lib.oom_unregister_thread.argtypes = [i64]
        lib.oom_reserve.argtypes = [i64, i64, lng]
        lib.oom_reserve.restype = ctypes.c_int
        lib.oom_release.argtypes = [i64]
        lib.oom_host_reserve.argtypes = [i64]
        lib.oom_host_release.argtypes = [i64]
        lib.oom_force_retry_oom.argtypes = [i64, lng, lng]
        lib.oom_force_split_and_retry_oom.argtypes = [i64, lng, lng]
        for f in ("oom_get_used", "oom_get_max_used", "oom_get_host_used",
                  "oom_get_budget"):
            getattr(lib, f).restype = i64
        lib.oom_get_blocked_threads.restype = lng
        lib.oom_get_retry_count.argtypes = [i64]
        lib.oom_get_retry_count.restype = lng
        lib.oom_get_split_count.argtypes = [i64]
        lib.oom_get_split_count.restype = lng
        lib.oom_get_blocked_ns.argtypes = [i64]
        lib.oom_get_blocked_ns.restype = i64
        _lib = lib
        return _lib


class NativeOomState:
    """Thin OO wrapper used by MemoryManager when the native lib loads."""

    def __init__(self, budget: int):
        self.lib = load()
        assert self.lib is not None
        self.lib.oom_init(budget)

    def reserve(self, nbytes: int, block_ms: int = 0) -> int:
        return self.lib.oom_reserve(threading.get_ident(), nbytes, block_ms)

    def release(self, nbytes: int):
        self.lib.oom_release(nbytes)

    def force_retry_oom(self, num: int = 1, skip: int = 0, tid=None):
        self.lib.oom_force_retry_oom(
            tid if tid is not None else threading.get_ident(), num, skip)

    def force_split_and_retry_oom(self, num: int = 1, skip: int = 0,
                                  tid=None):
        self.lib.oom_force_split_and_retry_oom(
            tid if tid is not None else threading.get_ident(), num, skip)

    def clear_injections(self):
        self.lib.oom_clear_injections()

    @property
    def used(self) -> int:
        return self.lib.oom_get_used()

    @property
    def max_used(self) -> int:
        return self.lib.oom_get_max_used()

    @property
    def blocked_threads(self) -> int:
        return self.lib.oom_get_blocked_threads()

    def retry_count(self, tid=None) -> int:
        return self.lib.oom_get_retry_count(
            tid if tid is not None else threading.get_ident())

    def blocked_ns(self, tid=None) -> int:
        return self.lib.oom_get_blocked_ns(
            tid if tid is not None else threading.get_ident())
