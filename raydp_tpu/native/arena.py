"""ctypes binding + on-demand build of the C++ shared-memory arena.

The C core (``csrc/store/arena.cpp``) is compiled into
``raydp_tpu/native/_lib/`` the first time a session needs it, under a name
that carries the hash of its source (:mod:`raydp_tpu.native.build`). Readers
of arena-resident objects do not need this library at all — they attach the
segment with :mod:`multiprocessing.shared_memory` and slice a zero-copy
memoryview; only writers (``rdt_alloc``) and the head's free path
(``rdt_free``) go through the native calls.
"""

from __future__ import annotations

import ctypes
import os
import threading
from typing import Dict, Optional, Tuple

from raydp_tpu.log import get_logger
from raydp_tpu.native.build import CSRC_DIR, build_library

logger = get_logger("native.arena")

_SRC = os.path.join(CSRC_DIR, "store", "arena.cpp")

_lib_lock = threading.Lock()
_lib: Optional[ctypes.CDLL] = None
_lib_failed = False


def _load() -> Optional[ctypes.CDLL]:
    global _lib, _lib_failed
    with _lib_lock:
        if _lib is not None or _lib_failed:
            return _lib
        try:
            lib = ctypes.CDLL(
                build_library(_SRC, "rdtstore", ["-lpthread", "-lrt"]))
            lib.rdt_arena_create.restype = ctypes.c_void_p
            lib.rdt_arena_create.argtypes = [ctypes.c_char_p, ctypes.c_uint64]
            lib.rdt_arena_attach.restype = ctypes.c_void_p
            lib.rdt_arena_attach.argtypes = [
                ctypes.c_char_p, ctypes.POINTER(ctypes.c_uint64)]
            lib.rdt_alloc.restype = ctypes.c_int64
            lib.rdt_alloc.argtypes = [ctypes.c_void_p, ctypes.c_uint64]
            lib.rdt_free.restype = ctypes.c_int
            lib.rdt_free.argtypes = [ctypes.c_void_p, ctypes.c_uint64]
            lib.rdt_stats.restype = None
            lib.rdt_stats.argtypes = [
                ctypes.c_void_p, ctypes.POINTER(ctypes.c_uint64)]
            lib.rdt_detach.restype = ctypes.c_int
            lib.rdt_detach.argtypes = [ctypes.c_void_p]
            lib.rdt_unlink.restype = ctypes.c_int
            lib.rdt_unlink.argtypes = [ctypes.c_char_p]
            _lib = lib
        except Exception as e:
            _lib_failed = True
            logger.warning("native store core unavailable (%s); "
                           "falling back to per-object segments", e)
        return _lib


def native_store_available() -> bool:
    return _load() is not None


class Arena:
    """One session-wide shared-memory arena holding all object payloads.

    ``segment`` is the Python-style segment name (no leading slash), the same
    name :class:`multiprocessing.shared_memory.SharedMemory` uses, so readers
    without the native library can still attach it.
    """

    def __init__(self, segment: str, base: int, size: int, owner: bool):
        self.segment = segment
        self.size = size
        self._base = base
        self._owner = owner
        self._closed = False

    # -- construction -------------------------------------------------------
    @classmethod
    def create(cls, segment: str, size: int) -> "Arena":
        lib = _load()
        if lib is None:
            raise RuntimeError("native store core unavailable")
        base = lib.rdt_arena_create(("/" + segment).encode(), size)
        if not base:
            raise RuntimeError(
                f"failed to create arena segment {segment} ({size} bytes)")
        return cls(segment, base, size, owner=True)

    @classmethod
    def attach(cls, segment: str) -> "Arena":
        lib = _load()
        if lib is None:
            raise RuntimeError("native store core unavailable")
        size = ctypes.c_uint64()
        base = lib.rdt_arena_attach(("/" + segment).encode(), ctypes.byref(size))
        if not base:
            raise RuntimeError(f"failed to attach arena segment {segment}")
        return cls(segment, base, size.value, owner=False)

    # -- allocation ---------------------------------------------------------
    def alloc(self, size: int) -> Optional[int]:
        """Payload offset for ``size`` bytes, or None if the arena is full."""
        off = _load().rdt_alloc(self._base, size)
        return None if off < 0 else off

    def free(self, offset: int) -> bool:
        return _load().rdt_free(self._base, offset) == 0

    def view(self, offset: int, size: int) -> memoryview:
        """Zero-copy writable view of the payload at ``offset``."""
        if offset < 0 or offset + size > self.size:
            raise ValueError(f"view [{offset}, {offset + size}) outside arena")
        if size == 0:
            return memoryview(b"")
        buf = (ctypes.c_ubyte * size).from_address(self._base + offset)
        return memoryview(buf).cast("B")

    def stats(self) -> Dict[str, int]:
        out = (ctypes.c_uint64 * 4)()
        _load().rdt_stats(self._base, out)
        return {"arena_size": out[0], "bytes_in_use": out[1],
                "num_allocs": out[2], "peak_bytes": out[3]}

    # -- lifetime -----------------------------------------------------------
    def detach(self) -> None:
        if not self._closed:
            self._closed = True
            _load().rdt_detach(self._base)

    def unlink(self) -> None:
        _load().rdt_unlink(("/" + self.segment).encode())

    def close(self) -> None:
        owner = self._owner
        self.detach()
        if owner:
            self.unlink()
