"""Native (C++) runtime components.

``SharedArena`` is a process-shared memory allocator used as the data plane of
the process executor - the TPU-host replacement for the reference's ZeroMQ
transport (petastorm/workers_pool/process_pool.py:52-74).  Workers copy column
payloads into the arena once; the consumer wraps them as numpy arrays with zero
additional copies and frees the block when the arrays are garbage collected.
"""

from __future__ import annotations

import ctypes
import logging
import threading
from multiprocessing import resource_tracker, shared_memory
from typing import Optional

logger = logging.getLogger(__name__)

def _configure_arena(lib: ctypes.CDLL) -> None:
    lib.psa_init.argtypes = [ctypes.c_void_p, ctypes.c_uint64]
    lib.psa_init.restype = ctypes.c_int
    lib.psa_check.argtypes = [ctypes.c_void_p]
    lib.psa_check.restype = ctypes.c_int
    lib.psa_alloc.argtypes = [ctypes.c_void_p, ctypes.c_uint64]
    lib.psa_alloc.restype = ctypes.c_int64
    lib.psa_free.argtypes = [ctypes.c_void_p, ctypes.c_uint64]
    lib.psa_free.restype = ctypes.c_int
    lib.psa_free_bytes.argtypes = [ctypes.c_void_p]
    lib.psa_free_bytes.restype = ctypes.c_uint64
    lib.psa_largest_free.argtypes = [ctypes.c_void_p]
    lib.psa_largest_free.restype = ctypes.c_uint64


def _load_lib() -> Optional[ctypes.CDLL]:
    from petastorm_tpu.native.build import load_library

    return load_library("shm_arena", _configure_arena)


def is_available() -> bool:
    """True if the zero-copy transport plane works here: the native library
    builds."""
    return transport_availability()["available"]


def transport_availability() -> dict:
    """``{"available": bool, "reason": str}`` for the zero-copy transport
    plane - the *why* behind :func:`is_available`, surfaced in
    ``Reader.diagnostics['native']['shm_transport']`` and the service
    client's hello log so a silently dark fast path is observable instead
    of just slow."""
    if not allocator_available():
        return {"available": False,
                "reason": "native shm_arena library unavailable (no"
                          " C++ toolchain? see petastorm_tpu.native.build)"}
    return {"available": True, "reason": "ok"}


def allocator_available() -> bool:
    """True if the C arena allocator itself is usable."""
    return _load_lib() is not None


#: serializes the resource-tracker monkeypatch below: two concurrent
#: attaches (e.g. a warm-cache lazy attach in a worker racing the transport
#: arena attach) could otherwise interleave save/restore and leave the
#: suppressed register installed process-wide permanently
_ATTACH_LOCK = threading.Lock()


def attach_shared_memory(name: str) -> shared_memory.SharedMemory:
    """Attach an existing named segment WITHOUT registering it with the
    resource tracker.

    python<3.13 registers even *attached* segments with the resource tracker,
    which would unlink the creator's segment when this process exits (and
    sending unregister instead races other attachers into KeyErrors inside
    the shared tracker).  Suppress the registration during the constructor
    call - the creator's own registration is the only one that should exist.
    """
    with _ATTACH_LOCK:
        orig_register = resource_tracker.register

        def _no_shm_register(rname, rtype):
            if rtype != "shared_memory":
                orig_register(rname, rtype)

        resource_tracker.register = _no_shm_register
        try:
            return shared_memory.SharedMemory(name=name, create=False)
        finally:
            resource_tracker.register = orig_register


class SharedArena:
    """One shared-memory segment + the C allocator over it.

    The creator (consumer process) calls ``SharedArena.create``; workers attach
    by name with ``SharedArena.attach``.  Python's SharedMemory handles segment
    lifetime; the C library handles allocation inside it.
    """

    def __init__(self, shm: shared_memory.SharedMemory, owner: bool):
        lib = _load_lib()
        if lib is None:
            raise RuntimeError("native shm_arena library unavailable")
        self._lib = lib
        self._shm = shm
        self._owner = owner
        self._closed = False    # allocation disabled (close requested)
        self._unmapped = False  # segment actually unmapped
        self._base = ctypes.addressof(ctypes.c_char.from_buffer(shm.buf))

    # -- lifecycle ------------------------------------------------------------

    @classmethod
    def create(cls, size_bytes: int, name: Optional[str] = None) -> "SharedArena":
        shm = shared_memory.SharedMemory(name=name, create=True, size=size_bytes)
        arena = cls(shm, owner=True)
        rc = arena._lib.psa_init(arena._base, shm.size)
        if rc != 0:
            shm.close()
            shm.unlink()
            raise RuntimeError(f"psa_init failed: {rc}")
        return arena

    @classmethod
    def attach(cls, name: str) -> "SharedArena":
        shm = attach_shared_memory(name)
        arena = cls(shm, owner=False)
        if not arena._lib.psa_check(arena._base):
            raise RuntimeError(f"shared arena {name!r} is not initialized")
        return arena

    @property
    def name(self) -> str:
        return self._shm.name

    @property
    def size(self) -> int:
        return self._shm.size

    @property
    def closed(self) -> bool:
        return self._closed

    def disown(self) -> None:
        """Give up unlink responsibility: ``close()`` will detach this
        process's mapping but leave the named segment alive for other
        attached processes (the warm-cache tier's lifecycle - the segment
        outlives any single reader; the creator process's resource-tracker
        registration still reclaims it at process exit)."""
        self._owner = False

    def close(self) -> None:
        """Unmap (and unlink, if owner) the segment.  If zero-copy batch views
        are still alive the close is deferred: allocation is disabled
        immediately, and a later close()/__del__ retries the unmap."""
        if self._unmapped:
            return
        # ctypes.from_buffer holds an export on shm.buf; drop it before close
        self._base = None
        self._closed = True  # no new allocs/frees; leases skip free from now on
        import gc

        gc.collect()
        try:
            self._shm.close()
        except BufferError:
            # numpy views into the segment are still alive somewhere; keep the
            # mapping open and retry on the next close()/__del__
            logger.debug("arena %s still has live views; deferring close",
                         self._shm.name)
            return
        self._unmapped = True
        if self._owner:
            self._owner = False  # unlink exactly once
            try:
                self._shm.unlink()
            except FileNotFoundError:
                pass

    def __del__(self):  # best-effort; explicit close() is the supported path
        try:
            self.close()
        except Exception:  # noqa: BLE001
            pass

    # -- allocation -----------------------------------------------------------

    def alloc(self, size: int) -> Optional[int]:
        """Payload offset, or None when the arena is currently full."""
        if self._closed:
            raise RuntimeError("arena is closed")
        off = self._lib.psa_alloc(self._base, size)
        if off == -2:
            raise RuntimeError("shared arena corrupted")
        return None if off < 0 else int(off)

    def free(self, offset: int) -> None:
        if self._closed:  # teardown already reclaimed everything
            return
        rc = self._lib.psa_free(self._base, offset)
        if rc != 0:
            raise RuntimeError(f"psa_free({offset}) failed: {rc}")

    def free_bytes(self) -> int:
        if self._closed:
            return 0
        return int(self._lib.psa_free_bytes(self._base))

    def largest_free(self) -> int:
        if self._closed:
            return 0
        return int(self._lib.psa_largest_free(self._base))

    def view(self, offset: int, size: int) -> memoryview:
        """Writable view of a payload region (no ownership transfer)."""
        if self._closed:
            raise RuntimeError("arena is closed")
        return self._shm.buf[offset:offset + size]
