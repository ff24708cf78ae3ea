"""Native (C++) components of ray_tpu — built with g++ at first import and
cached next to the sources (no pybind11 in this image; plain C ABI via
ctypes). See shm_store.cc for the object-store arena."""
from __future__ import annotations

import ctypes
import hashlib
import os
import subprocess
import threading
from typing import Optional

_DIR = os.path.dirname(os.path.abspath(__file__))
_SRC = os.path.join(_DIR, "shm_store.cc")


def _lib_path() -> str:
    """The library is named by the digest of its source, so a build left
    by another version of the source (a copied tree keeps no useful
    mtimes) is never loaded in its place."""
    with open(_SRC, "rb") as f:
        digest = hashlib.sha1(f.read()).hexdigest()[:12]
    return os.path.join(_DIR, f"libshm_store.{digest}.so")


_LIB = _lib_path()
_lock = threading.Lock()
_lib: Optional[ctypes.CDLL] = None
_build_failed = False


def _build() -> bool:
    # Per-process tmp name: N workers may race to build on a fresh checkout,
    # and two compilers writing one inode would publish a corrupt .so.
    tmp = f"{_LIB}.{os.getpid()}.tmp"
    cmd = ["g++", "-O2", "-fPIC", "-shared", "-std=c++17", "-o", tmp, _SRC,
           "-lpthread"]
    try:
        subprocess.run(cmd, check=True, capture_output=True, timeout=120)
        os.replace(tmp, _LIB)
        return True
    except Exception:  # noqa: BLE001 — fall back to the pure-python store
        try:
            os.unlink(tmp)
        except OSError:
            pass
        return False


def load_shm_store() -> Optional[ctypes.CDLL]:
    """Load (building if needed) the arena library; None if unavailable."""
    global _lib, _build_failed
    with _lock:
        if _lib is not None:
            return _lib
        if _build_failed:
            return None
        if not os.path.exists(_LIB):
            if not _build():
                _build_failed = True
                return None
        try:
            lib = ctypes.CDLL(_LIB)
        except OSError:
            _build_failed = True
            return None
        lib.rtpu_arena_create.restype = ctypes.c_void_p
        lib.rtpu_arena_create.argtypes = [ctypes.c_char_p, ctypes.c_uint64]
        lib.rtpu_arena_attach.restype = ctypes.c_void_p
        lib.rtpu_arena_attach.argtypes = [ctypes.c_char_p]
        lib.rtpu_arena_alloc.restype = ctypes.c_uint64
        lib.rtpu_arena_alloc.argtypes = [ctypes.c_void_p, ctypes.c_uint64]
        lib.rtpu_arena_free.restype = None
        lib.rtpu_arena_free.argtypes = [ctypes.c_void_p, ctypes.c_uint64]
        lib.rtpu_arena_base.restype = ctypes.c_void_p
        lib.rtpu_arena_base.argtypes = [ctypes.c_void_p]
        lib.rtpu_arena_size.restype = ctypes.c_uint64
        lib.rtpu_arena_size.argtypes = [ctypes.c_void_p]
        lib.rtpu_arena_used.restype = ctypes.c_uint64
        lib.rtpu_arena_used.argtypes = [ctypes.c_void_p]
        lib.rtpu_arena_num_allocs.restype = ctypes.c_uint64
        lib.rtpu_arena_num_allocs.argtypes = [ctypes.c_void_p]
        lib.rtpu_arena_close.restype = None
        lib.rtpu_arena_close.argtypes = [ctypes.c_void_p, ctypes.c_int]
        _lib = lib
        return lib


class Arena:
    """Thin OO wrapper over the C ABI. Owners allocate/free; attachers only
    read. ``view(offset, size)`` is a zero-copy memoryview into the shm."""

    def __init__(self, handle: int, lib: ctypes.CDLL, name: str, owner: bool):
        self._h = handle
        self._lib = lib
        self.name = name
        self.owner = owner
        base = lib.rtpu_arena_base(ctypes.c_void_p(handle))
        size = lib.rtpu_arena_size(ctypes.c_void_p(handle))
        self._mem = memoryview(
            (ctypes.c_ubyte * size).from_address(base)).cast("B")
        self._closed = False

    # -- construction -------------------------------------------------------
    @classmethod
    def create(cls, name: str, size: int) -> Optional["Arena"]:
        lib = load_shm_store()
        if lib is None:
            return None
        h = lib.rtpu_arena_create(name.encode(), size)
        if not h:
            return None
        return cls(h, lib, name, owner=True)

    @classmethod
    def attach(cls, name: str) -> Optional["Arena"]:
        lib = load_shm_store()
        if lib is None:
            return None
        h = lib.rtpu_arena_attach(name.encode())
        if not h:
            return None
        return cls(h, lib, name, owner=False)

    # -- allocator ----------------------------------------------------------
    def alloc(self, size: int) -> int:
        """Returns payload offset, or 0 if the arena is full."""
        return self._lib.rtpu_arena_alloc(ctypes.c_void_p(self._h), size)

    def free(self, offset: int) -> None:
        self._lib.rtpu_arena_free(ctypes.c_void_p(self._h), offset)

    def view(self, offset: int, size: int) -> memoryview:
        return self._mem[offset:offset + size]

    @property
    def buf(self) -> memoryview:
        """Whole-arena view (SharedMemory.buf-compatible)."""
        return self._mem

    @property
    def used_bytes(self) -> int:
        return self._lib.rtpu_arena_used(ctypes.c_void_p(self._h))

    @property
    def num_allocs(self) -> int:
        return self._lib.rtpu_arena_num_allocs(ctypes.c_void_p(self._h))

    # -- lifecycle ----------------------------------------------------------
    def unlink_only(self) -> None:
        """Remove the shm name WITHOUT unmapping — the safe shutdown path
        when zero-copy arrays may still be alive in this process (munmap
        under a live view is a SIGSEGV; the mapping dies with the process
        and the kernel reclaims memory once all mappings drop)."""
        self._closed = True
        if self.owner:
            try:
                os.unlink(f"/dev/shm/{self.name.lstrip('/')}")
            except OSError:
                pass

    def close(self, unlink: bool = False) -> None:
        if self._closed:
            return
        self._closed = True
        try:
            self._mem.release()
        except BufferError:
            # Zero-copy views are still exported somewhere; leave the
            # mapping in place (process is usually exiting) but still remove
            # the shm name so the memory is reclaimed once mappings drop.
            if unlink and self.owner:
                try:
                    os.unlink(f"/dev/shm/{self.name.lstrip('/')}")
                except OSError:
                    pass
            return
        self._lib.rtpu_arena_close(ctypes.c_void_p(self._h),
                                   1 if unlink else 0)

    def __del__(self):
        try:
            self.close(unlink=False)
        except Exception:  # noqa: BLE001 — interpreter teardown
            pass
