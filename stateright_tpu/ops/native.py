"""ctypes bindings for the C++ host core (native/stateright_core.cpp).

The reference's whole runtime is native (Rust); this module provides the
C++ equivalents of its L0 hot paths — the stable fingerprint mixer and the
lock-striped concurrent visited set (the DashMap analog,
src/checker/bfs.rs:29-31) — compiled on demand with g++ and loaded through
ctypes (pybind11 is not available here).  Everything degrades gracefully:
``load()`` returns None when no toolchain is present and callers fall back
to the pure-Python implementations.
"""

from __future__ import annotations

import ctypes
import hashlib
import os
import pathlib
import subprocess
import threading
from typing import Optional

_SRC = pathlib.Path(__file__).resolve().parent.parent.parent / "native"
_OUT_DIR = pathlib.Path(__file__).resolve().parent

_lock = threading.Lock()
_lib = None
_tried = False


def _compile() -> Optional[pathlib.Path]:
    src = _SRC / "stateright_core.cpp"
    if not src.exists():
        return None
    # Keyed on the source's content, not its mtime: a copied tree keeps
    # whatever mtimes the copy gave it, and must never load a library
    # built from other source.
    digest = hashlib.sha256(src.read_bytes()).hexdigest()[:16]
    out = _OUT_DIR / f"_libstateright_core.{digest}.so"
    if out.exists():
        return out
    tmp = out.with_suffix(f".tmp{os.getpid()}.so")
    try:
        subprocess.run(
            [
                "g++",
                "-O3",
                "-shared",
                "-fPIC",
                "-std=c++17",
                str(src),
                "-o",
                str(tmp),
            ],
            check=True,
            capture_output=True,
        )
        # Atomic rename: concurrent processes never dlopen a half-written
        # library.
        os.replace(tmp, out)
    except (OSError, subprocess.CalledProcessError):
        tmp.unlink(missing_ok=True)
        return None
    return out


def load():
    """The loaded library, or None if unavailable.  Thread-safe, cached."""
    global _lib, _tried
    with _lock:
        if _tried:
            return _lib
        _tried = True
        path = _compile()
        if path is None:
            return None
        try:
            lib = ctypes.CDLL(str(path))
        except OSError:
            return None
        lib.sr_fp64_words.restype = ctypes.c_uint64
        lib.sr_fp64_words.argtypes = [
            ctypes.POINTER(ctypes.c_uint32),
            ctypes.c_uint64,
        ]
        lib.sr_fp64_batch.restype = None
        lib.sr_fp64_batch.argtypes = [
            ctypes.POINTER(ctypes.c_uint32),
            ctypes.c_uint64,
            ctypes.c_uint64,
            ctypes.POINTER(ctypes.c_uint64),
        ]
        lib.sr_fpset_new.restype = ctypes.c_void_p
        lib.sr_fpset_new.argtypes = [ctypes.c_uint64]
        lib.sr_fpset_free.restype = None
        lib.sr_fpset_free.argtypes = [ctypes.c_void_p]
        lib.sr_fpset_len.restype = ctypes.c_uint64
        lib.sr_fpset_len.argtypes = [ctypes.c_void_p]
        lib.sr_fpset_insert.restype = ctypes.c_int32
        lib.sr_fpset_insert.argtypes = [
            ctypes.c_void_p,
            ctypes.c_uint64,
            ctypes.c_uint64,
        ]
        lib.sr_fpset_get_parent.restype = ctypes.c_int32
        lib.sr_fpset_get_parent.argtypes = [
            ctypes.c_void_p,
            ctypes.c_uint64,
            ctypes.POINTER(ctypes.c_uint64),
        ]
        lib.sr_fpset_contains.restype = ctypes.c_int32
        lib.sr_fpset_contains.argtypes = [ctypes.c_void_p, ctypes.c_uint64]
        lib.sr_twophase_bfs.restype = ctypes.c_int32
        lib.sr_twophase_bfs.argtypes = [
            ctypes.c_uint32,
            ctypes.c_uint64,
            ctypes.POINTER(ctypes.c_uint64),
            ctypes.POINTER(ctypes.c_uint64),
            ctypes.POINTER(ctypes.c_uint64),
        ]
        _lib = lib
        return _lib


def fp64_words_native(words) -> Optional[int]:
    """Native mixer over a uint32 word sequence, or None if unavailable."""
    lib = load()
    if lib is None:
        return None
    import array

    try:
        buf = array.array("I", words)
    except OverflowError:
        buf = array.array("I", [w & 0xFFFFFFFF for w in words])
    addr, n = buf.buffer_info()
    return lib.sr_fp64_words(
        ctypes.cast(addr, ctypes.POINTER(ctypes.c_uint32)), n
    )


def fp64_batch_native(words_matrix) -> Optional[list]:
    """Fingerprint every row of a [count, width] uint32 matrix (C loop);
    None if the native core is unavailable."""
    lib = load()
    if lib is None:
        return None
    import numpy as np

    m = np.ascontiguousarray(words_matrix, dtype=np.uint32)
    count, width = m.shape
    out = np.empty(count, dtype=np.uint64)
    lib.sr_fp64_batch(
        m.ctypes.data_as(ctypes.POINTER(ctypes.c_uint32)),
        count,
        width,
        out.ctypes.data_as(ctypes.POINTER(ctypes.c_uint64)),
    )
    return out.tolist()


class NativeFpSet:
    """Concurrent fingerprint -> parent-fingerprint map.

    Parent 0 encodes "root / none" (fingerprints themselves are nonzero).
    Grows automatically at 3/4 load (DashMap-style), so ``capacity_pow2``
    is only the initial table size.  This is the multi-thread visited set
    of the host graph engines (core/engine.py, ``threads > 1``): inserts
    release the GIL and contend per C++ stripe lock instead of serializing
    on a Python-level lock.
    """

    __slots__ = ("_lib", "_ptr", "_capacity")

    def __init__(self, capacity_pow2: int = 1 << 16):
        lib = load()
        if lib is None:
            raise RuntimeError("native core unavailable")
        self._lib = lib
        self._capacity = capacity_pow2
        self._ptr = lib.sr_fpset_new(capacity_pow2)
        if not self._ptr:
            raise ValueError("capacity must be a nonzero power of two")

    def insert(self, fp: int, parent: int = 0) -> bool:
        """Insert-if-absent; True iff newly inserted."""
        r = self._lib.sr_fpset_insert(self._ptr, fp, parent)
        if r < 0:  # unreachable since the table grows; kept as a backstop
            raise RuntimeError("native fingerprint set insert failed")
        return bool(r)

    def __contains__(self, fp: int) -> bool:
        return bool(self._lib.sr_fpset_contains(self._ptr, fp))

    def parent(self, fp: int) -> Optional[int]:
        out = ctypes.c_uint64()
        if self._lib.sr_fpset_get_parent(self._ptr, fp, ctypes.byref(out)):
            return out.value
        return None

    def __len__(self) -> int:
        return int(self._lib.sr_fpset_len(self._ptr))

    def __del__(self):
        ptr, self._ptr = getattr(self, "_ptr", None), None
        if ptr:
            self._lib.sr_fpset_free(ptr)


def twophase_bfs_native(n_rms: int, max_unique: int = 0) -> Optional[dict]:
    """Exhaustive single-threaded C++ BFS of the direct two-phase-commit
    model (native/stateright_core.cpp: packed successor generation +
    fingerprint + open-addressing dedup, NO property evaluation) — an
    independent host baseline for the device engines' 2pc counts.
    Returns ``{"unique_states", "generated", "max_depth"}``,
    None if the native core is unavailable.  Raises on bad arguments or
    a blown ``max_unique`` memory guard (0 = unlimited)."""
    lib = load()
    if lib is None:
        return None
    unique = ctypes.c_uint64()
    generated = ctypes.c_uint64()
    depth = ctypes.c_uint64()
    rc = lib.sr_twophase_bfs(
        n_rms, max_unique, ctypes.byref(unique), ctypes.byref(generated),
        ctypes.byref(depth),
    )
    if rc != 0:
        raise RuntimeError(
            f"sr_twophase_bfs(n_rms={n_rms}, max_unique={max_unique}) "
            f"failed (rc={rc}): bad arguments or unique-state guard "
            "exceeded"
        )
    return {
        "unique_states": unique.value,
        "generated": generated.value,
        "max_depth": depth.value,
    }


def available() -> bool:
    return load() is not None
