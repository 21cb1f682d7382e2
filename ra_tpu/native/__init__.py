"""ctypes bindings for the native WAL/IO library, with pure-Python fallback.

The library is built from ``wal_native.cpp`` with g++ on first use and
named by the SOURCE'S CONTENT (``libra_wal-<sha256 prefix>.so``, next to
the source, git-ignored): a binary left by another source revision, or
carried into a copied tree under the old fixed name, has a different
name and is never loaded.  Where the build fails — no toolchain, a
read-only package directory — the reason is logged and kept in
:data:`BUILD_ERROR`, and I/O falls back to os-level Python calls with
zlib.crc32: same semantics, lower throughput.  ``IO.native`` says which
of the two a process runs, and every durable engine repeats it in
``overview()["wal"]["io_path"]``.
"""
from __future__ import annotations

import ctypes
import hashlib
import logging
import os
import subprocess
import zlib

_HERE = os.path.dirname(os.path.abspath(__file__))
_SRC = os.path.join(_HERE, "wal_native.cpp")

_lib = None
#: why the native library is not in use (None while it is, or before
#: the first load attempt)
BUILD_ERROR = None


def _so_path() -> str:
    with open(_SRC, "rb") as f:
        digest = hashlib.sha256(f.read()).hexdigest()[:16]
    return os.path.join(_HERE, f"libra_wal-{digest}.so")


def _build(so: str) -> None:
    """Compile to a private name and rename into place, so concurrent
    first imports (soak children, test subprocesses) never load a
    half-written file."""
    tmp = f"{so}.{os.getpid()}.tmp"
    try:
        subprocess.run(["g++", "-O3", "-shared", "-fPIC", "-o", tmp, _SRC],
                       check=True, capture_output=True, timeout=120)
        os.replace(tmp, so)
    finally:
        if os.path.exists(tmp):
            os.unlink(tmp)


def _load():
    global _lib, BUILD_ERROR
    if _lib is not None or BUILD_ERROR is not None:
        return _lib
    try:
        so = _so_path()
        if not os.path.exists(so):
            _build(so)
        lib = ctypes.CDLL(so)
    except (OSError, subprocess.SubprocessError) as exc:
        detail = getattr(exc, "stderr", b"") or b""
        BUILD_ERROR = f"{type(exc).__name__}: {exc}" + (
            f": {detail.decode(errors='replace')[-400:]}" if detail else "")
        logging.getLogger("ra_tpu").warning(
            "native WAL library unavailable, using Python I/O: %s",
            BUILD_ERROR)
        return None
    lib.ra_wal_open.argtypes = [ctypes.c_char_p, ctypes.c_int]
    lib.ra_wal_open.restype = ctypes.c_int
    lib.ra_wal_open_sync.argtypes = [ctypes.c_char_p, ctypes.c_int]
    lib.ra_wal_open_sync.restype = ctypes.c_int
    lib.ra_wal_sync.argtypes = [ctypes.c_int, ctypes.c_int]
    lib.ra_wal_sync.restype = ctypes.c_int
    lib.ra_wal_write_batch.argtypes = [ctypes.c_int, ctypes.c_char_p,
                                       ctypes.c_size_t, ctypes.c_int]
    lib.ra_wal_write_batch.restype = ctypes.c_long
    lib.ra_wal_close.argtypes = [ctypes.c_int]
    lib.ra_pwrite.argtypes = [ctypes.c_int, ctypes.c_char_p,
                              ctypes.c_size_t, ctypes.c_long]
    lib.ra_pwrite.restype = ctypes.c_long
    lib.ra_pread.argtypes = [ctypes.c_int,
                             ctypes.POINTER(ctypes.c_char),
                             ctypes.c_size_t, ctypes.c_long]
    lib.ra_pread.restype = ctypes.c_long
    _lib = lib
    return _lib


class NativeIO:
    """Thin facade over the native lib (or the Python fallback).

    Records io operation counts/bytes (the ra_file_handle role,
    ra_file_handle.erl:26-40).  Plain int adds — approximate under
    concurrency, like any sampled io metric; reads via :meth:`stats`."""

    def __init__(self) -> None:
        self.lib = _load()
        self.native = self.lib is not None
        self._stats = {"reads": 0, "read_bytes": 0, "writes": 0,
                       "write_bytes": 0, "syncs": 0, "opens": 0}

    def stats(self) -> dict:
        return dict(self._stats)

    def random_open(self, path: str, truncate: bool = False) -> int:
        """Open for positioned I/O (pwrite/pread).  MUST NOT use O_APPEND:
        Linux pwrite ignores the offset on O_APPEND fds."""
        flags = os.O_CREAT | os.O_RDWR
        if truncate:
            flags |= os.O_TRUNC
        self._stats["opens"] += 1
        return os.open(path, flags, 0o644)

    # sync_mode: 0=none, 1=fdatasync, 2=fsync
    def wal_open(self, path: str, truncate: bool = False,
                 o_sync: bool = False) -> int:
        """o_sync opens the fd with O_SYNC: every write(2) is durable on
        return (the reference's `o_sync` write strategy)."""
        if self.native:
            fn = self.lib.ra_wal_open_sync if o_sync else \
                self.lib.ra_wal_open
            fd = fn(path.encode(), 1 if truncate else 0)
        else:
            flags = os.O_CREAT | os.O_RDWR | os.O_APPEND
            if o_sync:
                flags |= os.O_SYNC
            if truncate:
                flags |= os.O_TRUNC
            fd = os.open(path, flags, 0o644)
        if fd < 0:
            raise OSError(f"wal_open failed for {path}: {fd}")
        self._stats["opens"] += 1
        return fd

    def sync(self, fd: int, mode: int = 1) -> None:
        """Standalone durability syscall (sync_after_notify strategy)."""
        if mode == 0:
            return
        self._stats["syncs"] += 1
        if self.native:
            r = self.lib.ra_wal_sync(fd, mode)
            if r < 0:
                raise OSError(f"wal sync failed: errno {-r}")
            return
        if mode == 1:
            try:
                os.fdatasync(fd)
            except AttributeError:
                os.fsync(fd)
        else:
            os.fsync(fd)

    def write_batch(self, fd: int, buf: bytes, sync_mode: int = 1) -> int:
        self._stats["writes"] += 1
        self._stats["write_bytes"] += len(buf)
        if sync_mode:
            self._stats["syncs"] += 1
        if self.native:
            n = self.lib.ra_wal_write_batch(fd, buf, len(buf), sync_mode)
            if n < 0:
                raise OSError(f"wal write failed: errno {-n}")
            return n
        os.write(fd, buf)
        if sync_mode == 1:
            try:
                os.fdatasync(fd)
            except AttributeError:
                os.fsync(fd)
        elif sync_mode == 2:
            os.fsync(fd)
        return len(buf)

    def pwrite(self, fd: int, buf: bytes, off: int) -> int:
        self._stats["writes"] += 1
        self._stats["write_bytes"] += len(buf)
        if self.native:
            n = self.lib.ra_pwrite(fd, buf, len(buf), off)
            if n < 0:
                raise OSError(f"pwrite failed: errno {-n}")
            return n
        return os.pwrite(fd, buf, off)

    def pread(self, fd: int, length: int, off: int) -> bytes:
        self._stats["reads"] += 1
        self._stats["read_bytes"] += length
        if self.native:
            buf = ctypes.create_string_buffer(length)
            n = self.lib.ra_pread(fd, buf, length, off)
            if n < 0:
                raise OSError(f"pread failed: errno {-n}")
            return buf.raw[:n]
        return os.pread(fd, length, off)

    def crc32(self, data: bytes, seed: int = 0) -> int:
        # zlib.crc32 is the same polynomial (verified bit-identical vs
        # the native slice-by-8 across sizes/seeds) and beats it at every
        # size: no ctypes FFI overhead on small records (~2x) and a
        # hardware-accelerated inner loop on large ones (~2.4x at 1MB)
        return zlib.crc32(data, seed)

    def close(self, fd: int) -> None:
        if self.native:
            self.lib.ra_wal_close(fd)
        else:
            os.close(fd)


IO = NativeIO()
