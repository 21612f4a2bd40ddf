"""ctypes binding for the native group-commit WAL (native/wal.cpp).

Reference behavior: the reference's WAL is raft-engine — a native log
store with batched fsync — behind the `LogStore` trait
(src/log-store/src/raft_engine/log_store.rs:46-120). `NativeWal` is a
drop-in for the Python `Wal` (same directory, same record format, same
API) with appends and group commit in C++: concurrent writers share one
fdatasync instead of paying one each.

The shared library builds on first use with g++ (cached next to the
source, named by a hash of it: utils/native_build.py). If the toolchain
is unavailable the caller falls back to the Python Wal —
`load_library()` returns None.
"""

from __future__ import annotations

import ctypes
import logging
import subprocess
import threading
from typing import Optional

from ..errors import StorageError
from .wal import Wal

logger = logging.getLogger(__name__)

_build_lock = threading.Lock()
_lib: Optional[ctypes.CDLL] = None
_lib_failed = False


def load_library() -> Optional[ctypes.CDLL]:
    """Build (if needed) + load the native WAL library; None on failure."""
    global _lib, _lib_failed
    if _lib is not None:
        return _lib
    if _lib_failed:
        return None
    with _build_lock:
        if _lib is not None:
            return _lib
        from ..utils.native_build import build_native_library
        try:
            lib = ctypes.CDLL(build_native_library("wal"))
        except (subprocess.SubprocessError, OSError) as e:
            logger.warning("native WAL build failed (%s); using Python "
                           "WAL", e)
            _lib_failed = True
            return None
        lib.wal_open.restype = ctypes.c_void_p
        lib.wal_open.argtypes = [ctypes.c_char_p, ctypes.c_uint64,
                                 ctypes.c_uint32]
        lib.wal_append.restype = ctypes.c_int64
        lib.wal_append.argtypes = [ctypes.c_void_p, ctypes.c_uint64,
                                   ctypes.c_uint32, ctypes.c_char_p,
                                   ctypes.c_uint32]
        lib.wal_wait.restype = ctypes.c_int
        lib.wal_wait.argtypes = [ctypes.c_void_p, ctypes.c_int64,
                                 ctypes.c_uint32]
        lib.wal_sync.restype = ctypes.c_int
        lib.wal_sync.argtypes = [ctypes.c_void_p]
        lib.wal_obsolete.restype = ctypes.c_int
        lib.wal_obsolete.argtypes = [ctypes.c_void_p, ctypes.c_uint64]
        lib.wal_close.restype = None
        lib.wal_close.argtypes = [ctypes.c_void_p]
        _lib = lib
        return _lib


class NativeWal(Wal):
    """Same surface as `Wal`; append/sync/obsolete run in C++.

    `sync_on_write=True` maps to "append then wait for the group-commit
    epoch" — N concurrent writers pay ONE fdatasync, not N.
    Reads (`read_from`) reuse the Python segment parser: the format is
    shared and replay is a cold path.
    """

    def __init__(self, dir_path: str, *, sync_on_write: bool = False,
                 segment_bytes: Optional[int] = None,
                 group_interval_us: int = 500):
        lib = load_library()
        if lib is None:
            raise StorageError("native WAL library unavailable")
        super().__init__(dir_path, sync_on_write=sync_on_write,
                         segment_bytes=segment_bytes)
        self._libref = lib
        self._handle = lib.wal_open(
            dir_path.encode(), self.segment_bytes, group_interval_us)
        if not self._handle:
            raise StorageError(f"wal_open failed for {dir_path}")

    # ---- overridden hot path ----
    def append_async(self, seq: int, payload: bytes,
                     schema_version: int = 0) -> int:
        """Write one record in C++; returns the native group-commit
        ticket for :meth:`wait_durable` (no durability wait here)."""
        from ..common.failpoint import fail_point
        fail_point("wal_append")
        handle = self._handle
        if handle is None:
            raise StorageError("append on closed NativeWal")
        ticket = self._libref.wal_append(handle, seq, schema_version,
                                         payload, len(payload))
        if ticket < 0:
            raise StorageError(f"wal_append failed: errno {-ticket}")
        from ..common.telemetry import increment_counter
        increment_counter("wal_bytes", len(payload))
        return ticket

    def append(self, seq: int, payload: bytes,
               schema_version: int = 0) -> None:
        ticket = self.append_async(seq, payload, schema_version)
        if self.sync_on_write:
            self._wait_ticket(ticket)

    def wait_durable(self, ticket: int) -> None:
        """Wait for the native group-commit epoch covering `ticket` —
        N concurrent writers share ONE fdatasync in C++."""
        from ..common.failpoint import fail_point
        fail_point("wal_group_commit")
        self._wait_ticket(ticket)

    def _wait_ticket(self, ticket: int) -> None:
        from ..common.failpoint import fail_point
        from ..common.telemetry import timer
        handle = self._handle
        if handle is None:
            raise StorageError("wait on closed NativeWal")
        fail_point("wal_fsync")
        with timer("wal_fsync"):
            rc = self._libref.wal_wait(handle, ticket, 30_000)
        if rc != 0:
            raise StorageError(f"wal_wait failed: {rc}")

    def sync(self) -> None:
        if self._handle is not None:
            rc = self._libref.wal_sync(self._handle)
            if rc != 0:
                raise StorageError(f"wal_sync failed: {rc}")

    def read_from(self, start_seq: int):
        # flush C++ buffers (appends use unbuffered write(2); a sync makes
        # everything visible+durable before replay reads the files)
        self.sync()
        # bypass Wal's file-handle bookkeeping; segments live on disk
        segs = self._segments()
        for i, (first, path) in enumerate(segs):
            if i + 1 < len(segs) and segs[i + 1][0] <= start_seq:
                continue
            records, clean, good_pos = self._read_segment(path, start_seq)
            yield from records
            if not clean:
                if i + 1 < len(segs):
                    raise StorageError(
                        f"corrupt WAL record mid-log in {path}; refusing "
                        f"to replay past the gap")
                self._repair_torn_tail(path, good_pos)
                return

    def obsolete(self, seq: int) -> None:
        if self._handle is not None:
            rc = self._libref.wal_obsolete(self._handle, seq)
            if rc != 0:
                raise StorageError(f"wal_obsolete failed: {rc}")

    def close(self) -> None:
        handle, self._handle = self._handle, None
        if handle is not None:
            self._libref.wal_close(handle)

    def __del__(self):  # pragma: no cover
        try:
            self.close()
        except Exception:  # greptlint: disable=GL01 — finalizers must
            # never raise; at interpreter teardown even logging can fail
            pass


def wal_backend() -> str:
    """Which WAL `make_wal(backend="auto")` gives this process."""
    return "native" if load_library() is not None else "python"


def make_wal(dir_path: str, *, sync_on_write: bool = False,
             segment_bytes: Optional[int] = None,
             backend: str = "auto") -> Wal:
    """WAL factory: 'native' | 'python' | 'auto' (native with fallback)."""
    if backend in ("auto", "native") and load_library() is not None:
        return NativeWal(dir_path, sync_on_write=sync_on_write,
                         segment_bytes=segment_bytes)
    if backend == "native":
        raise StorageError("native WAL requested but unavailable")
    return Wal(dir_path, sync_on_write=sync_on_write,
               segment_bytes=segment_bytes)
