"""ctypes binding for the native C++ data feed (csrc/datafeed).

Reference analog: framework/data_feed.cc driving trainer threads; here the
native reader keeps a prefetch ring of length-prefixed records ahead of the
host loop (which is ahead of jax dispatch). The first use in a process runs
the Makefile (g++ is part of the baked toolchain), which builds the .so when
it is absent or older than its source — a stale binary left in the tree is
never loaded as is."""
from __future__ import annotations

import ctypes
import os
import subprocess
from typing import Iterator, List, Optional, Sequence

import numpy as np

from . import IterableDataset

_SRC_DIR = os.path.join(os.path.dirname(__file__), "..", "..", "csrc",
                        "datafeed")
_LIB_PATH = os.path.join(_SRC_DIR, "libdatafeed.so")
_LIB = None


def _load_lib():
    global _LIB
    if _LIB is not None:
        return _LIB
    subprocess.run(["make", "-C", _SRC_DIR], check=True,
                   capture_output=True)
    lib = ctypes.CDLL(_LIB_PATH)
    lib.datafeed_create.restype = ctypes.c_void_p
    lib.datafeed_create.argtypes = [
        ctypes.POINTER(ctypes.c_char_p), ctypes.c_int64, ctypes.c_int,
        ctypes.c_int64, ctypes.c_int]
    lib.datafeed_next.restype = ctypes.c_int64
    lib.datafeed_next.argtypes = [ctypes.c_void_p,
                                  ctypes.POINTER(ctypes.c_uint8),
                                  ctypes.c_int64]
    lib.datafeed_queue_size.restype = ctypes.c_int64
    lib.datafeed_queue_size.argtypes = [ctypes.c_void_p]
    lib.datafeed_destroy.argtypes = [ctypes.c_void_p]
    lib.datafeed_write_records.restype = ctypes.c_int64
    lib.datafeed_write_records.argtypes = [
        ctypes.c_char_p, ctypes.POINTER(ctypes.c_uint8),
        ctypes.POINTER(ctypes.c_int64), ctypes.c_int64]
    _LIB = lib
    return lib


def write_record_file(path: str, records: Sequence[bytes]) -> int:
    """Write length-prefixed records via the native writer."""
    lib = _load_lib()
    blob = b"".join(records)
    lengths = np.asarray([len(r) for r in records], np.int64)
    buf = (ctypes.c_uint8 * len(blob)).from_buffer_copy(blob) if blob else \
        (ctypes.c_uint8 * 1)()
    n = lib.datafeed_write_records(
        path.encode(), buf,
        lengths.ctypes.data_as(ctypes.POINTER(ctypes.c_int64)),
        len(records))
    if n < 0:
        raise IOError(f"failed writing records to {path}")
    return int(n)


class NativeRecordReader:
    """Iterate raw record bytes from the native prefetching reader."""

    def __init__(self, files: List[str], num_threads: int = 2,
                 capacity: int = 1024, repeat: int = 1,
                 max_record_bytes: int = 1 << 20):
        self._lib = _load_lib()
        arr = (ctypes.c_char_p * len(files))(
            *[f.encode() for f in files])
        self._handle = self._lib.datafeed_create(
            arr, len(files), num_threads, capacity, repeat)
        if not self._handle:
            raise RuntimeError("datafeed_create failed")
        self._buf = (ctypes.c_uint8 * max_record_bytes)()
        self._buf_len = max_record_bytes
        self._closed = False

    _END_OF_DATA = -3
    _BUFFER_TOO_SMALL = -1

    def __iter__(self) -> Iterator[bytes]:
        while True:
            n = self._lib.datafeed_next(self._handle, self._buf,
                                        self._buf_len)
            if n == self._END_OF_DATA:
                return
            if n == self._BUFFER_TOO_SMALL:  # grow buffer and retry
                self._buf_len *= 2
                self._buf = (ctypes.c_uint8 * self._buf_len)()
                continue
            if n < 0:
                raise IOError("native datafeed read error")
            yield bytes(bytearray(self._buf[:n]))

    def queue_size(self) -> int:
        return self._lib.datafeed_queue_size(self._handle)

    def close(self):
        if not self._closed and self._handle:
            self._lib.datafeed_destroy(self._handle)
            self._closed = True

    def __del__(self):
        try:
            self.close()
        except Exception:
            pass


class RecordFileDataset(IterableDataset):
    """IterableDataset over native record files with an optional decoder
    (e.g. np.frombuffer) — plugs straight into DataLoader."""

    def __init__(self, files: List[str], decoder=None, num_threads: int = 2,
                 capacity: int = 1024, repeat: int = 1):
        self.files = files
        self.decoder = decoder
        self.num_threads = num_threads
        self.capacity = capacity
        self.repeat = repeat

    def __iter__(self):
        reader = NativeRecordReader(self.files, self.num_threads,
                                    self.capacity, self.repeat)
        try:
            for rec in reader:
                yield self.decoder(rec) if self.decoder else rec
        finally:
            reader.close()
