"""Binary I/O helpers shared by the profile / PMS / CMS file formats.

Layout conventions (little-endian throughout):

* json block : [u32 length][utf-8 bytes]
* array block: [4s dtype code][u8 ndim][u64 x ndim shape][raw C-order bytes]

These helpers exist so every on-disk format in :mod:`repro.core` measures its
exact byte footprint (the paper's evaluation is in bytes, Tables 1/2/4).

Zero-copy contract: :func:`unpack_array` returns a *view* over the caller's
buffer (``np.frombuffer`` with an offset, never an intermediate slice), so
decoding a profile from an ``mmap`` aliases the page cache instead of
materializing a private copy.  The view keeps the backing buffer alive; it
is read-only whenever the buffer is (bytes, ``ACCESS_READ`` maps) — callers
that need to mutate must copy explicitly, exactly as before.
"""
from __future__ import annotations

import json
import struct
from typing import BinaryIO

import numpy as np

_DTYPE_CODES = {
    "u8  ": np.dtype(np.uint8),
    "u16 ": np.dtype(np.uint16),
    "u32 ": np.dtype(np.uint32),
    "u64 ": np.dtype(np.uint64),
    "i32 ": np.dtype(np.int32),
    "i64 ": np.dtype(np.int64),
    "f32 ": np.dtype(np.float32),
    "f64 ": np.dtype(np.float64),
}
_CODE_FOR_DTYPE = {v: k for k, v in _DTYPE_CODES.items()}


def pack_json(obj) -> bytes:
    payload = json.dumps(obj, separators=(",", ":")).encode("utf-8")
    return struct.pack("<I", len(payload)) + payload


def unpack_json(buf: bytes, off: int = 0):
    (n,) = struct.unpack_from("<I", buf, off)
    off += 4
    obj = json.loads(buf[off : off + n].decode("utf-8"))
    return obj, off + n


def write_json(f: BinaryIO, obj) -> int:
    data = pack_json(obj)
    f.write(data)
    return len(data)


def read_json(f: BinaryIO):
    (n,) = struct.unpack("<I", f.read(4))
    return json.loads(f.read(n).decode("utf-8"))


def pack_array(arr: np.ndarray) -> bytes:
    arr = np.ascontiguousarray(arr)
    code = _CODE_FOR_DTYPE[arr.dtype]
    head = code.encode("ascii") + struct.pack("<B", arr.ndim)
    head += struct.pack(f"<{arr.ndim}Q", *arr.shape)
    return head + arr.tobytes()


def packed_nbytes(arr: np.ndarray) -> int:
    """Size of :func:`pack_array`'s output without materializing it."""
    return 5 + 8 * arr.ndim + arr.nbytes


def pack_array_into(view, off: int, arr: np.ndarray) -> int:
    """Write the :func:`pack_array` layout directly into a writable buffer
    (a bytearray or shared-memory view) at ``off``; returns the new offset.

    Byte-for-byte identical to ``pack_array`` — a plane written into a
    shared-memory slab must be the bytes of the same plane shipped inline.
    """
    arr = np.ascontiguousarray(arr)
    code = _CODE_FOR_DTYPE[arr.dtype]
    view[off : off + 4] = code.encode("ascii")
    struct.pack_into("<B", view, off + 4, arr.ndim)
    struct.pack_into(f"<{arr.ndim}Q", view, off + 5, *arr.shape)
    off += 5 + 8 * arr.ndim
    if arr.nbytes:
        dst = np.frombuffer(view, dtype=np.uint8, count=arr.nbytes, offset=off)
        dst[:] = arr.reshape(-1).view(np.uint8)
    return off + arr.nbytes


def unpack_array(buf, off: int = 0):
    code = bytes(buf[off : off + 4]).decode("ascii")
    dtype = _DTYPE_CODES[code]
    off += 4
    (ndim,) = struct.unpack_from("<B", buf, off)
    off += 1
    shape = struct.unpack_from(f"<{ndim}Q", buf, off)
    off += 8 * ndim
    count = int(np.prod(shape)) if ndim else 1
    nbytes = count * dtype.itemsize
    # a view over the caller's buffer (page cache for mmaps), not a copy
    arr = np.frombuffer(buf, dtype=dtype, count=count, offset=off).reshape(shape)
    return arr, off + nbytes


def write_array(f: BinaryIO, arr: np.ndarray) -> int:
    data = pack_array(arr)
    f.write(data)
    return len(data)


def read_array(f: BinaryIO) -> np.ndarray:
    code = f.read(4).decode("ascii")
    dtype = _DTYPE_CODES[code]
    (ndim,) = struct.unpack("<B", f.read(1))
    shape = struct.unpack(f"<{ndim}Q", f.read(8 * ndim))
    count = int(np.prod(shape)) if ndim else 1
    return np.frombuffer(f.read(count * dtype.itemsize), dtype=dtype).reshape(shape)
