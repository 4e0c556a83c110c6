"""A plain reader of the databases ``analyze`` writes: ``db.pms`` and
``db.cms``, parsed from their documented layouts (``docs/formats.md``) with
nothing but numpy, so that the check does not read the program's output
through the program's own reader.

Array block: 4-byte dtype code, u8 ndim, u64 shape per dim, raw bytes.
JSON block: u32 length, UTF-8.  All little-endian.
"""
from __future__ import annotations

import json
import struct
from dataclasses import dataclass

import numpy as np

_DTYPES = {b"u8  ": np.uint8, b"u16 ": np.uint16, b"u32 ": np.uint32,
           b"u64 ": np.uint64, b"i32 ": np.int32, b"i64 ": np.int64,
           b"f32 ": np.float32, b"f64 ": np.float64}


def _array(buf: bytes, off: int) -> tuple[np.ndarray, int]:
    dtype = np.dtype(_DTYPES[bytes(buf[off:off + 4])])
    (ndim,) = struct.unpack_from("<B", buf, off + 4)
    shape = struct.unpack_from(f"<{ndim}Q", buf, off + 5)
    off += 5 + 8 * ndim
    count = int(np.prod(shape)) if ndim else 1
    arr = np.frombuffer(buf, dtype, count=count, offset=off).reshape(shape)
    return arr, off + count * dtype.itemsize


def _json(buf: bytes, off: int):
    (n,) = struct.unpack_from("<I", buf, off)
    return json.loads(bytes(buf[off + 4:off + 4 + n]).decode("utf-8")), off + 4 + n


@dataclass
class Database:
    """One analysis result, as arrays.

    ``planes[p]`` is profile ``p``'s ``(ctx, mid, val)`` in file order (mid
    carries bit 15 for inclusive values); ``stats`` the summary statistics
    per (ctx, mid); ``cms`` the context-major ``(ctx, mid, prof, val)`` in
    file order, or None where no CMS was written."""
    parent: np.ndarray
    kind: np.ndarray
    names: list[str]
    planes: list[tuple[np.ndarray, np.ndarray, np.ndarray]]
    stats: dict[str, np.ndarray]
    cms: tuple[np.ndarray, np.ndarray, np.ndarray, np.ndarray] | None


def read_pms(path: str):
    with open(path, "rb") as f:
        buf = f.read()
    if buf[:4] != b"RPMS":
        raise ValueError(f"{path}: not a PMS file")
    n_prof, meta_off = struct.unpack_from("<QQ", buf, 8)
    index = np.frombuffer(buf, np.uint64, count=4 * n_prof,
                          offset=24).reshape(-1, 4)
    planes = []
    for off, nbytes, _, _ in index.astype(np.int64):
        if nbytes == 0:
            planes.append((np.empty(0, np.int64),) * 2 + (np.empty(0),))
            continue
        ctx, o = _array(buf, off)
        start, o = _array(buf, o)
        mid, o = _array(buf, o)
        val, _ = _array(buf, o)
        rows = np.repeat(ctx.astype(np.int64), np.diff(start.astype(np.int64)))
        planes.append((rows, mid.astype(np.int64), val.astype(np.float64)))
    meta, o = _json(buf, int(meta_off))
    tree = {}
    if meta.get("has_tree"):
        for key in ("parent", "kind", "name_id", "names"):
            tree[key], o = _array(buf, o)
    stats = {}
    for key in meta.get("stats_fields", []):
        stats[key], o = _array(buf, o)
    names = bytes(tree["names"]).decode("utf-8").split("\x00") if tree else []
    return (tree.get("parent", np.empty(0, np.int64)).astype(np.int64),
            tree.get("kind", np.empty(0, np.uint8)),
            [names[i] for i in tree.get("name_id", [])], planes, stats)


def read_cms(path: str):
    with open(path, "rb") as f:
        buf = f.read()
    if buf[:4] != b"RCMS":
        raise ValueError(f"{path}: not a CMS file")
    (n_ctx,) = struct.unpack_from("<Q", buf, 8)
    offsets = np.frombuffer(buf, np.uint64, count=n_ctx + 1,
                            offset=24).astype(np.int64)
    parts = {k: [] for k in ("ctx", "mid", "prof", "val")}
    for c in np.flatnonzero(np.diff(offsets)):
        mids, o = _array(buf, int(offsets[c]))
        mstart, o = _array(buf, o)
        prof, o = _array(buf, o)
        val, _ = _array(buf, o)
        x = int(prof.size)
        parts["ctx"].append(np.full(x, c, np.int64))
        parts["mid"].append(np.repeat(mids.astype(np.int64),
                                      np.diff(mstart.astype(np.int64))))
        parts["prof"].append(prof.astype(np.int64))
        parts["val"].append(val.astype(np.float64))
    return tuple(np.concatenate(parts[k]) if parts[k] else
                 np.empty(0, np.float64 if k == "val" else np.int64)
                 for k in ("ctx", "mid", "prof", "val"))


def read_database(pms_path: str, cms_path: str | None) -> Database:
    parent, kind, names, planes, stats = read_pms(pms_path)
    cms = read_cms(cms_path) if cms_path else None
    return Database(parent, kind, names, planes, stats, cms)
