"""Jit'd public wrappers for the Pallas kernels.

Wrappers own padding/alignment (block-multiple lengths, out-of-range
sentinel ids) and backend selection: on TPU the compiled kernels run
natively; on the CPU backend, and only there, they execute under
``interpret=True`` so every test validates the actual kernel bodies
against the jnp oracles.  Any other backend compiles them or fails.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from repro.kernels import blockscan as _bs
from repro.kernels import int8_quant as _q8
from repro.kernels import scatter_add as _sc
from repro.kernels import segstats as _ss


LANE = 128     # minor-dim tile multiple (f32, TPU v4/v5)
SUBLANE = 8    # second-minor tile multiple (f32)
ID_TILE = 1024  # XLA's tile for a 1-D s32/f32 array; a 1-D block must match

# kernel name -> the modes ("compiled" / "interpret") it was traced in by
# this process: the evidence that a run on the chip interpreted nothing
TRACED_MODES: dict[str, set[str]] = {}


def _interpret(kernel: str) -> bool:
    interpret = jax.default_backend() == "cpu"
    TRACED_MODES.setdefault(kernel, set()).add(
        "interpret" if interpret else "compiled")
    return interpret


def _align_up(x: int, mult: int) -> int:
    return -(-int(x) // mult) * mult


def _clamp_block(requested: int, n: int, align: int) -> int:
    """Clamp a block size to the problem size without breaking TPU tiling.

    A plain ``min(requested, max(align, n))`` can produce block sizes like
    200 that pass ``interpret=True`` but are illegal BlockSpecs on real
    hardware (the lane dim must be a multiple of 128, sublanes of 8): the
    clamp is rounded *up* to the alignment, and padding covers the slack.
    """
    b = min(int(requested), max(align, int(n)))
    b = max(align, _align_up(b, align))
    assert b % align == 0 and b > 0, (requested, n, align, b)
    return b


def _pad_to(x: jax.Array, mult: int, fill) -> jax.Array:
    n = x.shape[0]
    pad = (-n) % mult
    if pad == 0:
        return x
    return jnp.concatenate([x, jnp.full((pad,) + x.shape[1:], fill, x.dtype)])


@functools.partial(jax.jit, static_argnames=("num_segments", "block_n", "block_s"))
def segstats(ids: jax.Array, vals: jax.Array, num_segments: int,
             block_n: int = _ss.DEFAULT_BLOCK_N,
             block_s: int = _ss.DEFAULT_BLOCK_S) -> jax.Array:
    """Segmented stats (S, 8): [sum, cnt, min, max, sumsq, ...].

    ``ids`` sorted ascending int32; values f32.  Empty segments finalize to
    min=max=0 (matching :class:`repro.core.stats.StatsAccumulator`).
    """
    block_s = _clamp_block(block_s, num_segments, LANE)
    block_n = _clamp_block(block_n, ids.shape[0], ID_TILE)
    ids = _pad_to(ids.astype(jnp.int32), block_n, num_segments)
    vals = _pad_to(vals.astype(jnp.float32), block_n, 0)
    out = _ss.segstats_pallas(ids, vals, num_segments, block_n=block_n,
                              block_s=block_s,
                              interpret=_interpret("segstats"))
    out = out[:num_segments]
    empty = out[:, 1] == 0
    out = out.at[:, 2].set(jnp.where(empty, 0.0, out[:, 2]))
    out = out.at[:, 3].set(jnp.where(empty, 0.0, out[:, 3]))
    return out


@functools.partial(jax.jit, static_argnames=("block_n",))
def blockscan(x: jax.Array, block_n: int = _bs.DEFAULT_BLOCK_N) -> jax.Array:
    """Inclusive prefix sum along axis 0; accepts (N,) or (N, M)."""
    squeeze = x.ndim == 1
    if squeeze:
        x = x[:, None]
    n, m = x.shape
    block_n = _clamp_block(block_n, n, SUBLANE)
    xp = _pad_to(x, block_n, 0)
    if m > _bs.MAX_BLOCK_M:  # zero columns: the scan is column-local
        xp = _pad_to(xp.T, _bs.MAX_BLOCK_M, 0).T
    out = _bs.blockscan_pallas(xp, block_n=block_n,
                               interpret=_interpret("blockscan"))[:n, :m]
    return out[:, 0] if squeeze else out


def exclusive_scan(x: jax.Array) -> jax.Array:
    """Exclusive scan with total appended: (N,) -> (N+1,); CMS offsets."""
    inc = blockscan(x)
    return jnp.concatenate([jnp.zeros((1,) + x.shape[1:], inc.dtype), inc])


@functools.partial(jax.jit, static_argnames=("num_segments", "block_n", "block_s"))
def scatter_add(ids: jax.Array, vals: jax.Array, num_segments: int,
                block_n: int = _sc.DEFAULT_BLOCK_N,
                block_s: int = _sc.DEFAULT_BLOCK_S) -> jax.Array:
    """out[s] += vals[ids == s]; vals (N,) or (N, M); unsorted ids allowed."""
    block_s = _clamp_block(block_s, num_segments, LANE)
    block_n = _clamp_block(block_n, ids.shape[0], ID_TILE)
    squeeze = vals.ndim == 1
    if squeeze:
        vals = vals[:, None]
    ids = _pad_to(ids.astype(jnp.int32), block_n, num_segments)
    vals = _pad_to(vals.astype(jnp.float32), block_n, 0)
    out = _sc.scatter_add_pallas(ids, vals, num_segments, block_n=block_n,
                                 block_s=block_s,
                                 interpret=_interpret("scatter_add"))
    out = out[:num_segments]
    return out[:, 0] if squeeze else out


def histogram(ids: jax.Array, num_segments: int) -> jax.Array:
    return scatter_add(ids, jnp.ones(ids.shape[0], jnp.float32), num_segments)


@functools.partial(jax.jit, static_argnames=("block_n",))
def int8_quant(x: jax.Array, block_n: int = _q8.DEFAULT_BLOCK_N):
    """Block-scaled int8 quantization: (q, scales, err); pads internally."""
    n = x.shape[0]
    block_n = _clamp_block(block_n, n, LANE)
    xp = _pad_to(x.astype(jnp.float32), block_n, 0)
    q, s, e = _q8.int8_quant_pallas(xp, block_n=block_n,
                                    interpret=_interpret("int8_quant"))
    return q[:n], s, e[:n]


def int8_dequant(q: jax.Array, scales: jax.Array, n: int,
                 block_n: int = _q8.DEFAULT_BLOCK_N) -> jax.Array:
    """Invert :func:`int8_quant`: ``q`` are the first ``n`` quantized values
    (the wrapper trims its padding), ``scales`` one f32 per ``block_n``
    block.  ``block_n`` must match the quantization call — both resolve it
    through the same clamp, so passing the same ``n`` suffices."""
    block_n = _clamp_block(block_n, n, LANE)
    npad = scales.shape[0] * block_n
    pad = npad - q.shape[0]
    if pad < 0:
        raise ValueError(
            f"int8_dequant: {q.shape[0]} quantized values exceed the "
            f"capacity of {scales.shape[0]} scale blocks x block_n="
            f"{block_n} ({npad}); scales/block_n do not match the "
            f"int8_quant call that produced them")
    qp = jnp.concatenate([q, jnp.zeros(pad, q.dtype)]) if pad else q
    full = (qp.astype(jnp.float32).reshape(-1, block_n) * scales[:, None]).reshape(-1)
    return full[:n]


# -- composite: the propagation primitive (paper §4.1.2, DESIGN.md §4) -------

def inclusive_from_exclusive(exclusive, end: jax.Array, at=None,
                             columns: int | None = None) -> jax.Array:
    """inclusive[i] = cumsum[end[i]] - cumsum[i] over preorder values (N, M).

    ``exclusive`` is the (N, M) matrix, or with ``columns`` = M its
    non-zeros as ``(rows, cols, vals)``: the matrix is then built here, and
    an entry whose row is out of range (a padding sentinel) is dropped.
    With ``at`` = ``(ir, ic)`` only ``inclusive[ir, ic]`` is returned.
    ``columns`` is static under ``jax.jit``."""
    if columns is not None:
        rows, cols, vals = exclusive
        exclusive = jnp.zeros((end.shape[0], columns), vals.dtype).at[
            rows, cols].set(vals, mode="drop")
    n = exclusive.shape[0]
    inc = blockscan(exclusive)
    ps = jnp.concatenate([jnp.zeros((1, exclusive.shape[1]), inc.dtype), inc])
    incl = ps[end] - ps[jnp.arange(n)]
    if at is None:
        return incl
    ir, ic = at
    return incl[ir, ic]
