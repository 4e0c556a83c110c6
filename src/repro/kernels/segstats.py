"""Pallas TPU kernel: segmented statistics over sorted segment ids.

The aggregation hot spot of the paper (§4.1.2/§4.2.2): accumulate
{sum, count, min, max, sum-of-squares} of metric values per (context,
metric) key.  The CPU implementation uses per-context hash tables with
relaxed atomic accumulators; TPUs have neither hash tables nor atomics, so
the TPU-native formulation is a **tiled one-hot reduction**:

* grid = (segment tiles, value blocks), segment tile outer so every output
  tile sees its value blocks consecutively (legal TPU output revisiting);
* for a value block ``v (B,)`` with ids ``s (B,)`` and segment tile
  ``[j*T, (j+1)*T)``: ``mask = (s[:, None] == j*T + iota(T))`` — a (B, T)
  VMEM tile; ``sum/cnt/sumsq`` are ``mask^T @ {v, 1, v^2}`` contractions
  that run on the MXU; min/max are masked VPU reductions.

Arithmetic intensity: each value block is read once from HBM per segment
tile (nb*ns*B*4 bytes) and does O(B*T) MXU work — for T ≤ 1k the extra
flops are far below the 197 TF/s roof while avoiding HBM-bound
gather/scatter, which TPUs lack.

Block sizes (v5e): B=1024 values x T=512 segments -> mask tile is
1024x512xf32 = 2 MiB of VMEM, well inside the 16 MiB/core budget and
128-aligned on both MXU operand dims.  B matches the (1024) tile XLA gives
a 1-D s32/f32 array; Mosaic refuses a 1-D block that does not.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

DEFAULT_BLOCK_N = 1024  # values per block: XLA tiles 1-D s32/f32 by 1024
DEFAULT_BLOCK_S = 512   # segments per tile

# output rows are padded to a lane-aligned 8 columns:
# [sum, cnt, min, max, sumsq, 0, 0, 0]
N_STATS = 8

_HIGHEST = jax.lax.Precision.HIGHEST


def _segstats_kernel(ids_ref, val_ref, out_ref, *, block_s: int):
    j = pl.program_id(0)  # segment tile (outer)
    i = pl.program_id(1)  # value block (inner)

    @pl.when(i == 0)
    def _init():
        # min starts at +inf, max at -inf, the sums at 0 (Mosaic lowers no
        # scatter, so no .at[].set)
        col = jax.lax.broadcasted_iota(jnp.int32, out_ref.shape, 1)
        out_ref[...] = jnp.where(col == 2, jnp.inf,
                                 jnp.where(col == 3, -jnp.inf, 0.0))

    ids = ids_ref[...]            # (B,) int32 (global segment ids, sorted)
    vals = val_ref[...]           # (B,) f32
    seg0 = j * block_s
    local = ids - seg0
    cols = jax.lax.broadcasted_iota(jnp.int32, (ids.shape[0], block_s), 1)
    mask = (local[:, None] == cols).astype(vals.dtype)     # (B, T)
    # MXU contractions at full f32 precision: a reduced-precision pass would
    # round integer values above 2^8 and break the "exact" dtype contract
    s = jnp.dot(mask.T, vals, precision=_HIGHEST,
                preferred_element_type=jnp.float32)
    c = jnp.sum(mask, axis=0)
    q = jnp.dot(mask.T, vals * vals, precision=_HIGHEST,
                preferred_element_type=jnp.float32)
    # VPU masked min/max
    big = jnp.asarray(jnp.inf, vals.dtype)
    mn = jnp.min(jnp.where(mask > 0, vals[:, None], big), axis=0)
    mx = jnp.max(jnp.where(mask > 0, vals[:, None], -big), axis=0)

    out = out_ref[...]
    out_ref[...] = jnp.stack(
        [out[:, 0] + s, out[:, 1] + c,
         jnp.minimum(out[:, 2], mn), jnp.maximum(out[:, 3], mx),
         out[:, 4] + q,
         out[:, 5], out[:, 6], out[:, 7]],
        axis=1,
    )


def segstats_pallas(ids: jax.Array, vals: jax.Array, num_segments: int,
                    *, block_n: int = DEFAULT_BLOCK_N,
                    block_s: int = DEFAULT_BLOCK_S,
                    interpret: bool = False) -> jax.Array:
    """Returns (num_segments_padded, 8) [sum, cnt, min, max, sumsq, ...].

    ``ids`` must be sorted ascending; callers pad ``ids`` with an
    out-of-range sentinel (>= num_segments) — sentinel rows match no
    segment tile and contribute nothing.
    """
    n = ids.shape[0]
    assert n % block_n == 0, "ops wrapper pads to block multiple"
    s_pad = -(-num_segments // block_s) * block_s
    grid = (s_pad // block_s, n // block_n)
    out = pl.pallas_call(
        functools.partial(_segstats_kernel, block_s=block_s),
        grid=grid,
        in_specs=[
            pl.BlockSpec((block_n,), lambda j, i: (i,)),
            pl.BlockSpec((block_n,), lambda j, i: (i,)),
        ],
        out_specs=pl.BlockSpec((block_s, N_STATS), lambda j, i: (j, 0)),
        out_shape=jax.ShapeDtypeStruct((s_pad, N_STATS), jnp.float32),
        interpret=interpret,
    )(ids, vals)
    return out
