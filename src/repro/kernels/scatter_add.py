"""Pallas TPU kernel: scatter-add as tiled one-hot MXU contraction.

Used for (a) context histograms in the CMS size census (paper §4.3.2) and
(b) densifying a profile's sparse rows onto the unified preorder vector
before propagation.  TPUs have no scatter unit; the canonical formulation
is ``one_hot(idx)^T @ vals`` per (segment tile, value block), accumulated
over value blocks — all MXU work on 128-aligned tiles.

Unlike :mod:`repro.kernels.segstats` this kernel does **not** require
sorted indices (histograms aren't sorted); it trades that generality for
doing only the sum statistic.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

DEFAULT_BLOCK_N = 1024  # the (1024) tile XLA gives a 1-D s32 id array
DEFAULT_BLOCK_S = 512


def _scatter_kernel(ids_ref, val_ref, out_ref, *, block_s: int):
    j = pl.program_id(0)
    i = pl.program_id(1)

    @pl.when(i == 0)
    def _init():
        out_ref[...] = jnp.zeros_like(out_ref)

    ids = ids_ref[...]
    vals = val_ref[...]                               # (B, M)
    local = ids - j * block_s
    cols = jax.lax.broadcasted_iota(jnp.int32, (ids.shape[0], block_s), 1)
    onehot = (local[:, None] == cols).astype(vals.dtype)   # (B, T)
    # full f32 precision: integer counts stay exact (the "exact" contract)
    out_ref[...] += jnp.dot(onehot.T, vals, precision=jax.lax.Precision.HIGHEST,
                            preferred_element_type=jnp.float32)


def scatter_add_pallas(ids: jax.Array, vals: jax.Array, num_segments: int,
                       *, block_n: int = DEFAULT_BLOCK_N,
                       block_s: int = DEFAULT_BLOCK_S,
                       interpret: bool = False) -> jax.Array:
    """out[s, :] = sum of vals rows with ids == s; (S_pad, M) f32 output.

    Out-of-range ids (sentinel padding) contribute nothing.
    """
    n = ids.shape[0]
    m = vals.shape[1]
    assert n % block_n == 0
    s_pad = -(-num_segments // block_s) * block_s
    grid = (s_pad // block_s, n // block_n)
    return pl.pallas_call(
        functools.partial(_scatter_kernel, block_s=block_s),
        grid=grid,
        in_specs=[
            pl.BlockSpec((block_n,), lambda j, i: (i,)),
            pl.BlockSpec((block_n, m), lambda j, i: (i, 0)),
        ],
        out_specs=pl.BlockSpec((block_s, m), lambda j, i: (j, 0)),
        out_shape=jax.ShapeDtypeStruct((s_pad, m), jnp.float32),
        interpret=interpret,
    )(ids, vals)
