"""Pallas TPU kernel: blocked multi-column prefix sum.

The propagation hot spot (paper §4.1.2) after the preorder rewrite
(DESIGN.md §4): inclusive metric costs are ``cumsum[end[i]] - cumsum[i]``
over the preorder-scattered exclusive values, and CMS offsets (§4.3.2) are
an exclusive scan over per-context sizes.  Both reduce to one long prefix
sum.

TPU shape: grid iterates value blocks sequentially (TPU grids are
sequential per core), carrying the running block total in a VMEM scratch
accumulator — the parallel-scan "carry" without atomics.  Rows are tiled
(block_n x M); M, the metric columns of one launch, stays whole up to
``MAX_BLOCK_M`` columns and is tiled beyond that (columns are independent,
so the tiling changes no bits), which keeps a block inside the 16 MiB of
scoped VMEM.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

DEFAULT_BLOCK_N = 1024
MAX_BLOCK_M = 512    # a (1024, 1024) f32 block overflows scoped VMEM on v5e


def _scan_kernel(x_ref, o_ref, carry_ref):
    i = pl.program_id(1)  # row block (inner; column block j is outer)

    @pl.when(i == 0)
    def _init():
        carry_ref[...] = jnp.zeros_like(carry_ref)

    x = x_ref[...]                       # (B, M)
    # inclusive scan within the block by log-step shift-and-add (Mosaic has
    # no cumsum): after the step with shift k, row r holds the sum of rows
    # (r - 2k, r].  Integer partial sums are exact in any order, so int32
    # and "exact"-class f32 blocks match a sequential cumsum bit for bit.
    row = jax.lax.broadcasted_iota(jnp.int32, x.shape, 0)
    zero = jnp.zeros_like(x)
    k = 1
    while k < x.shape[0]:
        x = x + jnp.where(row >= k, pltpu.roll(x, k, 0), zero)
        k *= 2
    s = x + carry_ref[...]               # + carry of the previous blocks
    o_ref[...] = s
    carry_ref[...] = s[-1:, :]


def blockscan_pallas(x: jax.Array, *, block_n: int = DEFAULT_BLOCK_N,
                     interpret: bool = False) -> jax.Array:
    """Inclusive prefix sum along axis 0 of (N, M); N % block_n == 0, and
    M % MAX_BLOCK_M == 0 when M > MAX_BLOCK_M."""
    n, m = x.shape
    block_m = min(m, MAX_BLOCK_M)
    assert n % block_n == 0 and m % block_m == 0
    return pl.pallas_call(
        _scan_kernel,
        grid=(m // block_m, n // block_n),
        in_specs=[pl.BlockSpec((block_n, block_m), lambda j, i: (i, j))],
        out_specs=pl.BlockSpec((block_n, block_m), lambda j, i: (i, j)),
        out_shape=jax.ShapeDtypeStruct((n, m), x.dtype),
        scratch_shapes=[pltpu.VMEM((1, block_m), x.dtype)],
        interpret=interpret,
    )(x)
