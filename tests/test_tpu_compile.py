"""Compile the main-path Pallas kernels for a TPU v5e chip, without one.

The TPU compiler is installed even where no chip is attached: it compiles
for a described ``v5e:2x2`` topology and refuses what the chip would refuse
(unlowerable primitives, block shapes that break the tiling, too much fast
memory), none of which the interpret-mode tests can see.  Shapes are the
ones ``chip_smoke.py`` reaches on its PeleC(1+82) fleet.

The topology is described inside a fixture, never at import: only one
process at a time may load the TPU library, and every test worker imports
this file.
"""
import os

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.kernels import ops

# unified tree of chip_smoke's fleet: 1,200 shared contexts, one "worker"
# phase, and per profile a module plus 4,096 private contexts (96 profiles)
N_CTX = 1200 + 1 + 96 * (1 + 4096)
COMBINE_VALUES = 8192        # one profile's values, bucketed (batch._bucket)
CENSUS_ROWS = 96 * 8192      # every plane's rows, concatenated


@pytest.fixture(scope="module")
def one_chip():
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache

    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    try:
        topo = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # no TPU compiler in this installation
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    # a compile for a described chip is written to the persistent cache but
    # cannot be read back without one: keep the cache out of it
    enabled = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    # the wrappers pick interpret mode from the (CPU) backend; steer them
    # to the compiled kernels, and drop traces made in interpret mode
    with pytest.MonkeyPatch.context() as mp:
        mp.setattr(ops, "_interpret", lambda kernel: False)
        jax.clear_caches()
        yield SingleDeviceSharding(topo.devices[0])
        jax.clear_caches()
    jax.config.update("jax_enable_compilation_cache", enabled)
    compilation_cache.reset_cache()


def _compile(fn, *shapes, **static):
    compiled = jax.jit(fn, static_argnames=tuple(static)).lower(
        *shapes, **static).compile()
    assert "tpu_custom_call" in compiled.as_text()  # the Mosaic kernel
    return compiled


def _arg(shape, dtype, sharding):
    return jax.ShapeDtypeStruct(shape, dtype, sharding=sharding)


@pytest.mark.parametrize("m", [8, 128, 512, 1024])
def test_blockscan_f32_propagation_compiles(one_chip, m):
    """The inclusive propagation at every column bucket one launch takes:
    8 (a 1-metric CPU rank) up to 4 coalesced 82-metric GPU streams (512)
    and beyond (column-tiled)."""
    _compile(ops.inclusive_from_exclusive,
             _arg((N_CTX, m), jnp.float32, one_chip),
             _arg((N_CTX,), jnp.int32, one_chip))


# the benchmark's PeleC(1+82) fleet: its unified tree, and the triplet and
# support-pair classes of a propagation launch (batch.TRIPLET_FLOOR,
# batch.SUPPORT_FLOOR and the next class up)
BENCH_CTX = 68_497


@pytest.mark.parametrize("buckets", [(4096, 8192), (8192, 8192)])
@pytest.mark.parametrize("m", [8, 128, 256, 512])
def test_sparse_propagation_compiles(one_chip, m, buckets):
    """The propagation as the analysis launches it: (row, column, value)
    triplets in, the matrix built and scanned on the device, the inclusive
    sums at the support pairs out, at every column class of the fleet."""
    nt, ns = buckets
    i32 = jnp.int32
    _compile(ops.inclusive_from_exclusive,
             (_arg((nt,), i32, one_chip), _arg((nt,), i32, one_chip),
              _arg((nt,), jnp.float32, one_chip)),
             _arg((BENCH_CTX,), i32, one_chip),
             (_arg((ns,), i32, one_chip), _arg((ns,), i32, one_chip)),
             columns=m)


def test_blockscan_int32_offsets_compile(one_chip):
    """The CMS stripe offsets: an int32 exclusive scan over every context."""
    _compile(ops.exclusive_scan, _arg((N_CTX,), jnp.int32, one_chip))


def test_segstats_combine_compiles(one_chip):
    _compile(ops.segstats, _arg((COMBINE_VALUES,), jnp.int32, one_chip),
             _arg((COMBINE_VALUES,), jnp.float32, one_chip),
             num_segments=COMBINE_VALUES)


def test_scatter_add_census_compiles(one_chip):
    _compile(ops.histogram, _arg((CENSUS_ROWS,), jnp.int32, one_chip),
             num_segments=N_CTX)
