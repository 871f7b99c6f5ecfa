"""The device tier's gather, compiled for a TPU v5e chip that is described,
not attached: at the row counts the chip smoke serves (16,777,216-row
columns), every case must compile, keep the Pallas kernel where the kernel
serves the runs, and need no more temporary HBM than the output it writes.

Nothing here runs on a device; it catches what interpret mode cannot see —
block shapes the TPU compiler refuses, a prefetched index that overflows
SMEM, and layouts that pad a column out to 128 lanes in a temporary.
"""

from __future__ import annotations

import os

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from repro.kernels.fragment_gather import gather_ref
from repro.kernels.fragment_gather.ops import _compiled_call, block_plan, tile_aligned

N = 1 << 24  # rows of the chip smoke's events table
M = 1 << 20


@pytest.fixture(scope="module")
def one_chip():
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache
    from jax.sharding import SingleDeviceSharding

    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    try:
        topo = topologies.get_topology_desc(platform="tpu", topology_name="v5e:2x2")
    except Exception as e:  # no TPU compiler in this installation
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    # a described chip's executables cannot be read back from the persistent
    # cache, so keep them out of it
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield SingleDeviceSharding(topo.devices[0])
    jax.config.update("jax_enable_compilation_cache", was)


def _compile(fn, one_chip, *shapes):
    specs = [jax.ShapeDtypeStruct(s, d, sharding=one_chip) for s, d in shapes]
    return jax.jit(fn).lower(*specs).compile()


def _out_bytes(bounds, dtype) -> int:
    return sum(hi - lo for lo, hi in bounds) * np.dtype(dtype).itemsize


KERNEL_CASES = [
    # 4,194,304 rows of two fragment-aligned runs, per column dtype
    ("f32", jnp.float32, [(0, 2 * M), (8 * M, 10 * M)]),
    ("int32", jnp.int32, [(0, 2 * M), (8 * M, 10 * M)]),
    ("int8", jnp.int8, [(0, 2 * M), (8 * M, 10 * M)]),
    ("bool", jnp.bool_, [(0, 2 * M), (8 * M, 10 * M)]),
    # runs aligned to one tile only: 12,288 blocks of 1024 rows, more than
    # one call's SMEM index holds, so the gather runs as chained calls
    ("f32-multi-run", jnp.float32, [(1024, 6 * M + 1024), (8 * M, 10 * M), (12 * M, 16 * M)]),
]


@pytest.mark.parametrize("name,dtype,bounds", KERNEL_CASES, ids=[c[0] for c in KERNEL_CASES])
def test_gather_kernel_compiles_within_output_bytes(one_chip, name, dtype, bounds):
    assert tile_aligned(N, bounds)
    rb, block_idx = block_plan(bounds)
    compiled = _compile(
        _compiled_call(rb, False), one_chip,
        ((N,), dtype), (block_idx.shape, jnp.int32),
    )
    assert "tpu_custom_call" in compiled.as_text()
    mem = compiled.memory_analysis()
    assert mem.output_size_in_bytes == _out_bytes(bounds, dtype)
    assert mem.temp_size_in_bytes <= mem.output_size_in_bytes, mem


def test_unaligned_runs_compile_as_xla_slices(one_chip):
    """Runs off the tile grid never reach the kernel: device_union serves
    them as slices and one concatenate."""
    bounds = [(3, 2 * M + 3), (8 * M + 5, 10 * M + 1)]
    assert not tile_aligned(N, bounds)
    compiled = _compile(
        lambda src: gather_ref(src, bounds), one_chip, ((N,), jnp.float32)
    )
    assert "tpu_custom_call" not in compiled.as_text()
    mem = compiled.memory_analysis()
    # the chip pads a 1-D array to whole tiles: 4,194,298 rows take 4,194,304
    assert mem.output_size_in_bytes >= _out_bytes(bounds, jnp.float32)
    assert mem.temp_size_in_bytes <= mem.output_size_in_bytes, mem
