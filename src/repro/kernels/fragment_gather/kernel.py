"""Fragment run-gather — Pallas TPU kernel (the paper's Fig. 4, on-device).

Paper tie-in: the differential cache assembles a logical dataframe from
*fragments* — some rows from cached buffers, some from a fresh residual
scan.  On the host that assembly is zero-copy (numpy views); on the
**device** a jax consumer needs each column as one dense array in HBM.  This
kernel materializes it: the output is the concatenation of row runs of one
pinned 1-D column, ``out = concat(src[lo:hi] for lo, hi in runs)``.

TPU-native design:
- **Lane-dense view.**  A 1-D column of ``n`` rows is viewed as
  ``(n / 128, 128)``: 128 consecutive rows fill the 128 lanes of a vreg row,
  so one ``(8, 128)`` tile holds :data:`TILE_ROWS` = 1024 consecutive rows.
  For ``n`` a multiple of 1024 that view has the same bytes in the same
  order as the 1-D layout, so XLA lowers the reshape to a bitcast and no
  value is padded out to a lane of its own.  (Viewing the column as
  ``(n, 1)`` instead pads every row to 128 lanes in a temporary: 10.7 GB
  for one 16M-row f32 gather.)
- **Scalar-prefetched block index.**  Rows move in blocks of ``row_block``
  rows (a multiple of 1024, so block shapes are ``(8k, 128)``, the shape
  the TPU compiler accepts).  ``pltpu.PrefetchScalarGridSpec`` prefetches
  the per-output-block source block index into SMEM, where it drives the
  input ``BlockSpec``'s index map: the DMA engine streams exactly the
  requested source block per grid step.
- **Bounded SMEM.**  One ``pallas_call`` prefetches at most
  :data:`MAX_GRID_STEPS` indices (32 KiB of SMEM, of 1 MiB).  Longer
  gathers run as several calls that write disjoint block ranges of one
  output buffer, passed from call to call through ``input_output_aliases``
  so no call copies it.
"""

from __future__ import annotations

import jax
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

__all__ = ["LANES", "TILE_ROWS", "MAX_GRID_STEPS", "fragment_gather_call"]

LANES = 128
TILE_ROWS = 8 * LANES  # rows in one (8, 128) tile of the lane-dense view
MAX_GRID_STEPS = 8192  # prefetched block indices per pallas_call (int32 each)


def _copy_kernel(idx_ref, src_ref, *refs):
    # the gather happened in the index_map; the body is a copy into the
    # output block (the last ref — an aliased output buffer, when there is
    # one, sits before it in HBM untouched)
    refs[-1][...] = src_ref[...]


def _out_block(base: int):
    return lambda i, idx: (i + base, 0)


def fragment_gather_call(
    src: jax.Array,  # (n,) pinned column, n a multiple of row_block's tile
    block_idx: jax.Array,  # (n_blocks,) int32: source block of each output block
    *,
    row_block: int,
    interpret: bool,
) -> jax.Array:
    assert row_block % TILE_ROWS == 0, row_block
    assert src.shape[0] % TILE_ROWS == 0, "pins are padded to whole tiles"
    sub = row_block // LANES
    view = src.reshape(-1, LANES)
    n_blocks = block_idx.shape[0]
    out_shape = jax.ShapeDtypeStruct((n_blocks * sub, LANES), src.dtype)
    out = None
    for base in range(0, n_blocks, MAX_GRID_STEPS):
        steps = min(MAX_GRID_STEPS, n_blocks - base)
        in_specs = [pl.BlockSpec((sub, LANES), lambda i, idx: (idx[i], 0))]
        operands = [block_idx[base : base + steps], view]
        aliases = {}
        if out is not None:
            in_specs.append(pl.BlockSpec(memory_space=pl.ANY))
            operands.append(out)
            aliases = {2: 0}
        out = pl.pallas_call(
            _copy_kernel,
            grid_spec=pltpu.PrefetchScalarGridSpec(
                num_scalar_prefetch=1,
                grid=(steps,),
                in_specs=in_specs,
                out_specs=pl.BlockSpec((sub, LANES), _out_block(base)),
            ),
            out_shape=out_shape,
            input_output_aliases=aliases,
            interpret=interpret,
            name="fragment_gather",
        )(*operands)
    return out.reshape(-1)
