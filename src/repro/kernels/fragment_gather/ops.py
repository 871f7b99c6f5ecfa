"""jit'd wrapper: concatenate tile-aligned row runs of a pinned 1-D column.

The caller passes the output's row layout as ``(lo, hi)`` runs of ``src``.
Runs that start and end on a :data:`TILE_ROWS` boundary go through the
Pallas kernel in blocks of the largest power of two, up to
:data:`MAX_ROW_BLOCK` rows, that divides every run start and length.  Other
runs are the caller's to serve with plain XLA slices (``core.device``
counts them as ``gather_fallbacks``): the TPU compiler refuses blocks
narrower than one ``(8, 128)`` tile, so the kernel has no row-granular
path.

The Pallas call is wrapped in a memoized ``jax.jit``: eager interpret mode
replays the grid in Python (milliseconds per step), while the jitted
interpreter runs it as one XLA loop.
"""

from __future__ import annotations

import functools
import math
from typing import Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from repro.kernels.fragment_gather.kernel import TILE_ROWS, fragment_gather_call

__all__ = ["fragment_gather", "tile_aligned", "block_plan", "resolve_interpret"]

# largest block: 256 KiB of f32 per buffer, so the input and output blocks,
# double-buffered, take 1 MiB of VMEM
MAX_ROW_BLOCK = 64 * TILE_ROWS


def resolve_interpret(interpret: Optional[bool] = None) -> bool:
    """``None`` means: compiled on a TPU, the Pallas interpreter elsewhere."""
    return jax.default_backend() != "tpu" if interpret is None else interpret


def tile_aligned(n_rows: int, bounds: Sequence[Tuple[int, int]]) -> bool:
    """Whether the kernel can serve ``bounds`` of an ``n_rows`` column:
    the column and every run start and end on whole tiles."""
    return n_rows % TILE_ROWS == 0 and all(
        lo % TILE_ROWS == 0 and hi % TILE_ROWS == 0 for lo, hi in bounds
    )


def block_plan(bounds: Sequence[Tuple[int, int]]) -> Tuple[int, np.ndarray]:
    """The row block for tile-aligned ``bounds`` (the largest power of two
    dividing every run start and length, at most :data:`MAX_ROW_BLOCK`)
    and the source block index of each output block."""
    g = 0
    for lo, hi in bounds:
        g = math.gcd(g, lo, hi - lo)
    rb = min(g & -g, MAX_ROW_BLOCK)
    block_idx = np.concatenate(
        [np.arange(lo // rb, hi // rb, dtype=np.int32) for lo, hi in bounds]
    )
    return rb, block_idx


@functools.lru_cache(maxsize=64)
def _compiled_call(row_block: int, interpret: bool):
    return jax.jit(
        functools.partial(
            fragment_gather_call, row_block=row_block, interpret=interpret
        )
    )


def fragment_gather(
    src: jax.Array,  # (n,) device column, n a multiple of TILE_ROWS
    bounds: Sequence[Tuple[int, int]],  # half-open row runs, output order
    *,
    interpret: Optional[bool] = None,
) -> jax.Array:
    """``concat(src[lo:hi] for lo, hi in bounds)`` on device."""
    interpret = resolve_interpret(interpret)
    bounds = [(int(lo), int(hi)) for lo, hi in bounds if hi > lo]
    if not bounds:
        return src[:0]
    n = int(src.shape[0])
    lo_min = min(lo for lo, _ in bounds)
    hi_max = max(hi for _, hi in bounds)
    if lo_min < 0 or hi_max > n:
        raise IndexError(f"runs span [{lo_min}, {hi_max}) of a {n}-row column")
    if src.ndim != 1 or not tile_aligned(n, bounds):
        raise ValueError(
            f"fragment_gather takes runs of a 1-D column on {TILE_ROWS}-row "
            f"tiles; got shape {tuple(src.shape)}, runs {bounds[:4]}..."
        )
    rb, block_idx = block_plan(bounds)
    return _compiled_call(rb, interpret)(src, jnp.asarray(block_idx))
