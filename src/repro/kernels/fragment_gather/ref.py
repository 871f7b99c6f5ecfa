"""Oracle: plain jnp slices and one concatenate."""

from __future__ import annotations

from typing import Sequence, Tuple

import jax
import jax.numpy as jnp

__all__ = ["gather_ref"]


def gather_ref(src: jax.Array, bounds: Sequence[Tuple[int, int]]) -> jax.Array:
    """``concat(src[lo:hi] for lo, hi in bounds)`` of a 1-D column."""
    return jnp.concatenate([src[lo:hi] for lo, hi in bounds] or [src[:0]])
