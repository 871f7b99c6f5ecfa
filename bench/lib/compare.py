"""The comparison that decides ``correct``: outputs against the reference,
bit for bit."""

from __future__ import annotations

from typing import Dict

import numpy as np

Outputs = Dict[str, Dict[str, np.ndarray]]


def _bits(a: np.ndarray) -> np.ndarray:
    a = np.ascontiguousarray(a)
    return a.view(np.dtype(f"u{a.dtype.itemsize}")) if a.dtype.itemsize in (1, 2, 4, 8) else a


def compare(got: Outputs, want: Outputs) -> Dict[str, int]:
    """``mismatched_values``: values whose bits differ; ``mismatched_shapes``:
    outputs or columns missing, extra, or of another dtype or length;
    ``values``: values compared."""
    out = {"values": 0, "mismatched_values": 0, "mismatched_shapes": 0}
    for node in set(got) | set(want):
        if node not in got or node not in want:
            out["mismatched_shapes"] += 1
            continue
        g, w = got[node], want[node]
        for col in set(g) | set(w):
            if col not in g or col not in w:
                out["mismatched_shapes"] += 1
                continue
            a, b = np.asarray(g[col]), np.asarray(w[col])
            if a.dtype != b.dtype or a.shape != b.shape:
                out["mismatched_shapes"] += 1
                continue
            out["values"] += int(b.size)
            out["mismatched_values"] += int(np.count_nonzero(_bits(a) != _bits(b)))
    return out
