"""Plain reference for the edit loop over ``nyc_yellow_2023_01``.

It works from the seed's data alone, as numpy arrays: no catalog, no cache,
no jax and nothing the program made.  For a run it takes the rows of the
table (the month and the fragments appended so far) whose pickup key lies in
the run's windows, in key order, converts them as jax's 32-bit mode does
(float64 to float32 by rounding to nearest, int64 to int32), and applies the
two stages' arithmetic: ``feats`` maps a float ``v`` to ``v`` where
``v >= 0`` and to ``v * 0.5`` elsewhere, and keeps the int64 key;
``score`` multiplies every float of ``feats`` by the gain, and carries the
key as int32.  Every operation is exactly rounded, so the program's output
has to equal this bit for bit.

``float_dtype`` is the precision of the floats; the control computes in
``bfloat16`` and casts back to float32.
"""

from __future__ import annotations

from typing import Dict, List, Optional, Tuple

import numpy as np


class Reference:
    def __init__(self, config: Dict, seed: int, tables):
        self.config, self.seed, self.tables = config, seed, tables
        self.parts: List[Dict[str, np.ndarray]] = []
        self.table: Optional[Tuple[int, Dict[str, np.ndarray]]] = None

    def _data(self, appends: int) -> Dict[str, np.ndarray]:
        """The key and the float columns as they stand after ``appends``
        appended fragments."""
        if self.table is None or self.table[0] != appends:
            while len(self.parts) <= appends:
                part = self.tables.columns(self.config, self.seed, part=len(self.parts))
                self.parts.append(
                    {c: v for c, v in part.items() if v.dtype.kind == "f" or c == self.tables.SORT_KEY}
                )
            data = {
                c: np.concatenate([p[c] for p in self.parts[: appends + 1]])
                for c in self.parts[0]
            }
            # parts are written in key order with unique keys, so rows in
            # table order are rows in key order
            if not np.all(np.diff(data[self.tables.SORT_KEY]) > 0):
                raise ValueError("pickup keys are not unique and ascending")
            self.table = (appends, data)
        return self.table[1]

    def outputs(self, run: Dict, float_dtype=np.float32) -> Dict[str, Dict[str, np.ndarray]]:
        data = self._data(int(run["appends"]))
        sort_key = self.tables.SORT_KEY
        key = data[sort_key]
        keep = np.zeros(key.shape, dtype=bool)
        for lo, hi in run["windows"]:
            keep |= (key >= lo) & (key < hi)
        rows = np.nonzero(keep)[0]
        feats = {sort_key: key[rows]}
        score = {sort_key: feats[sort_key].astype(np.int32)}
        half, gain = float_dtype(0.5), float_dtype(run["gain"])
        for c in run["columns"]:
            v = data[c][rows].astype(float_dtype)
            f = np.where(v >= 0, v, v * half)
            feats[c] = f.astype(np.float32)
            score[c] = (f * gain).astype(np.float32)
        return {"feats": feats, "score": score}
