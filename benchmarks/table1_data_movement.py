"""Paper Table I: moving a dataframe into a user function.

Four paths, as in the paper:
  1. fragments in (simulated) S3    — range-reads + assembly,
  2. fragments on local SSD         — same decode path, no S3 latency,
  3. Arrow-analog IPC file, mmap'd  — the paper's "Arrow IPC ≈ 0 s" row,
  4. zero-copy view of a cache element — the differential cache's serving
     path (slice of a shared buffer).

We report wall seconds on this host; the claim under test is the ORDERING and the ≈0 cost of IPC/views, which is exactly
what motivates the Arrow-backed cache design (paper §III-A).
"""

from __future__ import annotations

import os
import tempfile
import time
from typing import Dict, List

import numpy as np

from repro.core.cache import DifferentialCache
from repro.core.columnar import Table, read_ipc, write_ipc
from repro.core.intervals import IntervalSet
from repro.core.planner import ScanExecutor
from repro.lake.catalog import Catalog
from repro.lake.s3sim import ObjectStore

__all__ = ["run", "format_table"]


def _mktable(rows: int, seed=0) -> Table:
    rng = np.random.default_rng(seed)
    return Table(
        {
            "ts": np.arange(rows, dtype=np.int64),
            "c1": rng.standard_normal(rows),
            "c2": rng.standard_normal(rows),
            "c3": rng.integers(0, 1000, rows),
        }
    )


def _consume(tbl) -> float:
    """The 'user function': touch one value per column (forces mmap pages
    only where needed — the zero-copy claim)."""
    t = tbl.combine() if hasattr(tbl, "combine") else tbl
    return float(sum(np.asarray(t.column(n)[-1]).item() for n in t.column_names))


def run(rows: int = 2_000_000) -> List[Dict]:
    results = []
    with tempfile.TemporaryDirectory() as tmp:
        data = _mktable(rows)
        nbytes = data.nbytes

        # --- 1) S3 fragments
        store = ObjectStore(os.path.join(tmp, "s3"))
        catalog = Catalog(store, rows_per_fragment=1 << 18)
        catalog.create_table("b", "t", data.schema(), "ts")
        catalog.append("b.t", data)
        ex = ScanExecutor(store, catalog, cache=None)
        t0 = time.perf_counter()
        out = ex.scan("b.t", ["c1", "c2", "c3"], IntervalSet.of((0, rows)))
        _consume(out)
        wall = time.perf_counter() - t0
        results.append(
            {"source": "fragments in S3", "rows": rows,
             "gbytes": nbytes / 1e9, "wall_s": wall, "total_s": wall}
        )

        # --- 2) SSD fragments: same path, no simulated latency
        t0 = time.perf_counter()
        out = ex.scan("b.t", ["c1", "c2", "c3"], IntervalSet.of((0, rows)))
        # (second scan is cache-free: executor built with cache=None →
        #  DifferentialCache default — use a NoCache executor instead)
        from repro.core.baselines import NoCache

        ex2 = ScanExecutor(store, catalog, cache=NoCache())
        t0 = time.perf_counter()
        out = ex2.scan("b.t", ["c1", "c2", "c3"], IntervalSet.of((0, rows)))
        _consume(out)
        results.append(
            {"source": "fragments on SSD", "rows": rows, "gbytes": nbytes / 1e9,
             "wall_s": time.perf_counter() - t0,
             "total_s": time.perf_counter() - t0}
        )

        # --- 3) Arrow-analog IPC, memory-mapped
        ipc_path = os.path.join(tmp, "t.ripc")
        write_ipc(data, ipc_path)
        t0 = time.perf_counter()
        tbl = read_ipc(ipc_path, mmap=True)
        _consume(tbl)
        results.append(
            {"source": "IPC file (mmap)", "rows": rows, "gbytes": nbytes / 1e9,
             "wall_s": time.perf_counter() - t0,
             "total_s": time.perf_counter() - t0}
        )

        # --- 4) zero-copy cache view (the differential cache's hit path)
        cache = DifferentialCache()
        ex3 = ScanExecutor(store, catalog, cache=cache)
        ex3.scan("b.t", ["c1", "c2", "c3"], IntervalSet.of((0, rows)))  # warm
        t0 = time.perf_counter()
        out = ex3.scan("b.t", ["c1", "c2", "c3"], IntervalSet.of((0, rows)))
        _consume(out)
        results.append(
            {"source": "differential-cache view (zero-copy)", "rows": rows,
             "gbytes": nbytes / 1e9, "wall_s": time.perf_counter() - t0,
             "total_s": time.perf_counter() - t0}
        )
    return results


def format_table(results: List[Dict]) -> str:
    lines = [
        "| Rows (size) | Source | Wall (s) | Total incl. sim S3 (s) |",
        "|---|---|---|---|",
    ]
    for r in results:
        lines.append(
            "| {rows:,} ({gbytes:.2f} GB) | {source} | {wall_s:.3f} | {total_s:.3f} |".format(**r)
        )
    return "\n".join(lines)


if __name__ == "__main__":
    print(format_table(run()))
