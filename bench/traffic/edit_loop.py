"""Closed-loop edit traffic: one data scientist iterating through ``Workspace.run``.

The project is ``device_project`` of ``benchmarks/bench8_device.py`` (copied
here): ``feats``, a jax rowwise stage over a window of the table, and
``score``, a jax full-window stage with a closed-over gain.  The edits follow
the proportions of ``benchmarks/workloads.py`` ``iteration_edits``: in every
13 runs, 5 reruns, 5 window edits, 1 append of one fragment, 1 feature add
or remove and 1 code edit (the gain), in the order of the traffic file's
``script``.  Windows are held in fragments from the end of the table ("the
latest weeks"), so an append slides them.  Window edits are widen, narrow,
shift or a two-interval split, half on the fragment grid and half moved off
the 1,024-row tile grid.

The window is whole cycles of the script, ``round(seconds /
cycle_seconds)`` of them: a fixed list of runs from the seed, so every
program times the same mix of runs, however fast it is.

Nothing may compile inside the measured window, and the shapes a run meets
depend on the whole history of the caches.  So set-up first drives exactly
the window's runs, from the same seed and the same start, through a
rehearsal workspace, then frees it and builds the measured workspace, whose
window replays them.  Appends are written once, by the rehearsal; the
measured workspace sees each one as the newer snapshot its next run is
pinned to, exactly as data arriving from an ingestion job.
"""

from __future__ import annotations

import gc
import itertools
import os
import time
from dataclasses import dataclass
from typing import Callable, Dict, Iterator, List, Set, Tuple

import numpy as np

WINDOW_EDITS = ("widen", "narrow", "shift", "split")


def project(where: str, columns: Tuple[str, ...], gain: float, table: str):
    """scan -> feats (jax rowwise) -> score (jax full window); both stages
    use exactly rounded elementwise operations only, so a residual
    recompute is bitwise equal to a cold one."""
    from repro.pipeline.dsl import Model, Project, model, runtime

    p = Project("edit_loop")

    @model(project=p, incremental="rowwise")
    @runtime("jax")
    def feats(data=Model(table, columns=list(columns), filter=where)):
        import jax.numpy as jnp

        return {
            k: (jnp.where(v >= 0, v, v * jnp.float32(0.5)) if v.dtype.kind == "f" else v)
            for k, v in data.items()
        }

    @model(project=p, incremental="none")
    @runtime("jax")
    def score(data=Model("feats")):
        import jax.numpy as jnp

        return {
            k: (v * jnp.float32(gain) if v.dtype.kind == "f" else v)
            for k, v in data.items()
        }

    return p


def window_filter(windows: List[Tuple[int, int]], key: str) -> str:
    return " OR ".join(f"({key} >= {lo} AND {key} < {hi})" for lo, hi in windows)


@dataclass(frozen=True)
class Edit:
    """One run of the loop: what the user changed and the project it runs."""

    kind: str  # cold | rerun | widen | narrow | shift | split | append | feature | code
    aligned: bool  # window boundaries on the fragment grid
    rows: Tuple[Tuple[int, int], ...]  # half-open row intervals of the table
    windows: Tuple[Tuple[int, int], ...]  # the same rows as half-open key intervals
    columns: Tuple[str, ...]
    gain: float
    appends: int  # fragments appended to the month so far

    @property
    def label(self) -> str:
        if self.kind in WINDOW_EDITS:
            return f"{self.kind}.{'grid' if self.aligned else 'off_grid'}"
        return self.kind

    def describe(self) -> Dict:
        return {
            "kind": self.kind,
            "label": self.label,
            "windows": [list(w) for w in self.windows],
            "columns": list(self.columns),
            "gain": self.gain,
            "appends": self.appends,
        }


def fragment_start(config: Dict, index: int) -> int:
    """First row of fragment ``index``: the month's fragments, its last one
    short, then one per appended fragment."""
    rows, frag = int(config["rows"]), int(config["rows_per_fragment"])
    month = -(-rows // frag)
    return index * frag if index < month else rows + (index - month) * frag


def schedule(config: Dict, traffic: Dict, seed: int, tables) -> Iterator[Edit]:
    """The cold pass's edit, then the traffic's script of edits, over and over.

    Every seed runs the same script with the same window sizes, so every
    seed does the same work in the same order and meets the same shapes
    (which the persistent compile cache then holds); the seed draws the data
    and the gains."""
    rng = np.random.default_rng([seed, 1])
    month = -(-int(config["rows"]) // int(config["rows_per_fragment"]))
    shapes = {k: [tuple(w) for w in v] for k, v in traffic["windows"].items()}
    gains = list(traffic["gains"])
    feature = tuple(traffic["feature_column"])
    columns = tuple(traffic["columns"])
    appends, gain, name, offsets = 0, gains[0], "base", None

    def edit(kind: str, aligned: bool) -> Edit:
        last = month + appends
        rows = []
        for i, (a, b) in enumerate(shapes[name]):
            lo, hi = fragment_start(config, last + a), fragment_start(config, last + b)
            if offsets is not None:
                # off the tile grid: every inner boundary moves by its own
                # offset, the table end moves inwards
                lo += offsets[2 * i]
                hi = hi - offsets[2 * i + 1] if b == 0 else hi + offsets[2 * i + 1]
            rows.append((max(lo, 0), hi))
        keys = tuple((tables.key_bound(config, lo), tables.key_bound(config, hi)) for lo, hi in rows)
        return Edit(kind, aligned, tuple(rows), keys, columns, gain, appends)

    yield edit("cold", True)
    while True:
        for step in traffic["script"]:
            kind, _, grid = step.partition(".")
            aligned = grid != "off_grid"
            if kind in WINDOW_EDITS:
                name = kind
                offsets = None if aligned else traffic["off_grid_offsets"]
            elif kind == "append":
                appends += 1
            elif kind == "feature":
                columns = (
                    tuple(c for c in columns if c not in feature)
                    if set(feature) <= set(columns)
                    else columns + feature
                )
            elif kind == "code":
                gain = gains[(gains.index(gain) + 1 + int(rng.integers(len(gains) - 1))) % len(gains)]
            elif kind != "rerun":
                raise ValueError(f"unknown edit {step!r}")
            yield edit(kind, aligned)


def window_edits(config: Dict, traffic: Dict, seed: int, tables, seconds: float) -> List[Edit]:
    """The cold pass's edit and the window's: ``round(seconds /
    cycle_seconds)`` whole cycles of the script, at least one."""
    cycles = max(1, round(seconds / float(traffic["cycle_seconds"])))
    return list(itertools.islice(schedule(config, traffic, seed, tables), 1 + cycles * len(traffic["script"])))


def sample_positions(edits: List[Edit], seed: int) -> Set[int]:
    """Which of the window's runs the reference checks: for each kind of
    run, one drawn from the seed and the last one (in the last cycle)."""
    by_label: Dict[str, List[int]] = {}
    for i, e in enumerate(edits):
        by_label.setdefault(e.label, []).append(i)
    rng = np.random.default_rng([seed, 2])
    keep: Set[int] = set()
    for _label, where in sorted(by_label.items()):
        keep.add(where[int(rng.integers(len(where)))])
        keep.add(where[-1])
    return keep


def host_outputs(result) -> Dict[str, Dict[str, np.ndarray]]:
    """The run's outputs as host arrays (device copies are not kept)."""
    return {
        node: {c: np.asarray(t.column(c)) for c in t.column_names}
        for node, t in result.outputs.items()
    }


COUNTERS = (
    "bytes_from_store",
    "bytes_from_cache",
    "bytes_from_model_cache",
    "bytes_h2d",
    "bytes_d2h",
    "device_hits",
    "gather_fast",
    "gather_fallbacks",
    "device_union_bytes",
    "rows_to_user_fns",
)


def counters(result) -> Dict[str, int]:
    return {k: int(getattr(result, k)) for k in COUNTERS}


class Driver:
    """Set-up, window and samples of one closed-loop edit cell."""

    def __init__(
        self,
        config: Dict,
        traffic: Dict,
        seed: int,
        workdir: str,
        tables,
        compile_seconds: Callable[[], float],
    ):
        self.config, self.traffic, self.seed = config, traffic, seed
        self.root = os.path.join(workdir, "lake")
        self.tables = tables
        self.compile_seconds = compile_seconds
        self.table = config["table"]
        self.snapshots: List[str] = []
        self.edits: List[Edit] = []  # the cold pass's, then the window's
        self.rehearsed = 0  # runs of the window that set-up rehearsed
        self.setup_parts: Dict[str, float] = {}
        self.ws = None
        self._samples: List[Tuple[Dict, Dict]] = []

    # -- set-up -------------------------------------------------------------
    def _workspace(self):
        from repro.core.device import DeviceTier
        from repro.pipeline.executor import Workspace

        return Workspace(
            self.root,
            rows_per_fragment=int(self.config["rows_per_fragment"]),
            device=DeviceTier() if self.config["device_tier"] else None,
        )

    def _append(self, ws, part: int) -> None:
        from repro.core.columnar import Table

        snap = ws.catalog.append(
            self.table, Table(self.tables.columns(self.config, self.seed, part=part))
        )
        self.snapshots.append(snap.snapshot_id)

    def _run(self, ws, edit: Edit):
        if edit.appends == len(self.snapshots):
            self._append(ws, edit.appends)
        where = window_filter(list(edit.windows), self.tables.SORT_KEY)
        proj = project(where, edit.columns, edit.gain, self.table)
        t0 = time.perf_counter()
        result = ws.run(proj, snapshot_pins={self.table: self.snapshots[edit.appends]})
        return result, t0, time.perf_counter()

    def _rehearse(self) -> None:
        """The cold pass and every run of the window, on a workspace of its
        own over the same lake; compiles not counted."""
        ws = self._workspace()
        for edit in self.edits:
            self._run(ws, edit)
            self.rehearsed += 1
        self.rehearsed -= 1  # the cold pass
        del ws
        gc.collect()

    def setup(self, seconds: float) -> None:
        from repro.core.columnar import Table
        from repro.lake.catalog import Catalog
        from repro.lake.s3sim import ObjectStore

        self.edits = window_edits(self.config, self.traffic, self.seed, self.tables, seconds)
        t = time.perf_counter()
        catalog = Catalog(ObjectStore(self.root), int(self.config["rows_per_fragment"]))
        ns, name = self.table.rsplit(".", 1)
        catalog.create_table(ns, name, self.tables.SCHEMA, self.tables.SORT_KEY)
        month = self.tables.columns(self.config, self.seed)
        self.snapshots.append(catalog.append(self.table, Table(month)).snapshot_id)
        del month, catalog
        self.setup_parts["write_s"] = time.perf_counter() - t

        t, c = time.perf_counter(), self.compile_seconds()
        self._rehearse()
        self.setup_parts["rehearsal_s"] = time.perf_counter() - t
        self.setup_parts["rehearsal_compile_s"] = self.compile_seconds() - c

        t = time.perf_counter()
        self.ws = self._workspace()
        self._run(self.ws, self.edits[0])
        self.setup_parts["cold_s"] = time.perf_counter() - t

    # -- the measured window -------------------------------------------------
    def window(self, seconds: float) -> List[Dict]:
        """The window's runs, as set-up fixed them; ``seconds`` chose how
        many."""
        edits = self.edits[1:]
        keep = sample_positions(edits, self.seed)
        cycle = len(self.traffic["script"])
        records: List[Dict] = []
        start = time.perf_counter()
        for i, edit in enumerate(edits):
            record = {"kind": edit.label, "cycle": i // cycle, "ok": False}
            try:
                result, t0, t1 = self._run(self.ws, edit)
            except Exception as exc:  # the window records a failed run and stops
                record.update(error=repr(exc), start_s=time.perf_counter() - start)
                records.append(record)
                break
            record.update(
                ok=True,
                start_s=t0 - start,
                end_s=t1 - start,
                latency_s=t1 - t0,
                counters=counters(result),
            )
            records.append(record)
            if i in keep:
                self._samples.append((edit.describe(), host_outputs(result)))
            del result
        return records

    def samples(self) -> List[Tuple[Dict, Dict]]:
        return self._samples

    def close(self) -> None:
        self.ws = None
        gc.collect()
