"""The system scan function (paper Fig. 3) — logical scan → physical plan →
assembled columnar dataframe, through a pluggable cache policy.

`ScanExecutor.scan()` is the function Bauplan inserts *before* user code: it
translates a `Model("raw_data", columns=…, filter=…)` reference into cache
slices + residual object-storage reads, UNIONs them (zero-copy,
:class:`ChunkedTable`), applies any post-predicate, and hands the caller a
columnar dataframe.  It also returns a :class:`ScanReport` so benchmarks can
attribute bytes to cache vs store — the paper's Table II currency.

A ``ResultCache`` (memoizing the *final* output under the exact input hash,
post-predicate included) is implemented here rather than in
``core.baselines`` because it wraps the whole executor, not the scan layer.
"""

from __future__ import annotations

from collections import OrderedDict
from dataclasses import dataclass
from typing import TYPE_CHECKING, Callable, List, Optional, Sequence, Tuple

import numpy as np

from repro.core.baselines import NoCache
from repro.core.cache import CacheStore, DifferentialCache
from repro.core.columnar import ChunkedTable, Table
from repro.core.intervals import IntervalSet
from repro.core.scan import Scan, read_window
from repro.core.serve import DEVICE_FIELDS, ClaimKey, serve
from repro.lake.s3sim import ObjectStore
from repro.obs.explain import Explainer, RunExplanation
from repro.obs.metrics import Metrics
from repro.obs.trace import Tracer, get_tracer

if TYPE_CHECKING:  # annotation-only: a runtime import would close the
    # lake -> fragments -> core -> ... -> lake.catalog package cycle
    from repro.lake.catalog import Catalog


__all__ = ["ScanExecutor", "ScanReport", "ResultCachingExecutor", "Predicate"]

# A post-scan row predicate: column arrays in, boolean mask out.  It is applied
# AFTER assembly and is NOT part of the cache geometry (window/projections),
# mirroring real engines: window+projection push down, residual predicates
# filter in memory.
Predicate = Callable[[Table], np.ndarray]


@dataclass
class ScanReport:
    table: str
    snapshot_id: str
    columns: Tuple[str, ...]
    window_pairs: tuple
    bytes_from_store: int
    bytes_from_cache: int
    store_requests: int
    cache_chunks: int  # hit-served cache views ONLY (never the residual)
    fully_cached: bool
    residual_rows: int = 0  # rows fetched fresh from object storage
    bytes_from_spill: int = 0  # payload bytes promoted spill -> RAM for hits
    bytes_mmap: int = 0  # mmap-promoted spill payload bytes (zero-copy reads)
    coalesced_waits: int = 0  # replans after subscribing to another's claim
    # device-tier ledger (all zero on the numpy path)
    bytes_h2d: int = 0  # host->device bytes this scan uploaded
    device_hits: int = 0  # hit columns served from resident device pins
    gather_fast: int = 0  # multi-run gathers served by fragment_gather
    gather_fallbacks: int = 0  # multi-run gathers off the tile grid (XLA slices)
    device_union_bytes: int = 0  # output bytes assembled on device

    @property
    def bytes_processed(self) -> int:
        """Bytes moved from object storage — the paper's Table II metric."""
        return self.bytes_from_store


class ScanExecutor:
    """Executes logical scans through a cache policy against a catalog."""

    def __init__(
        self,
        store: ObjectStore,
        catalog: Catalog,
        cache: Optional[CacheStore] = None,
        tenant: Optional[str] = None,
        tracer: Optional[Tracer] = None,
        metrics: Optional[Metrics] = None,
        explainer: Optional[Explainer] = None,
    ):
        self.store = store
        self.catalog = catalog
        self.cache = cache if cache is not None else DifferentialCache()
        self.tenant = tenant  # attribution when the cache is tenant-aware
        # obs wiring: share the cache's registry/tracer unless given one, so
        # spill-tier hit bytes and scan-level series land in ONE registry
        self.tracer = tracer or self.cache.tracer or get_tracer()
        self.metrics = metrics or self.cache.metrics or Metrics()
        self.explainer = explainer if explainer is not None else Explainer()
        self.reports: List[ScanReport] = []

    # -- the system function -------------------------------------------------
    def scan(
        self,
        table: str,
        columns: Sequence[str],
        window: Optional[IntervalSet] = None,
        snapshot_id: Optional[str] = None,
        predicate: Optional[Predicate] = None,
        sorted_output: bool = False,
        device_consumer: bool = False,
        explain: Optional[RunExplanation] = None,
    ) -> ChunkedTable:
        with self.tracer.span("scan", table=table, tenant=self.tenant or ""):
            meta = self.catalog.table(table)
            snapshot = (
                self.catalog.snapshot(table, snapshot_id)
                if snapshot_id
                else self.catalog.current_snapshot(table)
            )
            window = window if window is not None else IntervalSet.everything()
            scan = Scan(table, snapshot.snapshot_id, tuple(columns), window)
            phys = scan.physical_columns(meta.sort_key)
            proj = [c for c in phys if c in scan.columns]

            # device serving path: only when the consumer declared itself a jax
            # node AND the cache carries a device tier AND this scan's output is
            # the raw hit∪residual UNION (a post-predicate or a host sort would
            # reshape rows after assembly — those scans stay on the numpy path)
            tier = (
                self.cache.device
                if device_consumer and predicate is None and not sorted_output
                else None
            )
            plan_kwargs = {"device_consumer": True} if tier is not None else {}

            def produce(residual: IntervalSet, _empty, _ledger) -> Tuple[Table, int]:
                fresh = read_window(
                    self.store, snapshot, residual, phys, meta.sort_key,
                    schema=meta.schema,
                )
                return fresh, fresh.num_rows

            def insert(residual: IntervalSet, fresh: Table, device_arrays) -> None:
                kwargs = {} if device_arrays is None else {"device_arrays": device_arrays}
                self.cache.insert(
                    scan, snapshot, meta.sort_key, residual, fresh,
                    tenant=self.tenant, **kwargs,
                )

            # thread-local ledger: per-scan deltas stay exact when concurrent
            # runs (repro.service workers) share this object store
            ledger = self.store.thread_stats()
            before = ledger.snapshot()
            served = serve(
                self.cache,
                self.tracer,
                self.metrics,
                span="scan",
                attrs={"table": table},
                lock_attrs={"store": "scan", "tenant": self.tenant or ""},
                plan=lambda: self.cache.plan(
                    scan, snapshot, meta.sort_key, tenant=self.tenant, **plan_kwargs
                ),
                produce=produce,
                insert=insert,
                claim=ClaimKey(scan.table, phys, snapshot.snapshot_id, "scan"),
                sort_key=meta.sort_key,
                tier=tier,
                site="scan",
                pin_columns=proj,
                explain=explain is not None and explain.enabled,
            )
            delta = ledger.delta(before)
            stats = served.stats
            # the UNION's chunks: the hits' views in plan order, then the
            # residual as one chunk
            chunks = list(served.views)
            fresh = served.fresh
            if fresh is not None and fresh.num_rows:
                chunks.append(fresh)

            # the scan-level series the ScanReport fields reconcile against
            m = self.metrics
            m.counter("scan_requests", table=table).inc()
            m.counter("bytes_from_store", table=table).inc(delta.bytes_read)
            m.counter("store_requests", table=table).inc(delta.get_requests)
            m.counter("bytes_mmap", table=table).inc(delta.bytes_mmap)

            if explain is not None and explain.enabled:
                hit_tier = (
                    "ram+spill"
                    if stats.bytes_from_spill
                    else ("ram" if stats.cached_bytes else "store")
                )
                self.explainer.classify_scan(
                    explain,
                    table=table,
                    window=window,
                    residual=served.plan.residual,
                    columns=tuple(phys),
                    elements=served.elements,
                    snapshot=snapshot,
                    # lazy: only a genuine invalidation pays the pointer read
                    current_id=lambda: explain.current_ids(self.catalog, [table])[table],
                    rows=stats.fresh_rows,
                    tier=hit_tier,
                    quarantined=stats.quarantined,
                )

            with self.tracer.span("scan.union", table=table, chunks=len(chunks)):
                out = ChunkedTable(chunks)
                if predicate is not None:
                    out = ChunkedTable([c.filter(predicate(c)) for c in out.chunks])
                # sort while the sort key is still physically present, THEN
                # project it away unless requested — sorted_output must hold even
                # when the key is not among the projections
                if sorted_output and out.chunks:
                    out = ChunkedTable([out.combine().sort_by(meta.sort_key)])
                out = out.select(proj)
                if served.device and chunks:
                    # assemble the UNION on device too: run layout mirrors the
                    # host chunk order exactly, so device_columns[c] is
                    # bitwise-equal to jnp.asarray(out.column(c)) —
                    # property-checked in test_device
                    from repro.core.device import DeviceChunkedTable, device_union

                    hits = served.runs[: len(served.views)]
                    dev_runs = [(dev, lo, hi) for _, _, dev, lo, hi in hits]
                    if fresh is not None and fresh.num_rows:
                        dev_runs.append((served.fresh_dev, 0, fresh.num_rows))
                    arrays = device_union(
                        dev_runs, proj, interpret=tier.interpret, ledger=stats.device,
                        bounded=tier.bounded,
                    )
                    out = DeviceChunkedTable(out.chunks, arrays)

            dev = stats.device
            self.reports.append(
                ScanReport(
                    table=table,
                    snapshot_id=snapshot.snapshot_id,
                    columns=scan.columns,
                    window_pairs=window.to_pairs(),
                    bytes_from_store=delta.bytes_read,
                    bytes_from_cache=stats.cached_bytes,
                    store_requests=delta.get_requests,
                    cache_chunks=len(served.views),
                    fully_cached=served.plan.fully_cached,
                    residual_rows=stats.fresh_rows,
                    bytes_from_spill=stats.bytes_from_spill,
                    bytes_mmap=delta.bytes_mmap,
                    coalesced_waits=stats.coalesced_waits,
                    **{f: dev.get(f, 0) for f in DEVICE_FIELDS},
                )
            )
            return out

    # -- accounting ----------------------------------------------------------
    def total_bytes_processed(self) -> int:
        return sum(r.bytes_from_store for r in self.reports)


class ResultCachingExecutor:
    """The paper's *result cache* baseline: memoize the fully-assembled output
    under the hash of the exact inputs (predicate identity included).

    ``max_bytes`` bounds the memo with LRU eviction — an unbounded memo would
    hand the baseline infinite memory on long workloads and skew
    Table-II-style comparisons against the (byte-budgeted) scan caches."""

    def __init__(
        self, store: ObjectStore, catalog: Catalog, max_bytes: Optional[int] = None
    ):
        self.inner = ScanExecutor(store, catalog, cache=NoCache())
        self.max_bytes = max_bytes
        self._memo: "OrderedDict[tuple, ChunkedTable]" = OrderedDict()
        self._bytes = 0  # running memo size: eviction must not be O(n²)
        self.lookups = 0
        self.hits = 0
        self.evictions = 0

    @property
    def nbytes(self) -> int:
        return self._bytes

    @property
    def reports(self) -> List[ScanReport]:
        return self.inner.reports

    def scan(
        self,
        table: str,
        columns: Sequence[str],
        window: Optional[IntervalSet] = None,
        snapshot_id: Optional[str] = None,
        predicate: Optional[Predicate] = None,
        sorted_output: bool = False,
    ) -> ChunkedTable:
        self.lookups += 1
        snapshot = (
            self.inner.catalog.snapshot(table, snapshot_id)
            if snapshot_id
            else self.inner.catalog.current_snapshot(table)
        )
        # key on the predicate OBJECT, not id(): the tuple key holds a strong
        # reference, so a memo hit implies the very same (still-alive)
        # callable — id() alone gives false hits once a collected
        # predicate's id is recycled for a new one
        key = (
            table,
            snapshot.snapshot_id,
            tuple(sorted(columns)),
            (window or IntervalSet.everything()).to_pairs(),
            predicate,
            sorted_output,
        )
        if key in self._memo:
            self.hits += 1
            self._memo.move_to_end(key)  # LRU freshness
            # record a zero-byte report so workload traces stay comparable
            self.inner.reports.append(
                ScanReport(table, snapshot.snapshot_id, tuple(sorted(columns)),
                           key[3], 0, self._memo[key].nbytes, 0,
                           len(self._memo[key].chunks), True)
            )
            return self._memo[key]
        out = self.inner.scan(table, columns, window, snapshot_id, predicate, sorted_output)
        if self.max_bytes is not None and out.nbytes > self.max_bytes:
            # a result bigger than the whole budget is not retained — and it
            # must not churn out every hot entry on its way through
            return out
        self._memo[key] = out
        self._bytes += out.nbytes
        if self.max_bytes is not None:
            while self._bytes > self.max_bytes:  # evict LRU-first
                _, evicted = self._memo.popitem(last=False)
                self._bytes -= evicted.nbytes
                self.evictions += 1
        return out

    def total_bytes_processed(self) -> int:
        return self.inner.total_bytes_processed()
