"""The serve loop under both the system scan
(:class:`~repro.core.planner.ScanExecutor`) and the pipeline's incremental
nodes (``repro.pipeline.executor.Workspace``): plan a window against cached
elements, claim the residual, take the hits' views and device pins, compute
the residual and insert it.  The caller supplies what differs — its store's
plan and insert, the residual's producer (an object-store read or a user
function), the claim's identity, the columns to pin — and assembles the
UNION itself.

The plan, the claim, the views and the pins are taken in one critical
section under the store's lock: a concurrent insert may merge or evict the
elements a plan's hits reference once the lock is released.  Claim waits
hold no lock.  The store's lock is taken before the device tier's, never
the reverse.
"""

from __future__ import annotations

import time
from dataclasses import dataclass, field
from typing import Any, Callable, Dict, Hashable, List, NamedTuple, Optional, Sequence, Tuple

from repro.core.cache import CachePlan, CacheStore, key_runs
from repro.core.columnar import Table
from repro.core.intervals import IntervalSet
from repro.obs.metrics import Metrics
from repro.obs.trace import Tracer

__all__ = ["DEVICE_FIELDS", "ClaimKey", "ServeStats", "Served", "serve"]

# the device ledger's counters every scan report and run result carries
DEVICE_FIELDS = (
    "bytes_h2d", "device_hits", "gather_fast", "gather_fallbacks", "device_union_bytes",
)


class ClaimKey(NamedTuple):
    """A residual claim's identity (see ``SharedStore.claim_residual``)."""

    signature: Hashable
    columns: Sequence[str]  # the columns served; () for an element's own
    snapshot_id: str
    kind: str  # "scan", "rowwise" or "keyed"; also the counters' label


@dataclass
class ServeStats:
    """The ledger the scan report and the node stats share.  Promotions and
    quarantines count over every plan round: a discarded plan's are still
    this call's doing."""

    cached_rows: int = 0  # rows the final plan's hits serve
    cached_bytes: int = 0  # bytes of their views
    fresh_rows: int = 0  # rows the producer reports for the residual
    bytes_from_spill: int = 0  # payload bytes promoted spill -> RAM
    quarantined: int = 0  # spilled payloads that failed verification
    coalesced_waits: int = 0  # rounds spent waiting on another run's claim
    # the device ledger: pins, spill -> device promotions, residual uploads,
    # and what the caller's producer and device UNION add
    device: Dict[str, int] = field(default_factory=dict)


@dataclass
class Served:
    plan: CachePlan  # the final round's
    # (window lo, host table, device arrays, row lo, row hi): the hits' runs
    # in plan order, then the residual's in key order
    runs: List[Tuple]
    views: List[Table]  # the hits' runs as zero-copy views, in plan order
    fresh: Optional[Table]  # the residual's rows; None for an empty residual
    fresh_dev: Optional[Dict[str, Any]]
    empty: Optional[Table]  # a hit's columns with no rows
    device: bool  # every run carries device arrays
    elements: List[Tuple]  # (window, pins, columns, table) before the insert
    stats: ServeStats


def serve(
    store: CacheStore,
    tracer: Tracer,
    metrics: Metrics,
    *,
    span: str,
    attrs: Dict[str, Any],
    lock_attrs: Dict[str, str],
    plan: Callable[[], CachePlan],
    produce: Callable[[IntervalSet, Optional[Table], Dict[str, int]], Tuple[Table, int]],
    insert: Callable[[IntervalSet, Table, Optional[Dict[str, Any]]], Any],
    claim: ClaimKey,
    sort_key: str,
    tier=None,
    site: str = "",
    pin_columns: Optional[Sequence[str]] = None,
    explain: bool = False,
) -> Served:
    """Serve one window through ``store``.

    Spans ``<span>.plan``, ``.claim_wait``, ``.residual`` (``rows``) and
    ``.insert`` carry ``attrs``; the wait for the store's lock is span
    ``store.lock_wait`` with ``lock_attrs``.  Hits are viewed in
    ``claim.columns``.  ``produce(residual, empty, ledger)`` returns the
    residual's rows sorted by ``sort_key`` and the row count to report;
    ``empty`` is a hit's columns with no rows, or None, and ``ledger`` the
    device ledger.  ``insert(residual, rows, device_arrays)`` stores them.
    With a ``tier`` (a DeviceTier; None serves host only) every hit's
    ``pin_columns`` are pinned and the residual's uploaded (``device.h2d``
    span ``site``); any that cannot be leaves the result host only.  A
    node's output columns exist only once its function has run, so
    ``pin_columns`` None pins each table's own.  ``explain`` captures the
    elements for the explainer."""
    locked = lambda: tracer.acquire(store.lock, "store.lock_wait", **lock_attrs)
    stats = ServeStats()
    ledger = stats.device
    elements: List[Tuple] = []
    owned = None
    try:
        while True:
            runs: List[Tuple] = []
            views: List[Table] = []
            empty: Optional[Table] = None
            dev_ok = tier is not None
            stats.cached_rows = stats.cached_bytes = 0
            wait = None
            with locked(), tracer.span(f"{span}.plan", **attrs):
                p = plan()
                stats.bytes_from_spill += p.promoted_spill_bytes
                stats.quarantined += p.quarantined
                if p.bytes_h2d:
                    ledger["bytes_h2d"] = ledger.get("bytes_h2d", 0) + p.bytes_h2d
                if not p.residual.empty:
                    if explain:
                        elements = [
                            (e.window, e.pins, e.columns, e.table)
                            for e in store.elements(claim.signature)
                        ]
                    owned, wait = store.claim_residual(
                        claim.signature, p.residual, claim.columns,
                        snapshot_id=claim.snapshot_id, kind=claim.kind,
                    )
                if wait is None:
                    for hit in p.hits:
                        e = hit.element
                        view = e.data.select(claim.columns or e.columns)
                        if empty is None:
                            empty = view.slice(0, 0)
                        arrays = None
                        if dev_ok:
                            arrays = tier.pin_columns(
                                e, e.columns if pin_columns is None else pin_columns, ledger
                            )
                            dev_ok = arrays is not None
                        for iv, lo, hi in e.window_runs(hit.window):
                            runs.append((iv.lo, view, arrays, lo, hi))
                            views.append(view.slice(lo, hi))
                            stats.cached_rows += hi - lo
                            stats.cached_bytes += views[-1].nbytes
            if wait is None:
                break
            # another run computes an overlapping residual: wait, then
            # replan against its insert.  The timeout is the store's claim
            # lease, so a dead owner's claim expires before a waiter gives up
            stats.coalesced_waits += 1
            t_wait = time.perf_counter()
            with tracer.span(f"{span}.claim_wait", **attrs):
                wait.wait(timeout=store.claim_timeout)
            metrics.histogram("claim_wait_seconds", kind=claim.kind).observe(
                time.perf_counter() - t_wait
            )

        fresh = fresh_dev = None
        if not p.residual.empty:
            with tracer.span(f"{span}.residual", **attrs) as res_sp:
                fresh, stats.fresh_rows = produce(p.residual, empty, ledger)
                res_sp.attrs["rows"] = stats.fresh_rows
            # fresh rows interleave with hit windows in key order: one run
            # per residual interval
            fresh_runs = key_runs(fresh.column(sort_key), p.residual)
            if sum(hi - lo for _, lo, hi in fresh_runs) != fresh.num_rows:
                raise ValueError(
                    f"{' '.join(map(str, attrs.values()))}: output keys outside "
                    f"the residual window {p.residual} (an output row may only "
                    f"derive from input rows at its own key)"
                )
            if dev_ok and fresh.num_rows:
                from repro.core.device import upload_residual

                fresh_dev = upload_residual(
                    fresh, fresh.column_names if pin_columns is None else pin_columns,
                    ledger, tracer, site, tier.bounded,
                )
                dev_ok = fresh_dev is not None
            # the fresh device arrays go with the insert, so the store's
            # merge replicates device to device: warm runs upload only the
            # residual, never the merged payload
            with locked(), tracer.span(f"{span}.insert", **attrs):
                insert(p.residual, fresh, fresh_dev)
            runs.extend((iv.lo, fresh, fresh_dev, lo, hi) for iv, lo, hi in fresh_runs)
    finally:
        if owned is not None:
            store.release_residual(owned)

    metrics.counter("residual_rows", kind=claim.kind).inc(stats.fresh_rows)
    metrics.counter("cache_hit_bytes", tier="ram").inc(stats.cached_bytes)
    if stats.coalesced_waits:
        metrics.counter("coalesced_wait_rounds", kind=claim.kind).inc(stats.coalesced_waits)
    return Served(p, runs, views, fresh, fresh_dev, empty, dev_ok, elements, stats)
