"""The data-plane worker: an incremental re-execution engine.

Semantics from the paper (Fig. 2/3):

- system scans run through the shared :class:`ScanExecutor`, i.e. the
  differential cache, and feed user functions as columnar tables;
- model→model handoffs are in-memory and zero-copy;
- the ``jax`` runtime receives ``{column: jnp.ndarray}`` — the "second
  language" demonstrating that the cache sits *below* language choice;
- ``materialize=True`` publishes a model's output back to the catalog as an
  Iceberg-style table (a new snapshot), closing the loop for downstream DAGs.

Beyond the paper's leaf scans, the cache sits below EVERY node: a
:class:`Workspace` holds a second :class:`DifferentialStore` for intermediate
``@model`` outputs.  A node declared ``incremental="rowwise"`` (single- or
multi-input) or ``incremental="keyed"`` is planned exactly like a scan —

1. look up cache elements under the node's *signature* (code hash, runtime,
   upstream signatures — computed by ``compile_plan``);
2. serve the cached windows that are still valid under the current leaf
   snapshot (model elements pin the leaf fragments their rows were derived
   from, so append/overwrite invalidation reuses the scan machinery);
3. run the user function only on the *residual* window's rows;
4. UNION hit views + fresh rows zero-copy, store the residual back.

Multi-input rowwise nodes (incremental sort-merge joins) plan ONE joint
window — the intersection of their inputs' windows — and feed the function
the zip-aligned residual slice of EVERY input; their cache elements pin the
fragments of all leaf tables (labeled pins), so either side's append or
overwrite invalidates exactly the touched key ranges.  Keyed nodes
(per-key-group aggregations) reuse the identical machinery because key-range
windows can never split a key group: groups live at single key points, every
boundary the system produces (filter bounds, fragment key-min/max pins) is a
key-range bound, and residual inputs are re-read by key range — so a dirty
leaf fragment maps, via its key stats, to dirty *key groups*, each of which
is re-aggregated whole and UNION-merged with untouched cached groups.

Warm iteration cost is therefore proportional to the *edit* (rows whose
inputs actually changed), not to the pipeline: re-running an unchanged
project recomputes nothing; widening a window or appending upstream rows
recomputes only the delta; editing a function's code changes its signature
and (through signature chaining) recomputes it and its descendants from
scratch — automatically, with no user annotations beyond the contract.

A :class:`Workspace` bundles store+catalog+both caches and persists across
runs — the caches are shared by every user/pipeline in the workspace, which
is what makes the paper's multi-user §III-A workload work.
"""

from __future__ import annotations

import contextlib
import time
from dataclasses import dataclass, field
from typing import Any, Callable, Dict, List, Optional, Tuple

import numpy as np

from repro.core.cache import (
    CachePlan,
    DifferentialCache,
    DifferentialStore,
    key_runs,
    multi_pins_for,
    pins_for,
    snapshots_usable_window,
)
from repro.core.columnar import ChunkedTable, Table, concat_tables
from repro.core.device import ROW_BLOCK
from repro.core.intervals import NEG_INF, POS_INF, Interval, IntervalSet
from repro.core.planner import ScanExecutor
from repro.core.serve import DEVICE_FIELDS, ClaimKey, serve
from repro.lake.catalog import Catalog, Snapshot
from repro.lake.s3sim import ObjectStore
from repro.obs import Decision, Explainer, Metrics, RunExplanation, Tracer, get_tracer
from repro.pipeline.dag import build_dag
from repro.pipeline.dsl import Project
from repro.pipeline.filters import parse_filter
from repro.pipeline.physical import PhysicalPlan, SystemScanStep, UserFnStep, compile_plan

__all__ = ["Workspace", "RunResult", "run_project", "rowwise_lengths"]


@dataclass
class RunResult:
    outputs: Dict[str, Table]
    bytes_from_store: int
    bytes_from_cache: int
    wall_seconds: float
    plan: PhysicalPlan
    # incremental-engine ledger: how much work the user functions actually did
    rows_to_user_fns: int = 0
    bytes_from_model_cache: int = 0
    node_stats: Dict[str, Dict[str, int]] = field(default_factory=dict)
    # tiered-cache ledger: payload bytes promoted spill -> RAM for this run
    # (scan cache + model store), and residuals this run did NOT compute
    # because it subscribed to another run's in-flight claim
    bytes_from_spill: int = 0
    coalesced_waits: int = 0
    # device-tier ledger (all zero without a device tier / on numpy paths)
    bytes_h2d: int = 0  # host->device bytes this run uploaded
    bytes_d2h: int = 0  # device->host bytes (jax fn outputs landing back)
    device_hits: int = 0  # columns/pins served from resident device arrays
    device_evictions: int = 0  # tier entries LRU-demoted during this run
    gather_fast: int = 0  # multi-run gathers served by fragment_gather
    gather_fallbacks: int = 0  # multi-run gathers off the tile grid (XLA slices)
    device_union_bytes: int = 0  # output bytes assembled on device
    # spill-tier mmap promotions: payload bytes page-faulted in from local
    # spill files instead of travelling through simulated GETs
    bytes_mmap: int = 0
    # the run's cache-decision trail (repro.obs.explain.RunExplanation);
    # None when the workspace's explainer is disabled
    explanation: Optional[Any] = field(default=None, repr=False, compare=False)

    def explain(self) -> str:
        """One line per node/scan decision this run made — the action
        (serve/recompute) and the classified cause — plus the run's single
        highest-precedence primary cause."""
        if self.explanation is None:
            return "explainer disabled"
        return self.explanation.render()


class Workspace:
    """Long-lived execution context: one object store, one catalog, one
    differential scan cache, and one differential *model-output* store,
    shared by all users and languages."""

    def __init__(
        self,
        root: str,
        cache: Optional[Any] = None,
        rows_per_fragment: int = 1 << 16,
        model_cache_bytes: Optional[int] = None,
        *,
        store: Optional[ObjectStore] = None,
        catalog: Optional[Catalog] = None,
        model_store: Optional[DifferentialStore] = None,
        tenant: Optional[str] = None,
        enforce_scopes: bool = False,
        strict_contracts: bool = True,
        device: Optional[Any] = None,
        metrics: Optional[Metrics] = None,
        tracer: Optional[Tracer] = None,
        explainer: Optional[Explainer] = None,
    ):
        # every collaborator is injectable so repro.service can hand many
        # tenant workspaces ONE object store, ONE catalog, ONE scan cache and
        # ONE model store; defaults keep the single-user construction
        # (`Workspace(root)`) byte-for-byte identical to before
        if catalog is not None and rows_per_fragment != 1 << 16:
            raise ValueError(
                "rows_per_fragment applies to the workspace-built catalog; "
                "an injected catalog keeps its own"
            )
        if model_store is not None and model_cache_bytes is not None:
            raise ValueError(
                "model_cache_bytes applies to the workspace-built model "
                "store; an injected store keeps its own budget"
            )
        self.store = store if store is not None else ObjectStore(root)
        self.catalog = (
            catalog
            if catalog is not None
            else Catalog(self.store, rows_per_fragment=rows_per_fragment)
        )
        if catalog is None:
            # this workspace owns the catalog lifecycle, so restart recovery
            # is its job: resolve publish intents a crashed run left behind
            # (no-op — zero reads — when the journal is empty).  Injected
            # catalogs are recovered by their owner (the service).
            self.catalog.recover_journal()
        # ONE observability registry and tracer span the workspace: an
        # injected store's registry wins (the service wires every tenant
        # workspace to its shared one), so a single scrape covers the scan
        # cache, the model store, their spill/device tiers and the run loop
        injected = [c for c in (model_store, cache) if c is not None]
        self.metrics = (
            metrics or next((c.metrics for c in injected if c.metrics), None) or Metrics()
        )
        self.tracer = (
            tracer or next((c.tracer for c in injected if c.tracer), None) or get_tracer()
        )
        # the explainer is per-workspace by default: its cross-run signature
        # memory is keyed by node name, which is only meaningful within one
        # tenant's pipeline history
        self.explainer = explainer if explainer is not None else Explainer()
        self.scans = ScanExecutor(
            self.store,
            self.catalog,
            cache=(
                cache
                if cache is not None
                else DifferentialCache(
                    metrics=self.metrics,
                    metrics_labels={"store": "scan"},
                    tracer=self.tracer,
                )
            ),
            tenant=tenant,
            tracer=self.tracer,
            metrics=self.metrics,
            explainer=self.explainer,
        )
        # intermediate @model outputs, keyed by node signature; windows are
        # sort-key windows of the node's rowwise chain.  Plan+slice and
        # insert happen under the STORE's lock (not a per-workspace one) so
        # a concurrent run's insert — possibly through a different Workspace
        # sharing the store — can't merge/evict an element between planning
        # a hit and taking its views (see repro.core.serve)
        self.model_store = (
            model_store
            if model_store is not None
            else DifferentialStore(
                max_bytes=model_cache_bytes,
                metrics=self.metrics,
                metrics_labels={"store": "model"},
                tracer=self.tracer,
            )
        )
        # device tier (repro.core.device.DeviceTier): pass an instance, or
        # ``device=True`` for a default-budget tier.  One tier backs BOTH
        # caches so scan hits and model-output hits share the byte budget.
        # An injected store that already carries a tier keeps it (service:
        # many tenant workspaces over one device), and this workspace adopts
        # it so its executors see the same ledger.
        if device is True:
            from repro.core.device import DeviceTier

            device = DeviceTier()
        self.device = device
        if self.device is not None:
            if (
                isinstance(self.scans.cache, DifferentialStore)
                and self.scans.cache.device is None
            ):
                self.scans.cache.device = self.device
            if self.model_store.device is None:
                self.model_store.device = self.device
        else:
            self.device = self.model_store.device or self.scans.cache.device
        self.tenant = tenant
        # plan-time scope enforcement (repro.analysis): reject any plan
        # whose scans request columns outside the consumer's verified or
        # declared read scope BEFORE a single byte is read — the service
        # entry point for untrusted tenants.  strict_contracts=False
        # demotes static contract violations to warnings at DAG time.
        self.enforce_scopes = enforce_scopes
        self.strict_contracts = strict_contracts

    # -- running -------------------------------------------------------------
    def run(
        self,
        project: Project,
        verbose: bool = False,
        snapshot_pins: Optional[Dict[str, str]] = None,
    ) -> RunResult:
        """Execute ``project``.  ``snapshot_pins`` maps catalog table names to
        snapshot ids and applies wherever the user did not pin one explicitly
        (``Model(snapshot_id=…)`` wins) — tenant sessions use it to run every
        scan against the session's frozen view of the lake.  Pins are an
        execution-time choice, NOT part of node signatures: two tenants
        running the same DAG under different pins share cache elements
        wherever their snapshots' fragments agree (validity is re-checked
        per run through fragment pins)."""
        dag = build_dag(project, strict=self.strict_contracts)
        sort_keys = {
            t: self.catalog.table(t).sort_key
            for leaves in dag.scan_leaves.values()
            for _arg, ref in leaves
            for t in [ref.name]
        }
        plan = compile_plan(dag, sort_keys)
        if self.enforce_scopes:
            self._enforce_scopes(dag, plan, sort_keys)
        if verbose:
            print(plan.describe())
        t0 = time.perf_counter()
        # thread-local ledger: exact per-run attribution even when many
        # service workers drive one shared object store concurrently
        ledger = self.store.thread_stats()
        before = ledger.snapshot()
        reports_before = len(self.scans.reports)
        dev_evictions_before = (
            self.device.device_evictions if self.device is not None else 0
        )
        # liveness tick: a shared store reclaims signatures no plan has
        # referenced for N runs (plain stores have no such hook).  The scan
        # cache ticks too — its "signatures" are table names, so tables no
        # run has scanned for N runs are reclaimed the same way
        for shared in (self.model_store, self.scans.cache):
            shared.begin_run()

        results: Dict[str, Table] = {}
        node_stats: Dict[str, Dict[str, int]] = {}
        # resolve each leaf table's snapshot ONCE per run: chained rowwise
        # nodes must plan against the same snapshot their upstream's rows
        # came from, or a commit landing mid-run would let a downstream node
        # pin fragments whose rows its input never contained
        leaf_snapshots: Dict[Tuple[str, Optional[str]], Snapshot] = {}
        pins = snapshot_pins or {}
        expl = self.explainer.begin_run(tenant=self.tenant)
        with self.tracer.span(
            "run", tenant=self.tenant or "", nodes=len(plan.steps)
        ):
            for step in plan.steps:
                fn = dag.project[step.model].fn
                with self.tracer.span(
                    "node", model=step.model, incremental=step.incremental
                ):
                    if step.incremental in ("rowwise", "keyed"):
                        out, stats = self._run_incremental(
                            step, plan, fn, results, leaf_snapshots, pins, expl
                        )
                    else:
                        out, stats = self._run_full(
                            step, plan, fn, results, pins, expl
                        )
                    results[step.model] = out
                    node_stats[step.model] = stats
                    if step.materialize:
                        # the leaf snapshot this run's rows were derived from
                        # is the publication's validity anchor (see
                        # _materialize); the single-leaf provenance property
                        # cannot describe a join, so multi-leaf nodes
                        # republish in full
                        leaf_snap = (
                            self._leaf_snapshots_for(step, leaf_snapshots, pins)[
                                step.leaf_table
                            ]
                            if step.incremental in ("rowwise", "keyed")
                            and len(step.leaf_pairs) == 1
                            else None
                        )
                        with self.tracer.span("publish", model=step.model):
                            self._materialize(step, out, leaf_snap)
        self.explainer.finish_run(expl)

        delta = ledger.delta(before)
        scan_reports = self.scans.reports[reports_before:]
        # the ledger fields both the node stats and the scan reports carry
        shared_fields = {
            f: sum(s.get(f, 0) for s in node_stats.values())
            + sum(getattr(r, f) for r in scan_reports)
            for f in ("bytes_from_spill", "coalesced_waits") + DEVICE_FIELDS
        }
        result = RunResult(
            outputs=results,
            bytes_from_store=delta.bytes_read,
            bytes_from_cache=sum(r.bytes_from_cache for r in scan_reports),
            wall_seconds=time.perf_counter() - t0,
            plan=plan,
            rows_to_user_fns=sum(s["fresh_rows"] for s in node_stats.values()),
            bytes_from_model_cache=sum(
                s["model_cache_bytes"] for s in node_stats.values()
            ),
            node_stats=node_stats,
            bytes_d2h=sum(s.get("bytes_d2h", 0) for s in node_stats.values()),
            device_evictions=(
                self.device.device_evictions - dev_evictions_before
                if self.device is not None
                else 0
            ),
            **shared_fields,
            bytes_mmap=delta.bytes_mmap,
            explanation=expl if expl.enabled else None,
        )
        # run-level registry rollup: RunResult keeps exact per-run
        # attribution; these counters are the service-wide monotonic view
        # one Prometheus scrape can watch
        m, ten = self.metrics, self.tenant or ""
        m.counter("runs_total", tenant=ten).inc()
        m.counter("run_bytes_from_store", tenant=ten).inc(result.bytes_from_store)
        m.counter("run_bytes_from_cache", tenant=ten).inc(
            result.bytes_from_cache + result.bytes_from_model_cache
        )
        m.counter("run_rows_to_user_fns", tenant=ten).inc(result.rows_to_user_fns)
        m.counter("run_bytes_from_spill", tenant=ten).inc(result.bytes_from_spill)
        m.counter("run_coalesced_waits", tenant=ten).inc(result.coalesced_waits)
        m.counter("run_bytes_mmap", tenant=ten).inc(result.bytes_mmap)
        return result

    # -- plan-time scope enforcement ------------------------------------------
    def _enforce_scopes(self, dag, plan: PhysicalPlan, sort_keys) -> None:
        """Every scan's columns must lie inside the consuming node's
        verified/declared read scope (plus the table's sort key, which the
        platform attaches for windowing, and the filter's predicate
        columns, which the platform — not the function — evaluates).  A
        node whose scope is UNKNOWN and undeclared cannot be admitted at
        all: there is no bound to enforce.  Raises ScopeViolation before
        any byte leaves the store."""
        from repro.analysis import ScopeViolation
        from repro.pipeline.filters import parse_filter as _parse

        for s in plan.scans:
            mdef = dag.project[s.model]
            scope = getattr(mdef, "read_scope", None)
            code = getattr(mdef.fn, "__code__", None)
            loc = dict(
                model=s.model,
                filename=code.co_filename if code else None,
                lineno=code.co_firstlineno if code else None,
            )
            if scope is None:
                raise ScopeViolation(
                    f"read scope is UNKNOWN (analysis could not prove a "
                    f"bound and no reads= declaration was given) — an "
                    f"enforcing workspace admits only scoped nodes",
                    **loc,
                )
            sort_key = sort_keys[s.table]
            parsed = _parse(s.predicate_filter, sort_key)
            allowed = set(scope) | {sort_key} | set(parsed.predicate_columns)
            extra = sorted(set(s.columns) - allowed)
            if extra:
                raise ScopeViolation(
                    f"plan requests column(s) {extra} of {s.table} outside "
                    f"the verified read scope {sorted(scope)}",
                    **loc,
                )

    # -- node execution: full recompute (incremental="none") -----------------
    def _exec_scan(
        self,
        s: SystemScanStep,
        window: Optional[IntervalSet] = None,
        pins: Optional[Dict[str, str]] = None,
        device_consumer: bool = False,
        explain: Optional[RunExplanation] = None,
    ) -> ChunkedTable:
        meta = self.catalog.table(s.table)
        parsed = parse_filter(s.predicate_filter, meta.sort_key)
        snapshot_id = s.snapshot_id
        if snapshot_id is None and pins:
            snapshot_id = pins.get(s.table)
        return self.scans.scan(
            s.table,
            s.columns,
            window=window if window is not None else s.window,
            snapshot_id=snapshot_id,
            predicate=parsed.predicate_fn(),
            device_consumer=device_consumer,
            explain=explain,
        )

    def _run_full(
        self,
        step: UserFnStep,
        plan: PhysicalPlan,
        fn: Callable,
        results: Dict[str, Table],
        pins: Dict[str, str],
        expl: RunExplanation,
    ) -> Tuple[Table, Dict[str, int]]:
        kwargs: Dict[str, Any] = {}
        rows = 0
        use_device = self.device is not None and step.runtime == "jax"
        for arg, (kind, ref) in step.bindings:
            if kind == "scan":
                kwargs[arg] = self._exec_scan(
                    plan.scans[ref],
                    pins=pins,
                    device_consumer=use_device,
                    explain=expl,
                )
            else:
                kwargs[arg] = results[ref]
            rows += kwargs[arg].num_rows
        dev_ledger: Dict[str, int] = {}
        out = _invoke(fn, step.runtime, kwargs, self.tracer, dev_ledger)
        if expl.enabled:
            expl.record(
                Decision(
                    run_id=expl.run_id,
                    node=step.model,
                    kind="full",
                    action="recompute",
                    window=step.window.to_pairs(),
                    residual=step.window.to_pairs(),
                    cause="not-incremental",
                    detail="no incremental contract — recomputed in full",
                    root=step.model,
                    rows=rows,
                    signature=str(step.signature or "")[:16],
                )
            )
        stats = {"fresh_rows": rows, "cached_rows": 0, "model_cache_bytes": 0}
        stats.update(dev_ledger)
        return out, stats

    # -- node execution: differential (incremental="rowwise"/"keyed") --------
    def _leaf_snapshots_for(
        self,
        step: UserFnStep,
        leaf_snapshots: Dict[Tuple[str, Optional[str]], Snapshot],
        pins: Dict[str, str],
    ) -> Dict[str, Snapshot]:
        """One resolved snapshot per leaf table under the node's windowed
        chains, shared through the per-run memo (see ``run``)."""
        out: Dict[str, Snapshot] = {}
        for table, snapshot_id in step.leaf_pairs:
            if snapshot_id is None and pins:
                snapshot_id = pins.get(table)
            key = (table, snapshot_id)
            if key not in leaf_snapshots:
                if snapshot_id is not None:
                    snap = self.catalog.snapshot(table, snapshot_id)
                else:
                    snap = self.catalog.current_snapshot(table)
                leaf_snapshots[key] = snap
            out[table] = leaf_snapshots[key]
        return out

    def _residual_inputs(
        self,
        step: UserFnStep,
        plan: PhysicalPlan,
        results: Dict[str, Table],
        residual: IntervalSet,
        snapshots: Dict[str, Snapshot],
        expl: RunExplanation,
    ) -> Dict[str, Table]:
        """Each input of the node restricted to the residual window, sorted
        by the sort key and always carrying the sort-key column.  For a
        multi-input node these are zip-aligned slices: every input is
        windowed by the SAME key, so slicing each one to the same residual
        yields exactly the rows the function must align."""
        inputs: Dict[str, Table] = {}
        for arg, (kind, ref) in step.bindings:
            if kind != "scan":
                upstream = results[ref]  # windowed upstream: sorted, has the key
                rows = self._rows_in(upstream, upstream.column(step.sort_key), residual)
                inputs[arg] = rows if rows is not None else upstream.slice(0, 0)
                continue
            s = plan.scans[ref]
            # the sort key must ride along so the engine can window the
            # output; the scan cache itself is below this call
            cols = tuple(sorted(set(s.columns) | {step.sort_key}))
            s_with_key = SystemScanStep(
                model=s.model,
                arg=s.arg,
                table=s.table,
                columns=cols,
                window_pairs=s.window_pairs,
                predicate_filter=s.predicate_filter,
                snapshot_id=snapshots[s.table].snapshot_id,
            )
            chunked = self._exec_scan(s_with_key, window=residual, explain=expl)
            if chunked.chunks:
                inputs[arg] = chunked.combine().sort_by(step.sort_key)
            else:
                # zero rows in the residual (e.g. a window widened beyond the
                # data): keep the input schema-complete so the fn and the
                # windowing below still see the declared columns
                schema = self.catalog.table(s.table).schema
                dt = lambda n: np.dtype(schema[n]) if n in schema else np.int64
                inputs[arg] = Table({n: np.empty(0, dtype=dt(n)) for n in cols})
        return inputs

    def _run_incremental(
        self,
        step: UserFnStep,
        plan: PhysicalPlan,
        fn: Callable,
        results: Dict[str, Table],
        leaf_snapshots: Dict[Tuple[str, Optional[str]], Snapshot],
        snap_pins: Dict[str, str],
        expl: RunExplanation,
    ) -> Tuple[Table, Dict[str, int]]:
        snapshots = self._leaf_snapshots_for(step, leaf_snapshots, snap_pins)
        if step.window.empty:
            # degenerate joint window (e.g. BETWEEN 5 AND 1, or a join of
            # disjoint filters): run the fn once on empty, schema-complete
            # inputs — nothing to cache or serve
            kwargs = self._residual_inputs(
                step, plan, results, IntervalSet.empty_set(), snapshots, expl
            )
            out = _invoke(fn, step.runtime, kwargs, self.tracer)
            return self._windowed_output(step, kwargs, out), {
                "fresh_rows": 0,
                "cached_rows": 0,
                "model_cache_bytes": 0,
            }
        usable_fn = lambda e: snapshots_usable_window(e, snapshots)
        # device serving: a jax-runtime node consumes the hit∪residual UNION
        # as device arrays, skipping the H2D copy its _invoke would otherwise
        # pay.  Bails to numpy whenever any hit column has no device analog.
        tier = self.device if step.runtime == "jax" else None

        def plan_window() -> CachePlan:
            # cost is row-extent, not fragment bytes: serving ANY cached rows
            # saves user-function compute, even inside a partially-covered
            # fragment (unlike a physical scan, which must re-read the whole
            # fragment's column chunks either way)
            return self.model_store.plan_window(
                signature=step.signature,
                window=step.window,
                columns=(),
                cost_fn=lambda w: w.measure(),
                usable_fn=usable_fn,
                tenant=self.tenant,
                device_consumer=tier is not None,
            )

        pins: Tuple = ()

        def produce(
            residual: IntervalSet, empty: Optional[Table], ledger: Dict[str, int]
        ) -> Tuple[Table, int]:
            nonlocal pins
            # the insert's fragment pins, found before its lock is taken
            pins = (
                pins_for(*snapshots.values(), residual)
                if len(snapshots) == 1
                else multi_pins_for(snapshots, residual)
            )
            kwargs = self._residual_inputs(
                step, plan, results, residual, snapshots, expl
            )
            total_in = sum(t.num_rows for t in kwargs.values())
            if total_in == 0 and empty is not None:
                # nothing to compute; keep the output schema from a hit view
                return empty, 0
            out = _invoke(
                fn, step.runtime, kwargs, self.tracer, ledger,
                rowwise=step.incremental == "rowwise" and len(kwargs) == 1,
            )
            return self._windowed_output(step, kwargs, out), total_in

        def insert(residual: IntervalSet, fresh: Table, device_arrays) -> None:
            self.model_store.insert_window(
                signature=step.signature,
                table=step.leaf_table,
                sort_key=step.sort_key,
                window=residual,
                data=fresh,
                pins=pins,
                usable_fn=usable_fn,
                tenant=self.tenant,
                device_arrays=device_arrays,
            )

        # hold a signature read-pin for the whole node execution: a shared
        # store must not liveness/LRU-reclaim the signature group an
        # in-flight run is working against (plain stores: no-op)
        with self.model_store.reading(step.signature):
            served = serve(
                self.model_store,
                self.tracer,
                self.metrics,
                span="node",
                attrs={"model": step.model},
                lock_attrs={"store": "model", "tenant": self.tenant or ""},
                plan=plan_window,
                produce=produce,
                insert=insert,
                # one coalescing identity for the full snapshot vector: claims
                # only match when EVERY leaf snapshot agrees (single-leaf nodes
                # reduce to the plain snapshot id, matching the scan's)
                claim=ClaimKey(
                    step.signature,
                    (),
                    ",".join(f"{t}:{s.snapshot_id}" for t, s in sorted(snapshots.items())),
                    step.incremental,
                ),
                sort_key=step.sort_key,
                tier=tier,
                site="fresh",
                explain=expl.enabled,
            )
        stats = served.stats

        if expl.enabled:
            with self.tracer.span("node.explain", model=step.model):
                self.explainer.classify_node(
                    expl,
                    node=step.model,
                    kind=step.incremental,
                    sig_parts=step.sig_parts,
                    signature=step.signature,
                    window=step.window,
                    residual=served.plan.residual,
                    elements=served.elements,
                    snapshots=snapshots,
                    # lazy: only a genuine invalidation pays the pointer read
                    current_ids=lambda: expl.current_ids(self.catalog, snapshots),
                    rows=stats.fresh_rows,
                    tier=(
                        "ram+spill"
                        if stats.bytes_from_spill
                        else ("ram" if stats.cached_rows else "")
                    ),
                    quarantined=stats.quarantined,
                )

        # hit and residual windows are disjoint intervals of the key and each
        # run is key-sorted, so the runs in window order ARE the stable sort
        # of their concatenation: equal keys never span runs
        runs = _in_window_order(served.runs)
        # span the union only when there is one: the single-run serve is a
        # zero-copy view and a span around it would just be tracer tax
        union_span = (
            self.tracer.span("node.union", model=step.model, runs=len(runs))
            if len(runs) > 1 or (served.device and runs)
            else contextlib.nullcontext()
        )
        with union_span:
            if runs:
                out_tbl = _concat_runs(runs)
            else:
                out_tbl = served.fresh if served.fresh is not None else served.empty
            if served.device and runs:
                # the same UNION on device, from the same runs — bitwise
                # (device_columns[c] == jnp.asarray(out_tbl.column(c)))
                from repro.core.device import DeviceTable, device_union

                arrays = device_union(
                    [(dev, lo, hi) for _, _, dev, lo, hi in runs],
                    list(out_tbl.column_names),
                    interpret=tier.interpret,
                    ledger=stats.device,
                    bounded=tier.bounded,
                )
                out_tbl = DeviceTable(out_tbl, arrays)
        node_stats = {
            "fresh_rows": stats.fresh_rows,
            "cached_rows": stats.cached_rows,
            "model_cache_bytes": stats.cached_bytes,
            "bytes_from_spill": stats.bytes_from_spill,
            "coalesced_waits": stats.coalesced_waits,
        }
        node_stats.update(stats.device)
        return out_tbl, node_stats

    def _windowed_output(
        self, step: UserFnStep, inputs: Dict[str, Table], out: Table
    ) -> Table:
        """Enforce the node's incrementality contract and return the output
        sorted by the sort key, with the key column present.  Columns are put
        in sorted order — the canonical layout cache elements store — so cold
        and warm assemblies are chunk-compatible and byte-identical.

        Single-input rowwise keeps the position-alignment convenience (the
        engine attaches the key when the function did not return it); keyed
        and multi-input rowwise functions must ALWAYS return the key —
        aggregation collapses positions and joins zip inputs of different
        lengths, so position alignment is undefined for both."""
        if step.incremental == "rowwise" and len(inputs) == 1:
            (in_tbl,) = inputs.values()
            return self._windowed_output_rowwise(step, in_tbl, out)
        total_in = sum(t.num_rows for t in inputs.values())
        if out.num_rows > total_in:
            raise ValueError(
                f"{step.model}: incremental={step.incremental!r} functions "
                f"must not create rows ({total_in} in across "
                f"{len(inputs)} input(s), {out.num_rows} out)"
            )
        if step.sort_key not in out.column_names:
            what = (
                "a keyed aggregation"
                if step.incremental == "keyed"
                else "a multi-input rowwise function"
            )
            raise ValueError(
                f"{step.model}: {what} must return the sort key column "
                f"{step.sort_key!r} (the engine cannot position-align it)"
            )
        in_keys = np.concatenate(
            [np.asarray(t.column(step.sort_key)) for t in inputs.values()]
        )
        out_keys = np.asarray(out.column(step.sort_key))
        if out_keys.dtype != in_keys.dtype:
            # a runtime narrowed the key (jax x32): cast back and verify
            # losslessness — wrapped values cannot address the cache
            cast = out_keys.astype(in_keys.dtype)
            if out_keys.size and not np.isin(cast, in_keys).all():
                raise ValueError(
                    f"{step.model}: sort key {step.sort_key!r} came back as "
                    f"{out_keys.dtype} with values outside the input keys — "
                    f"the runtime truncated it (jax x32?); keep keys within "
                    f"its integer range"
                )
            cols = {n: out.column(n) for n in out.column_names}
            cols[step.sort_key] = cast
            out = Table(cols)
            out_keys = cast
        if out_keys.size and not np.isin(out_keys, in_keys).all():
            # output keys outside the residual's input keys would land in
            # windows this residual does not own — cached neighbours would
            # then disagree with a cold run
            raise ValueError(
                f"{step.model}: incremental={step.incremental!r} output "
                f"keys must be drawn from the input keys (an output row may "
                f"only derive from input rows at its own key)"
            )
        return out.select(sorted(out.column_names)).sort_by(step.sort_key)

    def _windowed_output_rowwise(
        self, step: UserFnStep, in_tbl: Table, out: Table
    ) -> Table:
        if out.num_rows > in_tbl.num_rows:
            raise ValueError(
                f"{step.model}: incremental='rowwise' functions must not "
                f"create rows ({in_tbl.num_rows} in, {out.num_rows} out)"
            )
        in_keys = in_tbl.column(step.sort_key)
        if out.num_rows == in_tbl.num_rows:
            # rows neither dropped nor reordered (the contract): restore the
            # EXACT input key column position-aligned, whether or not the fn
            # echoed one — runtimes may round-trip dtypes (jax x32 truncates
            # int64 to int32) and the key is the cache's addressing
            # dimension, so it must stay bit-exact
            cols = {n: out.column(n) for n in out.column_names}
            cols[step.sort_key] = in_keys
            out = Table(cols)
        else:
            if step.sort_key not in out.column_names:
                raise ValueError(
                    f"{step.model}: a rowwise function that drops rows must "
                    f"return the sort key column {step.sort_key!r} (the "
                    f"engine cannot position-align it)"
                )
            out_keys = np.asarray(out.column(step.sort_key))
            if out_keys.dtype != in_keys.dtype:
                # a runtime narrowed the key (jax x32): cast back and verify
                # losslessness — wrapped values cannot address the cache
                cast = out_keys.astype(in_keys.dtype)
                if out_keys.size and not np.isin(cast, in_keys).all():
                    raise ValueError(
                        f"{step.model}: sort key {step.sort_key!r} came back "
                        f"as {out_keys.dtype} with values outside the input "
                        f"keys — the runtime truncated it (jax x32?); avoid "
                        f"dropping rows in this runtime or keep keys within "
                        f"its integer range"
                    )
                cols = {n: out.column(n) for n in out.column_names}
                cols[step.sort_key] = cast
                out = Table(cols)
        return out.select(sorted(out.column_names)).sort_by(step.sort_key)

    # -- incremental materialization -----------------------------------------
    @staticmethod
    def _rows_in(table: Table, keys: np.ndarray, window: IntervalSet) -> Optional[Table]:
        """``table``'s rows whose sort key lies inside ``window`` (table is
        sorted by the key); None when the window holds no rows."""
        parts = [table.slice(lo, hi) for _iv, lo, hi in key_runs(keys, window)]
        if not parts:
            return None
        return concat_tables(parts)

    @staticmethod
    def _changed_since_publish(pub_leaf: Snapshot, cur_leaf: Snapshot) -> IntervalSet:
        """Key windows whose leaf fragments differ between the snapshot the
        published rows were derived from and the one this run used — the
        exact regions where published rows may disagree with the run's
        output (same signature implies same values everywhere else)."""
        if pub_leaf.snapshot_id == cur_leaf.snapshot_id:
            return IntervalSet.empty_set()
        pub_ids, cur_ids = pub_leaf.fragment_ids, cur_leaf.fragment_ids
        changed = [
            Interval(int(f.key_min), int(f.key_max) + 1)
            for f in pub_leaf.fragments
            if f.fragment_id not in cur_ids
        ] + [
            Interval(int(f.key_min), int(f.key_max) + 1)
            for f in cur_leaf.fragments
            if f.fragment_id not in pub_ids
        ]
        return IntervalSet(changed)

    def _materialize(
        self, step: UserFnStep, table: Table, leaf_snapshot: Optional[Snapshot]
    ) -> None:
        """Publish a model's output to the catalog *incrementally*.

        The published table mirrors the latest run's output.  For a rowwise
        node whose signature matches the last publish, only the diff is
        committed — instead of re-appending the full output every run (which
        both grew the table unboundedly and duplicated rows):

        - windows whose *leaf fragments* changed between the publication's
          recorded leaf snapshot and this run's are overwritten (keying on
          the published state, not on "recomputed this run", matters: a
          window another run already freshened into the shared cache arrives
          here as a cache hit, yet still must be republished);
        - windows of the run the table never covered are appended;
        - windows the run no longer covers are deleted.

        A signature change (code/schema edit), a non-rowwise node, or a
        publication without recorded provenance republishes in full.  The
        whole diff lands in ONE atomic commit (``overwrite_ranges``) carrying
        the ``signature`` + ``leaf_snapshot`` provenance properties, so
        concurrent readers see either the previous or the new publication —
        never a torn mix — and an interrupted publish leaves provenance
        untouched for the retry to re-derive the same diff.

        The commit is optimistic (``expected_parent``): under the service,
        two tenants materializing the same model race on the catalog CAS and
        the loser's :class:`~repro.lake.catalog.CommitConflict` propagates to
        the session retry loop.
        """
        model_name = step.model
        full = f"models.{model_name}"
        # rowwise outputs are canonicalized to sorted column order, so
        # "first column" is NOT the sort key — use the plan's when present
        sort_key = step.sort_key
        if sort_key is None or sort_key not in table.column_names:
            sort_key = table.column_names[0]
        table = table.sort_by(sort_key)
        sig = step.signature or ""
        try:
            meta = self.catalog.table(full)
            created = False
        except KeyError:
            try:
                meta = self.catalog.create_table(
                    "models", model_name, table.schema(), sort_key
                )
                created = True
            except FileExistsError:
                # lost a concurrent create race: treat the winner's table as
                # pre-existing; the CAS on the commits below still protects
                # the content (losers raise CommitConflict -> session retry)
                meta = self.catalog.table(full)
                created = False
        cur, published = self.catalog.pointer_state(full)
        published_sig = published.get("signature")
        published_leaf_id = published.get("leaf_snapshot")
        props = {"signature": sig}
        if leaf_snapshot is not None:
            props["leaf_snapshot"] = leaf_snapshot.snapshot_id

        if (
            created
            or leaf_snapshot is None
            or published_sig != sig
            or not published_leaf_id
        ):
            # first publish / arbitrary transformation / code or schema edit
            # / unknown provenance: mirror the full output
            if not cur.fragments:
                if table.num_rows:
                    self.catalog.append(
                        full, table, expected_parent=cur.snapshot_id, properties=props
                    )
                return
            new_schema = table.schema()
            self.catalog.overwrite_range(
                full,
                NEG_INF,
                POS_INF,
                data=table,
                expected_parent=cur.snapshot_id,
                properties=props,
                schema=new_schema if new_schema != meta.schema else None,
            )
            return

        # same signature, rowwise, known provenance: differential publish
        # against the windows the current fragment set covers
        pub_window = IntervalSet(
            [Interval(int(f.key_min), int(f.key_max) + 1) for f in cur.fragments]
        )
        new_window = step.window
        keys = table.column(sort_key)
        pub_leaf = self.catalog.snapshot(step.leaf_table, published_leaf_id)
        stale = self._changed_since_publish(pub_leaf, leaf_snapshot)

        # the diff, all of it landing in one commit:
        # - deleted: published but outside this run's output (narrowed filter)
        # - rewritten: published windows whose leaf rows changed since the
        #   recorded publication
        # - added: windows the table never covered (widened filter, appended
        #   upstream rows — whether recomputed or cache-served)
        deleted = pub_window.difference(new_window)
        rewritten = stale.intersect(pub_window).intersect(new_window)
        added = new_window.difference(pub_window)
        rows = self._rows_in(table, keys, rewritten.union(added))
        drop = deleted.union(rewritten)
        if not drop.empty:
            self.catalog.overwrite_ranges(
                full,
                drop.to_pairs(),
                data=rows,
                expected_parent=cur.snapshot_id,
                properties=props,
            )
        elif rows is not None:
            self.catalog.append(
                full, rows, expected_parent=cur.snapshot_id, properties=props
            )


def _to_table(value: Any) -> Table:
    if isinstance(value, Table):
        return value
    if isinstance(value, ChunkedTable):
        return value.combine()
    if isinstance(value, dict):
        cols = {}
        for k, v in value.items():
            arr = np.asarray(v)
            cols[k] = arr
        return Table(cols)
    raise TypeError(f"model must return Table/ChunkedTable/dict, got {type(value)}")


# a single-input rowwise jax stage runs on pieces of at most PIECE_ROWS
# rows, each padded on the host to a power of two of at least ROW_BLOCK rows
# and its outputs trimmed back after the copy: eager jax compiles every op
# once per array length, and these are the only lengths such a stage meets,
# whatever lengths its residuals take
PIECE_ROWS = 1 << 20


def _in_window_order(runs: List[Tuple]) -> List[Tuple]:
    """``(window lo, table, device arrays, row lo, row hi)`` runs sorted by
    window lo, consecutive runs of adjacent rows of one table joined: the
    residual's intervals with no hit between them are one slice of it."""
    out: List[Tuple] = []
    for run in sorted(runs, key=lambda r: r[0]):
        if out and out[-1][1] is run[1] and out[-1][4] == run[3]:
            out[-1] = out[-1][:4] + (run[4],)
        else:
            out.append(run)
    return out


def _concat_runs(runs: List[Tuple]) -> Table:
    """The host UNION of ``(window lo, table, device arrays, row lo, row hi)``
    runs given in window order: one concatenation per column.  A single run
    stays a zero-copy view."""
    if len(runs) == 1:
        _, table, _, lo, hi = runs[0]
        return table.slice(lo, hi)
    return Table({
        c: np.concatenate([t.column(c)[lo:hi] for _, t, _, lo, hi in runs])
        for c in runs[0][1].column_names
    })


def _padded_length(rows: int) -> int:
    return max(ROW_BLOCK, 1 << (rows - 1).bit_length())


def rowwise_lengths(rows: int) -> List[int]:
    """Every length a single-input rowwise jax stage is called at when its
    input holds at most ``rows`` rows."""
    top = _padded_length(min(rows, PIECE_ROWS))
    return [ROW_BLOCK << k for k in range((top // ROW_BLOCK).bit_length())]


def _invoke(
    fn: Callable,
    runtime: str,
    kwargs: Dict[str, Any],
    tracer: Tracer,
    ledger: Optional[Dict[str, int]] = None,
    rowwise: bool = False,
) -> Table:
    """Call a user function on its inputs.  ``rowwise`` marks a
    single-input rowwise stage (each output row derives from its own input
    row alone): a jax one then runs piece by piece, at the lengths
    :func:`rowwise_lengths` lists.  A padded piece repeats its last
    row, so its padding rows are in the stage's domain; their outputs are
    copied back with the rest and dropped on the host."""
    if runtime == "numpy":
        prepared = {
            k: (v.combine() if isinstance(v, ChunkedTable) else v)
            for k, v in kwargs.items()
        }
        with tracer.span("node.call", runtime=runtime):
            out = fn(**prepared)
        return _to_table(out)
    if runtime != "jax":
        raise ValueError(f"unknown runtime {runtime!r}")
    if rowwise:
        ((arg, v),) = kwargs.items()
        if v.num_rows:
            host = v.combine() if isinstance(v, ChunkedTable) else v
            outs = []
            for lo in range(0, host.num_rows, PIECE_ROWS):
                piece = host.slice(lo, min(lo + PIECE_ROWS, host.num_rows))
                n = piece.num_rows
                pad = _padded_length(n) - n
                if pad:
                    piece = Table({
                        c: np.pad(piece.column(c), (0, pad), mode="edge")
                        for c in piece.column_names
                    })
                out = _invoke_jax(fn, {arg: piece}, tracer, ledger)
                outs.append({k: col[:n] for k, col in out.items()})
            if len(outs) == 1:
                return Table(outs[0])
            return Table({k: np.concatenate([o[k] for o in outs]) for k in outs[0]})
    return Table(_invoke_jax(fn, kwargs, tracer, ledger))


def _invoke_jax(
    fn: Callable,
    kwargs: Dict[str, Any],
    tracer: Tracer,
    ledger: Optional[Dict[str, int]],
) -> Dict[str, np.ndarray]:
    import jax
    import jax.numpy as jnp

    def _count(key: str, by: int) -> None:
        if ledger is not None:
            ledger[key] = ledger.get(key, 0) + by

    prepared = {}
    h2d = 0
    with tracer.span("device.h2d", site="input") as sp:
        for k, v in kwargs.items():
            # device-resident inputs (DeviceTable / DeviceChunkedTable)
            # hand their columns straight to the fn — zero host
            # round-trips; any column without a device copy falls back
            # to the H2D conversion
            devcols = getattr(v, "device_columns", None) or {}
            names = v.column_names
            cols: Dict[str, Any] = {}
            host = None
            for name in names:
                arr = devcols.get(name)
                if arr is not None:
                    _count("device_hits", 1)
                else:
                    if host is None:
                        host = v.combine() if isinstance(v, ChunkedTable) else v
                    arr = jnp.asarray(host.column(name))
                    nbytes = int(arr.nbytes)
                    h2d += nbytes
                    _count("bytes_h2d", nbytes)
                cols[name] = arr
            prepared[k] = cols
        if tracer.enabled:
            sp.attrs["bytes"] = h2d
    with tracer.span("node.call", runtime="jax"):
        out = fn(**prepared)
    if not isinstance(out, dict):
        raise TypeError("jax models must return {column: jnp.ndarray}")
    if tracer.enabled:
        # the host's wait for the device queue, apart from the copy
        with tracer.span("device.wait"):
            jax.block_until_ready(out)
    host_out = {}
    d2h = 0
    with tracer.span("device.d2h") as sp:
        for k, v in out.items():
            arr = np.asarray(v)
            nbytes = int(arr.nbytes)
            d2h += nbytes
            _count("bytes_d2h", nbytes)
            host_out[k] = arr
        if tracer.enabled:
            sp.attrs["bytes"] = d2h
    return host_out


def run_project(workspace: Workspace, project: Project, **kw) -> RunResult:
    return workspace.run(project, **kw)
