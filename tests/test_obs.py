"""repro.obs (ISSUE 9): structured tracing, the unified metrics registry,
and the cache-decision explainer.

Covers the span tracer (nesting, thread isolation, save/load + Chrome
export, disabled no-ops, bounded retention), the Metrics registry (labels,
histograms, Prometheus exposition, MetricAttr write-through), the
derived-not-duplicated consistency between registry series and the legacy
reports (ScanReport, RunResult, SharedStore.stats(), ServiceReport), the
explainer's 11-edit cause matrix plus its lazy catalog-read discipline,
mmap-promoted spill byte attribution, the configurable claim-residual
lease (dead-claim takeover + an executor abandoning a dead claim), and a
threaded multi-tenant tracing stress test whose metrics totals reconcile
exactly with the per-run reports.
"""

import threading
import time

import numpy as np
import pytest

from repro.core.columnar import Table
from repro.core.intervals import Interval, IntervalSet
from repro.obs import Explainer, MetricAttr, Metrics, Tracer
from repro.obs.trace import chrome_trace, load_trace
from repro.pipeline import Model, Project, Workspace, model
from repro.service import DONE, PipelineService, SharedStore

from test_service import (
    TABLE,
    assert_outputs_bitwise_equal,
    pipeline_project,
    write_events,
)


# ------------------------------------------------------------------- tracer
def test_tracer_nesting_and_attrs():
    tr = Tracer()
    with tr.span("root", a=1) as sp:
        with tr.span("child"):
            pass
        sp.attrs["rows"] = 5
    roots = tr.roots()
    assert len(roots) == 1
    root = roots[0]
    assert root.name == "root"
    assert root.attrs == {"a": 1, "rows": 5}
    assert [c.name for c in root.children] == ["child"]
    child = root.children[0]
    assert root.t0_ns <= child.t0_ns <= child.t1_ns <= root.t1_ns
    assert root.tid == child.tid == threading.get_ident()


def test_tracer_disabled_is_noop():
    tr = Tracer(enabled=False)
    with tr.span("x") as sp:
        sp.attrs["k"] = 1  # scratch dict; never read
    tr.add_span("y", 0, 10)
    assert tr.roots() == []
    assert tr.summary() == {}


def test_tracer_exception_annotates_span():
    tr = Tracer()
    with pytest.raises(ValueError):
        with tr.span("boom"):
            raise ValueError("x")
    (root,) = tr.roots()
    assert root.attrs["error"] == "ValueError"
    assert root.t1_ns >= root.t0_ns


def test_tracer_threads_do_not_cross_nest():
    tr = Tracer()
    barrier = threading.Barrier(4)

    def work(i):
        barrier.wait()
        for _ in range(50):
            with tr.span("outer", thread=i):
                with tr.span("inner", thread=i):
                    pass

    threads = [threading.Thread(target=work, args=(i,)) for i in range(4)]
    for t in threads:
        t.start()
    for t in threads:
        t.join()
    roots = tr.roots()
    assert len(roots) == 200
    for root in roots:
        assert root.name == "outer"
        (inner,) = root.children
        # a child born on another thread would violate both of these
        assert inner.attrs["thread"] == root.attrs["thread"]
        assert inner.tid == root.tid


def test_tracer_add_span_nests_and_roots():
    tr = Tracer()
    with tr.span("run"):
        tr.add_span("queue_wait", 100, 200, tenant="a")
    tr.add_span("orphan", 300, 400)
    runs = tr.find("run")
    assert [c.name for c in runs[0].children] == ["queue_wait"]
    assert runs[0].children[0].duration_s == pytest.approx(100e-9)
    assert [r.name for r in tr.roots()] == ["run", "orphan"]


def test_tracer_bounded_retention_and_clear():
    tr = Tracer(max_roots=4)
    for i in range(10):
        with tr.span("s", i=i):
            pass
    roots = tr.roots()
    assert len(roots) == 4
    assert [r.attrs["i"] for r in roots] == [6, 7, 8, 9]  # most recent kept
    tr.clear()
    assert tr.roots() == []


def test_tracer_save_load_chrome_roundtrip(tmp_path):
    tr = Tracer()
    with tr.span("root", table="t", obj=IntervalSet.of((0, 5))):
        with tr.span("child"):
            pass
    path = str(tmp_path / "trace.json")
    tr.save(path)
    loaded = load_trace(path)
    assert len(loaded) == 1
    assert loaded[0].name == "root"
    assert [c.name for c in loaded[0].children] == ["child"]
    assert loaded[0].t0_ns == tr.roots()[0].t0_ns

    payload = chrome_trace(loaded)
    events = payload["traceEvents"]
    assert [e["name"] for e in sorted(events, key=lambda e: e["ts"])] == [
        "root",
        "child",
    ]
    for e in events:
        assert e["ph"] == "X" and e["dur"] >= 0
        # every arg must be JSON-primitive (non-primitives render via repr)
        for v in e["args"].values():
            assert isinstance(v, (str, int, float, bool, type(None)))

    with pytest.raises(ValueError):
        bad = str(tmp_path / "bad.json")
        with open(bad, "w") as f:
            f.write("{}")
        load_trace(bad)


def test_tracer_summary_counts_every_depth():
    tr = Tracer()
    for _ in range(3):
        with tr.span("outer"):
            with tr.span("inner"):
                pass
    s = tr.summary()
    assert s["outer"]["count"] == 3 and s["inner"]["count"] == 3
    assert s["outer"]["total_s"] >= s["inner"]["total_s"] >= 0


# ------------------------------------------------------------------ metrics
def test_metrics_counters_gauges_labels():
    m = Metrics()
    m.counter("hits", tier="ram").inc(3)
    m.counter("hits", tier="spill").inc(2)
    assert m.value("hits", tier="ram") == 3
    assert m.value("hits", tier="disk") == 0  # never touched
    assert m.total("hits") == 5
    g = m.gauge("inflight")
    g.inc(4)
    g.dec()
    assert m.value("inflight") == 3
    # same (name, labels) returns the same cell
    assert m.counter("hits", tier="ram") is m.counter("hits", tier="ram")


def test_metrics_histogram_and_exposition():
    m = Metrics()
    h = m.histogram("wait_seconds", buckets=(0.1, 1.0), kind="scan")
    for v in (0.05, 0.5, 5.0):
        h.observe(v)
    assert h.count == 3 and h.sum == pytest.approx(5.55)
    m.counter("hits", tier="ram").inc(7)
    text = m.to_text()
    assert "# TYPE hits counter" in text
    assert 'hits{tier="ram"} 7' in text
    assert "# TYPE wait_seconds histogram" in text
    # cumulative buckets: le=0.1 -> 1, le=1.0 -> 2, +Inf -> 3
    assert 'wait_seconds_bucket{kind="scan",le="0.1"} 1' in text
    assert 'wait_seconds_bucket{kind="scan",le="1.0"} 2' in text
    assert 'wait_seconds_bucket{kind="scan",le="+Inf"} 3' in text
    assert 'wait_seconds_count{kind="scan"} 3' in text


def test_metrics_snapshot_delta():
    m = Metrics()
    m.counter("n").inc(2)
    before = m.snapshot()
    m.counter("n").inc(5)
    m.histogram("h").observe(0.2)
    after = m.snapshot()
    assert after["n"] - before.get("n", 0) == 5
    assert after["h_count"] == 1


def test_metric_attr_write_through():
    m = Metrics()

    class Store:
        lookups = MetricAttr("cache_lookups")

        def __init__(self, metrics, labels):
            self.metrics = metrics
            self.metrics_labels = labels

    a = Store(m, {"store": "scan"})
    b = Store(m, {"store": "model"})
    a.lookups += 1
    a.lookups += 1
    b.lookups = 7
    # legacy attribute reads and the registry see the same cells
    assert a.lookups == 2 and b.lookups == 7
    assert m.value("cache_lookups", store="scan") == 2
    assert m.value("cache_lookups", store="model") == 7
    assert m.total("cache_lookups") == 9


# ----------------------------------------- derived-not-duplicated consistency
def test_run_result_derives_from_registry(tmp_path):
    """The run-level registry rollup must agree exactly with the RunResult
    it was derived from — cold and warm."""
    ws = Workspace(str(tmp_path / "ws"), rows_per_fragment=256)
    write_events(ws.catalog, 0, 1200)
    for _ in range(2):  # cold, then warm
        before = ws.metrics.snapshot()
        res = ws.run(pipeline_project(hi=1199))
        after = ws.metrics.snapshot()
        delta = {k: after[k] - before.get(k, 0) for k in after}
        assert delta['runs_total{tenant=""}'] == 1
        assert delta['run_bytes_from_store{tenant=""}'] == res.bytes_from_store
        assert delta['run_rows_to_user_fns{tenant=""}'] == res.rows_to_user_fns
        assert (
            delta['run_bytes_from_cache{tenant=""}']
            == res.bytes_from_cache + res.bytes_from_model_cache
        )
        assert delta['run_bytes_mmap{tenant=""}'] == res.bytes_mmap


def test_scan_report_derives_from_registry(tmp_path):
    ws = Workspace(str(tmp_path / "ws"), rows_per_fragment=256)
    write_events(ws.catalog, 0, 1000)
    p = Project("scanonly")

    @model(project=p)
    def reader(
        data=Model(TABLE, columns=["v1"], filter="eventTime BETWEEN 0 AND 799")
    ):
        return {"v1": data.column("v1")}

    for expect_cached in (False, True):
        before = ws.metrics.snapshot()
        ws.run(p)
        after = ws.metrics.snapshot()
        delta = {k: after[k] - before.get(k, 0) for k in after}
        rep = ws.scans.reports[-1]
        assert rep.fully_cached is expect_cached
        key = f'bytes_from_store{{table="{TABLE}"}}'
        assert delta.get(key, 0) == rep.bytes_from_store
        assert delta[f'scan_requests{{table="{TABLE}"}}'] == 1
        assert delta.get('cache_hit_bytes{tier="ram"}', 0) == rep.bytes_from_cache
        assert delta.get('residual_rows{kind="scan"}', 0) == rep.residual_rows


def test_shared_store_stats_read_registry_cells(tmp_path):
    def _elem(lo, hi):
        return Table(
            {
                "k": np.arange(lo, hi, dtype=np.int64),
                "x": np.arange(lo, hi, dtype=np.float64),
            }
        )

    store = SharedStore()
    store.insert_window(
        "a", "t", "k", IntervalSet.of((0, 100)), _elem(0, 100), tenant="t1"
    )
    store.plan_window("a", IntervalSet.of((0, 50)), (), lambda w: w.measure())
    store.plan_window("b", IntervalSet.of((0, 50)), (), lambda w: w.measure())
    st = store.stats()
    assert st["lookups"] == 2 and st["full_hits"] == 1
    # the stats() dict and the legacy attributes both read the SAME registry
    # cells — not copies that could drift
    assert store.metrics.total("cache_lookups") == st["lookups"]
    assert store.metrics.total("cache_full_hits") == st["full_hits"]
    assert store.metrics.total("claim_timeouts") == st["claim_timeouts"] == 0


def test_service_report_metrics_text(tmp_path):
    with PipelineService(
        str(tmp_path / "svc"), workers=1, rows_per_fragment=256
    ) as svc:
        write_events(svc.catalog, 0, 600)
        svc.session("alice").run(pipeline_project(hi=599))
        svc.submit("bob", pipeline_project(hi=599)).wait(30.0)
        report = svc.report()
        text = report.metrics_text()
        assert "# TYPE runs_total counter" in text
        assert 'runs_total{tenant="alice"} 1' in text
        assert 'runs_total{tenant="bob"} 1' in text
        assert 'service_runs_total{state="DONE"} 1' in text  # submit() path only
        assert 'queue_wait_seconds_count{tenant="bob"} 1' in text
        # per-store labels separate the two shared stores in one scrape
        assert 'cache_lookups{store="model"}' in text
        assert 'cache_lookups{store="scan"}' in text
        assert (
            svc.metrics.value("cache_lookups", store="model")
            == report.model_store["lookups"]
        )


# --------------------------------------------- spans of the device-tier loop
RUN_COUNTERS = (
    "bytes_from_store",
    "bytes_from_cache",
    "rows_to_user_fns",
    "bytes_from_model_cache",
    "bytes_from_spill",
    "coalesced_waits",
    "bytes_h2d",
    "bytes_d2h",
    "device_hits",
    "device_evictions",
    "gather_fast",
    "gather_fallbacks",
    "device_union_bytes",
    "bytes_mmap",
)


def _edit_loop(root, tracer, device=True):
    """A tiny edit loop through ``Workspace.run``: cold, widen, rerun,
    shift off the tile grid, append, widen.  Yields each run's result with
    the spans it recorded (the tracer is cleared before every run)."""
    from repro.core.device import DeviceTier
    from test_device import SCHEMA as DEV_SCHEMA
    from test_device import events_table, jax_feature_project

    ws = Workspace(
        root,
        rows_per_fragment=128,
        device=DeviceTier(interpret=True) if device else None,
        tracer=tracer,
    )
    ws.catalog.create_table("ns", "raw", DEV_SCHEMA, "eventTime")
    ws.catalog.append("ns.raw", events_table(0, 1024))
    windows = [(0, 512), (0, 768), (0, 768), (37, 900), None, (37, 1280)]
    for w in windows:
        if w is None:
            ws.catalog.append("ns.raw", events_table(1024, 1280))
            continue
        tracer.clear()
        res = ws.run(jax_feature_project(f"eventTime >= {w[0]} AND eventTime < {w[1]}"))
        yield res, [sp for root in tracer.roots() for sp in root.walk()]


def _parents(tracer_roots):
    out = []
    for root in tracer_roots:
        stack = [(root, None)]
        while stack:
            sp, parent = stack.pop()
            out.append((sp, parent))
            stack.extend((c, sp) for c in sp.children)
    return out


@pytest.mark.parametrize("device", [True, False])
def test_merge_replicate_span_only_under_merge_with_a_device_tier(tmp_path, device):
    tr = Tracer()
    merges, replicated = 0, []
    for _res, _spans in _edit_loop(str(tmp_path / "ws"), tr, device=device):
        for sp, parent in _parents(tr.roots()):
            if sp.name == "cache.merge":
                merges += 1
                assert sp.attrs["bytes"] > 0 and sp.attrs["rows"] > 0
            elif sp.name == "cache.merge.replicate":
                assert parent is not None and parent.name == "cache.merge"
                replicated.append(sp.attrs["bytes"])
    assert merges > 0
    assert len(replicated) == (merges if device else 0)
    # scan-cache elements hold no pins here (their scans feed host code), so
    # only the model store's merges replicate any bytes
    if device:
        assert max(replicated) > 0


def test_union_span_counts_runs_only_when_traced(tmp_path):
    """``node.union`` carries the number of runs it concatenated.  A
    single-run host-only serve (the cold pass, the rerun of a merged
    window) opens no span, and a disabled tracer records none."""
    tr = Tracer()
    per_run = [
        [sp.attrs["runs"] for sp in spans if sp.name == "node.union"]
        for _res, spans in _edit_loop(str(tmp_path / "on"), tr, device=False)
    ]
    # cold, widen (hit + residual), rerun, shift, widen past the append
    assert per_run == [[], [2], [], [2], [2]]
    off = Tracer(enabled=False)
    assert len(list(_edit_loop(str(tmp_path / "off"), off, device=False))) == 5
    assert off.roots() == [] and off.find("node.union") == []


def test_transfer_spans_add_up_to_the_run_ledger(tmp_path):
    """Every byte a run counts crossing the host link lies under one span
    name: ``device.h2d`` up (pins, fresh residuals, jax inputs) and
    ``device.d2h`` down, each ``device.d2h`` after its ``device.wait``."""
    tr = Tracer()
    runs = 0
    for res, spans in _edit_loop(str(tmp_path / "ws"), tr):
        runs += 1
        names = [sp.name for sp in spans]
        h2d = sum(sp.attrs["bytes"] for sp in spans if sp.name == "device.h2d")
        d2h = sum(sp.attrs["bytes"] for sp in spans if sp.name == "device.d2h")
        assert h2d == res.bytes_h2d
        assert d2h == res.bytes_d2h > 0
        assert names.count("device.wait") == names.count("device.d2h") == names.count("node.call")
        assert {sp.attrs["runtime"] for sp in spans if sp.name == "node.call"} == {"jax"}
        assert names.count("node.explain") == 1  # the one rowwise node
        for sp, parent in _parents(tr.roots()):
            if sp.name == "device.wait":
                i = parent.children.index(sp)
                assert parent.children[i + 1].name == "device.d2h"
                assert parent.children[i - 1].name == "node.call"
    assert runs == 5
    sites = {sp.attrs.get("site") for sp in tr.find("device.h2d")}
    assert {"input", "fresh"} <= sites


def test_disabled_tracer_records_nothing_and_counts_the_same(tmp_path):
    on, off = Tracer(), Tracer(enabled=False)
    traced = list(_edit_loop(str(tmp_path / "on"), on))
    quiet = list(_edit_loop(str(tmp_path / "off"), off))
    assert off.roots() == [] and all(spans == [] for _res, spans in quiet)
    assert len(traced) == len(quiet)
    for (a, _), (b, _) in zip(traced, quiet):
        assert {k: getattr(a, k) for k in RUN_COUNTERS} == {
            k: getattr(b, k) for k in RUN_COUNTERS
        }
        assert a.node_stats == b.node_stats
        for name, table in a.outputs.items():
            for col in table.column_names:
                np.testing.assert_array_equal(
                    np.asarray(table.column(col)),
                    np.asarray(b.outputs[name].column(col)),
                )


# ---------------------------------------------------------------- explainer
def test_edit_matrix_diagnoses_all_causes(tmp_path):
    from repro.explain import edit_matrix_demo

    rows = edit_matrix_demo(str(tmp_path / "matrix"))
    assert len(rows) == 11
    mismatches = [
        (label, expected, got)
        for label, expected, got, _res in rows
        if expected != got
    ]
    assert not mismatches, mismatches
    # the decisions surface through RunResult.explain()
    _label, _exp, _got, last = rows[-1]
    assert "primary cause" in last.explain()


def test_explainer_serve_paths_never_read_catalog_head():
    """current_ids is resolved lazily: a fully-served window and a pure
    filter widen both classify without touching the catalog head pointer
    (that read is ~100us of fsync-adjacent IO on the warm serve path)."""
    ex = Explainer()
    expl = ex.begin_run()
    calls = []

    def ids():
        calls.append(1)
        return {}

    sig = (("code", "a"), ("inputs", ()))
    common = dict(
        kind="rowwise", sig_parts=sig, signature="s", snapshots={}, current_ids=ids
    )
    # cold: no cached elements to diagnose against
    cause = ex.classify_node(
        expl,
        node="n",
        window=IntervalSet.of((0, 10)),
        residual=IntervalSet.of((0, 10)),
        elements=[],
        **common,
    )
    assert cause == "cold"
    # serve: empty residual short-circuits before any invalidation analysis
    cause = ex.classify_node(
        expl,
        node="n",
        window=IntervalSet.of((0, 10)),
        residual=IntervalSet(),
        elements=[],
        **common,
    )
    assert cause == "cached"
    # widen: residual entirely outside the cached window
    cause = ex.classify_node(
        expl,
        node="n",
        window=IntervalSet.of((0, 20)),
        residual=IntervalSet.of((10, 20)),
        elements=[(IntervalSet.of((0, 10)), (), ("x",), "t")],
        **common,
    )
    assert cause == "window-widened"
    assert not calls, "catalog head was read on a serve/widen path"


def test_explainer_disabled_and_enabled_render(tmp_path):
    ws = Workspace(
        str(tmp_path / "off"), rows_per_fragment=256, explainer=Explainer(enabled=False)
    )
    write_events(ws.catalog, 0, 400)
    res = ws.run(pipeline_project(hi=399))
    assert res.explanation is None
    assert res.explain() == "explainer disabled"

    ws2 = Workspace(str(tmp_path / "on"), rows_per_fragment=256)
    write_events(ws2.catalog, 0, 400)
    res2 = ws2.run(pipeline_project(hi=399))
    text = res2.explain()
    assert "primary cause: cold" in text
    res3 = ws2.run(pipeline_project(hi=399))
    assert "primary cause: cached" in res3.explain()
    assert {d.action for d in res3.explanation.events} == {"serve"}


# ----------------------------------------------------- mmap byte attribution
def test_mmap_promotion_lands_on_every_ledger(tmp_path):
    """read_ipc(mmap=True) via local_path used to bypass the ObjectStore
    ledger entirely; the bytes_mmap counter closes that hole, and the spill
    tier, the object store, and the registry must all agree."""

    def _tbl(lo, hi):
        return Table(
            {
                "k": np.arange(lo, hi, dtype=np.int64),
                "x": np.arange(lo, hi, dtype=np.float64),
            }
        )

    store = SharedStore(max_bytes=3000, spill_root=str(tmp_path / "spill"))
    store.insert_window("a", "t", "k", IntervalSet.of((0, 100)), _tbl(0, 100))
    store.insert_window("b", "t", "k", IntervalSet.of((200, 300)), _tbl(200, 300))
    assert store.demotions == 1  # "a" went to the spill tier
    plan = store.plan_window(
        "a", IntervalSet.of((0, 100)), (), lambda w: w.measure()
    )
    assert plan.fully_cached and plan.promoted_spill_bytes > 0
    assert store.spill.bytes_mmap > 0
    assert store.spill.store.stats.bytes_mmap == store.spill.bytes_mmap
    assert store.metrics.total("spill_bytes_mmap") == store.spill.bytes_mmap
    # mmap bytes are zero-copy page faults, not simulated GET traffic
    assert store.spill.store.stats.bytes_read < store.spill.bytes_mmap


# ------------------------------------------------------- claim lease timeout
def test_dead_claim_takeover_at_the_store(tmp_path):
    store = SharedStore(claim_timeout=0.05)
    win = IntervalSet.of((0, 100))
    out = {}

    def grab():
        out["claim"], _ = store.claim_residual(
            "sig", win, snapshot_id="s", kind="rowwise"
        )

    t = threading.Thread(target=grab)
    t.start()
    t.join()
    assert out["claim"] is not None
    # this thread subscribes to the (now-orphaned) in-flight claim
    c, ev = store.claim_residual("sig", win, snapshot_id="s", kind="rowwise")
    assert c is None and ev is not None
    assert store.coalesced_waits == 1
    time.sleep(0.06)  # let the lease lapse
    # replan: the dead claim is retired, its subscribers woken, and the
    # caller takes the residual over
    c2, ev2 = store.claim_residual("sig", win, snapshot_id="s", kind="rowwise")
    assert c2 is not None and ev2 is None
    assert store.claim_timeouts == 1
    assert ev.is_set(), "subscribers of the dead claim must be woken"
    store.release_residual(c2)
    assert store.stats()["claim_timeouts"] == 1


def test_executor_abandons_dead_claim(tmp_path):
    """Regression for the claim lease wiring end to end: a subscriber whose
    claim owner died must wake within the configured timeout, replan, take
    the residual over, and produce correct output."""
    ms = SharedStore(claim_timeout=0.2)
    ws = Workspace(
        str(tmp_path / "ws"), rows_per_fragment=256, model_store=ms
    )
    write_events(ws.catalog, 0, 1000)
    project = pipeline_project(hi=1999)
    ws.run(project)  # warm: populates ms with the node signatures
    signatures = list(ms._elements)
    assert signatures
    write_events(ws.catalog, 1000, 1200)  # append -> next run has a residual
    token = f"{TABLE}:{ws.catalog.current_snapshot_id(TABLE)}"
    wide = IntervalSet([Interval(0, 1 << 60)])

    def register_dead_claims():
        # claim every signature and exit without releasing: the owner died
        for sig in signatures:
            claim, ev = ms.claim_residual(
                sig, wide, snapshot_id=token, kind="rowwise"
            )
            assert claim is not None and ev is None

    t = threading.Thread(target=register_dead_claims)
    t.start()
    t.join()
    t0 = time.monotonic()
    res = ws.run(project)
    elapsed = time.monotonic() - t0
    assert ms.claim_timeouts >= 1, "the dead claims were never retired"
    assert res.coalesced_waits >= 1, "the run never subscribed before takeover"
    assert elapsed < 5.0, "a dead claim must not block for the full lease x N"
    # reference replays the same append history (events are seeded per append)
    ref_ws = Workspace(str(tmp_path / "ref"), rows_per_fragment=256)
    write_events(ref_ws.catalog, 0, 1000)
    write_events(ref_ws.catalog, 1000, 1200)
    assert_outputs_bitwise_equal(res, ref_ws.run(project))


# ----------------------------------- threaded multi-tenant tracing stress (c)
def test_service_tracing_threaded_stress(tmp_path):
    """Concurrent tenants + appends on one traced service: every run gets a
    complete, well-nested span tree on its worker thread, no events are
    lost or cross-attached, and the registry's run totals reconcile exactly
    with the per-run reports."""
    tracer = Tracer()
    n_runs, n_tenants = 12, 3
    with PipelineService(
        str(tmp_path / "svc"), workers=4, rows_per_fragment=256, tracer=tracer
    ) as svc:
        write_events(svc.catalog, 0, 2000)
        handles = []
        for i in range(n_runs):
            handles.append(
                svc.submit(f"t{i % n_tenants}", pipeline_project(hi=10**9))
            )
            if i % 4 == 3:  # appends race the in-flight runs
                lo = 2000 + 200 * (i // 4)
                write_events(svc.catalog, lo, lo + 200)
        for h in handles:
            h.wait(60.0)
        assert all(h.state == DONE for h in handles), [h.error for h in handles]
        results = [h.result for h in handles]

        service_runs = tracer.find("service.run")
        assert len(service_runs) == n_runs
        assert {sp.attrs["run_id"] for sp in service_runs} == {
            h.run_id for h in handles
        }
        # span-tree integrity: every descendant closed within its parent's
        # interval, on the parent's thread; no span attached twice
        seen = set()
        for root in tracer.roots():
            for sp in root.walk():
                assert id(sp) not in seen, "span attached to two parents"
                seen.add(id(sp))
                for c in sp.children:
                    assert sp.t0_ns <= c.t0_ns and c.t1_ns <= sp.t1_ns
                    assert c.tid == sp.tid
        # each service.run wraps exactly one executor run span
        for sp in service_runs:
            runs_below = [s for s in sp.walk() if s.name == "run"]
            assert len(runs_below) == 1
            assert runs_below[0].attrs["tenant"] == sp.attrs["tenant"]
        # queue waits land as their own roots (they are not run time)
        assert len(tracer.find("service.queue_wait")) == n_runs

        # exact reconciliation: per-run reports vs the registry rollup
        m = svc.metrics
        assert m.total("runs_total") == n_runs
        assert m.total("run_bytes_from_store") == sum(
            r.bytes_from_store for r in results
        )
        assert m.total("run_rows_to_user_fns") == sum(
            r.rows_to_user_fns for r in results
        )
        assert m.total("run_bytes_from_cache") == sum(
            r.bytes_from_cache + r.bytes_from_model_cache for r in results
        )
        assert m.value("service_runs_total", state=DONE) == n_runs
        qcount = sum(
            h.count
            for (name, _), h in m._histograms.items()
            if name == "queue_wait_seconds"
        )
        assert qcount == n_runs
        # every run produced a complete decision trail
        for r in results:
            assert r.explanation is not None and r.explanation.events


# --------------------------------------------------------- bench9 acceptance
def test_bench9_acceptance():
    from benchmarks import bench9_obs as b9

    result = b9.run(rows=2000, reps=1)
    e = result["explainer"]
    assert e["correct"] == e["total"] == 11
    o = result["overhead"]
    assert o["baseline_s"] > 0 and o["trace_s"] > 0 and o["full_s"] > 0
    # the wall-time gate itself runs in CI at full scale; a unit test only
    # sanity-checks the measurement plumbing
    assert "overhead_pct" in o and "explain_overhead_pct" in o
    assert result["metrics"]["runs_total"] > 0
    assert sum(v["count"] for v in result["trace"].values()) > 0
    table = b9.format_table(result)
    assert "explainer: 11/11" in table
