"""repro.service (ISSUE 4 tentpole): the multi-tenant pipeline service over
one shared, concurrency-safe differential cache.

Covers the SharedStore disciplines (global LRU across tenants, per-tenant
quotas, signature-liveness eviction, reader pins), tenant sessions (snapshot
pinning, commit-retry), the scheduler (states, admission bound, fairness),
cross-tenant cache reuse with bitwise-equal outputs, racing catalog commits
(exactly one CommitConflict; retries converge), the incremental
materializer (ROADMAP (d)), and a threaded stress test (concurrent runs +
appends + evictions on one SharedStore, no torn reads).
"""

import threading
import time

import numpy as np
import pytest

from repro.core.columnar import Table
from repro.core.intervals import IntervalSet
from repro.lake.catalog import Catalog, CommitConflict
from repro.lake.s3sim import ObjectStore
from repro.pipeline import Model, Project, Workspace, model, runtime
from repro.service import (
    DONE,
    FAILED,
    PipelineService,
    QueueFull,
    SharedStore,
    TenantSession,
)

SCHEMA = {"eventTime": "<i8", "v1": "<f8", "v2": "<f8", "flag": "<i8"}
TABLE = "ns.events"


def events_table(lo, hi, seed=0):
    n = hi - lo
    rng = np.random.default_rng(seed + lo)
    return Table(
        {
            "eventTime": np.arange(lo, hi, dtype=np.int64),
            "v1": rng.standard_normal(n),
            "v2": rng.standard_normal(n),
            "flag": rng.integers(0, 4, n).astype(np.int64),
        }
    )


def write_events(catalog, lo, hi, seed=0):
    try:
        catalog.table(TABLE)
    except KeyError:
        catalog.create_table("ns", "events", SCHEMA, "eventTime")
    catalog.append(TABLE, events_table(lo, hi, seed))


def pipeline_project(hi, gain=1.0, materialize=False):
    """cleaned (rowwise drop) -> scored (rowwise map): identical code across
    calls, so every tenant constructing it gets the identical signature."""
    p = Project("svc")

    @model(project=p, incremental="rowwise")
    @runtime("numpy")
    def cleaned(
        data=Model(TABLE, columns=["v1", "v2", "flag"],
                   filter=f"eventTime BETWEEN 0 AND {hi}")
    ):
        return data.filter(data.column("flag") > 0)

    @model(project=p, incremental="rowwise", materialize=materialize)
    @runtime("numpy")
    def scored(data=Model("cleaned")):
        out = {n: data.column(n) for n in data.column_names}
        out["score"] = gain * (
            np.asarray(data.column("v1"), np.float64)
            + np.asarray(data.column("v2"), np.float64)
        )
        return out

    return p


def assert_outputs_bitwise_equal(res_a, res_b):
    assert set(res_a.outputs) == set(res_b.outputs)
    for name in res_a.outputs:
        a, b = res_a.outputs[name], res_b.outputs[name]
        assert a.column_names == b.column_names, name
        for col in a.column_names:
            np.testing.assert_array_equal(
                a.column(col), b.column(col), err_msg=f"{name}:{col}"
            )


def cold_reference(tmp_path, name, project, rows=2000):
    ws = Workspace(str(tmp_path / name), rows_per_fragment=256)
    write_events(ws.catalog, 0, rows)
    return ws.run(project)


# ------------------------------------------------------------ SharedStore unit
def _elem(lo, hi):
    return Table(
        {"k": np.arange(lo, hi, dtype=np.int64), "x": np.arange(lo, hi, dtype=np.float64)}
    )


def test_shared_store_global_lru_spans_tenants():
    elem_bytes = _elem(0, 100).nbytes
    store = SharedStore(max_bytes=2 * elem_bytes)
    store.insert_window("a", "t", "k", IntervalSet.of((0, 100)), _elem(0, 100), tenant="t1")
    store.insert_window("b", "t", "k", IntervalSet.of((0, 100)), _elem(0, 100), tenant="t2")
    store.insert_window("c", "t", "k", IntervalSet.of((0, 100)), _elem(0, 100), tenant="t1")
    assert store.nbytes <= 2 * elem_bytes
    assert store.elements("a") == []  # LRU victim regardless of owner
    assert store.elements("b") and store.elements("c")


def test_shared_store_tenant_quota_evicts_own_elements_only():
    elem_bytes = _elem(0, 100).nbytes
    store = SharedStore(tenant_quota_bytes=2 * elem_bytes)
    store.insert_window("x", "t", "k", IntervalSet.of((0, 100)), _elem(0, 100), tenant="t2")
    for sig in ("a", "b", "c"):
        store.insert_window(sig, "t", "k", IntervalSet.of((0, 100)), _elem(0, 100), tenant="t1")
    assert store.tenant_bytes("t1") <= 2 * elem_bytes
    assert store.quota_evictions == 1
    assert store.elements("a") == []  # t1's eldest went
    assert store.elements("x"), "another tenant's bytes must survive t1's quota"


def test_shared_store_liveness_reclaims_stale_signatures():
    store = SharedStore(liveness_runs=3)
    store.insert_window("old", "t", "k", IntervalSet.of((0, 50)), _elem(0, 50))
    cost = lambda w: w.measure()
    for _ in range(5):
        store.begin_run()
        store.plan_window("hot", IntervalSet.of((0, 50)), (), cost)
    assert store.elements("old") == []
    assert store.liveness_evictions == 1
    # the planned-every-run signature group is never reclaimed
    store.insert_window("hot", "t", "k", IntervalSet.of((0, 50)), _elem(0, 50))
    for _ in range(2):
        store.begin_run()
        store.plan_window("hot", IntervalSet.of((0, 50)), (), cost)
    assert store.elements("hot")


def test_shared_store_reader_pin_blocks_every_eviction_path():
    elem_bytes = _elem(0, 100).nbytes
    store = SharedStore(max_bytes=1 * elem_bytes, liveness_runs=1)
    store.insert_window("pinned", "t", "k", IntervalSet.of((0, 100)), _elem(0, 100))
    with store.reading("pinned"):
        # LRU: inserting over budget must not evict the pinned group
        store.insert_window("other", "t", "k", IntervalSet.of((0, 100)), _elem(0, 100))
        assert store.elements("pinned")
        # liveness: many runs without a plan touching "pinned"
        for _ in range(5):
            store.begin_run()
        assert store.elements("pinned")
    # pin released: the next insert's LRU pass may now reclaim it
    store.insert_window("third", "t", "k", IntervalSet.of((0, 100)), _elem(0, 100))
    assert store.nbytes <= elem_bytes


def test_scan_cache_policies_are_live_in_the_service(tmp_path):
    """The shared SCAN cache gets the same service policies as the model
    store: its liveness clock ticks per run and its elements carry tenant
    attribution (cross-tenant reuse counted)."""
    with PipelineService(
        str(tmp_path / "svc"), workers=1, rows_per_fragment=256, liveness_runs=2
    ) as svc:
        write_events(svc.catalog, 0, 500)
        svc.session("alice").run(pipeline_project(hi=499))
        assert svc.scan_cache.run_seq > 0
        assert svc.scan_cache.elements(TABLE)
        elems = svc.scan_cache.elements(TABLE)
        assert all(e.owner == "alice" for e in elems)
        # a plain (non-incremental) project always scans, so bob's nested
        # read hits alice's scan element directly
        scan_only = Project("scanonly")

        @model(project=scan_only)
        def reader(
            data=Model(TABLE, columns=["v1"], filter="eventTime BETWEEN 0 AND 299")
        ):
            return {"v1": data.column("v1")}

        rb = svc.session("bob").run(scan_only)
        assert rb.bytes_from_store == 0 and rb.bytes_from_cache > 0
        assert svc.scan_cache.cross_tenant_hits > 0
        # a table no run scans for liveness_runs runs is reclaimed
        other = Project("other")

        @model(project=other)
        def nothing(data=Model("ns.unused", columns=["v1"])):
            return data

        svc.catalog.create_table("ns", "unused", SCHEMA, "eventTime")
        svc.session("alice").refresh_pins(["ns.unused"])
        for _ in range(4):
            svc.session("alice").run(other)
        assert svc.scan_cache.elements(TABLE) == []
        assert svc.scan_cache.liveness_evictions > 0


def test_shared_store_counts_cross_tenant_reuse():
    store = SharedStore()
    store.insert_window("s", "t", "k", IntervalSet.of((0, 100)), _elem(0, 100), tenant="alice")
    cost = lambda w: w.measure()
    plan = store.plan_window("s", IntervalSet.of((0, 80)), (), cost, tenant="bob")
    assert plan.fully_cached
    assert store.cross_tenant_hits == 1
    assert store.cross_tenant_rows == 80
    # a tenant re-reading its own bytes is not cross-tenant reuse
    store.plan_window("s", IntervalSet.of((0, 80)), (), cost, tenant="alice")
    assert store.cross_tenant_hits == 1


# --------------------------------------------------- cross-tenant cache reuse
def test_second_tenant_pays_only_residual(tmp_path):
    with PipelineService(str(tmp_path / "svc"), workers=2, rows_per_fragment=256) as svc:
        write_events(svc.catalog, 0, 2000)
        ra = svc.session("alice").run(pipeline_project(hi=1599))
        rb = svc.session("bob").run(pipeline_project(hi=1999))
        # bob's plan subtracts alice's windows: only (1599, 1999] recomputes
        assert rb.node_stats["cleaned"]["fresh_rows"] == 400
        assert rb.bytes_from_model_cache > 0
        assert svc.model_store.cross_tenant_hits > 0
        assert 0 < rb.bytes_from_store < ra.bytes_from_store / 2
        cold = cold_reference(tmp_path, "bob-cold", pipeline_project(hi=1999))
        assert_outputs_bitwise_equal(rb, cold)


def test_nested_window_tenant_is_fully_served(tmp_path):
    with PipelineService(str(tmp_path / "svc"), workers=2, rows_per_fragment=256) as svc:
        write_events(svc.catalog, 0, 2000)
        svc.session("alice").run(pipeline_project(hi=1999))
        rb = svc.session("bob").run(pipeline_project(hi=999))
        assert rb.rows_to_user_fns == 0
        assert rb.bytes_from_store == 0
        assert_outputs_bitwise_equal(
            rb, cold_reference(tmp_path, "nested-cold", pipeline_project(hi=999))
        )


# ------------------------------------------------------------ tenant sessions
def test_session_pins_freeze_the_lake_view(tmp_path):
    with PipelineService(str(tmp_path / "svc"), workers=1, rows_per_fragment=256) as svc:
        write_events(svc.catalog, 0, 1000)
        alice = svc.session("alice")  # pins at 1000 rows
        svc.catalog.append(TABLE, events_table(1000, 1500, seed=5))
        r1 = alice.run(pipeline_project(hi=1999))
        # bob's session pins AFTER the append: sees 1500 rows
        bob = svc.session("bob")
        r2 = bob.run(pipeline_project(hi=1999))
        assert r1.outputs["scored"].num_rows < r2.outputs["scored"].num_rows
        # refreshing alice's pins catches her up, reusing bob's bytes
        alice.refresh_pins()
        r3 = alice.run(pipeline_project(hi=1999))
        assert r3.outputs["scored"].num_rows == r2.outputs["scored"].num_rows
        assert r3.rows_to_user_fns == 0  # bob already paid for the delta


def test_explicit_model_snapshot_beats_session_pin(tmp_path):
    with PipelineService(str(tmp_path / "svc"), workers=1, rows_per_fragment=256) as svc:
        write_events(svc.catalog, 0, 500)
        old = svc.catalog.current_snapshot(TABLE).snapshot_id
        svc.catalog.append(TABLE, events_table(500, 800, seed=2))
        session = svc.session("alice")  # pins at 800 rows
        p = Project("tt")

        @model(project=p, incremental="rowwise")
        def pinned(
            data=Model(TABLE, columns=["v1"], filter="eventTime BETWEEN 0 AND 999",
                       snapshot_id=old)
        ):
            return {n: data.column(n) for n in data.column_names}

        res = session.run(p)
        assert res.outputs["pinned"].num_rows == 500  # user pin wins


# ----------------------------------------------------------------- scheduler
def test_scheduler_states_and_failure_isolation(tmp_path):
    with PipelineService(str(tmp_path / "svc"), workers=2, rows_per_fragment=256) as svc:
        write_events(svc.catalog, 0, 500)
        ok = svc.submit("alice", pipeline_project(hi=499))

        p_bad = Project("bad")

        @model(project=p_bad)
        def broken(data=Model(TABLE, columns=["v1"], filter="eventTime < 100")):
            raise RuntimeError("user code exploded")

        bad = svc.submit("bob", p_bad)
        ok.wait(30)
        bad.wait(30)
        assert ok.state == DONE and ok.result is not None
        assert bad.state == FAILED and isinstance(bad.error, RuntimeError)
        # the failed run neither killed a worker nor poisoned the service
        again = svc.submit("bob", pipeline_project(hi=499)).wait(30)
        assert again.state == DONE


def test_scheduler_admission_bound(tmp_path):
    with PipelineService(
        str(tmp_path / "svc"), workers=1, rows_per_fragment=256, max_queued=2
    ) as svc:
        write_events(svc.catalog, 0, 500)

        release = threading.Event()
        p_slow = Project("slow")

        @model(project=p_slow)
        def blocker(data=Model(TABLE, columns=["v1"], filter="eventTime < 10")):
            release.wait(30)
            return data

        h = svc.submit("alice", p_slow)
        while h.state != "RUNNING":
            time.sleep(0.005)
        svc.submit("bob", pipeline_project(hi=99))
        svc.submit("carol", pipeline_project(hi=99))
        with pytest.raises(QueueFull):
            svc.submit("dave", pipeline_project(hi=99))
        release.set()


def test_scheduler_fairness_many_vs_one(tmp_path):
    """A tenant submitting a burst must not starve another tenant's single
    run: with round-robin pick, bob's run is dispatched no later than
    alice's second queued run."""
    with PipelineService(str(tmp_path / "svc"), workers=1, rows_per_fragment=256) as svc:
        write_events(svc.catalog, 0, 500)
        order = []
        lock = threading.Lock()

        def tracked(tag, hi):
            p = Project(f"t{tag}{hi}")

            @model(project=p)
            def track(data=Model(TABLE, columns=["v1"], filter=f"eventTime < {hi}")):
                with lock:
                    order.append(tag)
                return data

            return p

        gate = threading.Event()
        p_gate = Project("gate")

        @model(project=p_gate)
        def hold(data=Model(TABLE, columns=["v1"], filter="eventTime < 5")):
            gate.wait(30)
            return data

        svc.submit("alice", p_gate)
        for i in range(4):
            svc.submit("alice", tracked("a", 20 + i))
        svc.submit("bob", tracked("b", 50))
        gate.set()
        svc.drain(60)
        assert order.index("b") <= 1, order


# ------------------------------------------- racing commits (satellite task)
def test_two_racing_writers_surface_exactly_one_conflict(tmp_path):
    store = ObjectStore(str(tmp_path / "lake"))
    catalog = Catalog(store, rows_per_fragment=256)
    write_events(catalog, 0, 100)
    parent = catalog.current_snapshot(TABLE).snapshot_id

    barrier = threading.Barrier(2)
    outcomes = []
    olock = threading.Lock()

    def writer(lo):
        barrier.wait()
        try:
            catalog.append(TABLE, events_table(lo, lo + 50), expected_parent=parent)
            result = "ok"
        except CommitConflict:
            result = "conflict"
        with olock:
            outcomes.append(result)

    threads = [threading.Thread(target=writer, args=(lo,)) for lo in (100, 200)]
    for t in threads:
        t.start()
    for t in threads:
        t.join()
    assert sorted(outcomes) == ["conflict", "ok"]


def test_session_retry_converges_with_both_snapshots_in_chain(tmp_path):
    store = ObjectStore(str(tmp_path / "lake"))
    catalog = Catalog(store, rows_per_fragment=256)
    write_events(catalog, 0, 100)
    base = catalog.current_snapshot(TABLE)

    def make_session(name):
        ws = Workspace(store.root, store=store, catalog=catalog, tenant=name)
        return TenantSession(name, ws)

    s1, s2 = make_session("w1"), make_session("w2")
    barrier = threading.Barrier(2)
    errors = []

    def writer(session, lo):
        barrier.wait()
        try:
            session.append(TABLE, events_table(lo, lo + 50))
        except BaseException as e:  # pragma: no cover - diagnostic
            errors.append(e)

    threads = [
        threading.Thread(target=writer, args=(s, lo))
        for s, lo in ((s1, 100), (s2, 200))
    ]
    for t in threads:
        t.start()
    for t in threads:
        t.join()
    assert not errors
    history = catalog.history(TABLE)
    assert history[0].snapshot_id != base.snapshot_id or history[-1].sequence == base.sequence + 2
    assert history[-1].sequence == base.sequence + 2  # both commits landed
    rows = sum(f.row_count for f in history[-1].fragments)
    assert rows == 200  # 100 base + both writers' 50


# ----------------------------------- incremental materialization (ROADMAP d)
def read_published(catalog, store, name="scored"):
    """The models.<name> table's full current content, sorted by key."""
    from repro.core.planner import ScanExecutor
    from repro.core.baselines import NoCache

    ex = ScanExecutor(store, catalog, cache=NoCache())
    meta = catalog.table(f"models.{name}")
    cols = sorted(meta.schema)
    return ex.scan(f"models.{name}", cols, sorted_output=True).combine()


def assert_published_mirrors(ws, res, name="scored"):
    pub = read_published(ws.catalog, ws.store, name)
    out = res.outputs[name]
    assert pub.num_rows == out.num_rows
    for col in out.column_names:
        np.testing.assert_array_equal(
            np.asarray(pub.column(col)), np.asarray(out.column(col)), err_msg=col
        )


def test_materialize_rerun_does_not_duplicate(tmp_path):
    ws = Workspace(str(tmp_path / "lake"), rows_per_fragment=256)
    write_events(ws.catalog, 0, 1000)
    r1 = ws.run(pipeline_project(hi=799, materialize=True))
    seq1 = ws.catalog.current_snapshot("models.scored").sequence
    r2 = ws.run(pipeline_project(hi=799, materialize=True))
    assert_published_mirrors(ws, r2)
    # nothing recomputed -> nothing committed
    assert ws.catalog.current_snapshot("models.scored").sequence == seq1


def test_materialize_widen_appends_residual_only(tmp_path):
    ws = Workspace(str(tmp_path / "lake"), rows_per_fragment=256)
    write_events(ws.catalog, 0, 1000)
    ws.run(pipeline_project(hi=499, materialize=True))
    published_before = read_published(ws.catalog, ws.store).num_rows
    res = ws.run(pipeline_project(hi=999, materialize=True))
    snap = ws.catalog.current_snapshot("models.scored")
    assert snap.operation == "append"
    appended = sum(f.row_count for f in snap.fragments) - published_before
    assert appended == res.outputs["scored"].num_rows - published_before
    assert_published_mirrors(ws, res)


def test_materialize_upstream_overwrite_rewrites_window(tmp_path):
    ws = Workspace(str(tmp_path / "lake"), rows_per_fragment=256)
    write_events(ws.catalog, 0, 1000)
    ws.run(pipeline_project(hi=999, materialize=True))
    seq_before = ws.catalog.current_snapshot("models.scored").sequence
    ws.catalog.overwrite_range(TABLE, 300, 400, events_table(300, 400, seed=42))
    res = ws.run(pipeline_project(hi=999, materialize=True))
    assert_published_mirrors(ws, res)
    # the whole diff lands atomically: readers never see a torn mid-publish
    # state between separate delete/overwrite/append commits
    assert ws.catalog.current_snapshot("models.scored").sequence == seq_before + 1


def test_materialize_narrow_deletes_stale_rows(tmp_path):
    ws = Workspace(str(tmp_path / "lake"), rows_per_fragment=256)
    write_events(ws.catalog, 0, 1000)
    ws.run(pipeline_project(hi=999, materialize=True))
    res = ws.run(pipeline_project(hi=399, materialize=True))
    assert_published_mirrors(ws, res)
    # widening back must restore the full mirror from cache-served rows
    res2 = ws.run(pipeline_project(hi=999, materialize=True))
    assert_published_mirrors(ws, res2)


def test_materialize_code_edit_republishes_in_full(tmp_path):
    ws = Workspace(str(tmp_path / "lake"), rows_per_fragment=256)
    write_events(ws.catalog, 0, 1000)
    ws.run(pipeline_project(hi=999, materialize=True))
    res = ws.run(pipeline_project(hi=999, gain=2.0, materialize=True))
    assert ws.catalog.current_snapshot("models.scored").operation == "overwrite"
    assert_published_mirrors(ws, res)


def test_materialize_republishes_windows_freshened_by_other_runs(tmp_path):
    """Republication is keyed on the PUBLISHED leaf snapshot, not on what
    this run recomputed: when another tenant's non-materializing run already
    freshened the overwritten window into the shared cache, the materializing
    run serves it as a cache hit — and must still republish it."""
    with PipelineService(str(tmp_path / "svc"), workers=1, rows_per_fragment=256) as svc:
        write_events(svc.catalog, 0, 1000)
        publisher = svc.session("publisher")
        res = publisher.run(pipeline_project(hi=999, materialize=True))
        assert_published_mirrors(publisher.workspace, res)
        # upstream overwrite, then a DIFFERENT tenant (no materialize) pays
        # for the recompute, leaving the shared cache fresh
        svc.catalog.overwrite_range(TABLE, 300, 400, events_table(300, 400, seed=9))
        other = svc.session("other")
        other.run(pipeline_project(hi=999, materialize=False))
        # the publisher's run is now a pure cache hit...
        publisher.refresh_pins([TABLE])
        res2 = publisher.run(pipeline_project(hi=999, materialize=True))
        assert res2.rows_to_user_fns == 0
        # ...and the published table still picks up the overwritten window
        assert_published_mirrors(publisher.workspace, res2)


def test_code_fingerprint_sees_kwonly_defaults(tmp_path):
    """A keyword-only default lives in __kwdefaults__; editing it must
    invalidate like any other constant edit."""
    from repro.pipeline.dsl import code_fingerprint

    def make(gain):
        def fn(data=Model(TABLE, columns=["v1"]), *, g=gain):
            return {"s": g * data.column("v1")}

        return fn

    assert code_fingerprint(make(2.0)) != code_fingerprint(make(3.0))
    assert code_fingerprint(make(2.0)) == code_fingerprint(make(2.0))


def test_materialize_upstream_append_into_covered_range(tmp_path):
    ws = Workspace(str(tmp_path / "lake"), rows_per_fragment=256)
    write_events(ws.catalog, 0, 1000)
    ws.run(pipeline_project(hi=1999, materialize=True))
    write_events(ws.catalog, 1000, 1200, seed=4)
    res = ws.run(pipeline_project(hi=1999, materialize=True))
    assert_published_mirrors(ws, res)


def test_concurrent_materialize_of_new_model_converges(tmp_path):
    """Two tenants materializing the same brand-new model race on
    create_table AND on content commits; both runs must converge (the create
    loser adopts the winner's table, commit losers retry via the session)."""
    with PipelineService(str(tmp_path / "svc"), workers=2, rows_per_fragment=256) as svc:
        write_events(svc.catalog, 0, 1000)
        h1 = svc.submit("alice", pipeline_project(hi=999, materialize=True))
        h2 = svc.submit("bob", pipeline_project(hi=999, materialize=True))
        h1.wait(60)
        h2.wait(60)
        assert h1.state == DONE, h1.error
        assert h2.state == DONE, h2.error
        assert_published_mirrors(svc.session("alice").workspace, h1.result)


def test_session_reads_its_own_publishes(tmp_path):
    """A run that materializes a model advances the session's pin for the
    published table — the tenant's next scan sees the fresh snapshot even
    though the table was pinned before the publish."""
    with PipelineService(str(tmp_path / "svc"), workers=1, rows_per_fragment=256) as svc:
        write_events(svc.catalog, 0, 1000)
        svc.session("bootstrap").run(pipeline_project(hi=299, materialize=True))
        alice = svc.session("alice")  # pins models.scored at the 300-row publish
        res = alice.run(pipeline_project(hi=999, materialize=True))

        consumer = Project("consumer")

        @model(project=consumer)
        def reader(d=Model("models.scored", columns=["score"])):
            return {"score": d.column("score")}

        seen = alice.run(consumer).outputs["reader"].num_rows
        assert seen == res.outputs["scored"].num_rows


# ------------------------------------------------------- threaded stress test
def test_threaded_stress_no_torn_reads(tmp_path):
    """Concurrent pipeline runs + catalog appends + forced evictions on ONE
    SharedStore: every run's outputs must be bitwise-equal to a cold run of
    the same project against the session's pinned snapshot."""
    rows = 1200
    with PipelineService(
        str(tmp_path / "svc"),
        workers=4,
        rows_per_fragment=128,
        model_cache_bytes=50_000,  # well under the working set: eviction churn
        liveness_runs=4,
    ) as svc:
        write_events(svc.catalog, 0, rows)
        # pin reader sessions BEFORE the writer starts: their reference
        # output is deterministic whatever the writer commits
        readers = [svc.session(t) for t in ("alice", "bob")]

        stop = threading.Event()

        def appender():
            session = svc.session("writer")
            lo = rows
            while not stop.is_set():
                session.append(TABLE, events_table(lo, lo + 64, seed=7))
                lo += 64
                time.sleep(0.002)

        wt = threading.Thread(target=appender)
        wt.start()
        try:
            his = [399, 799, 1199, 599, 999, 1199, 399, 1099]
            handles = [
                svc.submit(readers[i % 2].tenant_id, pipeline_project(hi=hi))
                for i, hi in enumerate(his)
            ]
            svc.drain(120)
        finally:
            stop.set()
            wt.join()

        refs = {}
        for hi, h in zip(his, handles):
            assert h.state == DONE, h.error
            if hi not in refs:
                refs[hi] = cold_reference(
                    tmp_path, f"stress-cold-{hi}-{len(refs)}",
                    pipeline_project(hi=hi), rows=rows,
                )
            assert_outputs_bitwise_equal(h.result, refs[hi])
        # one more run alone, under new code: its insert overflows the
        # budget while nothing else is in flight, so at least one unpinned
        # element must go, whatever order the concurrent runs took above
        alone = svc.run(readers[0].tenant_id, pipeline_project(hi=1199, gain=2.0))
        assert_outputs_bitwise_equal(
            alone,
            cold_reference(tmp_path, "stress-cold-alone", pipeline_project(hi=1199, gain=2.0),
                           rows=rows),
        )
        assert svc.model_store.evictions > 0, "stress must actually evict"
        rep = svc.report()
        assert rep.model_store["cross_tenant_hits"] > 0


# -------------------------------------------------- acceptance: the >=3x gate
def test_service_bench_meets_3x_acceptance():
    """The BENCH_4 scenario (same code CI smokes): every warm tenant —
    including those with windows widened past the shared coverage — moves
    >=3x fewer bytes from the store than its own cold run, with bitwise-equal
    outputs (asserted inside bench4.run)."""
    from benchmarks import bench4_service as b4

    result = b4.run(rows=4000, tenants=3)
    assert result["min_bytes_ratio"] >= 3.0, result
    assert result["min_rows_ratio"] >= 3.0, result
    assert result["model_store"]["cross_tenant_hits"] > 0


# ------------------------------------------- the service on the device tier
LINES = "tpch.lineitem"
LINE_SCHEMA = {
    "l_shipdate": "<i8",
    "l_quantity": "<f8",
    "l_extendedprice": "<f8",
    "l_discount": "<f8",
    "l_tax": "<f8",
    "l_returnflag": "|S1",
    "l_linestatus": "|S1",
}


def lineitem_table(days=60, per_day=37, seed=3):
    """A small lineitem-shaped table: whole days of lines in key order."""
    rng = np.random.default_rng(seed)
    n = days * per_day
    ship = np.repeat(np.arange(1, days + 1, dtype=np.int64), per_day)
    qty = rng.integers(1, 51, n).astype(np.float64)
    return Table(
        {
            "l_shipdate": ship,
            "l_quantity": qty,
            "l_extendedprice": qty * rng.integers(90000, 200000, n) / 100.0,
            "l_discount": rng.integers(0, 11, n) / 100.0,
            "l_tax": rng.integers(0, 9, n) / 100.0,
            "l_returnflag": np.where(ship < 30, np.where(rng.random(n) < 0.5, b"R", b"A"), b"N").astype("S1"),
            "l_linestatus": np.where(ship < 35, b"F", b"O").astype("S1"),
        }
    )


def write_lines(catalog):
    catalog.create_table("tpch", "lineitem", LINE_SCHEMA, "l_shipdate")
    catalog.append(LINES, lineitem_table())


def q1_like(hi):
    """Q1's shape: a jax rowwise price stage, a numpy grouping stage."""
    where = f"l_shipdate >= 0 AND l_shipdate < {hi}"
    p = Project("q1")

    @model(project=p, incremental="rowwise")
    @runtime("jax")
    def prices(data=Model(LINES, columns=["l_extendedprice", "l_discount", "l_tax"], filter=where)):
        import jax.numpy as jnp

        one = jnp.float32(1)
        disc_price = data["l_extendedprice"] * (one - data["l_discount"])
        return {"disc_price": disc_price, "charge": disc_price * (one + data["l_tax"])}

    @model(project=p)
    @runtime("numpy")
    def report(lines=Model(LINES, columns=["l_returnflag", "l_linestatus", "l_quantity"],
                           filter=where),
               priced=Model("prices")):
        code = (lines.column("l_returnflag").view(np.uint8).astype(np.int64) * 256
                + lines.column("l_linestatus").view(np.uint8))
        groups, index = np.unique(code, return_inverse=True)
        return {
            "group": groups,
            "sum_qty": np.bincount(index, weights=lines.column("l_quantity")),
            "sum_charge": np.bincount(index, weights=priced.column("charge")),
        }

    return p


def q6_like(lo, hi, cents):
    """Q6's shape: a jax rowwise predicate-and-revenue stage, a numpy sum."""
    where = f"l_shipdate >= {lo} AND l_shipdate < {hi}"
    d_lo, d_hi = (cents - 1) / 100, (cents + 1) / 100
    p = Project("q6")

    @model(project=p, incremental="rowwise")
    @runtime("jax")
    def rev(data=Model(LINES, columns=["l_extendedprice", "l_discount", "l_quantity"], filter=where)):
        import jax.numpy as jnp

        disc = data["l_discount"]
        keep = (disc >= jnp.float32(d_lo)) & (disc <= jnp.float32(d_hi)) & (
            data["l_quantity"] < jnp.float32(24)
        )
        return {"keep": keep, "revenue": data["l_extendedprice"] * disc}

    @model(project=p)
    @runtime("numpy")
    def total(r=Model("rev")):
        return {"revenue": np.array([np.sum(r.column("revenue")[r.column("keep")], dtype=np.float64)])}

    return p


def jax_to_jax(hi):
    """A jax stage read by a jax stage: the node's output is kept on device."""
    where = f"l_shipdate >= 0 AND l_shipdate < {hi}"
    p = Project("devfeat")

    @model(project=p, incremental="rowwise")
    @runtime("jax")
    def feats(data=Model(LINES, columns=["l_extendedprice", "l_discount"], filter=where)):
        return {"net": data["l_extendedprice"] * data["l_discount"]}

    @model(project=p)
    @runtime("jax")
    def scaled(f=Model("feats")):
        import jax.numpy as jnp

        return {"net": f["net"] * jnp.float32(2), "l_shipdate": f["l_shipdate"]}

    return p


def cold_nocache(tmp_path, name, project):
    from repro.core.baselines import NoCache

    ws = Workspace(str(tmp_path / name), cache=NoCache())
    write_lines(ws.catalog)
    return ws.run(project)


@pytest.mark.parametrize("bounded", [False, True])
def test_device_backed_service_matches_cold_runs(tmp_path, bounded):
    """Four tenants, four workers, one DeviceTier behind both shared stores:
    every output of every run equals a cold NoCache run bit for bit, and a
    tenant asking what another computed on the device is served from the
    device copies with nothing uploaded.  A bounded tier holds only
    power-of-two lengths of at least ``BOUNDED_MIN_ROWS`` rows."""
    from repro.core.device import BOUNDED_MIN_ROWS, DeviceTier

    tier = DeviceTier(interpret=True, bounded=bounded)
    projects = {
        "q1.40": lambda: q1_like(40), "q1.55": lambda: q1_like(55), "q1.25": lambda: q1_like(25),
        "q6.a": lambda: q6_like(10, 40, 6), "q6.b": lambda: q6_like(30, 61, 6),
        "q6.c": lambda: q6_like(10, 40, 3),
    }
    order = ["q1.40", "q6.a", "q1.55", "q6.b", "q1.25", "q6.c", "q6.a", "q1.40"]
    with PipelineService(str(tmp_path / "svc"), workers=4, rows_per_fragment=256,
                         device=tier) as svc:
        assert svc.scan_cache.device is tier and svc.model_store.device is tier
        write_lines(svc.catalog)
        tenants = ["t0", "t1", "t2", "t3"]
        handles = [
            (name, svc.submit(tenants[i % 4], projects[name]()))
            for i, name in enumerate(order)
        ]
        svc.drain(120)
        refs = {}
        for name, h in handles:
            assert h.state == DONE, h.error
            if name not in refs:
                refs[name] = cold_nocache(tmp_path, f"cold-{name}", projects[name]())
            assert_outputs_bitwise_equal(h.result, refs[name])
        assert svc.session("t0").workspace.device is tier

        first = svc.run("t0", jax_to_jax(50))
        second = svc.run("t1", jax_to_jax(50))
        ref = cold_nocache(tmp_path, "cold-dev", jax_to_jax(50))
        assert_outputs_bitwise_equal(first, ref)
        assert_outputs_bitwise_equal(second, ref)
        assert first.bytes_h2d > 0
        assert second.device_hits > 0 and second.bytes_h2d == 0
        assert svc.report().model_store["cross_tenant_hits"] > 0
        stats = tier.stats()
        assert stats["device_pins"] > 0 and stats["bytes_replicated"] > 0
        if bounded:
            lengths = {e.arr.shape[0] for e in tier._entries.values()}
            assert all(n >= BOUNDED_MIN_ROWS and n & (n - 1) == 0 for n in lengths)


@pytest.mark.parametrize("rows", [1023, 1024, 1025, 2047, 2048, 2049, (1 << 20) + 3])
def test_rowwise_jax_stage_at_bounded_lengths(tmp_path, rows):
    """A rowwise jax stage over ``rows`` input rows runs on pieces of at
    most ``PIECE_ROWS`` rows, each padded to one of ``rowwise_lengths``:
    its output equals one call on the whole input bit for bit, only those
    lengths reach the stage, and the ledger counts the bytes that crossed,
    padding included."""
    import jax.numpy as jnp

    from repro.obs import Tracer
    from repro.pipeline.executor import PIECE_ROWS, _invoke, _padded_length, rowwise_lengths

    rng = np.random.default_rng(rows)
    table = Table({"k": np.arange(rows, dtype=np.int64), "x": rng.standard_normal(rows)})
    seen = []

    def fn(data):
        seen.append(int(data["x"].shape[0]))
        return {"y": data["x"] * jnp.float32(3) - jnp.float32(1), "ok": data["x"] > 0}

    whole = _invoke(fn, "jax", {"data": table}, Tracer(enabled=False))
    ledger = {}
    seen.clear()
    pieces = _invoke(fn, "jax", {"data": table}, Tracer(enabled=False), ledger, rowwise=True)
    full, tail = divmod(rows, PIECE_ROWS)
    assert seen == [PIECE_ROWS] * full + ([_padded_length(tail)] if tail else [])
    assert set(seen) <= set(rowwise_lengths(rows))
    for c in ("y", "ok"):
        np.testing.assert_array_equal(pieces.column(c), whole.column(c))
        assert len(pieces.column(c)) == rows
    copied = sum(seen)
    assert copied - rows < max(1024, tail)
    assert ledger["bytes_d2h"] == copied * (4 + 1)
    assert ledger["bytes_h2d"] == copied * (4 + 4)


def test_lock_wait_span_only_with_the_tracer_on(tmp_path):
    """Two tenants at once: each wait for a shared store's lock is a
    ``store.lock_wait`` span beside (never inside) the plan and insert spans
    it precedes; with the tracer off nothing is recorded."""
    from repro.obs import Tracer

    for enabled in (True, False):
        tracer = Tracer(enabled=enabled)
        with PipelineService(str(tmp_path / f"lw-{enabled}"), workers=2, rows_per_fragment=256,
                             tracer=tracer) as svc:
            write_lines(svc.catalog)
            hs = [svc.submit(t, q1_like(50)) for t in ("a", "b")]
            svc.drain(60)
            assert all(h.state == DONE for h in hs)
        spans = [sp for root in tracer.roots() for sp in root.walk()]
        waits = [sp for sp in spans if sp.name == "store.lock_wait"]
        if not enabled:
            assert spans == []
            continue
        assert {w.attrs["store"] for w in waits} == {"scan", "model"}
        assert {w.attrs["tenant"] for w in waits} == {"a", "b"}
        critical = [sp for sp in spans if sp.name in ("scan.plan", "scan.insert", "node.plan", "node.insert")]
        assert len(waits) == len(critical)
        for sp in critical:
            assert not any(c.name == "store.lock_wait" for c in sp.walk())
        for parent in spans:
            kids = parent.children
            for i, c in enumerate(kids):
                if c.name == "store.lock_wait":
                    nxt = kids[i + 1]
                    assert nxt.name in ("scan.plan", "scan.insert", "node.plan", "node.insert")
                    assert c.t1_ns <= nxt.t0_ns
