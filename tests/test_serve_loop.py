"""The serve loop (repro.core.serve) and the store protocol under it.

Every store the executors drive exposes one protocol (lock, device tier,
claims, read pins, elements, run clock); a plan reports its own
quarantines.  The loop's spans — names, attributes and nesting — are what
the benchmark's metrics read, so a traced, partly warm run through a
device tier pins each of them to its reader.  (A partly cached scan's
chunk order is checked in test_planner_regressions.)
"""

import os
import threading

import numpy as np
import pytest

from repro.core.baselines import NoCache, ScanCache
from repro.core.cache import CacheStore, DifferentialCache, DifferentialStore
from repro.core.columnar import Table
from repro.core.device import DeviceTier
from repro.core.intervals import Interval, IntervalSet
from repro.core.spill import SpillTier
from repro.lake.s3sim import ObjectStore
from repro.obs import Tracer
from repro.pipeline.dsl import Model, Project, model, runtime
from repro.pipeline.executor import Workspace
from repro.service import SharedStore
from repro.service.store import SharedScanCache

from test_device import SCHEMA, events_table

BENCH = os.path.join(os.path.dirname(__file__), os.pardir, "bench")

STORES = {
    "NoCache": NoCache,
    "ScanCache": ScanCache,
    "DifferentialCache": DifferentialCache,
    "DifferentialStore": DifferentialStore,
    "SharedStore": SharedStore,
    "SharedScanCache": SharedScanCache,
}


@pytest.mark.parametrize("name", sorted(STORES))
def test_store_protocol(name):
    store = STORES[name]()
    assert isinstance(store, CacheStore)
    with store.lock:  # reentrant: shared stores compose under it
        with store.lock:
            pass
    assert store.device is None
    assert float(store.claim_timeout) > 0
    with store.reading("sig"):
        assert list(store.elements("sig")) == []
    store.begin_run()
    window = IntervalSet([Interval(0, 10)])
    claim, wait = store.claim_residual("sig", window, ("k",), snapshot_id="s", kind="scan")
    assert wait is None
    if isinstance(store, SharedStore):
        assert claim is not None  # the service coalesces: this caller owns it
    else:
        assert claim is None  # a plain store claims nothing
    store.release_residual(claim)


def test_plan_reports_its_quarantines(tmp_path):
    """Corrupt two spilled payloads: the plan that meets them counts both,
    exactly the rise of the store's ``plan_quarantines`` counter, and
    leaves their windows to the residual."""
    objects = ObjectStore(str(tmp_path / "root"))
    store = DifferentialStore(spill=SpillTier(objects, prefix="_spill/model"))
    windows = [(0, 100), (200, 300), (400, 500)]
    for lo, hi in windows:
        rng = np.random.default_rng(lo)
        store.insert_window(
            signature="sig",
            table="t",
            sort_key="k",
            window=IntervalSet([Interval(lo, hi)]),
            data=_table(lo, hi, rng),
        )
    store.demote_all()
    payloads = sorted(k for k in objects.list("_spill/model") if not k.endswith(".json"))
    assert len(payloads) == 3
    for key in payloads[:2]:  # torn uploads
        path = objects.local_path(key)
        with open(path, "r+b") as f:
            f.truncate(os.path.getsize(path) // 2)

    before = store.plan_quarantines
    plan = store.plan_window(
        signature="sig",
        window=IntervalSet([Interval(0, 500)]),
        columns=(),
        cost_fn=lambda w: w.measure(),
    )
    assert plan.quarantined == store.plan_quarantines - before == 2
    assert len(plan.hits) == 1
    assert plan.residual.measure() == 500 - 100


def _table(lo, hi, rng):
    return Table({"k": np.arange(lo, hi, dtype=np.int64), "x": rng.standard_normal(hi - lo)})


# the bench/ file that reads each span the loop and its callers emit
# (bench/lib/profile.py reads every span by name for the idle breakdown)
SPAN_READERS = {
    "scan.plan": "metrics/plan_ms.run.py",
    "node.plan": "metrics/plan_ms.run.py",
    "scan.claim_wait": "metrics/claim_wait_ms.service.py",
    "node.claim_wait": "metrics/claim_wait_ms.service.py",
    "scan.union": "metrics/union_host_ms.run.py",
    "node.union": "metrics/union_host_ms.run.py",
    "store.lock_wait": "metrics/lock_wait_ms.service.py",
    "scan.residual": "lib/profile.py",
    "node.residual": "lib/profile.py",
    "scan.insert": "lib/profile.py",
    "node.insert": "lib/profile.py",
    "node.explain": "lib/profile.py",
    "device.h2d": "lib/profile.py",
}
SPAN_ATTRS = {
    "scan.plan": {"table"},
    "scan.claim_wait": {"table"},
    "scan.residual": {"table", "rows"},
    "scan.insert": {"table"},
    "scan.union": {"table", "chunks"},
    "node.plan": {"model"},
    "node.claim_wait": {"model"},
    "node.residual": {"model", "rows"},
    "node.insert": {"model"},
    "node.union": {"model", "runs"},
    "node.explain": {"model"},
    "store.lock_wait": {"store", "tenant"},
    "device.h2d": {"site", "bytes"},
}


def _device_project(hi):
    """A jax rowwise node over the scan (the node loop, its residual read
    through the scan loop) and a full jax node scanning another column (the
    scan loop on the device path)."""
    p = Project("contract")
    where = f"eventTime >= 0 AND eventTime < {hi}"

    @model(project=p, incremental="rowwise")
    @runtime("jax")
    def cleaned(data=Model("ns.raw", columns=["c1", "c3"], filter=where)):
        return {k: v for k, v in data.items()}

    @model(project=p)
    @runtime("jax")
    def full(data=Model("ns.raw", columns=["c2"], filter=where)):
        return {k: v for k, v in data.items()}

    return p


def _parents(tracer):
    out = []
    stack = [(root, None) for root in tracer.roots()]
    while stack:
        sp, parent = stack.pop()
        out.append((sp, parent))
        stack.extend((c, sp) for c in sp.children)
    return out


def test_serve_loop_spans_attrs_and_nesting(tmp_path):
    tracer = Tracer()
    model_store = SharedStore()
    scan_cache = SharedScanCache()
    ws = Workspace(
        str(tmp_path / "ws"),
        rows_per_fragment=128,
        cache=scan_cache,
        model_store=model_store,
        device=DeviceTier(interpret=True),
        tracer=tracer,
        tenant="t0",
    )
    ws.catalog.create_table("ns", "raw", SCHEMA, "eventTime")
    ws.catalog.append("ns.raw", events_table(0, 1024))
    ws.run(_device_project(512))  # cold
    (signature,) = list(model_store._elements)
    snap = ws.catalog.current_snapshot_id("ns.raw")
    wide = IntervalSet([Interval(0, 1 << 40)])

    # a live owner of each residual on another thread, releasing its claim
    # only once the run has subscribed to it: the run waits on both claims,
    # then replans and takes the residual itself
    claims = {
        model_store: lambda: model_store.claim_residual(
            signature, wide, snapshot_id=f"ns.raw:{snap}", kind="rowwise"
        ),
        scan_cache: lambda: scan_cache.claim_residual(
            "ns.raw", wide, tuple(SCHEMA), snapshot_id=snap, kind="scan"
        ),
    }
    subscribed = {store: threading.Event() for store in claims}
    claimed = {store: threading.Event() for store in claims}
    for store, event in subscribed.items():

        def spy(*args, _real=store.claim_residual, _event=event, **kwargs):
            mine, wait = _real(*args, **kwargs)
            if wait is not None:
                _event.set()
            return mine, wait

        store.claim_residual = spy

    def owner(store):
        mine, _ = claims[store]()
        assert mine is not None
        claimed[store].set()
        subscribed[store].wait(60)
        store.release_residual(mine)

    owners = [threading.Thread(target=owner, args=(store,)) for store in claims]
    for t in owners:
        t.start()
    for event in claimed.values():
        assert event.wait(60)
    tracer.clear()
    try:
        res = ws.run(_device_project(768))  # partly warm: [0, 512) hits
    finally:
        for event in subscribed.values():
            event.set()
        for t in owners:
            t.join(60)
    assert not any(t.is_alive() for t in owners)
    assert res.coalesced_waits >= 2

    pairs = _parents(tracer)
    seen = {}
    for sp, parent in pairs:
        if sp.name in SPAN_ATTRS:
            assert SPAN_ATTRS[sp.name] <= set(sp.attrs), (sp.name, sp.attrs)
            seen.setdefault(sp.name, []).append((sp, parent))
    assert set(seen) == set(SPAN_ATTRS)
    for name, reader in SPAN_READERS.items():
        text = open(os.path.join(BENCH, reader)).read()
        assert name in text or "def idle_by_span" in text, (name, reader)

    # nesting: each loop span sits directly under its caller's span
    for name, spans in seen.items():
        prefix = name.split(".")[0]
        for sp, parent in spans:
            if prefix in ("scan", "node"):
                assert parent.name == prefix, (name, parent.name)
    for sp, parent in seen["store.lock_wait"]:
        assert parent.name == {"scan": "scan", "model": "node"}[sp.attrs["store"]]
        assert sp.attrs["tenant"] == "t0"
        # the wait ends where the critical section it guards begins
        following = parent.children[parent.children.index(sp) + 1]
        assert following.name in (f"{parent.name}.plan", f"{parent.name}.insert")
    for phase in ("plan", "insert"):
        for sp, parent in seen[f"scan.{phase}"] + seen[f"node.{phase}"]:
            before = parent.children[parent.children.index(sp) - 1]
            assert before.name == "store.lock_wait"
    sites = {sp.attrs["site"]: parent.name for sp, parent in seen["device.h2d"]}
    assert sites.get("scan") == "scan" and sites.get("fresh") == "node"
    # the node's residual input is read through the scan loop
    assert any(
        parent is not None and parent.name == "node.residual"
        for sp, parent in pairs
        if sp.name == "scan"
    )
    assert [sp.attrs["runs"] for sp, _ in seen["node.union"]] == [2]
    assert sorted(sp.attrs["chunks"] for sp, _ in seen["scan.union"]) == [1, 2]
    (rows,) = [sp.attrs["rows"] for sp, _ in seen["node.residual"]]
    assert rows == 256

