"""Device-resident cache tier (ISSUE 8): pinning, device UNION assembly,
LRU demotion, spill→device promotion, fallback accounting, and the bitwise
contract against the numpy path — property-checked and swept through the
full edit matrix with a device-enabled warm workspace.
"""

from __future__ import annotations

import numpy as np
import pytest
from hypothesis import given, settings
from hypothesis import strategies as st

import jax.numpy as jnp

from edit_matrix import standard_matrix, sweep
from repro.core.cache import DifferentialStore
from repro.core.columnar import ChunkedTable, Table
from repro.core.device import (
    ROW_BLOCK,
    DeviceChunkedTable,
    DeviceTier,
    device_union,
)
from repro.core.device import _pad_rows
from repro.core.intervals import Interval, IntervalSet
from repro.core.spill import ObjectStore, SpillTier
from repro.kernels.fragment_gather import fragment_gather, gather_ref
from repro.pipeline.dsl import Model, Project, model, runtime
from repro.pipeline.executor import Workspace

SCHEMA = {"eventTime": "<i8", "c1": "<f8", "c2": "<f8", "c3": "<i8"}


def events_table(lo, hi, seed=0):
    n = hi - lo
    rng = np.random.default_rng(seed + lo)
    return Table(
        {
            "eventTime": np.arange(lo, hi, dtype=np.int64),
            "c1": rng.standard_normal(n),
            "c2": rng.standard_normal(n),
            "c3": rng.integers(0, 100, n).astype(np.int64),
        }
    )


def jax_feature_project(where="eventTime >= 0 AND eventTime < 800",
                        columns=("c1", "c3"), gain=1.0, scaled_mode="none"):
    """cleaned (jax rowwise) -> scaled (jax): the device tier's consumer
    shape.  ``scaled_mode="none"`` makes the second stage a full-window
    consumer (re-reads every row each run — where the numpy path pays the
    host link); the edit-matrix sweep uses ``"rowwise"`` so its
    zero-recompute expectations hold.  Exactly-rounded elementwise ops only —
    residual recomputes must be bitwise-stable across batch shapes."""
    p = Project("devfeat")
    cols = list(columns)

    @model(project=p, incremental="rowwise")
    @runtime("jax")
    def cleaned(data=Model("ns.raw", columns=cols, filter=where)):
        return {
            k: (jnp.where(v >= 0, v, v * jnp.float32(0.5)) if v.dtype.kind == "f" else v)
            for k, v in data.items()
        }

    @model(project=p, incremental=scaled_mode)
    @runtime("jax")
    def scaled(data=Model("cleaned")):
        return {
            k: (v * jnp.float32(gain) if v.dtype.kind == "f" else v)
            for k, v in data.items()
        }

    return p


# ---------------------------------------------------- device_union: property
@settings(max_examples=30, deadline=None)
@given(
    st.integers(0, 2**31 - 1),
    st.sampled_from(["<f4", "<i8", "<i1"]),
    st.integers(0, 5),
    st.booleans(),
)
def test_device_union_bitwise_equals_numpy_reference(seed, dtype, n_runs, aligned):
    """The bitwise contract across dtypes (f32 / i64 / i8), run counts
    (including the empty-residual and single-fragment shapes), and window
    alignment (aligned → block-run fast path; non-aligned → fallback):
    device_union of padded pins ≡ host np.concatenate then jnp.asarray."""
    rng = np.random.default_rng(seed)
    dt = np.dtype(dtype)
    providers = []
    for _ in range(int(rng.integers(1, 4))):
        rows = int(rng.integers(1, 3 * ROW_BLOCK))
        if dt.kind == "f":
            host = rng.standard_normal(rows).astype(dt)
        else:
            host = rng.integers(-100, 100, rows).astype(dt)
        providers.append(host)
    runs, host_parts = [], []
    dev = [{"x": _pad_rows(jnp.asarray(h))} for h in providers]
    for _ in range(n_runs):
        i = int(rng.integers(len(providers)))
        n = len(providers[i])
        lo = int(rng.integers(0, n + 1))
        hi = int(rng.integers(lo, n + 1))
        if aligned:
            lo, hi = (lo // ROW_BLOCK) * ROW_BLOCK, (hi // ROW_BLOCK) * ROW_BLOCK
        runs.append((dev[i], lo, hi))
        host_parts.append(providers[i][lo:hi])
    ledger = {}
    got = device_union(runs, ["x"], interpret=True, ledger=ledger)
    if not runs:
        assert got == {}
        return
    expected = np.asarray(  # x32 downcast commutes with the concat
        jnp.asarray(np.concatenate(host_parts or [providers[0][0:0]]))
    )
    np.testing.assert_array_equal(np.asarray(got["x"]), expected)


def test_device_union_single_fragment_is_a_slice():
    """One run from one provider: a gather would be the identity, so the
    union is a device slice — no kernel call counted either way."""
    host = np.arange(64, dtype=np.float32)
    ledger = {}
    got = device_union(
        [({"x": _pad_rows(jnp.asarray(host))}, 8, 40)], ["x"],
        interpret=True, ledger=ledger,
    )
    np.testing.assert_array_equal(np.asarray(got["x"]), host[8:40])
    assert "gather_fast" not in ledger and "gather_fallbacks" not in ledger
    assert ledger["device_unions"] == 1


def test_device_union_multi_interval_hits_fast_path():
    """Two aligned runs of ONE provider become a single block-run
    fragment_gather on the tiled fast path."""
    host = np.arange(4096, dtype=np.float32)
    prov = {"x": _pad_rows(jnp.asarray(host))}
    ledger = {}
    got = device_union(
        [(prov, 0, 1024), (prov, 2048, 4096)], ["x"], interpret=True, ledger=ledger
    )
    np.testing.assert_array_equal(
        np.asarray(got["x"]), np.concatenate([host[0:1024], host[2048:4096]])
    )
    assert ledger["gather_fast"] == 1
    assert "gather_fallbacks" not in ledger


def test_device_union_non_aligned_counts_fallback_downgrade():
    """Off-tile runs still serve (XLA slices, no kernel) but the downgrade
    is counted, not hidden."""
    host = np.arange(512, dtype=np.float32)
    prov = {"x": _pad_rows(jnp.asarray(host))}
    ledger = {}
    got = device_union(
        [(prov, 3, 130), (prov, 259, 500)], ["x"], interpret=True, ledger=ledger
    )
    np.testing.assert_array_equal(
        np.asarray(got["x"]), np.concatenate([host[3:130], host[259:500]])
    )
    assert ledger["gather_fallbacks"] == 1
    assert "gather_fast" not in ledger


def test_device_union_empty_runs_yield_empty_columns():
    prov = {"x": _pad_rows(jnp.asarray(np.arange(16, dtype=np.float32)))}
    got = device_union([(prov, 4, 4), (prov, 9, 9)], ["x"], interpret=True)
    assert np.asarray(got["x"]).shape == (0,)


# ------------------------------------------------ bounded tier: closed shapes
@pytest.mark.parametrize("dtype", ["<f8", "<i8", "|b1"])
def test_bounded_union_equals_exact_union(dtype):
    """A bounded tier's union holds the exact union's rows first, at a
    power-of-two length; a DeviceTable over it trims to the real rows."""
    from repro.core.device import DeviceTable, _upload, bounded_rows

    rng = np.random.default_rng(len(dtype))
    hosts = [
        (rng.standard_normal(n) * 50).astype(dtype)
        for n in (70_000, 3, 150_000)
    ]
    exact = [{"x": _pad_rows(jnp.asarray(h))} for h in hosts]
    bounded = [{"x": _upload(h, True)[0]} for h in hosts]
    layout = [(2, 5, 90_000), (0, 0, 3), (1, 1, 3), (0, 64_000, 70_000), (2, 90_000, 150_000)]
    want = device_union([(exact[i], lo, hi) for i, lo, hi in layout], ["x"], interpret=True)
    got = device_union(
        [(bounded[i], lo, hi) for i, lo, hi in layout], ["x"], interpret=True, bounded=True
    )
    rows = int(want["x"].shape[0])
    assert got["x"].shape == (bounded_rows(rows),)
    np.testing.assert_array_equal(np.asarray(got["x"])[:rows], np.asarray(want["x"]))
    table = DeviceTable(Table({"x": np.asarray(want["x"])}), got)
    np.testing.assert_array_equal(np.asarray(table.device_columns["x"]), np.asarray(want["x"]))


def test_bounded_tier_compiles_nothing_after_warm():
    """After ``warm``, pins, uploads, unions and merge replicas of any
    layout up to the warmed rows run without a compile."""
    from jax import monitoring

    from repro.core.device import upload_residual

    tier = DeviceTier(interpret=True, bounded=True)
    tier.warm([np.float32, np.int32], 400_000)
    compiles = []

    def listen(event, duration, **_kw):
        if event == "/jax/core/compile/backend_compile_duration":
            compiles.append(duration)

    monitoring.register_event_duration_secs_listener(listen)
    try:
        rng = np.random.default_rng(3)
        for n in (5, 1_000, 65_537, 131_071, 199_999):
            elem = _Elem(Table({"x": rng.standard_normal(n), "k": np.arange(n)}))
            pinned = tier.pin_columns(elem, ["x", "k"])
            fresh = upload_residual(
                Table({"x": rng.standard_normal(n // 2 + 1), "k": np.arange(n // 2 + 1)}),
                ["x", "k"], {}, tier.tracer, "test", bounded=True,
            )
            lo = int(rng.integers(0, n))
            device_union(
                [(pinned, lo, n), (fresh, 0, n // 2 + 1)], ["x", "k"], bounded=True
            )["x"].block_until_ready()
    finally:
        monitoring.unregister_event_duration_listener(listen)
    assert compiles == []


# ------------------------------------------------- fragment_gather regressions
def test_fragment_gather_tail_not_padded_into_output():
    """The output is exactly the runs: a pin's tile-padded tail never leaks
    zero rows into it (the pin padding regression)."""
    host = np.random.default_rng(0).standard_normal(3000).astype(np.float32)
    src = _pad_rows(jnp.asarray(host))
    assert src.shape == (3 * ROW_BLOCK,)
    bounds = [(2048, 3072), (0, 1024)]
    out = fragment_gather(src, bounds, interpret=True)
    assert out.shape == (2048,)
    np.testing.assert_array_equal(np.asarray(out), np.asarray(gather_ref(src, bounds)))
    np.testing.assert_array_equal(np.asarray(out[:952]), host[2048:])


def test_fragment_gather_rejects_out_of_range_indices():
    src = jnp.asarray(np.zeros(1024, np.float32))
    with pytest.raises(IndexError):
        fragment_gather(src, [(0, 1024), (1024, 2048)], interpret=True)


# ----------------------------------------------------- ChunkedTable column memo
def test_chunked_table_column_memoized_and_frozen():
    chunks = [events_table(0, 100), events_table(100, 200)]
    ct = ChunkedTable(chunks)
    a = ct.column("c1")
    assert ct.column("c1") is a, "second access must hit the memo"
    with pytest.raises(ValueError):
        a[0] = 99.0  # memoized arrays are read-only: aliasing is safe


def test_chunked_table_single_chunk_column_is_zero_copy():
    t = events_table(0, 50)
    ct = ChunkedTable([t])
    assert np.shares_memory(ct.column("c1"), t.column("c1"))


# -------------------------------------------------------------- DeviceTier unit
class _Elem:
    _next = iter(range(10_000, 20_000))

    def __init__(self, data):
        self.elem_id = next(self._next)
        self.data = data


def test_device_tier_pin_hit_and_lru_eviction():
    col = np.arange(256, dtype=np.float64)
    elem_bytes = _pad_rows(jnp.asarray(col)).nbytes
    tier = DeviceTier(max_bytes=2 * elem_bytes, interpret=True)
    elems = [_Elem(Table({"x": col + i})) for i in range(3)]
    for e in elems[:2]:
        assert tier.pin(e, "x") is not None
    assert tier.pin(elems[0], "x") is not None  # refresh elems[0]'s LRU slot
    assert tier.stats()["device_hits"] == 1
    assert tier.pin(elems[2], "x") is not None  # over budget → evict elems[1]
    assert tier.get(elems[1].elem_id, "x") is None
    assert tier.get(elems[0].elem_id, "x") is not None
    assert tier.stats()["device_evictions"] == 1
    assert tier.nbytes <= 2 * elem_bytes


def test_device_tier_unsupported_dtype_falls_back():
    tier = DeviceTier(interpret=True)
    e = _Elem(Table({"s": np.array(["a", "b"], dtype="<U1")}))
    assert tier.pin(e, "s") is None
    assert tier.pin_columns(e, ["s"]) is None
    assert len(tier) == 0


def test_device_tier_drop_element_forgets_all_pins():
    tier = DeviceTier(interpret=True)
    e = _Elem(events_table(0, 32))
    assert tier.pin_columns(e, ["c1", "c3"]) is not None
    assert len(tier) == 2
    tier.drop_element(e.elem_id)
    assert len(tier) == 0
    assert tier.get(e.elem_id, "c1") is None


# ----------------------------------------- store integration: merge replication
def _insert(store, sig, lo, hi, seed=0):
    return _insert_pairs(store, sig, ((lo, hi),), seed)


def _insert_pairs(store, sig, pairs, seed=0, on_device=False):
    """Insert an element over the windows ``pairs``; ``on_device`` registers
    its payload with the device tier as a residual computed there."""
    keys = np.concatenate([np.arange(lo, hi, dtype=np.int64) for lo, hi in pairs])
    data = Table({
        "k": keys,
        "x": np.random.default_rng(seed + int(keys[0])).standard_normal(keys.size),
    })
    return store.insert_window(
        signature=sig, table="t", sort_key="k",
        window=IntervalSet.of(*pairs), data=data,
        device_arrays=(
            {c: jnp.asarray(data.column(c)) for c in data.column_names}
            if on_device else None
        ),
    )


@pytest.mark.parametrize(
    "a_pairs, b_pairs",
    [
        pytest.param(((0, 64),), ((64, 128),), id="append"),
        pytest.param(((0, 32), (64, 96)), ((32, 64),), id="split"),
    ],
)
def test_merge_replicates_pins_device_to_device(a_pairs, b_pairs):
    """Merging two pinned elements rebuilds the merged pin by device→device
    gather: zero new H2D, bytes_replicated > 0, parents dropped, and the
    replica equals the host payload row for row."""
    tier = DeviceTier(interpret=True)
    store = DifferentialStore(device=tier)
    a = _insert_pairs(store, "s", a_pairs)
    tier.pin_columns(a, ["k", "x"])
    h2d_before = tier.stats()["bytes_h2d"]
    whole = IntervalSet.of(*a_pairs).union(IntervalSet.of(*b_pairs))
    plan = store.plan_window(
        "s", whole, (), lambda w: w.measure(), device_consumer=True,
    )
    assert plan.residual.to_pairs() == b_pairs
    _insert_pairs(store, "s", b_pairs, seed=1, on_device=True)
    (merged,) = store.elements("s")
    assert merged.window == whole
    stats = tier.stats()
    assert stats["bytes_h2d"] == h2d_before, "merge must not upload"
    assert stats["bytes_replicated"] > 0
    assert tier.element_arrays(a, ["k", "x"]) is None, "parents dropped"
    arrays = tier.element_arrays(merged, ["k", "x"])
    assert arrays is not None
    for c in ("k", "x"):
        np.testing.assert_array_equal(
            np.asarray(arrays[c][: merged.data.num_rows]),
            np.asarray(jnp.asarray(merged.data.column(c))),
        )


def test_merge_never_sorts(monkeypatch):
    """A merge through ``insert_window`` concatenates key-ordered runs; it
    never sorts the merged payload, on the host or for the device replica."""
    def refuse(self, name):
        raise AssertionError("a merge sorted its payload")

    monkeypatch.setattr(Table, "sort_by", refuse)
    tier = DeviceTier(interpret=True)
    store = DifferentialStore(device=tier)
    a = _insert_pairs(store, "s", ((0, 32), (64, 96)))
    tier.pin_columns(a, ["k", "x"])
    b = _insert_pairs(store, "s", ((32, 64),), seed=7, on_device=True)
    (merged,) = store.elements("s")
    assert merged.window.to_pairs() == ((0, 96),)
    assert tier.stats()["bytes_replicated"] > 0
    np.testing.assert_array_equal(merged.data.column("k"), np.arange(96))
    np.testing.assert_array_equal(
        merged.data.column("x"),
        np.concatenate([a.data.column("x")[:32], b.data.column("x"),
                        a.data.column("x")[32:]]),
    )


@pytest.mark.parametrize("bounded", [False, True], ids=["exact", "bounded"])
def test_union_never_sorts(tmp_path, monkeypatch, bounded):
    """A warm widen serves one hit and a residual on both sides of it: the
    node concatenates their runs in window order, never sorting or combining
    them, and the device union built from the same runs equals the host."""
    import sys

    from repro.core.device import DeviceTable

    def refuse(cls, name):
        orig = getattr(cls, name)

        def spy(self, *args):
            if sys._getframe(1).f_code.co_name == "_run_incremental":
                raise AssertionError(f"the union called {cls.__name__}.{name}")
            return orig(self, *args)

        monkeypatch.setattr(cls, name, spy)

    refuse(Table, "sort_by")
    refuse(ChunkedTable, "sort_by")
    refuse(ChunkedTable, "combine")
    ws = Workspace(
        str(tmp_path / "dev"), rows_per_fragment=128,
        device=DeviceTier(interpret=True, bounded=bounded),
    )
    ws.catalog.create_table("ns", "raw", SCHEMA, "eventTime")
    ws.catalog.append("ns.raw", events_table(0, 1024))
    ws.run(jax_feature_project(_w(256, 512)))
    res = ws.run(jax_feature_project(_w(0, 1024)))
    stats = res.node_stats["cleaned"]
    assert stats["cached_rows"] == 256 and stats["fresh_rows"] == 768
    out = res.outputs["cleaned"]
    assert isinstance(out, DeviceTable)
    assert set(out.device_columns) == set(out.column_names)
    for c, arr in out.device_columns.items():
        np.testing.assert_array_equal(np.asarray(arr), out.column(c), err_msg=c)


def test_spill_promotion_goes_straight_to_device(tmp_path):
    """A demoted element planned for a jax consumer promotes mmap → H2D
    once: resident on device, plan charged with the upload."""
    tier = DeviceTier(interpret=True)
    spill = SpillTier(ObjectStore(str(tmp_path / "obj")))
    store = DifferentialStore(spill=spill, device=tier)
    _insert(store, "s", 0, 64)
    store.demote_all()
    assert store.nbytes == 0
    plan = store.plan_window(
        "s", IntervalSet([Interval(0, 64)]), (), lambda w: w.measure(),
        device_consumer=True,
    )
    assert plan.hits
    assert spill.device_promotions == 1
    assert plan.bytes_h2d > 0
    assert tier.get(plan.hits[0].element.elem_id, "x") is not None


def test_shared_store_stats_carry_device_ledger(tmp_path):
    from repro.service import SharedStore

    plain = SharedStore()
    keys = ("device_nbytes", "device_entries", "bytes_h2d", "device_hits",
            "device_evictions", "device_pins", "bytes_replicated")
    s = plain.stats()
    assert all(s[k] == 0 for k in keys)

    tiered = SharedStore(device=DeviceTier(interpret=True))
    _insert(tiered, "s", 0, 32)
    tiered.device.pin_columns(tiered.elements("s")[0], ["k", "x"])
    s = tiered.stats()
    assert s["device_entries"] == 2 and s["bytes_h2d"] > 0


# ------------------------------------------------- executor: end-to-end serving
def _dev_workspace(root, device=True):
    ws = Workspace(
        root, rows_per_fragment=128,
        device=DeviceTier(interpret=True) if device else None,
    )
    ws.catalog.create_table("ns", "raw", SCHEMA, "eventTime")
    ws.catalog.append("ns.raw", events_table(0, 1024))
    return ws


def _w(lo, hi):
    return f"(eventTime >= {lo} AND eventTime < {hi})"


def test_warm_run_serves_from_device_and_counts_hits(tmp_path):
    ws = _dev_workspace(str(tmp_path / "dev"))
    ref = _dev_workspace(str(tmp_path / "ref"), device=False)
    for where in (_w(0, 1024), _w(0, 1024)):
        dres = ws.run(jax_feature_project(where))
        rres = ref.run(jax_feature_project(where))
        for name, table in dres.outputs.items():
            for col in table.column_names:
                np.testing.assert_array_equal(
                    np.asarray(table.column(col)),
                    np.asarray(rres.outputs[name].column(col)),
                    err_msg=f"{name}:{col}",
                )
    assert dres.bytes_h2d == 0, "warm rerun must not touch the host link"
    assert dres.device_hits > 0
    assert rres.bytes_h2d >= 0  # numpy path counts its uploads too
    assert ws.device.stats()["device_entries"] > 0


def test_multi_interval_window_takes_gather_fast_path(tmp_path):
    """An OR-window served from two intervals of one merged element is a
    genuine multi-run fragment_gather — tile-aligned bounds hit the kernel
    and the ledger says so."""
    ws = _dev_workspace(str(tmp_path / "dev"))
    ws.catalog.append("ns.raw", events_table(1024, 4096))
    ws.run(jax_feature_project(_w(0, 4096)))
    res = ws.run(jax_feature_project(f"{_w(0, 1024)} OR {_w(2048, 4096)}"))
    assert res.gather_fast >= 1
    assert res.bytes_h2d == 0


def test_non_aligned_window_counts_fallback_downgrade(tmp_path):
    ws = _dev_workspace(str(tmp_path / "dev"))
    ws.run(jax_feature_project(_w(0, 1024)))
    res = ws.run(jax_feature_project(f"{_w(3, 259)} OR {_w(515, 1019)}"))
    assert res.gather_fallbacks >= 1
    ref = _dev_workspace(str(tmp_path / "ref"), device=False)
    ref.run(jax_feature_project(_w(0, 1024)))
    rres = ref.run(jax_feature_project(f"{_w(3, 259)} OR {_w(515, 1019)}"))
    for name, table in res.outputs.items():
        for col in table.column_names:
            np.testing.assert_array_equal(
                np.asarray(table.column(col)),
                np.asarray(rres.outputs[name].column(col)),
            )


def test_device_chunked_table_select_keeps_device_columns():
    t = events_table(0, 64)
    dct = DeviceChunkedTable([t], {"c1": jnp.asarray(t.column("c1"))})
    sel = dct.select(["c1", "c3"])
    assert isinstance(sel, DeviceChunkedTable)
    assert set(sel.device_columns) == {"c1"}


# ----------------------------------------------------- the edit-matrix contract
def test_edit_matrix_device_warm_vs_numpy_cold(tmp_path):
    """The strictest gate: a device-enabled warm workspace driven through the
    full ISSUE-6 edit matrix must stay bitwise-equal to numpy-path cold
    replays on EVERY edit (the cold setups get no device tier)."""

    def setup(root):
        # sweep() uses one warm root and fresh cold roots per edit: give the
        # warm workspace the tier, the cold references the plain numpy path
        return _dev_workspace(root, device=root.endswith("em-warm"))

    def factory(hi=499, columns=("c1", "c3"), gain=1.0):
        return jax_feature_project(
            _w(0, hi + 1), columns=columns, gain=gain, scaled_mode="rowwise"
        )

    append = lambda c: c.append("ns.raw", events_table(1024, 1124, seed=9))
    overwrite = lambda c: c.overwrite_range(
        "ns.raw", 128, 256, events_table(128, 256, seed=77)
    )
    edits = standard_matrix(
        base=dict(hi=499),
        widen=dict(hi=1023),
        narrow=dict(hi=299),
        beyond=dict(hi=4999),
        feature_add=dict(hi=4999, columns=("c1", "c2", "c3")),
        feature_remove=dict(hi=4999),
        code_edit=dict(hi=4999, gain=2.0),
        append=append,
        overwrite=overwrite,
    )
    results = sweep(tmp_path, setup, factory, edits)
    assert any(w.device_hits > 0 for _l, w, _c in results[1:]), (
        "the warm workspace never served from the device tier"
    )


# ----------------------------------------------------------- bench8 acceptance
def test_bench8_acceptance():
    from benchmarks import bench8_device as b8

    # smallest scale where the fixed-size append residual (one fragment)
    # doesn't dominate the device path's warm uploads
    result = b8.run(rows=16384)
    assert result["bitwise_equal"]
    assert result["warm"]["h2d_ratio"] >= 5
    assert result["warm"]["gather_fast"] >= 1
    assert result["roofline"]["modeled_speedup"] > 1
