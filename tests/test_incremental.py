"""The incrementality contract (ISSUE 3 tentpole): intermediate ``@model``
outputs are cached differentially, and every pipeline edit — feature add/
remove, window widen/narrow, upstream append, function code edit — produces
outputs bitwise-identical to a cold full run while recomputing only the
residual.  The edit sweep itself lives in the shared harness
(``tests/edit_matrix.py``, ISSUE 6), instantiated here for the single-input
rowwise contract; ``test_keyed.py``/``test_multi_input.py`` instantiate the
same matrix for the keyed and multi-input contracts.

Also unit-covers the generalized :class:`DifferentialStore` (the greedy
window-subtraction planner split out of :class:`DifferentialCache`) and the
DSL/DAG validation of the ``incremental="rowwise"`` contract.
"""

import numpy as np
import pytest

from edit_matrix import (
    assert_outputs_bitwise_equal,
    expect_fresh_rows,
    expect_fresh_rows_between,
    expect_zero_rows,
    standard_matrix,
    sweep,
)
from repro.core.cache import DifferentialCache, DifferentialStore
from repro.core.columnar import ChunkedTable, Table
from repro.core.intervals import IntervalSet
from repro.pipeline import DagError, Model, Project, Workspace, build_dag, model, runtime
from repro.pipeline.dsl import code_fingerprint

SCHEMA = {"eventTime": "<i8", "c1": "<f8", "c2": "<f8", "c3": "<i8"}


def events_table(lo, hi, seed=0):
    n = hi - lo
    rng = np.random.default_rng(seed + lo)
    return Table(
        {
            "eventTime": np.arange(lo, hi, dtype=np.int64),  # unique keys
            "c1": rng.standard_normal(n),
            "c2": rng.standard_normal(n),
            "c3": rng.integers(0, 100, n).astype(np.int64),
        }
    )


def make_workspace(tmp_path, name="lake", rows=1000):
    ws = Workspace(str(tmp_path / name), rows_per_fragment=128)
    ws.catalog.create_table("ns", "raw", SCHEMA, "eventTime")
    ws.catalog.append("ns.raw", events_table(0, rows))
    return ws


def feature_project(hi=799, columns=("c1", "c3"), gain=1.0):
    """cleaned (rowwise drop) -> scaled (rowwise map) — the minimal
    incremental chain, parameterized along the three edit axes."""
    p = Project("feat")
    cols = list(columns)

    @model(project=p, incremental="rowwise")
    @runtime("numpy")
    def cleaned(
        data=Model("ns.raw", columns=cols, filter=f"eventTime BETWEEN 0 AND {hi}")
    ):
        return data.filter(data.column("eventTime") % 10 != 0)

    @model(project=p, incremental="rowwise")
    @runtime("numpy")
    def scaled(data=Model("cleaned")):
        out = {n: data.column(n) for n in data.column_names}
        out["score"] = gain * np.asarray(data.column("c1"), dtype=np.float64)
        return out

    return p


# ----------------------------------------------------- DifferentialStore unit
def _store_elem_data(lo, hi):
    return Table(
        {"k": np.arange(lo, hi, dtype=np.int64), "x": np.arange(lo, hi, dtype=np.float64)}
    )


def test_store_plans_any_signature_differentially():
    store = DifferentialStore()
    sig = ("fnhash", "numpy", ("scan", "t"))
    cost = lambda w: w.measure()
    store.insert_window(sig, "t", "k", IntervalSet.of((0, 50)), _store_elem_data(0, 50))

    plan = store.plan_window(sig, IntervalSet.of((0, 80)), (), cost)
    assert [h.window.to_pairs() for h in plan.hits] == [((0, 50),)]
    assert plan.residual.to_pairs() == ((50, 80),)

    # a different signature sees nothing
    other = store.plan_window(("other",), IntervalSet.of((0, 80)), (), cost)
    assert not other.hits and other.residual.to_pairs() == ((0, 80),)


def test_store_merges_touching_windows_per_signature():
    store = DifferentialStore()
    store.insert_window("s", "t", "k", IntervalSet.of((0, 50)), _store_elem_data(0, 50))
    store.insert_window("s", "t", "k", IntervalSet.of((50, 100)), _store_elem_data(50, 100))
    elems = store.elements("s")
    assert len(elems) == 1
    assert elems[0].window.to_pairs() == ((0, 100),)
    np.testing.assert_array_equal(
        elems[0].data.column("k"), np.arange(0, 100, dtype=np.int64)
    )


def test_store_partial_window_coverage_is_served():
    """Measure-based cost serves cached rows even inside a partially-covered
    region — the property model nodes need and fragment-byte cost can't give."""
    store = DifferentialStore()
    store.insert_window("s", "t", "k", IntervalSet.of((10, 40)), _store_elem_data(10, 40))
    plan = store.plan_window("s", IntervalSet.of((0, 100)), (), lambda w: w.measure())
    assert plan.hits and plan.hits[0].window.to_pairs() == ((10, 40),)
    assert plan.residual.to_pairs() == ((0, 10), (40, 100))


def test_store_lru_eviction_budget():
    elem_bytes = _store_elem_data(0, 100).nbytes
    store = DifferentialStore(max_bytes=3 * elem_bytes)
    for i, sig in enumerate(["a", "b", "c", "d"]):
        store.insert_window(
            sig, "t", "k", IntervalSet.of((0, 100)), _store_elem_data(0, 100)
        )
    assert store.nbytes <= 3 * elem_bytes
    assert store.evictions == 1
    assert store.elements("a") == []  # eldest signature evicted
    assert store.elements("d")


def test_differential_cache_is_a_store_specialization():
    """The scan cache exposes the store surface (shared counters/eviction)."""
    cache = DifferentialCache()
    assert isinstance(cache, DifferentialStore)
    assert cache.lookups == 0 and cache.nbytes == 0


# ------------------------------------------------------------- DSL validation
def test_rowwise_multi_input_accepted():
    """≥2 inputs is the multi-input rowwise contract (an incremental join),
    no longer a structural error — see test_multi_input.py for execution."""
    p = Project("join-ok")

    @model(project=p, incremental="rowwise")
    def join(
        a=Model("ns.x", columns=["c1"]),
        b=Model("ns.y", columns=["c1"]),
    ):
        return a

    dag = build_dag(p)
    assert dag.order == ["join"]


def test_rowwise_requires_windowed_upstream():
    p = Project("bad2")

    @model(project=p)  # default: none
    def agg(data=Model("ns.raw", columns=["c1"])):
        return data

    @model(project=p, incremental="rowwise")
    def downstream(data=Model("agg")):
        return data

    with pytest.raises(DagError, match="windowed"):
        build_dag(p)


def test_unknown_incremental_mode_rejected():
    with pytest.raises(ValueError, match="incremental"):
        model(incremental="columnar")


def test_code_fingerprint_tracks_behaviour_not_model_refs():
    def make(gain, hi):
        def fn(data=Model("ns.raw", columns=["c1"], filter=f"eventTime < {hi}")):
            return {"s": gain * data.column("c1")}

        return fn

    # same behaviour, different window -> same fingerprint (the window is the
    # differential dimension, not identity)
    assert code_fingerprint(make(2.0, 100)) == code_fingerprint(make(2.0, 999))
    # different closed-over constant -> different fingerprint (a code edit)
    assert code_fingerprint(make(2.0, 100)) != code_fingerprint(make(3.0, 100))


def test_code_fingerprint_sees_large_array_closures():
    """repr() elides interior array values ('...'), so closed-over weight
    vectors differing only in the middle must still change the fingerprint —
    the hash reads array bytes, also through containers."""

    def make(weights):
        def fn(data=Model("ns.raw", columns=["c1"])):
            return {"s": data.column("c1") * weights.sum()}

        return fn

    a = np.zeros(5000)
    b = np.zeros(5000)
    b[2500] = 5.0  # invisible to repr()
    assert repr(a) == repr(b)
    assert code_fingerprint(make(a)) != code_fingerprint(make(b))
    assert code_fingerprint(make(a)) == code_fingerprint(make(np.zeros(5000)))

    def make_nested(cfg):
        def fn(data=Model("ns.raw", columns=["c1"])):
            return {"s": data.column("c1") * cfg["w"].sum()}

        return fn

    assert code_fingerprint(make_nested({"w": a})) != code_fingerprint(
        make_nested({"w": b})
    )


# ------------------------------------------------- the incrementality contract
def run_cold(tmp_path, name, project, mutations=()):
    """Fresh workspace + same catalog history -> the reference full run."""
    ws = make_workspace(tmp_path, name)
    for m in mutations:
        m(ws.catalog)
    return ws.run(project)


def _setup(root):
    ws = Workspace(root, rows_per_fragment=128)
    ws.catalog.create_table("ns", "raw", SCHEMA, "eventTime")
    ws.catalog.append("ns.raw", events_table(0, 1000))
    return ws


def test_edit_matrix_rowwise(tmp_path):
    """The full ISSUE-6 edit matrix for the single-input rowwise contract:
    one warm workspace through every edit axis, each answer bitwise-equal to
    a cold replay, with exact residual row counts where they are derivable."""
    append = lambda c: c.append("ns.raw", events_table(1000, 1100, seed=9))
    overwrite = lambda c: c.overwrite_range(
        "ns.raw", 100, 200, events_table(100, 200, seed=77)
    )

    def expect_rerun_served_from_model_cache(warm, cold):
        assert warm.bytes_from_store == 0
        assert warm.bytes_from_model_cache > 0

    def expect_feature_add(warm, cold):
        assert warm.rows_to_user_fns > 0  # schema change: recompute required
        assert "c2" in warm.outputs["scaled"].column_names

    def expect_code_edit(warm, cold):
        # `cleaned` untouched by the gain edit: full hit; `scaled` recomputes
        assert warm.node_stats["cleaned"]["fresh_rows"] == 0
        assert warm.node_stats["scaled"]["fresh_rows"] > 0

    edits = standard_matrix(
        base=dict(hi=499),
        widen=dict(hi=999),
        narrow=dict(hi=299),
        beyond=dict(hi=4999),
        feature_add=dict(hi=4999, columns=("c1", "c2", "c3")),
        feature_remove=dict(hi=4999),
        code_edit=dict(hi=4999, gain=2.0),
        append=append,
        overwrite=overwrite,
        expectations={
            "rerun": expect_rerun_served_from_model_cache,
            # residual (499, 1000): exactly the newly-exposed 500 keys
            "widen": expect_fresh_rows("cleaned", 500),
            # widening past the data's extent: the residual holds no rows
            "beyond": expect_fresh_rows("cleaned", 0),
            "feature-add": expect_feature_add,
            # dropping c2 flips the signature BACK to one the cache still
            # covers over the full window: zero recompute
            "feature-remove": expect_zero_rows,
            # exactly the 100 appended rows, through both stages
            "append": expect_fresh_rows("cleaned", 100),
            # overwritten keys [100, 200) span at most 3 of the 128-row
            # fragments; everything else serves from cache
            "overwrite": expect_fresh_rows_between("cleaned", 1, 384),
            "code-edit": expect_code_edit,
        },
    )
    sweep(tmp_path, _setup, feature_project, edits)


def test_downstream_of_scan_edit_invalidates_through_chain(tmp_path):
    """Editing the scan (feature add) changes the leaf signature component,
    which must propagate: BOTH stages recompute."""
    ws = make_workspace(tmp_path)
    ws.run(feature_project(columns=("c1", "c3")))
    res = ws.run(feature_project(columns=("c1", "c2", "c3")))
    assert res.node_stats["cleaned"]["fresh_rows"] > 0
    assert res.node_stats["scaled"]["fresh_rows"] > 0


def test_warm_full_hit_is_zero_copy(tmp_path):
    ws = make_workspace(tmp_path)
    ws.run(feature_project())
    res = ws.run(feature_project())
    elems = ws.model_store.elements()
    assert elems
    out = res.outputs["scaled"]
    assert any(
        np.shares_memory(out.column("score"), e.data.column("score"))
        for e in elems
        if "score" in e.data.column_names
    ), "a fully-cached model output must be a view over the element buffer"


def test_rowwise_jax_runtime_cached_across_languages(tmp_path):
    """The model store sits below language choice, like the scan cache."""
    p = Project("jaxinc")

    @model(project=p, incremental="rowwise")
    @runtime("jax")
    def jfeat(data=Model("ns.raw", columns=["c1"], filter="eventTime BETWEEN 0 AND 499")):
        import jax.numpy as jnp

        return {k: (v * jnp.float32(2.0) if v.dtype.kind == "f" else v)
                for k, v in data.items()}

    ws = make_workspace(tmp_path)
    r1 = ws.run(p)
    r2 = ws.run(p)
    assert r2.rows_to_user_fns == 0
    assert_outputs_bitwise_equal(r1, r2)


def test_rowwise_fn_creating_rows_rejected(tmp_path):
    p = Project("badrows")

    @model(project=p, incremental="rowwise")
    def doubler(data=Model("ns.raw", columns=["c1"], filter="eventTime < 100")):
        c = data.column("c1")
        return {"c1": np.concatenate([c, c])}

    ws = make_workspace(tmp_path)
    with pytest.raises(ValueError, match="must not\\s+create rows"):
        ws.run(p)


def test_rowwise_dropping_fn_must_return_sort_key(tmp_path):
    p = Project("baddrop")

    @model(project=p, incremental="rowwise")
    def dropper(data=Model("ns.raw", columns=["c1"], filter="eventTime < 100")):
        c = data.column("c1")
        return {"c1": c[c > 0]}  # drops rows, loses the key

    ws = make_workspace(tmp_path)
    with pytest.raises(ValueError, match="sort key"):
        ws.run(p)


def test_none_mode_unaffected_and_default(tmp_path):
    """Existing projects (no contract declared) keep full-recompute
    semantics: the fn sees exactly its declared columns, every run."""
    p = Project("plain")
    seen_cols = []

    @model(project=p)
    def agg(data=Model("ns.raw", columns=["c1"], filter="eventTime < 500")):
        seen_cols.append(data.column_names)
        return {"mean": np.array([data.column("c1").mean()])}

    ws = make_workspace(tmp_path)
    ws.run(p)
    ws.run(p)
    assert seen_cols == [("c1",), ("c1",)]  # no surprise key column
    res = ws.run(p)
    assert res.rows_to_user_fns == 500  # recomputed every run


def test_materialized_rowwise_model_keeps_sort_key(tmp_path):
    """Rowwise outputs are canonicalized to sorted column order, so the
    materializer must take the sort key from the plan, not from 'first
    column' (which would be 'c1' here and mis-sort the published table)."""
    p = Project("matinc")

    @model(project=p, incremental="rowwise", materialize=True)
    def published(
        data=Model("ns.raw", columns=["c1"], filter="eventTime BETWEEN 0 AND 99")
    ):
        return {n: data.column(n) for n in data.column_names}

    ws = make_workspace(tmp_path)
    ws.run(p)
    meta = ws.catalog.table("models.published")
    assert meta.sort_key == "eventTime"


def test_jax_runtime_sort_key_stays_int64(tmp_path):
    """jax x32 truncates int64 to int32 in flight; the engine must restore
    the exact input key (position-aligned), since the key addresses the
    cache — keys >= 2**31 would otherwise wrap and corrupt windowing."""
    p = Project("bigkeys")
    BASE = 2**31  # beyond int32

    @model(project=p, incremental="rowwise")
    @runtime("jax")
    def jmap(data=Model("ns.big", columns=["c1"], filter=f"eventTime >= {BASE}")):
        import jax.numpy as jnp

        return {k: (v * jnp.float32(2.0) if v.dtype.kind == "f" else v)
                for k, v in data.items()}

    ws = Workspace(str(tmp_path / "lake"), rows_per_fragment=128)
    ws.catalog.create_table("ns", "big", {"eventTime": "<i8", "c1": "<f8"}, "eventTime")
    rng = np.random.default_rng(0)
    ws.catalog.append(
        "ns.big",
        Table({
            "eventTime": np.arange(BASE, BASE + 500, dtype=np.int64),
            "c1": rng.standard_normal(500),
        }),
    )
    r1 = ws.run(p)
    keys = r1.outputs["jmap"].column("eventTime")
    assert keys.dtype == np.int64
    np.testing.assert_array_equal(keys, np.arange(BASE, BASE + 500, dtype=np.int64))
    r2 = ws.run(p)  # warm: the restored keys must address the cache exactly
    assert r2.rows_to_user_fns == 0
    assert_outputs_bitwise_equal(r1, r2)


def test_window_widened_beyond_data_has_empty_residual_rows(tmp_path):
    """A residual window holding zero rows (widening past the data's extent)
    must not crash and must stay correct once the rows later appear."""
    ws = make_workspace(tmp_path)  # keys [0, 1000)
    ws.run(feature_project(hi=999))
    res = ws.run(feature_project(hi=4999))  # residual (1000, 5000]: no rows
    assert res.node_stats["cleaned"]["fresh_rows"] == 0
    assert res.outputs["scaled"].num_rows == ws.run(feature_project(hi=999)).outputs["scaled"].num_rows

    # the empty residual was cached with pins; appending rows there must
    # invalidate it and recompute exactly the new rows
    ws.catalog.append("ns.raw", events_table(2000, 2100, seed=3))
    res2 = ws.run(feature_project(hi=4999))
    assert res2.node_stats["cleaned"]["fresh_rows"] == 100
    append = lambda c: c.append("ns.raw", events_table(2000, 2100, seed=3))
    assert_outputs_bitwise_equal(
        res2,
        run_cold(tmp_path, "cold-beyond", feature_project(hi=4999), mutations=[append]),
    )


def test_degenerate_empty_window_runs_fn_on_empty_input(tmp_path):
    p = Project("degenerate")

    @model(project=p, incremental="rowwise")
    def noop(data=Model("ns.raw", columns=["c1"], filter="eventTime BETWEEN 5 AND 1")):
        return {n: data.column(n) for n in data.column_names}

    ws = make_workspace(tmp_path)
    res = ws.run(p)
    out = res.outputs["noop"]
    assert out.num_rows == 0
    assert set(out.column_names) == {"c1", "eventTime"}


# ------------------------------------------------------- the node's host UNION
def _where(pairs):
    return " OR ".join(f"(eventTime >= {lo} AND eventTime < {hi})" for lo, hi in pairs)


def _union_workspace(root, tracer=None):
    """``ns.raw`` (unique keys), ``ns.rep`` (three rows per even key) and
    ``ns.even`` (one row per even key), all keyed by ``eventTime``."""
    ws = Workspace(root, rows_per_fragment=128, tracer=tracer)
    ws.catalog.create_table("ns", "raw", SCHEMA, "eventTime")
    ws.catalog.append("ns.raw", events_table(0, 1000))
    rng = np.random.default_rng(5)
    even = np.arange(0, 1000, 2, dtype=np.int64)
    ws.catalog.create_table("ns", "rep", {"eventTime": "<i8", "v": "<f8"}, "eventTime")
    ws.catalog.append(
        "ns.rep", Table({"eventTime": np.repeat(even, 3), "v": rng.standard_normal(1500)})
    )
    ws.catalog.create_table("ns", "even", {"eventTime": "<i8", "ry": "<f8"}, "eventTime")
    ws.catalog.append("ns.even", Table({"eventTime": even, "ry": rng.standard_normal(500)}))
    return ws


def _union_project(kind, pairs):
    """One incremental node over the window ``pairs``: a rowwise map, a
    rowwise function that drops rows, a keyed aggregation over repeated
    keys, or a multi-input rowwise join."""
    p = Project("union")
    where = _where(pairs)
    if kind in ("map", "drop"):

        @model(project=p, incremental="rowwise")
        @runtime("numpy")
        def node(data=Model("ns.raw", columns=["c1", "c3"], filter=where)):
            if kind == "drop":
                data = data.filter(data.column("c3") % 3 != 0)
            out = {n: data.column(n) for n in data.column_names}
            out["score"] = 2.0 * np.asarray(data.column("c1"))
            return out

    elif kind == "keyed":

        @model(project=p, incremental="keyed")
        @runtime("numpy")
        def node(data=Model("ns.rep", columns=["v"], filter=where)):
            keys, starts = np.unique(np.asarray(data.column("eventTime")), return_index=True)
            if keys.size == 0:
                return {"eventTime": keys, "total": np.zeros(0)}
            return {"eventTime": keys, "total": np.add.reduceat(data.column("v"), starts)}

    else:

        @model(project=p, incremental="rowwise")
        @runtime("numpy")
        def node(
            left=Model("ns.raw", columns=["c1"], filter=where),
            right=Model("ns.even", columns=["ry"], filter=where),
        ):
            common, li, ri = np.intersect1d(
                left.column("eventTime"), right.column("eventTime"), return_indices=True
            )
            return {
                "eventTime": common,
                "c1": np.asarray(left.column("c1"))[li],
                "ry": np.asarray(right.column("ry"))[ri],
            }

    return p


def _assert_bitwise(a, b):
    assert a.column_names == b.column_names
    for c in a.column_names:
        x, y = np.asarray(a.column(c)), np.asarray(b.column(c))
        assert x.dtype == y.dtype and x.tobytes() == y.tobytes(), c


_APPEND = lambda c: c.append("ns.raw", events_table(1000, 1100, seed=9))


@pytest.mark.parametrize(
    "kind,primes,final,mutations,runs",
    [
        # the fresh table's two residual intervals around one hit
        ("map", [[(300, 600)]], [(100, 900)], [], 3),
        ("map", [[(0, 500)]], [(250, 750)], [], 2),
        ("map", [[(0, 1100)]], [(0, 1100)], [_APPEND], 2),
        # a split rerun: two views of one element, no residual
        ("map", [[(0, 1000)]], [(0, 300), (600, 1000)], [], 2),
        ("map", [[(0, 300), (600, 1000)]], [(37, 901)], [], 3),
        ("map", [[(0, 300)], [(600, 1000)]], [(0, 1000)], [], 3),
        ("drop", [[(300, 600)]], [(100, 900)], [], 3),
        ("keyed", [[(300, 600)]], [(100, 900)], [], 3),
        ("multi", [[(300, 600)]], [(100, 900)], [], 3),
    ],
    ids=["widen", "shift", "append", "split", "narrow_off_grid", "two_elements",
         "drops_rows", "keyed", "multi_input"],
)
def test_union_concatenates_runs_in_window_order(
    tmp_path, monkeypatch, kind, primes, final, mutations, runs
):
    """A warm serve of hits and residual is the concatenation of their runs
    in window order: bit for bit the stable sort of the same chunks in any
    order, and a cold run on the same snapshot."""
    from repro.obs.trace import Tracer
    from repro.pipeline import executor

    tracer = Tracer()
    ws = _union_workspace(str(tmp_path / "warm"), tracer)
    for pairs in primes:
        ws.run(_union_project(kind, pairs))
    for m in mutations:
        m(ws.catalog)
    seen = []
    concat = executor._concat_runs
    monkeypatch.setattr(
        executor, "_concat_runs", lambda r: seen.append((list(r), concat(r))) or seen[-1][1]
    )
    tracer.clear()
    warm = ws.run(_union_project(kind, final))

    assert warm.node_stats["node"]["cached_rows"] > 0
    (got_runs, got), = seen
    assert [sp.attrs["runs"] for sp in tracer.find("node.union")] == [runs] == [len(got_runs)]
    assert [r[0] for r in got_runs] == sorted(r[0] for r in got_runs)
    _assert_bitwise(got, warm.outputs["node"])
    chunks = [t.slice(lo, hi) for _, t, _, lo, hi in got_runs]
    _assert_bitwise(got, ChunkedTable(chunks[::-1]).combine().sort_by("eventTime"))

    cold = _union_workspace(str(tmp_path / "cold"))
    for m in mutations:
        m(cold.catalog)
    ref = cold.run(_union_project(kind, final))
    _assert_bitwise(warm.outputs["node"], ref.outputs["node"])


def test_cold_split_window_serves_one_slice(tmp_path, monkeypatch):
    """A cold run over two intervals computes one residual whose runs hold
    adjacent rows: they join into one zero-copy slice, and no union span
    opens."""
    from repro.obs.trace import Tracer
    from repro.pipeline import executor

    tracer = Tracer()
    ws = _union_workspace(str(tmp_path / "lake"), tracer)
    seen = []
    concat = executor._concat_runs
    monkeypatch.setattr(
        executor, "_concat_runs", lambda r: seen.append(list(r)) or concat(r)
    )
    out = ws.run(_union_project("map", [(0, 300), (600, 1000)])).outputs["node"]
    (((_, fresh, _, lo, hi),),) = seen
    assert (lo, hi) == (0, fresh.num_rows) == (0, 700)
    assert np.shares_memory(out.column("score"), fresh.column("score"))
    keys = np.asarray(out.column("eventTime"))
    assert np.array_equal(keys, np.r_[0:300, 600:1000])
    assert tracer.find("node.union") == []


def test_warm_rerun_of_a_window_with_no_rows(tmp_path):
    """A window beyond the data caches an empty element; serving it again
    gives the same empty, schema-complete output."""
    ws = _union_workspace(str(tmp_path / "lake"))
    cold = ws.run(_union_project("map", [(5000, 6000)]))
    warm = ws.run(_union_project("map", [(5000, 6000)]))
    assert warm.rows_to_user_fns == 0
    assert warm.outputs["node"].num_rows == 0
    _assert_bitwise(warm.outputs["node"], cold.outputs["node"])


# -------------------------------------------------- acceptance: the ≥5× gate
def test_iteration_loop_meets_5x_acceptance(tmp_path):
    """The BENCH_3 iteration loop (same code CI smokes): warm bytes-from-store
    and rows-passed-to-user-fns must drop ≥5× vs per-iteration cold runs,
    with bitwise-equal outputs (asserted inside bench3.run)."""
    from benchmarks import bench3_incremental as b3

    result = b3.run(rows=4000)
    totals = result["totals"]
    assert totals["bytes_ratio"] >= 5.0, totals
    assert totals["rows_ratio"] >= 5.0, totals
