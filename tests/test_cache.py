"""Correctness tests for the differential cache (paper §III).

The central invariant: for ANY sequence of scans against ANY snapshot
history, a scan served through the differential cache returns exactly the
same multiset of rows as an uncached scan — while reading no more bytes from
object storage than the uncached path, and strictly fewer when windows
overlap.
"""

import numpy as np
import pytest
from hypothesis import given, settings, strategies as st

from repro.core.baselines import NoCache, ScanCache
from repro.core.cache import DifferentialCache, DifferentialStore
from repro.core.columnar import ChunkedTable, Table, concat_tables
from repro.core.intervals import IntervalSet
from repro.core.planner import ResultCachingExecutor, ScanExecutor
from repro.lake.catalog import Catalog
from repro.lake.s3sim import ObjectStore
from repro.obs import Tracer

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


@pytest.fixture()
def env(tmp_path):
    store = ObjectStore(str(tmp_path / "s3"))
    catalog = Catalog(store, rows_per_fragment=64)
    catalog.create_table("ns", "raw", SCHEMA, "eventTime")
    catalog.append("ns.raw", events_table(0, 1000))
    return store, catalog


def rows_of(chunked, cols):
    t = chunked.combine()
    if t.num_rows == 0:
        return set()
    return set(zip(*[t.column(c).tolist() for c in cols]))


def reference_rows(store, catalog, cols, window):
    ex = ScanExecutor(store, catalog, cache=NoCache())
    return rows_of(ex.scan("ns.raw", cols, window), cols)


# --------------------------------------------------------------- §III-A flow
def test_paper_section3a_workload(env):
    """Users A, B, A′ from §III-A — the motivating example, verbatim."""
    store, catalog = env
    ex = ScanExecutor(store, catalog, cache=DifferentialCache())

    # (1) user A: c1,c2,c3 over Jan (here keys [0, 310))
    before = store.stats.bytes_read
    ex.scan("ns.raw", ["c1", "c2", "c3"], IntervalSet.of((0, 310)))
    bytes_a = store.stats.bytes_read - before
    assert bytes_a > 0

    # (2) user B: c1,c3 over Jan..Feb ([0, 620)) — only Feb should be fetched
    before = store.stats.bytes_read
    out_b = ex.scan("ns.raw", ["c1", "c3"], IntervalSet.of((0, 620)))
    bytes_b = store.stats.bytes_read - before
    assert bytes_b > 0
    assert bytes_b < bytes_a  # differential: roughly the Feb half, 2 cols
    assert rows_of(out_b, ["c1", "c3"]) == reference_rows(store, catalog, ["c1", "c3"], IntervalSet.of((0, 620)))

    # (3) user A again: c2 only, one day ([0, 10)) — zero object-store reads
    before = store.stats.bytes_read
    out_a2 = ex.scan("ns.raw", ["c2"], IntervalSet.of((0, 10)))
    assert store.stats.bytes_read == before, "request #3 requires no scan (paper Fig. 4)"
    assert rows_of(out_a2, ["c2"]) == reference_rows(store, catalog, ["c2"], IntervalSet.of((0, 10)))


def test_exact_repeat_is_free(env):
    store, catalog = env
    ex = ScanExecutor(store, catalog, cache=DifferentialCache())
    w = IntervalSet.of((100, 300))
    ex.scan("ns.raw", ["c1"], w)
    before = store.stats.bytes_read
    out = ex.scan("ns.raw", ["c1"], w)
    assert store.stats.bytes_read == before
    assert rows_of(out, ["c1"]) == reference_rows(store, catalog, ["c1"], w)


def test_superset_projection_serves_subset(env):
    store, catalog = env
    ex = ScanExecutor(store, catalog, cache=DifferentialCache())
    ex.scan("ns.raw", ["c1", "c2", "c3"], IntervalSet.of((0, 200)))
    before = store.stats.bytes_read
    out = ex.scan("ns.raw", ["c3"], IntervalSet.of((50, 150)))
    assert store.stats.bytes_read == before
    assert rows_of(out, ["c3"]) == reference_rows(store, catalog, ["c3"], IntervalSet.of((50, 150)))


def test_subset_projection_does_not_serve_superset(env):
    store, catalog = env
    ex = ScanExecutor(store, catalog, cache=DifferentialCache())
    ex.scan("ns.raw", ["c1"], IntervalSet.of((0, 200)))
    before = store.stats.bytes_read
    out = ex.scan("ns.raw", ["c1", "c2"], IntervalSet.of((0, 200)))
    assert store.stats.bytes_read > before  # must re-fetch: c2 missing
    assert rows_of(out, ["c1", "c2"]) == reference_rows(store, catalog, ["c1", "c2"], IntervalSet.of((0, 200)))


def test_adjacent_windows_merge_into_one_element(env):
    store, catalog = env
    cache = DifferentialCache()
    ex = ScanExecutor(store, catalog, cache=cache)
    ex.scan("ns.raw", ["c1"], IntervalSet.of((0, 128)))
    ex.scan("ns.raw", ["c1"], IntervalSet.of((128, 256)))
    elems = cache.elements("ns.raw")
    assert len(elems) == 1  # merged (overlapping/adjacent combine, §III-B)
    assert elems[0].window.to_pairs() == ((0, 256),)


def test_disjoint_windows_covered_after_gap_fill(env):
    store, catalog = env
    ex = ScanExecutor(store, catalog, cache=DifferentialCache())
    ex.scan("ns.raw", ["c1"], IntervalSet.of((0, 100)))
    ex.scan("ns.raw", ["c1"], IntervalSet.of((400, 500)))
    # spanning scan: only the gap [100,400) should be fetched
    before = store.stats.bytes_read
    out = ex.scan("ns.raw", ["c1"], IntervalSet.of((0, 500)))
    gap_only = store.stats.bytes_read - before
    assert gap_only > 0
    ex2 = ScanExecutor(store, catalog, cache=NoCache())
    before = store.stats.bytes_read
    ex2.scan("ns.raw", ["c1"], IntervalSet.of((0, 500)))
    full = store.stats.bytes_read - before
    assert gap_only < full
    assert rows_of(out, ["c1"]) == reference_rows(store, catalog, ["c1"], IntervalSet.of((0, 500)))


def test_cache_serves_views_zero_copy(env):
    store, catalog = env
    cache = DifferentialCache()
    ex = ScanExecutor(store, catalog, cache=cache)
    ex.scan("ns.raw", ["c1"], IntervalSet.of((0, 320)))
    out = ex.scan("ns.raw", ["c1"], IntervalSet.of((10, 300)))
    elem = cache.elements("ns.raw")[0]
    assert any(
        np.shares_memory(chunk.column("c1"), elem.data.column("c1"))
        for chunk in out.chunks
    ), "cache hits must be zero-copy views over the element buffer"


def test_invalidation_on_overwrite(env):
    store, catalog = env
    ex = ScanExecutor(store, catalog, cache=DifferentialCache())
    ex.scan("ns.raw", ["c1"], IntervalSet.of((0, 1000)))
    # mutate part of the table: delete keys [0, 128)
    catalog.overwrite_range("ns.raw", 0, 128)
    out = ex.scan("ns.raw", ["c1"], IntervalSet.of((0, 1000)))
    assert rows_of(out, ["c1"]) == reference_rows(store, catalog, ["c1"], IntervalSet.of((0, 1000)))


def test_differential_invalidation_is_partial(env):
    """Beyond-paper: untouched windows survive a mutation elsewhere."""
    store, catalog = env
    ex = ScanExecutor(store, catalog, cache=DifferentialCache())
    ex.scan("ns.raw", ["c1"], IntervalSet.of((0, 1000)))
    catalog.overwrite_range("ns.raw", 900, 1000)  # touch only the tail
    before = store.stats.bytes_read
    out = ex.scan("ns.raw", ["c1"], IntervalSet.of((0, 256)))
    assert store.stats.bytes_read == before, "untouched window must stay cached"
    assert rows_of(out, ["c1"]) == reference_rows(store, catalog, ["c1"], IntervalSet.of((0, 256)))


def test_append_extends_validity(env):
    store, catalog = env
    ex = ScanExecutor(store, catalog, cache=DifferentialCache())
    ex.scan("ns.raw", ["c1"], IntervalSet.of((0, 500)))
    catalog.append("ns.raw", events_table(1000, 1200))
    before = store.stats.bytes_read
    out = ex.scan("ns.raw", ["c1"], IntervalSet.of((0, 500)))
    assert store.stats.bytes_read == before  # append outside window: still valid
    assert rows_of(out, ["c1"]) == reference_rows(store, catalog, ["c1"], IntervalSet.of((0, 500)))


def test_eviction_under_budget(env):
    store, catalog = env
    cache = DifferentialCache(max_bytes=20_000)
    ex = ScanExecutor(store, catalog, cache=cache)
    for lo in range(0, 1000, 100):
        ex.scan("ns.raw", ["c1", "c2", "c3"], IntervalSet.of((lo, lo + 100)))
    assert cache.nbytes <= 20_000
    assert cache.evictions > 0
    # correctness survives eviction
    out = ex.scan("ns.raw", ["c1"], IntervalSet.of((0, 1000)))
    assert rows_of(out, ["c1"]) == reference_rows(store, catalog, ["c1"], IntervalSet.of((0, 1000)))


def test_warm_vs_cold_residual_is_delta_only(env):
    """Paper §III / Table 2 behavior: a repeated scan's residual fetch is 0
    bytes, and widening the time window fetches exactly the delta — the same
    bytes an uncached executor reads for the delta window alone."""
    store, catalog = env
    ex = ScanExecutor(store, catalog, cache=DifferentialCache())
    cols = ["c1", "c2"]
    w = IntervalSet.of((0, 256))

    # cold scan populates the cache
    ex.scan("ns.raw", cols, w)
    assert ex.reports[-1].bytes_from_store > 0

    # warm repeat: the plan's residual fetch is 0 bytes, all from cache
    before = store.stats.bytes_read
    ex.scan("ns.raw", cols, w)
    warm = ex.reports[-1]
    assert store.stats.bytes_read == before
    assert warm.bytes_from_store == 0 and warm.store_requests == 0
    assert warm.fully_cached and warm.bytes_from_cache > 0

    # widen the window: fetched bytes == the delta only
    ex.scan("ns.raw", cols, IntervalSet.of((0, 512)))
    widened = ex.reports[-1]
    cold = ScanExecutor(store, catalog, cache=NoCache())
    cold.scan("ns.raw", cols, IntervalSet.of((256, 512)))
    delta_bytes = cold.reports[-1].bytes_from_store
    assert widened.bytes_from_store == delta_bytes > 0
    assert rows_of(
        ex.scan("ns.raw", cols, IntervalSet.of((0, 512))), cols
    ) == reference_rows(store, catalog, cols, IntervalSet.of((0, 512)))


def test_scan_cache_baseline_exact_match_only(env):
    store, catalog = env
    ex = ScanExecutor(store, catalog, cache=ScanCache())
    w = IntervalSet.of((0, 200))
    ex.scan("ns.raw", ["c1"], w)
    before = store.stats.bytes_read
    ex.scan("ns.raw", ["c1"], w)  # exact repeat: hit
    assert store.stats.bytes_read == before
    ex.scan("ns.raw", ["c1"], IntervalSet.of((0, 199)))  # overlap: miss
    assert store.stats.bytes_read > before


def test_result_cache_baseline(env):
    store, catalog = env
    ex = ResultCachingExecutor(store, catalog)
    w = IntervalSet.of((0, 200))
    ex.scan("ns.raw", ["c1"], w)
    before = store.stats.bytes_read
    ex.scan("ns.raw", ["c1"], w)
    assert store.stats.bytes_read == before
    assert ex.hits == 1


def test_predicate_post_filter(env):
    store, catalog = env
    ex = ScanExecutor(store, catalog, cache=DifferentialCache())
    pred = lambda t: t.column("c3") % 2 == 0
    out = ex.scan("ns.raw", ["c3"], IntervalSet.of((0, 100)), predicate=pred)
    vals = out.combine().column("c3")
    assert np.all(vals % 2 == 0)
    # predicate doesn't poison the cache: unfiltered scan still correct
    out2 = ex.scan("ns.raw", ["c3"], IntervalSet.of((0, 100)))
    assert rows_of(out2, ["c3"]) == reference_rows(store, catalog, ["c3"], IntervalSet.of((0, 100)))


# ------------------------------------------------- cross-snapshot merging
def test_merge_respects_snapshots_out_of_order_append(env):
    """Elements cached under different snapshots may only merge their
    *usable* windows: an element predating an out-of-order append must not
    donate its (now row-incomplete) window to a merged element whose pins
    include the new fragment — that made the missing rows look valid."""
    store, catalog = env
    cache = DifferentialCache()
    ex = ScanExecutor(store, catalog, cache=cache)
    ex.scan("ns.raw", ["c1"], IntervalSet.of((0, 128)))  # E1 @ snapshot 1

    # out-of-order append: NEW rows whose keys land inside E1's window
    catalog.append(
        "ns.raw",
        Table(
            {
                "eventTime": np.arange(50, 60, dtype=np.int64),
                "c1": np.arange(10, dtype=np.float64) + 5000.0,
                "c2": np.zeros(10),
                "c3": np.zeros(10, dtype=np.int64),
            }
        ),
    )
    # overlapping scan under snapshot 2: fetches the residual (which pins
    # the new fragment) and merges it with E1
    ex.scan("ns.raw", ["c1"], IntervalSet.of((32, 256)))

    got = rows_of(ex.scan("ns.raw", ["c1"], IntervalSet.of((0, 256))), ["c1"])
    want = reference_rows(store, catalog, ["c1"], IntervalSet.of((0, 256)))
    assert got == want, "merged element must include the appended rows"


def test_merge_after_overwrite_drops_stale_rows(env):
    """After an overwrite, merging an old element with a fresh one must not
    carry the old element's dropped-fragment rows into the merged data."""
    store, catalog = env
    cache = DifferentialCache()
    ex = ScanExecutor(store, catalog, cache=cache)
    ex.scan("ns.raw", ["c1"], IntervalSet.of((0, 128)))  # E1 @ snapshot 1

    catalog.overwrite_range(
        "ns.raw",
        0,
        64,
        Table(
            {
                "eventTime": np.arange(0, 64, dtype=np.int64),
                "c1": -(np.arange(64, dtype=np.float64) + 1000.0),
                "c2": np.zeros(64),
                "c3": np.zeros(64, dtype=np.int64),
            }
        ),
    )
    ex.scan("ns.raw", ["c1"], IntervalSet.of((0, 256)))  # residual + merge

    # every element must reproduce the reference rows over its FULL claimed
    # window — stale rows inside merged data fail this even when the serving
    # path happens to mask them
    cols = ["c1", "eventTime"]
    for e in cache.elements("ns.raw"):
        chunks = e.slice_window(e.window, cols)
        got = rows_of(ChunkedTable(chunks), cols) if chunks else set()
        want = reference_rows(store, catalog, cols, e.window)
        assert got == want
    got = rows_of(ex.scan("ns.raw", ["c1"], IntervalSet.of((0, 256))), ["c1"])
    assert got == reference_rows(store, catalog, ["c1"], IntervalSet.of((0, 256)))


# ------------------------------------------------ sort-free merge payloads
def _payload(pairs, seed, dup):
    """A key-sorted payload over ``pairs``: int64 key ``k`` (each key twice
    when ``dup``), and value columns of three dtypes, one NaN included."""
    keys = np.concatenate([np.arange(lo, hi, dtype=np.int64) for lo, hi in pairs])
    if dup:
        keys = np.repeat(keys, 2)
    rng = np.random.default_rng(seed)
    x = rng.standard_normal(keys.size)
    x[keys.size // 3] = np.nan
    return Table({
        "k": keys,
        "x": x,
        "y": rng.standard_normal(keys.size).astype(np.float32),
        "z": rng.integers(-50, 50, keys.size).astype(np.int32),
    })


@pytest.mark.parametrize(
    "a_pairs, b_pairs, a_bad, b_bad, dup",
    [
        pytest.param(((0, 64),), ((64, 128),), (), (), False, id="append"),
        pytest.param(((64, 128),), ((0, 64),), (), (), False, id="left"),
        pytest.param(((0, 32), (64, 96)), ((32, 64),), (), (), False, id="split"),
        pytest.param(((0, 128),), ((32, 64),), (), (), False, id="b_only_empty"),
        pytest.param(((0, 64),), ((48, 128),), ((16, 40),), (), False,
                     id="a_partly_invalidated"),
        pytest.param(((0, 32),), ((32, 64),), (), (), True, id="duplicate_keys"),
        pytest.param(((0, 64),), ((32, 96),), ((0, 64),), ((80, 96),), False,
                     id="single_partial_run"),
    ],
)
def test_merge_equals_concat_then_sort(a_pairs, b_pairs, a_bad, b_bad, dup):
    """A merge concatenates the sides' disjoint key-ordered runs in window
    order; the payload must equal, bitwise and column for column, the
    stable sort of the same parts, and own its buffers unless it is one
    side's whole payload."""
    tracer = Tracer()
    store = DifferentialStore(tracer=tracer)
    sides = {}

    def usable(e):
        bad = a_bad if e is sides.get("a") else b_bad
        return e.window.difference(IntervalSet.of(*bad))

    def insert(name, pairs, seed):
        sides[name] = store.insert_window(
            signature="s", table="t", sort_key="k", window=IntervalSet.of(*pairs),
            data=_payload(pairs, seed, dup), usable_fn=usable,
        )

    insert("a", a_pairs, 1)
    insert("b", b_pairs, 2)
    a, b = sides["a"], sides["b"]
    a_use = usable(a)
    b_only = usable(b).difference(a_use)
    parts = a.slice_window(a_use, a.columns) + b.slice_window(b_only, b.columns)
    want = concat_tables(parts).sort_by("k")

    (merged,) = store.elements("s")
    assert merged.window == a_use.union(usable(b))
    got = merged.data
    assert got.column_names == want.column_names == a.columns
    for c in want.column_names:
        assert got.column(c).dtype == want.column(c).dtype
        assert got.column(c).tobytes() == want.column(c).tobytes(), c
    (span,) = tracer.find("cache.merge")
    assert span.attrs["runs"] == len(parts)
    whole_side = len(parts) == 1 and parts[0].num_rows in (
        a.data.num_rows, b.data.num_rows)
    if not whole_side:
        for c in got.column_names:
            for side in (a, b):
                assert not np.shares_memory(got.column(c), side.data.column(c))


# --------------------------------------------------------- property testing
window_strategy = st.tuples(st.integers(0, 1000), st.integers(0, 1000)).map(
    lambda p: (min(p), max(p) + 1)
)
cols_strategy = st.sets(st.sampled_from(["c1", "c2", "c3"]), min_size=1).map(sorted)


@settings(max_examples=25, deadline=None)
@given(st.lists(st.tuples(cols_strategy, window_strategy), min_size=1, max_size=8))
def test_property_any_scan_sequence_is_correct(scans):
    """For any scan sequence: differential output == uncached output, and
    cumulative bytes read never exceed the uncached path's."""
    import tempfile

    with tempfile.TemporaryDirectory() as d:
        store = ObjectStore(d + "/s3")
        catalog = Catalog(store, rows_per_fragment=128)
        catalog.create_table("ns", "raw", SCHEMA, "eventTime")
        catalog.append("ns.raw", events_table(0, 1000))
        baseline_start = store.stats.bytes_read

        cached = ScanExecutor(store, catalog, cache=DifferentialCache())
        uncached = ScanExecutor(store, catalog, cache=NoCache())

        cached_bytes = 0
        uncached_bytes = 0
        for cols, (lo, hi) in scans:
            w = IntervalSet.of((lo, hi))
            b0 = store.stats.bytes_read
            got = rows_of(cached.scan("ns.raw", cols, w), cols)
            cached_bytes += store.stats.bytes_read - b0
            b0 = store.stats.bytes_read
            want = rows_of(uncached.scan("ns.raw", cols, w), cols)
            uncached_bytes += store.stats.bytes_read - b0
            assert got == want
        assert cached_bytes <= uncached_bytes


@settings(max_examples=15, deadline=None)
@given(
    st.lists(st.tuples(cols_strategy, window_strategy), min_size=1, max_size=5),
    st.lists(st.tuples(window_strategy, st.booleans()), min_size=1, max_size=3),
)
def test_property_correct_across_mutations(scans, mutations):
    """Scans interleaved with appends/overwrites stay correct (invalidation)."""
    import tempfile

    with tempfile.TemporaryDirectory() as d:
        store = ObjectStore(d + "/s3")
        catalog = Catalog(store, rows_per_fragment=128)
        catalog.create_table("ns", "raw", SCHEMA, "eventTime")
        catalog.append("ns.raw", events_table(0, 500))
        cached = ScanExecutor(store, catalog, cache=DifferentialCache())
        uncached = ScanExecutor(store, catalog, cache=NoCache())

        ops = [("scan", s) for s in scans] + [("mut", m) for m in mutations]
        # deterministic interleave
        ops.sort(key=lambda o: hash(str(o)) % 1000)
        next_key = 2000
        for kind, payload in ops:
            if kind == "scan":
                cols, (lo, hi) = payload
                w = IntervalSet.of((lo, hi))
                got = rows_of(cached.scan("ns.raw", cols, w), cols)
                want = rows_of(uncached.scan("ns.raw", cols, w), cols)
                assert got == want
            else:
                (lo, hi), is_append = payload
                if is_append:
                    catalog.append("ns.raw", events_table(next_key, next_key + 50))
                    next_key += 50
                else:
                    catalog.overwrite_range("ns.raw", lo, hi)
