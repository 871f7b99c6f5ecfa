"""Regression tests for ScanExecutor / ResultCachingExecutor edge cases:
sorted output when the sort key is projected away, and result-cache memo
keys surviving predicate garbage collection."""

import numpy as np
import pytest

from repro.core.baselines import NoCache
from repro.core.cache import DifferentialCache
from repro.core.columnar import Table
from repro.core.intervals import IntervalSet
from repro.core.planner import ResultCachingExecutor, ScanExecutor
from repro.lake.catalog import Catalog
from repro.lake.s3sim import ObjectStore

SCHEMA = {"eventTime": "<i8", "c1": "<f8", "c3": "<i8"}


@pytest.fixture()
def env(tmp_path):
    store = ObjectStore(str(tmp_path / "s3"))
    catalog = Catalog(store, rows_per_fragment=64)
    catalog.create_table("ns", "raw", SCHEMA, "eventTime")
    rng = np.random.default_rng(0)
    catalog.append(
        "ns.raw",
        Table(
            {
                "eventTime": np.arange(1000, dtype=np.int64),
                "c1": rng.standard_normal(1000),
                "c3": rng.integers(0, 100, 1000).astype(np.int64),
            }
        ),
    )
    return store, catalog


# ---------------------------------------------------------------- sorted_output
def test_sorted_output_without_sort_key_in_projection(env):
    """``sorted_output=True`` must hold even when ``eventTime`` is not among
    the projected columns: sort on the physical columns (which always carry
    the key), THEN project it away — silently returning cache-hit chunks in
    plan order is not an option."""
    store, catalog = env
    ex = ScanExecutor(store, catalog, cache=DifferentialCache())
    # prime the cache with a mid-table window so the later spanning scan
    # assembles out-of-order chunks (cache hit first, residual after)
    ex.scan("ns.raw", ["c1"], IntervalSet.of((500, 600)))
    out = ex.scan("ns.raw", ["c1"], IntervalSet.of((0, 1000)), sorted_output=True)
    assert out.column_names == ("c1",)  # key still projected away

    ref = ScanExecutor(store, catalog, cache=NoCache())
    want = (
        ref.scan("ns.raw", ["c1", "eventTime"], IntervalSet.of((0, 1000)))
        .combine()
        .sort_by("eventTime")
        .column("c1")
    )
    np.testing.assert_array_equal(out.combine().column("c1"), want)


def test_sorted_output_with_sort_key_still_sorted(env):
    store, catalog = env
    ex = ScanExecutor(store, catalog, cache=DifferentialCache())
    ex.scan("ns.raw", ["c1"], IntervalSet.of((300, 400)))
    out = ex.scan(
        "ns.raw", ["c1", "eventTime"], IntervalSet.of((0, 700)), sorted_output=True
    )
    keys = out.combine().column("eventTime")
    assert np.all(np.diff(keys) >= 0)


# ------------------------------------------------------------- result cache
def test_result_cache_predicate_id_reuse_no_false_hit(env):
    """The memo key must hold the predicate OBJECT: keying on ``id()`` gave
    false hits when CPython recycled a collected predicate's address for
    the next one."""
    store, catalog = env
    ex = ResultCachingExecutor(store, catalog)

    def run(thresh):
        # fresh predicate each call; the previous one is garbage by then, so
        # with an id() key CPython routinely hands the new closure the SAME
        # address -> false memo hit serving the previous threshold's rows
        def pred(t):
            return t.column("c3") >= thresh

        out = ex.scan("ns.raw", ["c3"], IntervalSet.of((0, 1000)), predicate=pred)
        return np.asarray(out.combine().column("c3"))

    ref = ScanExecutor(store, catalog, cache=NoCache())
    full = ref.scan("ns.raw", ["c3"], IntervalSet.of((0, 1000))).combine().column("c3")
    for thresh in (10, 50, 90, 50):
        got = run(thresh)
        want = np.sort(full[full >= thresh])
        np.testing.assert_array_equal(np.sort(got), want)


def test_cache_chunks_counts_only_hit_views(env):
    """A pure cache miss must report ``cache_chunks == 0``: the old code
    counted ``len(chunks)`` *after* appending the fresh residual, so a cold
    scan claimed one cache chunk.  Residual volume now lands in the separate
    ``residual_rows`` field."""
    store, catalog = env
    ex = ScanExecutor(store, catalog, cache=DifferentialCache())

    ex.scan("ns.raw", ["c1"], IntervalSet.of((0, 200)))  # cold: pure miss
    cold = ex.reports[-1]
    assert cold.cache_chunks == 0
    assert cold.residual_rows == 200

    ex.scan("ns.raw", ["c1"], IntervalSet.of((0, 200)))  # warm: pure hit
    warm = ex.reports[-1]
    assert warm.cache_chunks == 1
    assert warm.residual_rows == 0

    ex.scan("ns.raw", ["c1"], IntervalSet.of((0, 300)))  # partial
    part = ex.reports[-1]
    assert part.cache_chunks == 1
    assert part.residual_rows == 100

    # two hits: the greedy plan takes the larger element first, so the
    # chunks are the hits in plan order (not window order), then the
    # residual as one chunk
    ex.scan("ns.raw", ["c1"], IntervalSet.of((512, 896)))
    out = ex.scan("ns.raw", ["c1", "eventTime"], IntervalSet.of((0, 1000)))
    two = ex.reports[-1]
    assert two.cache_chunks == 2
    assert two.residual_rows == 1000 - 300 - 384
    keys = [c.column("eventTime").tolist() for c in out.chunks]
    assert keys[0] == list(range(512, 896))
    assert keys[1] == list(range(0, 300))
    assert keys[2] == list(range(300, 512)) + list(range(896, 1000))


def test_concurrent_scans_and_appends_stay_correct(env):
    """Threads doing overlapping scans while others append: every scan's
    result must equal an uncached scan of the snapshot it planned against.
    Regression for slicing hit elements OUTSIDE the executor lock — a
    concurrent insert could merge/evict the planned element between the plan
    and the slice."""
    import threading

    store, catalog = env
    ex = ScanExecutor(store, catalog, cache=DifferentialCache())
    errors = []
    stop = threading.Event()

    def scanner(seed):
        rng = np.random.default_rng(seed)
        try:
            for _ in range(30):
                lo = int(rng.integers(0, 900))
                hi = lo + int(rng.integers(1, 100))
                out = ex.scan("ns.raw", ["c1", "eventTime"], IntervalSet.of((lo, hi)))
                t = out.combine()
                snap = catalog.snapshot("ns.raw", ex.reports[-1].snapshot_id)
                ref = ScanExecutor(store, catalog, cache=NoCache())
                want = ref.scan(
                    "ns.raw", ["c1", "eventTime"], IntervalSet.of((lo, hi)),
                    snapshot_id=snap.snapshot_id,
                ).combine()
                got = sorted(zip(t.column("eventTime").tolist(), t.column("c1").tolist()))
                exp = sorted(zip(want.column("eventTime").tolist(), want.column("c1").tolist()))
                if got != exp:
                    errors.append((lo, hi, len(got), len(exp)))
        except Exception as e:  # pragma: no cover - failure reporting
            errors.append(repr(e))

    def appender():
        nxt = 1000
        while not stop.is_set():
            rng = np.random.default_rng(nxt)
            catalog.append(
                "ns.raw",
                Table(
                    {
                        "eventTime": np.arange(nxt, nxt + 40, dtype=np.int64),
                        "c1": rng.standard_normal(40),
                        "c3": rng.integers(0, 100, 40).astype(np.int64),
                    }
                ),
            )
            nxt += 40

    threads = [threading.Thread(target=scanner, args=(s,)) for s in range(4)]
    app = threading.Thread(target=appender)
    app.start()
    for t in threads:
        t.start()
    for t in threads:
        t.join()
    stop.set()
    app.join()
    assert not errors, errors[:5]


def test_result_cache_lru_byte_budget(env):
    """The result-cache baseline must hold its memo under ``max_bytes`` with
    LRU eviction — an unbounded memo skews Table-II comparisons."""
    store, catalog = env
    ex = ResultCachingExecutor(store, catalog, max_bytes=8_000)
    for lo in range(0, 1000, 100):
        ex.scan("ns.raw", ["c1", "c3"], IntervalSet.of((lo, lo + 100)))
    assert ex.nbytes <= 8_000
    assert ex.evictions > 0

    # LRU order: the most recently used entry survives, the eldest is gone
    before = store.stats.bytes_read
    ex.scan("ns.raw", ["c1", "c3"], IntervalSet.of((900, 1000)))  # still memoized
    assert store.stats.bytes_read == before
    ex.scan("ns.raw", ["c1", "c3"], IntervalSet.of((0, 100)))  # evicted: refetch
    assert store.stats.bytes_read > before
    # correctness after eviction
    got = ex.scan("ns.raw", ["c1"], IntervalSet.of((0, 1000))).combine()
    ref = ScanExecutor(store, catalog, cache=NoCache())
    want = ref.scan("ns.raw", ["c1"], IntervalSet.of((0, 1000))).combine()
    np.testing.assert_array_equal(
        np.sort(got.column("c1")), np.sort(want.column("c1"))
    )


def test_result_cache_oversize_result_does_not_wipe_memo(env):
    """A single result larger than the whole budget passes through unretained
    — it must not evict every hot entry on its way."""
    store, catalog = env
    ex = ResultCachingExecutor(store, catalog, max_bytes=4_000)
    ex.scan("ns.raw", ["c1"], IntervalSet.of((0, 100)))  # 800 B, hot
    hot_bytes = ex.nbytes
    assert 0 < hot_bytes <= 4_000
    ex.scan("ns.raw", ["c1", "c3", "eventTime"], IntervalSet.of((0, 1000)))  # 24 kB
    assert ex.nbytes == hot_bytes  # memo untouched, oversize not retained
    assert ex.evictions == 0
    before = store.stats.bytes_read
    ex.scan("ns.raw", ["c1"], IntervalSet.of((0, 100)))  # still memoized
    assert store.stats.bytes_read == before


def test_result_cache_same_predicate_object_still_hits(env):
    store, catalog = env
    ex = ResultCachingExecutor(store, catalog)
    pred = lambda t: t.column("c3") >= 50
    ex.scan("ns.raw", ["c3"], IntervalSet.of((0, 1000)), predicate=pred)
    before = store.stats.bytes_read
    ex.scan("ns.raw", ["c3"], IntervalSet.of((0, 1000)), predicate=pred)
    assert ex.hits == 1
    assert store.stats.bytes_read == before
