"""The benchmark's inputs come from ``--seed`` alone, and every seed gets
the same kinds and sizes of work."""

from collections import Counter

import numpy as np
import pytest

from bench.lib import loader
from bench.tests import tiny

SEEDS = (0, 2**31 + 7)
WINDOW_KINDS = ("widen", "narrow", "shift", "split")


def _parts(config=None):
    bench = loader.spec()
    config = config or loader.config(bench, tiny.CONFIG)
    traffic = loader.traffic("edit_device")
    return config, traffic, loader.part("traffic", "edit_loop"), loader.part("traffic", config["data"])


def _edits(seed, seconds, config=None):
    config, traffic, edit_loop, tables = _parts(config)
    return edit_loop.window_edits(config, traffic, seed, tables, seconds), config, traffic


def test_table_is_made_from_the_seed():
    config, _, _, tables = _parts(tiny.events()[0])
    a, b, c = (tables.columns(config, s) for s in (SEEDS[1], SEEDS[1], 3))
    assert list(a) == list(tables.SCHEMA) == list(config["schema"])
    for col, dtype in tables.SCHEMA.items():
        assert a[col].dtype == np.dtype(dtype)
        assert len(a[col]) == config["rows"]
        assert np.array_equal(a[col], b[col])
    assert any(not np.array_equal(a[col], c[col]) for col in a)


def test_keys_are_unique_and_bounds_fall_between_rows():
    """Over the month and appended fragments, pickup keys ascend strictly,
    and ``key_bound(r)`` lies above row ``r - 1`` and at most at row ``r``;
    every key stays inside int32."""
    config, _, _, tables = _parts(tiny.events()[0])
    key = np.concatenate(
        [tables.columns(config, SEEDS[1], part=p)[tables.SORT_KEY] for p in range(4)]
    )
    assert len(key) == config["rows"] + 3 * config["rows_per_fragment"]
    assert np.all(np.diff(key) > 0)
    for r in (1, 777, config["rows"] - 1, config["rows"], len(key) - 1):
        bound = tables.key_bound(config, r)
        assert key[r - 1] < bound <= key[r]
    full = loader.config(loader.spec(), tiny.CONFIG)
    last = full["rows"] + 1000 * full["rows_per_fragment"]
    assert tables.key_bound(full, last) < 2**31


def test_edit_schedule_is_made_from_the_seed():
    one, _, _ = _edits(SEEDS[1], 60)
    again, _, _ = _edits(SEEDS[1], 60)
    other, _, _ = _edits(SEEDS[0], 60)
    assert one == again
    assert one != other
    # the same work in the same order: only the gains differ
    assert [(e.label, e.rows, e.windows, e.columns, e.appends) for e in one] == [
        (e.label, e.rows, e.windows, e.columns, e.appends) for e in other
    ]


@pytest.mark.parametrize("seconds", [0.2, 20, 51])
def test_window_is_whole_cycles_of_the_script(seconds):
    edits, _, traffic = _edits(SEEDS[1], seconds)
    cycles = max(1, round(seconds / traffic["cycle_seconds"]))
    assert edits[0].kind == "cold"
    assert [e.label for e in edits[1:]] == traffic["script"] * cycles


def test_every_cycle_has_the_mix_of_the_edit_loop():
    """Each 13 runs hold 5 reruns, 5 window edits, an append, a feature edit
    and a code edit; half the window edits are off the grid; windows stay
    inside the table; on-grid windows start on a fragment, off-grid ones
    leave the 1,024-row tile grid."""
    edits, config, _ = _edits(SEEDS[1], 20)
    edit_loop = _parts()[2]
    frag = config["rows_per_fragment"]
    for c in range(len(edits) // 13):
        cycle = edits[1 + 13 * c : 1 + 13 * (c + 1)]
        kinds = Counter("window" if e.kind in WINDOW_KINDS else e.kind for e in cycle)
        assert kinds == {"rerun": 5, "window": 5, "append": 1, "feature": 1, "code": 1}
    window_edits = [e for e in edits[1:27] if e.kind in WINDOW_KINDS]
    assert sum(e.aligned for e in window_edits) == len(window_edits) / 2
    month = -(-config["rows"] // frag)
    starts = {edit_loop.fragment_start(config, i) for i in range(month + 40)}
    for e in edits:
        end = config["rows"] + e.appends * frag
        assert all(0 <= lo < hi <= end for lo, hi in e.rows)
        if e.kind in WINDOW_KINDS:
            if e.aligned:
                assert all(lo in starts for lo, _ in e.rows)
            else:
                assert all(lo not in starts and lo % 1024 for lo, _ in e.rows)


def test_samples_cover_every_kind_and_the_last_cycle():
    edits, _, traffic = _edits(SEEDS[1], 20)
    window = edits[1:]
    edit_loop = _parts()[2]
    keep = edit_loop.sample_positions(window, SEEDS[1])
    assert keep == edit_loop.sample_positions(window, SEEDS[1])
    assert {window[i].label for i in keep} == {e.label for e in window}
    last = len(window) - len(traffic["script"])
    assert {window[i].label for i in keep if i >= last} == {e.label for e in window}
