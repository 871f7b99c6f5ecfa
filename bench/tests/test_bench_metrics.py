"""Every reducer, checked on a small recorded trace committed beside this
file: ``data/events_trace.json`` holds the spans, counters and device trace
of a traced 20-second run of the edit loop on a TPU v5e, over a
16,777,216-row events table (``bench/run.py --trace 1 --dump``).  The
reducers read only those records, whatever table made them.  Each expected
value is worked out here again, the plain way."""

import json
import os

import numpy as np
import pytest

from bench.lib import loader, profile

DATA = os.path.join(os.path.dirname(__file__), "data")


def bundle(name):
    with open(os.path.join(DATA, name)) as f:
        return json.load(f)


def reduce(metric, b):
    return loader.part("metrics", metric).reduce(b)


def spans_named(spans, names):
    out = []
    stack = list(spans)
    while stack:
        sp = stack.pop()
        stack.extend(sp.get("children", []))
        if sp["name"] in names:
            out.append(sp)
    return out


def self_s(sp):
    return (sp["t1_ns"] - sp["t0_ns"] - sum(c["t1_ns"] - c["t0_ns"] for c in sp.get("children", []))) / 1e9


def busy_s(ops, lo, hi):
    edges = sorted((max(t, lo), min(t + d, hi)) for _, t, d, _ in ops if t + d > lo and t < hi)
    total, end = 0, lo
    for a, b in edges:
        if b > end:
            total += b - max(a, end)
            end = b
    return total / 1e9


@pytest.fixture(scope="module")
def events():
    return bundle("events_trace.json")


def test_latency_percentiles(events):
    lat = sorted(r["latency_s"] for r in events["requests"] if r["ok"])
    n = len(lat)
    assert reduce("run_ms_p50", events) == pytest.approx(1e3 * (lat[(n - 1) // 2] + lat[n // 2]) / 2)
    pos = 0.9 * (n - 1)
    i = int(pos)
    assert reduce("run_ms_p90", events) == pytest.approx(1e3 * (lat[i] + (pos - i) * (lat[i + 1] - lat[i])))
    assert reduce("setup_s", events) == events["setup_s"]


def test_span_self_times(events):
    runs = sum(r["ok"] for r in events["requests"])
    plan = sum(self_s(s) for s in spans_named(events["spans"], {"scan.plan", "node.plan"}))
    union = sum(self_s(s) for s in spans_named(events["spans"], {"scan.union", "node.union"}))
    assert reduce("plan_ms.run", events) == pytest.approx(plan / runs * 1e3)
    assert reduce("union_host_ms.run", events) == pytest.approx(union / runs * 1e3)
    assert 0 < union / runs < max(r["latency_s"] for r in events["requests"])


def test_counters_per_run(events):
    ok = [r for r in events["requests"] if r["ok"]]
    assert reduce("store_mb_per_run.run", events) == pytest.approx(
        np.mean([r["counters"]["bytes_from_store"] for r in ok]) / 1e6
    )
    assert reduce("h2d_mb_per_run.run", events) == pytest.approx(
        np.mean([r["counters"]["bytes_h2d"] for r in ok]) / 1e6
    )
    assert reduce("compiles_in_window.run", events) == events["compiles"] == 0


def test_device_trace(events):
    p = events["profile"]
    (lo, dur), = [(t, d) for n, t, d in p["marks"] if n == "bench.window"]
    busy = busy_s(p["ops"], lo, lo + dur)
    assert reduce("device_idle_share.run", events) == pytest.approx(100 * (1 - busy / (dur / 1e9)))
    per_op = {}
    for n, t, d, _ in p["ops"]:
        if t + d > lo and t < lo + dur:
            per_op[n] = per_op.get(n, 0) + min(t + d, lo + dur) - max(t, lo)
    top = profile.top_ops(p, k=3)
    assert [n for n, _ in top] == sorted(per_op, key=per_op.get, reverse=True)[:3]
    assert [s for _, s in top] == pytest.approx([per_op[n] / 1e9 for n, _ in top])


def test_no_trace_no_device_metric(events):
    """A reader that finds nothing returns nothing, never 0."""
    bare = {k: v for k, v in events.items() if k not in ("profile", "spans")}
    for m in ("device_idle_share.run", "plan_ms.run", "union_host_ms.run"):
        assert reduce(m, bare) is None


def test_idle_gaps_are_labelled_by_the_innermost_open_span():
    prof = {
        "ops": [["a", 100, 50, 0], ["b", 120, 60, 0], ["c", 400, 100, 0]],
        "marks": [["bench.window", 0, 1000]],
    }
    assert profile.busy_seconds(prof) == pytest.approx(180e-9)
    spans = [("run", 0, 1000, 0), ("merge", 190, 390, 1)]
    gaps = dict(profile.idle_by_span(prof, spans, offset_ns=0))
    # [0,100) and [500,1000) under "run" alone; [180,400) inside "merge"
    assert gaps == pytest.approx({"run": 600e-9, "merge": 220e-9})
    assert profile.op_name("%fragment_gather.1 = s32[6144,128]{1,0} custom-call(...)") == "fragment_gather"
    assert profile.op_name("%copy-start = (s32[8]) copy-start(s32[8] %a)") == "copy-start"
