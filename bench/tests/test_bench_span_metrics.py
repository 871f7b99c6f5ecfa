"""The readers of the merge and device-transfer spans, on a traced window
of the tiny edit loop (``tiny.py``) driven on the CPU with the program's
tracer on and no profiler.  Each expected value is worked out here again,
the plain way, from the spans themselves."""

import tempfile

import pytest

from bench.lib import loader
from bench.tests import tiny
from bench.tests.test_bench_metrics import self_s, spans_named

METRICS = {
    "merge_host_ms.run": ("ms", "cache", "run_ms_p90"),
    "merge_mb_per_run.run": ("MB", "cache", "run_ms_p90"),
    "merge_replicate_ms.run": ("ms", "device tier", "run_ms_p90"),
    "device_wait_ms.run": ("ms", "device", "run_ms_p50"),
    "d2h_ms.run": ("ms", "device tier", "run_ms_p50"),
}


@pytest.fixture(scope="module")
def window():
    from repro.obs.trace import Tracer, set_tracer

    config, traffic = tiny.events()
    tables = loader.part("traffic", config["data"])
    generator = loader.part("traffic", traffic["generator"])
    tracer = Tracer(max_roots=1 << 22)
    previous = set_tracer(tracer)
    try:
        with tempfile.TemporaryDirectory(prefix="bench_") as workdir:
            driver = generator.Driver(config, traffic, tiny.SEED, workdir, tables, lambda: 0.0)
            driver.setup(0.25)
            tracer.clear()
            records = driver.window(0.25)
            driver.close()
    finally:
        set_tracer(previous)
    return {"requests": records, "spans": tracer.to_dicts()}


def reduce(metric, bundle):
    return loader.part("metrics", metric).reduce(bundle)


def named(spans, name):
    return spans_named(spans, {name})


def seconds(sp):
    return (sp["t1_ns"] - sp["t0_ns"]) / 1e9


def test_a_cycle_merges_and_recomputes(window):
    runs = window["requests"]
    assert len(runs) == 26 and all(r["ok"] for r in runs)
    assert len(named(window["spans"], "cache.merge")) == 12
    assert len(named(window["spans"], "node.residual")) == 7


def test_merge_readers(window):
    runs = len(window["requests"])
    merges = named(window["spans"], "cache.merge")
    host = sum(self_s(m) for m in merges)
    replicas = named(window["spans"], "cache.merge.replicate")
    assert len(replicas) == len(merges)
    assert reduce("merge_host_ms.run", window) == pytest.approx(host / runs * 1e3)
    assert reduce("merge_mb_per_run.run", window) == pytest.approx(
        sum(m["attrs"]["bytes"] for m in merges) / runs / 1e6
    )
    assert reduce("merge_replicate_ms.run", window) == pytest.approx(
        sum(seconds(r) for r in replicas) / runs * 1e3
    )
    for metric in ("merge_host_ms.run", "merge_mb_per_run.run", "merge_replicate_ms.run"):
        assert reduce(metric, window) > 0


def test_device_transfer_readers(window):
    runs = len(window["requests"])
    waits = named(window["spans"], "device.wait")
    copies = named(window["spans"], "device.d2h")
    assert len(waits) == len(copies) > 0
    assert reduce("device_wait_ms.run", window) == pytest.approx(
        sum(seconds(w) for w in waits) / runs * 1e3
    )
    assert reduce("d2h_ms.run", window) == pytest.approx(sum(seconds(c) for c in copies) / runs * 1e3)
    assert reduce("device_wait_ms.run", window) > 0 and reduce("d2h_ms.run", window) > 0
    # the copies are every byte the runs count coming back from the device
    assert sum(c["attrs"]["bytes"] for c in copies) == sum(
        r["counters"]["bytes_d2h"] for r in window["requests"]
    )


@pytest.mark.parametrize("metric", sorted(METRICS))
def test_reader_finds_nothing_without_spans(window, metric):
    """A program without these spans (a run with the tracer off, or one
    that lacks them) reads nothing, never 0."""
    assert reduce(metric, {"requests": window["requests"]}) is None
    assert reduce(metric, {"requests": window["requests"], "spans": []}) is None


@pytest.mark.parametrize("metric", sorted(METRICS))
def test_benchmark_lists_the_reader(metric):
    (entry,) = [m for m in loader.spec()["per_layer"] if m["name"] == metric]
    unit, layer, moves = METRICS[metric]
    assert entry == {
        "name": metric,
        "unit": unit,
        "better": "lower",
        "source": "program_span",
        "layer": layer,
        "moves": moves,
        "workloads": ["events_edit.device"],
    }
