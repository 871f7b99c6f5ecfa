"""The readers of the service's waits, on a traced window of the tiny
service cell (``test_bench_service_cell.tiny``) driven on the CPU with the
program's tracer on and no profiler.  Each expected value is worked out
here again, the plain way, from the spans themselves."""

import tempfile

import pytest

from bench.lib import loader
from bench.tests.test_bench_metrics import self_s, spans_named
from bench.tests.test_bench_service_cell import SEED, tiny

METRICS = {
    "lock_wait_ms.service": ("store", "store.lock_wait"),
    "queue_wait_ms.service": ("scheduler", "service.queue_wait"),
    "claim_wait_ms.service": ("store", None),
}


@pytest.fixture(scope="module")
def window():
    from repro.obs.trace import Tracer, set_tracer

    config, traffic = tiny()
    tables = loader.part("traffic", config["data"])
    generator = loader.part("traffic", traffic["generator"])
    tracer = Tracer(max_roots=1 << 22)
    previous = set_tracer(tracer)
    try:
        with tempfile.TemporaryDirectory(prefix="bench_") as workdir:
            driver = generator.Driver(config, traffic, SEED, workdir, tables, lambda: 0.0)
            driver.setup(2.0)
            tracer.clear()
            records = driver.window(2.0)
            driver.close()
    finally:
        set_tracer(previous)
    return {"requests": records, "spans": tracer.to_dicts()}


def reduce(metric, bundle):
    return loader.part("metrics", metric).reduce(bundle)


def seconds(sp):
    return (sp["t1_ns"] - sp["t0_ns"]) / 1e9


def test_wait_readers(window):
    runs = sum(1 for r in window["requests"] if r["ok"])
    assert runs == len(window["requests"]) == 40
    for metric in ("lock_wait_ms.service", "queue_wait_ms.service"):
        spans = spans_named(window["spans"], {METRICS[metric][1]})
        assert len(spans) >= runs
        assert reduce(metric, window) == pytest.approx(sum(seconds(s) for s in spans) / runs * 1e3)
        assert reduce(metric, window) > 0
    waits = spans_named(window["spans"], {"scan.claim_wait", "node.claim_wait"})
    assert reduce("claim_wait_ms.service", window) == pytest.approx(
        sum(self_s(w) for w in waits) / runs * 1e3
    )


def test_lock_waits_name_their_store_and_tenant(window):
    waits = spans_named(window["spans"], {"store.lock_wait"})
    assert {w["attrs"]["store"] for w in waits} == {"scan", "model"}
    assert {w["attrs"]["tenant"] for w in waits} <= {f"tenant{i:02d}" for i in range(16)}


@pytest.mark.parametrize("metric", sorted(METRICS))
def test_reader_finds_nothing_without_spans(window, metric):
    """A run with the tracer off reads nothing, never 0."""
    assert reduce(metric, {"requests": window["requests"]}) is None
    assert reduce(metric, {"requests": window["requests"], "spans": []}) is None


@pytest.mark.parametrize("metric", sorted(METRICS))
def test_benchmark_lists_the_reader(metric):
    (entry,) = [m for m in loader.spec()["per_layer"] if m["name"] == metric]
    assert entry == {
        "name": metric,
        "unit": "ms",
        "better": "lower",
        "source": "program_span",
        "layer": METRICS[metric][0],
        "moves": "run_ms_p90",
        "workloads": ["tpch_service.open"],
    }
