"""Waiting in the service's admission queue, in ms per request: duration
of the ``service.queue_wait`` spans (service/scheduler.py, from ``submit`` to
a worker taking the run; one in-flight run per tenant, ``workers`` in all)."""

from bench.lib.spans import durations


def reduce(bundle):
    runs = sum(1 for r in bundle["requests"] if r["ok"])
    spans = durations(bundle.get("spans", []), "service.queue_wait")
    if not runs or not spans:
        return None
    return sum(spans) / runs * 1e3
