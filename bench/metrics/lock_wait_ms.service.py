"""Waiting for a shared store's lock, in ms per request: duration of the
``store.lock_wait`` spans (core/planner.py ``scan.plan`` / ``scan.insert``,
pipeline/executor.py ``node.plan`` / ``node.insert``: the wait before each
critical section, attrs ``store`` and ``tenant``)."""

from bench.lib.spans import durations


def reduce(bundle):
    runs = sum(1 for r in bundle["requests"] if r["ok"])
    spans = durations(bundle.get("spans", []), "store.lock_wait")
    if not runs or not spans:
        return None
    return sum(spans) / runs * 1e3
