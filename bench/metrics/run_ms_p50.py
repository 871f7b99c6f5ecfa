"""Median wall time, in ms, of every ``Workspace.run`` in the window."""

from bench.lib.stats import percentile


def reduce(bundle):
    p = percentile([r["latency_s"] for r in bundle["requests"] if r["ok"]], 50)
    return None if p is None else p * 1e3
