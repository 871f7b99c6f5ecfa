"""90th percentile wall time, in ms, of every ``Workspace.run`` in the
window: whole cycles of the edit script, so the same runs on every side."""

from bench.lib.stats import percentile


def reduce(bundle):
    p = percentile([r["latency_s"] for r in bundle["requests"] if r["ok"]], 90)
    return None if p is None else p * 1e3
