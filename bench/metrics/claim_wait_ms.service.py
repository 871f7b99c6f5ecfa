"""Waiting on another run's in-flight residual (coalescing), in ms per
request: self time of the ``scan.claim_wait`` and ``node.claim_wait`` spans
(core/planner.py, pipeline/executor.py).  0 in a traced window where no run
waited; nothing without spans."""

from bench.lib.spans import self_seconds


def reduce(bundle):
    runs = sum(1 for r in bundle["requests"] if r["ok"])
    if not runs or not bundle.get("spans"):
        return None
    return self_seconds(bundle["spans"], ("scan.claim_wait", "node.claim_wait")) / runs * 1e3
