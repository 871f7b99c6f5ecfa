"""Planning against cached elements, in ms per run: self time of the
``scan.plan`` and ``node.plan`` spans (core/planner.py, pipeline/executor.py)."""

from bench.lib.spans import self_seconds


def reduce(bundle):
    runs = sum(1 for r in bundle["requests"] if r["ok"])
    if not runs or "spans" not in bundle:
        return None
    return self_seconds(bundle["spans"], ("scan.plan", "node.plan")) / runs * 1e3
