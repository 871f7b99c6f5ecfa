"""Host time of the hit-and-residual UNION, in ms per run: self time of the
``scan.union`` and ``node.union`` spans (the device half is only enqueued
there)."""

from bench.lib.spans import durations, self_seconds

NAMES = ("scan.union", "node.union")


def reduce(bundle):
    runs = sum(1 for r in bundle["requests"] if r["ok"])
    if not runs or not any(durations(bundle.get("spans", []), n) for n in NAMES):
        return None
    return self_seconds(bundle["spans"], NAMES) / runs * 1e3
