"""Device replica of the cache's merges, in ms per run: duration of the
``cache.merge.replicate`` spans (core/device.py ``replicate_merge``, which
enqueues the device-to-device gather, and the parents' pins dropped)."""

from bench.lib.spans import durations


def reduce(bundle):
    runs = sum(1 for r in bundle["requests"] if r["ok"])
    spans = durations(bundle.get("spans", []), "cache.merge.replicate")
    if not runs or not spans:
        return None
    return sum(spans) / runs * 1e3
