"""Host half of the cache's merges, in ms per run: self time of the
``cache.merge`` spans (core/cache.py ``_merge_pair``: usable windows,
slices, the concat-and-sort and the pins), which leaves out the device
replica under ``cache.merge.replicate``."""

from bench.lib.spans import durations, self_seconds


def reduce(bundle):
    runs = sum(1 for r in bundle["requests"] if r["ok"])
    if not runs or not durations(bundle.get("spans", []), "cache.merge"):
        return None
    return self_seconds(bundle["spans"], ("cache.merge",)) / runs * 1e3
