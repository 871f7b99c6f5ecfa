"""Bytes of the elements the cache's merges build, in MB per run: the
``bytes`` of every ``cache.merge`` span (core/cache.py ``_merge_pair``)."""

from bench.lib.spans import walk


def reduce(bundle):
    runs = sum(1 for r in bundle["requests"] if r["ok"])
    merges = [sp for sp, _ in walk(bundle.get("spans", [])) if sp["name"] == "cache.merge"]
    if not runs or not merges:
        return None
    return sum(sp["attrs"].get("bytes", 0) for sp in merges) / runs / 1e6
