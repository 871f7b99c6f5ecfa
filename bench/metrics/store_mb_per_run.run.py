"""Bytes read from the object store (lake/catalog.py, lake/fragments.py), in
MB per run."""


def reduce(bundle):
    runs = [r["counters"]["bytes_from_store"] for r in bundle["requests"] if r["ok"]]
    return sum(runs) / len(runs) / 1e6 if runs else None
