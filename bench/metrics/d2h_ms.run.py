"""Copying a jax stage's outputs back to the host, in ms per run: duration
of the ``device.d2h`` spans (pipeline/executor.py ``_invoke``, one
``np.asarray`` per output column, after ``device.wait``)."""

from bench.lib.spans import durations


def reduce(bundle):
    runs = sum(1 for r in bundle["requests"] if r["ok"])
    spans = durations(bundle.get("spans", []), "device.d2h")
    if not runs or not spans:
        return None
    return sum(spans) / runs * 1e3
