"""The host's wait for the device queue after a jax stage, in ms per run:
duration of the ``device.wait`` spans (pipeline/executor.py ``_invoke``,
``jax.block_until_ready`` on the stage's outputs before they are copied)."""

from bench.lib.spans import durations


def reduce(bundle):
    runs = sum(1 for r in bundle["requests"] if r["ok"])
    spans = durations(bundle.get("spans", []), "device.wait")
    if not runs or not spans:
        return None
    return sum(spans) / runs * 1e3
