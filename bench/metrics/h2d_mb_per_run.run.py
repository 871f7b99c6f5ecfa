"""Bytes copied host to device (core/device.py pins, residual uploads and
jax inputs without a device copy), in MB per run."""


def reduce(bundle):
    runs = [r["counters"]["bytes_h2d"] for r in bundle["requests"] if r["ok"]]
    return sum(runs) / len(runs) / 1e6 if runs else None
