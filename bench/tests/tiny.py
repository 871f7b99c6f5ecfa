"""A tiny size of the edit cell, for runs of the whole harness on the CPU:
a month of 31,720 trips in fragments of 2,048 rows, its last one short."""

from bench.lib import loader

SEED = 2**31 + 11
CONFIG = "nyc_yellow_2023_01"


def events():
    bench = loader.spec()
    config = dict(loader.config(bench, CONFIG), rows=15 * 2048 + 1000, rows_per_fragment=2048)
    traffic = dict(
        loader.traffic("edit_device"),
        windows={
            "base": [[-12, 0]],
            "widen": [[-15, 0]],
            "narrow": [[-6, 0]],
            "shift": [[-13, -1]],
            "split": [[-15, -11], [-6, 0]],
        },
    )
    return config, traffic
