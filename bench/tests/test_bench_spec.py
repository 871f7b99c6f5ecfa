"""``BENCHMARK.json`` names parts that exist, and each is found by its name
alone."""

import os

from bench.lib import loader


def test_every_named_part_has_its_file():
    bench = loader.spec()
    for c in bench["configs"]:
        config = loader.config(bench, c["name"])
        assert config["name"] == c["name"]
        tables = loader.part("traffic", config["data"])
        assert config["schema"] == tables.SCHEMA
        assert hasattr(loader.part("reference", c["name"]), "Reference")
    for w in bench["workloads"]:
        traffic = loader.traffic(w["traffic"])
        assert hasattr(loader.part("traffic", traffic["generator"]), "Driver")
    for m in bench["end_to_end"] + bench["per_layer"]:
        assert callable(loader.part("metrics", m["name"]).reduce)
    for p in bench["paths"]:
        assert os.path.isdir(os.path.join(loader.ROOT, p))
