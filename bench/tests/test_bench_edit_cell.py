"""The whole harness on the CPU, at a tiny size of ``events_edit.device``:
the program agrees with the plain reference bit for bit, the bfloat16
control does not, and an answer altered where it is produced is caught."""

import numpy as np

from bench.lib.harness import run_cell
from bench.tests import tiny

CELL = "events_edit.device"


def test_program_agrees_with_reference_and_control_fails():
    config, traffic = tiny.events()
    result, report, readings = run_cell(
        CELL, tiny.SEED, 0.25, False, config=config, traffic=traffic, control=True
    )
    program, control = readings["program"], readings["control"]
    assert program["values"] > 0
    assert program["mismatched_values"] == 0 and program["mismatched_shapes"] == 0
    assert control["mismatched_values"] > 0
    assert result["correct"] is False  # judged on the control
    assert result["attempted"] > 0 and result["failed"] == 0
    assert list(result)[-1] == "checks"
    assert set(result["metrics"]) == {"run_ms_p50", "run_ms_p90", "setup_s"}
    assert report[-1].startswith("check ")


def test_answer_altered_in_the_device_union_is_caught(monkeypatch):
    """A value of the device-assembled UNION is changed where it is made;
    the full-window stage reads it, so a served answer is wrong."""
    import repro.core.device as device

    inner = device.device_union

    def altered(runs, columns, **kw):
        out = inner(runs, columns, **kw)
        for c in columns:
            if out[c].dtype.kind == "f" and out[c].shape[0]:
                out[c] = out[c].at[out[c].shape[0] // 2].add(np.float32(1.0))
        return out

    monkeypatch.setattr(device, "device_union", altered)
    config, traffic = tiny.events()
    result, _report, readings = run_cell(CELL, tiny.SEED, 0.25, False, config=config, traffic=traffic)
    assert readings["program"]["mismatched_values"] > 0
    assert result["correct"] is False


def test_half_of_the_union_left_out_is_caught(monkeypatch):
    """The device-assembled UNION keeps only the first half of its rows."""
    import repro.core.device as device

    inner = device.device_union

    def halved(runs, columns, **kw):
        return {c: v[: v.shape[0] // 2] for c, v in inner(runs, columns, **kw).items()}

    monkeypatch.setattr(device, "device_union", halved)
    config, traffic = tiny.events()
    result, _report, readings = run_cell(CELL, tiny.SEED, 0.25, False, config=config, traffic=traffic)
    assert readings["program"]["mismatched_shapes"] > 0
    assert result["correct"] is False


def test_append_that_leaves_the_table_unchanged_is_caught(monkeypatch):
    """After the month, every append hands back the snapshot it started
    from: the table's state is unchanged, and runs pinned to it miss the
    new trips."""
    import dataclasses

    from repro.lake.catalog import Catalog

    inner = Catalog.append
    calls = []

    def stale(self, full_name, data, *args, **kw):
        snap = inner(self, full_name, data, *args, **kw)
        calls.append(full_name)
        return snap if len(calls) == 1 else dataclasses.replace(snap, snapshot_id=snap.parent_id)

    monkeypatch.setattr(Catalog, "append", stale)
    config, traffic = tiny.events()
    result, _report, readings = run_cell(CELL, tiny.SEED, 0.25, False, config=config, traffic=traffic)
    assert len(calls) > 1
    assert readings["program"]["mismatched_shapes"] > 0
    assert result["correct"] is False
