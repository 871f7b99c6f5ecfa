"""The whole harness on the CPU, at a tiny size of ``tpch_service.open``:
the service's answers agree with the plain reference bit for bit, the
bfloat16 control does not, and two planted faults are caught: a window
cached under other parameters served as if it were the request's, and one
Q1 line counted in the wrong group."""

import re

import numpy as np
import pytest

from bench.lib import loader
from bench.lib.harness import run_cell

CELL = "tpch_service.open"
SEED = 2**31 + 77


def tiny():
    """40,000 lines of 10,000 orders in fragments of 2,048 rows; 20 requests
    a second for a 2-second window after 16 warm-up requests."""
    bench = loader.spec()
    config = dict(loader.config(bench, "tpch_sf1"), rows=40000, orders=10000, parts=2000,
                  suppliers=100, rows_per_fragment=2048)
    traffic = dict(loader.traffic("service_open"), rate_per_s=20.0, warmup_requests=16)
    return config, traffic


def run(**kw):
    config, traffic = tiny()
    return run_cell(CELL, SEED, 2.0, False, config=config, traffic=traffic, **kw)


def test_program_agrees_with_reference_and_control_fails():
    result, report, readings = run(control=True)
    program, control = readings["program"], readings["control"]
    assert program["values"] > 0
    assert program["mismatched_values"] == 0 and program["mismatched_shapes"] == 0
    assert control["mismatched_values"] > 0 and control["mismatched_shapes"] == 0
    assert result["correct"] is False  # judged on the control
    assert result["attempted"] == 40 and result["failed"] == 0
    assert set(result["metrics"]) == {"run_ms_p90", "setup_s"}
    (crossed,) = re.findall(r"cross_tenant_samples (\d+)", "\n".join(report))
    assert int(crossed) >= 1


def test_window_cached_under_other_parameters_is_caught(monkeypatch):
    """Signatures that ignore the stage's closed-over constants: one Q6
    variant is served the windows another variant computed."""
    import repro.pipeline.physical as physical

    monkeypatch.setattr(physical, "code_fingerprint", lambda fn: fn.__qualname__)
    result, _report, readings = run()
    assert readings["program"]["mismatched_values"] > 0
    assert result["correct"] is False


def test_q1_line_in_the_wrong_group_is_caught(monkeypatch):
    """The grouping stage reads one line's return flag as another's."""
    from repro.core.columnar import Table

    service = loader.part("traffic", "tpch_service")
    inner = service.q1_project

    def moved(*args):
        p = inner(*args)
        fn = p["q1"].fn

        def wrong(lines, prices):
            cols = {c: np.array(lines.column(c)) for c in lines.column_names}
            flag = cols["l_returnflag"]
            flag[len(flag) // 2] = b"A" if flag[len(flag) // 2] != b"A" else b"R"
            return fn(lines=Table(cols), prices=prices)

        p["q1"].fn = wrong
        return p

    monkeypatch.setattr(service, "q1_project", moved)
    result, _report, readings = run()
    assert readings["program"]["mismatched_values"] > 0
    assert result["correct"] is False


def test_schedule_is_fixed_by_the_seed():
    service = loader.part("traffic", "tpch_service")
    _config, traffic = tiny()
    a = service.schedule(traffic, SEED, 2.0)
    b = service.schedule(traffic, SEED, 2.0)
    c = service.schedule(traffic, SEED + 1, 2.0)
    assert a == b and a != c
    warmup, window = a
    assert len(warmup) == 16 and len(window) == 40
    assert [r.at_s for r in window] == sorted(r.at_s for r in window) and window[0].at_s == 0
    labels = {r.label for r in window}
    assert labels == {"q1.fresh", "q1.repeat", "q6.fresh", "q6.repeat"}
    for r in window:
        if r.query == "q1":
            assert 60 <= r.params[0] <= 120
        else:
            year, cents, qty = r.params
            assert 1993 <= year <= 1997 and 2 <= cents <= 9 and qty in (24, 25)


def test_table_follows_dbgen_rules():
    config, _traffic = tiny()
    tables = loader.part("traffic", "tpch_lineitem")
    cols = tables.columns(config, SEED)
    assert {c: str(v.dtype) for c, v in cols.items()} == {
        c: str(np.dtype(t)) for c, t in tables.SCHEMA.items()
    }
    n = len(cols["l_orderkey"])
    assert n == config["rows"]
    ship, receipt = cols["l_shipdate"], cols["l_receiptdate"]
    assert np.all(np.diff(ship) >= 0)
    assert np.all((receipt - ship >= 1) & (receipt - ship <= 30))
    now = tables.day("1995-06-17")
    assert set(cols["l_returnflag"][receipt > now].tolist()) == {b"N"}
    assert set(cols["l_returnflag"][receipt <= now].tolist()) == {b"R", b"A"}
    assert np.array_equal(cols["l_linestatus"] == b"O", ship > now)
    assert np.all(cols["l_orderkey"] % 32 >= 1) and np.all(cols["l_orderkey"] % 32 <= 8)
    assert np.all((cols["l_linenumber"] >= 1) & (cols["l_linenumber"] <= 7))
    assert np.all(np.isin(np.rint(cols["l_discount"] * 100), np.arange(11)))
    retail = (90000 + (cols["l_partkey"] // 10) % 20001 + 100 * (cols["l_partkey"] % 1000)) / 100
    np.testing.assert_allclose(cols["l_extendedprice"], cols["l_quantity"] * retail, rtol=1e-12)
    assert max(len(c) for c in cols["l_comment"].tolist()) <= 43


@pytest.mark.parametrize("seed", [SEED, 3000000019])
def test_reference_meets_tpch_answer_precision(seed):
    """Float32 per-line arithmetic keeps the queries' meaning: money within
    $100 and averages within 1% of a float64 computation."""
    config, _traffic = tiny()
    tables = loader.part("traffic", "tpch_lineitem")
    ref_mod = loader.part("reference", "tpch_sf1")
    check = ref_mod.self_check(ref_mod.Reference(config, seed, tables))
    assert check["money_usd"] <= 100 and check["average_rel"] <= 0.01
    assert set(check["q1_groups"]) == {"AF", "NF", "NO", "RF"}
