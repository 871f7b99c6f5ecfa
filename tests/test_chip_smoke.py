"""The chip smoke's phases, rehearsed on the CPU at 16,384 rows with the
Pallas interpreter: the same edit loop, bitwise checks, device-ledger gates
and two-tenant service phase that ``chip_smoke.py`` runs on a TPU."""

from __future__ import annotations

import os
import subprocess
import sys

import chip_smoke


def test_chip_smoke_phases_on_cpu(tmp_path):
    rows, frag, lines = 16384, 2048, []
    dev, ref = chip_smoke.load(str(tmp_path), rows, seed=3, frag=frag, interpret=True)
    records, want = chip_smoke.edit_loop(dev, ref, rows, 3, frag, log=lines.append)
    by_label = {r["label"]: r for r in records}
    assert list(by_label) == [label for label, _w, _m in chip_smoke.edits(rows, 3, frag)]
    assert by_label["split"]["gather_fast"] >= 1
    assert by_label["split_unaligned"]["gather_fallbacks"] >= 1
    assert by_label["rerun"]["bytes_h2d"] == 0
    assert by_label["rerun_appended"]["rows"] == rows + frag
    assert len(lines) == len(records)
    walls = chip_smoke.service_phase(
        str(tmp_path / "reference"), chip_smoke.edits(rows, 3, frag)[-1][1],
        want, frag, log=lines.append,
    )
    assert len(walls) == 2


def test_chip_smoke_refuses_cpu(tmp_path):
    """No TPU: non-zero exit, and no ok line."""
    out = subprocess.run(
        [sys.executable, chip_smoke.__file__],
        capture_output=True, text=True, timeout=300, cwd=tmp_path,
        env={**os.environ, "JAX_PLATFORMS": "cpu"},
    )
    assert out.returncode != 0
    assert '"ok"' not in out.stdout
