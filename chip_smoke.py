"""Chip smoke test: the differential cache's device-serving path on one TPU.

Drives the lakehouse through the entry points its users call —
``Workspace.run`` over the paper's edit loop, then ``PipelineService.submit``
from two tenants — at 16,777,216 rows (about one month of the paper's
NYC-taxi scenario).  The device tier pins cached columns in HBM and
assembles every hit∪residual UNION there, through the compiled
``fragment_gather`` kernel where the runs fall on whole tiles and XLA slices
where they do not.  Every output of every run is compared bitwise with a
workspace that has no device tier, over an identically seeded table.

Run it on a machine with one TPU, from the root of the repository:

    python chip_smoke.py [--seed 0]

It exits non-zero, and never prints the final ok line, when JAX finds no TPU,
when the repository is not beside it, when any output differs from the
reference, when the device path was not taken, or when any phase fails.  The
last line of a passing run is one JSON object naming the device.

The compile cache goes to ``$JAX_COMPILATION_CACHE_DIR`` when that is set,
and to ``<repo>/.jax_cache`` otherwise.
"""

from __future__ import annotations

import argparse
import functools
import json
import os
import sys
import tempfile
import time
from typing import Callable, Dict, List, Optional, Tuple

import numpy as np

REPO = os.path.dirname(os.path.abspath(__file__))
ROWS = 1 << 24
FRAG = 1 << 16  # rows per fragment, the catalog's default


def _check(ok: bool, msg: str) -> None:
    if not ok:
        raise RuntimeError(msg)


def place_compile_cache() -> str:
    """JAX reads ``$JAX_COMPILATION_CACHE_DIR`` itself; only without it is
    the cache put at the repository's fixed ``.jax_cache``."""
    import jax

    path = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if not path:
        path = os.path.join(REPO, ".jax_cache")
        jax.config.update("jax_compilation_cache_dir", path)
    return path


def edits(rows: int, seed: int, frag: int) -> List[Tuple[str, str, Optional[Callable]]]:
    """(label, window filter, catalog mutation) — the paper's edit loop over
    a ``rows``-row table, ``rows`` a multiple of ``frag``."""
    from benchmarks.bench8_device import _win
    from benchmarks.workloads import write_events

    a, b, c = rows // 3 // frag * frag, 2 * rows // 3 // frag * frag, rows
    return [
        ("cold", _win(0, b), None),
        ("rerun", _win(0, b), None),
        ("widen", _win(0, c), None),
        ("narrow", _win(0, a), None),
        # two hit intervals of one merged element, on fragment boundaries:
        # one multi-run fragment_gather per column
        ("split", f"{_win(0, a)} OR {_win(b, c)}", None),
        # the same shape off the tile grid: XLA slices, counted as fallbacks
        ("split_unaligned", f"{_win(5, a + 3)} OR {_win(b + 7, c - 1)}", None),
        (
            "append",
            _win(0, c + frag),
            lambda catalog: write_events(catalog, frag, seed=seed + 1, lo=c),
        ),
        ("rerun_appended", _win(0, c + frag), None),
    ]


def load(root: str, rows: int, seed: int, frag: int, interpret: Optional[bool] = None):
    """Two workspaces over identically seeded ``rows``-row event tables: one
    with a device tier, one without (the reference)."""
    from benchmarks.workloads import write_events
    from repro.core.device import DeviceTier
    from repro.pipeline.executor import Workspace

    dev = Workspace(
        os.path.join(root, "device"),
        rows_per_fragment=frag,
        device=DeviceTier(interpret=interpret),
    )
    ref = Workspace(os.path.join(root, "reference"), rows_per_fragment=frag)
    for ws in (dev, ref):
        write_events(ws.catalog, rows, seed=seed)
    return dev, ref


def same_outputs(label: str, got, want) -> None:
    _check(sorted(got.outputs) == sorted(want.outputs), f"{label}: models differ")
    for name, table in want.outputs.items():
        other = got.outputs[name]
        _check(other.column_names == table.column_names, f"{label}:{name}: columns")
        for col in table.column_names:
            _check(
                np.array_equal(
                    np.asarray(other.column(col)), np.asarray(table.column(col))
                ),
                f"{label}:{name}:{col} differs from the reference",
            )


def edit_loop(dev, ref, rows: int, seed: int, frag: int, log=print):
    """Run every edit on both workspaces, check the outputs bitwise and the
    device ledger, and log one line per edit.  Returns the per-edit records
    and the reference's last result."""
    from benchmarks.bench8_device import device_project

    records: List[Dict] = []
    want = None
    for label, where, mutate in edits(rows, seed, frag):
        if mutate is not None:
            mutate(dev.catalog)
            mutate(ref.catalog)
        t0 = time.perf_counter()
        got = dev.run(device_project(where))
        wall = time.perf_counter() - t0
        t0 = time.perf_counter()
        want = ref.run(device_project(where))
        ref_wall = time.perf_counter() - t0
        same_outputs(label, got, want)
        rec = {
            "label": label,
            "wall_s": wall,
            "ref_wall_s": ref_wall,
            "rows": int(got.outputs["score"].num_rows),
            "bytes_h2d": int(got.bytes_h2d),
            "device_hits": int(got.device_hits),
            "gather_fast": int(got.gather_fast),
            "gather_fallbacks": int(got.gather_fallbacks),
            "device_union_bytes": int(got.device_union_bytes),
        }
        log(
            "edit {label:<16} wall_s={wall_s} ref_wall_s={ref_wall_s} rows={rows} "
            "bytes_h2d={bytes_h2d} device_hits={device_hits} "
            "gather_fast={gather_fast} gather_fallbacks={gather_fallbacks} "
            "device_union_bytes={device_union_bytes}".format(**rec)
        )
        _check(rec["device_union_bytes"] > 0, f"{label}: no UNION was assembled on device")
        if label != "cold":
            _check(rec["device_hits"] > 0, f"{label}: nothing served from the device tier")
        if label == "split":
            _check(rec["gather_fast"] >= 1, "split: fragment_gather did not run")
        if label == "split_unaligned":
            _check(rec["gather_fallbacks"] >= 1, "split_unaligned: no fallback counted")
        records.append(rec)
    return records, want


def service_phase(root: str, where: str, want, frag: int, log=print):
    """Two tenants submit the same jax project to one two-worker service
    over the reference's lakehouse; both results must equal ``want``."""
    from benchmarks.bench8_device import device_project
    from repro.service import DONE, PipelineService

    walls = []
    with PipelineService(root, workers=2, rows_per_fragment=frag) as svc:
        handles = [
            svc.submit(f"tenant{i}", device_project(where)) for i in range(2)
        ]
        for h in handles:
            h.wait(timeout=600)
            if h.state != DONE:
                raise RuntimeError(f"service run of {h.tenant} failed") from h.error
            same_outputs(f"service:{h.tenant}", h.result, want)
            walls.append(h.wall_seconds)
            log(f"service {h.tenant} wall_s={h.wall_seconds} state={h.state}")
    return walls


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--seed", type=int, default=0)
    seed = ap.parse_args(argv).seed
    say = functools.partial(print, flush=True)

    import jax

    devices = jax.devices()
    d0 = devices[0]
    say(
        f"platform={d0.platform} kind={d0.device_kind} count={len(devices)} "
        f"jax={jax.__version__}"
    )
    if d0.platform != "tpu":
        print("no TPU: this smoke runs on the chip only", file=sys.stderr)
        return 1
    say(f"compile cache: {place_compile_cache()}")

    sys.path[:0] = [os.path.join(REPO, "src"), REPO]
    from repro.kernels.fragment_gather.ops import resolve_interpret

    with tempfile.TemporaryDirectory(prefix="chip_smoke_") as root:
        t0 = time.perf_counter()
        dev, ref = load(root, ROWS, seed, FRAG)
        say(f"load rows={ROWS} wall_s={time.perf_counter() - t0}")
        interpret = resolve_interpret(dev.device.interpret)
        say(f"fragment_gather mode: {'interpret' if interpret else 'compiled'}")
        _check(not interpret, "fragment_gather resolved to interpret mode")

        _records, want = edit_loop(dev, ref, ROWS, seed, FRAG, log=say)
        stats = dev.device.stats()
        peak = (d0.memory_stats() or {}).get("peak_bytes_in_use")
        say(
            f"tier resident_bytes={stats['device_nbytes']} "
            f"entries={stats['device_entries']} "
            f"bytes_replicated={stats['bytes_replicated']} "
            f"peak_bytes_in_use={peak}"
        )
        service_phase(
            os.path.join(root, "reference"),
            edits(ROWS, seed, FRAG)[-1][1],
            want,
            FRAG,
            log=say,
        )

    print(
        json.dumps(
            {
                "ok": True,
                "device": {
                    "platform": d0.platform,
                    "kind": d0.device_kind,
                    "count": len(devices),
                },
            }
        )
    )
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
