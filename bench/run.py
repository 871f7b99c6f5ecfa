"""Run one cell of the benchmark once, on the chips of this machine.

    python3 bench/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

from the root of a checkout.  ``--trace 0`` measures the cell's end-to-end
metrics with the program's tracer off; ``--trace 1`` makes a run of its own
with spans and the JAX profiler on, and reports the per-layer metrics.  The last line of standard output is one JSON
object: ``correct``, ``attempted``, ``failed``, ``metrics``, ``device``
(``busy_s`` and ``window_s`` when traced), ``breakdown`` when traced, and
``checks`` last: each number compared with the reference beside its limit.
The same numbers are the last lines of standard error.

It exits non-zero, printing no result, where JAX finds no accelerator or
fewer chips than the cell asks for, and where anything of the run fails.
The compile cache is kept at ``bench/.jax_cache`` of the checkout, so only
the first run of a cell there compiles.

``--control 1`` puts the plain reference, computed in bfloat16, in the
program's place for the comparison: it must come out not correct.  The
benchmark's own runs never pass it.
"""

from __future__ import annotations

import argparse
import json
import os
import sys
import time

BENCH = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(BENCH)


def main(argv=None) -> int:
    t_start = time.perf_counter()
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    ap.add_argument("--control", type=int, choices=(0, 1), default=0)
    ap.add_argument("--dump", help="write the run's spans, counters and trace as JSON here")
    args = ap.parse_args(argv)

    sys.path[:0] = [os.path.join(ROOT, "src"), ROOT]
    from bench.lib import loader

    cell = loader.cell(loader.spec(ROOT), args.workload)
    import jax

    devices = jax.devices()
    if devices[0].platform == "cpu" or len(devices) < int(cell["chips"]):
        print(
            f"{args.workload} needs {cell['chips']} accelerator chip(s); JAX found "
            f"{len(devices)} {devices[0].platform} device(s)",
            file=sys.stderr,
        )
        return 2
    jax.config.update("jax_compilation_cache_dir", os.path.join(BENCH, ".jax_cache"))
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)

    from bench.lib.harness import run_cell

    def dump(bundle):
        with open(args.dump, "w") as f:
            json.dump(bundle, f)

    result, report, _readings = run_cell(
        args.workload,
        args.seed,
        args.seconds,
        bool(args.trace),
        control=bool(args.control),
        t_start=t_start,
        dump=dump if args.dump else None,
    )
    for line in report:
        print(line, file=sys.stderr)
    print(json.dumps(result))
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
