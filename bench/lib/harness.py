"""One run of one cell: set-up, the measured window, the reference check and
the metrics, as one JSON-able result.

``run.py`` calls :func:`run_cell` once it has found the chips the cell asks
for; the tests call it on the CPU at a tiny size.  Nothing here knows a cell,
a configuration or a metric by name: ``BENCHMARK.json`` names them and
:mod:`bench.lib.loader` finds their files.
"""

from __future__ import annotations

import contextlib
import gc
import glob
import os
import tempfile
import threading
import time
from typing import Callable, Dict, Iterator, List, Optional, Tuple

from bench.lib import loader, profile
from bench.lib.compare import compare
from bench.lib.spans import flat

# the jax.monitoring event of one backend compile, which the window must
# not hold (a hit in the persistent compile cache records none)
COMPILE_EVENT = "/jax/core/compile/backend_compile_duration"


class CompileWatch:
    """Counts backend compiles, and the seconds they took, while it is
    installed."""

    def __init__(self):
        self.count = 0
        self._seconds = 0.0
        self._lock = threading.Lock()

    def __call__(self, event: str, duration: float, **_kw) -> None:
        if event == COMPILE_EVENT:
            with self._lock:
                self._seconds += duration
                self.count += 1

    def seconds(self) -> float:
        return self._seconds

    @contextlib.contextmanager
    def installed(self) -> Iterator["CompileWatch"]:
        from jax import monitoring

        monitoring.register_event_duration_secs_listener(self)
        try:
            yield self
        finally:
            monitoring.unregister_event_duration_listener(self)


def settle() -> None:
    """Wait for the device: programs run in launch order, so a last tiny one
    finishing means every earlier one has."""
    import jax.numpy as jnp

    (jnp.zeros((), jnp.float32) + 1).block_until_ready()


def memory_peak(devices) -> int:
    peaks = [(d.memory_stats() or {}).get("peak_bytes_in_use", 0) for d in devices]
    return int(max(peaks, default=0))


def device_peaks(kind: str) -> Optional[Dict]:
    return loader.load_json(os.path.join(loader.BENCH, "peaks.json")).get(kind)


def run_cell(
    cell_name: str,
    seed: int,
    seconds: float,
    trace: bool,
    *,
    control: bool = False,
    t_start: Optional[float] = None,
    config: Optional[Dict] = None,
    traffic: Optional[Dict] = None,
    dump: Optional[Callable[[Dict], None]] = None,
) -> Tuple[Dict, List[str], Dict[str, Dict[str, int]]]:
    """Run ``cell_name`` once; returns the result line's object, the report
    lines that go before it on standard error, and the readings of the
    comparison (``program``, and ``control`` where asked).  ``config`` and
    ``traffic`` replace the cell's files (the tests' tiny sizes);
    ``control`` puts the reference, computed in bfloat16, in the program's
    place for the comparison."""
    import jax

    from repro.obs.trace import Tracer, set_tracer

    t_start = time.perf_counter() if t_start is None else t_start
    bench = loader.spec()
    cell = loader.cell(bench, cell_name)
    config = config if config is not None else loader.config(bench, cell["config"])
    traffic = traffic if traffic is not None else loader.traffic(cell["traffic"])
    tables = loader.part("traffic", config["data"])
    if config["schema"] != tables.SCHEMA:
        raise ValueError(f"{config['name']}: schema differs from traffic/{config['data']}.py")
    generator = loader.part("traffic", traffic["generator"])
    reference = loader.part("reference", config["name"])
    group = "per_layer" if trace else "end_to_end"
    wanted = loader.metrics_for(bench, cell_name, group)
    devices = jax.devices()[: int(cell["chips"])]
    d0 = devices[0]
    peaks = device_peaks(d0.device_kind)
    if trace and d0.platform == "tpu" and peaks is None:
        raise KeyError(f"no peaks for device kind {d0.device_kind!r} in peaks.json")

    # the program's default tracer is on; only the traced run records spans
    tracer = Tracer(enabled=trace, max_roots=1 << 22)
    previous = set_tracer(tracer)
    watch = CompileWatch()
    bundle: Dict = {"peaks": peaks}
    try:
        with watch.installed(), tempfile.TemporaryDirectory(prefix="bench_") as workdir:
            driver = generator.Driver(
                config, traffic, seed, workdir, tables, watch.seconds
            )
            driver.setup(seconds)
            settle()
            bundle["setup_s"] = time.perf_counter() - t_start
            tracer.clear()
            compiles = watch.count
            prof_dir = os.path.join(workdir, "profile")
            with contextlib.ExitStack() as stack:
                if trace:
                    options = jax.profiler.ProfileOptions()
                    options.python_tracer_level = 0
                    options.host_tracer_level = 1
                    jax.profiler.start_trace(prof_dir, profiler_options=options)
                    stack.callback(jax.profiler.stop_trace)
                with jax.profiler.TraceAnnotation("bench.window"):
                    perf0 = time.perf_counter_ns()
                    records = driver.window(seconds)
                    settle()
                    bundle["window_s"] = (time.perf_counter_ns() - perf0) / 1e9
            bundle["compiles"] = watch.count - compiles
            bundle["requests"] = records
            if trace:
                bundle["spans"] = tracer.to_dicts()
                (xplane,) = glob.glob(os.path.join(prof_dir, "**", "*.xplane.pb"), recursive=True)
                bundle["profile"] = profile.read(xplane)
                if bundle["profile"]["ops"]:
                    # host spans onto the trace's timeline, by the window mark
                    bundle["profile"]["span_offset_ns"] = profile.window(bundle["profile"])[0] - perf0
            peak = memory_peak(devices)
            samples = driver.samples()
            rehearsed = getattr(driver, "rehearsed", None)
            setup_parts = dict(getattr(driver, "setup_parts", {}))
            driver.close()
            del driver
            gc.collect()
    finally:
        set_tracer(previous)

    # the reference runs once the program is gone
    ref = reference.Reference(config, seed, tables)
    readings = {"values": 0, "mismatched_values": 0, "mismatched_shapes": 0}
    control_readings = dict(readings)
    import ml_dtypes

    for described, outputs in samples:
        want = ref.outputs(described)
        for k, v in compare(outputs, want).items():
            readings[k] += v
        if control:
            lower = ref.outputs(described, float_dtype=ml_dtypes.bfloat16)
            for k, v in compare(lower, want).items():
                control_readings[k] += v
    attempted = len(records)
    failed = sum(1 for r in records if not r["ok"])
    judged = control_readings if control else readings
    checks = {
        "mismatched_values": {"value": judged["mismatched_values"], "limit": 0},
        "mismatched_shapes": {"value": judged["mismatched_shapes"], "limit": 0},
        "failed": {"value": failed, "limit": 0},
        "samples_missing": {"value": int(not samples), "limit": 0},
    }
    correct = attempted > 0 and all(c["value"] <= c["limit"] for c in checks.values())

    metrics: Dict[str, Dict] = {}
    for m in wanted:
        value = loader.part("metrics", m["name"]).reduce(bundle)
        if value is not None:
            metrics[m["name"]] = {"value": value, "unit": m["unit"]}

    result: Dict = {
        "correct": bool(correct),
        "attempted": attempted,
        "failed": failed,
        "metrics": metrics,
        "device": {
            "platform": d0.platform,
            "kind": d0.device_kind,
            "count": len(jax.devices()),
            "memory_peak_bytes": peak,
        },
    }
    if trace and bundle["profile"]["ops"]:
        busy = profile.busy_seconds(bundle["profile"])
        lo, hi = profile.window(bundle["profile"])
        result["device"]["busy_s"] = busy
        result["device"]["window_s"] = (hi - lo) / 1e9
        result["breakdown"] = {
            "device_ops": profile.top_ops(bundle["profile"]),
            "idle_gaps": profile.idle_by_span(
                bundle["profile"], flat(bundle["spans"]), bundle["profile"]["span_offset_ns"]
            ),
        }
    result["checks"] = checks

    report = [
        f"setup_s {bundle['setup_s']} window_s {bundle['window_s']} "
        f"compiles_in_window {bundle['compiles']} runs_rehearsed {rehearsed} "
        f"runs_in_window {len(records)}",
        f"memory_peak_bytes {peak}",
        "setup parts: " + " ".join(f"{k} {v}" for k, v in setup_parts.items()),
    ]
    report.append(
        f"reading program over {len(samples)} samples: "
        + " ".join(f"{k} {v}" for k, v in readings.items())
    )
    if control:
        report.append(
            "reading control (bfloat16 reference): "
            + " ".join(f"{k} {v}" for k, v in control_readings.items())
        )
    report.extend(f"check {n} {c['value']} limit {c['limit']}" for n, c in checks.items())
    if dump is not None:
        dump(bundle)
    return result, report, {"program": readings, "control": control_readings}
