"""Device time from the JAX profiler's trace.

``read`` turns an ``.xplane.pb`` into what the reductions need: every XLA
op event of each ``/device:TPU:<n>`` plane (its op name without the
instance suffix, start and duration in ns) and the host annotations the
harness wrote (``bench.window``), all on the trace's one timeline.  Device
planes and host lines were looked at by hand on a TPU v5e trace: ops sit on
the line "XLA Ops", named by their HLO text (``%fragment_gather.1 = ...
custom-call(...)``).
"""

from __future__ import annotations

import re
from typing import Dict, List, Optional, Sequence, Tuple

_OP = re.compile(r"^%?([A-Za-z_][\w\-]*?)(?:\.\d+)?(?:\s*=|$)")


def op_name(text: str) -> str:
    """``%fragment_gather.1 = s32[...] custom-call(...)`` -> ``fragment_gather``."""
    m = _OP.match(text.strip())
    return m.group(1) if m else text.split(" ", 1)[0]


def read(path: str) -> Dict:
    from jax.profiler import ProfileData

    data = ProfileData.from_file(path)
    ops: List[List] = []
    marks: List[List] = []
    devices = 0
    for plane in data.planes:
        if plane.name.startswith("/device:TPU:"):
            devices += 1
            index = int(plane.name.rsplit(":", 1)[1])
            for line in plane.lines:
                if line.name == "XLA Ops":
                    ops.extend(
                        [op_name(e.name), int(e.start_ns), int(e.duration_ns), index]
                        for e in line.events
                    )
        elif plane.name == "/host:CPU":
            for line in plane.lines:
                marks.extend(
                    [e.name, int(e.start_ns), int(e.duration_ns)]
                    for e in line.events
                    if e.name.startswith("bench.")
                )
    ops.sort(key=lambda o: o[1])
    return {"ops": ops, "marks": marks, "devices": devices}


def window(profile: Dict) -> Tuple[int, int]:
    """Start and end, in trace ns, of the measured window's annotation."""
    for name, t0, dur in profile["marks"]:
        if name == "bench.window":
            return t0, t0 + dur
    raise ValueError("the trace holds no bench.window annotation")


def busy_intervals(ops: Sequence[Sequence], lo: int, hi: int, device: int) -> List[Tuple[int, int]]:
    """The union of one device's op intervals, clipped to ``[lo, hi)``."""
    out: List[List[int]] = []
    for _name, t0, dur, dev in sorted((o for o in ops if o[3] == device), key=lambda o: o[1]):
        a, b = max(t0, lo), min(t0 + dur, hi)
        if b <= a:
            continue
        if out and a <= out[-1][1]:
            out[-1][1] = max(out[-1][1], b)
        else:
            out.append([a, b])
    return [(a, b) for a, b in out]


def busy_seconds(profile: Dict) -> Optional[float]:
    """Busy seconds in the window, averaged over the devices traced; None
    when no op ran."""
    lo, hi = window(profile)
    devices = sorted({o[3] for o in profile["ops"]})
    if not devices:
        return None
    total = sum(
        b - a for d in devices for a, b in busy_intervals(profile["ops"], lo, hi, d)
    )
    return total / len(devices) / 1e9


def top_ops(profile: Dict, k: int = 10) -> List[List]:
    """``[op name, device seconds]`` of the ``k`` ops that took most time."""
    lo, hi = window(profile)
    total: Dict[str, int] = {}
    for n, t0, dur, _ in profile["ops"]:
        if t0 + dur > lo and t0 < hi:
            total[n] = total.get(n, 0) + min(t0 + dur, hi) - max(t0, lo)
    ranked = sorted(total.items(), key=lambda kv: -kv[1])[:k]
    return [[n, ns / 1e9] for n, ns in ranked]


def idle_by_span(profile: Dict, spans: Sequence[Tuple[str, int, int, int]], offset_ns: int, k: int = 10) -> List[List]:
    """Idle device time in the window, by the innermost program span open
    at the middle of each gap (``spans`` on the host's perf-counter clock,
    ``offset_ns`` added to put them on the trace's); ``[span, seconds]`` of
    the ``k`` largest, "no span" where none was open."""
    lo, hi = window(profile)
    device = min((o[3] for o in profile["ops"]), default=0)
    busy = busy_intervals(profile["ops"], lo, hi, device)
    gaps, cursor = [], lo
    for a, b in busy:
        if a > cursor:
            gaps.append((cursor, a))
        cursor = max(cursor, b)
    if hi > cursor:
        gaps.append((cursor, hi))
    # one sweep over span starts, span ends and gap middles in time order
    events = []
    for i, (n, t0, t1, d) in enumerate(spans):
        events.append((t0 + offset_ns, 0, i))
        events.append((t1 + offset_ns, 1, i))
    for j, (a, b) in enumerate(gaps):
        events.append(((a + b) // 2, 2, j))
    events.sort()
    active: Dict[int, Tuple[int, int]] = {}
    total: Dict[str, int] = {}
    for t, kind, i in events:
        if kind == 0:
            active[i] = (spans[i][3], t)
        elif kind == 1:
            active.pop(i, None)
        else:
            a, b = gaps[i]
            inner = max(active, key=lambda s: active[s], default=None)
            label = spans[inner][0] if inner is not None else "no span"
            total[label] = total.get(label, 0) + (b - a)
    ranked = sorted(total.items(), key=lambda kv: -kv[1])[:k]
    return [[n, ns / 1e9] for n, ns in ranked]
