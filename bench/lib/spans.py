"""Self time of the program's spans, from a dump of its span trees.

The dump is what ``repro.obs.Tracer.to_dicts()`` returns: nested
``{"name", "t0_ns", "t1_ns", "tid", "attrs", "children"}``.  A span's self
time is its duration less the part its children cover (children of one span
run one after another on its thread).
"""

from __future__ import annotations

from typing import Dict, Iterable, Iterator, List, Tuple


def walk(spans: Iterable[Dict], depth: int = 0) -> Iterator[Tuple[Dict, int]]:
    for sp in spans:
        yield sp, depth
        yield from walk(sp.get("children", ()), depth + 1)


def self_ns(sp: Dict) -> int:
    return (sp["t1_ns"] - sp["t0_ns"]) - sum(
        c["t1_ns"] - c["t0_ns"] for c in sp.get("children", ())
    )


def self_seconds(spans: Iterable[Dict], names: Iterable[str]) -> float:
    """Total self time, in seconds, of every span with one of ``names``."""
    wanted = set(names)
    return sum(self_ns(sp) for sp, _ in walk(spans) if sp["name"] in wanted) / 1e9


def durations(spans: Iterable[Dict], name: str) -> List[float]:
    """Seconds of every span called ``name``."""
    return [(sp["t1_ns"] - sp["t0_ns"]) / 1e9 for sp, _ in walk(spans) if sp["name"] == name]


def flat(spans: Iterable[Dict]) -> List[Tuple[str, int, int, int]]:
    """``(name, t0_ns, t1_ns, depth)`` of every span."""
    return [(sp["name"], sp["t0_ns"], sp["t1_ns"], d) for sp, d in walk(spans)]
