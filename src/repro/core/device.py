"""The device tier: hot cache elements pinned as jax device arrays.

The differential cache saves bytes *recomputed*, but every byte served still
transits host memory: residual assembly and the hit∪residual UNION run in
numpy, so a jax-runtime node pays a host→device copy for data the cache
already "had".  :class:`DeviceTier` closes that gap:

- **pinning**: a cache element's payload columns are uploaded once as jax
  device arrays (column-major — one 1-D array per ``(element, column)``,
  padded to :data:`ROW_BLOCK` rows, one ``(8, 128)`` tile of the column's
  lane-dense view).  Pins are keyed by ``elem_id``; element ids are never
  reused (merges mint new elements), so a stale pin can never alias a
  different payload.
- **serving**: :func:`device_union` assembles hit∪residual output columns
  *on device* — contiguous row runs of pinned elements go through the
  ``fragment_gather`` Pallas kernel when they start and end on whole tiles
  (other runs are XLA slices, counted as ``gather_fallbacks``), and the
  per-source outputs are concatenated device-side.  No host round-trip.
- **merge replication**: when the store merges two pinned elements, the
  merged element's device columns are built by gathering from the parents'
  pins (device→device), so a warm iteration loop re-uploads only the fresh
  residual — H2D bytes stay proportional to the *edit*, exactly like the
  RAM tier's recompute bytes.
- **demotion**: the tier has its own byte budget with LRU eviction.  The
  RAM tier stays authoritative (a device pin is a *copy*, never the only
  copy), so demotion is just a drop — the next jax consumer re-pins.

Bitwise discipline: jax's x32 default downcasts ``int64``/``float64`` on
``jnp.asarray``.  The downcast is elementwise, so it commutes with gather
and concatenation — pinning the downcast column and gathering on device
yields bit-identical arrays to the host path's concatenate-then-``asarray``.
``tests/test_device.py`` property-checks this across dtypes and window
shapes; the edit-matrix sweep holds it across every warm/cold edit pair.

Bounded shapes: eager jax compiles a program per array length, and the
layouts a union takes follow the order its cache's writers planned in.
One writer can replay them ahead of time; tenants of a shared service
cannot.  ``DeviceTier(bounded=True)`` therefore holds every array at a
power-of-two length of at least :data:`BOUNDED_MIN_ROWS` rows (padded on
the host before the copy) and assembles unions and merge replicas by one
jitted placement per pair of lengths, so what it runs is a closed set that
:meth:`DeviceTier.warm` compiles before any request.  Its unions never take
the ``fragment_gather`` kernel, whose programs follow the run layout.

Everything here is advisory: any unsupported dtype, non-jax runtime, or
missing pin falls back to the numpy path with no semantic change.
"""

from __future__ import annotations

import functools
import threading
from typing import Any, Dict, List, Mapping, Optional, Sequence, Tuple

import numpy as np

from repro.core.columnar import ChunkedTable, Table
from repro.obs.metrics import MetricAttr, Metrics
from repro.obs.trace import Tracer, get_tracer

__all__ = [
    "ROW_BLOCK",
    "BOUNDED_MIN_ROWS",
    "bounded_rows",
    "DeviceTier",
    "DeviceTable",
    "DeviceChunkedTable",
    "device_union",
]

# pin-time padding granularity: every pinned column is padded to whole
# (8, 128) tiles of its lane-dense view, so any tile-aligned run of a pin is
# a run of whole fragment_gather blocks (= fragment_gather's TILE_ROWS)
ROW_BLOCK = 1024

# the shortest array a bounded tier holds; every other is twice a shorter
BOUNDED_MIN_ROWS = 1 << 16


def bounded_rows(rows: int) -> int:
    """The length a bounded tier holds ``rows`` rows at."""
    return max(BOUNDED_MIN_ROWS, 1 << (rows - 1).bit_length())


def _bump(ledger: Optional[Dict[str, int]], key: str, by: int = 1) -> None:
    if ledger is not None:
        ledger[key] = ledger.get(key, 0) + by


def _pad_rows(arr, mult: int = ROW_BLOCK):
    import jax.numpy as jnp

    pad = (-arr.shape[0]) % mult
    if pad == 0:
        return arr
    return jnp.pad(arr, (0, pad))


def _upload(col: np.ndarray, bounded: bool):
    """``col`` on the device: padded there to whole tiles, or for a bounded
    tier padded on the host to :func:`bounded_rows` first, so the copy is
    the only operation.  Returns the array and the bytes copied."""
    import jax
    import jax.numpy as jnp

    if not bounded:
        arr = _pad_rows(jnp.asarray(col))
        return arr, int(np.dtype(arr.dtype).itemsize) * int(col.shape[0])
    host = np.zeros(bounded_rows(len(col)), jax.dtypes.canonicalize_dtype(col.dtype))
    host[: len(col)] = col
    return jax.device_put(host), int(host.nbytes)


@functools.lru_cache(maxsize=None)
def _placer():
    import jax
    import jax.numpy as jnp
    from jax import lax

    def place(acc, src, lo, off, n):
        """``acc`` with rows ``[off, off + n)`` set to ``src[lo:lo + n]``;
        the offsets are operands, so one program serves every run between
        arrays of these two lengths."""
        size = acc.shape[0]
        zeros = jnp.zeros(size, src.dtype)
        window = lax.dynamic_slice(jnp.concatenate([src, zeros]), (lo,), (size,))
        shifted = lax.dynamic_slice(jnp.concatenate([zeros, window]), (size - off,), (size,))
        i = lax.iota(jnp.int32, size)
        return jnp.where((i >= off) & (i < off + n), shifted, acc)

    return jax.jit(place)


class _DeviceEntry:
    __slots__ = ("arr", "rows", "nbytes", "last_used")

    def __init__(self, arr, rows: int, last_used: int):
        self.arr = arr  # 1-D device array, padded to ROW_BLOCK rows
        self.rows = rows  # real (unpadded) rows
        self.nbytes = int(arr.nbytes)
        self.last_used = last_used


class DeviceTier:
    """Byte-budgeted LRU cache of ``(elem_id, column) → jax device array``.

    ``interpret=None`` auto-selects Pallas interpret mode off-TPU (the
    kernel wrapper's convention); tests force ``interpret=True``.
    ``bounded`` holds arrays at :func:`bounded_rows` lengths (module
    docstring).
    """

    # ledger (surfaced through SharedStore.stats() / ScanReport / RunResult);
    # registry-backed — see DifferentialStore's counters
    bytes_h2d = MetricAttr("device_bytes_h2d")  # host→device bytes uploaded by pins
    device_hits = MetricAttr("device_hits")  # pin/get requests served resident
    device_evictions = MetricAttr("device_evictions")  # LRU-demoted entries
    pins = MetricAttr("device_pins")  # entries uploaded (misses)
    bytes_replicated = MetricAttr("device_bytes_replicated")  # d2d merge bytes

    def __init__(
        self,
        max_bytes: Optional[int] = None,
        interpret: Optional[bool] = None,
        bounded: bool = False,
    ):
        self.max_bytes = max_bytes
        self.interpret = interpret
        self.bounded = bounded
        self.lock = threading.RLock()
        self._entries: Dict[Tuple[int, str], _DeviceEntry] = {}
        self._by_elem: Dict[int, set] = {}
        self._clock = 0
        self._metrics: Optional[Metrics] = None
        self._tracer: Optional[Tracer] = None
        self.metrics_labels: Dict[str, str] = {}

    @property
    def metrics(self) -> Metrics:
        if self._metrics is None:
            self._metrics = Metrics()
        return self._metrics

    @property
    def tracer(self) -> Tracer:
        return self._tracer if self._tracer is not None else get_tracer()

    def adopt_obs(self, metrics: Metrics, tracer: Tracer) -> None:
        """Join an owner's registry/tracer.  One tier often backs both the
        scan cache and the model store — the first owner wins, so the tier's
        counters land in exactly one registry."""
        if self._metrics is None:
            self._metrics = metrics
        if self._tracer is None:
            self._tracer = tracer

    # -- inspection ----------------------------------------------------------
    @property
    def nbytes(self) -> int:
        with self.lock:
            return sum(e.nbytes for e in self._entries.values())

    def __len__(self) -> int:
        with self.lock:
            return len(self._entries)

    def stats(self) -> Dict[str, int]:
        with self.lock:
            return {
                "device_nbytes": sum(e.nbytes for e in self._entries.values()),
                "device_entries": len(self._entries),
                "bytes_h2d": self.bytes_h2d,
                "device_hits": self.device_hits,
                "device_evictions": self.device_evictions,
                "device_pins": self.pins,
                "bytes_replicated": self.bytes_replicated,
            }

    @staticmethod
    def supported(dtype) -> bool:
        """Dtypes the device path serves; everything else stays on the
        numpy path (strings/objects/datetimes have no jax analog here)."""
        return np.dtype(dtype).kind in "fiub"

    # -- pinning -------------------------------------------------------------
    def get(self, elem_id: int, column: str):
        """The resident device array for ``(elem_id, column)``, or None.
        Never uploads."""
        with self.lock:
            e = self._entries.get((elem_id, column))
            if e is None:
                return None
            self._clock += 1
            e.last_used = self._clock
            self.device_hits += 1
            return e.arr

    def pin(self, elem, column: str, ledger: Optional[Dict[str, int]] = None):
        """The device array for one element column, uploading on miss.
        Returns None when the element is demoted (no RAM payload to read)
        or the dtype is unsupported — callers fall back to numpy."""
        with self.lock:
            e = self._entries.get((elem.elem_id, column))
            if e is not None:
                self._clock += 1
                e.last_used = self._clock
                self.device_hits += 1
                _bump(ledger, "device_hits")
                return e.arr
        data = elem.data
        if data is None or column not in data.column_names:
            return None
        col = data.column(column)
        if not self.supported(col.dtype):
            return None
        with self.tracer.span("device.h2d", elem=elem.elem_id, column=column) as sp:
            arr, h2d = _upload(col, self.bounded)
            sp.attrs["bytes"] = h2d
        return self._insert(
            elem.elem_id, column, arr, int(col.shape[0]), h2d=h2d, ledger=ledger
        )

    def pin_columns(
        self, elem, columns: Sequence[str], ledger: Optional[Dict[str, int]] = None
    ) -> Optional[Dict[str, Any]]:
        """All-or-nothing pin of several columns (a partial union provider
        would force a per-column host/device split downstream)."""
        out: Dict[str, Any] = {}
        for c in columns:
            arr = self.pin(elem, c, ledger)
            if arr is None:
                return None
            out[c] = arr
        return out

    def pin_table(
        self, elem_id: int, table: Table, ledger: Optional[Dict[str, int]] = None
    ) -> bool:
        """Upload every supported column of ``table`` under ``elem_id`` —
        the spill tier's straight-to-device promotion (mmap → H2D once).
        Returns True when all columns landed."""
        ok = True
        with self.tracer.span("device.h2d", elem=elem_id) as sp:
            total = 0
            for c in table.column_names:
                col = table.column(c)
                if not self.supported(col.dtype):
                    ok = False
                    continue
                with self.lock:
                    if (elem_id, c) in self._entries:
                        continue
                arr, h2d = _upload(col, self.bounded)
                total += h2d
                self._insert(elem_id, c, arr, int(col.shape[0]), h2d=h2d, ledger=ledger)
            sp.attrs["bytes"] = total
        return ok

    def adopt(
        self,
        elem_id: int,
        arrays: Mapping[str, Any],
        rows: int,
        *,
        replicated: bool = False,
    ) -> int:
        """Register already-on-device columns for ``elem_id`` (a fresh
        residual the executor just converted, or a merge replica) — no H2D
        is counted here; the producer accounted for the transfer.  Returns
        the device bytes registered, padding included."""
        total = 0
        for c, arr in arrays.items():
            # a bounded tier's arrays come at bounded_rows lengths already
            padded = arr if self.bounded else _pad_rows(arr)
            total += int(padded.nbytes)
            if replicated:
                with self.lock:
                    self.bytes_replicated += int(padded.nbytes)
            self._insert(elem_id, c, padded, rows, h2d=0, ledger=None)
        return total

    def _insert(self, elem_id, column, arr, rows, *, h2d, ledger):
        with self.lock:
            key = (elem_id, column)
            existing = self._entries.get(key)
            if existing is not None:  # lost an upload race: keep the first
                self.device_hits += 1
                return existing.arr
            self._clock += 1
            self._entries[key] = _DeviceEntry(arr, rows, self._clock)
            self._by_elem.setdefault(elem_id, set()).add(column)
            self.pins += 1
            if h2d:
                self.bytes_h2d += h2d
                _bump(ledger, "bytes_h2d", h2d)
            self._evict()
        return arr

    # -- merge replication ---------------------------------------------------
    def element_arrays(self, elem, columns: Sequence[str]) -> Optional[Dict[str, Any]]:
        """Resident arrays for all ``columns`` of ``elem`` — None unless every
        one is already pinned (replication never uploads)."""
        out: Dict[str, Any] = {}
        with self.lock:
            for c in columns:
                e = self._entries.get((elem.elem_id, c))
                if e is None:
                    return None
                out[c] = e.arr
        return out

    def replicate_merge(self, a, b, merged, runs) -> int:
        """Build the merged element's device columns from its parents'
        pins (device→device fragment gather — zero H2D).  ``runs`` is the
        list ``DifferentialStore._merge_pair`` concatenated the host payload
        from: ``(key, side, lo, hi)`` row runs of ``a`` and ``b`` in merged
        key order, so host and device copies follow one list.  Returns the
        device bytes of the replica; 0, pinning nothing, when either parent
        is not fully resident here."""
        cols = list(merged.columns)
        prov_a = self.element_arrays(a, cols)
        prov_b = self.element_arrays(b, cols)
        if prov_a is None or prov_b is None:
            return 0
        if not runs:
            return 0  # empty merge: nothing to pin, trivially replicated
        prov = {a.elem_id: prov_a, b.elem_id: prov_b}
        arrays = device_union(
            [(prov[side.elem_id], lo, hi) for _key, side, lo, hi in runs],
            cols,
            interpret=self.interpret,
            bounded=self.bounded,
        )
        return self.adopt(merged.elem_id, arrays, merged.data.num_rows, replicated=True)

    def warm(self, dtypes: Sequence[Any], max_rows: int) -> None:
        """Compile what a bounded tier runs on arrays of ``dtypes`` up to
        ``max_rows`` rows: the zero fill of each length and the placement
        between each pair of lengths."""
        import jax.numpy as jnp

        sizes = [BOUNDED_MIN_ROWS]
        while sizes[-1] < bounded_rows(max_rows):
            sizes.append(2 * sizes[-1])
        place = _placer()
        for dt in dtypes:
            arrays = [jnp.zeros(n, dt) for n in sizes]
            for acc in arrays:
                for src in arrays:
                    place(acc, src, 0, 0, 1).block_until_ready()

    # -- demotion ------------------------------------------------------------
    def drop_element(self, elem_id: int) -> None:
        """Forget every pin of ``elem_id`` (the element merged away or left
        the store index).  Handed-out arrays stay valid — jax buffers are
        immutable and outlive the tier's reference."""
        with self.lock:
            for c in self._by_elem.pop(elem_id, ()):
                self._entries.pop((elem_id, c), None)

    def clear(self) -> None:
        with self.lock:
            self._entries.clear()
            self._by_elem.clear()

    def _evict(self) -> None:
        if self.max_bytes is None:
            return
        with self.lock:
            while (
                sum(e.nbytes for e in self._entries.values()) > self.max_bytes
                and self._entries
            ):
                key = min(self._entries, key=lambda k: self._entries[k].last_used)
                self._entries.pop(key)
                elem_id, column = key
                cols = self._by_elem.get(elem_id)
                if cols is not None:
                    cols.discard(column)
                    if not cols:
                        del self._by_elem[elem_id]
                self.device_evictions += 1


def upload_residual(
    fresh: Table,
    columns: Sequence[str],
    ledger: Dict[str, int],
    tracer: Tracer,
    site: str,
    bounded: bool = False,
) -> Optional[Dict[str, Any]]:
    """Upload a fresh residual's columns: the one H2D transfer its bytes
    ever pay, since the arrays go to the cache insert and every later
    consumer, post-merge ones included, serves from device.  One
    ``device.h2d`` span (``site`` names the caller); None, uploading
    nothing, when any column's dtype has no device analog.  ``bounded``:
    at :func:`bounded_rows` lengths, for a bounded tier."""
    if not all(DeviceTier.supported(fresh.column(c).dtype) for c in columns):
        return None
    import jax.numpy as jnp

    with tracer.span("device.h2d", site=site) as sp:
        if bounded:
            out, h2d = {}, 0
            for c in columns:
                out[c], nbytes = _upload(fresh.column(c), True)
                h2d += nbytes
        else:
            out = {c: jnp.asarray(fresh.column(c)) for c in columns}
            h2d = sum(int(arr.nbytes) for arr in out.values())
        if tracer.enabled:
            sp.attrs["bytes"] = h2d
    _bump(ledger, "bytes_h2d", h2d)
    return out


# ---------------------------------------------------------------------------
# device-side UNION assembly
# ---------------------------------------------------------------------------

def device_union(
    runs: Sequence[Tuple[Mapping[str, Any], int, int]],
    columns: Sequence[str],
    *,
    interpret: Optional[bool] = None,
    ledger: Optional[Dict[str, int]] = None,
    bounded: bool = False,
) -> Dict[str, Any]:
    """Assemble the hit∪residual UNION on device.

    ``runs`` is the output's row layout **in final row order**: each entry is
    ``(arrays, lo, hi)`` — a provider mapping of padded 1-D device columns
    and the half-open real-row range it contributes.  Consecutive runs from
    the same provider become ONE ``fragment_gather`` call when every run
    starts and ends on a whole tile (the multi-interval hit case — a true
    block-run gather, counted as ``gather_fast``); other multi-run groups are
    XLA slices, counted as ``gather_fallbacks``, and single-run groups are
    plain slices (a gather would be the identity).  Returns exact-length
    device columns, bitwise-equal to the numpy reference ``np.concatenate``
    of the same slices followed by ``jnp.asarray``.  ``bounded`` (the
    providers are a bounded tier's): the columns come at
    :func:`bounded_rows` of the real rows instead, each run placed by one
    program per pair of lengths, and the rows past the real ones are
    padding.
    """
    import jax.numpy as jnp

    from repro.kernels.fragment_gather.ops import fragment_gather, tile_aligned

    if not runs:
        return {}
    if bounded:
        return _bounded_union(runs, columns, ledger)
    # group consecutive runs by provider identity
    groups: List[Tuple[Mapping[str, Any], List[Tuple[int, int]]]] = []
    for arrays, lo, hi in runs:
        if hi <= lo:
            continue
        if groups and groups[-1][0] is arrays:
            groups[-1][1].append((lo, hi))
        else:
            groups.append((arrays, [(lo, hi)]))
    if not groups:
        first = runs[0][0]
        return {c: first[c][0:0] for c in columns}

    out: Dict[str, Any] = {}
    total_rows = 0
    for c in columns:
        parts = []
        for arrays, bounds in groups:
            src = arrays[c]
            if len(bounds) > 1 and tile_aligned(int(src.shape[0]), bounds):
                _bump(ledger, "gather_fast")
                parts.append(fragment_gather(src, bounds, interpret=interpret))
            else:
                if len(bounds) > 1:
                    _bump(ledger, "gather_fallbacks")
                parts.extend(src[lo:hi] for lo, hi in bounds)
        col = parts[0] if len(parts) == 1 else jnp.concatenate(parts)
        out[c] = col
        total_rows = int(col.shape[0])
        _bump(ledger, "device_union_bytes", int(col.nbytes))
    _bump(ledger, "device_unions")
    _bump(ledger, "device_union_rows", total_rows)
    return out


def _bounded_union(runs, columns, ledger) -> Dict[str, Any]:
    import jax.numpy as jnp

    first = runs[0][0]
    runs = [(arrays, lo, hi) for arrays, lo, hi in runs if hi > lo]
    total = sum(hi - lo for _arrays, lo, hi in runs)
    size = bounded_rows(total)
    place = _placer()
    out: Dict[str, Any] = {}
    for c in columns:
        col = jnp.zeros(size, first[c].dtype)
        off = 0
        for arrays, lo, hi in runs:
            col = place(col, arrays[c], lo, off, hi - lo)
            off += hi - lo
        out[c] = col
        _bump(ledger, "device_union_bytes", int(np.dtype(col.dtype).itemsize) * total)
    _bump(ledger, "device_unions")
    _bump(ledger, "device_union_rows", total)
    return out


def _trimmed(arrays: Mapping[str, Any], rows: int) -> Dict[str, Any]:
    return {c: (a if a.shape[0] == rows else a[:rows]) for c, a in arrays.items()}


# ---------------------------------------------------------------------------
# device-aware table wrappers
# ---------------------------------------------------------------------------

class _DeviceColumns:
    """``device_columns``: the device copies at the table's row count.  A
    bounded tier's longer arrays are trimmed on first use, which only a jax
    consumer makes (one slice per length)."""

    __slots__ = ()

    @property
    def device_columns(self) -> Dict[str, Any]:
        if self._exact is None:
            self._exact = _trimmed(self._device, self.num_rows)
        return self._exact


class DeviceTable(_DeviceColumns, Table):
    """A host :class:`Table` carrying device-resident copies of (some of)
    its columns.  The host columns stay authoritative; ``device_columns``
    are advisory, bitwise-equal jax arrays a jax-runtime consumer uses to
    skip the H2D conversion.  Views (``select``/``slice``/…) return plain
    Tables — device association does not survive reshaping."""

    __slots__ = ("_device", "_exact")

    def __init__(self, host: Table, device_columns: Mapping[str, Any]):
        super().__init__({n: host.column(n) for n in host.column_names})
        self._device = dict(device_columns)
        self._exact = None


class DeviceChunkedTable(_DeviceColumns, ChunkedTable):
    """A :class:`ChunkedTable` whose *combined* columns are also resident on
    device.  ``device_columns[c]`` equals ``jnp.asarray(self.column(c))``
    bitwise (chunk concatenation order)."""

    __slots__ = ("_device", "_exact")

    def __init__(self, chunks, device_columns: Mapping[str, Any]):
        super().__init__(chunks)
        self._device = dict(device_columns)
        self._exact = None

    def select(self, names):
        return DeviceChunkedTable(
            [c.select(names) for c in self.chunks],
            {n: self._device[n] for n in names if n in self._device},
        )
