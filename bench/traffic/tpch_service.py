"""Open-loop TPC-H traffic: tenants of one ``PipelineService`` asking Q1 and Q6.

A team of data scientists shares one lakehouse service for dashboards and
notebooks and re-runs the same reports with new dates and discounts.  Each
tenant's project is the query as the specification writes it, split where
the runtimes split it:

- Q1: ``q1_prices``, a jax rowwise stage over the lines shipped by
  1998-12-01 minus DELTA days, computes ``disc_price`` and ``charge`` in
  float32, scaled by 100 and 10,000 so that the discount and tax factors
  are whole numbers (float32 holds 0.93 or 1.06 only approximately, and
  that one rounding per distinct discount or tax would bias every sum by
  more than TPC-H's $100 at SF1); ``q1``, a numpy stage, groups the same lines by
  (``l_returnflag``, ``l_linestatus``) and takes the eight aggregates, sums
  in float64 in key order.  The one-letter flags reach only the numpy stage:
  the device tier has no bytes kind.
- Q6: ``q6_rev``, a jax rowwise stage over one year's lines, computes the
  predicate on ``l_discount`` and ``l_quantity`` and ``l_extendedprice *
  l_discount``; ``q6``, a numpy stage, sums the kept revenue in float64.

Everything is fixed from the seed before the run: each request's tenant
(Zipf over the tenants), query (Q1 : Q6 as the traffic file says), and
parameters (with the traffic's share, fresh substitution parameters drawn
as in clause 2.4; otherwise the tenant's previous parameters of that query),
and its arrival time (Poisson at the traffic's rate).  The window holds
``round(rate * seconds)`` requests, so every program times the same
requests.  Latency runs from the scheduled arrival to the result in the
client's hands (``RunHandle.done_ns``), so queueing counts.

The service runs on its default device tier (``device=True``), a bounded one:
each jax stage's windows are pinned on the chip, merged there when a
residual joins them, and served to every tenant from there.  Set-up writes
the table, runs each query's jax stage once at every length the executor
calls a rowwise stage at (``rowwise_lengths``), compiles the tier's
programs for the dtypes those stages hand it, and then drives a warm-up
stream drawn from the seed apart from the window's through the measured
service, so the window starts on a working set and still meets misses and
residuals.
"""

from __future__ import annotations

import gc
import os
import time
from dataclasses import dataclass
from typing import Callable, Dict, List, Optional, Tuple

import numpy as np

Q1_END = "1998-12-01"
COUNTERS = (
    "bytes_from_store",
    "bytes_from_cache",
    "bytes_from_model_cache",
    "bytes_h2d",
    "bytes_d2h",
    "device_hits",
    "rows_to_user_fns",
    "coalesced_waits",
)
# what each jax stage reads (the engine adds the sort key)
Q1_STAGE = ["l_extendedprice", "l_discount", "l_tax"]
Q6_STAGE = ["l_extendedprice", "l_discount", "l_quantity"]
# seed streams
WINDOW, WARMUP, ARRIVALS, SAMPLES = 1, 2, 3, 4


def q1_project(delta: int, table: str, tables):
    from repro.pipeline.dsl import Model, Project, model, runtime

    where = f"l_shipdate >= 0 AND l_shipdate < {tables.day(Q1_END) - delta + 1}"
    p = Project("tpch_q1")

    @model(project=p, incremental="rowwise")
    @runtime("jax")
    def q1_prices(data=Model(table, columns=Q1_STAGE, filter=where)):
        import jax.numpy as jnp

        # (1 - l_discount) and (1 + l_tax) as whole cents: exact in float32,
        # so no sum inherits one rounding of each of the 11 discounts
        hundred = jnp.float32(100)
        disc = hundred - jnp.round(data["l_discount"] * hundred)
        tax = hundred + jnp.round(data["l_tax"] * hundred)
        disc_price_x100 = data["l_extendedprice"] * disc
        return {"disc_price_x100": disc_price_x100, "charge_x10000": disc_price_x100 * tax}

    @model(project=p)
    @runtime("numpy")
    def q1(
        lines=Model(
            table,
            columns=["l_shipdate", "l_returnflag", "l_linestatus", "l_quantity",
                     "l_extendedprice", "l_discount"],
            filter=where,
        ),
        prices=Model("q1_prices"),
    ):
        key = lines.column("l_shipdate")
        if not np.all(key[1:] >= key[:-1]):
            lines = lines.sort_by("l_shipdate")
        if not np.array_equal(lines.column("l_shipdate"), prices.column("l_shipdate")):
            raise ValueError("q1: the lines and their prices are not aligned")
        code = (lines.column("l_returnflag").view(np.uint8).astype(np.uint16) << 8) | lines.column(
            "l_linestatus"
        ).view(np.uint8)
        count = np.bincount(code, minlength=1 << 16)
        groups = np.flatnonzero(count)
        lookup = np.zeros(1 << 16, np.intp)
        lookup[groups] = np.arange(len(groups))
        index = lookup[code]
        n = count[groups]

        def total(column, t=lines):
            return np.bincount(index, weights=t.column(column), minlength=len(groups))

        sum_qty, sum_base = total("l_quantity"), total("l_extendedprice")
        return {
            "l_returnflag": (groups >> 8).astype(np.uint8).view("S1"),
            "l_linestatus": (groups & 255).astype(np.uint8).view("S1"),
            "sum_qty": sum_qty,
            "sum_base_price": sum_base,
            "sum_disc_price": total("disc_price_x100", prices) / 100,
            "sum_charge": total("charge_x10000", prices) / 10000,
            "avg_qty": sum_qty / n,
            "avg_price": sum_base / n,
            "avg_disc": total("l_discount") / n,
            "count_order": n.astype(np.int64),
        }

    return p


def q6_project(year: int, discount_cents: int, quantity: int, table: str, tables):
    from repro.pipeline.dsl import Model, Project, model, runtime

    lo, hi = tables.day(f"{year}-01-01"), tables.day(f"{year + 1}-01-01")
    where = f"l_shipdate >= {lo} AND l_shipdate < {hi}"
    d_lo, d_hi, q = (discount_cents - 1) / 100, (discount_cents + 1) / 100, float(quantity)
    p = Project("tpch_q6")

    @model(project=p, incremental="rowwise")
    @runtime("jax")
    def q6_rev(data=Model(table, columns=Q6_STAGE, filter=where)):
        import jax.numpy as jnp

        disc = data["l_discount"]
        keep = (disc >= jnp.float32(d_lo)) & (disc <= jnp.float32(d_hi)) & (
            data["l_quantity"] < jnp.float32(q)
        )
        return {"keep": keep, "revenue": data["l_extendedprice"] * disc}

    @model(project=p)
    @runtime("numpy")
    def q6(rev=Model("q6_rev")):
        revenue = rev.column("revenue")[rev.column("keep")]
        return {"revenue": np.array([np.sum(revenue, dtype=np.float64)])}

    return p


@dataclass(frozen=True)
class Request:
    tenant: str
    query: str  # q1 | q6
    fresh: bool
    params: Tuple[int, ...]  # (delta,) or (year, discount_cents, quantity)
    at_s: float = 0.0  # scheduled arrival, from the window's start

    @property
    def label(self) -> str:
        return f"{self.query}.{'fresh' if self.fresh else 'repeat'}"

    def describe(self) -> Dict:
        if self.query == "q1":
            return {"query": "q1", "delta": self.params[0], "tenant": self.tenant}
        year, cents, quantity = self.params
        return {"query": "q6", "year": year, "discount_cents": cents, "quantity": quantity,
                "tenant": self.tenant}


def tenant_shares(traffic: Dict) -> np.ndarray:
    w = 1.0 / np.arange(1, int(traffic["tenants"]) + 1) ** float(traffic["zipf_s"])
    return w / w.sum()


def draw_params(traffic: Dict, query: str, rng: np.random.Generator) -> Tuple[int, ...]:
    """Substitution parameters of clause 2.4."""
    if query == "q1":
        lo, hi = traffic["q1_delta_days"]
        return (int(rng.integers(lo, hi + 1)),)
    (ylo, yhi), (clo, chi), (qlo, qhi) = (
        traffic["q6_years"], traffic["q6_discount_cents"], traffic["q6_quantity"]
    )
    return (int(rng.integers(ylo, yhi + 1)), int(rng.integers(clo, chi + 1)),
            int(rng.integers(qlo, qhi + 1)))


def draw_requests(traffic: Dict, seed: int, stream: int, count: int,
                  memory: Dict[Tuple[str, str], Tuple[int, ...]]) -> List[Request]:
    """``count`` requests of one stream; ``memory`` holds each tenant's
    previous parameters per query and is carried from stream to stream."""
    rng = np.random.default_rng([seed, stream])
    shares = tenant_shares(traffic)
    out = []
    for t in rng.choice(len(shares), count, p=shares):
        tenant = f"tenant{int(t):02d}"
        query = "q1" if rng.random() < float(traffic["q1_share"]) else "q6"
        previous = memory.get((tenant, query))
        fresh = previous is None or rng.random() < float(traffic["fresh_share"])
        params = draw_params(traffic, query, rng) if fresh else previous
        memory[(tenant, query)] = params
        out.append(Request(tenant, query, fresh, params))
    return out


def arrivals(traffic: Dict, seed: int, count: int) -> np.ndarray:
    """Poisson arrival offsets at the traffic's rate, the first at 0."""
    gaps = np.random.default_rng([seed, ARRIVALS]).exponential(1.0 / float(traffic["rate_per_s"]), count)
    return np.concatenate([[0.0], np.cumsum(gaps[:-1])]) if count else gaps


def schedule(traffic: Dict, seed: int, seconds: float) -> Tuple[List[Request], List[Request]]:
    """The warm-up stream and the window's requests with their arrivals."""
    memory: Dict[Tuple[str, str], Tuple[int, ...]] = {}
    warmup = draw_requests(traffic, seed, WARMUP, int(traffic["warmup_requests"]), memory)
    count = max(1, round(float(traffic["rate_per_s"]) * seconds))
    window = draw_requests(traffic, seed, WINDOW, count, memory)
    at = arrivals(traffic, seed, count)
    return warmup, [Request(r.tenant, r.query, r.fresh, r.params, float(a)) for r, a in zip(window, at)]


def _crossing(warmup: List[Request], window: List[Request]) -> Dict[str, List[int]]:
    """Per query, the window's positions whose tenant had never asked for
    that stage's signature while another tenant had, in order."""
    asked: Dict[Tuple, set] = {}
    for r in warmup:
        asked.setdefault(signature(r), set()).add(r.tenant)
    out: Dict[str, List[int]] = {}
    for i, r in enumerate(window):
        who = asked.setdefault(signature(r), set())
        if r.tenant not in who and who:
            out.setdefault(r.query, []).append(i)
        who.add(r.tenant)
    return out


def sample_positions(warmup: List[Request], window: List[Request],
                     served: Optional[Dict[int, bool]], seed: int) -> Tuple[List[int], List[int]]:
    """Which of the window's requests the reference checks: per kind, one
    drawn from the seed and the last one; per query, the first whose tenant
    had never asked for that stage's signature while another tenant had,
    and that was served from the model store (``served``, by position), so
    from windows another tenant computed.  Returns every position kept, and
    those last ones.  Without ``served`` it returns every position that may
    be kept."""
    rng = np.random.default_rng([seed, SAMPLES])
    by_label: Dict[str, List[int]] = {}
    for i, r in enumerate(window):
        by_label.setdefault(r.label, []).append(i)
    keep = set()
    for _label, where in sorted(by_label.items()):
        keep.add(where[int(rng.integers(len(where)))])
        keep.add(where[-1])
    crossing = _crossing(warmup, window)
    if served is None:
        return sorted(keep.union(*crossing.values())), []
    crossed = [next(i for i in where if served.get(i)) for where in crossing.values()
               if any(served.get(i) for i in where)]
    return sorted(keep | set(crossed)), sorted(crossed)


def signature(r: Request) -> Tuple:
    """What the jax stage's signature depends on: Q1's stage has no
    parameter; Q6's closes over DISCOUNT and QUANTITY."""
    return ("q1",) if r.query == "q1" else ("q6",) + tuple(r.params[1:])


def host_outputs(result) -> Dict[str, Dict[str, np.ndarray]]:
    return {
        node: {c: np.asarray(t.column(c)) for c in t.column_names}
        for node, t in result.outputs.items()
    }


class Driver:
    """Set-up, window and samples of one open-loop service cell."""

    def __init__(self, config: Dict, traffic: Dict, seed: int, workdir: str, tables,
                 compile_seconds: Callable[[], float]):
        self.config, self.traffic, self.seed = config, traffic, seed
        self.root = os.path.join(workdir, "lake")
        self.tables = tables
        self.compile_seconds = compile_seconds
        self.table = config["table"]
        self.setup_parts: Dict[str, float] = {}
        self.svc = None
        self.warmup: List[Request] = []
        self.requests: List[Request] = []
        self._projects: Dict[Tuple, object] = {}
        self._samples: List[Tuple[Dict, Dict]] = []

    def project(self, r: Request):
        key = (r.query,) + r.params
        if key not in self._projects:
            if r.query == "q1":
                self._projects[key] = q1_project(r.params[0], self.table, self.tables)
            else:
                self._projects[key] = q6_project(*r.params, self.table, self.tables)
        return self._projects[key]

    # -- set-up -------------------------------------------------------------
    def _compile(self) -> None:
        """Each query's jax stage at every length the executor calls a
        rowwise stage at on this table (``rowwise_lengths``), and the device
        tier's programs for the dtypes those stages hand it: the only shapes
        the window meets, whatever order the tenants plan in.  The stages'
        constants are arguments, not part of a compiled program, so one Q6
        variant stands for all."""
        import jax
        import jax.numpy as jnp

        from repro.pipeline.executor import rowwise_lengths

        schema, rows = self.tables.SCHEMA, int(self.config["rows"])
        q1 = q1_project(int(self.traffic["q1_delta_days"][0]), self.table, self.tables)
        q6 = q6_project(int(self.traffic["q6_years"][0]), int(self.traffic["q6_discount_cents"][0]),
                        int(self.traffic["q6_quantity"][0]), self.table, self.tables)
        dtypes = {jax.dtypes.canonicalize_dtype(np.dtype(schema[self.tables.SORT_KEY]))}
        for proj, name, stage in ((q1, "q1_prices", Q1_STAGE), (q6, "q6_rev", Q6_STAGE)):
            fn = proj[name].fn
            columns = stage + [self.tables.SORT_KEY]
            for n in rowwise_lengths(rows):
                out = fn(data={c: jnp.asarray(np.ones(n, schema[c])) for c in columns})
                dtypes.update(np.asarray(v).dtype for v in out.values())
        if self.svc.device is not None:
            self.svc.device.warm(sorted(dtypes, key=str), rows)

    def setup(self, seconds: float) -> None:
        from repro.core.columnar import Table
        from repro.service import PipelineService

        self.warmup, self.requests = schedule(self.traffic, self.seed, seconds)
        t = time.perf_counter()
        self.svc = PipelineService(
            self.root,
            workers=int(self.traffic["workers"]),
            rows_per_fragment=int(self.config["rows_per_fragment"]),
            device=True if self.config["device_tier"] else None,
        )
        ns, name = self.table.rsplit(".", 1)
        self.svc.catalog.create_table(ns, name, self.tables.SCHEMA, self.tables.SORT_KEY)
        self.svc.catalog.append(self.table, Table(self.tables.columns(self.config, self.seed)))
        gc.collect()
        self.setup_parts["write_s"] = time.perf_counter() - t

        t, c = time.perf_counter(), self.compile_seconds()
        self._compile()
        self.setup_parts["compile_s"] = time.perf_counter() - t
        self.setup_parts["compile_backend_s"] = self.compile_seconds() - c

        t = time.perf_counter()
        for r in self.requests:
            self.project(r)
        handles = [self.svc.submit(r.tenant, self.project(r)) for r in self.warmup]
        self.svc.drain()
        failed = [h.error for h in handles if h.error is not None]
        if failed:
            raise RuntimeError(f"warm-up request failed: {failed[0]!r}") from failed[0]
        del handles
        gc.collect()
        self.setup_parts["warmup_s"] = time.perf_counter() - t

    # -- the measured window -------------------------------------------------
    def window(self, seconds: float) -> List[Dict]:
        """Submit each request at its arrival, then wait for all; ``seconds``
        chose how many there are.  While it waits for the next arrival the
        client takes each finished request's record, and keeps the result
        only where the reference may check it: the others are dropped, as
        a client drops an answer it has read."""
        svc = self.svc
        may_keep = set(sample_positions(self.warmup, self.requests, None, self.seed)[0])
        records: List[Dict] = [{} for _ in self.requests]
        kept: Dict[int, object] = {}
        served: Dict[int, bool] = {}
        pending: List[Tuple[int, int, object]] = []

        def collect(everything: bool) -> None:
            left = []
            for i, due, h in pending:
                if not (everything or h.done):
                    left.append((i, due, h))
                    continue
                r = self.requests[i]
                record = {"kind": r.label, "tenant": r.tenant, "ok": h.error is None,
                          "start_s": r.at_s}
                if h.error is None:
                    record.update(latency_s=(h.done_ns - due) / 1e9, counters={
                        k: int(getattr(h.result, k)) for k in COUNTERS
                    })
                    served[i] = h.result.bytes_from_model_cache > 0
                    if i in may_keep:
                        kept[i] = h.result
                else:
                    record["error"] = repr(h.error)
                records[i] = record
            pending[:] = left

        late = 0
        t0 = time.perf_counter_ns()
        for i, r in enumerate(self.requests):
            due = t0 + int(r.at_s * 1e9)
            if due > time.perf_counter_ns():
                collect(False)
            wait = (due - time.perf_counter_ns()) / 1e9
            if wait > 0:
                time.sleep(wait)
            late = max(late, time.perf_counter_ns() - due)
            pending.append((i, due, svc.submit(r.tenant, self.project(r))))
        svc.drain()
        collect(True)
        self.setup_parts["submit_late_max_s"] = late / 1e9
        keep, crossed = sample_positions(self.warmup, self.requests, served, self.seed)
        self.setup_parts["cross_tenant_samples"] = len(crossed)
        for i in keep:
            if i in kept:
                self._samples.append((self.requests[i].describe(), host_outputs(kept[i])))
        kept.clear()
        return records

    def samples(self) -> List[Tuple[Dict, Dict]]:
        return self._samples

    def close(self) -> None:
        if self.svc is not None:
            self.svc.shutdown()
        self.svc = None
        gc.collect()
