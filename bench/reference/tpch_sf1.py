"""Plain reference for TPC-H Q1 and Q6 over ``tpch_sf1``'s ``LINEITEM``.

It works from the seed's data alone, as numpy arrays: no catalog, no cache,
no jax and nothing the program made.  The table's rows are in key order
(``l_shipdate``, ties in (``l_orderkey``, ``l_linenumber``) order), so a
window of whole days is one run of rows.

- Q1 with DELTA: the lines shipped by 1998-12-01 minus DELTA days.  Per line,
  in float32 as jax's 32-bit mode computes it, ``disc_price_x100 =
  l_extendedprice * (100 - round(100 * l_discount))`` and ``charge_x10000 =
  disc_price_x100 * (100 + round(100 * l_tax))`` (node ``q1_prices``, with
  the sort key).  Then per
  (``l_returnflag``, ``l_linestatus``), in that order, the query's eight
  aggregates: float64 sums taken line by line in key order, averages as sum
  over count, and the count (node ``q1``).
- Q6 with DATE, DISCOUNT and QUANTITY: the lines shipped in DATE's year; per
  line, in float32, ``keep`` (DISCOUNT - 0.01 <= l_discount <= DISCOUNT +
  0.01 and l_quantity < QUANTITY) and ``revenue = l_extendedprice *
  l_discount`` (node ``q6_rev``); then the float64 sum of the kept revenue
  (node ``q6``).

Every per-line operation is exactly rounded, so the program's output has to
equal this bit for bit.  ``float_dtype`` is the precision of the per-line
arithmetic; the control computes it in ``bfloat16`` and casts back to
float32.  :func:`self_check` compares the float32 answers with a float64
computation by TPC-H's answer precision (clause 2.1.3.5: money within $100,
averages within 1%).
"""

from __future__ import annotations

from typing import Dict, Optional

import numpy as np

Q1_END = "1998-12-01"


class Reference:
    def __init__(self, config: Dict, seed: int, tables):
        self.config, self.seed, self.tables = config, seed, tables
        self._data: Optional[Dict[str, np.ndarray]] = None

    @property
    def data(self) -> Dict[str, np.ndarray]:
        if self._data is None:
            self._data = self.tables.columns(self.config, self.seed)
            key = self._data[self.tables.SORT_KEY]
            if not np.all(key[1:] >= key[:-1]):
                raise ValueError("lineitem is not in l_shipdate order")
        return self._data

    def rows(self, lo: int, hi: int) -> slice:
        """The rows whose ship date lies in days ``[lo, hi)``."""
        key = self.data[self.tables.SORT_KEY]
        return slice(int(np.searchsorted(key, lo)), int(np.searchsorted(key, hi)))

    def outputs(self, run: Dict, float_dtype=np.float32) -> Dict[str, Dict[str, np.ndarray]]:
        if run["query"] == "q1":
            return self.q1(int(run["delta"]), float_dtype)
        return self.q6(int(run["year"]), int(run["discount_cents"]), int(run["quantity"]), float_dtype)

    def q1(self, delta: int, float_dtype=np.float32) -> Dict[str, Dict[str, np.ndarray]]:
        d = self.data
        rows = self.rows(0, self.tables.day(Q1_END) - delta + 1)
        hundred = float_dtype(100)
        disc = hundred - np.round(d["l_discount"][rows].astype(float_dtype) * hundred)
        tax = hundred + np.round(d["l_tax"][rows].astype(float_dtype) * hundred)
        disc_price = d["l_extendedprice"][rows].astype(float_dtype) * disc
        prices = {
            "charge_x10000": (disc_price * tax).astype(np.float32),
            "disc_price_x100": disc_price.astype(np.float32),
            "l_shipdate": d["l_shipdate"][rows],
        }
        q1 = q1_aggregates(
            d["l_returnflag"][rows], d["l_linestatus"][rows], d["l_quantity"][rows],
            d["l_extendedprice"][rows], d["l_discount"][rows],
            prices["disc_price_x100"], prices["charge_x10000"],
        )
        return {"q1_prices": prices, "q1": q1}

    def q6(self, year: int, discount_cents: int, quantity: int, float_dtype=np.float32):
        d = self.data
        rows = self.rows(self.tables.day(f"{year}-01-01"), self.tables.day(f"{year + 1}-01-01"))
        disc = d["l_discount"][rows].astype(float_dtype)
        keep = (
            (disc >= float_dtype((discount_cents - 1) / 100))
            & (disc <= float_dtype((discount_cents + 1) / 100))
            & (d["l_quantity"][rows].astype(float_dtype) < float_dtype(quantity))
        )
        revenue = (d["l_extendedprice"][rows].astype(float_dtype) * disc).astype(np.float32)
        return {
            "q6_rev": {"keep": keep, "revenue": revenue, "l_shipdate": d["l_shipdate"][rows]},
            "q6": {"revenue": np.array([np.sum(revenue[keep], dtype=np.float64)])},
        }


def q1_aggregates(returnflag, linestatus, quantity, extendedprice, discount, disc_price_x100,
                  charge_x10000):
    """Q1's groups in (returnflag, linestatus) order, each sum a float64
    accumulation over the lines in key order; the scaled prices are
    divided back once summed."""
    code = returnflag.view(np.uint8).astype(np.int64) * 256 + linestatus.view(np.uint8)
    count = np.bincount(code, minlength=1 << 16)
    groups = np.flatnonzero(count)
    index = np.searchsorted(groups, code)
    n = count[groups]

    def total(x):
        return np.bincount(index, weights=x, minlength=len(groups))

    sum_qty, sum_base = total(quantity), total(extendedprice)
    return {
        "l_returnflag": (groups // 256).astype(np.uint8).view("S1"),
        "l_linestatus": (groups % 256).astype(np.uint8).view("S1"),
        "sum_qty": sum_qty,
        "sum_base_price": sum_base,
        "sum_disc_price": total(disc_price_x100) / 100,
        "sum_charge": total(charge_x10000) / 10000,
        "avg_qty": sum_qty / n,
        "avg_price": sum_base / n,
        "avg_disc": total(discount) / n,
        "count_order": n.astype(np.int64),
    }


def self_check(ref: Reference, delta: int = 90, year: int = 1994, discount_cents: int = 6,
               quantity: int = 24) -> Dict[str, float]:
    """The float32 answers against float64 ones from the same lines, at the
    specification's validation parameters: the largest money difference
    (sums, and Q6's revenue) in dollars, the largest relative difference of
    an average, and Q1's group counts."""
    got = ref.q1(delta)["q1"]
    d = ref.data
    rows = ref.rows(0, ref.tables.day(Q1_END) - delta + 1)
    price, disc = d["l_extendedprice"][rows], d["l_discount"][rows]
    disc_price = price * (1 - disc)
    want = q1_aggregates(
        d["l_returnflag"][rows], d["l_linestatus"][rows], d["l_quantity"][rows], price, disc,
        disc_price * 100, disc_price * (1 + d["l_tax"][rows]) * 10000,
    )
    money = max(
        float(np.max(np.abs(got[c] - want[c])))
        for c in ("sum_qty", "sum_base_price", "sum_disc_price", "sum_charge")
    )
    averages = max(
        float(np.max(np.abs(got[c] / want[c] - 1))) for c in ("avg_qty", "avg_price", "avg_disc")
    )
    rows6 = ref.rows(ref.tables.day(f"{year}-01-01"), ref.tables.day(f"{year + 1}-01-01"))
    cents = np.rint(d["l_discount"][rows6] * 100)
    keep = (np.abs(cents - discount_cents) <= 1) & (d["l_quantity"][rows6] < quantity)
    revenue = float(np.sum((d["l_extendedprice"][rows6] * d["l_discount"][rows6])[keep]))
    q6 = float(ref.q6(year, discount_cents, quantity)["q6"]["revenue"][0])
    return {
        "money_usd": max(money, abs(q6 - revenue)),
        "average_rel": averages,
        "q6_revenue": q6,
        "q1_groups": {
            (f + s).decode(): int(c)
            for f, s, c in zip(got["l_returnflag"].tolist(), got["l_linestatus"].tolist(), got["count_order"])
        },
    }
