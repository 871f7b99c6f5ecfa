"""TPC-H ``LINEITEM`` at a scale factor, made from a seed by dbgen's rules.

The 16 columns of the specification (clause 1.4.1) at their published
widths: the keys and ``l_linenumber`` int64, the four DECIMAL columns
float64, the two one-letter flags ``|S1``, the three dates int64 days since
1992-01-01 (so every date reaches a jax stage as an exact int32), and
``l_shipinstruct`` / ``l_shipmode`` / ``l_comment`` as CHAR(25), CHAR(10)
and VARCHAR(44) padded.

Values follow clause 4.2.3: ``orders`` orders with sparse keys (the first 8
of every 32), O_ORDERDATE uniform over [STARTDATE, ENDDATE - 151 days], 1-7
lines each, ship / commit / receipt dates offset from the order date, the
return flag and line status from CURRENTDATE (1995-06-17), and the extended
price from P_RETAILPRICE of the part.  The last orders' line counts are set
so that the table has exactly ``rows`` lines (dbgen's counts are random, and
the specification publishes SF1's total).  ``l_comment`` comes from a seeded
pool of strings, not dbgen's text grammar.

Rows are in (``l_shipdate``, ``l_orderkey``, ``l_linenumber``) order: the
sort key, then one fixed order among lines shipped on one day.
"""

from __future__ import annotations

import datetime as dt
from typing import Dict

import numpy as np

SCHEMA = {
    "l_orderkey": "<i8",
    "l_partkey": "<i8",
    "l_suppkey": "<i8",
    "l_linenumber": "<i8",
    "l_quantity": "<f8",
    "l_extendedprice": "<f8",
    "l_discount": "<f8",
    "l_tax": "<f8",
    "l_returnflag": "|S1",
    "l_linestatus": "|S1",
    "l_shipdate": "<i8",
    "l_commitdate": "<i8",
    "l_receiptdate": "<i8",
    "l_shipinstruct": "|S25",
    "l_shipmode": "|S10",
    "l_comment": "|S44",
}
SORT_KEY = "l_shipdate"
EPOCH = dt.date(1992, 1, 1)  # STARTDATE: day 0
SHIPINSTRUCT = [b"DELIVER IN PERSON", b"COLLECT COD", b"NONE", b"TAKE BACK RETURN"]
SHIPMODE = [b"REG AIR", b"AIR", b"RAIL", b"SHIP", b"TRUCK", b"MAIL", b"FOB"]
WORDS = (
    b"furiously carefully quickly slyly blithely ironic regular final express "
    b"special pending bold even silent unusual idle packages requests accounts "
    b"deposits foxes ideas theodolites pinto beans instructions dependencies "
    b"excuses platelets asymptotes courts dolphins multipliers sauternes warthogs"
).split()
COMMENT_POOL = 4096


def day(iso: str) -> int:
    """Days from 1992-01-01 to ``iso``."""
    return (dt.date.fromisoformat(iso) - EPOCH).days


CURRENTDATE = day("1995-06-17")
LAST_ORDERDATE = day("1998-12-31") - 151


def line_counts(config: Dict, rng: np.random.Generator) -> np.ndarray:
    """Lines per order, uniform 1..7, with the last orders' counts moved
    towards 7 (or 1) until they sum to ``rows``."""
    orders, rows = int(config["orders"]), int(config["rows"])
    counts = rng.integers(1, 8, orders)
    diff = rows - int(counts.sum())
    room = (7 - counts) if diff > 0 else (counts - 1)
    take = np.minimum(room[::-1], np.maximum(abs(diff) - np.concatenate([[0], np.cumsum(room[::-1])[:-1]]), 0))
    counts[::-1] += np.sign(diff) * take
    if int(counts.sum()) != rows:
        raise ValueError(f"cannot spread {rows} lines over {orders} orders of 1-7 lines")
    return counts


def comment_pool(rng: np.random.Generator) -> np.ndarray:
    """Seeded comments of 10-43 characters, from the words of dbgen's grammar."""
    out = []
    for _ in range(COMMENT_POOL):
        words = rng.choice(len(WORDS), 12)
        text = b" ".join(WORDS[w] for w in words)[: int(rng.integers(10, 44))]
        out.append(text)
    return np.array(out, dtype="S44")


def columns(config: Dict, seed: int) -> Dict[str, np.ndarray]:
    """The whole table from ``seed``, in (shipdate, orderkey, linenumber) order."""
    rng = np.random.default_rng([seed, 0])
    orders = int(config["orders"])
    parts, suppliers = int(config["parts"]), int(config["suppliers"])
    counts = line_counts(config, rng)
    n = int(counts.sum())
    index = np.arange(orders, dtype=np.int64)
    orderkey = np.repeat((index // 8) * 32 + index % 8 + 1, counts)
    orderdate = np.repeat(rng.integers(0, LAST_ORDERDATE + 1, orders), counts)
    starts = np.repeat(np.cumsum(counts) - counts, counts)
    linenumber = np.arange(n, dtype=np.int64) - starts + 1

    partkey = rng.integers(1, parts + 1, n)
    supp = rng.integers(0, 4, n)
    suppkey = (partkey + supp * (suppliers // 4 + (partkey - 1) // suppliers)) % suppliers + 1
    quantity = rng.integers(1, 51, n).astype(np.float64)
    retail_cents = 90000 + (partkey // 10) % 20001 + 100 * (partkey % 1000)
    extendedprice = quantity * retail_cents / 100.0
    discount = rng.integers(0, 11, n) / 100.0
    tax = rng.integers(0, 9, n) / 100.0
    shipdate = orderdate + rng.integers(1, 122, n)
    commitdate = orderdate + rng.integers(30, 91, n)
    receiptdate = shipdate + rng.integers(1, 31, n)
    returnflag = np.where(
        receiptdate <= CURRENTDATE,
        np.where(rng.random(n) < 0.5, b"R", b"A"),
        b"N",
    ).astype("S1")
    linestatus = np.where(shipdate > CURRENTDATE, b"O", b"F").astype("S1")
    shipinstruct = np.array(SHIPINSTRUCT, dtype="S25")[rng.integers(0, 4, n)]
    shipmode = np.array(SHIPMODE, dtype="S10")[rng.integers(0, 7, n)]
    comment = comment_pool(rng)[rng.integers(0, COMMENT_POOL, n)]

    order = np.lexsort((linenumber, orderkey, shipdate))
    cols = {
        "l_orderkey": orderkey,
        "l_partkey": partkey,
        "l_suppkey": suppkey,
        "l_linenumber": linenumber,
        "l_quantity": quantity,
        "l_extendedprice": extendedprice,
        "l_discount": discount,
        "l_tax": tax,
        "l_returnflag": returnflag,
        "l_linestatus": linestatus,
        "l_shipdate": shipdate,
        "l_commitdate": commitdate,
        "l_receiptdate": receiptdate,
        "l_shipinstruct": shipinstruct,
        "l_shipmode": shipmode,
        "l_comment": comment,
    }
    return {c: np.ascontiguousarray(cols[c][order]).astype(SCHEMA[c]) for c in SCHEMA}
