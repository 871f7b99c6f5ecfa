"""One month of NYC yellow-taxi trips, made from a seed.

The schema is the TLC's yellow trip record (its data dictionary; the 19
columns of ``yellow_tripdata_2023-01.parquet``, in their types: int64,
float64 and a one-letter ``store_and_fwd_flag``).  The values are drawn
from the seed to plausible marginals of the month; they are not the TLC's
trips.  The lake is sorted by ``tpep_pickup_datetime``, held as int64 tenths
of a second since 2023-01-01 00:00, so that every key is unique and stays
inside int32 (jax's 32-bit mode narrows int64 columns that reach a jax
stage).  Other datetimes use the same unit.

Row ``r`` of the table, counted over the base month and the fragments
appended after it, has its pickup in its own slot ``[key_bound(r),
key_bound(r + 1))``, the month's tenths of a second split evenly over its
trips.  So a window ``[key_bound(a), key_bound(b))`` holds exactly rows
``a .. b - 1``.
"""

from __future__ import annotations

from typing import Dict, Tuple

import numpy as np

SCHEMA = {
    "VendorID": "<i8",
    "tpep_pickup_datetime": "<i8",
    "tpep_dropoff_datetime": "<i8",
    "passenger_count": "<f8",
    "trip_distance": "<f8",
    "RatecodeID": "<f8",
    "store_and_fwd_flag": "|S1",
    "PULocationID": "<i8",
    "DOLocationID": "<i8",
    "payment_type": "<i8",
    "fare_amount": "<f8",
    "extra": "<f8",
    "mta_tax": "<f8",
    "tip_amount": "<f8",
    "tolls_amount": "<f8",
    "improvement_surcharge": "<f8",
    "total_amount": "<f8",
    "congestion_surcharge": "<f8",
    "airport_fee": "<f8",
}
SORT_KEY = "tpep_pickup_datetime"
MONTH_TENTHS = 31 * 24 * 3600 * 10  # January 2023


def part_rows(config: Dict, part: int) -> Tuple[int, int]:
    """``(first row, rows)`` of ``part``: 0 is the month, ``k`` the k-th
    fragment appended after it."""
    rows, frag = int(config["rows"]), int(config["rows_per_fragment"])
    return (0, rows) if part == 0 else (rows + (part - 1) * frag, frag)


def key_bound(config: Dict, row) -> int:
    """A key above every row before ``row`` and at most row ``row``'s."""
    return row * MONTH_TENTHS // int(config["rows"])


def columns(config: Dict, seed: int, part: int = 0) -> Dict[str, np.ndarray]:
    """The trips of ``part``, each part from its own stream of ``seed``."""
    first, n = part_rows(config, part)
    rng = np.random.default_rng([seed, part])
    slots = key_bound(config, np.arange(first, first + n + 1, dtype=np.int64))
    pickup = slots[:-1] + rng.integers(0, np.diff(slots))
    minutes = np.exp(rng.normal(np.log(12.0), 0.6, n))
    distance = np.round(np.exp(rng.normal(np.log(1.8), 0.9, n)), 2)
    payment = rng.choice(np.array([1, 2, 3, 4], dtype=np.int64), n, p=[0.78, 0.19, 0.01, 0.02])
    refund = rng.random(n) < 0.01
    sign = np.where(refund, -1.0, 1.0)
    fare = sign * np.round(3.0 + 1.75 * distance + 0.7 * minutes, 2)
    extra = rng.choice(np.array([0.0, 1.0, 2.5, 5.0]), n, p=[0.4, 0.3, 0.25, 0.05])
    tip = np.where(payment == 1, np.round(np.abs(fare) * rng.uniform(0.1, 0.3, n), 2), 0.0)
    tolls = np.where(rng.random(n) < 0.08, 6.55, 0.0)
    congestion = np.where(rng.random(n) < 0.92, 2.5, 0.0)
    airport = np.where(rng.random(n) < 0.09, 1.25, 0.0)
    mta, improvement = 0.5 * sign, 1.0 * sign
    return {
        "VendorID": np.where(rng.random(n) < 0.73, 2, 1).astype(np.int64),
        "tpep_pickup_datetime": pickup,
        "tpep_dropoff_datetime": pickup + np.rint(minutes * 600).astype(np.int64),
        "passenger_count": rng.choice(
            np.arange(7, dtype=np.float64), n, p=[0.02, 0.74, 0.15, 0.04, 0.02, 0.02, 0.01]
        ),
        "trip_distance": distance,
        "RatecodeID": rng.choice(
            np.array([1.0, 2.0, 3.0, 4.0, 5.0]), n, p=[0.94, 0.04, 0.005, 0.005, 0.01]
        ),
        "store_and_fwd_flag": np.where(rng.random(n) < 0.007, b"Y", b"N").astype("S1"),
        "PULocationID": rng.integers(1, 266, n, dtype=np.int64),
        "DOLocationID": rng.integers(1, 266, n, dtype=np.int64),
        "payment_type": payment,
        "fare_amount": fare,
        "extra": extra,
        "mta_tax": mta,
        "tip_amount": tip,
        "tolls_amount": tolls,
        "improvement_surcharge": improvement,
        "total_amount": np.round(fare + extra + mta + tip + tolls + improvement + congestion + airport, 2),
        "congestion_surcharge": congestion,
        "airport_fee": airport,
    }
