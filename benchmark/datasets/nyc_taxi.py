"""A month of New York yellow-taxi trips (NYC Taxi and Limousine Commission,
*TLC Trip Record Data*: ``yellow_tripdata_2015-01.csv`` as the *Data Dictionary
- Yellow Taxi Trip Records* of that year lays it out): a seeded, vectorised
generator that writes the table's 19 columns as parquet part files.

    VendorID               int64      1-2
    tpep_pickup_datetime   timestamp  to the second, local time, no zone
    tpep_dropoff_datetime  timestamp  to the second, local time, no zone
    passenger_count        int64      0-9
    trip_distance          double     miles, two decimals
    pickup_longitude       double     a float32's value; 0 where the GPS had no fix
    pickup_latitude        double
    RateCodeID             int64      1-6 and 99
    store_and_fwd_flag     string     Y / N
    dropoff_longitude      double
    dropoff_latitude       double
    payment_type           int64      1-5
    fare_amount            double     money to the cent
    extra, mta_tax, tip_amount, tolls_amount, improvement_surcharge  double
    total_amount           double     the sum of the six amounts before it

What is the source's: the columns, their order and types, the month, second
resolution, the code sets, money to the cent, ``SOURCE_ROWS``.  What is
assumed (``benchmark/configs/nyc_taxi.json`` names each): the hour-of-day and
weekday profile of the pick-ups, the trip durations, the mixture the
coordinates are drawn from and the share of ``0, 0``, the laws of distance and
amounts, that no column has a null (the public file has next to none) and the
random streams (numpy's, from ``--seed``).  ``rows`` trips are drawn over the
whole month, as every 2^j-th row of the file would be.  It imports nothing of
the program and runs no Python loop over rows.
"""

from __future__ import annotations

import os
import shutil
from typing import Iterable, Optional

import numpy as np
import pyarrow as pa
import pyarrow.parquet as pq

ROWS_PER_PART = 500_000  # income.py's and criteo_display.py's
SOURCE_ROWS = 12_748_986
MONTH_START = np.datetime64("2015-01-01T00:00:00", "s")  # a Thursday
MONTH_DAYS = 31

INTEGERS = ["VendorID", "passenger_count", "RateCodeID", "payment_type"]
COORDINATES = ["pickup_longitude", "pickup_latitude", "dropoff_longitude", "dropoff_latitude"]
AMOUNTS = ["fare_amount", "extra", "mta_tax", "tip_amount", "tolls_amount", "improvement_surcharge"]
TIMESTAMPS = ["tpep_pickup_datetime", "tpep_dropoff_datetime"]
NUMERIC = ["VendorID", "passenger_count", "trip_distance", "pickup_longitude", "pickup_latitude",
           "RateCodeID", "dropoff_longitude", "dropoff_latitude", "payment_type",
           *AMOUNTS, "total_amount"]  # the 16, in the file's order
SCHEMA = pa.schema([
    ("VendorID", pa.int64()), ("tpep_pickup_datetime", pa.timestamp("s")),
    ("tpep_dropoff_datetime", pa.timestamp("s")), ("passenger_count", pa.int64()),
    ("trip_distance", pa.float64()), ("pickup_longitude", pa.float64()),
    ("pickup_latitude", pa.float64()), ("RateCodeID", pa.int64()),
    ("store_and_fwd_flag", pa.string()), ("dropoff_longitude", pa.float64()),
    ("dropoff_latitude", pa.float64()), ("payment_type", pa.int64()),
    ("fare_amount", pa.float64()), ("extra", pa.float64()), ("mta_tax", pa.float64()),
    ("tip_amount", pa.float64()), ("tolls_amount", pa.float64()),
    ("improvement_surcharge", pa.float64()), ("total_amount", pa.float64())])

# the source's code sets, with assumed shares
VENDORS = ([1, 2], [0.47, 0.53])
PASSENGERS = (list(range(10)), [0.0005, 0.7040, 0.1400, 0.0410, 0.0200, 0.0550, 0.0390, 0.0002, 0.0002, 0.0001])
RATE_CODES = ([1, 2, 3, 4, 5, 6, 99], [0.9730, 0.0210, 0.0020, 0.0005, 0.0034, 0.00005, 0.00005])
PAYMENT_TYPES = ([1, 2, 3, 4, 5], [0.6200, 0.3750, 0.0035, 0.0014, 0.0001])
STORE_AND_FWD_Y = 0.009

# assumed: pick-ups per weekday (Mon..Sun) and per hour of a weekday / of a weekend day
WEEKDAY_WEIGHT = [0.90, 0.96, 1.00, 1.05, 1.10, 1.12, 0.95]
HOUR_WEEKDAY = [1.6, 1.0, 0.7, 0.5, 0.5, 0.8, 2.2, 3.9, 4.8, 4.8, 4.6, 4.7,
                5.0, 5.0, 5.2, 5.0, 4.4, 5.2, 6.3, 6.4, 5.9, 5.7, 5.2, 3.6]
HOUR_WEEKEND = [5.0, 4.4, 3.6, 2.7, 1.6, 0.8, 0.8, 1.2, 2.0, 3.2, 4.3, 5.0,
                5.4, 5.4, 5.4, 5.3, 5.1, 5.4, 6.0, 6.0, 5.4, 5.2, 5.4, 5.4]
# assumed: a trip lasts exp(N(log 660 s, 0.75)), at least a second; one in 500 is a
# meter left running, uniform up to a day less a second: the heavy tail, and
# why the drop-offs run into 1 February
DURATION_MEDIAN_S, DURATION_SIGMA = 660.0, 0.75
LONG_TRIP_SHARE, LONG_TRIP_MAX_S = 0.002, 86_399
# assumed: where a trip starts or ends (longitude, latitude, spread in degrees, share)
PLACES = [(-73.982, 40.752, 0.022, 0.900), (-73.872, 40.774, 0.004, 0.035),
          (-73.782, 40.645, 0.006, 0.025), (-73.950, 40.700, 0.050, 0.040)]
NO_FIX_SHARE = 0.018  # rows whose GPS had no fix: 0, 0 at both ends (nine in ten) or at one
# assumed: miles = exp(N(log 1.7, 0.85)), two decimals; airport runs (rate 2) N(17.5, 1.5)
DISTANCE_MEDIAN, DISTANCE_SIGMA = 1.7, 0.85


def _choice(rng: np.random.Generator, values, shares, n: int) -> np.ndarray:
    p = np.asarray(shares, np.float64)
    return np.asarray(values, np.int64)[rng.choice(len(values), size=n, p=p / p.sum())]


def pickup_cells() -> np.ndarray:
    """The probability of each (day of the month, hour) cell, 31 x 24, flat."""
    dow = (np.arange(MONTH_DAYS) + 3) % 7  # 1 January 2015 is a Thursday; Mon=0
    hours = np.where((dow >= 5)[:, None], np.asarray(HOUR_WEEKEND), np.asarray(HOUR_WEEKDAY))
    w = np.asarray(WEEKDAY_WEIGHT)[dow][:, None] * hours / hours.sum(axis=1, keepdims=True)
    return (w / w.sum()).ravel()


def _coordinates(rng: np.random.Generator, n: int):
    place = rng.choice(len(PLACES), size=n, p=[p[3] for p in PLACES])
    lon0, lat0, spread, _ = (np.asarray(x) for x in zip(*PLACES))
    lon = lon0[place] + spread[place] * rng.standard_normal(n)
    lat = lat0[place] + spread[place] * rng.standard_normal(n)
    # the public file's coordinates are float32 values written out in full
    return lon.astype(np.float32).astype(np.float64), lat.astype(np.float32).astype(np.float64)


def synthesize(rows: int, seed: int) -> dict:
    """The 19 columns as numpy arrays (the timestamps int64 seconds of the epoch)."""
    rng = np.random.default_rng(seed)
    cell = rng.choice(MONTH_DAYS * 24, size=rows, p=pickup_cells())
    start = MONTH_START.astype(np.int64)
    pickup = start + cell * 3600 + rng.integers(0, 3600, rows)
    duration = np.maximum(1, np.exp(np.log(DURATION_MEDIAN_S) + DURATION_SIGMA * rng.standard_normal(rows)))
    long_trip = rng.random(rows) < LONG_TRIP_SHARE
    duration = np.where(long_trip, rng.integers(3600, LONG_TRIP_MAX_S + 1, rows),
                        np.minimum(duration, LONG_TRIP_MAX_S)).astype(np.int64)
    rate = _choice(rng, *RATE_CODES, rows)
    airport = rate == 2
    miles = np.exp(np.log(DISTANCE_MEDIAN) + DISTANCE_SIGMA * rng.standard_normal(rows))
    miles = np.round(np.where(airport, np.abs(17.5 + 1.5 * rng.standard_normal(rows)), miles), 2)
    plon, plat = _coordinates(rng, rows)
    dlon, dlat = _coordinates(rng, rows)
    no_fix, which = rng.random(rows) < NO_FIX_SHARE, rng.random(rows)
    p_off, d_off = no_fix & (which < 0.95), no_fix & (which > 0.05)
    plon, plat = np.where(p_off, 0.0, plon), np.where(p_off, 0.0, plat)
    dlon, dlat = np.where(d_off, 0.0, dlon), np.where(d_off, 0.0, dlat)
    payment = _choice(rng, *PAYMENT_TYPES, rows)

    # money in whole cents, so that total_amount is the sum of its parts to the cent
    metered = 250 + 50 * np.round((250 * miles + 40 * (duration / 60.0)) / 50.0)  # 50 c steps
    negotiated = np.round(100 * np.exp(np.log(60.0) + 0.9 * rng.standard_normal(rows)))  # rate 5: a tail above 180
    fare = np.where(airport, 5200, np.where(rate == 5, negotiated, np.minimum(metered, 50_000))).astype(np.int64)
    hour = (pickup - start) // 3600 % 24
    weekday = ((pickup - start) // 86400 + 3) % 7 < 5
    extra = np.where((hour >= 20) | (hour < 6), 50, np.where(weekday & (hour >= 16), 100, 0))
    extra = np.where(airport | (rate == 5), 0, extra).astype(np.int64)
    mta_tax = np.where((rate == 5) | (rng.random(rows) < 0.004), 0, 50).astype(np.int64)
    tip_share = np.clip(0.20 + 0.06 * rng.standard_normal(rows), 0.0, 1.0)
    tip = np.where((payment == 1) & (rng.random(rows) > 0.03), np.round(fare * tip_share), 0).astype(np.int64)
    tolls = np.where(rng.random(rows) < np.where(airport, 0.55, 0.03), 533, 0).astype(np.int64)
    surcharge = np.where(rng.random(rows) < 0.001, 0, 30).astype(np.int64)
    cents = {"fare_amount": fare, "extra": extra, "mta_tax": mta_tax, "tip_amount": tip, "tolls_amount": tolls,
             "improvement_surcharge": surcharge, "total_amount": fare + extra + mta_tax + tip + tolls + surcharge}
    return {
        "VendorID": _choice(rng, *VENDORS, rows),
        "tpep_pickup_datetime": pickup, "tpep_dropoff_datetime": pickup + duration,
        "passenger_count": _choice(rng, *PASSENGERS, rows),
        "trip_distance": miles, "pickup_longitude": plon, "pickup_latitude": plat,
        "RateCodeID": rate,
        "store_and_fwd_flag": rng.random(rows) < STORE_AND_FWD_Y,  # True: Y
        "dropoff_longitude": dlon, "dropoff_latitude": dlat, "payment_type": payment,
        **{name: amount / 100.0 for name, amount in cents.items()},
    }


def arrow_table(cols: dict, lo: int, hi: int) -> pa.Table:
    def part(field):
        x = cols[field.name][lo:hi]
        if field.name == "store_and_fwd_flag":
            return pa.DictionaryArray.from_arrays(
                pa.array(x.astype(np.int8)), pa.array(["N", "Y"])).cast(pa.string())
        return pa.array(x, type=pa.int64()).cast(field.type) if pa.types.is_timestamp(field.type) \
            else pa.array(x, type=field.type)

    return pa.Table.from_arrays([part(f) for f in SCHEMA], schema=SCHEMA)


def generate(dest: str, seed: int, parts: Iterable[str], rows: int,
             source_rows: Optional[int] = None) -> None:
    """Write the table under ``dest/parquet`` (``dest`` emptied first) as
    part files of ``ROWS_PER_PART`` rows, the last one the rest, in the order
    of the rows.  ``parquet`` is the one part this dataset has;
    ``source_rows`` is taken and ignored (no baseline)."""
    unknown = set(parts) - {"parquet"}
    if unknown:
        raise ValueError(f"unknown dataset parts {sorted(unknown)}")
    shutil.rmtree(dest, ignore_errors=True)
    os.makedirs(dest)
    if "parquet" not in set(parts):
        return
    out_dir = os.path.join(dest, "parquet")
    os.makedirs(out_dir)
    cols = synthesize(rows, seed)
    for i, lo in enumerate(range(0, rows, ROWS_PER_PART)):
        pq.write_table(arrow_table(cols, lo, min(lo + ROWS_PER_PART, rows)),
                       os.path.join(out_dir, f"part-{i:05d}.parquet"))
