"""Two years of hotel searches, clicks and bookings (Expedia, *Expedia Hotel
Recommendations*, Kaggle 2016: ``train.csv`` as the competition's data page
lays it out): a seeded, vectorised generator that writes the table's 24
columns as parquet part files.

    date_time                  timestamp  to the second, 2013-01-07 .. 2014-12-31
    site_name                  int64      the point of sale's site, 2-53
    posa_continent             int64      0-4
    user_location_country      int64      0-239
    user_location_region       int64      0-1027
    user_location_city         int64      0-56508
    orig_destination_distance  double     miles, 4 decimals; empty in 36 % of the rows
    user_id                    int64      0-1198785
    is_mobile, is_package      int64      0 / 1
    channel                    int64      0-10
    srch_ci, srch_co           date       check-in and check-out; empty together in one row of 800
    srch_adults_cnt            int64      0-9
    srch_children_cnt          int64      0-9
    srch_rm_cnt                int64      0-8
    srch_destination_id        int64      0-65107
    srch_destination_type_id   int64      1-9
    is_booking                 int64      1 = a booking (8 %), 0 = a click
    cnt                        int64      similar events of the session, 1-269
    hotel_continent            int64      0-6
    hotel_country              int64      0-212
    hotel_market               int64      0-2117
    hotel_cluster              int64      0-99, the competition's target

What is the source's: the columns, their order and types, the two years, second
resolution, the ranges of the ids and codes, the two shares of empty cells,
the share of bookings, ``SOURCE_ROWS``.  What is assumed
(``benchmark/configs/expedia_hotel.json`` names each): the calendar of the
events (a trend, a yearly and a weekly season, an hour-of-day profile), the
lead between an event and its check-in and the nights stayed, the skew of
every id, the laws of distance and counts, and the random streams (numpy's,
from ``--seed``).  The few rows of the public file whose check-in year is
malformed (2161, 2558) are left out: every date here is one a guest could
mean.  ``rows`` events are drawn over the whole two years, as every 2^j-th
row of the file would be.  It imports nothing of the program and runs no
Python loop over rows.
"""

from __future__ import annotations

import os
import shutil
from typing import Iterable, Optional

import numpy as np
import pyarrow as pa
import pyarrow.parquet as pq

ROWS_PER_PART = 500_000  # nyc_taxi.py's and criteo_display.py's
SOURCE_ROWS = 37_670_293
FIRST_DAY = np.datetime64("2013-01-07", "D")  # a Monday
DAYS = 724  # ... to 2014-12-31

TIMESTAMPS = ["date_time", "srch_ci", "srch_co"]
DATES = ["srch_ci", "srch_co"]
COLUMNS = ["date_time", "site_name", "posa_continent", "user_location_country", "user_location_region",
           "user_location_city", "orig_destination_distance", "user_id", "is_mobile", "is_package", "channel",
           "srch_ci", "srch_co", "srch_adults_cnt", "srch_children_cnt", "srch_rm_cnt", "srch_destination_id",
           "srch_destination_type_id", "is_booking", "cnt", "hotel_continent", "hotel_country", "hotel_market",
           "hotel_cluster"]
NUMERIC = [c for c in COLUMNS if c not in TIMESTAMPS]  # the 21, in the file's order
SCHEMA = pa.schema([(c, pa.timestamp("s") if c == "date_time" else pa.date32() if c in DATES
                     else pa.float64() if c == "orig_destination_distance" else pa.int64()) for c in COLUMNS])

# the source's shares of empty cells and of bookings
DISTANCE_NULL_SHARE = 0.36
STAY_NULL_SHARE = 1.0 / 800  # srch_ci and srch_co, empty together
BOOKING_SHARE = 0.08
# the source's id ranges (the largest value + 1), with an assumed skew: a value's rank r is drawn with
# density r^-SKEW (Zipf-like), and the ranks are spread over the range by a fixed multiplier
IDS = {"site_name": (52, 0.9, 2), "user_location_country": (240, 0.9, 0), "user_location_region": (1028, 0.7, 0),
       "user_location_city": (56_509, 0.6, 0), "user_id": (1_198_786, 0.3, 0), "srch_destination_id": (65_108, 0.8, 0),
       "hotel_country": (213, 0.9, 0), "hotel_market": (2_118, 0.7, 0), "hotel_cluster": (100, 0.3, 0)}
# the source's small code sets, with assumed shares
CODES = {"posa_continent": ([0, 1, 2, 3, 4], [0.02, 0.12, 0.10, 0.73, 0.03]),
         "channel": (list(range(11)), [0.12, 0.10, 0.08, 0.05, 0.04, 0.06, 0.01, 0.01, 0.01, 0.50, 0.02]),
         "srch_adults_cnt": (list(range(10)), [0.002, 0.22, 0.65, 0.05, 0.06, 0.008, 0.007, 0.001, 0.001, 0.001]),
         "srch_children_cnt": (list(range(10)), [0.79, 0.10, 0.08, 0.02, 0.005, 0.002, 0.001, 0.001, 0.0005, 0.0005]),
         "srch_rm_cnt": (list(range(9)), [0.0005, 0.91, 0.07, 0.012, 0.004, 0.002, 0.001, 0.0003, 0.0002]),
         "srch_destination_type_id": (list(range(1, 10)), [0.62, 0.01, 0.06, 0.05, 0.11, 0.14, 0.005, 0.004, 0.001]),
         "hotel_continent": (list(range(7)), [0.05, 0.001, 0.55, 0.13, 0.10, 0.04, 0.129])}
MOBILE_SHARE, PACKAGE_SHARE = 0.13, 0.25
# assumed: the calendar.  Events a day grow by TREND over the two years, swing by YEARLY around a summer
# peak (day 200 of the year), and follow the weekday (Mon..Sun); the hour follows HOUR_SHARE
TREND, YEARLY, YEARLY_PEAK_DAY = 0.8, 0.25, 200
WEEKDAY_WEIGHT = [1.08, 1.10, 1.08, 1.04, 0.96, 0.84, 0.90]
HOUR_SHARE = [1.6, 1.1, 0.8, 0.7, 0.7, 0.9, 1.5, 2.6, 3.8, 4.8, 5.6, 6.0,
              6.1, 6.2, 6.2, 6.1, 6.0, 5.9, 5.8, 5.9, 5.8, 5.0, 3.9, 2.6]
# assumed: a stay starts exp(N(log 18, 1.25)) days after the event (a third within the week, a tail of
# months), at most LEAD_MAX_DAYS; it lasts 1 + a geometric number of nights, at most STAY_MAX_NIGHTS
LEAD_MEDIAN_DAYS, LEAD_SIGMA, LEAD_MAX_DAYS = 18.0, 1.25, 500
STAY_EXTRA_NIGHTS_P, STAY_MAX_NIGHTS = 0.42, 28
# assumed: miles = exp(N(log 750, 1.6)) to 4 decimals, as the public file's; similar events 1 + geometric
DISTANCE_MEDIAN, DISTANCE_SIGMA = 750.0, 1.6
CNT_P, CNT_MAX = 0.72, 269


def day_weights() -> np.ndarray:
    """The probability of each of the 724 days."""
    d = np.arange(DAYS)
    day_of_year = (FIRST_DAY + d - (FIRST_DAY + d).astype("datetime64[Y]")).astype(np.int64)
    w = ((1.0 + TREND * d / DAYS) * (1.0 + YEARLY * np.cos(2 * np.pi * (day_of_year - YEARLY_PEAK_DAY) / 365.25))
         * np.asarray(WEEKDAY_WEIGHT)[d % 7])  # day 0 is a Monday
    return w / w.sum()


def _ids(rng: np.random.Generator, name: str, n: int) -> np.ndarray:
    size, skew, first = IDS[name]
    rank = np.minimum((size * rng.random(n) ** (1.0 / (1.0 - skew))).astype(np.int64), size - 1)
    step = next(m for m in range(int(size * 0.618) | 1, 2 * size, 2) if np.gcd(m, size) == 1)
    return first + (rank * step + size // 3) % size  # popular values all over the range, not at its start


def _choice(rng: np.random.Generator, values, shares, n: int) -> np.ndarray:
    p = np.asarray(shares, np.float64)
    return np.asarray(values, np.int64)[rng.choice(len(values), size=n, p=p / p.sum())]


def synthesize(rows: int, seed: int) -> dict:
    """The 24 columns as numpy arrays: ``date_time`` int64 seconds of the epoch, the two dates int32 days
    of the epoch, and ``<name>__null`` (bool) for the three columns that have empty cells."""
    rng = np.random.default_rng(seed)
    day = rng.choice(DAYS, size=rows, p=day_weights())
    hour = rng.choice(24, size=rows, p=np.asarray(HOUR_SHARE) / np.sum(HOUR_SHARE))
    first = FIRST_DAY.astype(np.int64)  # days of the epoch
    date_time = (first + day) * 86400 + hour * 3600 + rng.integers(0, 3600, rows)
    lead = np.minimum(np.exp(np.log(LEAD_MEDIAN_DAYS) + LEAD_SIGMA * rng.standard_normal(rows)), LEAD_MAX_DAYS)
    check_in = first + day + lead.astype(np.int64)
    nights = np.minimum(rng.geometric(1.0 - STAY_EXTRA_NIGHTS_P, rows), STAY_MAX_NIGHTS)
    no_stay = rng.random(rows) < STAY_NULL_SHARE
    booking = (rng.random(rows) < BOOKING_SHARE).astype(np.int64)
    cols = {
        "date_time": date_time,
        "orig_destination_distance": np.round(
            np.exp(np.log(DISTANCE_MEDIAN) + DISTANCE_SIGMA * rng.standard_normal(rows)), 4),
        "orig_destination_distance__null": rng.random(rows) < DISTANCE_NULL_SHARE,
        "is_mobile": (rng.random(rows) < MOBILE_SHARE).astype(np.int64),
        "is_package": (rng.random(rows) < PACKAGE_SHARE).astype(np.int64),
        "srch_ci": check_in.astype(np.int32), "srch_ci__null": no_stay,
        "srch_co": (check_in + nights).astype(np.int32), "srch_co__null": no_stay,
        "is_booking": booking,
        # a booking is one event; clicks come in runs
        "cnt": np.where(booking == 1, 1, np.minimum(rng.geometric(CNT_P, rows), CNT_MAX)).astype(np.int64),
    }
    for name in IDS:
        cols[name] = _ids(rng, name, rows)
    for name, (values, shares) in CODES.items():
        cols[name] = _choice(rng, values, shares, rows)
    return cols


def arrow_table(cols: dict, lo: int, hi: int) -> pa.Table:
    def part(field):
        x = cols[field.name][lo:hi]
        null = cols.get(field.name + "__null")
        mask = None if null is None else null[lo:hi]
        if pa.types.is_timestamp(field.type):
            return pa.array(x, type=pa.int64()).cast(field.type)
        if pa.types.is_date32(field.type):
            return pa.array(x, type=pa.int32(), mask=mask).cast(field.type)
        return pa.array(x, type=field.type, mask=mask)

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
