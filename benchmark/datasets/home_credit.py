"""Home Credit's application table (*Home Credit Default Risk*, Home Credit
Group on Kaggle, 2018: ``application_train.csv`` as
``HomeCredit_columns_description.csv`` lays it out): a seeded, vectorised
generator that writes the table's 122 columns as parquet part files.

    SK_ID_CURR, TARGET                      int64   the id; 1 = payment difficulties (8.07 %)
    104 numeric attributes                  65 float64, 39 int64
    16 string attributes                    2, 3, 2, 2, 7, 8, 5, 6, 6, 18, 7, 58, 4, 3, 7, 2 values

What is the source's (``LAYOUT``, ``STRINGS``): the columns, their order,
names and types, the numbers of values of the string columns and the values
themselves, ``SOURCE_ROWS``, the event rate, and the null structure: nulls in
67 columns and in no integer one; the 47 housing columns 47-70 % missing and
missing together (``HOUSING_NULLS``: one draw a row decides them all, so the
patterns are nested); ``OWN_CAR_AGE`` missing where ``FLAG_OWN_CAR`` is ``N``;
the six ``AMT_REQ_CREDIT_BUREAU_*`` missing together, as the four
``*_CNT_SOCIAL_CIRCLE``; ``DAYS_*`` counted backwards from the application,
``DAYS_EMPLOYED`` 365,243 for pensioners, who have no occupation, no employer
phone and the organisation ``XNA``.  What is assumed
(``benchmark/configs/home_credit.json`` names each): every distribution, share
and rate below, and how ``TARGET`` depends on the attributes (one latent score
a row, ``COUPLING`` says how strongly each attribute follows it).  Every
float64 value is one a float32 holds, as the program stores it, so that the
program and the plain reference bin the same numbers.  The data are made
without the program (see ``require_complete_rows_stated`` for the one question
asked of it) and with no Python loop over rows.
"""

from __future__ import annotations

import math
import os
import shutil
from typing import Iterable, Optional

import numpy as np
import pyarrow as pa
import pyarrow.parquet as pq

ROWS_PER_PART = 500_000  # income.py's, criteo_display.py's and nyc_taxi.py's: the table is one part
SOURCE_ROWS = 307_511
EVENT_RATE = 24_825 / 307_511  # TARGET = 1

HOUSING_MEASURES = ["APARTMENTS", "BASEMENTAREA", "YEARS_BEGINEXPLUATATION", "YEARS_BUILD", "COMMONAREA",
                    "ELEVATORS", "ENTRANCES", "FLOORSMAX", "FLOORSMIN", "LANDAREA", "LIVINGAPARTMENTS",
                    "LIVINGAREA", "NONLIVINGAPARTMENTS", "NONLIVINGAREA"]
HOUSING_STRINGS = ["FONDKAPREMONT_MODE", "HOUSETYPE_MODE", "WALLSMATERIAL_MODE", "EMERGENCYSTATE_MODE"]
REGION_FLAGS = ["REG_REGION_NOT_LIVE_REGION", "REG_REGION_NOT_WORK_REGION", "LIVE_REGION_NOT_WORK_REGION",
                "REG_CITY_NOT_LIVE_CITY", "REG_CITY_NOT_WORK_CITY", "LIVE_CITY_NOT_WORK_CITY"]
SOCIAL = ["OBS_30_CNT_SOCIAL_CIRCLE", "DEF_30_CNT_SOCIAL_CIRCLE", "OBS_60_CNT_SOCIAL_CIRCLE",
          "DEF_60_CNT_SOCIAL_CIRCLE"]
BUREAU = ["AMT_REQ_CREDIT_BUREAU_" + s for s in ("HOUR", "DAY", "WEEK", "MON", "QRT", "YEAR")]
DOCUMENTS = [f"FLAG_DOCUMENT_{i}" for i in range(2, 22)]

_I, _F, _S = "int64", "float64", "string"
LAYOUT = (  # the file's order
    [("SK_ID_CURR", _I), ("TARGET", _I), ("NAME_CONTRACT_TYPE", _S), ("CODE_GENDER", _S), ("FLAG_OWN_CAR", _S),
     ("FLAG_OWN_REALTY", _S), ("CNT_CHILDREN", _I), ("AMT_INCOME_TOTAL", _F), ("AMT_CREDIT", _F),
     ("AMT_ANNUITY", _F), ("AMT_GOODS_PRICE", _F), ("NAME_TYPE_SUITE", _S), ("NAME_INCOME_TYPE", _S),
     ("NAME_EDUCATION_TYPE", _S), ("NAME_FAMILY_STATUS", _S), ("NAME_HOUSING_TYPE", _S),
     ("REGION_POPULATION_RELATIVE", _F), ("DAYS_BIRTH", _I), ("DAYS_EMPLOYED", _I), ("DAYS_REGISTRATION", _F),
     ("DAYS_ID_PUBLISH", _I), ("OWN_CAR_AGE", _F), ("FLAG_MOBIL", _I), ("FLAG_EMP_PHONE", _I),
     ("FLAG_WORK_PHONE", _I), ("FLAG_CONT_MOBILE", _I), ("FLAG_PHONE", _I), ("FLAG_EMAIL", _I),
     ("OCCUPATION_TYPE", _S), ("CNT_FAM_MEMBERS", _F), ("REGION_RATING_CLIENT", _I),
     ("REGION_RATING_CLIENT_W_CITY", _I), ("WEEKDAY_APPR_PROCESS_START", _S), ("HOUR_APPR_PROCESS_START", _I)]
    + [(c, _I) for c in REGION_FLAGS]
    + [("ORGANIZATION_TYPE", _S), ("EXT_SOURCE_1", _F), ("EXT_SOURCE_2", _F), ("EXT_SOURCE_3", _F)]
    + [(m + suffix, _F) for suffix in ("_AVG", "_MODE", "_MEDI") for m in HOUSING_MEASURES]
    + [("FONDKAPREMONT_MODE", _S), ("HOUSETYPE_MODE", _S), ("TOTALAREA_MODE", _F), ("WALLSMATERIAL_MODE", _S),
       ("EMERGENCYSTATE_MODE", _S)]
    + [(c, _F) for c in SOCIAL] + [("DAYS_LAST_PHONE_CHANGE", _F)]
    + [(c, _I) for c in DOCUMENTS] + [(c, _F) for c in BUREAU])
SCHEMA = pa.schema([(name, {"int64": pa.int64(), "float64": pa.float64(), "string": pa.string()}[kind])
                    for name, kind in LAYOUT])
NUMERIC = [name for name, kind in LAYOUT if kind != _S and name not in ("SK_ID_CURR", "TARGET")]  # the 104

ORGANIZATIONS = (["Business Entity Type 3", "XNA", "Self-employed", "Other", "Medicine", "Business Entity Type 2",
                  "Government", "School", "Trade: type 7", "Kindergarten", "Construction", "Business Entity Type 1",
                  "Transport: type 4", "Trade: type 3", "Industry: type 9", "Industry: type 3", "Security",
                  "Housing", "Industry: type 11", "Military", "Bank", "Agriculture", "Police", "Transport: type 2",
                  "Postal", "Security Ministries", "Trade: type 2", "Restaurant", "Services", "University",
                  "Industry: type 7", "Transport: type 3", "Industry: type 1", "Hotel", "Electricity",
                  "Industry: type 4", "Trade: type 6", "Industry: type 5", "Insurance", "Telecom", "Emergency",
                  "Industry: type 2", "Advertising", "Realtor", "Culture", "Industry: type 12", "Trade: type 1",
                  "Mobile", "Legal Services", "Cleaning", "Transport: type 1", "Industry: type 6",
                  "Industry: type 10", "Religion", "Industry: type 13", "Trade: type 4", "Trade: type 5",
                  "Industry: type 8"])  # the source's 58, from the most frequent to the rarest
# The source's values of each string column, with assumed shares (each list sums to 1 once normalised); the
# order of a list is from the least to the most risky where the column is coupled to the score (COUPLING).
STRINGS = {
    "NAME_CONTRACT_TYPE": (["Revolving loans", "Cash loans"], [0.095, 0.905]),
    "CODE_GENDER": (["F", "M", "XNA"], [0.6583, 0.3417, 0.000013]),
    "FLAG_OWN_CAR": (["N", "Y"], [0.66, 0.34]),
    "FLAG_OWN_REALTY": (["Y", "N"], [0.694, 0.306]),
    "NAME_TYPE_SUITE": (["Unaccompanied", "Family", "Spouse, partner", "Children", "Other_B", "Other_A",
                         "Group of people"], [0.812, 0.131, 0.037, 0.0107, 0.0058, 0.0028, 0.0009]),
    "NAME_INCOME_TYPE": (["Pensioner", "State servant", "Commercial associate", "Working", "Unemployed", "Student",
                          "Businessman", "Maternity leave"],
                         [0.18, 0.0706, 0.2329, 0.5163, 0.00007, 0.00006, 0.00003, 0.000016]),
    "NAME_EDUCATION_TYPE": (["Academic degree", "Higher education", "Incomplete higher",
                             "Secondary / secondary special", "Lower secondary"],
                            [0.0005, 0.2434, 0.0334, 0.7103, 0.0124]),
    "NAME_FAMILY_STATUS": (["Widow", "Married", "Separated", "Civil marriage", "Single / not married", "Unknown"],
                           [0.0523, 0.6388, 0.0643, 0.0968, 0.1478, 0.0000065]),
    "NAME_HOUSING_TYPE": (["Office apartment", "House / apartment", "Co-op apartment", "Municipal apartment",
                           "With parents", "Rented apartment"], [0.0085, 0.8873, 0.0036, 0.0364, 0.0483, 0.0159]),
    "OCCUPATION_TYPE": (["Accountants", "High skill tech staff", "Managers", "Core staff", "HR staff", "IT staff",
                         "Private service staff", "Medicine staff", "Secretaries", "Realty agents",
                         "Cleaning staff", "Sales staff", "Cooking staff", "Laborers", "Security staff",
                         "Waiters/barmen staff", "Drivers", "Low-skill Laborers"],
                        [0.0465, 0.0539, 0.1012, 0.1306, 0.0027, 0.0025, 0.0126, 0.0404, 0.0062, 0.0036,
                         0.022, 0.152, 0.0282, 0.2614, 0.0318, 0.0064, 0.0881, 0.0099]),
    "WEEKDAY_APPR_PROCESS_START": (["MONDAY", "TUESDAY", "WEDNESDAY", "THURSDAY", "FRIDAY", "SATURDAY", "SUNDAY"],
                                   [0.165, 0.175, 0.169, 0.165, 0.164, 0.110, 0.052]),
    "ORGANIZATION_TYPE": (ORGANIZATIONS, [0.27 * (i + 1.0) ** -1.05 for i in range(55)] + [0.00021, 0.00016, 0.00008]),
    "FONDKAPREMONT_MODE": (["reg oper account", "reg oper spec account", "not specified", "org spec account"],
                           [0.757, 0.124, 0.058, 0.061]),
    "HOUSETYPE_MODE": (["block of flats", "specific housing", "terraced house"], [0.9823, 0.0098, 0.0079]),
    "WALLSMATERIAL_MODE": (["Panel", "Stone, brick", "Block", "Wooden", "Mixed", "Monolithic", "Others"],
                           [0.437, 0.429, 0.0612, 0.0355, 0.0152, 0.0118, 0.0108]),
    "EMERGENCYSTATE_MODE": (["No", "Yes"], [0.9856, 0.0144]),
}
# the share of rows in which a housing column is missing; one draw a row decides all 47, so a row that
# lacks a column lacks every column of a higher share as well
HOUSING_NULLS = {"APARTMENTS": 0.5075, "BASEMENTAREA": 0.5852, "YEARS_BEGINEXPLUATATION": 0.4878,
                 "YEARS_BUILD": 0.6650, "COMMONAREA": 0.6987, "ELEVATORS": 0.5330, "ENTRANCES": 0.5035,
                 "FLOORSMAX": 0.4976, "FLOORSMIN": 0.6785, "LANDAREA": 0.5938, "LIVINGAPARTMENTS": 0.6835,
                 "LIVINGAREA": 0.5019, "NONLIVINGAPARTMENTS": 0.6943, "NONLIVINGAREA": 0.5518,
                 "FONDKAPREMONT_MODE": 0.6839, "HOUSETYPE_MODE": 0.5018, "TOTALAREA_MODE": 0.4827,
                 "WALLSMATERIAL_MODE": 0.5084, "EMERGENCYSTATE_MODE": 0.4740}
# assumed: a housing measure is a share of a building normalised to [0, 1]: its median and spread (of a
# log-normal), and the decimals the file shows
HOUSING_LAWS = {"APARTMENTS": (0.088, 0.75), "BASEMENTAREA": (0.076, 0.70), "YEARS_BEGINEXPLUATATION": (0.9816, 0.006),
                "YEARS_BUILD": (0.755, 0.12), "COMMONAREA": (0.021, 1.00), "ELEVATORS": (0.04, 1.2),
                "ENTRANCES": (0.1379, 0.55), "FLOORSMAX": (0.1667, 0.55), "FLOORSMIN": (0.2083, 0.50),
                "LANDAREA": (0.048, 0.90), "LIVINGAPARTMENTS": (0.0756, 0.70), "LIVINGAREA": (0.0745, 0.80),
                "NONLIVINGAPARTMENTS": (0.0039, 1.5), "NONLIVINGAREA": (0.0036, 1.8)}
OTHER_NULLS = {"AMT_ANNUITY": 12 / 307_511, "AMT_GOODS_PRICE": 278 / 307_511, "NAME_TYPE_SUITE": 1_292 / 307_511,
               "CNT_FAM_MEMBERS": 2 / 307_511, "EXT_SOURCE_1": 0.5638, "EXT_SOURCE_2": 660 / 307_511,
               "EXT_SOURCE_3": 0.1983, "SOCIAL": 1_021 / 307_511, "DAYS_LAST_PHONE_CHANGE": 1 / 307_511,
               "BUREAU": 0.1350, "OCCUPATION_TYPE_OF_THE_EMPLOYED": 0.1625}
PENSIONER_SHARE, PENSIONER_DAYS = 0.18, 365_243
# assumed: how strongly an attribute follows the row's latent score (the correlation of a standard normal
# that picks its value with the score; 0 where the column is not listed): the three external scores
# strongly, so that their information value passes 0.1; most flags not at all
COUPLING = {"EXT_SOURCE_1": -0.42, "EXT_SOURCE_2": -0.45, "EXT_SOURCE_3": -0.48, "AGE": -0.22, "EMPLOYED": -0.16,
            "NAME_EDUCATION_TYPE": 0.16, "CODE_GENDER": 0.14, "NAME_INCOME_TYPE": 0.10, "OCCUPATION_TYPE": 0.14,
            "ORGANIZATION_TYPE": 0.08, "REGION_RATING_CLIENT": 0.16, "DAYS_ID_PUBLISH": -0.13,
            "DAYS_LAST_PHONE_CHANGE": -0.14, "DAYS_REGISTRATION": -0.10, "AMT_GOODS_PRICE": -0.10,
            "NAME_CONTRACT_TYPE": 0.08, "FLAG_OWN_CAR": -0.06, "NAME_HOUSING_TYPE": 0.09, "NAME_FAMILY_STATUS": 0.09,
            "REG_CITY_NOT_WORK_CITY": 0.13, "REG_CITY_NOT_LIVE_CITY": 0.12, "FLAG_DOCUMENT_3": 0.11,
            "DEF_30_CNT_SOCIAL_CIRCLE": 0.09, "FLOORSMAX": -0.11, "HOUSING": -0.07,
            "AMT_REQ_CREDIT_BUREAU_YEAR": 0.05, "OWN_CAR_AGE": 0.10}
SCORE_WEIGHT = 1.5  # the logit of TARGET is SCORE_WEIGHT x the score, shifted until the event rate is EVENT_RATE
REGION_POPULATIONS = 81  # distinct values of REGION_POPULATION_RELATIVE in the source
DOCUMENT_RATES = [0.00004, 0.71, 0.00008, 0.0151, 0.0881, 0.00019, 0.0814, 0.0039, 0.00002, 0.0039, 0.0000065,
                  0.0035, 0.0029, 0.0012, 0.0099, 0.00027, 0.0081, 0.0006, 0.0005, 0.00033]  # FLAG_DOCUMENT_2..21
# assumed, as remembered of the source: none of the 25 holders of document 4, of the students and of the
# businessmen had payment difficulties (NAME_INCOME_TYPE's indices 5 and 6): groups without an event
NEVER_DEFAULT_FLAG, NEVER_DEFAULT_INCOME_TYPES = "FLAG_DOCUMENT_4", (5, 6)
REGION_FLAG_RATES = [0.0151, 0.0508, 0.0407, 0.0782, 0.2305, 0.1796]
BUREAU_MEANS = [0.0064, 0.0070, 0.0344, 0.2674, 0.2655, 1.9000]

# the normal's distribution function by a table: 4,001 knots are exact to 1e-7, the draws need no more
_KNOTS = np.linspace(-6.0, 6.0, 4001)
_PHI = np.array([0.5 * (1.0 + math.erf(x / math.sqrt(2.0))) for x in _KNOTS])


def _f32(x: np.ndarray) -> np.ndarray:
    """The float64 of the value a float32 holds: what the table stores."""
    return np.asarray(x, np.float64).astype(np.float32).astype(np.float64)


def _few(rng: np.random.Generator, n: int, rate: float) -> np.ndarray:
    """True in ``round(rate x n)`` rows, and in one at least: a column that has a
    handful of nulls in the source has some at every seed and size."""
    out = np.zeros(n, bool)
    out[rng.choice(n, size=min(n, max(1, round(rate * n))), replace=False)] = True
    return out


def _every_value(rng: np.random.Generator, picked: np.ndarray, values: int, allowed=None) -> np.ndarray:
    """``picked`` with every index below ``values`` in it at least once (the
    source's number of values at every seed), the missing ones put into rows
    drawn among ``allowed``."""
    rows = np.arange(len(picked)) if allowed is None else np.flatnonzero(allowed)
    missing = np.setdiff1d(np.arange(values), picked[rows])
    while len(missing) and len(rows) >= values:
        picked[rng.choice(rows, size=len(missing), replace=False)] = missing
        missing = np.setdiff1d(np.arange(values), picked[rows])
    return picked


class _Draws:
    """Uniforms and normals that follow the rows' score as ``COUPLING`` says."""

    def __init__(self, rng: np.random.Generator, score: np.ndarray):
        self.rng, self.score, self.n = rng, score, len(score)

    def normal(self, key: str = "") -> np.ndarray:
        rho = COUPLING.get(key, 0.0)
        eps = self.rng.standard_normal(self.n)
        return rho * self.score + math.sqrt(1.0 - rho * rho) * eps if rho else eps

    def uniform(self, key: str = "") -> np.ndarray:
        if not COUPLING.get(key):
            return self.rng.random(self.n)
        return np.interp(self.normal(key), _KNOTS, _PHI)

    def pick(self, key: str, shares) -> np.ndarray:
        p = np.asarray(shares, np.float64)
        return np.minimum(np.searchsorted(np.cumsum(p / p.sum()), self.uniform(key), side="right"), len(p) - 1)

    def flag(self, key: str, rate: float) -> np.ndarray:
        return (self.uniform(key) > 1.0 - rate).astype(np.int64)

    def poisson(self, key: str, mean: float, most: int) -> np.ndarray:
        k = np.arange(most + 1)
        pmf = np.exp(k * math.log(mean) - mean - np.array([math.lgamma(i + 1.0) for i in k]))
        return self.pick(key, pmf)


def synthesize(rows: int, seed: int) -> dict:
    """The 122 columns as numpy arrays: a string column as indices into its
    values, a column with nulls beside ``<name>_null`` (True where missing)."""
    n = int(rows)
    rng = np.random.default_rng([int(seed), 0x40C3ED17])
    score = rng.standard_normal(n)
    d = _Draws(rng, score)
    out = {"SK_ID_CURR": 100_002 + np.arange(n, dtype=np.int64) + np.cumsum(rng.random(n) < 0.137)}

    # who: age, work, family
    age = np.clip(43.9 + 11.9 * d.normal("AGE"), 20.52, 69.12)
    retired = d.rng.random(n) < np.clip(PENSIONER_SHARE * np.exp((age - 43.9) / 6.0) / 3.3, 0.0, 0.985)
    out["DAYS_BIRTH"] = -np.round(age * 365.25).astype(np.int64)
    years_worked = np.minimum(np.exp(math.log(4.5) + 0.95 * -d.normal("EMPLOYED")), age - 18.0)
    out["DAYS_EMPLOYED"] = np.where(retired, PENSIONER_DAYS, -np.maximum(np.round(years_worked * 365.25), 0)).astype(np.int64)
    income_type = d.pick("NAME_INCOME_TYPE", STRINGS["NAME_INCOME_TYPE"][1][1:]) + 1
    out["NAME_INCOME_TYPE"] = np.where(retired, 0, _every_value(rng, income_type, 8, ~retired & (income_type > 0)))
    organization = d.pick("ORGANIZATION_TYPE", [s for i, s in enumerate(STRINGS["ORGANIZATION_TYPE"][1]) if i != 1])
    organization = _every_value(rng, organization, 57, ~retired)
    out["ORGANIZATION_TYPE"] = np.where(retired, 1, organization + (organization >= 1))  # index 1 is XNA
    out["OCCUPATION_TYPE_null"] = retired | (rng.random(n) < OTHER_NULLS["OCCUPATION_TYPE_OF_THE_EMPLOYED"])
    out["OCCUPATION_TYPE"] = _every_value(rng, d.pick("OCCUPATION_TYPE", STRINGS["OCCUPATION_TYPE"][1]), 18,
                                          ~out["OCCUPATION_TYPE_null"])
    for name in ("NAME_CONTRACT_TYPE", "CODE_GENDER", "FLAG_OWN_CAR", "FLAG_OWN_REALTY", "NAME_TYPE_SUITE",
                 "NAME_EDUCATION_TYPE", "NAME_FAMILY_STATUS", "NAME_HOUSING_TYPE", "WEEKDAY_APPR_PROCESS_START"):
        null = _few(rng, n, OTHER_NULLS[name]) if name in OTHER_NULLS else None
        out[name] = _every_value(rng, d.pick(name, STRINGS[name][1]), len(STRINGS[name][0]), None if null is None else ~null)
        if null is not None:
            out[name + "_null"] = null
    children = d.pick("", [0.7002, 0.1988, 0.0870, 0.0121, 0.0014, 0.0003, 0.0001, 0.00003, 0.00002, 0.00002])
    children = np.where(rng.random(n) < 0.00003, rng.integers(10, 20, n), children)
    out["CNT_CHILDREN"] = children.astype(np.int64)
    partnered = np.isin(out["NAME_FAMILY_STATUS"], [1, 3])  # Married, Civil marriage
    out["CNT_FAM_MEMBERS"] = (children + 1 + partnered).astype(np.float64)
    out["CNT_FAM_MEMBERS_null"] = _few(rng, n, OTHER_NULLS["CNT_FAM_MEMBERS"])

    # what is asked for: amounts, each to the cent and then to the float32 the table stores
    income = 4_500.0 * np.maximum(np.round(np.exp(math.log(150_000.0) + 0.48 * d.normal()) / 4_500.0), 6)
    income = np.where(rng.random(n) < 0.0004, np.round(income * np.exp(2.0 + rng.random(n) * 3.0), 2), income)
    out["AMT_INCOME_TOTAL"] = _f32(np.minimum(income, 117_000_000.0))
    goods = 4_500.0 * np.clip(np.round(np.exp(math.log(450_000.0) + 0.68 * d.normal("AMT_GOODS_PRICE")) / 4_500.0), 9, 900)
    credit = np.clip(np.round(goods * (1.0 + 0.25 * rng.random(n) * (rng.random(n) < 0.75)) * 2.0) / 2.0, 45_000.0, 4_050_000.0)
    out["AMT_CREDIT"], out["AMT_GOODS_PRICE"] = _f32(credit), _f32(goods)
    out["AMT_GOODS_PRICE_null"] = _few(rng, n, OTHER_NULLS["AMT_GOODS_PRICE"])
    out["AMT_ANNUITY"] = _f32(np.clip(np.round(credit * (0.025 + 0.075 * rng.random(n) ** 1.5) * 2.0) / 2.0, 1_615.5, 258_025.5))
    out["AMT_ANNUITY_null"] = _few(rng, n, OTHER_NULLS["AMT_ANNUITY"])

    # where: the region, the registration, the documents
    region = np.sort(_f32(np.round(0.00029 * (0.072508 / 0.00029) ** (np.arange(REGION_POPULATIONS) / 80.0), 6)))
    out["REGION_POPULATION_RELATIVE"] = region[d.pick("", np.exp(-0.5 * ((np.arange(REGION_POPULATIONS) - 52) / 14.0) ** 2))]
    rating = d.pick("REGION_RATING_CLIENT", [0.1047, 0.7381, 0.1572])
    out["REGION_RATING_CLIENT"] = (rating + 1).astype(np.int64)
    moved = rng.random(n)
    out["REGION_RATING_CLIENT_W_CITY"] = np.clip(rating + 1 - (moved < 0.03) + (moved > 0.99), 1, 3).astype(np.int64)
    out["HOUR_APPR_PROCESS_START"] = np.clip(np.round(12.06 + 3.27 * d.normal()), 0, 23).astype(np.int64)
    for name, rate in zip(REGION_FLAGS, REGION_FLAG_RATES):
        out[name] = d.flag(name, rate)
    days = age * 365.25
    out["DAYS_REGISTRATION"] = _f32(-np.round((days - 6_500.0).clip(400.0) * d.uniform("DAYS_REGISTRATION") ** 1.3))
    out["DAYS_ID_PUBLISH"] = -np.round(np.minimum(6_500.0 * d.uniform("DAYS_ID_PUBLISH") ** 0.8, days - 5_100.0).clip(0.0)).astype(np.int64)
    phone = np.minimum(np.round(-960.0 * np.log1p(-d.uniform("DAYS_LAST_PHONE_CHANGE") * 0.988)), 4_292.0)
    out["DAYS_LAST_PHONE_CHANGE"] = _f32(-np.where(rng.random(n) < 0.12, 0.0, phone))
    out["DAYS_LAST_PHONE_CHANGE_null"] = _few(rng, n, OTHER_NULLS["DAYS_LAST_PHONE_CHANGE"])
    out["FLAG_MOBIL"] = (rng.random(n) >= 1.0 / SOURCE_ROWS).astype(np.int64)
    out["FLAG_EMP_PHONE"] = (~retired).astype(np.int64)
    for name, rate in (("FLAG_WORK_PHONE", 0.1994), ("FLAG_CONT_MOBILE", 0.9981), ("FLAG_PHONE", 0.2811),
                       ("FLAG_EMAIL", 0.0567)):
        out[name] = d.flag(name, rate)
    for name, rate in zip(DOCUMENTS, DOCUMENT_RATES):
        out[name] = _few(rng, n, rate).astype(np.int64) if name == NEVER_DEFAULT_FLAG else d.flag(name, rate)
    out["OWN_CAR_AGE"] = _f32(np.minimum(np.round(np.exp(math.log(9.0) + 0.75 * d.normal("OWN_CAR_AGE"))), 91.0))
    out["OWN_CAR_AGE_null"] = out["FLAG_OWN_CAR"] == 0  # no car, no age

    # what others say: three external scores in (0, 1)
    for name, centre, spread in (("EXT_SOURCE_1", 0.02, 0.95), ("EXT_SOURCE_2", 0.28, 0.95), ("EXT_SOURCE_3", 0.08, 0.90)):
        out[name] = _f32(np.clip(1.0 / (1.0 + np.exp(-(centre + spread * d.normal(name)))), 1e-6, 0.96))
        out[name + "_null"] = _few(rng, n, OTHER_NULLS[name])

    # the building: 14 measures three times over, and five more columns, missing together
    absent = rng.random(n)
    building = d.normal("HOUSING")
    for m in HOUSING_MEASURES:
        median, sigma = HOUSING_LAWS[m]
        own = d.normal(m)
        avg = np.clip(median * np.exp(sigma * (0.6 * building + 0.8 * own)), 0.0, 1.0)
        if median < 0.03:  # many buildings have none of it
            avg = np.where(rng.random(n) < 0.45, 0.0, avg)
        wobble = 1.0 + 0.04 * rng.standard_normal((2, n)) * (rng.random((2, n)) < 0.35)
        for suffix, value in (("_AVG", avg), ("_MODE", np.clip(avg * wobble[0], 0.0, 1.0)),
                              ("_MEDI", np.clip(avg * wobble[1], 0.0, 1.0))):
            out[m + suffix] = _f32(np.round(value, 4))
            out[m + suffix + "_null"] = absent < HOUSING_NULLS[m]
    out["TOTALAREA_MODE"] = _f32(np.round(np.clip(0.069 * np.exp(0.8 * (0.7 * building + 0.7 * d.normal())), 0.0, 1.0), 4))
    out["TOTALAREA_MODE_null"] = absent < HOUSING_NULLS["TOTALAREA_MODE"]
    for name in HOUSING_STRINGS:
        out[name + "_null"] = absent < HOUSING_NULLS[name]
        out[name] = _every_value(rng, d.pick(name, STRINGS[name][1]), len(STRINGS[name][0]), ~out[name + "_null"])

    # the circle and the bureau
    obs30 = np.where(rng.random(n) < 0.00003, rng.integers(30, 349, n), d.poisson("", 1.42, 30))
    def30 = np.minimum(obs30, d.poisson("DEF_30_CNT_SOCIAL_CIRCLE", 0.143, 8))
    obs60 = np.maximum(obs30 - (rng.random(n) < 0.02), 0)
    def60 = np.minimum(np.maximum(def30 - (rng.random(n) < 0.3), 0), obs60)
    no_circle = _few(rng, n, OTHER_NULLS["SOCIAL"])
    for name, value in zip(SOCIAL, (obs30, def30, obs60, def60)):
        out[name], out[name + "_null"] = value.astype(np.float64), no_circle
    no_bureau = rng.random(n) < OTHER_NULLS["BUREAU"]
    for name, mean in zip(BUREAU, BUREAU_MEANS):
        out[name], out[name + "_null"] = d.poisson(name, mean, 25).astype(np.float64), no_bureau

    # the label: the score's logit, shifted by bisection until the table's expected event rate is the source's
    lo, hi = -12.0, 6.0
    for _ in range(40):
        mid = 0.5 * (lo + hi)
        lo, hi = (mid, hi) if np.mean(1.0 / (1.0 + np.exp(-(mid + SCORE_WEIGHT * score)))) < EVENT_RATE else (lo, mid)
    target = rng.random(n) < 1.0 / (1.0 + np.exp(-(lo + SCORE_WEIGHT * score)))
    # groups without an event, as the source has them: what the half-row correction of the information value is for
    spared = (out[NEVER_DEFAULT_FLAG] == 1) | np.isin(out["NAME_INCOME_TYPE"], NEVER_DEFAULT_INCOME_TYPES)
    out["TARGET"] = (target & ~spared).astype(np.int64)
    return out


def arrow_table(cols: dict, lo: int, hi: int) -> pa.Table:
    """Rows ``lo:hi`` of ``synthesize``'s columns as an Arrow table of ``SCHEMA``."""
    arrays = []
    for field in SCHEMA:
        values = cols[field.name][lo:hi]
        null = cols.get(field.name + "_null")
        null = None if null is None else null[lo:hi]
        if pa.types.is_string(field.type):
            indices = pa.array(values.astype(np.int8), mask=null)
            arrays.append(pa.DictionaryArray.from_arrays(indices, pa.array(STRINGS[field.name][0])).cast(pa.string()))
        else:
            arrays.append(pa.array(values, type=field.type, mask=null))
    return pa.Table.from_arrays(arrays, schema=SCHEMA)


def require_complete_rows_stated() -> None:
    """Stop at once where the program cannot run this deployment.  The
    comparison holds the correlation matrix to the rows complete in all 105
    columns and reads their count from the stage row on which the program
    states it; a program from before that row would run every pass of the
    window and fail each comparison, which is known before any data is made:
    say it then, with an exit code that is not 0.  (The one thing this module
    asks of the program; the data are made without it.)"""
    from anovos_tpu.data_analyzer import association_evaluator

    if getattr(association_evaluator, "COMPLETE_ROWS_ROW", None) != "assoc/corr":
        raise SystemExit("home_credit: this checkout's association_evaluator states no complete_rows on an "
                         "assoc/corr stage row (COMPLETE_ROWS_ROW): it cannot run the deployment")


def generate(dest: str, seed: int, parts: Iterable[str], rows: int,
             source_rows: Optional[int] = None) -> None:
    """Write the table under ``dest/parquet`` (``dest`` emptied first) as
    part files of ``ROWS_PER_PART`` rows, the last one the rest, in the order
    of the rows: one file at the source's 307,511.  ``parquet`` is the one
    part this dataset has; ``source_rows`` is taken and ignored (no baseline)."""
    unknown = set(parts) - {"parquet"}
    if unknown:
        raise ValueError(f"unknown dataset parts {sorted(unknown)}")
    require_complete_rows_stated()
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
