"""Lending Club's loan data 2007-2018Q4 as published on Kaggle
(``wordsforthewise/lending-club``, ``accepted_2007_to_2018Q4.csv``, columns per
Lending Club's ``LCDataDictionary.xlsx``): a seeded, vectorised generator that
writes the table's yearly vintages 2012 ... 2018 as parquet, each with the
source's 151 columns in file order (113 ``float64``, 38 strings).

    parquet              the 2018 vintage        (the table under test)
    source               the 2015 vintage        (the one the scorecard was developed on)
    stability_index/0-6  the vintages 2012-2018  (3 = source, 6 = parquet: the same bytes)

What is the source's: the column names, their order and types, the loans a
year (``PUBLISHED_ROWS``), the class of every string column's cardinality and
the null structure by vintage (``benchmark/configs/lending_club.json``,
``published``).  What is assumed (the same file names each under ``assumed``):
every distribution and how it moves by year, the laws the free-text columns
are drawn from, the random streams (numpy's, from ``--seed`` and the year).
The vintages 2007-2011 and the files' 33 footer lines are left out.  It
imports nothing of the program and runs no Python loop over rows.
"""

from __future__ import annotations

import functools
import os
import shutil
from concurrent.futures import ThreadPoolExecutor
from typing import Iterable, Optional, Sequence

import numpy as np
import pyarrow as pa
import pyarrow.compute as pc
import pyarrow.parquet as pq

ROWS_PER_PART = 500_000  # income.py's: a vintage is one part file
YEARS = (2012, 2013, 2014, 2015, 2016, 2017, 2018)
PUBLISHED_ROWS = {2007: 603, 2008: 2_393, 2009: 5_281, 2010: 12_537, 2011: 21_721, 2012: 53_367,
                  2013: 134_814, 2014: 235_629, 2015: 421_095, 2016: 434_407, 2017: 443_579, 2018: 495_242}
TARGET_YEAR, SOURCE_YEAR = 2018, 2015
MONTHS = ("Jan", "Feb", "Mar", "Apr", "May", "Jun", "Jul", "Aug", "Sep", "Oct", "Nov", "Dec")
SNAPSHOT = (2019, 3)  # the month the file was drawn: the newest last_pymnt_d and last_credit_pull_d

_LAYOUT = """id member_id loan_amnt funded_amnt funded_amnt_inv term int_rate installment grade sub_grade
emp_title emp_length home_ownership annual_inc verification_status issue_d loan_status pymnt_plan url desc purpose
title zip_code addr_state dti delinq_2yrs earliest_cr_line fico_range_low fico_range_high inq_last_6mths
mths_since_last_delinq mths_since_last_record open_acc pub_rec revol_bal revol_util total_acc initial_list_status
out_prncp out_prncp_inv total_pymnt total_pymnt_inv total_rec_prncp total_rec_int total_rec_late_fee recoveries
collection_recovery_fee last_pymnt_d last_pymnt_amnt next_pymnt_d last_credit_pull_d last_fico_range_high
last_fico_range_low collections_12_mths_ex_med mths_since_last_major_derog policy_code application_type
annual_inc_joint dti_joint verification_status_joint acc_now_delinq tot_coll_amt tot_cur_bal open_acc_6m open_act_il
open_il_12m open_il_24m mths_since_rcnt_il total_bal_il il_util open_rv_12m open_rv_24m max_bal_bc all_util
total_rev_hi_lim inq_fi total_cu_tl inq_last_12m acc_open_past_24mths avg_cur_bal bc_open_to_buy bc_util
chargeoff_within_12_mths delinq_amnt mo_sin_old_il_acct mo_sin_old_rev_tl_op mo_sin_rcnt_rev_tl_op mo_sin_rcnt_tl
mort_acc mths_since_recent_bc mths_since_recent_bc_dlq mths_since_recent_inq mths_since_recent_revol_delinq
num_accts_ever_120_pd num_actv_bc_tl num_actv_rev_tl num_bc_sats num_bc_tl num_il_tl num_op_rev_tl num_rev_accts
num_rev_tl_bal_gt_0 num_sats num_tl_120dpd_2m num_tl_30dpd num_tl_90g_dpd_24m num_tl_op_past_12m pct_tl_nvr_dlq
percent_bc_gt_75 pub_rec_bankruptcies tax_liens tot_hi_cred_lim total_bal_ex_mort total_bc_limit
total_il_high_credit_limit revol_bal_joint sec_app_fico_range_low sec_app_fico_range_high sec_app_earliest_cr_line
sec_app_inq_last_6mths sec_app_mort_acc sec_app_open_acc sec_app_revol_util sec_app_open_act_il
sec_app_num_rev_accts sec_app_chargeoff_within_12_mths sec_app_collections_12_mths_ex_med
sec_app_mths_since_last_major_derog hardship_flag hardship_type hardship_reason hardship_status deferral_term
hardship_amount hardship_start_date hardship_end_date payment_plan_start_date hardship_length hardship_dpd
hardship_loan_status orig_projected_additional_accrued_interest hardship_payoff_balance_amount
hardship_last_payment_amount disbursement_method debt_settlement_flag debt_settlement_flag_date settlement_status
settlement_date settlement_amount settlement_percentage settlement_term"""
COLUMNS = _LAYOUT.split()  # the file's order, 151
STRINGS = """id term grade sub_grade emp_title emp_length home_ownership verification_status issue_d loan_status
pymnt_plan url desc purpose title zip_code addr_state earliest_cr_line initial_list_status last_pymnt_d next_pymnt_d
last_credit_pull_d application_type verification_status_joint sec_app_earliest_cr_line hardship_flag hardship_type
hardship_reason hardship_status hardship_start_date hardship_end_date payment_plan_start_date hardship_loan_status
disbursement_method debt_settlement_flag debt_settlement_flag_date settlement_status settlement_date""".split()  # the 38
NUMERIC = [c for c in COLUMNS if c not in STRINGS]  # the 113, member_id among them
SCHEMA = pa.schema([(c, pa.string() if c in STRINGS else pa.float64()) for c in COLUMNS])

# the null structure: blocks of columns that appear together in their year
BUREAU_2015_12 = """open_acc_6m open_act_il open_il_12m open_il_24m mths_since_rcnt_il total_bal_il il_util open_rv_12m
open_rv_24m max_bal_bc all_util inq_fi total_cu_tl inq_last_12m""".split()  # from December 2015 on (14)
_BLOCK_2012 = COLUMNS[COLUMNS.index("acc_open_past_24mths"):COLUMNS.index("total_il_high_credit_limit") + 1]
BUREAU_2012_07 = ["tot_coll_amt", "tot_cur_bal", "total_rev_hi_lim"] + _BLOCK_2012  # from mid-2012 on
JOINT = ["annual_inc_joint", "dti_joint", "verification_status_joint"]  # from late 2015 on
SECOND_APPLICANT = COLUMNS[COLUMNS.index("revol_bal_joint"):COLUMNS.index("sec_app_mths_since_last_major_derog") + 1]
HARDSHIP = COLUMNS[COLUMNS.index("hardship_type"):COLUMNS.index("hardship_last_payment_amount") + 1]  # 14
SETTLEMENT = COLUMNS[COLUMNS.index("debt_settlement_flag_date"):]  # the six after debt_settlement_flag

# ---- assumed: the closed vocabularies, most frequent first, and their shares ----
GRADES = "ABCDEFG"
GRADE_MIX = {2012: (.16, .35, .24, .14, .07, .03, .01), 2013: (.13, .33, .28, .15, .07, .035, .005),
             2014: (.16, .28, .28, .16, .08, .03, .01), 2015: (.175, .28, .285, .15, .08, .025, .005),
             2016: (.165, .30, .30, .14, .065, .025, .005), 2017: (.17, .30, .33, .14, .045, .012, .003),
             2018: (.27, .29, .27, .13, .035, .004, .001)}
TERM_36 = {2012: .82, 2013: .75, 2014: .69, 2015: .67, 2016: .74, 2017: .73, 2018: .70}
RATE_SHIFT = {2012: 1.1, 2013: 1.6, 2014: 0.9, 2015: 0.0, 2016: 0.3, 2017: 0.4, 2018: 0.2}  # points of int_rate
LOAN_MEDIAN = {2012: 11_800, 2013: 12_900, 2014: 13_200, 2015: 13_600, 2016: 12_900, 2017: 13_000, 2018: 14_200}
INCOME_MEDIAN = {2012: 59_500, 2013: 62_500, 2014: 64_000, 2015: 65_500, 2016: 67_000, 2017: 68_500, 2018: 70_000}
DTI_MEAN = {2012: 16.7, 2013: 17.2, 2014: 18.0, 2015: 19.1, 2016: 18.7, 2017: 18.9, 2018: 19.5}
FICO_SCALE = {2012: 33.0, 2013: 30.0, 2014: 30.0, 2015: 30.5, 2016: 31.5, 2017: 33.0, 2018: 37.0}  # over 660
INQ_6M = {2012: .85, 2013: .80, 2014: .72, 2015: .62, 2016: .57, 2017: .50, 2018: .44}
REVOL_UTIL = {2012: 58.0, 2013: 57.5, 2014: 55.5, 2015: 53.5, 2016: 51.0, 2017: 48.5, 2018: 45.5}
EMP_TITLE_NULL = {2012: .062, 2013: .060, 2014: .055, 2015: .057, 2016: .065, 2017: .070, 2018: .085}
DESC_FILLED = {2012: .45, 2013: .30, 2014: .064, 2015: .0001, 2016: .00003, 2017: 0.0, 2018: 0.0}
JOINT_SHARE = {2012: 0.0, 2013: 0.0, 2014: 0.0, 2015: .004, 2016: .012, 2017: .065, 2018: .14}  # 2015: of Oct-Dec
SECOND_SHARE = {2017: .6, 2018: .96}  # of the joint applications, from 2017 on
CURRENT_SHARE = {2012: 0.0, 2013: 0.0, 2014: .08, 2015: .15, 2016: .40, 2017: .65, 2018: .88}  # still being paid
CHARGED_OFF = {2012: .16, 2013: .155, 2014: .175, 2015: .19, 2016: .18, 2017: .12, 2018: .035}
LIST_W = {2012: .25, 2013: .30, 2014: .50, 2015: .60, 2016: .72, 2017: .75, 2018: .80}  # initial_list_status w
VERIFIED = {2012: (.33, .28, .39), 2013: (.33, .30, .37), 2014: (.28, .35, .37), 2015: (.28, .40, .32),
            2016: (.31, .40, .29), 2017: (.33, .39, .28), 2018: (.40, .36, .24)}  # Not Verified, Source Verified, Verified
HOME = (("MORTGAGE", .493), ("RENT", .396), ("OWN", .1105), ("ANY", .0004), ("OTHER", .00007), ("NONE", .00003))
EMP_LENGTH = (("10+ years", .349), ("2 years", .095), ("< 1 year", .087), ("3 years", .084), ("1 year", .069),
              ("5 years", .066), ("4 years", .063), ("6 years", .049), ("8 years", .046), ("7 years", .046),
              ("9 years", .040))  # 11 values; the rest (6 %) is missing
PURPOSE = (("debt_consolidation", .565), ("credit_card", .228), ("home_improvement", .066), ("other", .061),
           ("major_purchase", .022), ("medical", .012), ("small_business", .011), ("car", .0105), ("vacation", .007),
           ("moving", .007), ("house", .006), ("wedding", .001), ("renewable_energy", .0007), ("educational", .0002))
PURPOSE_TITLE = ("Debt consolidation", "Credit card refinancing", "Home improvement", "Other", "Major purchase",
                 "Medical expenses", "Business", "Car financing", "Vacation", "Moving and relocation", "Home buying",
                 "Wedding expenses", "Green loan", "Learning and training")
STATES = ("CA NY TX FL IL NJ PA OH GA VA NC MI MD AZ MA CO WA MN IN MO TN CT NV WI AL OR SC LA KY OK KS AR UT NM MS "
          "HI NH RI WV NE MT DE DC AK WY VT SD ME ID ND IA").split()  # 51, by loans
LATE_STATUS = ("Late (31-120 days)", "In Grace Period", "Late (16-30 days)", "Default")
HARDSHIP_REASON = ("NATURAL_DISASTER", "EXCESSIVE_OBLIGATIONS", "UNEMPLOYMENT", "INCOME_CURTAILMENT", "MEDICAL",
                   "REDUCED_HOURS", "DIVORCE", "FAMILY_DEATH", "DISABILITY")
HARDSHIP_STATUS = ("COMPLETED", "BROKEN", "ACTIVE")
HARDSHIP_LOAN_STATUS = ("Late (16-30 days)", "Current", "In Grace Period", "Late (31-120 days)", "Issued")
SETTLEMENT_STATUS = ("ACTIVE", "COMPLETE", "BROKEN")
EMP_TITLES = (("Teacher", .0180), ("Manager", .0165), ("Owner", .0105), ("Registered Nurse", .0080), ("RN", .0075),
              ("Supervisor", .0070), ("Driver", .0065), ("Sales", .0062), ("Project Manager", .0052),
              ("Office Manager", .0046), ("General Manager", .0042), ("Director", .0038), ("owner", .0036),
              ("manager", .0034), ("Engineer", .0032), ("President", .0030), ("teacher", .0028),
              ("Vice President", .0026), ("Operations Manager", .0025), ("Accountant", .0024))
_WORDS_A = ("Senior", "Lead", "Assistant", "Associate", "Chief", "Junior", "Staff", "Principal", "Regional", "District",
            "Head", "Deputy", "Certified", "Licensed", "Night", "Field", "Corporate", "Executive", "General", "Senior Lead",
            "Sr", "Jr", "Asst", "Assoc", "senior", "lead", "assistant", "associate", "Interim", "Global", "Area", "Shift")
_WORDS_B = ("Analyst", "Engineer", "Technician", "Nurse", "Clerk", "Driver", "Mechanic", "Consultant", "Specialist",
            "Coordinator", "Administrator", "Manager", "Supervisor", "Officer", "Operator", "Director", "Representative",
            "Agent", "Inspector", "Planner", "Buyer", "Designer", "Developer", "Architect", "Auditor", "Teller", "Cashier",
            "Foreman", "Electrician", "Plumber", "Welder", "Machinist", "Therapist", "Pharmacist", "Paralegal", "Attorney",
            "Instructor", "Professor", "Counselor", "Dispatcher", "Estimator", "Controller", "Bookkeeper", "Underwriter",
            "Processor", "Scheduler", "Recruiter", "Trainer", "Superintendent", "Custodian", "Carpenter", "Painter",
            "Chef", "Cook", "Server", "Bartender", "Stylist", "Pilot", "Conductor", "Lineman", "Programmer", "Scientist",
            "Chemist", "Surveyor")
_WORDS_C = ("Sales", "Operations", "Finance", "Accounting", "Logistics", "Marketing", "Services", "Support", "Systems",
            "Security", "Quality", "Safety", "Maintenance", "Production", "Purchasing", "Payroll", "Billing", "Claims",
            "Compliance", "Facilities", "Engineering", "Research", "Development", "Radiology", "Surgery", "Pediatrics",
            "Transportation", "Warehouse", "Distribution", "Construction", "Manufacturing", "Retail", "Wholesale",
            "Insurance", "Banking", "Lending", "Mortgage", "Admissions", "Athletics", "Housing", "Dining", "Grounds",
            "Fleet", "Parts", "Service", "Networks", "Infrastructure", "Data", "Analytics", "Benefits", "Training",
            "Recruiting", "Legal", "Contracts", "Procurement", "Inventory", "Shipping", "Receiving", "Assembly",
            "Fabrication", "Testing", "Design", "Planning", "Strategy")
# assumed: P(rank r) ~ (r + offset)^-exponent over a universe of that many values, under the named head
EMP_TITLE_LAW = (1 << 21, 1.03, 40.0)   # ~150,000 values among 495,242 rows, ~480,000 among 2.26 million
TITLE_LAW = (1 << 18, 1.25, 6.0)        # behind the purposes' own titles (85-99 % of a vintage by year)
ZIP_LAW = (956, 0.75, 12.0)             # "945xx": three digits and xx
TITLE_FREE = {2012: .55, 2013: .45, 2014: .13, 2015: .025, 2016: .012, 2017: .012, 2018: .012}  # typed, not chosen
CREDIT_LINE_YEARS = 66                  # earliest_cr_line: the months of the 66 years before the vintage's (<= 792)


def _f32(x: np.ndarray) -> np.ndarray:
    """Float64 values a float32 can hold: what the table stores, so that the
    program and the reference bin and add up the same numbers."""
    return np.asarray(x, np.float64).astype(np.float32).astype(np.float64)


@functools.lru_cache(maxsize=None)  # one law, seven vintages
def _cdf(universe: int, exponent: float, offset: float) -> np.ndarray:
    w = (np.arange(1, universe + 1, dtype=np.float64) + offset) ** -exponent
    c = np.cumsum(w)
    return c / c[-1]


def _ranks(rng: np.random.Generator, n: int, cdf: np.ndarray) -> np.ndarray:
    return np.minimum(np.searchsorted(cdf, rng.random(n)), len(cdf) - 1)


def _pick(rng: np.random.Generator, n: int, shares: Sequence[float]) -> np.ndarray:
    """``n`` indices into ``shares`` (normalised), every index present where ``n`` allows."""
    p = np.asarray(shares, np.float64)
    out = np.minimum(np.searchsorted(np.cumsum(p / p.sum()), rng.random(n)), len(p) - 1)
    live = np.flatnonzero(p > 0)
    if n >= 4 * len(live):  # a closed vocabulary shows every value in every vintage
        out[rng.choice(n, len(live), replace=False)] = live
    return out


def _strings(values: Sequence[str], idx: np.ndarray, null: Optional[np.ndarray] = None) -> pa.Array:
    """``values[idx]`` as an Arrow string array, null where ``null``."""
    taken = pa.array(values, pa.string()).take(pa.array(idx.astype(np.int64)))
    if null is None or not null.any():
        return taken
    return pc.if_else(pa.array(~null), taken, pa.scalar(None, pa.string()))


def _month_names(first_year: int, years: int) -> list:
    return [f"{m}-{y}" for y in range(first_year, first_year + years) for m in MONTHS]


def emp_title_universe() -> pa.Array:
    """The 2^21 values an ``emp_title`` is drawn from: the named head, then
    three words of free text, a value a rank."""
    universe, _, _ = EMP_TITLE_LAW
    k = np.arange(universe, dtype=np.int64)
    a, b, c = pa.array(_WORDS_A), pa.array(_WORDS_B), pa.array(_WORDS_C)
    na, nb, nc = len(_WORDS_A), len(_WORDS_B), len(_WORDS_C)
    words = pc.binary_join_element_wise(a.take(pa.array(k % na)), b.take(pa.array((k // na) % nb)),
                                        c.take(pa.array((k // (na * nb)) % nc)), " ")
    tail = pc.binary_join_element_wise(words, pa.array(k // (na * nb * nc)).cast(pa.string()), " ")
    head = pa.array([t for t, _ in EMP_TITLES], pa.string())
    return pa.concat_arrays([head, tail.slice(len(head))])


def _null_where(values: np.ndarray, null: np.ndarray) -> np.ndarray:
    out = np.array(values, np.float64)
    out[null] = np.nan
    return out


def synthesize(year: int, rows: int, seed: int, emp_titles: Optional[pa.Array] = None) -> pa.Table:
    """One vintage: ``rows`` loans issued in ``year``, the 151 columns."""
    rng = np.random.default_rng([int(seed) & 0xFFFFFFFF, int(seed) >> 32, year])
    n, y = rows, year
    col: dict = {}

    def flag(rate):
        return rng.random(n) < rate

    def count(mean, most):  # a small count with a long tail
        return np.minimum(rng.poisson(mean * rng.gamma(1.0, 1.0, n)), most).astype(np.float64)

    def amount(median, sigma, step=1.0, most=np.inf):
        return _f32(np.minimum(np.round(median * np.exp(sigma * rng.standard_normal(n)) / step) * step, most))

    def months(mean, most):
        return np.minimum(np.floor(rng.gamma(1.6, mean / 1.6, n)), most)

    # ---- when, and which blocks of columns the loan has ----
    month = _pick(rng, n, np.linspace(0.8, 1.25, 12) if y <= 2014 else np.ones(12))  # 0-based; the book grew
    col["issue_d"] = _strings(_month_names(y, 1), month)
    def some(mask, among):  # a rare block shows in every vintage that has it, whatever the size
        if n >= 64 and not mask.any() and among.any():
            mask[rng.choice(np.flatnonzero(among))] = True
        return mask

    has_2012 = (month >= 6) if y == 2012 else np.ones(n, bool)
    has_bureau = some((month == 11) & flag(0.5), month == 11) if y == 2015 else np.full(n, y >= 2016)
    late_2015 = (month >= 9) if y == 2015 else np.ones(n, bool)  # the joint applications began in October 2015
    joint = some(late_2015 & flag(JOINT_SHARE[y]), late_2015 & (JOINT_SHARE[y] > 0))
    second = some(joint & flag(SECOND_SHARE.get(y, 0.0)), joint & (y >= 2017))

    # ---- the loan ----
    cap = 35_000 if y <= 2015 else 40_000
    loan = np.clip(np.round(LOAN_MEDIAN[y] * np.exp(0.62 * rng.standard_normal(n)) / 25) * 25, 1_000, cap)
    loan = np.where(flag(0.45), np.clip(np.round(loan / 1_000) * 1_000, 1_000, cap), loan)  # round asks
    t36 = flag(TERM_36[y] - 0.12 * (loan > 20_000))
    grade = _pick(rng, n, GRADE_MIX[y])
    grade = np.minimum(grade + (~t36 & flag(0.45)), 6)  # the long loans price worse
    sub = grade * 5 + _pick(rng, n, (.22, .21, .20, .19, .18))
    quarter = month // 3
    steps = np.random.default_rng([int(seed) & 0xFFFFFFFF, y, 7]).normal(0.0, 0.18, 4)  # the rate sheet moved a quarter
    rate = np.round(5.32 + 0.62 * sub + 0.011 * sub ** 2 + RATE_SHIFT[y] + steps[quarter], 2)
    r, k = rate / 1200.0, np.where(t36, 36, 60)
    col["loan_amnt"] = loan
    col["funded_amnt"] = loan
    col["funded_amnt_inv"] = _f32(np.where(flag(0.04), np.round(loan * rng.uniform(0.9, 1.0, n), 2), loan))
    col["term"] = _strings((" 36 months", " 60 months"), (~t36).astype(np.int64))
    col["int_rate"] = _f32(rate)
    col["installment"] = _f32(np.round(loan * r / (1 - (1 + r) ** -k), 2))
    col["grade"] = _strings(list(GRADES), grade)
    col["sub_grade"] = _strings([g + str(i) for g in GRADES for i in range(1, 6)], sub)

    # ---- the borrower ----
    income = amount(INCOME_MEDIAN[y], 0.52, 1.0)
    rich = flag(2.5e-5)
    income[rich] = _f32(np.round(10 ** rng.uniform(6.0, 8.04, int(rich.sum()))))  # the tail to 1.1 x 10^8
    if y == SOURCE_YEAR and n >= 64:
        income[int(rng.integers(n))] = 110_000_000.0  # the file's largest, in the vintage the cut-offs come from
    col["annual_inc"] = income
    col["home_ownership"] = _strings([v for v, _ in HOME], _pick(rng, n, [s for _, s in HOME]))
    col["verification_status"] = _strings(("Not Verified", "Source Verified", "Verified"), _pick(rng, n, VERIFIED[y]))
    col["emp_length"] = _strings([v for v, _ in EMP_LENGTH], _pick(rng, n, [s for _, s in EMP_LENGTH]), flag(0.062))
    purpose = _pick(rng, n, [s for _, s in PURPOSE])
    col["purpose"] = _strings([v for v, _ in PURPOSE], purpose)
    col["addr_state"] = _strings(STATES, _pick(rng, n, (np.arange(len(STATES)) + 3.0) ** -0.95))
    zips = _ranks(rng, n, _cdf(*ZIP_LAW))
    col["zip_code"] = _strings([f"{(z * 7919) % 1000:03d}xx" for z in range(ZIP_LAW[0])], zips)
    dti = np.round(np.clip(DTI_MEAN[y] + 8.3 * rng.standard_normal(n), 0.0, None) + (y >= 2016) * 6.0 * flag(0.03)
                   * rng.exponential(1.0, n), 2)
    dti[flag(2e-5 * (y >= 2016))] = 999.0
    col["dti"] = _null_where(_f32(dti), flag(0.0009) if y >= 2017 else np.zeros(n, bool))
    col["delinq_2yrs"] = count(0.31, 39)
    fico = np.minimum(660 + 5 * np.floor(rng.exponential(FICO_SCALE[y] / 5.0, n)), 845)
    col["fico_range_low"] = fico
    col["fico_range_high"] = fico + 4
    col["inq_last_6mths"] = np.minimum(rng.poisson(INQ_6M[y], n), 8 if y <= 2013 else 6).astype(np.float64)
    col["mths_since_last_delinq"] = _null_where(months(34, 190), flag(0.51))
    col["mths_since_last_record"] = _null_where(months(72, 129), flag(0.84))
    open_acc = np.clip(rng.poisson(11.6 * rng.gamma(5.0, 0.2, n)), 1, 90).astype(np.float64)
    col["open_acc"] = open_acc
    col["pub_rec"] = count(0.20, 60)
    revol = amount(11_300, 1.05, 1.0, 2_900_000)
    col["revol_bal"] = revol
    util = np.round(np.clip(REVOL_UTIL[y] + 24.0 * rng.standard_normal(n), 0.0, 160.0), 1)
    col["revol_util"] = _null_where(_f32(util), flag(0.0008))
    col["total_acc"] = np.minimum(open_acc + rng.poisson(12.5, n), 170).astype(np.float64)
    col["initial_list_status"] = _strings(("w", "f"), (~flag(LIST_W[y])).astype(np.int64))
    line_year = np.clip(y - 3 - np.floor(rng.gamma(3.2, 4.6, n)), y - CREDIT_LINE_YEARS, y - 3).astype(np.int64)
    col["earliest_cr_line"] = _strings(_month_names(y - CREDIT_LINE_YEARS, CREDIT_LINE_YEARS),
                                       (line_year - (y - CREDIT_LINE_YEARS)) * 12 + rng.integers(0, 12, n))

    # ---- how the loan went ----
    current = flag(CURRENT_SHARE[y])
    late = current & flag(0.035)
    charged = ~current & flag(CHARGED_OFF[y] / max(1.0 - CURRENT_SHARE[y], 0.05))
    status = np.where(current, 2, np.where(charged, 1, 0))
    status[late] = 3 + _pick(rng, int(late.sum()), (.55, .25, .15, .05))
    col["loan_status"] = _strings(("Fully Paid", "Charged Off", "Current") + LATE_STATUS, status)
    col["pymnt_plan"] = _strings(("n", "y"), (late & flag(0.02)).astype(np.int64))
    age = np.maximum((SNAPSHOT[0] - y) * 12 + SNAPSHOT[1] - 1 - month, 1)  # months on book at the snapshot
    paid_share = np.where(current, np.minimum(age / k, 0.97) * rng.uniform(0.9, 1.1, n),
                          np.where(charged, rng.uniform(0.05, 0.7, n), 1.0))
    paid_share = np.clip(paid_share, 0.0, 1.0)
    principal = np.round(loan * paid_share, 2)
    interest = np.round(loan * r * k * 0.55 * np.where(current | charged, paid_share, rng.uniform(0.35, 1.0, n)), 2)
    late_fee = np.where(flag(0.03), np.round(rng.exponential(28.0, n), 2), 0.0)
    recovered = np.where(charged & flag(0.6), np.round(loan * rng.uniform(0.01, 0.25, n), 2), 0.0)
    out = np.where(current, np.round(loan - principal, 2), 0.0)
    col["out_prncp"] = _f32(out)
    col["out_prncp_inv"] = col["out_prncp"]
    total = _f32(principal + interest + late_fee + recovered)
    col["total_pymnt"] = total
    col["total_pymnt_inv"] = total
    col["total_rec_prncp"] = _f32(principal)
    col["total_rec_int"] = _f32(interest)
    col["total_rec_late_fee"] = _f32(late_fee)
    col["recoveries"] = _f32(recovered)
    col["collection_recovery_fee"] = _f32(np.round(recovered * 0.17, 2))
    first = (y - 2007) * 12 + month  # months since January 2007
    last_paid = np.minimum(first + np.maximum(np.round(age * np.where(current, 1.0, rng.uniform(0.15, 1.0, n))), 1),
                           (SNAPSHOT[0] - 2007) * 12 + SNAPSHOT[1] - 1).astype(np.int64)
    book = _month_names(2007, SNAPSHOT[0] - 2007 + 1)
    col["last_pymnt_d"] = _strings(book, last_paid, flag(0.0012))
    col["last_pymnt_amnt"] = _f32(np.where(current, col["installment"], np.round(loan * rng.uniform(0.02, 0.9, n), 2)))
    col["next_pymnt_d"] = _strings(book, (SNAPSHOT[0] - 2007) * 12 + SNAPSHOT[1] - flag(0.3), ~current)
    pulled = np.minimum(last_paid + rng.integers(0, 14, n), (SNAPSHOT[0] - 2007) * 12 + SNAPSHOT[1] - 1)
    col["last_credit_pull_d"] = _strings(book, pulled, flag(0.00004))
    drift = np.where(charged, -110.0, 8.0) + 42.0 * rng.standard_normal(n)
    last_fico = np.clip(5 * np.round((fico + drift) / 5), 300, 845)
    last_fico_low = np.where(flag(0.002), 0.0, last_fico)
    col["last_fico_range_high"] = np.where(last_fico_low == 0, 499.0, last_fico + 4)
    col["last_fico_range_low"] = last_fico_low
    col["collections_12_mths_ex_med"] = count(0.016, 20)
    col["mths_since_last_major_derog"] = _null_where(months(44, 197), flag(0.74))
    col["policy_code"] = np.ones(n)
    col["application_type"] = _strings(("Individual", "Joint App"), joint.astype(np.int64))
    col["annual_inc_joint"] = _null_where(_f32(np.round(income * rng.uniform(1.2, 2.4, n))), ~joint)
    col["dti_joint"] = _null_where(_f32(np.round(np.clip(dti * rng.uniform(0.5, 1.0, n), 0, 69.5), 2)), ~joint)
    col["verification_status_joint"] = _strings(("Not Verified", "Source Verified", "Verified"),
                                                _pick(rng, n, (.52, .28, .20)), ~joint)
    col["acc_now_delinq"] = count(0.004, 14)

    # ---- the bureau's attributes, mid-2012 on ----
    none_2012 = ~has_2012
    bc_limit = amount(16_500, 1.0, 100.0, 1_500_000)
    laws_2012 = {
        "tot_coll_amt": np.where(flag(0.15), amount(620, 1.5, 1.0, 9_152_545), 0.0),
        "tot_cur_bal": amount(82_000, 1.25, 1.0, 9_971_659),
        "total_rev_hi_lim": amount(25_500, 0.85, 100.0, 9_999_999),
        "acc_open_past_24mths": np.minimum(rng.poisson(4.5 * rng.gamma(2.2, 1 / 2.2, n)), 64).astype(np.float64),
        "avg_cur_bal": amount(7_400, 1.2, 1.0, 958_084),
        "bc_open_to_buy": _null_where(amount(5_300, 1.45, 1.0, 711_140), flag(0.011)),
        "bc_util": _null_where(_f32(np.round(np.clip(REVOL_UTIL[y] + 6 + 28.0 * rng.standard_normal(n), 0, 250), 1)),
                               flag(0.012)),
        "chargeoff_within_12_mths": count(0.008, 10),
        "delinq_amnt": np.where(flag(0.003), amount(2_800, 1.4, 1.0, 249_925), 0.0),
        "mo_sin_old_il_acct": _null_where(np.minimum(np.round(rng.gamma(5.6, 22.5, n)), 999), flag(0.03)),
        "mo_sin_old_rev_tl_op": np.clip(np.round(rng.gamma(3.8, 47.8, n)), 1, 999),
        "mo_sin_rcnt_rev_tl_op": months(14, 547),
        "mo_sin_rcnt_tl": months(8, 382),
        "mort_acc": np.minimum(rng.poisson(1.55 * rng.gamma(0.9, 1 / 0.9, n)), 61).astype(np.float64),
        "mths_since_recent_bc": _null_where(months(24.8, 661), flag(0.010)),
        "mths_since_recent_bc_dlq": _null_where(months(39, 202), flag(0.77)),
        "mths_since_recent_inq": _null_where(np.minimum(months(7, 25), 25), flag(0.125)),
        "mths_since_recent_revol_delinq": _null_where(months(36, 202), flag(0.67)),
        "num_accts_ever_120_pd": count(0.5, 58),
        "num_actv_bc_tl": np.minimum(rng.poisson(3.7 * rng.gamma(4.0, 0.25, n)), 50).astype(np.float64),
        "num_actv_rev_tl": np.minimum(rng.poisson(5.6 * rng.gamma(4.0, 0.25, n)), 72).astype(np.float64),
        "num_bc_sats": np.minimum(rng.poisson(4.8 * rng.gamma(4.0, 0.25, n)), 71).astype(np.float64),
        "num_bc_tl": np.minimum(rng.poisson(7.7 * rng.gamma(4.0, 0.25, n)), 86).astype(np.float64),
        "num_il_tl": np.minimum(rng.poisson(8.4 * rng.gamma(2.5, 0.4, n)), 159).astype(np.float64),
        "num_op_rev_tl": np.minimum(rng.poisson(8.2 * rng.gamma(4.5, 1 / 4.5, n)), 91).astype(np.float64),
        "num_rev_accts": np.minimum(rng.poisson(14.0 * rng.gamma(4.0, 0.25, n)), 151).astype(np.float64),
        "num_rev_tl_bal_gt_0": np.minimum(rng.poisson(5.6 * rng.gamma(4.0, 0.25, n)), 65).astype(np.float64),
        "num_sats": np.minimum(open_acc, 101),
        "num_tl_120dpd_2m": _null_where(count(0.0008, 7), flag(0.04)),
        "num_tl_30dpd": count(0.003, 4),
        "num_tl_90g_dpd_24m": count(0.085, 58),
        "num_tl_op_past_12m": np.minimum(rng.poisson(2.1 * rng.gamma(2.0, 0.5, n)), 32).astype(np.float64),
        "pct_tl_nvr_dlq": _f32(np.round(100.0 - np.where(flag(0.45), rng.gamma(1.4, 9.0, n), 0.0).clip(0, 100), 1)),
        "percent_bc_gt_75": _null_where(_f32(np.round(np.clip(rng.choice(
            [0.0, 25.0, 33.3, 50.0, 66.7, 75.0, 100.0], n, p=(.30, .08, .09, .16, .09, .06, .22))
            + (REVOL_UTIL[y] - 52.0) * 0.2 * flag(0.5), 0, 100), 1)), flag(0.011)),
        "pub_rec_bankruptcies": count(0.13, 12),
        "tax_liens": count(0.047, 85),
        "tot_hi_cred_lim": amount(114_000, 1.05, 1.0, 9_999_999),
        "total_bal_ex_mort": amount(37_500, 0.9, 1.0, 3_408_095),
        "total_bc_limit": bc_limit,
        "total_il_high_credit_limit": np.where(flag(0.12), 0.0, amount(32_500, 0.95, 1.0, 2_118_996)),
    }
    for c in BUREAU_2012_07:
        col[c] = _null_where(laws_2012[c], none_2012)

    # ---- the bureau's trade-line attributes, December 2015 on ----
    none_bureau = ~has_bureau
    laws_2015 = {
        "open_acc_6m": np.minimum(rng.poisson(0.93 * rng.gamma(1.6, 1 / 1.6, n)), 18).astype(np.float64),
        "open_act_il": np.minimum(rng.poisson(2.8 * rng.gamma(1.5, 1 / 1.5, n)), 57).astype(np.float64),
        "open_il_12m": np.minimum(rng.poisson(0.68 * rng.gamma(1.6, 1 / 1.6, n)), 25).astype(np.float64),
        "open_il_24m": np.minimum(rng.poisson(1.56 * rng.gamma(1.6, 1 / 1.6, n)), 51).astype(np.float64),
        "mths_since_rcnt_il": _null_where(months(21, 511), flag(0.03)),
        "total_bal_il": np.where(flag(0.1), 0.0, amount(24_000, 1.1, 1.0, 1_837_038)),
        "il_util": _null_where(np.minimum(np.round(rng.gamma(9.0, 7.7, n)), 1_000), flag(0.13)),
        "open_rv_12m": np.minimum(rng.poisson(1.29 * rng.gamma(1.5, 1 / 1.5, n)), 28).astype(np.float64),
        "open_rv_24m": np.minimum(rng.poisson(2.75 * rng.gamma(1.7, 1 / 1.7, n)), 60).astype(np.float64),
        "max_bal_bc": amount(4_400, 1.0, 1.0, 1_170_668),
        "all_util": np.minimum(np.round(np.clip(57.0 + 20.5 * rng.standard_normal(n), 0, None)), 239),
        "inq_fi": count(1.0, 48),
        "total_cu_tl": count(1.5, 111),
        "inq_last_12m": np.minimum(rng.poisson(2.0 * rng.gamma(1.2, 1 / 1.2, n)), 67).astype(np.float64),
    }
    for c in BUREAU_2015_12:
        col[c] = _null_where(laws_2015[c], none_bureau)

    # ---- the second applicant, 2017 on ----
    sec_fico = np.minimum(540 + 5 * np.floor(rng.gamma(6.0, 4.5, n)), 845)
    laws_second = {
        "revol_bal_joint": amount(26_500, 0.85, 1.0, 1_110_019),
        "sec_app_fico_range_low": sec_fico, "sec_app_fico_range_high": sec_fico + 4,
        "sec_app_inq_last_6mths": np.minimum(rng.poisson(0.63, n), 6).astype(np.float64),
        "sec_app_mort_acc": np.minimum(rng.poisson(1.5 * rng.gamma(0.9, 1 / 0.9, n)), 27).astype(np.float64),
        "sec_app_open_acc": np.clip(rng.poisson(11.5 * rng.gamma(4.0, 0.25, n)), 0, 82).astype(np.float64),
        "sec_app_revol_util": _null_where(_f32(np.round(np.clip(58.0 + 26.0 * rng.standard_normal(n), 0, 434), 1)),
                                          flag(0.017)),
        "sec_app_open_act_il": np.minimum(rng.poisson(3.0 * rng.gamma(1.5, 1 / 1.5, n)), 43).astype(np.float64),
        "sec_app_num_rev_accts": np.minimum(rng.poisson(12.5 * rng.gamma(3.0, 1 / 3.0, n)), 106).astype(np.float64),
        "sec_app_chargeoff_within_12_mths": count(0.046, 21),
        "sec_app_collections_12_mths_ex_med": count(0.078, 23),
        "sec_app_mths_since_last_major_derog": _null_where(months(36, 185), flag(0.66)),
    }
    for c in SECOND_APPLICANT:
        if c == "sec_app_earliest_cr_line":
            sec_year = np.clip(y - 2 - np.floor(rng.gamma(3.0, 4.5, n)), y - 55, y - 2).astype(np.int64)
            col[c] = _strings(_month_names(y - 55, 55), (sec_year - (y - 55)) * 12 + rng.integers(0, 12, n), ~second)
        else:
            col[c] = _null_where(laws_second[c], ~second)

    # ---- hardship plans and settlements: in under 1-2 % of the rows ----
    open_2017 = first + k > (2017 - 2007) * 12  # the plans began in 2017: the loan was still on the book
    hard = some(open_2017 & flag(0.0008 if y <= 2013 else 0.0055), open_2017)
    nh = ~hard
    start = np.clip(first + rng.integers(3, 40, n), (2017 - 2007) * 12, len(book) - 4).astype(np.int64)
    col["hardship_flag"] = _strings(("N", "Y"), (hard & flag(0.08)).astype(np.int64))
    col["hardship_type"] = _strings(("INTEREST ONLY-3 MONTHS DEFERRAL",), np.zeros(n, np.int64), nh)
    col["hardship_reason"] = _strings(HARDSHIP_REASON, _pick(rng, n, (.27, .20, .19, .10, .09, .06, .04, .03, .02)), nh)
    col["hardship_status"] = _strings(HARDSHIP_STATUS, _pick(rng, n, (.65, .25, .10)), nh)
    col["deferral_term"] = _null_where(np.full(n, 3.0), nh)
    col["hardship_amount"] = _null_where(_f32(np.round(col["installment"] * rng.uniform(0.1, 0.75, n), 2)), nh)
    col["hardship_start_date"] = _strings(book, start, nh)
    col["hardship_end_date"] = _strings(book, start + 3, nh)
    col["payment_plan_start_date"] = _strings(book, start + 1, nh)
    col["hardship_length"] = _null_where(np.full(n, 3.0), nh)
    col["hardship_dpd"] = _null_where(np.minimum(np.floor(rng.exponential(13.0, n)), 37), nh)
    col["hardship_loan_status"] = _strings(HARDSHIP_LOAN_STATUS, _pick(rng, n, (.44, .24, .18, .13, .01)), nh)
    col["orig_projected_additional_accrued_interest"] = _null_where(
        _f32(np.round(col["installment"] * rng.uniform(0.3, 2.2, n), 2)), nh | flag(0.2))
    col["hardship_payoff_balance_amount"] = _null_where(_f32(np.round(loan * rng.uniform(0.2, 0.98, n), 2)), nh)
    col["hardship_last_payment_amount"] = _null_where(_f32(np.round(col["installment"] * rng.uniform(0.02, 1.1, n), 2)), nh)
    col["disbursement_method"] = _strings(("Cash", "DirectPay"), (flag(0.075) if y >= 2017 else np.zeros(n, bool))
                                          .astype(np.int64))
    settled = some(charged & flag(0.09), charged)
    ns = ~settled
    when = np.clip(last_paid + rng.integers(2, 16, n), (2015 - 2007) * 12 + 7, len(book) - 1).astype(np.int64)
    col["debt_settlement_flag"] = _strings(("N", "Y"), settled.astype(np.int64))
    col["debt_settlement_flag_date"] = _strings(book, when, ns)
    col["settlement_status"] = _strings(SETTLEMENT_STATUS, _pick(rng, n, (.42, .40, .18)), ns)
    col["settlement_date"] = _strings(book, np.maximum(when - rng.integers(0, 4, n), 0), ns)
    pct = np.round(rng.choice([45.0, 50.0, 40.0, 55.0, 60.0, 65.0, 35.0], n) + np.where(flag(0.3), np.round(
        rng.uniform(-5, 5, n), 2), 0.0), 2)
    col["settlement_amount"] = _null_where(_f32(np.round(loan * rng.uniform(0.2, 0.9, n) * pct / 100.0, 2)), ns)
    col["settlement_percentage"] = _null_where(_f32(pct), ns)
    col["settlement_term"] = _null_where(np.minimum(rng.poisson(12.0, n), 65).astype(np.float64), ns)

    # ---- the identifiers and the free text ----
    ids = rng.permutation(np.arange(n, dtype=np.int64)) + (y - 2007) * 12_000_000 + 1_000_000
    digits = pa.array(ids).cast(pa.string())
    # ingest's type look makes a number of a string column whose every value is one (PERF.md section 7, item 15 d):
    # the source's digits stand behind a letter, so that the column stays the string that pandas reads
    col["id"] = pc.binary_join_element_wise(pa.scalar("L"), digits, "")
    col["member_id"] = np.full(n, np.nan)
    col["url"] = pc.binary_join_element_wise(
        pa.scalar("https://lendingclub.com/browse/loanDetail.action?loan_id="), digits, "")
    told = flag(DESC_FILLED[y])
    said = pc.binary_join_element_wise(
        pa.scalar("Borrower added on"), _strings([f"{m:02d}/{d:02d}/{y % 100:02d}" for m in range(1, 13)
                                                  for d in range(1, 29)], month * 28 + rng.integers(0, 28, n)),
        pa.scalar("> I would like to use this loan for"), _strings([v for v, _ in PURPOSE], purpose),
        pa.scalar("and pay it off; reference"), digits, " ")
    col["desc"] = pc.if_else(pa.array(told), said, pa.scalar(None, pa.string()))
    emp_titles = emp_title_universe() if emp_titles is None else emp_titles
    head = np.array([s for _, s in EMP_TITLES])
    named = flag(head.sum())
    title_rank = np.where(named, _pick(rng, n, head), len(head) + _ranks(rng, n, _cdf(EMP_TITLE_LAW[0] - len(head),
                                                                                       *EMP_TITLE_LAW[1:])))
    taken = emp_titles.take(pa.array(title_rank))
    col["emp_title"] = pc.if_else(pa.array(~flag(EMP_TITLE_NULL[y])), taken, pa.scalar(None, pa.string()))
    typed = flag(TITLE_FREE[y])
    free = _ranks(rng, n, _cdf(*TITLE_LAW))
    free_text = pc.binary_join_element_wise(
        _strings(("my", "My", "Loan for", "loan", "Pay off", "payoff", "Consolidate", "consolidation", "Freedom",
                  "fresh start", "New", "2nd", "Personal", "CC", "bills", "Final"), free % 16),
        _strings([v for v, _ in PURPOSE], (free // 16) % len(PURPOSE)), pa.array(free // (16 * len(PURPOSE)))
        .cast(pa.string()), " ")
    chosen = _strings(PURPOSE_TITLE, purpose)
    col["title"] = pc.if_else(pa.array(flag(0.0103 if y >= 2016 else 0.0001)), pa.scalar(None, pa.string()),
                              pc.if_else(pa.array(typed), free_text, chosen))

    arrays = [col[c] if isinstance(col[c], (pa.Array, pa.ChunkedArray)) else pa.array(col[c], pa.float64(),
                                                                                        from_pandas=True)
              for c in COLUMNS]
    return pa.Table.from_arrays(arrays, schema=SCHEMA)


def vintage_rows(rows: int) -> dict:
    """The rows of each vintage where the 2018 vintage holds ``rows``: every
    vintage cut alike (``rows`` = ceil(495,242 / 2^j) gives ceil(n / 2^j) for each)."""
    full = PUBLISHED_ROWS[TARGET_YEAR]
    return {y: max(-(-PUBLISHED_ROWS[y] * rows // full), 1) for y in YEARS}


def generate(dest: str, seed: int, parts: Iterable[str], rows: int,
             source_rows: Optional[int] = None) -> None:
    """Write the named ``parts`` under ``dest`` (emptied first).  ``rows`` is
    the 2018 vintage's count and sets every other's (``vintage_rows``);
    ``source_rows`` is taken and ignored: the source is the 2015 vintage."""
    parts = set(parts)
    unknown = parts - {"parquet", "source", "stability_index"}
    if unknown:
        raise ValueError(f"unknown dataset parts {sorted(unknown)}")
    held = vintage_rows(rows)
    shutil.rmtree(dest, ignore_errors=True)
    os.makedirs(dest)
    where = {y: [] for y in YEARS}  # the directories that hold each vintage
    if "parquet" in parts:
        where[TARGET_YEAR].append("parquet")
    if "source" in parts:
        where[SOURCE_YEAR].append("source")
    if "stability_index" in parts:
        for i, y in enumerate(YEARS):
            where[y].append(os.path.join("stability_index", str(i)))
    emp_titles = emp_title_universe()

    def vintage(y: int) -> None:
        table = synthesize(y, held[y], seed, emp_titles)
        for rel in where[y]:
            os.makedirs(os.path.join(dest, rel))
            for i, lo in enumerate(range(0, held[y], ROWS_PER_PART)):
                pq.write_table(table.slice(lo, ROWS_PER_PART), os.path.join(dest, rel, f"part-{i:05d}.parquet"))

    wanted = [y for y in YEARS if where[y]]
    with ThreadPoolExecutor(max_workers=min(len(wanted), 4) or 1) as pool:
        list(pool.map(vintage, wanted))
