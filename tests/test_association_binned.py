"""The association block against its plain reference (``tests/assoc_reference.py``:
numpy and pandas float64, nothing of ``anovos_tpu``) on a seeded table of 3,000
rows that has every hard case of a credit table: a column all null, a constant
flag, a flag so rare that every cut-off is equal, a sentinel on 18 % of the
rows, amounts in round steps (ties at every cut-off), a block of columns
missing together, a category without an event, 58 categories, and a label
with nulls.  Both routes of the group counts (the one-hot contraction of a
table on one device, the scatter-add of a table over the mesh), the counts
that ``IG_calculation`` takes from ``IV_calculation``, the grouping by value of
an unbinned numeric, and the four wrong answers the reference can give."""

import inspect
import os
import sys

import jax
import numpy as np
import pandas as pd
import pytest

sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))
import assoc_reference as ref  # noqa: E402

from anovos_tpu.data_analyzer import association_evaluator as ae  # noqa: E402
from anovos_tpu.data_transformer import transformers  # noqa: E402
from anovos_tpu.ops import quantiles, segment  # noqa: E402
from anovos_tpu.shared.runtime import derive_runtime, placement_scope  # noqa: E402
from anovos_tpu.shared.table import Table  # noqa: E402

ROWS = 3000
NUMERIC = ["all_null", "flag_const", "flag_rare", "sentinel", "amount", "steps", "house_a", "house_b", "score"]
CATEGORICAL = ["cat_noevent", "cat_wide"]
ARGS = {"numeric": NUMERIC, "categorical": CATEGORICAL, "correlation": [c for c in NUMERIC if c != "all_null"] + ["label"],
        "sure_rows": 100}
ROUNDED = 5.1e-5  # the tables' four decimals


def hard_cases(seed: int = 46) -> pd.DataFrame:
    g = np.random.default_rng(seed)
    z = g.standard_normal(ROWS)
    label = (g.random(ROWS) < 1 / (1 + np.exp(-(-2.2 + 1.3 * z)))).astype(np.float64)
    label[g.random(ROWS) < 0.02] = np.nan  # a label with nulls
    absent = g.random(ROWS)
    days = -np.round(np.exp(7.0 + 0.8 * g.standard_normal(ROWS)))
    rare = g.random(ROWS) < 0.004
    noevent = np.where(rare, "rare", np.where(z > 0.3, "hi", "lo")).astype(object)
    label[rare] = 0.0  # a category without an event
    noevent[g.random(ROWS) < 0.1] = None
    return pd.DataFrame({
        "all_null": np.full(ROWS, np.nan),
        "flag_const": np.ones(ROWS, np.int64),
        "flag_rare": (g.random(ROWS) < 0.005).astype(np.int64),  # every cut-off is 0
        "sentinel": np.where(g.random(ROWS) < 0.18, 365243.0, days),
        "amount": np.where(g.random(ROWS) < 0.4, np.nan, np.round(np.exp(11 + 0.9 * (0.3 * z + g.standard_normal(ROWS))), 2)),
        "steps": 4500.0 * np.round(np.exp(3.5 + 0.5 * g.standard_normal(ROWS))),  # ties at every cut-off
        "house_a": np.where(absent < 0.5, np.nan, g.random(ROWS)),  # missing together
        "house_b": np.where(absent < 0.65, np.nan, np.round(g.random(ROWS), 2)),
        "score": 1 / (1 + np.exp(-(0.2 - 0.6 * z + g.standard_normal(ROWS)))),
        "cat_noevent": noevent,
        "cat_wide": np.array([f"org {i:02d}" for i in range(58)], dtype=object)[np.minimum(g.zipf(1.3, ROWS) - 1, 57)],
        "label": label,
    })


@pytest.fixture(scope="module")
def frame():
    return hard_cases()


@pytest.fixture(scope="module")
def wanted(frame):
    return ref.answers(frame, ARGS, "label", 1, 10)


@pytest.fixture(scope="module", params=["mesh", "one_device"])
def table(request, frame):
    """The table over the suite's 8-device mesh (the group counts scatter-add)
    or on one device, as a ``device`` node of the scheduler and the chip have
    it (they contract one-hots)."""
    t = Table.from_pandas(frame)
    if request.param == "mesh":
        yield t
        return
    with placement_scope(derive_runtime(jax.devices()[:1])):
        yield t.to_active_placement()


def _series(odf, value):
    return odf.set_index("attribute")[value].astype("float64")


def test_iv_and_ig_of_every_hard_case_agree_with_the_reference(table, wanted):
    cols = NUMERIC + CATEGORICAL
    iv = _series(ae.IV_calculation(table, cols, label_col="label", event_label=1), "iv")
    ig = _series(ae.IG_calculation(table, cols, label_col="label", event_label=1), "ig")
    assert list(iv.index) == cols == list(ig.index)
    np.testing.assert_allclose(iv.to_numpy(), wanted["iv"][cols].to_numpy(), atol=ROUNDED, rtol=0)
    np.testing.assert_allclose(ig.to_numpy(), wanted["ig"][cols].to_numpy(), atol=ROUNDED, rtol=0)
    assert iv["all_null"] == 0 == iv["flag_const"]  # one group says nothing
    # ... and its gain is what the label's nulls make of it: the table's rate is over all rows, the group's over the labelled
    assert ig["flag_const"] == ig["all_null"] == pytest.approx(wanted["ig"]["flag_const"], abs=ROUNDED) and ig["flag_const"] < 0
    assert iv["score"] > 0.1 > iv["steps"]


def test_the_route_of_the_group_counts_follows_the_tables_layout(table, request):
    codes = table.columns["cat_wide"].data
    on_one = len(codes.sharding.device_set) == 1
    assert segment.count_route(58, codes) == (256, on_one) and segment.count_route(10, codes) == (16, on_one)
    assert ("one_device" in request.node.name) == on_one


def test_ig_takes_ivs_counts_and_a_changed_question_counts_again(frame, monkeypatch):
    t = Table.from_pandas(frame)
    calls = []
    real = ae._group_counts_program
    monkeypatch.setattr(ae, "_group_counts_program", lambda *a, **k: calls.append(k) or real(*a, **k))
    cols = NUMERIC + CATEGORICAL
    ae.IV_calculation(t, cols, label_col="label", event_label=1)
    assert len(calls) == 2  # the binned block and the categorical block: one program each
    ae.IG_calculation(t, cols, label_col="label", event_label=1)
    ae.IV_calculation(t, cols, label_col="label", event_label=1)
    assert len(calls) == 2 and len(t.__dict__["_assoc_cache"]) == 1
    ae.IG_calculation(t, cols, label_col="label", event_label=1,
                      encoding_configs={"bin_method": "equal_frequency", "bin_size": 5, "monotonicity_check": 0})
    assert len(calls) == 4 and len(t.__dict__["_assoc_cache"]) == 2
    assert [k["vocab_size"] for k in calls] == [16, 256, 16, 256]


def test_five_bins_and_equal_range_follow_their_rules(frame):
    t = Table.from_pandas(frame)
    five = ref.answers(frame, ARGS, "label", 1, 5)
    got = _series(ae.IV_calculation(t, NUMERIC, label_col="label", event_label=1,
                                    encoding_configs={"bin_method": "equal_frequency", "bin_size": 5}), "iv")
    np.testing.assert_allclose(got.to_numpy(), five["iv"][NUMERIC].to_numpy(), atol=ROUNDED, rtol=0)
    # equal range by hand: cut-offs at min + j (max - min) / 4 of the stored float32 values
    x = ref.stored(frame["score"])
    cuts = (x.min() + np.arange(1, 4) * np.float32((np.float32(x.max()) - np.float32(x.min())) / 4)).astype(np.float32)
    groups = 1 + np.searchsorted(cuts.astype(np.float64), x, side="left")
    labelled = frame["label"].notna().to_numpy()
    non, ev = ref.group_counts(groups, (frame["label"] == 1).to_numpy().astype(float), labelled)
    got = _series(ae.IV_calculation(t, ["score"], label_col="label", event_label=1,
                                    encoding_configs={"bin_method": "equal_range", "bin_size": 4}), "iv")
    assert got["score"] == pytest.approx(ref.information_value(non, ev), abs=ROUNDED)


@pytest.mark.parametrize("method", ["equal_frequency", "equal_range"])
def test_a_measures_bins_are_the_transformers_bins(table, frame, method):
    """The labelled rows of every group that ``IV_calculation`` counts are those
    of the bins ``attribute_binning`` gives the same table (one block-level step,
    ``transformers.binning_cutoffs`` and ``bin_block``, behind both), and a
    method neither knows is refused by both."""
    cols = [c for c in NUMERIC if c != "all_null"]  # equal_range drops a column without a value from the transformer's table
    enc = {"bin_method": method, "bin_size": 10, "monotonicity_check": 0}
    groups, _ = ae._label_groups(table, cols, "label", 1, enc)
    binned = transformers.attribute_binning(table, cols, method_type=method, bin_size=10).to_pandas()
    labelled, event = frame["label"].notna(), frame["label"] == 1
    for c in cols:
        bins = binned[c][labelled].fillna(0).astype(int)  # the nulls: one more group, counted last
        order = sorted(set(bins) - {0}) + ([0] if (bins == 0).any() else [])
        non = [int(((bins == b) & ~event[labelled]).sum()) for b in order]
        ev = [int(((bins == b) & event[labelled]).sum()) for b in order]
        np.testing.assert_array_equal(groups[c][0], non, err_msg=c)
        np.testing.assert_array_equal(groups[c][1], ev, err_msg=c)
    for call in (lambda: ae.IV_calculation(table, cols, label_col="label", event_label=1,
                                           encoding_configs={"bin_method": "equal_width", "bin_size": 10}),
                 lambda: transformers.attribute_binning(table, cols, method_type="equal_width")):
        with pytest.raises(TypeError, match="method_type"):
            call()


@pytest.mark.parametrize("encoding", [None, {"bin_method": "equal_frequency", "bin_size": 10, "monotonicity_check": 1}])
def test_a_numeric_grouped_by_its_values_is_fetched_and_says_so(frame, encoding):
    """Without ``encoding_configs`` a numeric column's groups are its exact
    values; with the monotonicity search they are the bins of the
    transformer's table: both go by the host, one column at a time."""
    from anovos_tpu.obs import get_tracer

    t = Table.from_pandas(frame)
    cols = ["steps", "flag_rare", "cat_noevent"]
    with get_tracer().span("probe", cat="test"):
        iv = _series(ae.IV_calculation(t, cols, label_col="label", event_label=1, encoding_configs=encoding), "iv")
    spans = [s for s in get_tracer().drain() if s.name == "assoc/group_counts"]
    assert spans[-1].args["host_rows"] == 2 * 2 * t.padded_rows and spans[-1].args["fetches"] == 2 + 2
    assert not [s for s in spans if s.name == "assoc/bin"]
    labelled = frame["label"].notna().to_numpy()
    event = (frame["label"] == 1).to_numpy().astype(float)
    if encoding is None:
        for c in ("steps", "flag_rare"):
            non, ev = ref.group_counts(ref.stored(frame[c]), event, labelled)
            assert iv[c] == pytest.approx(ref.information_value(non, ev), abs=ROUNDED)
    non, ev = ref.group_counts(1 + pd.factorize(frame["cat_noevent"], sort=True)[0], event, labelled)
    assert iv["cat_noevent"] == pytest.approx(ref.information_value(non, ev), abs=ROUNDED)


def test_the_correlation_is_over_the_rows_complete_in_every_column(table, wanted, frame):
    cols = ARGS["correlation"]
    got = ae.correlation_matrix(table, cols).set_index("attribute")
    X, M = table.numeric_block(cols)
    C, complete = ae._complete_case_corr(X, M, len(cols))
    assert complete == wanted["complete_rows"] == int(frame[cols].notna().all(axis=1).sum())
    for pair, want in wanted["correlation"].items():
        a, b = pair.split("~")
        if np.isnan(want):  # the constant flag: no correlation is defined
            assert np.isnan(got.loc[a, b]) and "flag_const" in (a, b)
        else:
            assert got.loc[a, b] == pytest.approx(want, abs=2e-6), pair
    assert C.shape == (len(cols), len(cols)) and np.allclose(np.diag(C), 1.0)


@pytest.mark.parametrize("fault", ref.FAULTS)
def test_each_wrong_answer_of_the_reference_is_told_from_the_programs(frame, wanted, fault):
    t = Table.from_pandas(frame)
    wrong = ref.answers(frame, ARGS, "label", 1, 10, fault=fault)
    cols = NUMERIC + CATEGORICAL
    if fault == "pairwise_correlation":
        got = ae.correlation_matrix(t, ARGS["correlation"]).set_index("attribute")
        gaps = [abs(got.loc[p.split("~")[0], p.split("~")[1]] - w) for p, w in wrong["correlation"].items() if not np.isnan(w)]
        assert max(gaps) > 1e-3 and wrong["complete_rows"] == wanted["complete_rows"]
        return
    iv = _series(ae.IV_calculation(t, cols, label_col="label", event_label=1), "iv")
    assert (iv - wrong["iv"][cols]).abs().max() > 1e-3
    moved = {"nulls_dropped": "amount", "strict_cutoff": "steps", "no_correction": "cat_noevent"}[fault]
    assert abs(iv[moved] - wrong["iv"][moved]) > 1e-3 and abs(iv[moved] - wanted["iv"][moved]) <= ROUNDED


def test_the_block_form_of_the_label_counts_is_the_column_form(table):
    cats = [table.columns[c] for c in CATEGORICAL]
    from anovos_tpu.shared.table import stack_padded

    codes, M = stack_padded([c.data for c in cats], [c.mask for c in cats], dtype=jax.numpy.int32)
    y = (table.columns["label"].data == 1).astype(jax.numpy.float32)
    p, dense = segment.count_route(58, codes)
    block = np.asarray(segment._block_label_counts_p(codes, M, y, vocab_size=p, dense=dense))
    assert block.shape == (2, 256)
    for i, c in enumerate(cats):
        one = np.asarray(segment.code_label_counts(c.data, c.mask, y, 58))
        np.testing.assert_array_equal(block[i], one)
    assert block.sum() > 0 and block[0, len(cats[0].vocab):].sum() == 0


def test_defaults_cannot_be_changed_by_a_caller():
    for fn in (ae.IV_calculation, ae.IG_calculation, ae.correlation_matrix, ae.variable_clustering):
        for name, p in inspect.signature(fn).parameters.items():
            assert not isinstance(p.default, (list, dict, set)), (fn.__name__, name)
    with pytest.raises(TypeError):
        ae._ENCODING["bin_size"] = 3
    src = inspect.getsource(ae)
    assert src.count("import jax\n") == 1 and "np.unique" in inspect.getsource(ae._value_codes)


def test_the_programs_carry_their_scopes():
    """The four scopes a reader of the device trace finds the block by are in
    the lowered programs' operation names, whatever the functions are called."""
    f32, i32, b = jax.numpy.float32, jax.numpy.int32, jax.numpy.bool_
    S = jax.ShapeDtypeStruct
    X, M = S((512, 8), f32), S((512, 8), b)
    lowered = {
        # attribute_binning's two programs, under the scope this block names to them
        "assoc/cutoffs": quantiles._masked_quantiles.lower(X, M, S((9,), f32), interpolation="lower",
                                                           scope="assoc/cutoffs"),
        "assoc/bin_apply": transformers._bin_apply_program.lower(X, S((8, 11), f32), scope="assoc/bin_apply"),
        "assoc/group_counts": ae._group_counts_program.lower(S((512, 8), i32), M, S((512,), f32), S((512,), b),
                                                             vocab_size=16, dense=True),
        "assoc/corr": ae._corr_program.lower(X, M, S((), i32), bf16=False),
    }
    for scope, low in lowered.items():
        assert scope + "/" in low.as_text(debug_info=True), scope
    assert ae.COMPLETE_ROWS_ROW == "assoc/corr"
