"""``shared.table.encode_strings`` against the plain encoding it replaced: a
``str()`` per row and ``np.unique`` over all of them, kept here as the
reference.  Codes, vocab (values, order and type) and mask must be equal
for every input, whichever path the column took; ``hashed`` and
``native_sort`` on the ``ingest/encode`` span say which, and who ordered the
vocab (Arrow over UTF-8 bytes, or ``np.unique`` over Python ``str``)."""

import numpy as np
import pandas as pd
import pytest

from anovos_tpu import obs
from anovos_tpu.data_ingest import data_ingest
from anovos_tpu.shared.table import Table, encode_strings


def reference(vals: np.ndarray):
    """The two lines ``_host_to_column`` had (PR 25's ``table.py:637-638``)
    and what it made of them."""
    isnull = pd.isna(vals)
    nn_strs = np.array([str(v) for v in vals[~isnull]], dtype=object)
    vocab, codes = np.unique(nn_strs, return_inverse=True)
    code_arr = np.full(len(vals), -1, dtype=np.int32)
    code_arr[~isnull] = codes.astype(np.int32)
    return code_arr, vocab.astype(object), ~isnull


def encode(values):
    """``(encoded, counts of its span)``, the counts held to what every
    span owes: Arrow orders the vocab of every column it hashed but a
    categorical's, and only there says where the span's time went."""
    enc = encode_strings(values)
    span = obs.get_tracer().snapshot()[-1]
    assert span.name == "ingest/encode"
    counts = span.args
    categorical = isinstance(getattr(values, "dtype", None), pd.CategoricalDtype)
    assert counts["native_sort"] == (counts["hashed"] and not categorical)
    if counts["native_sort"]:
        assert counts["hash_s"] >= 0 and counts["sort_s"] >= 0
        assert counts["hash_s"] + counts["sort_s"] <= span.dur_ns / 1e9
    else:
        assert "hash_s" not in counts and "sort_s" not in counts
    return enc, counts


def assert_same(enc, vals: np.ndarray):
    codes, vocab, mask = reference(vals)
    assert enc.codes.dtype == np.int32 and np.array_equal(enc.codes, codes)
    assert np.array_equal(enc.codes >= 0, mask)
    assert enc.vocab.dtype == object and len(enc.vocab) == len(vocab)
    assert list(enc.vocab) == list(vocab)
    assert all(type(v) is str for v in enc.vocab)


def _obj(values) -> np.ndarray:
    out = np.empty(len(values), dtype=object)
    out[:] = values
    return out


def _ids(n: int, order=None) -> np.ndarray:
    """The id column as ``benchmark/datasets/income.py`` makes it: distinct
    9-character keys, in order unless another is given."""
    return _obj([f"id{i:07d}" for i in (range(n) if order is None else order)])


def _shuffled_ids(n: int) -> np.ndarray:
    return _ids(n, np.random.default_rng(3).permutation(n))


def _every_utf8_length(n: int = 24_000) -> np.ndarray:
    """Distinct random strings over 1-, 2-, 3- and 4-byte code points (NUL
    among them, the surrogates not), every prefix of some of them, ``""``,
    and the neighbours across the lengths' borders: the order of UTF-8 bytes
    against the order of code points."""
    rng = np.random.default_rng(28)
    ranges = [(0x00, 0x80), (0x80, 0x800), (0x800, 0xD800), (0xE000, 0x10000), (0x10000, 0x110000)]
    words = {"", "\0", "\0\0", "a\0", "a\0b", "a", "\x7f", "\x80", "\u07ff", "\u0800", "\ud7ff",
             "\ue000", "\uffff", "\U00010000", "\U0010ffff", "\uffffz", "\U00010000z"}
    while len(words) < n:
        lo_hi = [ranges[i] for i in rng.integers(0, len(ranges), rng.integers(1, 9))]
        word = "".join(chr(rng.integers(lo, hi)) for lo, hi in lo_hi)
        words.add(word)
        if len(words) % 7 == 0:
            words.update(word[:k] for k in range(1, len(word)))
    vals = _obj(sorted(words))[rng.permutation(len(words))]
    vals = np.concatenate([vals, vals[: n // 3], _obj([None] * 50)])
    return vals[rng.permutation(len(vals))]


def _few_of_many(n: int) -> np.ndarray:
    rng = np.random.default_rng(5)
    vals = _obj([f"level {i}" for i in range(40)])[rng.integers(0, 40, n)]
    vals[rng.random(n) < 0.07] = None
    vals[rng.random(n) < 0.02] = np.nan
    return vals


# (values, hashed): what the array's own content makes the helper do
ARRAYS = {
    "all_str": (_obj(["b", "a", "c", "a"]), 1),
    "with_none": (_obj(["b", None, "a", None]), 1),
    "with_nan": (_obj(["b", np.nan, "a", float("nan")]), 1),
    "with_pd_na": (_obj(["b", pd.NA, "a"]), 1),
    "every_null_at_once": (_obj([None, "x", np.nan, pd.NA, "x", "w"]), 1),
    "empty_string_is_a_value": (_obj(["", None, "a", "", " "]), 1),
    "non_ascii": (_obj(["zebra", "éclair", "Zürich", "ß", "ss", "çedille", "z"]), 1),
    # code-point order is not UTF-16 order: U+FFFF sorts before U+10000
    "non_bmp": (_obj(["\U0001F600", "\uffff", "\U00010000", "a", "\ud7ff", "\U0001F600"]), 1),
    "case_only": (_obj(["a", "A", "b", "B", "aa", "Aa", "aA", "AA"]), 1),
    "one_distinct": (_obj(["same"] * 50), 1),
    "all_distinct_1e5": (_shuffled_ids(120_000), 1),
    # the scaled cell's id column, as it arrives and as it might
    "ids_sorted_4e5": (_ids(400_000), 1),
    "ids_reversed_4e5": (_ids(400_000, range(399_999, -1, -1)), 1),
    "ids_shuffled_4e5": (_shuffled_ids(400_000), 1),
    "every_utf8_length": (_every_utf8_length(), 1),
    "few_of_many_rows": (_few_of_many(50_000), 1),
    # pandas' own string hash table reads C strings: "b\0" and "b" are one key there
    "embedded_nul": (_obj(["b\0", "b", "b\0c", "b"]), 1),
    "numpy_str_scalars": (_obj([np.str_("x"), "x", np.str_("y"), None]), 1),
    "u_dtype": (np.array(["pear", "apple", "fig", "apple"]), 1),
    "all_null": (_obj([None, np.nan, pd.NA]), 0),
    "zero_rows": (np.empty(0, dtype=object), 0),
    "mixed_objects": (_obj([1, 1.0, True, "1", None]), 0),
    "bytes_objects": (_obj([b"x", "x", b"y", None]), 0),
    "s_dtype": (np.array([b"pear", b"apple", b"pear"]), 0),
    "str_and_nat": (_obj(["a", pd.NaT, "b"]), 0),
    "lone_surrogate": (_obj(["\ud800", "a", "\ud800", None]), 0),
}


@pytest.mark.parametrize("case", sorted(ARRAYS))
def test_array_encodes_as_the_plain_loop_does(case):
    vals, hashed = ARRAYS[case]
    enc, counts = encode(vals)
    assert_same(enc, vals)
    assert counts["rows"] == len(vals) and counts["distinct"] == len(enc.vocab)
    assert counts["hashed"] == hashed == counts["native_sort"]
    if case == "every_utf8_length":
        assert counts["distinct"] >= 20_000
        assert {len(v.encode()) for v in enc.vocab if len(v) == 1} == {1, 2, 3, 4}


def _chunked_arrow_str() -> pd.Series:
    parts = [pd.Series(["x", "y", None], dtype="str"), pd.Series(["z", "x", "é"], dtype="str"),
             pd.Series([], dtype="str"), pd.Series([None, "a"], dtype="str")]
    s = pd.concat(parts, ignore_index=True)
    assert s.array._pa_array.num_chunks > 1  # what read_host_frame's pd.concat leaves
    return s


def _concat_of_parts() -> pd.Series:
    """What ``read_host_frame`` hands over: the part frames' ``str`` columns
    under one ``pd.concat``, a chunked Arrow array, the parts' values
    interleaved in the order."""
    rng = np.random.default_rng(6)
    words = _obj([f"k{i:05d}é" for i in rng.permutation(9_000)] + [None] * 300)
    parts = [pd.DataFrame({"key": pd.Series(part, dtype="str")})
             for part in np.array_split(words[rng.permutation(len(words))], 4)]
    s = pd.concat(parts, ignore_index=True)["key"]
    assert s.array._pa_array.num_chunks == 4
    return s


SERIES = {
    "str_arrow_chunked": _chunked_arrow_str,
    "str_arrow_concat_of_parts": _concat_of_parts,
    "str_arrow_all_null": lambda: pd.Series([None, None], dtype="str"),
    "str_python_backed_nul": lambda: pd.Series(
        ["b\0", "b", "b\0", "a"], dtype=pd.StringDtype("python", na_value=np.nan)),
    "string_python": lambda: pd.Series(["q", pd.NA, "p", "q", ""], dtype="string[python]"),
    "string_pyarrow": lambda: pd.Series(["q", pd.NA, "p", "q", ""], dtype="string[pyarrow]"),
    "object_strings": lambda: pd.Series(_obj(["q", None, "p", np.nan])),
    "object_mixed": lambda: pd.Series(_obj([1, 1.0, True, "1", None])),
    "category_unused": lambda: pd.Series(
        pd.Categorical(["b", "a", None, "b"], categories=["z", "b", "never", "a"])),
    "category_all_null": lambda: pd.Series(pd.Categorical([None, None], categories=["a"])),
    # two categories that str() to one string are one value
    "category_colliding": lambda: pd.Series(
        pd.Categorical([1, "1", "0", None, 1], categories=["0", 1, "1", 2.5])),
    # 1 and "1" are one string, 1.5 and "1.5" another (pandas takes 1 and 1.0 for one
    # category): Python must order, and merge, what may be any object
    "category_int_str_float": lambda: pd.Series(
        pd.Categorical([1.5, "1", None, 1, "1.5", 1.5], categories=["1.5", 1, "1", 1.5, "unused"])),
}
SERIES_LOOPED = {"object_mixed"}


@pytest.mark.parametrize("case", sorted(SERIES))
def test_series_encodes_as_its_object_array_does(case):
    s = SERIES[case]()
    enc, counts = encode(s)
    assert_same(enc, s.to_numpy(dtype=object))
    assert counts["rows"] == len(s) and counts["hashed"] == (case not in SERIES_LOOPED)
    if case == "category_unused":
        assert list(enc.vocab) == ["a", "b"]
    if case == "category_colliding":
        assert list(enc.vocab) == ["0", "1"] and list(enc.codes) == [1, 1, 0, -1, 1]
    if case == "category_int_str_float":
        assert list(enc.vocab) == ["1", "1.5"] and list(enc.codes) == [1, 0, -1, 0, 1, 1]


def _frame(n: int = 700) -> pd.DataFrame:
    rng = np.random.default_rng(11)

    def pick(cats, null):
        return pd.Series(np.where(rng.random(n) < null, None,
                                  _obj(cats)[rng.integers(0, len(cats), n)]), dtype=object)

    return pd.DataFrame({
        "id": [f"r{i:05d}" for i in rng.permutation(n)],
        "city": pick(["Zürich", "zagreb", "Århus", "Aachen", "İzmir", "\U0001F600"], 0.1),
        "grade": pick(["a", "A", "b", "B"], 0.0),
        "note": pick(["x y", "x,y", 'say "hi"', "-"], 0.3),
        "amount": rng.normal(size=n),
    })


@pytest.mark.parametrize("file_type", ["csv", "parquet", "json"])
def test_read_dataset_keeps_vocab_and_device_codes(file_type, tmp_path):
    """Through the reader: what ``read_dataset`` puts on the device for each
    string column is what the plain loop makes of the frame the same files
    decode to (three part files, so Arrow-backed columns arrive chunked)."""
    df = _frame()
    path = str(tmp_path / file_type)
    data_ingest.write_dataset(Table.from_pandas(df), path, file_type,
                              {"repartition": 3, "mode": "overwrite"})
    files = data_ingest._resolve_files(path, file_type)
    assert len(files) == 3
    host = data_ingest.read_host_frame(files, file_type, {})
    before = len(obs.get_tracer().snapshot())
    tbl = data_ingest.read_dataset(path, file_type)
    spans = [sp for sp in obs.get_tracer().snapshot()[before:] if sp.name == "ingest/encode"]
    strings = ["id", "city", "grade", "note"]
    assert tbl.nrows == len(df) and len(spans) == len(strings)
    assert all(sp.args["hashed"] == 1 and sp.args["rows"] == len(df) for sp in spans)
    assert all(sp.args["native_sort"] == 1 for sp in spans)
    for name in strings:
        codes, vocab, mask = reference(host[name].to_numpy(dtype=object))
        col = tbl[name]
        assert col.kind == "cat" and col.dtype_name == "string"
        assert col.vocab.dtype == object and list(col.vocab) == list(vocab)
        assert np.array_equal(np.asarray(col.data)[: tbl.nrows], codes)
        assert np.array_equal(np.asarray(col.mask)[: tbl.nrows], mask)
        assert not np.asarray(col.mask)[tbl.nrows:].any()
        assert (np.asarray(col.data)[tbl.nrows:] == -1).all()
    assert tbl["amount"].kind == "num"


def test_from_pandas_names_the_dtypes_it_means():
    """pandas 3 spells the string dtype ``str``: such a column, a ``string``
    one and a categorical are encoded from the Series, an object column from
    its array, and all four give one answer."""
    words = ["b", None, "a", "b"]
    df = pd.DataFrame({
        "as_str": pd.Series(words, dtype="str"),
        "as_string": pd.Series(words, dtype="string"),
        "as_object": pd.Series(_obj(words)),
        "as_category": pd.Series(words, dtype="category"),
        "number": [1.0, 2.0, 3.0, 4.0],
    })
    before = len(obs.get_tracer().snapshot())
    tbl = Table.from_pandas(df)
    spans = [sp for sp in obs.get_tracer().snapshot()[before:] if sp.name == "ingest/encode"]
    assert len(spans) == 4 and all(sp.args["hashed"] == 1 for sp in spans)
    for name in ("as_str", "as_string", "as_object", "as_category"):
        col = tbl[name]
        assert col.kind == "cat" and list(col.vocab) == ["a", "b"]
        assert list(np.asarray(col.data)[:4]) == [1, -1, 0, 1]
        assert list(np.asarray(col.mask)[:4]) == [True, False, True, True]
    assert tbl["number"].kind == "num"


# ---------------------------------------- a long column of mostly distinct values ----
def _free_text(n, seed):
    """Mostly distinct values with what a partition by the first bytes has to
    get right: empty strings, values shorter than the prefix, one a prefix
    of another, NUL bytes, multi-byte characters, shared eight-byte
    prefixes, nulls and repeats."""
    g = np.random.default_rng(seed)
    words = np.array(["", "a", "a\x00", "ab", "abcdefgh", "abcdefghi", "abcdefgh\x00", "é", "éa", "ée",
                      "\U0001F600x", "zzzzzzzzzzzz", "id", "id0"], dtype=object)
    ids = np.array([f"id{int(i):07d}" for i in g.integers(0, n // 3, n // 3)], dtype=object)
    text = np.array(["".join(g.choice(list("ab é"), int(k))) for k in g.integers(0, 14, n // 3)], dtype=object)
    vals = np.concatenate([words[g.integers(0, len(words), n - 2 * (n // 3))], ids, text])
    vals[g.random(len(vals)) < 0.01] = None
    g.shuffle(vals)
    return vals


@pytest.mark.parametrize("seed", [1, 2])
def test_bucketed_encode_gives_the_one_hash_tables_codes_and_vocab(monkeypatch, seed):
    import pyarrow as pa

    from anovos_tpu.shared import table as table_mod

    vals = _free_text(9000, seed)
    arr = pa.array(vals, type=pa.large_string())
    for strings in (arr, arr.slice(17, 8000)):  # a slice: an offset into the buffers
        e = strings.dictionary_encode()
        want = table_mod._arrow_sorted_vocab_codes(
            e.indices.fill_null(-1).to_numpy(zero_copy_only=False), e.dictionary)
        got, counts = table_mod._bucketed_encode(strings)
        assert np.array_equal(got.codes, want.codes) and got.codes.dtype == np.int32
        assert list(got.vocab) == list(want.vocab)
        assert counts["hashed"] == counts["native_sort"] == 1 and counts["buckets"] > 1
    keys = table_mod._prefix_keys(arr)
    by_bytes = sorted(range(len(vals)), key=lambda i: (vals[i] or "").encode())
    assert (np.diff(keys[by_bytes].astype(np.float64)) >= 0).all()  # s <= t bytewise: key(s) <= key(t)
    # the path is taken by size and by the share of distinct values in the column's head, and only then
    assert table_mod._mostly_distinct(arr) and not table_mod._mostly_distinct(pa.array(["x", "y"] * 50, pa.large_string()))
    taken = []
    real = table_mod._bucketed_encode
    monkeypatch.setattr(table_mod, "_bucketed_encode", lambda s: taken.append(len(s)) or real(s))
    plain = encode_strings(pd.Series(vals, dtype="str"))
    assert taken == []  # 9,000 rows: one hash table
    monkeypatch.setattr(table_mod, "_BUCKETED_ENCODE_MIN_ROWS", 1000)
    bucketed = encode_strings(pd.Series(vals, dtype="str"))
    encode_strings(pd.Series(["x", "y"] * 1000, dtype="str"))  # long enough, two values: one hash table
    assert taken == [len(vals)]
    assert np.array_equal(bucketed.codes, plain.codes) and list(bucketed.vocab) == list(plain.vocab)
    assert (plain.codes < 0).sum() == sum(v is None for v in vals)
