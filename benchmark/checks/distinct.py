"""Exact distinct counts (nulls aside) of the string columns.
args: ``columns``.  Table: measures_of_cardinality."""

from benchmark.harness.check import exact, table


def read(out_dir, traffic, args):
    card = table(out_dir, traffic["tables"]["measures_of_cardinality"])
    return {a: int(v) for a, v in zip(card["attribute"], card["unique_values"])}


def reference(frames, args):
    return {c: int(frames.main[c].nunique(dropna=True)) for c in args["columns"]}


def compare(ans, ref, tolerances, args):
    return [exact("distinct", {c: ans.get(c) for c in ref}, ref)]
