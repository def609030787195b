"""Distinct programs the fresh pass traced and compiled or loaded (manifest
``compile_census``): what a fresh process pays per program."""


def read(run):
    census = run["fresh"]["manifest"].get("compile_census") or {}
    return census.get("distinct_programs")
