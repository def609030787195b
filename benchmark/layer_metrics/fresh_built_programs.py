"""Backend compiles of the fresh pass that were no load from the compile cache
(manifest ``compile_census.built_programs``): 0 means every program came from
the cache."""


def read(run):
    census = run["fresh"]["manifest"].get("compile_census") or {}
    return census.get("built_programs")
