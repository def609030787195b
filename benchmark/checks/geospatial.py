"""geospatial_controller's summary of the latitude-longitude pair it
detects: records and distinct pairs exact; min, max, mean and median of
each coordinate within the tolerances of those names.  Table: geospatial_stats."""

import pandas as pd

from benchmark.harness.check import exact, table, toleranced

STATS = ("min", "max", "mean", "median")


def read(out_dir, traffic, args):
    return table(out_dir, traffic["tables"]["geospatial_stats"]).iloc[0]


def reference(frames, args):
    lat, lon = frames.main[args["lat"]], frames.main[args["lon"]]
    out = {"records": len(lat), "distinct_pairs": len(frames.main[[args["lat"], args["lon"]]].drop_duplicates())}
    for k, x in (("lat", lat), ("lon", lon)):
        out.update({f"{k}_min": x.min(), f"{k}_max": x.max(), f"{k}_mean": x.mean(), f"{k}_median": x.median()})
    return pd.Series(out)


def compare(ans, ref, tolerances, args):
    counts = ["records", "distinct_pairs"]
    rows = [exact("geo_counts", {k: int(ans[k]) for k in counts}, {k: int(ref[k]) for k in counts})]
    for s in STATS:
        keys = [f"lat_{s}", f"lon_{s}"]
        rows.append(toleranced("geo_" + s, ans[keys].astype(float), ref[keys].astype(float), tolerances[s]))
    return rows
