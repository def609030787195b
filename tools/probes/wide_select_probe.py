"""Hand timing of how a wide segment class takes min, max and median (PR 52).

At one shape (rows x cols, class nseg; a skew like ``expedia_hotel.ts_inspect``'s:
a dense middle of days, a tail of one-row days, rows with no time) it times, each
as a program of its own with ``block_until_ready``, the functions of
``data_transformer/datetime.py`` themselves and the routes that were not taken:

- ``sort_picks``: one two-key sort a column (``_sort_picks``: the parent's route,
  kept for a class with few rows a bucket);
- ``group_keys``: the rows grouped by ONE sort of the buckets that carries the
  row index, the columns' keys gathered behind it (``_group_keys``),
  ``windowed_picks``: the ten windowed passes over the grouped keys
  (``_windowed_picks``), ``grouped_picks``: both (``_grouped_picks``), held to
  ``sort_picks`` to the bit;
- the parts of ``group_keys`` and the layouts not taken: ``group_index`` (the
  rank-1 sort alone), ``gather_rows`` ((rows, k)[idx], the one taken),
  ``gather_rows_32`` (the block padded to 32 lanes), ``gather_cols`` ((k, rows)[:, idx]);
- the route not taken: ``operands_<b>[_runs]``: the bucket sort with b columns'
  keys behind it as operands, a ``lax.map`` over the k / b blocks, over the whole
  length or in runs of its largest power of two.

    chiprun -- python -m tools.probes.wide_select_probe --out chiprun_out/probe52

The CPU (tier-1) run is a rehearsal at a small shape: its seconds mean nothing.
"""

from __future__ import annotations

import argparse
import json
import os
import statistics
import time

import jax
import jax.numpy as jnp
import numpy as np

from anovos_tpu.data_transformer import datetime as dtt


def skewed_block(rows: int, k: int, nseg: int, seed: int):
    """ids0, valid, V, Mv on the device: a dense middle of days (a third of the class), a tail of
    one-row days after it, a fifth of the rows with no time, 10 % of the values masked; integer-like,
    cents and free columns in turn."""
    g = np.random.default_rng(seed)
    dense = max(2, int(nseg * 0.35))
    ids = (nseg // 20 + g.integers(0, dense, rows)).astype(np.int32)
    tail = np.arange(nseg // 20 + dense, nseg - 1, 3, dtype=np.int32)
    ids[g.choice(rows, min(tail.size, rows), replace=False)] = tail[:rows]
    valid = g.random(rows) > 0.2
    key = jax.random.PRNGKey(seed)
    kinds = jnp.arange(k) % 3
    x = jax.random.normal(key, (rows, k), jnp.float32)
    V = jnp.where(kinds == 0, jnp.round(x * 3), jnp.where(kinds == 1, jnp.round(jnp.exp(x + 2) * 100) / 100, x * 50))
    Mv = jax.random.uniform(jax.random.fold_in(key, 1), (rows, k)) > 0.1
    return jnp.asarray(ids), jnp.asarray(valid), V, Mv


def timed(fn, *args, reps: int):
    """(median seconds of ``reps`` runs after one that compiles, that first run's seconds, the result)."""
    t0 = time.perf_counter()
    out = jax.block_until_ready(fn(*args))
    first = time.perf_counter() - t0
    runs = []
    for _ in range(reps):
        t0 = time.perf_counter()
        jax.block_until_ready(fn(*args))
        runs.append(time.perf_counter() - t0)
    return statistics.median(runs), first, out


def same_picks(got, want, cnt) -> bool:
    """Whether (mn, mx, med) agree to the bit where a bucket is live; a zero's sign, a denormal (a zero to
    the device's compare) and a NaN's payload aside."""
    live, tiny = np.asarray(cnt) > 0, np.finfo(np.float32).tiny
    ok = True
    for g, w in zip(got, want):
        g, w = np.asarray(g), np.asarray(w)
        same = (g.view(np.int32) == w.view(np.int32)) | ((np.abs(g) < tiny) & (np.abs(w) < tiny)) | (np.isnan(g) & np.isnan(w))
        ok &= bool(same[live].all())
    return ok


def operands_sort(s, keys, b: int, runs: bool):
    """The bucket sort with ``b`` of the (k, rows) keys behind it, a ``lax.map`` over the k / b blocks."""
    k, rows = keys.shape
    run = rows & -rows if runs else rows
    s2 = s.reshape(-1, run)

    def block(cols):
        return jax.lax.sort((s2, *(cols[j] for j in range(b))), dimension=1, num_keys=1, is_stable=False)

    return jax.lax.map(block, keys.reshape(k // b, b, -1, run))


def index_sort(s):
    return jax.lax.sort((s, jnp.arange(s.shape[0], dtype=jnp.int32)), num_keys=1, is_stable=False)


def probe(rows: int, k: int, nseg: int, seed: int, reps: int, variants) -> dict:
    ids0, valid, V, Mv = skewed_block(rows, k, nseg, seed)
    ok = Mv & valid[:, None]
    cnt = jax.jit(lambda i, o: jnp.stack([jnp.bincount(jnp.where(o[:, j], i, nseg), length=nseg + 1)[:nseg]
                                          for j in range(k)]).astype(jnp.float32))(ids0, ok)
    padded, chunk, nblk, steps = dtt._group_layout(rows, nseg)
    table, want = {}, None

    def row(name, fn, *args):
        sec, first, out = timed(fn, *args, reps=reps)
        table[name] = {"s": sec, "first_s": first}
        print(f"[probe] rows={rows} k={k} nseg={nseg} {name}: {sec:.4f} s (first {first:.1f})", flush=True)
        return out

    if "sort_picks" in variants:
        want = row("sort_picks", jax.jit(dtt._sort_picks, static_argnums=4), ids0, ok, V, cnt, nseg)
    grouped = None
    if "group_keys" in variants or "windowed_picks" in variants:
        grouped = row("group_keys", jax.jit(dtt._group_keys, static_argnums=4), ids0, valid, ok, V, nseg)
    if "windowed_picks" in variants:
        picks = row("windowed_picks", jax.jit(dtt._windowed_picks, static_argnums=3), *grouped, cnt, nseg)
        table["windowed_picks"].update(steps=int(dtt._window_steps(grouped[0], nseg)[2]), bound=steps,
                                       same=want is not None and same_picks(picks, want, cnt))
    if "grouped_picks" in variants:
        picks = row("grouped_picks", jax.jit(dtt._grouped_picks, static_argnums=5), ids0, valid, ok, V, cnt, nseg)
        table["grouped_picks"]["same"] = want is not None and same_picks(picks, want, cnt)
    # the routes not taken, on the same buckets and keys
    s = jnp.where(valid & (ids0 >= 0) & (ids0 < nseg), ids0, nseg).astype(jnp.int32)
    keys = jnp.where(ok, dtt._sort_keys(V), dtt._I32_BIG)  # (rows, k)
    for name in variants:
        if name.startswith("operands_"):  # operands_<b>[_runs]
            parts = name.split("_")
            b = min(int(parts[1]), k)
            if k % b == 0:
                row(name, jax.jit(lambda a, c, b=b, r="runs" in parts: operands_sort(a, c, b, r)), s, keys.T)
    if "group_index" in variants:
        _, idx = row("group_index", jax.jit(index_sort), s)
        row("gather_rows", jax.jit(lambda a, i: a[i]), keys, idx)
        row("gather_rows_32", jax.jit(lambda a, i: a[i]), jnp.pad(keys, ((0, 0), (0, -k % 32))), idx)
        row("gather_cols", jax.jit(lambda a, i: a[:, i]), keys.T, idx)
    flags = {n: r["same"] for n, r in table.items() if "same" in r}
    print(f"[probe] same as sort_picks: {flags}; layout (padded, chunk, blocks, steps' bound): "
          f"{(padded, chunk, nblk, steps)}; groups its rows by the rule: {dtt._groups_rows(rows, nseg)}", flush=True)
    return {"rows": rows, "cols": k, "nseg": nseg, "chunk": chunk, "seed": seed,
            "groups_rows": dtt._groups_rows(rows, nseg), "device": jax.devices()[0].device_kind, "table": table}


_ALL = ("sort_picks", "group_keys", "windowed_picks", "grouped_picks", "group_index", "operands_3", "operands_3_runs")


def main(argv=None) -> list:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--shape", action="append", default=[],
                    help="rows,cols,nseg (repeatable; default: the cell's 6291456,21,2048)")
    ap.add_argument("--variants", default=",".join(_ALL))
    ap.add_argument("--seed", type=int, default=52)
    ap.add_argument("--reps", type=int, default=3)
    ap.add_argument("--out", default=None, help="a directory for wide_select_probe.jsonl")
    a = ap.parse_args(argv)
    shapes = [tuple(int(x) for x in s.split(",")) for s in a.shape] or [(6291456, 21, 2048)]
    out = [probe(*shape, a.seed, a.reps, a.variants.split(",")) for shape in shapes]
    if a.out:
        os.makedirs(a.out, exist_ok=True)
        with open(os.path.join(a.out, "wide_select_probe.jsonl"), "a") as f:
            f.writelines(json.dumps(r) + "\n" for r in out)
    return out


if __name__ == "__main__":
    main()
