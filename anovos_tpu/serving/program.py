"""The AOT-compiled apply program: bundle → row→features pipeline.

``ApplyProgram`` rebuilds a bundle's transformer chain in apply-only form
(:func:`~anovos_tpu.data_transformer.transformers.from_state` — the batch
functions' pre-existing-model branches, so a served apply replays the
very same jitted programs as a batch re-apply) and owns the serving
shape-bucket discipline:

* **row buckets** — micro-batch row counts round up to geometric size
  classes (8, 16, 32, … up to the padded max batch), the PR 4 policy
  applied to the batch axis: every bucket maps to ONE set of compiled
  executables, so varying request widths hit cached programs instead of
  tracing.  Padding replicates existing rows (row-independent transforms
  make the padded rows' outputs discardable) rather than null rows,
  which would perturb inferred dtypes and break executable reuse.
* **warm()** — at server start, drive the full apply path once per
  bucket on schema-synthesized rows: every ``jax.jit`` in the chain
  lowers and compiles HERE, against the persistent XLA compile cache
  (``JAX_COMPILATION_CACHE_DIR``, else ``<checkout>/.jax_cache``) so a warm
  process boots in bounded time and a cold one pays each program once
  per (program, jaxlib) ever.  The measured wall and per-bucket compile
  counts are the server's cold-start record; after warm, a request-time
  apply at any bucket compiles NOTHING (graftcheck GC013 forbids
  request-path tracing; tests/test_serving.py pins the zero-compile
  contract through the census).

``ANOVOS_SERVE_BF16=1`` maps onto the PR 9 guarded sweep: the serving
process sets ``ANOVOS_TPU_BF16=1`` so any MXU matmul in the chain rides
``ops/mxu``'s bf16-inputs/f32-accumulation routing with the same
corruption-class guards the batch path tested.
"""

from __future__ import annotations

import logging
import os
import time
from typing import Dict, List, Optional

import numpy as np
import pandas as pd

from anovos_tpu.serving.bundle import FeatureBundle

logger = logging.getLogger("anovos_tpu.serving.program")

__all__ = ["ApplyProgram"]

_BUCKET_FLOOR = 8


class ApplyProgram:
    """Apply-only pipeline for one bundle, with bucketed-batch warm-up."""

    def __init__(self, bundle: FeatureBundle):
        from anovos_tpu.data_transformer import transformers as T

        if os.environ.get("ANOVOS_SERVE_BF16", "") == "1":
            # ride the PR 9 guarded sweep: bf16 inputs + f32 accumulation
            # on the MXU-safe pre-centered matmuls only (ops/mxu.py)
            os.environ["ANOVOS_TPU_BF16"] = "1"
        self.bundle = bundle
        self.transformers = [T.from_state(s) for s in bundle.chain]
        self.input_columns: List[dict] = bundle.input_columns
        self.warmed_buckets: List[int] = []
        self.warm_stats: Dict[str, object] = {}

    # -- shape buckets ------------------------------------------------------
    @staticmethod
    def row_buckets(max_rows: int) -> List[int]:
        """Geometric batch-size classes up to (and covering) ``max_rows``."""
        out = [_BUCKET_FLOOR]
        while out[-1] < max_rows:
            out.append(out[-1] * 2)
        return out

    @classmethod
    def bucket_rows(cls, n: int, max_rows: int) -> int:
        for b in cls.row_buckets(max_rows):
            if b >= n:
                return b
        return cls.row_buckets(max_rows)[-1]

    @staticmethod
    def pad_frame(df: pd.DataFrame, rows: int) -> pd.DataFrame:
        """Pad ``df`` up to ``rows`` by cycling its own rows.

        Replicated VALID rows keep dtypes and vocab identical to the
        unpadded frame (null-row padding would float-promote int columns
        and change compiled program signatures); every transform in the
        servable set is row-independent, so the replicas' outputs are
        sliced off without affecting the live rows."""
        if len(df) >= rows:
            return df
        idx = np.arange(rows) % len(df)
        return df.iloc[idx].reset_index(drop=True)

    # -- the apply path -----------------------------------------------------
    def apply_table(self, idf):
        for ft in self.transformers:
            idf = ft.apply(idf)
        return idf

    def apply_frame(self, df: pd.DataFrame) -> pd.DataFrame:
        """Coerced request frame → feature frame (live rows only)."""
        from anovos_tpu.shared.table import Table

        n = len(df)
        out = self.apply_table(Table.from_pandas(df))
        return out.to_pandas().iloc[:n]

    # -- warm-up ------------------------------------------------------------
    def synthetic_frame(self, rows: int) -> pd.DataFrame:
        """Schema-shaped rows for warm-up: numeric columns get a spread of
        finite values plus a null; cat columns cycle the fitted vocab
        sample plus a null — so warmed program signatures (dtypes, LUT
        size classes) match what coerced live requests produce."""
        data: Dict[str, object] = {}
        for col in self.input_columns:
            name, kind = col["name"], col["kind"]
            if kind == "cat":
                vocab = list(col.get("vocab") or ["a", "b"])
                vals = [vocab[i % len(vocab)] for i in range(rows)]
                if rows > 1:
                    vals[-1] = None
                data[name] = np.array(vals, dtype=object)
            elif kind == "ts":
                base = np.datetime64("2020-01-01T00:00:00")
                data[name] = base + np.arange(rows).astype("timedelta64[s]")
            else:
                vals = np.linspace(1.0, 2.0, rows).astype(np.float64)
                if rows > 1:
                    vals[-1] = np.nan
                data[name] = vals
        return pd.DataFrame(data)

    def warm(self, max_rows: int) -> dict:
        """Compile the whole apply path for every row bucket; returns the
        cold-start record (wall, buckets, per-bucket compile counts)."""
        from anovos_tpu.obs import compile_census

        compile_census.install()
        t0 = time.perf_counter()
        per_bucket: Dict[str, int] = {}
        buckets = self.row_buckets(max_rows)
        for b in buckets:
            mark = compile_census.mark()
            self.apply_frame(self.synthetic_frame(b))
            census = compile_census.census(since=mark)
            per_bucket[str(b)] = int(census.get("compiles_total") or 0)
        wall = time.perf_counter() - t0
        self.warmed_buckets = buckets
        self.warm_stats = {
            "warm_wall_s": round(wall, 3),
            "buckets": buckets,
            "compiles_per_bucket": per_bucket,
        }
        logger.info("serving warm-up: %d bucket(s) %s in %.2fs (compiles %s)",
                    len(buckets), buckets, wall, per_bucket)
        return dict(self.warm_stats)
