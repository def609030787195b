"""``anovos_tpu.serving`` — versioned feature bundles + online serving.

Anovos ends at batch artifacts; production feature engineering ends at a
serving endpoint (ROADMAP open item 3).  This subsystem closes that gap
with three layers, each riding machinery earlier PRs built:

* **bundle** (``serving.bundle``): every fitted transformer's state
  (binning edges, z/IQR/min-max scaler params, boxcox λs, encoder
  vocab maps, imputer fills, outlier keep-sets — exported through the
  ``data_transformer.transformers.fitted_state``/``from_state``
  round-trip contract) plus the input schema and shape-bucket classes,
  persisted as ONE versioned, content-addressed document in the PR 5
  CAS store.  The bundle version IS the sha256 of its canonical JSON;
  a format-version mismatch refuses to load.
* **program** (``serving.program``): the apply-only row→features
  pipeline rebuilt from a bundle.  ``warm()`` lowers and compiles every
  jitted program for each micro-batch shape bucket at server start —
  against the persistent XLA compile cache (PR 5), so cold start is
  bounded and measured — after which request-time applies replay cached
  executables only (graftcheck GC013 forbids request-path tracing).
  ``ANOVOS_SERVE_BF16=1`` routes the serving process's MXU matmuls
  through the PR 9 guarded bf16 sweep (``ANOVOS_TPU_BF16``).
* **server** (``serving.server``): a threaded request loop with a
  micro-batching queue (``ANOVOS_SERVE_BATCH_WINDOW_MS`` /
  ``ANOVOS_SERVE_MAX_BATCH``) that pads request batches onto the PR 4
  shape buckets so varying widths hit one executable, applies the PR 10
  sanitize policy at the request boundary (hostile ±inf / f32-overflow /
  schema-drift payloads get structured per-request quarantine responses,
  never a poisoned kernel or a dead server), books per-request latency
  and QPS through ``obs`` with devprof dispatch attribution on the apply
  path, and dumps a flight-recorder postmortem on fatal apply errors.

``python -m anovos_tpu.serving export|smoke`` is the CLI;
``tools/chaos_run.py --scenario serve-fault`` is the fault gate.
"""

from anovos_tpu.serving.bundle import (  # noqa: F401
    BUNDLE_FORMAT_VERSION,
    BundleVersionError,
    FeatureBundle,
    fit_bundle,
    list_bundles,
    load_bundle,
    save_bundle,
)
from anovos_tpu.serving.program import ApplyProgram  # noqa: F401
from anovos_tpu.serving.server import (  # noqa: F401
    FeatureServer,
    coerce_payload,
    frame_to_payload,
)
