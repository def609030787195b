"""anovos_tpu — a TPU-native feature-engineering-at-scale framework.

A ground-up JAX/XLA re-design of the Anovos workflow (reference: upstream
anovos, src/main/anovos): the Spark DataFrame engine is replaced by a
device-sharded columnar Table, Spark SQL aggregations by batched XLA
reductions with ICI collectives, and driver-side sklearn/TF models by
JAX-native models trained on TPU.

Subpackages mirror the reference's module surface (workflow.py dispatches by
the same YAML top-level keys):

- ``shared``            runtime (mesh singleton) + Table + dtype utils
- ``ops``               the kernel library (masked reductions, quantiles,
                        histograms, segment ops, correlation, ALS, KNN, ...)
- ``parallel``          mesh construction, sharding helpers, collectives
- ``data_ingest``       read/write/concat/join/column ops/sampling/auto-detect
- ``data_analyzer``     stats_generator, quality_checker, association_evaluator,
                        ts_analyzer, geospatial_analyzer
- ``drift_stability``   drift_detector, stability
- ``data_transformer``  transformers, datetime, geospatial
- ``data_report``       report_preprocessing + report generation (host-side)
- ``serving``           versioned feature bundles + the online feature server
- ``models``            JAX/flax models (autoencoder latent features, ...)
- ``feature_recommender`` / ``feature_store``
"""

import time as _time

# the package's first statement: what a process did before it is the
# manifest's ``process/interpreter``, what it imports from here to the end of
# ``workflow``'s imports is ``process/import`` (obs.manifest.process_section)
IMPORT_STARTED = _time.perf_counter()

from anovos_tpu.version import __version__  # noqa: E402,F401
