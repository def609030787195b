"""Shared runtime: mesh singleton, sharded columnar Table, dtype utilities.

Replaces the reference's ``shared/`` (spark.py SparkSession singleton +
utils.py dtype triage; src/main/anovos/shared/spark.py:26,97) with a JAX
device-mesh runtime and a device-resident Table.
"""

from anovos_tpu.shared.runtime import get_runtime, init_runtime  # noqa: F401
from anovos_tpu.shared.table import Column, Table  # noqa: F401
