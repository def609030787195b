"""Test harness: 8 virtual CPU devices (the multi-chip "fake backend" the
Spark reference never had — SURVEY.md §4).  Env vars must be set before jax
imports anywhere, so this conftest does it at import time."""

import os

_ON_TPU = os.environ.get("ANOVOS_TEST_TPU", "") == "1"

if not _ON_TPU:
    os.environ["JAX_PLATFORMS"] = "cpu"  # read by jax at backend init: enough on its own
    _flags = os.environ.get("XLA_FLAGS", "")
    if "xla_force_host_platform_device_count" not in _flags:
        os.environ["XLA_FLAGS"] = (_flags + " --xla_force_host_platform_device_count=8").strip()

import jax  # noqa: E402

# init_runtime turns JAX's persistent compilation cache on by default; the
# tests that count backend compiles (test_compile_census, test_shape_buckets)
# must see real compiles on every run, so the session runs with it off.  Set before the first compile: JAX decides once per process
# (the cache-placement tests reset that decision for themselves).
jax.config.update("jax_enable_compilation_cache", False)

import numpy as np  # noqa: E402
import pytest  # noqa: E402


@pytest.fixture(autouse=True)
def _compile_cache_dir_stays_the_tests_own():
    """``benchmark/run.py``'s ``main`` names its compile cache directory in
    ``os.environ``.  A test that calls it in-process must not hand that name to
    the subprocesses of every later test of its worker: their CPU programs
    landed in ``.jax_cache/benchmark/`` without the stamps a size-limited cache
    needs, and on the chip every write there failed (PERF.md, PR 25)."""
    before = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    yield
    if before is None:
        os.environ.pop("JAX_COMPILATION_CACHE_DIR", None)
    else:
        os.environ["JAX_COMPILATION_CACHE_DIR"] = before


@pytest.fixture(scope="module", autouse=True)
def _the_benchmark_harness_starts_cold(request):
    """``tests/benchmark/test_benchmark_harness.py`` counts the programs its
    first pass compiles (``fresh_programs > 0``, ``fresh_pass_s > pass_s``).
    xdist hands files to workers in an order that shifts with every file's
    length and duration, and a worker that has run the same programs at the
    same 2,000 rows leaves that pass nothing to compile (PERF.md section 7
    item 19): its module starts from empty jit caches whatever ran before, as
    ``tests/test_chip_smoke.py``'s cold run does."""
    if request.module.__name__.rsplit(".", 1)[-1] == "test_benchmark_harness":
        jax.clear_caches()
    yield


@pytest.fixture(scope="session", autouse=True)
def runtime():
    """Module-scoped runtime over the 8-device virtual mesh (the analogue of
    the reference's local[*] spark_session fixture, src/test/conftest.py:6-18)."""
    from anovos_tpu.shared.runtime import init_runtime

    rt = init_runtime()
    if _ON_TPU:
        # a leftover JAX_PLATFORMS=cpu in the shell would silently turn the
        # "on-hardware" sweep into a CPU run that misses every TPU-only
        # numerics class (bf16 MXU inputs, transcendental approximation)
        plat = jax.devices()[0].platform
        assert plat != "cpu", f"ANOVOS_TEST_TPU=1 but jax backend is {plat}"
    else:
        assert rt.n_devices == 8, f"expected 8 virtual devices, got {rt.n_devices}"
    return rt


@pytest.fixture(scope="session", autouse=True)
def income_dataset():
    """The seeded 32,561-row income dataset under ``data/income_dataset``
    (generated once; the shipped configs and several tests read it)."""
    from anovos_tpu.data_ingest.synthetic import generate

    return generate()


@pytest.fixture(scope="session")
def income_df(income_dataset):
    """The seeded income dataset as pandas (32,561 rows)."""
    from anovos_tpu.data_ingest.synthetic import load_income

    return load_income()


@pytest.fixture(scope="session")
def rng():
    return np.random.default_rng(42)
