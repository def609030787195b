# anovos_tpu demo image (mirrors the reference's demo/Dockerfile flow:
# build, run the demo pipeline, copy the report out — see run_demo.sh).
#
# The TPU runtime is provided by the host/pod environment in production;
# this image runs the demo on the CPU backend with a virtual 8-device mesh,
# which exercises the identical sharded code paths.
FROM python:3.12-slim

WORKDIR /app

# jax pinned to the version the framework is tested against; everything
# here is CPU-only so the image stays pullable anywhere
RUN pip install --no-cache-dir \
    "jax==0.9.0" "jaxlib==0.9.0" "numpy==2.0.2" "pandas==3.0.3" "pyarrow>=14" \
    "pyyaml>=6" "optax>=0.2" "scipy>=1.11" "sympy>=1.12" "statsmodels>=0.14"

COPY anovos_tpu/ /app/anovos_tpu/
COPY native/ /app/native/
COPY config/ /app/config/
COPY examples/ /app/examples/
COPY main.py pyproject.toml /app/

# build the native layer when a toolchain is present; the Python fallbacks
# cover every entry point if this is skipped
RUN (command -v g++ >/dev/null && cd native && make 2>/dev/null) || true

ENV JAX_PLATFORMS=cpu \
    XLA_FLAGS=--xla_force_host_platform_device_count=8

# the demo pipeline: config-driven run -> /app/report_stats/ml_anovos_report.html
CMD ["python", "examples/03_full_report.py", "/app"]
