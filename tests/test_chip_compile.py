"""Compile for the chip without the chip: the TPU compiler is installed and
compiles for a described, unattached ``v5e:2x2`` topology.  Kept here: the
two Pallas kernels that Mosaic accepts and the main path's large XLA programs
at 4 M rows x 16 columns on one described chip, each read against 16 GB.

One file, one worker: only one process may load the TPU library.  The
topology is described inside a fixture (never at import or collection), and
JAX's persistent compilation cache is off around these compiles — such an
entry is written but cannot be read back without a chip.
"""

import os

import pytest

ROWS = 1 << 22  # Runtime.pad_rows(4_000_000)
K = 16
HBM_BYTES = 16e9


@pytest.fixture(scope="module")
def topo():
    import jax
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache as cc

    os.environ.setdefault("TPU_LOG_DIR", "disabled")  # else the compiler logs under /tmp
    enabled = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    cc.reset_cache()
    try:
        desc = topologies.get_topology_desc(platform="tpu", topology_name="v5e:2x2")
    except Exception as e:
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    yield desc
    jax.config.update("jax_enable_compilation_cache", enabled)
    cc.reset_cache()


@pytest.fixture(scope="module")
def shapes(topo):
    import jax
    import jax.numpy as jnp
    from jax.sharding import SingleDeviceSharding

    one_chip = SingleDeviceSharding(topo.devices[0])

    def spec(shape, dtype):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)

    return {"X": spec((ROWS, K), jnp.float32), "M": spec((ROWS, K), jnp.bool_),
            "cuts": spec((K, 9), jnp.float32)}


def _compile(fn, *args, **kw):
    compiled = fn.lower(*args, **kw).compile()
    ma = compiled.memory_analysis()
    total = ma.temp_size_in_bytes + ma.argument_size_in_bytes + ma.output_size_in_bytes
    assert total < HBM_BYTES, f"{total / 1e9:.1f} GB does not fit one v5e chip"
    return compiled


def test_moments_pallas_compiles(shapes):
    from anovos_tpu.ops.pallas_kernels import moments_pallas

    assert "tpu_custom_call" in _compile(moments_pallas, shapes["X"], shapes["M"]).as_text()


def test_binned_histograms_pallas_compiles(shapes):
    """Refused before PR 22 (infer-vector-layout: i1 shape cast); the kernel
    now keeps every intermediate 2-D."""
    from anovos_tpu.ops.pallas_kernels import binned_histograms_pallas

    c = _compile(binned_histograms_pallas, shapes["X"], shapes["M"], shapes["cuts"], nbins=10)
    assert "tpu_custom_call" in c.as_text()


def test_masked_moments_compiles(shapes):
    from anovos_tpu.ops.reductions import _masked_moments_xla

    _compile(_masked_moments_xla, shapes["X"], shapes["M"])


def test_describe_numeric_compiles(shapes):
    """The fused describe (moments + the sort behind percentiles, mode and
    distinct count): the main path's largest program, ~3.2 GB of temp."""
    from anovos_tpu.ops.describe import _describe_numeric

    _compile(_describe_numeric, shapes["X"], shapes["M"])


def test_describe_wide_int_compiles_to_one_two_operand_sort(topo):
    """The wide pair's order statistics as the chip's compiler leaves them: one
    sort whose only operands are the two key halves (a stable sort would carry
    an iota besides, an argsort its permutation) and no gather of row length."""
    import re

    import jax
    import jax.numpy as jnp
    from jax.sharding import SingleDeviceSharding

    from anovos_tpu.ops.describe import PCTL_QS, _describe_wide_int

    rows, k = 1 << 16, 3
    one_chip = SingleDeviceSharding(topo.devices[0])
    pair = jax.ShapeDtypeStruct((rows, k), jnp.int32, sharding=one_chip)
    mask = jax.ShapeDtypeStruct((rows, k), jnp.bool_, sharding=one_chip)
    text = _compile(_describe_wide_int, pair, pair, mask, cp=False).as_text()
    sorts = re.findall(r" sort\(([^)]*)\)", text)
    assert len(sorts) == 1, sorts
    assert len(sorts[0].split(",")) == 2, sorts[0]
    # the takes that stay: the percentile grid's (11, k) and the mode's (k,)
    gathered = re.findall(r"= s32\[([0-9,]*)\]\S* gather\(", text)
    assert gathered and all(shape.split(",")[0] in (str(len(PCTL_QS)), str(k)) for shape in gathered), gathered


def test_ts_num_viz_program_compiles_without_a_scatter_or_a_sort(topo, shapes):
    """``nyc_taxi.ts_inspect``'s fused aggregate at a quarter of the month
    (4,194,304 x 16; the cell runs 8,388,608), classes 32 / 8 / 8: the moments
    by contraction, so no scatter; the medians by counting (PR 40), so no
    sort, and nothing as long as the rows is written but the scan's copy of
    the block (values and validity, 4 + 1 bytes a cell): the program's
    temporaries stay under that and a tenth."""
    import jax
    import jax.numpy as jnp
    from jax.sharding import SingleDeviceSharding

    from anovos_tpu.data_analyzer.ts_analyzer import _ts_num_viz_program

    one_chip = SingleDeviceSharding(topo.devices[0])
    secs = jax.ShapeDtypeStruct((ROWS,), jnp.int32, sharding=one_chip)
    valid = jax.ShapeDtypeStruct((ROWS,), jnp.bool_, sharding=one_chip)
    lo = jax.ShapeDtypeStruct((), jnp.int32, sharding=one_chip)
    compiled = _compile(_ts_num_viz_program, lo, secs, valid, shapes["X"], shapes["M"],
                        nseg_d=32, nseg_h=8, nseg_w=8, cp=False)
    text = compiled.as_text()
    assert " scatter(" not in text and " sort(" not in text and "ts/segment_aggregate" in text
    assert compiled.memory_analysis().temp_size_in_bytes < 1.1 * ROWS * K * 5 + (64 << 20)


def test_wide_segment_aggregate_compiles_to_a_contraction_and_one_sort_without_a_scatter(topo, shapes):
    """``expedia_hotel.ts_inspect``'s daily grain (class 1,024; here at 4,194,304 x 16): the moments by
    contraction of the values' bfloat16 parts, which the compiled program must still round with
    ``reduce-precision`` (a cast to bfloat16 and back is dropped inside a fusion: PERF.md section 6,
    PR 49); min, max and median by a selection over rows that ONE sort has grouped (PR 52: of rank 1,
    the buckets and the row index, whatever the columns), the keys gathered behind it; no scatter, and
    nothing as long as the rows but the keys (as built, gathered and turned), the sort's operands and
    the scan's copy of the block."""
    import re

    import jax
    import jax.numpy as jnp
    from jax.sharding import SingleDeviceSharding

    from anovos_tpu.data_transformer import datetime as dtt

    one_chip = SingleDeviceSharding(topo.devices[0])
    ids = jax.ShapeDtypeStruct((ROWS,), jnp.int32, sharding=one_chip)
    valid = jax.ShapeDtypeStruct((ROWS,), jnp.bool_, sharding=one_chip)
    assert dtt._groups_rows(ROWS, 1024) and dtt.aggregate_routes(ROWS, K, 1024)["wide_sorts"] == 1
    compiled = _compile(dtt._segment_aggregate_jit, ids, valid, shapes["X"], shapes["M"], nseg=1024, cp=False)
    text = compiled.as_text()
    assert " scatter(" not in text and "reduce-precision(" in text and " convolution(" in text
    sorts = [line for line in text.split("\n") if " sort(" in line]
    assert len(sorts) == 1 and "ts/segment_aggregate/wide/medians" in sorts[0], sorts
    assert len(re.findall(r" sort\(([^)]*)\)", sorts[0])[0].split(",")) == 2 and f"s32[{ROWS}]" in sorts[0], sorts[0]
    assert "ts/segment_aggregate/wide/moments" in text
    assert compiled.memory_analysis().temp_size_in_bytes < 3 * ROWS * K * 4 + (256 << 20)


def test_dense_binned_histograms_compiles(shapes, monkeypatch):
    """_flat_counts with the TPU-only dense budget (1 << 30): at 4 M x 16 x
    10 the compare-and-reduce branch is taken, which no CPU test reaches."""
    from anovos_tpu.ops import drift_kernels

    monkeypatch.setattr(drift_kernels, "_dense_budget", lambda: 1 << 30)
    assert ROWS * K * 10 <= 1 << 30
    c = _compile(drift_kernels._binned_histograms_xla, shapes["X"], shapes["M"],
                 shapes["cuts"], nbins=10)
    assert "scatter" not in c.as_text()


def test_masked_corr_compiles(shapes):
    from anovos_tpu.ops.correlation import _masked_corr

    _compile(_masked_corr, shapes["X"], shapes["M"])


def test_row_sharded_describe_and_histograms_compile_for_four_chips(topo):
    """The four-chip phase of chip_smoke.py: the same programs with rows
    sharded over a 4-device mesh built from the described topology."""
    import jax
    import jax.numpy as jnp
    import numpy as np
    from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

    from anovos_tpu.ops.drift_kernels import _binned_histograms_xla
    from anovos_tpu.ops.reductions import _masked_moments_xla

    mesh = Mesh(np.array(topo.devices).reshape(4, 1), ("data", "model"))
    rows, rep = NamedSharding(mesh, P("data")), NamedSharding(mesh, P())
    X = jax.ShapeDtypeStruct((ROWS, K), jnp.float32, sharding=rows)
    M = jax.ShapeDtypeStruct((ROWS, K), jnp.bool_, sharding=rows)
    cuts = jax.ShapeDtypeStruct((K, 9), jnp.float32, sharding=rep)
    text = _compile(_masked_moments_xla, X, M).as_text()
    assert "all-reduce" in text  # per-shard partials meet in a psum
    _compile(_binned_histograms_xla, X, M, cuts, nbins=10)


@pytest.mark.parametrize("chips", [1, 4])
def test_the_uploads_split_compiles_to_slices_on_each_chip_and_states_the_row_sharding(topo, chips):
    """``table._split_rows_program`` at the width the program has and the
    epsilon table's row bucket: a block of f32 columns and one of masks, on
    one described chip and with the rows over four.  Every output is on the
    very sharding ``Runtime.shard_rows`` gives, and no chip waits for another."""
    import jax
    import jax.numpy as jnp
    import numpy as np
    from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

    from anovos_tpu.shared import table as table_mod

    mesh = Mesh(np.array(topo.devices[:chips]).reshape(chips, 1), ("data", "model"))
    rows = NamedSharding(mesh, P("data"))
    for dtype in (jnp.float32, jnp.bool_):
        block = jax.ShapeDtypeStruct((table_mod._BLOCK_ARRAYS, 32768), dtype, sharding=NamedSharding(mesh, P(None, "data")))
        compiled = _compile(table_mod._split_rows_program(rows), block)
        assert len(compiled.output_shardings) == table_mod._BLOCK_ARRAYS
        assert all(s == rows for s in compiled.output_shardings)
        text = compiled.as_text()
        assert "all-" not in text and "collective-permute" not in text
        assert compiled.memory_analysis().temp_size_in_bytes == 0  # the block and its arrays, nothing between
