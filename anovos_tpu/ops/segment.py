"""Sort-based segment machinery: distinct counts, mode, group-by counts.

Replaces Spark's shuffle-based groupBy (stats_generator.py:386-401 mode loop;
:605-612 countDistinct/HLL).  Keys on device are int32 codes (categoricals)
or raw numerics; a device sort turns equal keys into contiguous segments and
transition-counting / bincount does the rest.  Static shapes throughout —
"mask-don't-shrink" (SURVEY.md §7 hard part 2).

The group counts (``code_counts``, ``code_label_counts``) and the per-code
lookup (``vocab_lookup``) of ONE column each have two routes inside their one
jitted program, picked by the static padded class (PR 44):

* a class of at most ``_DENSE_COUNT_LANES_MAX`` / ``_DENSE_GATHER_LANES_MAX``
  lanes is counted / looked up by contracting one-hots of the codes on the
  MXU, the rows in chunks under a ``lax.scan`` so that no one-hot exists
  whole.  The one-hots (and a count's 0/1 validity, and a LUT's bytes) are
  exact in bfloat16 and the products accumulate in f32: a count is the exact
  integer up to 2^24, where the f32 scatter-add of 1.0 stops counting too, and
  a looked-up value is the LUT's bits.  No value with a mantissa passes
  through bf16 (a label count's weights stay f32 at ``Precision.HIGHEST``).
* a wider class keeps the scatter-add / the index gather, whose time on the
  chip does not grow with the class (10-15 ms at 1,572,864 rows) while the
  contraction's multiply-adds do.

The two limits were measured on a TPU v5e (CHANGES.md, PR 44, has the
timings by class).  At 1,572,864 rows and 65,536 lanes a count takes 2.8 ms,
a label count 8.8, the gather of an f32 LUT 6.9 and of a bool LUT 1.3,
against 10.5-15.2 ms for the scatter-add and the index gather; at 4,096
lanes and below every contraction is 0.2-1.1 ms; at 32,768 rows every one is
at the dispatch floor, 0.2 ms against 0.25-0.35.  At 131,072 lanes and above
the contraction is still ahead by a tenth to a third (a bool LUT by more):
those classes are ROADMAP A8's, where a sort of the codes may beat both.
A column laid over a mesh keeps the scatter-add and the gather
(``on_one_device``).
"""

from __future__ import annotations

import functools
import math
import os
from typing import Tuple

import jax
import jax.numpy as jnp

from anovos_tpu.shared.runtime import column_parallel, wants_column_parallel
from anovos_tpu.obs import timed


@timed("ops.masked_nunique")
def masked_nunique(X: jax.Array, M: jax.Array) -> jax.Array:
    """Exact distinct count per column (valid entries only).

    X: (rows, k) — any numeric (cat codes included); M: (rows, k) bool.
    Sort each column with invalid → +inf, count value transitions among the
    first n valid slots.  On a multi-device mesh the sort runs
    column-parallel (runtime.column_parallel).
    """
    return _masked_nunique(X, M, cp=wants_column_parallel(X, M))


@functools.partial(jax.jit, static_argnames=("cp",))
def _masked_nunique(X: jax.Array, M: jax.Array, cp: bool = False) -> jax.Array:
    dt = jnp.float32 if X.dtype not in (jnp.float32, jnp.float64) else X.dtype
    big = jnp.asarray(jnp.finfo(dt).max, dt)
    Xs = jnp.sort(column_parallel(jnp.where(M, X.astype(dt), big), cp), axis=0)
    n = M.sum(axis=0)  # (k,)
    rows = X.shape[0]
    pos = jnp.arange(rows)[:, None]
    valid = pos < n[None, :]
    trans = jnp.concatenate(
        [jnp.ones((1, X.shape[1]), bool), Xs[1:] != Xs[:-1]], axis=0
    )
    return (trans & valid).sum(axis=0)


def _bucket_segments(n: int) -> int:
    """Static segment counts round up to 16^k size classes (min 16) up to
    65,536, and to 2^k classes above: every vocab size in a table then
    reuses ONE compiled program per row shape — unbucketed, a 19-column
    describe compiled code_counts 16 times on identical array shapes, a
    fresh XLA compile each.
    Power-of-SIXTEEN (coarser than describe_cat's dense-sweep pow-4
    buckets, which pay O(rows·k·vocab) per lane and must stay fine):
    segment_sum cost is rows-driven and the outputs are (vocab,)-scale
    vectors, so the coarse classes {16, 256, 4096, 65536} trade idle
    output lanes for a near-minimal distinct-program count across a run's
    vocab-size spread (cold-compile census).
    Above 65,536 the next 16^k classes are 1,048,576 and 16,777,216: a
    vocabulary of 65,537 values would pad to sixteen times its size, and
    every count vector fetched and every LUT uploaded is that padded vector
    (4 bytes a lane, up to 64 MB an array, per column per call).  There the
    padded dimension is memory-proportional, which is what
    ``bucket_segments_pow2`` is for: at most twice the vocabulary, and at
    most eight more classes up to 2^24 (hashed ids of 10^5–10^7 values: a
    click log's categoricals)."""
    if n > 65536:
        return bucket_segments_pow2(n)
    b = 16
    while b < max(n, 1):
        b *= 16
    return b


def bucket_segments_pow2(n: int) -> int:
    """2^k size classes (min 8) — for consumers whose PADDED dimension is
    memory-proportional (a (k, maxv) LUT matrix, a (k, nseg) aggregate
    table): waste stays ≤2× where the coarse 4^k/16^k classes could cost
    16× real bytes."""
    return max(8, 1 << (max(n, 1) - 1).bit_length())


def segment_class(n: int) -> int:
    """The static segment count a (k, nseg) aggregate program is compiled
    for: ``bucket_segments_pow2`` unless ``ANOVOS_SHAPE_BUCKETS=0`` asks for
    exact shapes."""
    return bucket_segments_pow2(n) if os.environ.get("ANOVOS_SHAPE_BUCKETS", "1") != "0" else n


@jax.jit
def cat_valid_mask(codes: jax.Array, M: jax.Array) -> jax.Array:
    """mask & (code >= 0) — THE categorical null rule as one shared
    program for every stacking call site (stats mask prep, varclus,
    large-cat describe)."""
    return M & (codes >= 0)


# A class of at most this many lanes takes its group counts (with and without
# a row weight) from a contraction with the codes' one-hot on the MXU; a wider
# one keeps the scatter-add, whose time does not grow with the class (10-14 ms
# at 1,572,864 rows) while the contraction's multiply-adds do.
_DENSE_COUNT_LANES_MAX = 65536
# ... and this many for a LUT's gather, which contracts a plane a byte of the
# LUT's dtype where a count contracts once.
_DENSE_GATHER_LANES_MAX = 65536
# rows a step of a dense program's scan: bounds the one-hots whatever the
# table's length (two of 8,192 x 256 in bf16 are 8 MB; a count at 65,536 lanes
# takes 2.8 ms in steps of 4,096 or 8,192 rows and 6.4 in steps of 16,384 or
# more, the other programs the same either way)
_DENSE_CHUNK_ROWS = 1 << 13


def dense_chunks(rows: int, most: int) -> int:
    """Rows a step of a small class's scans: ``most`` where the padded
    length allows it."""
    chunk = math.gcd(rows, most)
    return rows if chunk < min(rows, 4096) else chunk  # an unbucketed odd length: one chunk


def _dense_class(p: int, lanes_max: int) -> bool:
    """Whether a padded class ``p`` is under the limit and splits into two
    levels (every class of ``_bucket_segments`` is a power of two)."""
    return p <= lanes_max and p & (p - 1) == 0


def _levels(p: int) -> Tuple[int, int]:
    """(hi, lo) lanes of a class's two one-hots, ``hi * lo == p``: a code is
    ``hi_code * lo + lo_code``.  As square as a power of two splits (64 x 64
    for 4,096 lanes, 256 x 256 for 65,536): the contraction's two sides then
    fill the MXU's 128 x 128 alike."""
    lo = 1 << (p.bit_length() // 2)
    return p // lo, lo


def on_one_device(a) -> bool:
    """False for a concrete array laid over several devices (a ``Table``'s
    column on a mesh).  The dense programs scan the rows in chunks, and a
    ``reshape(n, chunk)`` of a row-sharded array would gather the rows where
    the scatter-add and the index gather are partitioned over them (and end
    in one all-reduce / in none): such a column keeps those."""
    if isinstance(a, jax.core.Tracer) or not isinstance(a, jax.Array):
        return True
    return len(a.sharding.device_set) == 1


def _contracts(p: int, lanes_max: int, codes) -> bool:
    """The static ``dense`` of the three programs, from what a call can see:
    the padded class and the layout of its concrete codes."""
    return _dense_class(p, lanes_max) and on_one_device(codes)


def count_route(vocab_size: int, codes) -> Tuple[int, bool]:
    """(padded class, ``dense``) of a group count over ``codes``: the two
    statics of the count programs."""
    p = _bucket_segments(vocab_size)
    return p, _contracts(p, _DENSE_COUNT_LANES_MAX, codes)


def segment_routes(classes, kind: str, sharded: bool = False) -> dict:
    """What calls over these padded classes count on their stage row: for
    ``kind`` "counts" the group counts by contraction and by scatter-add, for
    "gathers" the gathers of a LUT of at most 32 bits an entry by contraction
    and by index.  ``sharded``: the columns are laid over a mesh, where every
    call keeps the second route (``on_one_device``)."""
    names, lanes_max = {"counts": (("dense_counts", "scatter_counts"), _DENSE_COUNT_LANES_MAX),
                        "gathers": (("dense_gathers", "index_gathers"), _DENSE_GATHER_LANES_MAX)}[kind]
    dense = 0 if sharded else sum(_dense_class(p, lanes_max) for p in classes)
    return {names[0]: dense, names[1]: len(classes) - dense}


def _dense_group_sum(codes: jax.Array, w: jax.Array, p: int) -> jax.Array:
    """(p,) f32 sums of ``w`` by code, ``w`` already zero on every row that
    does not count.  Per chunk of rows the one-hot of the code's hi part
    (``_levels``), carrying ``w``, is contracted over the rows with the lo
    part's one-hot, and a ``lax.scan`` over the chunks carries the (hi, lo) sums.
    A bf16 ``w`` (0 / 1) makes both operands exact in bf16 and the product one
    MXU pass with f32 accumulation; an f32 ``w`` goes in at ``HIGHEST``."""
    rows = codes.shape[0]
    chunk = dense_chunks(rows, _DENSE_CHUNK_ROWS)
    hi_n, lo_n = _levels(p)
    hi_lanes = jnp.arange(hi_n, dtype=codes.dtype)
    lo_lanes = jnp.arange(lo_n, dtype=codes.dtype)
    precision = None if w.dtype == jnp.bfloat16 else jax.lax.Precision.HIGHEST

    def one(c, w_c):
        # a code outside [0, p) has no hi lane, as the scatter-add drops it
        lhs = jnp.where((c // lo_n)[:, None] == hi_lanes, w_c[:, None], 0).astype(w.dtype)
        hot_lo = ((c % lo_n)[:, None] == lo_lanes).astype(w.dtype)
        return jnp.einsum("rh,rl->hl", lhs, hot_lo, precision=precision,
                          preferred_element_type=jnp.float32)

    n = rows // chunk
    if n == 1:
        sums = one(codes, w)
    else:
        sums, _ = jax.lax.scan(lambda acc, xs: (acc + one(*xs), None),
                               jnp.zeros((hi_n, lo_n), jnp.float32),
                               (codes.reshape(n, chunk), w.reshape(n, chunk)))
    return sums.reshape(p)


def bf16_parts(x: jax.Array) -> Tuple[jax.Array, jax.Array, jax.Array]:
    """An f32 array as three bfloat16 arrays that add up to it, to the bit:
    eight bits of the mantissa each, the largest part first.  What a
    contraction with an exact 0 / 1 operand needs of precision ``HIGHEST``,
    in three bf16 products for its six.  Each part is rounded by
    ``lax.reduce_precision``, not by a cast to bfloat16 and back: inside a
    fusion the chip's compiler keeps such a round trip in f32 (excess
    precision), the rest comes out 0 and the sums are sums of bfloat16
    values, 2e-3 off (PERF.md section 6, PR 49, call a49).  A value that is
    not finite, or above bfloat16's largest (3.39e38), has no such parts
    (inf - inf): its sums come out NaN."""
    def rounded(a):
        return jax.lax.reduce_precision(a, exponent_bits=8, mantissa_bits=7)

    hi = rounded(x)
    rest = x - hi
    mid = rounded(rest)
    return hi.astype(jnp.bfloat16), mid.astype(jnp.bfloat16), (rest - mid).astype(jnp.bfloat16)


def dense_block_sums(ids: jax.Array, blocks, planes_of, nseg: int) -> jax.Array:
    """(m, nseg) f32 sums by bucket of the m bfloat16 columns that
    ``planes_of(*chunk of each of blocks)`` makes of a chunk of rows, zero
    where a row does not count; ``ids`` (rows,) in [0, nseg), any other id
    counts nowhere; ``blocks``: arrays as long as the rows.  Per chunk the
    buckets' one-hot (chunk, nseg) is contracted with the planes over the
    rows: both sides exact in bf16, the products added in f32, one MXU
    pass, and nothing as long as the rows is built but the inputs.  A
    ``lax.scan`` over the chunks carries the sums with their rounding
    (Neumaier's compensation: a bucket of 10^6 values of one sign keeps the
    1e-7 of one chunk's sum, where a plain f32 carry over 1,500 chunks loses
    1e-6 and a scatter-add, one update at a time, 1e-2).  The rows are padded
    to whole chunks, so no one-hot is ever longer than a chunk."""
    rows = ids.shape[0]
    chunk = min(_DENSE_CHUNK_ROWS, rows)
    pad = -rows % chunk
    if pad:
        ids = jnp.concatenate([ids, jnp.full((pad,), -1, ids.dtype)])
        blocks = [jnp.concatenate([b, jnp.zeros((pad, *b.shape[1:]), b.dtype)]) for b in blocks]
    lanes = jnp.arange(nseg, dtype=ids.dtype)

    def one(ids_c, *blocks_c):
        hot = (ids_c[:, None] == lanes).astype(jnp.bfloat16)
        return jnp.einsum("rs,rm->ms", hot, planes_of(*blocks_c), preferred_element_type=jnp.float32)

    n = (rows + pad) // chunk
    if n == 1:
        return one(ids, *blocks)

    def step(carry, xs):
        total, lost = carry
        part = one(*xs)
        new = total + part
        # what the addition rounded away, kept beside the sum and added once at the end
        lost = lost + jnp.where(jnp.abs(total) >= jnp.abs(part), (total - new) + part, (part - new) + total)
        return (new, lost), None

    xs = (ids.reshape(n, chunk), *(b.reshape(n, chunk, *b.shape[1:]) for b in blocks))
    zero = jnp.zeros(jax.eval_shape(one, *(x[0] for x in xs)).shape, jnp.float32)
    (total, lost), _ = jax.lax.scan(step, (zero, zero), xs)
    return total + lost


@functools.partial(jax.jit, static_argnames=("vocab_size", "dense"))
def _code_counts_p(codes: jax.Array, M: jax.Array, vocab_size: int, dense: bool = False) -> jax.Array:
    valid = M & (codes >= 0)
    if dense:
        return _dense_group_sum(codes, valid.astype(jnp.bfloat16), vocab_size)
    safe = jnp.where(valid, codes, 0)
    return jax.ops.segment_sum(
        valid.astype(jnp.float32), safe, num_segments=vocab_size
    )


@timed("ops.code_counts")
def code_counts(codes: jax.Array, M: jax.Array, vocab_size: int) -> jax.Array:
    """Frequency of each dictionary code for ONE categorical column.

    codes: (rows,) int32 with -1 for null; M: (rows,) bool.
    Returns counts PADDED to the ``_bucket_segments`` size class
    ({16, 256, 4096, …} ≥ vocab_size) — trailing lanes are zero.  Callers
    slice ``[:vocab_size]`` after host materialization: an on-device slice
    here compiled one dynamic_slice program per vocab size, re-creating
    exactly the per-shape compile tail the segment-class bucketing removes
    (PERF.md cold-compile census).

    A class of at most ``_DENSE_COUNT_LANES_MAX`` lanes is counted by
    one-hot contraction (``_dense_group_sum``), a wider one, or a column laid
    over a mesh, by scatter-add.  Both give the exact integer count up to
    2^24 rows a code, where f32 stops counting by ones on either route."""
    p, dense = count_route(vocab_size, codes)
    return _code_counts_p(codes, M, p, dense=dense)


@functools.partial(jax.jit, static_argnames=("vocab_size", "dense"))
def _code_label_counts_p(
    codes: jax.Array, M: jax.Array, y: jax.Array, vocab_size: int, dense: bool = False
) -> jax.Array:
    valid = M & (codes >= 0)
    w = jnp.where(valid, y, 0.0).astype(jnp.float32)
    if dense:
        return _dense_group_sum(codes, w, vocab_size)
    safe = jnp.where(valid, codes, 0)
    return jax.ops.segment_sum(w, safe, num_segments=vocab_size)


@timed("ops.code_label_counts")
def code_label_counts(
    codes: jax.Array, M: jax.Array, y: jax.Array, vocab_size: int
) -> jax.Array:
    """Per-code sum of a row weight/label (event counts for IV, target
    encoding).  Returns counts PADDED to the ``_bucket_segments`` class
    (trailing lanes zero) — same host-slice contract and the same two routes
    as :func:`code_counts`.

    ``y`` carries values, so on the dense route it stays f32 at
    ``Precision.HIGHEST``.  For weights of 0 / 1 (every caller in the tree:
    an event vector, or ones) the sum is exact in any order up to 2^24; for
    other weights the dense route's is an f32 sum in another order than the
    scatter-add's, and one non-finite weight on a counted row reaches every
    lane of its chunk (0 x inf) where the scatter-add keeps it to its own."""
    p, dense = count_route(vocab_size, codes)
    return _code_label_counts_p(codes, M, y, p, dense=dense)


@functools.partial(jax.jit, static_argnames=("vocab_size", "dense"))
def _block_label_counts_p(
    codes: jax.Array, M: jax.Array, y: jax.Array, vocab_size: int, dense: bool = False
) -> jax.Array:
    """:func:`code_label_counts` of every column of a (rows, k) block of codes
    against one weight ``y``, in ONE program: (k, vocab_size) sums.  The
    statics are :func:`count_route`'s, of the block's widest column; a caller
    inside a program of its own (association_evaluator's group counts, under
    their scope) passes them through."""
    return jax.vmap(lambda c, m: _code_label_counts_p(c, m, y, vocab_size, dense),
                    in_axes=1)(codes, M)


def _dense_gather(lut: jax.Array, codes: jax.Array) -> jax.Array:
    """``lut[clip(codes)]`` without an index: the LUT's BYTES are looked up.
    Each byte of the LUT's dtype is a plane of 0..255, exact in bf16; per
    chunk of rows the one-hot of the code's hi part is contracted with the
    planes, laid (hi, byte, lo), the lo one-hot picks its lane, and the bytes
    go back together by shifts.  One term of every sum is not an exact zero,
    so any 8-, 16- or 32-bit LUT comes back bit for bit: -0.0, denormals, inf
    and NaN, every int32."""
    rows, p = codes.shape[0], lut.shape[0]
    chunk = dense_chunks(rows, _DENSE_CHUNK_ROWS)
    hi_n, lo_n = _levels(p)
    word = {1: jnp.uint8, 2: jnp.uint16, 4: jnp.uint32}[lut.dtype.itemsize]
    bits = lut.astype(word) if lut.dtype == jnp.bool_ else jax.lax.bitcast_convert_type(lut, word)
    nbytes = lut.dtype.itemsize
    planes = jnp.stack([(bits >> (8 * b)) & 255 for b in range(nbytes)], axis=0)  # (byte, p)
    planes = planes.reshape(nbytes, hi_n, lo_n).transpose(1, 0, 2).astype(jnp.bfloat16)
    hi_lanes = jnp.arange(hi_n, dtype=codes.dtype)
    lo_lanes = jnp.arange(lo_n, dtype=codes.dtype)

    def one(c):
        c = jnp.clip(c, 0, p - 1)
        hot_hi = ((c // lo_n)[:, None] == hi_lanes).astype(jnp.bfloat16)
        got = jnp.einsum("rh,hbl->rbl", hot_hi, planes, preferred_element_type=jnp.float32)
        hot_lo = (c % lo_n)[:, None] == lo_lanes
        got = jnp.where(hot_lo[:, None, :], got, 0.0).sum(axis=2).astype(jnp.uint32)  # (chunk, byte)
        out = got[:, 0]
        for b in range(1, nbytes):
            out = out | (got[:, b] << (8 * b))
        return out.astype(word)

    n = rows // chunk
    out = one(codes) if n == 1 else jax.lax.map(one, codes.reshape(n, chunk)).reshape(rows)
    return out != 0 if lut.dtype == jnp.bool_ else jax.lax.bitcast_convert_type(out, lut.dtype)


@functools.partial(jax.jit, static_argnames=("dense",))
def _lut_gather(lut: jax.Array, codes: jax.Array, dense: bool = False) -> jax.Array:
    if dense:
        return _dense_gather(lut, codes)
    return lut[jnp.clip(codes, 0, lut.shape[0] - 1)]


@timed("ops.vocab_lookup")
def vocab_lookup(lut_host, codes: jax.Array) -> jax.Array:
    """Per-code lookup through a small host-built table.

    The LUT is padded to its ``_bucket_segments`` class so every vocab size shares one
    compiled gather per row shape (eagerly indexing ``jnp.asarray(lut)[codes]``
    per column compiled ~70 distinct gather programs across an e2e run).
    Codes are clipped; callers keep their own null/validity masking.

    A class of at most ``_DENSE_GATHER_LANES_MAX`` lanes is looked up by
    one-hot contraction with the LUT's bytes (``_dense_gather``: the same
    bits as the index gather for every dtype of at most 32 bits), a wider
    one, a wider dtype, or codes laid over a mesh, by index."""
    import numpy as np

    lut_host = np.asarray(lut_host)
    p = _bucket_segments(len(lut_host))
    if p > len(lut_host):
        lut_host = np.concatenate([lut_host, np.zeros(p - len(lut_host), lut_host.dtype)])
    lut = jnp.asarray(lut_host)
    return _lut_gather(lut, codes, dense=lut.dtype.itemsize <= 4 and _contracts(p, _DENSE_GATHER_LANES_MAX, codes))


def mode_from_counts(counts: jax.Array) -> Tuple[jax.Array, jax.Array]:
    """(argmax code, count) from a (vocab,) count vector; ties → lowest code
    (Spark's groupBy().orderBy(desc).limit(1) is nondeterministic on ties;
    we pin the deterministic choice)."""
    return jnp.argmax(counts), counts.max()


@jax.jit
def row_signature(Xcodes: jax.Array, M: jax.Array) -> jax.Array:
    """64-bit-ish hash per row over all columns (two f32 lanes) for duplicate
    detection (quality_checker.py:49 dedup).  Null hashes as a distinct
    sentinel.  Collision-checked host-side at stage boundary."""
    k = Xcodes.shape[1]
    vals = jnp.where(M, Xcodes, -2).astype(jnp.uint32)
    h1 = jnp.zeros(Xcodes.shape[0], jnp.uint32)
    h2 = jnp.zeros(Xcodes.shape[0], jnp.uint32)
    for j in range(k):  # unrolled — k is static and small
        h1 = (h1 * jnp.uint32(1000003)) ^ (vals[:, j] + jnp.uint32(0x9E3779B9))
        h2 = (h2 * jnp.uint32(69069)) ^ (vals[:, j] * jnp.uint32(2654435761) + jnp.uint32(j + 1))
    return jnp.stack([h1, h2], axis=1)  # (rows, 2) uint32 — x64-free 64-bit key
