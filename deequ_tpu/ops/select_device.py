"""Batched multi-rank SELECTION for device quantiles: iterative histogram
range-narrowing instead of a full sort.

``ops/kll_device.chunk_summary_batched`` pins each KLL stratum boundary by
sorting the whole chunk (one vmapped XLA sort per pass). But the summary only
ever READS k+W rank positions out of the sorted array; a comparison sort
computes n*log(n) order information to answer k+W rank queries. CPU
engines answer the same queries with introselect in O(n); the accelerator
equivalent built here is a *batched multi-rank radix selection*:

  1. Map the f32 hi plane to its order-preserving u32 key (one bitcast +
     bit-twiddle). The key order equals the sort path's order: -inf <
     ... < -0.0 < +0.0 < ... < +inf < NaN, with every NaN (either sign)
     keyed 0xFFFFFFFF because jnp.sort follows numpy semantics and
     places all NaNs last. Invalid rows take the +inf key itself — the
     sort path pads them with literal +inf, so they join the same tie
     group and ranks resolve to identical values.
  2. Narrow every target rank simultaneously with THREE histogram
     passes over the 16+8+8-bit radix digits: per target the counts of
     the next digit among the elements under the target's prefix; each
     target then walks the cumulative counts of its row to pick the
     bucket holding its rank, narrowing its [lo, hi) key range by the
     digit width. After the third pass every stratum midpoint and
     quantile rank is pinned to the exact 32-bit key at that rank. No
     sorted structure of the DATA ever exists.
  3. Reconstruct the f64 item per target: the selected f32 hi value
     plus a deterministically-chosen lo-plane rider (tie rule below),
     and extract the < w exact-remainder elements by threshold +
     stable tie-split + compaction, all three by COUNTING over groups
     of 32 elements (:func:`_remainder_source`): one pass packs the
     0/1 planes "above / at the bottom key, below / at the top key"
     into a u32 word a group (a matmul against powers of two), the
     tie split is an INDEX threshold (the index of the tie numbered
     ``tie_rank``: a running total over the groups and one word), and
     slot s reads the (s + 1)-th set element of the remainder's words,
     found by inverting the groups' running totals with a bincount and
     a cumulative count, one gather a slot. No n-long running count,
     no search.

Two formulations compute step 2 and the rider, bit for bit alike; the
plan's histogram variant (ops/histogram_device.py) picks one:

- ``"onehot"`` (what ``resolve_hist_variant`` gives an accelerator):
  :func:`_multirank_onehot`. Every pass is a blocked MATMUL over the
  rows: an element's membership in each target's prefix is a broadcast
  compare, a (block, R) 0/1 plane, and the counts are its transpose
  times the one-hot of the digit on the MXU. No per-element gather or
  scatter anywhere.
- ``"scatter"`` / ``"pallas"`` (a CPU backend's default; the A/B hatch):
  :func:`_multirank_lut`. An element's target row comes from a dense
  prefix->row lookup table (scattered from the <= R active target
  prefixes), the counts from a bincount under that variant, the rider
  from one scatter-min.

Read on the TPU v5e (PERF.md section 6, PR 30; one column of one resident
chunk, n = 4,772,185, k = 256): the LUT formulation with one-hot
bincounts 492.7 ms — of a 86 s suite over 50 columns x 3 chunks, 69.8%
was the remainder's n-element f64 scatter compaction, 17.5% the two LUT
gathers, 7.2% the scatter-min, 3.8% the three histogram passes; the sort
summary 81.7 ms (x6 faster than the selection it was to replace); the
matmul formulation with the compaction as a GATHER (W binary searches
over the running count of remainder elements) 41.4 ms (27.3 since PR
31, below; 16.6 since PR 35, which took the searches out: further
below). On the TPU a gather or scatter walks its n elements one
after another (32-43 ms a 4.77M-element pass, 364 ms in f64); a matmul
pass costs 5-12 ms.

Pass 1 (PERF.md section 6, PR 31): inside the 50-column step of the
benchmark's cell ``quantiles12m50.qscan`` it took 19.7 ms a column
summary where passes 2 and 3, the same 65,536 MACs a row, took 4.67
and 4.83. XLA:TPU picks the emitter of a matmul fusion by the
membership plane's row count and the size of its windows by whether it
sees an iota behind the operand: 256 rows against an iota on BOTH sides
(what pass 1 was) got a transposing emitter over 512 small windows a
block. Now the 256 values of the top digit reach the compare as the
prefixes of passes 2-3 do, an array of the run behind
``lax.optimization_barrier``, padded to ``_PASS1_ROWS`` = 264 rows (off
a multiple of 128; the padding matches no row): the compiler builds
pass 1 as it builds pass 2, to the cycle of its own estimate, and on the
chip it costs 4.67 ms. The histogram is the same, bit for bit (0/1
products, f32 accumulation per block of at most 2^16 rows, integer
fold), and so is every summary.

The extraction (PERF.md section 6, PR 35): inside that step the
remainder's compaction took 8.79 ms a column summary, 31% of the
device's time: W = 32,768 binary searches over the n-long running count
(23 dependent gathers each, 7.1 ns a gather: 5.38 ms), a ``(2, n)``
running count that numbered every tie at the two bounding keys (1.92)
and the running count itself (0.96). A gather costs its 7 ns whatever
the table, so a shorter search saves little; the gain is in not
searching: :func:`_remainder_source` finds positions in a 0/1 plane by
COUNTING over groups of 32 elements (a matmul packs a plane into words,
a bincount inverts the groups' running totals), with one word gather a
slot. 0.95 ms a column summary on the chip, of it 0.70 the three
W-element gathers that are left (the word, ``x[source]``,
``lo[source]``); the alone-figure above fell from 25.9 to 16.6 ms.
``source`` is the same array, slot for slot, and so is every summary.

Passes touch each element O(1) times in native u32/i32 ops — no f64
emulation, no u64: XLA:TPU rejects f64->u64 bitcasts, ops/hll.py. The
output contract is IDENTICAL to ``kll_device.chunk_summary``: the same
{items, weights, count, min, max}
summary with the same strata/remainder layout, so ``fold_summaries`` and
the whole KLL merge algebra (host sketches, persisted states, incremental
merges) are untouched.

Determinism and parity with the sort path (docs/numerics.md, "selection
kernel determinism"):

- the selected hi-plane VALUE at every rank is exactly the sort path's
  (both resolve the same total order on f32);
- the lo-plane rider for a stratum midpoint is the lo of the
  minimum-index element among the hi-plane ties. Exact duplicates (equal
  f64 values) carry equal lo, so the item is bit-identical to the sort
  path's; only *distinct* f64 values colliding on the same f32 hi (< 1
  ulp(f32) apart, ~6e-8 relative) can differ — inside the tie-order
  ambiguity the sort path already documents for itself;
- the remainder multiset reproduces the stable-argsort tie split
  exactly: ties at the threshold key enter the remainder in original
  index order (an index threshold: the ties at or after the one
  numbered ``tie_rank``), so remainder contents match the sort path
  element for element (the summary is order-insensitive;
  ``fold_summaries`` sorts per level).

jnp-only: no numpy mirror here — the host reference for tests is the sort
path itself.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np

from deequ_tpu.ops.histogram_device import (
    _plane_dtype,
    current_hist_variant,
    map_under_vmap,
)
from deequ_tpu.ops.kll_device import strata_capacity, strata_weight

# radix digit plan: 16 bits in the first pass (every target still shares
# the single full-range interval), then 8+8 under each target's own
# prefix. Three passes pin all 32 key bits.
_PASS1_BITS = 16
_PASS_BITS = 8
_B = 1 << _PASS_BITS

# largest sketch size the selection kernel accepts: the pass-2/3
# histograms (and the LUT formulation's tables) are O(k * 256) i32 per
# column (~17MB at this cap) — buffers chunk bisection cannot shrink,
# unlike the sort path whose footprint is O(n) — and the matmul
# formulation's work grows with k (R * 256 MACs a row a pass). Ops above
# the cap keep the sort path (the analyzers attach no selection variant).
# Default sketches sit far below (KLLSketch k=2048, ApproxQuantile's
# default relative_error=0.01 gives k=256); only extreme precision
# requests (relative_error below ~1.4e-4, i.e. k = 2.3/eps > 16384)
# exceed it and simply stay on the sort kernel.
MAX_SELECT_SKETCH_SIZE = 1 << 14


def monotone_u32(x, xp):
    """Order-preserving f32 -> u32 key: sign bit flipped for positives,
    all bits flipped for negatives — u32 `<` then agrees with the float
    order the sort path resolves (including -0.0 < +0.0), with ONE
    deliberate adjustment: every NaN (either sign bit) maps to
    0xFFFFFFFF, above +inf. ``jnp.sort``/``argsort`` follow numpy
    semantics and place ALL NaNs last regardless of sign; the plain
    sign-flip bijection would put -NaN *below* -inf and shift every rank
    between the two kernels (caught in review by a valid negative-NaN
    column)."""
    import jax

    bits = jax.lax.bitcast_convert_type(x, xp.uint32)
    neg = (bits >> xp.uint32(31)).astype(xp.bool_)
    key = xp.where(neg, ~bits, bits | xp.uint32(0x80000000))
    return xp.where(xp.isnan(x), xp.uint32(0xFFFFFFFF), key)


def inverse_monotone_u32(u, xp):
    """Inverse of ``monotone_u32``."""
    import jax

    u = u.astype(xp.uint32)
    pos = (u >> xp.uint32(31)).astype(xp.bool_)
    bits = xp.where(pos, u ^ xp.uint32(0x80000000), ~u)
    return jax.lax.bitcast_convert_type(bits, xp.float32)


def _segment_count(seg, num_segments: int, xp):
    """Histogram of i32 segment ids for the LUT formulation's passes and
    for the extraction's inverting count (:func:`_first_set`), under the
    routed kernel tier (ops/histogram_device.py): the scatter variant
    traces ``at[].add`` exactly as before round 14 (``at[].add`` rather
    than segment_sum — same scatter, but without materializing the
    all-ones operand, measured ~2x faster on CPU); the pallas variant
    replaces the scatter with a Mosaic grid kernel; under the one-hot
    variant the passes are matmuls of their own
    (``_select_u32_multirank``) and only the extraction gets here. The
    ambient variant is bound by the planner around the whole selection
    update (ops/scan_plan._bind_hist_variant), so every count of one
    summary traces the SAME kernel shape — the plan-hist-scatter lint
    contract."""
    from deequ_tpu.ops.histogram_device import bincount

    return bincount(seg, num_segments, xp, dtype=xp.int32)


def _bucket_of_rank(tcum, rank_rem, xp):
    """Per target: the first bucket whose cumulative count exceeds the
    target's rank-within-interval, and the count below that bucket.
    ``tcum`` is (R, B) cumulative counts; B is small, so a compare-reduce
    beats a batched binary search."""
    bucket = xp.sum((tcum <= rank_rem[:, None]).astype(xp.int32), axis=1)
    bucket = xp.minimum(bucket, tcum.shape[1] - 1)
    below = xp.take_along_axis(
        tcum, xp.maximum(bucket - 1, 0)[:, None], axis=1
    )[:, 0]
    below = xp.where(bucket > 0, below, 0).astype(xp.int32)
    return bucket, below


def _select_u32_multirank(u, ranks, xp):
    """The multi-rank selection under the AMBIENT histogram variant
    (ops/histogram_device.py): ``"onehot"`` is the blocked matmul
    formulation (:func:`_multirank_onehot`, what an accelerator plan
    resolves to), anything else the LUT formulation
    (:func:`_multirank_lut`) with its bincounts under that variant. Both
    return the same ``(keys, tie_rank, min_tie_index)``, bit for bit."""
    if current_hist_variant() == "onehot":
        return _multirank_onehot(u, ranks)
    return _multirank_lut(u, ranks, xp)


#: rows a block of the one-hot passes holds at most (a block's f32 counts
#: stay far below 2^24, so they are exact), and the elements its two
#: planes may hold together: the (block, R) membership plane grows with
#: the sketch, so the block shrinks (2^16 rows up to R = 768)
_ONEHOT_BLOCK_ROWS = 1 << 16
_ONEHOT_PLANE_ELEMENTS = 1 << 26


#: rows of pass 1's membership plane: the 256 values of the top digit and
#: one sublane tile of padding that matches no row. XLA:TPU picks a matmul
#: fusion's emitter by this count (at a multiple of 128 a transposing one,
#: twice as slow here) and its windows by whether it sees an iota behind
#: the operand (small ones, twice as slow again): module docstring,
#: tests/test_chip_compile.py
_PASS1_ROWS = _B + 8


def _row_blocks(a, block: int):
    """``a`` as ``(blocks, block)`` rows, the tail zero-padded."""
    blocks = -(-a.shape[0] // block)
    pad = blocks * block - a.shape[0]
    if pad:
        a = jnp.concatenate([a, jnp.zeros((pad,), a.dtype)])
    return a.reshape(blocks, block)


def _multirank_onehot_body(u, ranks):
    """:func:`_multirank_lut`'s result with no per-element gather or
    scatter: every pass is a blocked matmul over the rows.

    A histogram pass needs, per target, the counts of the next radix
    digit among the elements that share the target's prefix. The LUT
    formulation finds an element's target row by a gather through a
    dense prefix table and counts by a scatter-add; on the TPU each is a
    serial walk over the n elements. Here an element's membership in
    EVERY target's prefix is one broadcast compare, a ``(block, R)`` 0/1
    plane, and the counts are that plane's transpose times the one-hot
    of the digit, ``(R, 256)`` on the MXU with f32 accumulation (exact:
    products are 0/1, a block holds 2^16 rows). Targets that share a
    prefix get equal rows, so nothing is deduplicated and no table is
    built. The tie rider (the smallest index among the elements equal to
    each selected key) is a fourth pass of the same shape: a masked min
    over the block's rows.
    """
    R = ranks.shape[0]
    n = u.shape[0]
    plane = _plane_dtype(jnp)
    fits = _ONEHOT_PLANE_ELEMENTS // (R + _B)
    block = max(_B, min(
        _ONEHOT_BLOCK_ROWS,
        1 << (fits.bit_length() - 1),  # the power of two at or under it
        1 << (max(n, 1) - 1).bit_length(),
    ))
    rows_u = _row_blocks(u, block)
    # each block with the index of its first row: a row's index says
    # whether it is padding (>= n: in no target's prefix, tied with no key)
    blocks = (rows_u, jnp.arange(rows_u.shape[0], dtype=jnp.int32) * block)
    in_block = jnp.arange(block, dtype=jnp.int32)
    digits = jnp.arange(_B, dtype=jnp.int32)[None, :]
    rank_rem = ranks.astype(jnp.int32)

    def histogram(rows: int, member_and_digit):
        """``(rows, 256)`` counts: per block ``member^T @ one_hot(digit)``."""

        def add_block(counts, ub_start):
            ub, start = ub_start
            member, digit = member_and_digit(ub, start + in_block < n)
            onehot = (digit[:, None] == digits).astype(plane)
            block_counts = jnp.matmul(
                member.astype(plane).T, onehot,
                preferred_element_type=jnp.float32,
            )
            return counts + block_counts.astype(jnp.int32), None

        counts, _ = jax.lax.scan(
            add_block, jnp.zeros((rows, _B), jnp.int32), blocks
        )
        return counts

    # -- pass 1: the leading 16 bits as two 8-bit digits, one interval ---
    # the top digit's values reach the compare as the prefixes of passes
    # 2-3 do, an array of the run and not an iota the compiler sees, with
    # padding that matches no row (``_PASS1_ROWS``); the counts are the same
    def leading(ub, live):
        d1 = (ub >> jnp.uint32(_PASS1_BITS)).astype(jnp.int32)
        return ((d1 >> _PASS_BITS)[:, None] == heads[None, :]) & live[:, None], (
            d1 & (_B - 1)
        )

    with jax.named_scope("deequ.select.pass1"):
        heads = jnp.arange(_PASS1_ROWS, dtype=jnp.int32)
        heads = jax.lax.optimization_barrier(jnp.where(heads < _B, heads, -1))
        hist1 = histogram(_PASS1_ROWS, leading)[:_B].reshape(-1)
    cum1 = jnp.cumsum(hist1)
    pfx = jnp.searchsorted(cum1, rank_rem, side="right").astype(jnp.int32)
    below = jnp.where(pfx > 0, cum1[jnp.maximum(pfx - 1, 0)], 0)
    rank_rem = rank_rem - below.astype(jnp.int32)

    # -- pass 2: the elements under each target's 16-bit prefix ---------
    def under_prefix(ub, live):
        d1 = (ub >> jnp.uint32(_PASS1_BITS)).astype(jnp.int32)
        d2 = ((ub >> jnp.uint32(_PASS_BITS)) & jnp.uint32(_B - 1))
        return (d1[:, None] == pfx[None, :]) & live[:, None], (
            d2.astype(jnp.int32)
        )

    with jax.named_scope("deequ.select.pass2"):
        hist2 = histogram(R, under_prefix)
    bucket2, below2 = _bucket_of_rank(
        jnp.cumsum(hist2, axis=1), rank_rem, jnp
    )
    rank_rem = rank_rem - below2
    pfx24 = pfx * _B + bucket2

    # -- pass 3: the elements under each target's 24-bit prefix ---------
    def under_prefix24(ub, live):
        d = (ub >> jnp.uint32(_PASS_BITS)).astype(jnp.int32)
        return (d[:, None] == pfx24[None, :]) & live[:, None], (
            (ub & jnp.uint32(_B - 1)).astype(jnp.int32)
        )

    with jax.named_scope("deequ.select.pass3"):
        hist3 = histogram(R, under_prefix24)
    bucket3, below3 = _bucket_of_rank(
        jnp.cumsum(hist3, axis=1), rank_rem, jnp
    )
    rank_rem = rank_rem - below3
    keys = (pfx24.astype(jnp.uint32) << jnp.uint32(_PASS_BITS)) | (
        bucket3.astype(jnp.uint32)
    )

    # -- tie rider: the smallest index among each key's elements --------
    def min_index(best, ub_start):
        ub, start = ub_start
        index = jnp.minimum(start + in_block, n)  # padding: past the end
        tied = ub[:, None] == keys[None, :]
        return jnp.minimum(
            best, jnp.where(tied, index[:, None], n).min(axis=0)
        ), None

    with jax.named_scope("deequ.select.rider"):
        first, _ = jax.lax.scan(
            min_index, jnp.full((R,), n, jnp.int32), blocks
        )
    return keys, rank_rem, jnp.minimum(first, n - 1)


# one program whatever vmaps it: the batched one-hot matmul is the one
# XLA:TPU miscompiles (histogram_device.map_under_vmap)
_multirank_onehot = map_under_vmap(_multirank_onehot_body)


def _multirank_lut(u, ranks, xp):
    """Resolve ``ranks`` (R target rank positions, i32, each in [0, n))
    against the ascending order of ``u`` ((n,) u32 keys): returns

      (keys, tie_rank, min_tie_index)

    where ``keys[t]`` is the u32 key at sorted position ``ranks[t]``,
    ``tie_rank[t] = ranks[t] - #{u < keys[t]}`` is the target's 0-based
    position INSIDE its tie group (what a stable sort resolves by
    original index), and ``min_tie_index[t]`` is the smallest element
    index with ``u == keys[t]`` (clipped to n-1; only meaningful when
    the key is actually present, which it always is for ranks < m).
    Pure histogram range-narrowing: 3 fused bincount passes + 1
    scatter-min, never a sorted array of the data.
    """
    R = ranks.shape[0]
    n = u.shape[0]
    rank_rem = ranks.astype(xp.int32)
    idx = xp.arange(n, dtype=xp.int32)

    # -- pass 1: 16-bit leading digit, one shared full-range interval ----
    d1 = (u >> xp.uint32(_PASS1_BITS)).astype(xp.int32)
    with jax.named_scope("deequ.select.pass1"):
        hist1 = _segment_count(d1, 1 << _PASS1_BITS, xp)
    cum1 = xp.cumsum(hist1)
    pfx = xp.searchsorted(cum1, rank_rem, side="right").astype(xp.int32)
    below = xp.where(pfx > 0, cum1[xp.maximum(pfx - 1, 0)], 0)
    rank_rem = rank_rem - below.astype(xp.int32)

    # -- pass 2: dense 2^16 prefix->row LUT, 8-bit digit ----------------
    # duplicate target prefixes share the minimum target index as their
    # row (scatter-min), so shared intervals share one histogram row; a
    # LUT slot below R exists ONLY for active prefixes, so the row test
    # doubles as the membership test
    lut2 = (
        xp.full((1 << _PASS1_BITS,), R, dtype=xp.int32)
        .at[pfx]
        .min(xp.arange(R, dtype=xp.int32))
    )
    row2 = lut2[d1]
    d2 = ((u >> xp.uint32(_PASS_BITS)) & xp.uint32(_B - 1)).astype(xp.int32)
    seg2 = xp.where(row2 < R, row2 * _B + d2, R * _B)
    with jax.named_scope("deequ.select.pass2"):
        hist2 = _segment_count(seg2, R * _B + 1, xp)[: R * _B].reshape(R, _B)
    tcum2 = xp.cumsum(hist2, axis=1)[lut2[pfx]]
    bucket2, below2 = _bucket_of_rank(tcum2, rank_rem, xp)
    rank_rem = rank_rem - below2

    # -- pass 3: interval id = pass-2 cell (row2, digit2); the dense LUT
    # over the R*B cell space maps it to <= R rows ----------------------
    id3_t = lut2[pfx] * _B + bucket2
    lut3 = (
        xp.full((R * _B + 1,), R, dtype=xp.int32)
        .at[id3_t]
        .min(xp.arange(R, dtype=xp.int32))
    )
    row3 = lut3[xp.minimum(seg2, R * _B)]
    d3 = (u & xp.uint32(_B - 1)).astype(xp.int32)
    seg3 = xp.where(row3 < R, row3 * _B + d3, R * _B)
    with jax.named_scope("deequ.select.pass3"):
        hist3 = _segment_count(seg3, R * _B + 1, xp)[: R * _B].reshape(R, _B)
    tcum3 = xp.cumsum(hist3, axis=1)[lut3[id3_t]]
    bucket3, below3 = _bucket_of_rank(tcum3, rank_rem, xp)
    rank_rem = rank_rem - below3

    keys = (
        (pfx.astype(xp.uint32) << xp.uint32(2 * _PASS_BITS))
        | (bucket2.astype(xp.uint32) << xp.uint32(_PASS_BITS))
        | bucket3.astype(xp.uint32)
    )

    # tie rider source: after pass 3 a (row3, digit3) cell holds exactly
    # one distinct key, so the pass-3 segment ids double as tie-group ids
    # — one scatter-min finds each target's minimum-index tie element
    with jax.named_scope("deequ.select.rider"):
        min_cell = (
            xp.full((R * _B + 1,), n, dtype=xp.int32).at[seg3].min(idx)
        )
        min_tie_index = xp.minimum(
            min_cell[lut3[id3_t] * _B + bucket3], n - 1
        )
    return keys, rank_rem, min_tie_index


#: elements in a group of the remainder's extraction: a group's 0/1 plane
#: packs into ONE u32 word, lane l in bit l; and the elements in a row of
#: the matmul that packs them (the TPU's lane count: an n-long plane is
#: rows of 128 lanes where it lies), ``_QUARTERS`` groups a row
_GROUP = 32
_PACK_ROW = 128
_QUARTERS = _PACK_ROW // _GROUP


def _pack_weights():
    """``(_PACK_ROW, 2 * _QUARTERS)``: lane l of a row adds ``2^(l % 16)``
    to the low (columns 0-3) or the high (4-7) half of its group's word.
    Sixteen distinct powers of two a column: exact in bf16 and in the f32
    that accumulates them."""
    lanes = np.arange(_PACK_ROW)
    weights = np.zeros((_PACK_ROW, 2 * _QUARTERS), np.float32)
    weights[lanes, lanes // _GROUP + _QUARTERS * (lanes % _GROUP // 16)] = (
        2.0 ** (lanes % 16)
    )
    return weights


def _group_words(plane):
    """An n-long 0/1 plane as the u32 words of its groups of ``_GROUP``
    elements, lane l of a group in bit l: ``(_QUARTERS, rows)``, group
    ``_QUARTERS * r + j`` (elements ``128 r + 32 j`` and up) at ``[j, r]``.
    A matmul over the rows of 128 lanes the plane lies in, and its words
    the way the MXU hands them out, rows along the lanes: a reduction over
    32-element groups re-lays the whole plane out on the TPU, and so does
    a flat list of the words (0.37-0.6 ms a 4.77M-element plane by the
    compiler's own estimate, where this is 8 us)."""
    dtype = _plane_dtype(jnp)
    halves = jnp.matmul(
        _row_blocks(plane, _PACK_ROW).astype(dtype),
        jnp.asarray(_pack_weights(), dtype),
        preferred_element_type=jnp.float32,
    ).astype(jnp.uint32).T
    return halves[:_QUARTERS] | (halves[_QUARTERS:] << jnp.uint32(16))


def _bounds_words_body(u, bounds):
    """ONE pass over the keys: the four 0/1 planes the remainder is made
    of (above / at the bottom key, below / at the top key), in words."""
    v_b, v_t = bounds[0], bounds[1]
    return jnp.stack([
        _group_words(plane)
        for plane in (u > v_b, u == v_b, u < v_t, u == v_t)
    ])


# as the passes: the batched matmul is the one XLA:TPU miscompiles
_bounds_words = map_under_vmap(_bounds_words_body)


def _first_index(words):
    """The index of the first element of each group of ``words``."""
    quarter = jnp.arange(_QUARTERS, dtype=jnp.int32)[:, None] * _GROUP
    row = jnp.arange(words.shape[-1], dtype=jnp.int32)[None, :] * _PACK_ROW
    return quarter + row


def _running_totals(words):
    """Per group the number of set elements up to and including it, in
    index order: the running total of the ROWS' totals (n / 128 long) plus
    the quarters before it in its row."""
    totals = jax.lax.population_count(words).astype(jnp.int32)
    within = jnp.cumsum(totals, axis=-2)
    rows = within[..., -1, :]
    return within + (jnp.cumsum(rows, axis=-1) - rows)[..., None, :]


def _word_of(words, group):
    """``words`` at the groups numbered ``group`` in index order (one
    plane; clipped to the last group)."""
    group = jnp.minimum(group, words.size - 1)
    return words.reshape(-1)[
        group % _QUARTERS * words.shape[-1] + group // _QUARTERS
    ]


def _low_lanes(count):
    """The word whose lanes ``[0, count)`` are set (none at ``count <= 0``,
    all from ``_GROUP`` on)."""
    low = (
        jnp.uint32(1) << jnp.clip(count, 0, _GROUP - 1).astype(jnp.uint32)
    ) - jnp.uint32(1)
    return jnp.where(count >= _GROUP, jnp.uint32(0xFFFFFFFF), low)


def _nth_lane(word, q):
    """The lane of the ``q``-th (1-based) set bit of each word: how many
    lanes have fewer than ``q`` set bits up to and including themselves
    (``_GROUP`` where the word holds fewer than ``q``). Lane arithmetic on
    a ``(..., _GROUP)`` compare, no gather."""
    upto = _low_lanes(jnp.arange(1, _GROUP + 1, dtype=jnp.int32))
    counts = jax.lax.population_count(word[..., None] & upto)
    return jnp.sum(
        counts.astype(jnp.int32) < q[..., None], axis=-1, dtype=jnp.int32
    )


def _nth_set(words, q, n: int):
    """The index of the ``q``-th (1-based, ``q >= 1``) set element of one
    0/1 plane in words (:func:`_group_words`; ``q`` a scalar); ``n`` where
    the plane holds fewer than ``q``. By counting: the groups whose running
    total is below ``q`` are the groups wholly before it, and the largest
    such total is the number of elements before its group (two
    compare-reduces over the groups, 1/32 of the plane's length); the lane
    is arithmetic on the group's word."""
    running = _running_totals(words)
    wholly_before = running < q
    group = jnp.sum(wholly_before, dtype=jnp.int32)
    before = jnp.max(jnp.where(wholly_before, running, 0))
    index = group * _GROUP + _nth_lane(_word_of(words, group), q - before)
    return jnp.where(group < words.size, index, n)


def _first_set(words, count: int, n: int, xp):
    """The indices of the first ``count`` set elements of one 0/1 plane in
    words, ascending; ``n`` in the slots past the plane's total.
    :func:`_nth_set` at ``q = 1..count`` without a search: the groups'
    running totals are INVERTED by counting. Slot s lies in the group
    that follows every group whose running total is ``<= s``: their number
    is the cumulative count of a bincount of the running totals (under
    the ambient histogram variant, as the passes'), and the elements
    before that group are the largest running total ``<= s``, a cumulative
    max over the slots of the totals that occur. One gather a slot (the
    group's word); the lane is arithmetic."""
    running = _running_totals(words).reshape(-1)
    occurs = _segment_count(jnp.minimum(running, count), count + 1, xp)
    group = jnp.cumsum(occurs)[:count]
    slots = jnp.arange(count + 1, dtype=jnp.int32)
    before = jax.lax.cummax(jnp.where(occurs > 0, slots, 0))[:count]
    index = group * _GROUP + _nth_lane(
        _word_of(words, group), slots[1:] - before
    )
    return jnp.where(group < words.size, index, n)


def _remainder_source(u, bounds, tie_ranks, has_rem, W: int, xp):
    """``source[s]``, s in 0..W-1: the index of the (s + 1)-th element of
    the exact remainder in index order, ``n - 1`` in the slots past it
    (their weight is 0). The remainder is what lies between the two
    bounding keys ``bounds = (v_b, v_t)``, ties on either split by index as
    a stable argsort splits them: at ``v_b`` the ties numbered
    ``tie_ranks[0]`` and up join, at ``v_t`` those up to ``tie_ranks[1]``.

    By COUNTING over groups of ``_GROUP`` elements, never by an n-long
    running count and a search a slot (on the TPU a gather walks its
    elements one after another, and so did the 23 steps of each of W
    binary searches): ONE pass over the keys packs the four 0/1 planes
    the remainder is made of into a word a group; the tie split is an
    INDEX threshold (:func:`_nth_set`, once a bound), applied to the words;
    the slots come from the remainder's words by :func:`_first_set`."""
    n = u.shape[0]
    above, tie_b, below, tie_t = _bounds_words(u, bounds)
    # everything from here on is a group long: the index of each group's
    # first element, and the lanes that hold an element (the padding of
    # the last row is in no plane)
    first = _first_index(above)
    live = _low_lanes(n - first)
    # a tie numbered past the last one reads n: at the bottom no tie
    # joins, at the top every tie does
    i_b = _nth_set(tie_b & live, tie_ranks[0] + 1, n)
    i_t = _nth_set(tie_t & live, tie_ranks[1] + 1, n)
    rem = (
        (above | (tie_b & ~_low_lanes(i_b - first)))
        & (below | (tie_t & _low_lanes(i_t - first + 1)))
        & live
    )
    return xp.minimum(
        _first_set(xp.where(has_rem, rem, xp.uint32(0)), W, n, xp), n - 1
    )


def chunk_summary_select(x, valid, sketch_size: int, local_n: int, xp, lo):
    """Inside-jit: one chunk/shard -> the SAME fixed-shape weighted
    summary as ``kll_device.chunk_summary``, computed by multi-rank
    histogram selection instead of a device sort.

    ``lo`` is REQUIRED (the two-float pair planes are the selection key
    domain); wide-f64 columns stay on the sort path — the planner
    (ops/scan_plan.py) only routes pair/i32/hi-only layouts here.
    Returns {items (k+W,), weights (k+W,), count, min, max} with padding
    slots at weight 0, foldable by ``fold_summaries`` interchangeably
    with the sort path's summary.
    """
    from deequ_tpu.ops.df32 import masked_extremum

    k = sketch_size
    W = strata_capacity(local_n, k)

    # invalid rows take the +inf KEY — the sort path pads them with
    # literal +inf (`where(valid, x, inf)`), so they must join the same
    # tie group valid +inf values occupy, not a separate sentinel: with
    # valid NaNs present (numpy sort order puts NaNs after the padding)
    # ranks in [r0, m) can legitimately resolve to padding +inf, and the
    # selection must reproduce exactly that
    with jax.named_scope("deequ.select.keys"):
        u = xp.where(valid, monotone_u32(x, xp), monotone_u32(
            xp.asarray(np.float32(np.inf)), xp
        ))
        lo_plane = xp.where(valid, lo, xp.asarray(np.float32(0.0)))

    m = valid.sum()
    w, n_strata = strata_weight(m, k, xp)
    r0 = (n_strata * w).astype(xp.int32)  # first remainder rank

    # target ranks: k stratum midpoints + the remainder's [r0, m-1] rank
    # bounds, every one clipped into [0, m) so padded targets resolve
    # harmlessly (their weight is zeroed below, exactly like the sort
    # path's gather clip)
    sidx = xp.arange(k, dtype=xp.int32) * w.astype(xp.int32) + (
        w.astype(xp.int32) // 2
    )
    hi_rank = xp.maximum(m.astype(xp.int32) - 1, 0)
    targets = xp.concatenate(
        [
            xp.clip(sidx, 0, hi_rank),
            xp.clip(r0, 0, hi_rank)[None],
            hi_rank[None],
        ]
    )

    keys, tie_rank, tie_src = _select_u32_multirank(u, targets, xp)
    sel64 = inverse_monotone_u32(keys, xp).astype(xp.float64) + lo_plane[
        tie_src
    ].astype(xp.float64)

    s_on = xp.arange(k) < n_strata
    items_s = sel64[:k]
    weights_s = xp.where(s_on, w, 0)

    # exact remainder: the elements a stable argsort places at ranks
    # [r0, m) — bounded BELOW by the key at rank r0 and ABOVE by the key
    # at rank m-1, ties on either boundary split by original index order.
    # Both bounds are needed: rows the sort path pads with +inf can sit
    # at ranks >= m inside the same +inf tie group the remainder's top
    # ranks occupy, so "everything above the threshold" would overrun.
    with jax.named_scope("deequ.select.extract"):
        has_rem = r0 < m.astype(xp.int32)
        source = _remainder_source(
            u, keys[k:k + 2], tie_rank[k:k + 2], has_rem, W, xp
        )
        # item values come from the PADDED plane (invalid rows read as
        # +inf, lo zeroed) — the exact array the sort path gathers from
        items_r = xp.where(
            valid, x, xp.asarray(np.float32(np.inf))
        )[source].astype(xp.float64) + lo_plane[source].astype(xp.float64)
        n_rem = xp.where(has_rem, m.astype(xp.int32) - r0, 0)
        weights_r = xp.where(xp.arange(W, dtype=xp.int32) < n_rem, 1, 0)

    items = xp.concatenate([items_s, items_r])
    weights = xp.concatenate([weights_s, weights_r])
    items = xp.where(weights > 0, items, 0.0)

    mn = masked_extremum(x, lo, valid, xp, "min")
    mx = masked_extremum(x, lo, valid, xp, "max")
    return {
        "items": items,
        "weights": weights.astype(xp.float64),
        "count": m,
        "min": mn,
        "max": mx,
    }


def chunk_summary_select_batched(X, M, sketch_size: int, local_n: int, xp, lo):
    """K columns at once: (K, n) values + (K, n) validity + (K, n) lo
    planes -> summaries with a leading K axis. The members run one after
    another (``lax.map``): a member's passes are whole-device programs
    already, and its temporaries (the key plane, the 0/1 planes of the
    extraction, the blocks' one-hot planes) are n-sized — batched over K = 50 members
    they stood beside a resident table as tens of GB."""
    return jax.lax.map(
        lambda member: chunk_summary_select(
            member[0], member[1], sketch_size, local_n, xp, lo=member[2]
        ),
        (X, M, lo),
    )
