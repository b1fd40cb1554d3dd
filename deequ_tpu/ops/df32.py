"""Two-float (double-float) f32 numerics for the fused scan.

TPU v5e has no native f64 units: XLA emulates every f64 op in software at
roughly 1/10th of native f32 throughput (measured on this hardware: the
f64 fused profile scan spends ~30ms of device compute where the f32
equivalent spends ~2ms). The reference runs on JVM doubles
(analyzers/StandardDeviation.scala:37-44 and friends assume f64 states), so
the metric VALUES must keep ~f64 accuracy — the classic resolution is
double-float arithmetic: represent each f64 value x as a pair of f32s

    hi = f32(x),   lo = f32(x - hi)

which carries ~48 mantissa bits losslessly for the transfer (same 8
bytes/row as f64), lets every O(n) device operation run on native f32/i32
vector units, and confines f64 to O(1) scalars and O(n/2^levels) reduction
tails. Error-free transformations (Knuth TwoSum, Dekker TwoProd) keep the
accumulated reductions accurate to ~1e-13 relative — validated against the
f64 goldens (tests/test_analyzers_golden.py asserts rel=1e-12).

The same pair (bitcast to u32s) is ALSO the HLL hash key the engine already
used (ops/hll.py:_f64_key_u64 splits f64 exactly this way because
XLA:TPU rejects f64->u64 bitcasts) — so sketches stay bit-identical.

Every helper takes ``lo=None`` to mean "data is plain f64" (the escape
hatch for the wide plane: |x| > f32_max, huge integers and
predicate-compared columns) and falls back to the straight f64 reduction.
"""

from __future__ import annotations

import numpy as np

F32_MAX = float(np.finfo(np.float32).max)

# pair-path magnitude ceiling: 2^59 (~5.8e17). The pair REPRESENTATION
# is fine up to f32_max (~2^128), but the f32 arithmetic downstream needs
# headroom for the WORST compound: centered squares (values up to 2*max,
# squares 4*max^2) accumulated through 2^TREE_LEVELS = 32 tree halvings
# before the f64 tail — requiring 128 * max^2 < f32_max, i.e.
# max < 2^60.5 — plus the Dekker-split scratch (x * 4097). 2^59 clears
# the square-tree bound with 8x margin and the plain sum bound
# (2^25 rows * max) by far; larger columns route to the wide-f64 path
# (scan_engine._packs_as_pair).
PAIR_SAFE_MAX = float(2 ** 59)

# number of pairwise halving levels before the f64 tail reduce: the tail
# touches n/2^LEVELS elements in f64, which is negligible at 5 levels
TREE_LEVELS = 5


# Elements per block of the two host-side walks below. Their scratch (two
# f64 blocks and a bool block, ~0.5 MB) stays inside a core's L2 and is
# local to the call: nothing column-sized is allocated, because every such
# temporary is page-faulted in and handed back. Flat from 2^14 to 2^17 in
# a sweep on the v5e's host (PERF.md, PR 25). A constant, not a knob.
_HOST_BLOCK = 1 << 15


def split_pair_np(x: np.ndarray, hi: np.ndarray = None, lo: np.ndarray = None):
    """Host-side packer split: 1-D f64 -> (hi, lo) f32 planes.

    Mirrors ops/hll.py:_f64_key_u64 exactly (canonical +0.0 fold first) so
    device HLL hashing over the shipped pair is bit-identical to hashing
    the f64 values. Non-finite residuals (x = +/-inf => x - hi = nan)
    are zeroed so sums over columns containing infinities still produce
    the IEEE result (inf/nan) through the hi plane alone.

    ``hi`` and ``lo`` are the destinations, each as long as ``x`` (the
    packer passes rows of its staging planes); without them the call
    allocates and returns its own.
    """
    n = len(x)
    if hi is None:
        hi = np.empty(n, dtype=np.float32)
        lo = np.empty(n, dtype=np.float32)
    block = min(_HOST_BLOCK, n)
    canonical = np.empty(block, dtype=np.float64)
    diff = np.empty(block, dtype=np.float64)
    finite = np.empty(block, dtype=np.bool_)
    with np.errstate(over="ignore", invalid="ignore"):
        for start in range(0, n, _HOST_BLOCK):
            stop = min(start + _HOST_BLOCK, n)
            if stop - start < block:  # only the last block can be short
                block = stop - start
                canonical, diff, finite = (
                    canonical[:block], diff[:block], finite[:block]
                )
            h = hi[start:stop]
            np.add(x[start:stop], 0.0, out=canonical)
            h[...] = canonical
            np.subtract(canonical, h, out=diff)
            np.isfinite(diff, out=finite)
            # (count_nonzero: a third of .all()'s cost on a 256-row tenant)
            if np.count_nonzero(finite) < block:
                diff[np.logical_not(finite, out=finite)] = 0.0
            lo[start:stop] = diff
    return hi, lo


def pair_safe_np(values: np.ndarray) -> bool:
    """True when every finite value is safe for the f32-pair COMPUTE path
    (|x| <= PAIR_SAFE_MAX, leaving headroom for squares and partial-sum
    growth); columns with larger magnitudes ship as wide f64. False as
    soon as a block holds an unsafe value."""
    n = len(values)
    scratch = np.empty(min(_HOST_BLOCK, n), dtype=np.float64)
    for start in range(0, n, _HOST_BLOCK):
        stop = min(start + _HOST_BLOCK, n)
        mag = np.abs(values[start:stop], out=scratch[: stop - start])
        # a NaN or an inf fails the first comparison too: only then is the
        # block read again, for the largest of its finite values
        if not mag.max() <= PAIR_SAFE_MAX and (
            mag.max(where=np.isfinite(mag), initial=0.0) > PAIR_SAFE_MAX
        ):
            return False
    return True


def two_sum(a, b):
    """Error-free sum: s + err == a + b exactly (Knuth)."""
    s = a + b
    bb = s - a
    err = (a - (s - bb)) + (b - bb)
    return s, err


def _two_prod_err(a, b, p, xp):
    """Error term of p = a*b (Dekker split; no FMA exposed through jnp)."""
    split = xp.asarray(np.float32(4097.0))  # 2^12 + 1
    ta = a * split
    ah = ta - (ta - a)
    al = a - ah
    tb = b * split
    bh = tb - (tb - b)
    bl = b - bh
    return ((ah * bh - p) + ah * bl + al * bh) + al * bl


def int32_pair(v, xp):
    """Exact normalized (hi, lo) f32 pair from an int32 array.

    Split at 15 bits so both halves convert to f32 exactly, then one
    TwoSum renormalizes to (f32(v), v - f32(v)) — the same pair the packer
    produces for f64 values, keeping HLL keys consistent.
    """
    low = v & xp.int32(0x7FFF)
    high = v - low
    hi0 = high.astype(xp.float32)
    lo0 = low.astype(xp.float32)
    return two_sum(hi0, lo0)


def _f32 (xp, x):
    return xp.asarray(np.float32(x))


def _halves(x, fill, xp):
    """``x`` cut in two along the last axis, an odd length padded with
    one ``fill`` first."""
    m = x.shape[-1]
    if m % 2:
        pad = xp.full(x.shape[:-1] + (1,), fill, dtype=x.dtype)
        x = xp.concatenate([x, pad], axis=-1)
        m += 1
    return x[..., : m // 2], x[..., m // 2:]


def _pair_tree_sum(hi, lo, ok, xp, levels: int = TREE_LEVELS):
    """Sum the pair values where ok along the LAST axis, in f64: `levels`
    halving rounds of TwoSum with exact error accumulation over
    (s, c) = (where(ok, hi, 0), where(ok, lo, 0)), then an f64 tail
    reduce over the n/2^levels survivors. A 1-D column gives a scalar; a
    (C, n) plane gives C sums, each by the very same steps.

    The mask is applied to the HALVES of the first round, not to the
    whole planes: the same values, but nothing of whole length stands
    between the operands and the first TwoSum, so the compiler reads the
    planes where they lie and writes n/2 survivors (it wrote both masked
    planes out in full, and read them back, when they existed).

    Non-finite inputs poison TwoSum's error channel (inf - inf = NaN).
    The `s` channel is immune: at every round it is the plain f32 a + b,
    so the sum of its survivors is the IEEE-correct fallback: inf
    columns sum to inf (or NaN for mixed-sign infs / NaN data), matching
    the f64 path and the reference's JVM doubles. The lo plane is finite
    by construction (split_pair_np zeroes non-finite residuals,
    int32_pair is exact), so it cannot move an inf or a NaN."""
    z = _f32(xp, 0.0)
    if hi.shape[-1] <= 1 or levels < 1:
        s, c = xp.where(ok, hi, z), xp.where(ok, lo, z)
    else:
        (ok_a, ok_b), (hi_a, hi_b), (lo_a, lo_b) = (
            _halves(ok, False, xp), _halves(hi, 0.0, xp), _halves(lo, 0.0, xp)
        )
        s, err = two_sum(xp.where(ok_a, hi_a, z), xp.where(ok_b, hi_b, z))
        c = xp.where(ok_a, lo_a, z) + xp.where(ok_b, lo_b, z) + err
        for _ in range(levels - 1):
            if s.shape[-1] <= 1:
                break
            (s_a, s_b), (c_a, c_b) = _halves(s, 0.0, xp), _halves(c, 0.0, xp)
            s, err = two_sum(s_a, s_b)
            c = c_a + c_b + err
    tree = xp.sum(s.astype(xp.float64), axis=-1) + xp.sum(
        c.astype(xp.float64), axis=-1
    )
    # tree is NaN only when non-finite values were present (finite inputs
    # cannot overflow under PAIR_SAFE_MAX)
    return xp.where(
        xp.isnan(tree), xp.sum(s, axis=-1).astype(xp.float64), tree
    )


def _sum_product_pair(p, e, xp):
    """Reduce a product pair (p, e) along the last axis to f64: p in f64,
    e in f32 with one final convert.

    Product pairs do NOT use the compensated f32 tree: the p channel's
    producer is a multiply, and XLA's fusion duplicates that multiply into
    both TwoSum consumers, where LLVM may contract ONE copy into an FMA —
    the two consumers then see different roundings of `p` and the
    compensation adds noise instead of removing it (measured ~2e-9 rel on
    30k-row m2 under jit vs 1e-15 eager; the mesh/no-mesh matrix caught
    it). An f64 reduce of p is immune to contraction; summing e in f32
    contributes error ~6e-8 * sum|e| ~ 4e-15 * sum|p|, far below the
    1e-12 target. Cost: one full-length f64 reduce per moment column —
    only the moment/co-moment ops pay it, plain sums keep the f32 tree."""
    return xp.sum(p.astype(xp.float64), axis=-1) + xp.sum(e, axis=-1).astype(
        xp.float64
    )


def merge_tags_f64(is_sum, is_min, acc, new, xp):
    """Elementwise tagged merge of two flat f64 STATE vectors (the device
    analogue of ``scan_engine._tag_reduce_np``): ``is_sum``/``is_min``
    boolean masks select add / minimum, everything else is maximum.

    Deliberately UNcompensated: state leaves are already f64 chunk
    aggregates (the per-chunk reductions above did the two-float work),
    and the host fold merges them with plain IEEE f64 add/min/max — a
    TwoSum-compensated device merge would be *more* accurate than the
    host fold and break the bit-identity contract between the two paths
    (docs/numerics.md, fold order & determinism). f64 adds on the tiny
    state vector are scalar-count work; the 10x software-f64 penalty
    that pushed O(n) compute onto the f32 pair does not apply. Min/max
    propagate NaN exactly as numpy's do."""
    return xp.where(
        is_sum,
        acc + new,
        xp.where(is_min, xp.minimum(acc, new), xp.maximum(acc, new)),
    )


# Every masked_* reduction below runs along the LAST axis: a 1-D column
# (a where-filtered op, the expression evaluator, the selection kernels)
# gives scalars, and the packed (C, n) planes give C of each at once (the
# fused step's plane statistics, scan_engine.PlaneStats) by the same
# arithmetic, in the same order along the rows.


def masked_sum(hi, lo, ok, xp):
    """Sum of the pair values where ok, in f64, ~1e-13 accurate."""
    if lo is None:
        return xp.sum(xp.where(ok, hi, 0.0), axis=-1)
    return _pair_tree_sum(hi, lo, ok, xp)


def masked_count(ok, xp):
    """Row count as i32 (chunks are < 2^31 rows by construction)."""
    return xp.sum(ok, axis=-1, dtype=xp.int32)


def extremum_hi(hi, ok, xp, mode: str):
    """First stage of the exact extremum: min/max of the hi plane where
    ok (the identity where nothing is ok). All there is to it for a
    wide-f64 column, which has no lo plane."""
    red = xp.min if mode == "min" else xp.max
    ident = _f32(xp, np.inf if mode == "min" else -np.inf)
    return red(xp.where(ok, hi, ident), axis=-1)


def extremum_tie(hi, lo, ok, eh, xp, mode: str):
    """Second stage: the lo extremum among the rows whose hi equals the
    first stage's ``eh``, joined to it in f64. Exact because hi is the
    rounded-to-nearest f32 of x: hi_a < hi_b implies x_a <= x_b, so the
    true extremum lives in the hi-tie group."""
    red = xp.min if mode == "min" else xp.max
    ident = _f32(xp, np.inf if mode == "min" else -np.inf)
    gl = xp.where(ok & (hi == eh[..., None]), lo, ident)
    el = red(gl, axis=-1)
    # all-masked chunks: eh = +/-inf and el = +/-inf; callers guard on the
    # separate count, and inf + inf keeps the sign
    return eh.astype(xp.float64) + el.astype(xp.float64)


def masked_extremum(hi, lo, ok, xp, mode: str):
    """Exact min/max of pair values where ok, in f64: the extremum over
    hi, then over lo among the hi-ties."""
    if lo is None:
        return extremum_hi(hi, ok, xp, mode)
    return extremum_tie(hi, lo, ok, extremum_hi(hi, ok, xp, mode), xp, mode)


def _center(hi, lo, mean64, ok, xp):
    """(x - mean) as a renormalized f32 pair, masked rows zeroed.
    mean64 is f64 with one entry per reduced row of ``hi``: a SCALAR for
    a column, a (C,) vector broadcast over the rows of a (C, n) plane
    (f64 ops on that few values are free on TPU)."""
    mean64 = mean64[..., None]
    mh = mean64.astype(xp.float32)
    ml = (mean64 - mh.astype(xp.float64)).astype(xp.float32)
    if lo is None:
        # wide-f64 column: center in f64 directly
        d = xp.where(ok, hi - mean64, 0.0)
        return d, None
    z = _f32(xp, 0.0)
    # hi - mh only rounds exactly inside the Sterbenz range (mh/2..2mh);
    # outside it the lost bits made chunk m2 association-dependent at
    # ~1e-9 relative (caught by the single-device test matrix), so capture
    # them with a TwoSum. The small-term sum (lo - ml + err) rounds at
    # second order only.
    s1, e1 = two_sum(hi, -mh)
    dh, err = two_sum(s1, (lo - ml) + e1)
    dh = xp.where(ok, dh, z)
    dl = xp.where(ok, err, z)
    return dh, dl


def _sqr_pair(dh, dl, xp):
    """d^2 as (p, e) with p = f32 square and e the exact correction
    (TwoProd error + cross term; dl^2 is below the accumulation noise)."""
    p = dh * dh
    e = _two_prod_err(dh, dh, p, xp) + (dh + dh) * dl
    return p, e


def _mul_pair(ah, al, bh, bl, xp):
    """a*b as (p, e) for two pairs (co-moment products)."""
    p = ah * bh
    e = _two_prod_err(ah, bh, p, xp) + ah * bl + al * bh
    return p, e


def centered_m2(hi, lo, mean, ok, xp):
    """Sum of squared deviations from ``mean`` where ok, in f64: the
    second sweep of the chunk moments (it needs the first one's mean)."""
    dh, dl = _center(hi, lo, mean, ok, xp)
    if dl is None:
        return xp.sum(dh * dh, axis=-1)
    p, e = _sqr_pair(dh, dl, xp)
    return _sum_product_pair(p, e, xp)


def masked_moments(hi, lo, ok, xp):
    """(count_i32, sum_f64, mean_f64, m2_f64) — the Welford chunk moments
    (reference StandardDeviation.scala:37-44 merges these across chunks)."""
    cnt = masked_count(ok, xp)
    s = masked_sum(hi, lo, ok, xp)
    mean = s / xp.maximum(cnt, 1)
    return cnt, s, mean, centered_m2(hi, lo, mean, ok, xp)


def masked_comoments(a_hi, a_lo, b_hi, b_lo, ok, xp):
    """Correlation co-moment chunk state (n, x_avg, y_avg, ck, x_mk, y_mk)
    (reference Correlation.scala:37-52)."""
    import jax

    with jax.named_scope("deequ.comoments"):
        return _comoments(a_hi, a_lo, b_hi, b_lo, ok, xp)


def _comoments(a_hi, a_lo, b_hi, b_lo, ok, xp):
    cnt = masked_count(ok, xp)
    denom = xp.maximum(cnt, 1)
    sa = masked_sum(a_hi, a_lo, ok, xp)
    sb = masked_sum(b_hi, b_lo, ok, xp)
    ma = sa / denom
    mb = sb / denom
    dah, dal = _center(a_hi, a_lo, ma, ok, xp)
    dbh, dbl = _center(b_hi, b_lo, mb, ok, xp)
    if dal is None or dbl is None:
        da64 = dah if dal is None else dah.astype(xp.float64) + dal.astype(xp.float64)
        db64 = dbh if dbl is None else dbh.astype(xp.float64) + dbl.astype(xp.float64)
        ck = xp.sum(da64 * db64, axis=-1)
        x_mk = xp.sum(da64 * da64, axis=-1)
        y_mk = xp.sum(db64 * db64, axis=-1)
    else:
        pc, ec = _mul_pair(dah, dal, dbh, dbl, xp)
        ck = _sum_product_pair(pc, ec, xp)
        pa, ea = _sqr_pair(dah, dal, xp)
        x_mk = _sum_product_pair(pa, ea, xp)
        pb, eb = _sqr_pair(dbh, dbl, xp)
        y_mk = _sum_product_pair(pb, eb, xp)
    return cnt, ma, mb, ck, x_mk, y_mk
