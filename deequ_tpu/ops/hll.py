"""HyperLogLog++ cardinality sketch as a device kernel.

The reference implements HLL++ as a Catalyst ImperativeAggregate with a
per-row xxHash64 + leading-zero register max
(analyzers/catalyst/StatefulHyperloglogPlus.scala:89-149). The TPU-native
design keeps the exact state algebra — a fixed register file merged by
elementwise max — but vectorizes:

- numeric values hash ON DEVICE with a 64-bit finalizer (splitmix64) over
  their raw bits; the register file is one ``segment_max`` over the fused
  scan chunk, so ApproxCountDistinct shares the single scan pass and its
  cross-device merge is the engine's elementwise-``max`` collective;
- string values hash once per distinct dictionary entry on the host
  (xxhash64 over utf-8 bytes, O(cardinality)), then the device gathers
  hashes by code.

Estimation uses the standard HLL estimator with linear counting for the
small range (the reference additionally interpolates Spark's empirical bias
tables; we deliberately use the table-free estimator — same error class at
the default precision, no copied constants).

Default precision mirrors the reference's RELATIVE_SD = 0.05
(StatefulHyperloglogPlus.scala:154-161): p = 9, m = 512 registers.
"""

from __future__ import annotations

import functools
import math
from typing import Optional

import numpy as np

DEFAULT_RELATIVE_SD = 0.05
XXHASH_SEED = 42

_PRIME64_1 = 0x9E3779B185EBCA87
_PRIME64_2 = 0xC2B2AE3D27D4EB4F
_PRIME64_3 = 0x165667B19E3779F9
_PRIME64_4 = 0x85EBCA77C2B2AE63
_PRIME64_5 = 0x27D4EB2F165667C5
_MASK64 = (1 << 64) - 1


def precision_from_relative_sd(relative_sd: float = DEFAULT_RELATIVE_SD) -> int:
    """p such that 1.04/sqrt(2^p) <= relative_sd (reference derivation)."""
    return max(4, math.ceil(2.0 * math.log(1.106 / relative_sd) / math.log(2.0)))


def _rotl(x: int, r: int) -> int:
    return ((x << r) | (x >> (64 - r))) & _MASK64


def xxhash64_bytes(data: bytes, seed: int = XXHASH_SEED) -> int:
    """Pure-python xxHash64 (public algorithm) for host-side string hashing."""
    n = len(data)
    i = 0
    if n >= 32:
        v1 = (seed + _PRIME64_1 + _PRIME64_2) & _MASK64
        v2 = (seed + _PRIME64_2) & _MASK64
        v3 = seed & _MASK64
        v4 = (seed - _PRIME64_1) & _MASK64
        while i <= n - 32:
            for vi, off in ((0, 0), (1, 8), (2, 16), (3, 24)):
                lane = int.from_bytes(data[i + off:i + off + 8], "little")
                v = (v1, v2, v3, v4)[vi]
                v = (v + lane * _PRIME64_2) & _MASK64
                v = (_rotl(v, 31) * _PRIME64_1) & _MASK64
                if vi == 0:
                    v1 = v
                elif vi == 1:
                    v2 = v
                elif vi == 2:
                    v3 = v
                else:
                    v4 = v
            i += 32
        h = (_rotl(v1, 1) + _rotl(v2, 7) + _rotl(v3, 12) + _rotl(v4, 18)) & _MASK64
        for v in (v1, v2, v3, v4):
            v = (v * _PRIME64_2) & _MASK64
            v = (_rotl(v, 31) * _PRIME64_1) & _MASK64
            h ^= v
            h = (h * _PRIME64_1 + _PRIME64_4) & _MASK64
    else:
        h = (seed + _PRIME64_5) & _MASK64
    h = (h + n) & _MASK64
    while i <= n - 8:
        lane = int.from_bytes(data[i:i + 8], "little")
        k = (_rotl((lane * _PRIME64_2) & _MASK64, 31) * _PRIME64_1) & _MASK64
        h ^= k
        h = (_rotl(h, 27) * _PRIME64_1 + _PRIME64_4) & _MASK64
        i += 8
    if i <= n - 4:
        lane = int.from_bytes(data[i:i + 4], "little")
        h ^= (lane * _PRIME64_1) & _MASK64
        h = (_rotl(h, 23) * _PRIME64_2 + _PRIME64_3) & _MASK64
        i += 4
    while i < n:
        h ^= (data[i] * _PRIME64_5) & _MASK64
        h = (_rotl(h, 11) * _PRIME64_1) & _MASK64
        i += 1
    h ^= h >> 33
    h = (h * _PRIME64_2) & _MASK64
    h ^= h >> 29
    h = (h * _PRIME64_3) & _MASK64
    h ^= h >> 32
    return h


def hash_strings(values, seed: int = XXHASH_SEED) -> np.ndarray:
    """xxhash64 per distinct string (host, O(cardinality)); uses the C++
    batch kernel when available (deequ_tpu/native), bit-identical fallback."""
    from deequ_tpu import native

    hashed = native.hash_strings(values, seed)
    if hashed is not None:
        return hashed
    # deequ-lint: ignore[host-fetch] -- pure-python hash fallback over host strings
    return np.array(
        [xxhash64_bytes(str(v).encode("utf-8"), seed) for v in values],
        dtype=np.uint64,
    )


def splitmix64(x, xp):
    """64-bit avalanche finalizer (public constants), device-friendly."""
    x = x.astype(xp.uint64) if hasattr(x, "astype") else xp.asarray(x, xp.uint64)
    x = x + xp.uint64(0x9E3779B97F4A7C15)
    x = (x ^ (x >> xp.uint64(30))) * xp.uint64(0xBF58476D1CE4E5B9)
    x = (x ^ (x >> xp.uint64(27))) * xp.uint64(0x94D049BB133111EB)
    return x ^ (x >> xp.uint64(31))


def _f64_key_u64(values, xp):
    """f64 -> u64 key via a double-float split (XLA:TPU rejects f64->u64
    bitcasts and f64 frexp — "UNIMPLEMENTED: While rewriting computation
    to not contain X64 element types", re-established against libtpu
    0.0.34 for a described v5e, PR 21; f32 bitcasts work).

    hi = f32(x), lo = f32(x - hi) is the standard double-float decomposition:
    (hi, lo) carries ~48 mantissa bits, so the key is injective for all
    values distinguishable at that precision — ample for cardinality
    hashing. Host numpy uses the identical formula so states computed on
    different platforms merge consistently."""
    canonical = values + 0.0  # fold -0.0 into +0.0
    if xp is np:
        with np.errstate(over="ignore", invalid="ignore"):
            hi = canonical.astype(np.float32)  # |x| > f32 max folds to inf
            lo = (canonical - hi.astype(np.float64)).astype(np.float32)
    else:
        hi = canonical.astype(xp.float32)
        lo = (canonical - hi.astype(xp.float64)).astype(xp.float32)
    if xp is np:
        hi_bits = hi.view(np.uint32).astype(np.uint64)
        lo_bits = lo.view(np.uint32).astype(np.uint64)
    else:
        import jax

        hi_bits = jax.lax.bitcast_convert_type(hi, xp.uint32).astype(xp.uint64)
        lo_bits = jax.lax.bitcast_convert_type(lo, xp.uint32).astype(xp.uint64)
    return (hi_bits << xp.uint64(32)) | lo_bits


def hash_numeric_device(values, xp, seed: int = XXHASH_SEED):
    """Hash float64 values on device: injective 64-bit key -> splitmix64."""
    bits = _f64_key_u64(values, xp)
    return splitmix64(bits ^ xp.uint64((seed * 0x9E3779B97F4A7C15) & _MASK64), xp)


def clz64(x, xp):
    """Branchless count-leading-zeros for uint64 arrays."""
    n = xp.full(xp.shape(x), 64, dtype=xp.int32)
    for s in (32, 16, 8, 4, 2, 1):
        y = x >> xp.uint64(s)
        hit = y != 0
        x = xp.where(hit, y, x)
        n = n - xp.where(hit, xp.int32(s), xp.int32(0))
    return n - (x != 0).astype(xp.int32)


# -- u32-native hash path (register format v2) -------------------------------
#
# u64 arithmetic is software-emulated on TPU v5e; the r4 profile showed
# the 4 HLL columns' splitmix64 + 6-step clz64 as the DOMINANT device
# compute of the whole 105-metric scan (~15ms/column). The v2 path works
# in the u32 domain end to end: the packer's (hi, lo) f32 planes bitcast
# to two u32 lanes (32-bit bitcasts are native; XLA:TPU rejects 64-bit
# ones from f64 anyway, see _f64_key_u64), two murmur3 fmix32 finalizers
# (public constants) mix them with cross-dependence, and idx/rank come from
# native u32 shifts with a 5-step clz32. The rank still spans the same
# [1, 64-p+1] domain (32-p bits of lane A, then 32 bits of lane B), so
# the Ertl estimator is unchanged. Registers hashed this way are NOT
# mergeable with v1 (u64 splitmix) registers — ApproxCountDistinctState
# carries hash_version and refuses cross-version merges; string columns
# keep host xxhash64 (content-identical to v1) but are stamped v2 too.

HASH_VERSION = 2

# The one-hot MXU register FOLD dominates the column cost (15 ms per
# 10M-row column standalone on the v5e, my chip run, PR 21; the hash
# stage's share is not measured), so the u32 path's end-to-end HLL win is
# small. It stays the default anyway: it removes every software-emulated
# u64 op from the device and halves the string-LUT transfer bytes (packed
# i32 vs u64 hashes). The fold keeps R = 64 (the full 64 - p + 1 rank cap).


def fmix32(x, xp):
    """murmur3's 32-bit avalanche finalizer (public constants)."""
    x = x ^ (x >> xp.uint32(16))
    x = x * xp.uint32(0x85EBCA6B)
    x = x ^ (x >> xp.uint32(13))
    x = x * xp.uint32(0xC2B2AE35)
    return x ^ (x >> xp.uint32(16))


def clz32(x, xp):
    """Branchless count-leading-zeros for uint32 arrays."""
    n = xp.full(xp.shape(x), 32, dtype=xp.int32)
    for s in (16, 8, 4, 2, 1):
        y = x >> xp.uint32(s)
        hit = y != 0
        x = xp.where(hit, y, x)
        n = n - xp.where(hit, xp.int32(s), xp.int32(0))
    return n - (x != 0).astype(xp.int32)


def idx_rank_u32(hi_bits, lo_bits, p: int, xp, seed: int = XXHASH_SEED):
    """(idx, rank) for the HLL fold from two u32 lanes, all-u32 compute.

    BOTH output words mix BOTH input lanes: for dense float clusters the
    distinguishing entropy lives almost entirely in the lo lane (hi is
    the f32 rounding, ~2^23 granularity), so a word derived from hi
    alone caps the observable cardinality at the distinct-hi count — a
    first formulation made exactly that mistake and underestimated 10M
    normals 4x. a = fmix32(fmix32(hi ^ seed) ^ lo) provides idx (top p
    bits) + the first 32-p rank bits; b mixes the lanes in the opposite
    order with a different seed and extends the geometric tail to the
    full 64-p bits, so rank spans [1, 64-p+1] like the v1 u64 path."""
    s = xp.uint32(seed & 0xFFFFFFFF)
    a = fmix32(fmix32(hi_bits ^ s, xp) ^ lo_bits, xp)
    b = fmix32(fmix32(lo_bits ^ s ^ xp.uint32(0x9E3779B9), xp) ^ hi_bits, xp)
    idx = (a >> xp.uint32(32 - p)).astype(xp.int32)
    w1 = a << xp.uint32(p)
    r1 = clz32(w1, xp) + 1                     # w1 == 0 -> 33
    r2 = clz32(b, xp) + 1
    rank = xp.where(w1 != 0, r1, xp.int32(32 - p) + r2)
    return idx, xp.minimum(rank, 64 - p + 1)


def _pair_bits_u32(hi, lo, xp):
    """Bitcast the packer's (hi, lo) f32 planes to u32 lanes. Restores the
    NaN residual for non-finite values (the packer zeroes it so sums stay
    IEEE-correct) — matching what a from-f64 split derives."""
    if xp is np:
        with np.errstate(invalid="ignore"):
            lo = np.where(np.isfinite(hi), lo, np.float32(np.nan))
        return hi.view(np.uint32), lo.view(np.uint32)
    import jax

    lo = xp.where(xp.isfinite(hi), lo, xp.asarray(np.float32(np.nan)))
    return (
        jax.lax.bitcast_convert_type(hi, xp.uint32),
        jax.lax.bitcast_convert_type(lo, xp.uint32),
    )


def idx_rank_pair_device(hi, lo, p: int, xp, seed: int = XXHASH_SEED):
    """(idx, rank) straight from two-float pair planes — no u64 ops."""
    hb, lb = _pair_bits_u32(hi, lo, xp)
    return idx_rank_u32(hb, lb, p, xp, seed)


def idx_rank_numeric(values, p: int, xp, seed: int = XXHASH_SEED):
    """(idx, rank) from f64 values via the canonical double-float split
    (same split as the packer, so pair-path and wide-path registers are
    bit-identical; host numpy uses the identical formula so states merge
    across platforms)."""
    canonical = values + 0.0  # fold -0.0 into +0.0
    if xp is np:
        with np.errstate(over="ignore", invalid="ignore"):
            hi = canonical.astype(np.float32)
            diff = canonical - hi.astype(np.float64)
            lo = np.where(np.isfinite(diff), diff, 0.0).astype(np.float32)
    else:
        hi = canonical.astype(xp.float32)
        diff = canonical - hi.astype(xp.float64)
        lo = xp.where(xp.isfinite(diff), diff, 0.0).astype(xp.float32)
    return idx_rank_pair_device(hi, lo, p, xp, seed)


_MXU_FOLD_BLOCK = 1 << 22
_MXU_FOLD_MIN_ROWS = 1 << 16


@functools.lru_cache(maxsize=None)
def _mxu_fold_fn(m: int):
    """The blocked one-hot register fold for ``m`` registers, as a
    ``map_under_vmap`` program: XLA:TPU miscompiles the BATCHED one-hot
    matmul, so a vmapped fold (the coalesced service's tenant axis) maps
    the unbatched program instead (ops/histogram_device.py)."""
    import jax.numpy as jnp

    from deequ_tpu.ops.histogram_device import map_under_vmap, onehot_counts

    R = 64

    def fold(idx, rank):
        C = jnp.zeros((m, R), dtype=jnp.float32)
        for s in range(0, idx.shape[0], _MXU_FOLD_BLOCK):
            C = C + onehot_counts(
                idx[s:s + _MXU_FOLD_BLOCK], m,
                rank[s:s + _MXU_FOLD_BLOCK], R, jnp.bfloat16,
            )
        return ((C > 0) * jnp.arange(R)).max(axis=1).astype(jnp.int32)

    return map_under_vmap(fold)


def _registers_mxu_fold(idx, rank, m: int, xp):
    """Register fold as a one-hot bf16 matmul on the MXU.

    presence[i, r] = (#rows with idx==i and rank==r) > 0, computed as
    one_hot(idx)^T @ one_hot(rank) in row blocks; register[i] is then the
    highest present rank. This replaces the scatter-max (the matmul rides
    the systolic array: 15 ms standalone for a 10M-row column on the v5e,
    my chip run, PR 21; the scatter it replaced is not measured on this
    machine) and fuses into the surrounding scan.
    Exactness: one-hot products are 0/1 in bf16, accumulation is f32
    (counts are non-negative, so presence > 0 survives any f32 rounding).
    The one-hot rank width R = 64 covers every rank cap.
    """
    return _mxu_fold_fn(int(m))(idx, rank)


def idx_rank_from_hash64(hashes, p: int, xp):
    """(idx, rank) from 64-bit hashes — the v1 derivation, still used for
    string columns whose xxhash64 LUT is computed on HOST (numpy u64 ops
    are cheap there; the device only gathers i32 idx/rank)."""
    idx = (hashes >> xp.uint64(64 - p)).astype(xp.int32)
    rest = hashes << xp.uint64(p)
    rank = (clz64(rest, xp) + 1).astype(xp.int32)
    return idx, xp.minimum(rank, 64 - p + 1)


def pack_idx_rank(idx, rank):
    """Host LUT packing: one i32 per distinct value (rank <= 57 fits in
    6 bits). The device unpacks with native i32 shifts/masks."""
    return (idx.astype(np.int32) << np.int32(6)) | rank.astype(np.int32)


def string_idx_rank_lut(values, p: int, seed: int = XXHASH_SEED) -> np.ndarray:
    """Packed (idx, rank) LUT for a string dictionary: xxhash64 per
    distinct value on host, u64 idx/rank derivation on host, i32 out —
    register contents identical to hashing the values with v1."""
    hashes = hash_strings(values, seed)
    idx, rank = idx_rank_from_hash64(hashes, p, np)
    packed = pack_idx_rank(idx, rank)
    return packed if len(packed) else np.zeros(1, dtype=np.int32)


def _on_accelerator(xp) -> bool:
    """Traced code on a device that has an MXU: on a CPU backend the
    one-hot matmul is a large memory/FLOP regression over the scatter."""
    import jax

    return xp is not np and jax.devices()[0].platform != "cpu"


def _fold_registers(idx, rank, valid, p: int, xp, mxu: bool):
    """Registers take the max rank per idx; invalid rows contribute rank 0.
    Lowering paths: one-hot bf16 matmul on the MXU or XLA segment_max.
    The fold's one-hot width is fixed at 64: it covers every rank cap."""
    import jax

    m = 1 << p
    rank = xp.where(valid, rank, 0)
    idx = xp.where(valid, idx, 0)
    if mxu:
        return _registers_mxu_fold(idx, rank, m, xp)
    regs = jax.ops.segment_max(
        rank, idx, num_segments=m, indices_are_sorted=False
    ).astype(xp.int32)
    return xp.maximum(regs, 0)  # untouched segments fill with INT_MIN


def registers_from_idx_rank(idx, rank, valid, p: int, xp):
    """Fold (idx, rank) rows into an HLL register file on device: on the
    MXU for large device chunks, by segment_max for small chunks and host
    numpy."""
    import jax

    with jax.named_scope("deequ.hll.fold"):
        return _fold_registers(
            idx, rank, valid, p, xp,
            mxu=idx.shape[0] >= _MXU_FOLD_MIN_ROWS and _on_accelerator(xp),
        )


def registers_from_present(packed, present, p: int, xp):
    """The register file of a dictionary column from the entries PRESENT:
    ``packed`` is the dictionary's packed (idx, rank) LUT
    (:func:`string_idx_rank_lut`), ``present[k]`` whether some counted row
    holds entry ``k``. A register is the largest rank among the VALUES
    hashed to it, so the fold of the K entries present equals the fold of
    the rows (:func:`registers_from_idx_rank` after the per-row gather),
    register for register, at K elements of work in place of n. On an
    accelerator it rides the MXU whatever K: a scatter walks its elements
    one by one there, and the grouping program that calls it is held free
    of scatters but the wide counts (equal to numpy from 1 to 3M entries
    on the v5e, 30.4 ms a suite for the twenty columns of
    ``strings12m.sscan``: PERF.md section 6, PR 33)."""
    import jax

    with jax.named_scope("deequ.hll.present"):
        idx = (packed >> xp.int32(6)).astype(xp.int32)
        rank = (packed & xp.int32(0x3F)).astype(xp.int32)
        return _fold_registers(
            idx, rank, present, p, xp, mxu=_on_accelerator(xp)
        )


def registers_from_hashes(hashes, valid, p: int, xp):
    """Fold 64-bit hashes into a register file (v1 derivation; host paths
    and tests)."""
    idx, rank = idx_rank_from_hash64(hashes, p, xp)
    return registers_from_idx_rank(idx, rank, valid, p, xp)


def _sigma(x: float) -> float:
    """Ertl's sigma: sum for the zero-register (small-range) correction."""
    if x == 1.0:
        return float("inf")
    y = 1.0
    z = x
    while True:
        x = x * x
        z_prev = z
        z = z + x * y
        y = y + y
        if z == z_prev:
            return z


def _tau(x: float) -> float:
    """Ertl's tau: sum for the saturated-register (large-range) correction."""
    if x == 0.0 or x == 1.0:
        return 0.0
    y = 1.0
    z = 1.0 - x
    while True:
        x = math.sqrt(x)
        z_prev = z
        y = 0.5 * y
        z = z - (1.0 - x) ** 2 * y
        if z == z_prev:
            return z / 3.0


def estimate_cardinality(registers: np.ndarray) -> float:
    """Cardinality from an HLL register file via Ertl's improved estimator
    ("New cardinality estimation algorithms for HyperLogLog sketches",
    2017, public algorithm): a single closed-form estimate from the
    register-value histogram with sigma/tau corrections for the zero- and
    saturated-register tails.

    Replaces the classic raw-estimate + linear-counting switch whose
    uncorrected band at 2.5m-5m the reference patches with Spark's
    empirical bias tables (StatefulHyperloglogPlus.scala:210-297). Ertl's
    estimator is table-free AND unbiased across the whole range — no
    copied constants, tighter error than interpolated bias correction.
    """
    # deequ-lint: ignore[host-fetch] -- partials arrive host-side, drained (and accounted) by the scan fetch
    registers = np.asarray(registers)
    m = len(registers)
    p = int(round(math.log2(m)))
    q = 64 - p  # ranks are capped at q + 1 (registers_from_hashes)
    counts = np.bincount(
        registers.astype(np.int64), minlength=q + 2
    ).astype(np.float64)
    alpha_inf = 1.0 / (2.0 * math.log(2.0))
    # sum_{k=1..q} C[k] * 2^{-k}, accumulated small-to-large for accuracy
    z = m * _tau(1.0 - counts[q + 1] / m)
    for k in range(q, 0, -1):
        z = 0.5 * (z + counts[k])
    z = z + m * _sigma(counts[0] / m)
    # cardinality is a whole number: round like the reference
    # (StatefulHyperloglogPlus.scala count() ends with Java Math.round,
    # which is floor(x + 0.5) — python round() would go half-to-even)
    return float(math.floor(alpha_inf * m * m / z + 0.5))
