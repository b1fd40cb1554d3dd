"""Reference-conformance fixtures for the sketch estimators.

The reference pins exact sketch semantics: HLL++ as 52 x 6-bit registers
with xxHash64 and Spark's empirical bias tables
(analyzers/catalyst/StatefulHyperloglogPlus.scala:152-298), and KLL with
the compactor hierarchy of QuantileNonSample.scala:25-305. This framework
DELIBERATELY redesigned both (ops/hll.py docstring): a
table-free Ertl-style HLL estimator over the same register-max algebra,
and a device-built KLL with deterministic strata compaction feeding the
standard merge algebra. These tests pin the redesigned estimators to
GOLDEN values and to documented deviation bounds so any silent drift —
a changed hash constant, register derivation, estimator correction, or
rank rule — fails loudly. Persisted states depend on these exact
semantics: registers hashed with one constant must never merge with
registers hashed with another.

Documented deviation from the reference:
- HLL precision derivation is IDENTICAL (p = 9 / m = 512 registers from
  RELATIVE_SD = 0.05, StatefulHyperloglogPlus.scala:154-161), so the
  error CLASS matches (sigma ~ 1.04/sqrt(512) ~ 4.6%). The estimates
  differ numerically from the reference on identical data because the
  hash (splitmix64 over the double-float key vs xxHash64 of raw bits)
  and the mid-range correction (Ertl tau/sigma vs Spark's bias tables)
  differ. Measured deviation from TRUE cardinality across 1e2..1e6 is
  pinned below at <= 6% (reference's own target is ~5%).
- KLL rank rule is the reference's searchsorted-left / ceil(q*n)-1
  (QuantileNonSample.scala:126-278); compaction is deterministic strata
  midpoints instead of random-offset compactors, with the same rank
  error class (<= ~1% at sketch_size 256, pinned below).
"""

import numpy as np
import pytest

from deequ_tpu.ops import hll as H
from deequ_tpu.ops.kll import KLLSketchState

# -- HLL ---------------------------------------------------------------------

# exact register file for 32 fixed doubles (arange(1, 33) * 1.5) hashed
# through the production pipeline (splitmix64 over the double-float key,
# seed 42). If ANY entry changes, persisted ApproxCountDistinct states
# from earlier versions would silently merge wrongly — treat a failure
# here as a serde-breaking change, not a test to update casually.
_HLL_FIXTURE_REGISTERS = {
    8: 1, 30: 1, 55: 1, 83: 3, 91: 3, 116: 4, 150: 2, 161: 3, 171: 2,
    210: 1, 239: 3, 258: 2, 266: 3, 267: 2, 301: 2, 304: 2, 311: 1,
    312: 1, 314: 2, 349: 2, 362: 1, 425: 2, 433: 1, 451: 4, 458: 1,
    477: 4, 487: 1, 493: 1, 494: 8,
}


def test_hll_precision_matches_reference_derivation():
    """p from RELATIVE_SD = 0.05 via the reference's formula
    (StatefulHyperloglogPlus.scala:154-161): ceil(2*log2(1.106/sd))."""
    assert H.precision_from_relative_sd() == 9
    assert H.precision_from_relative_sd(0.05) == 9
    # the reference derives p = 4 at sd ~ 0.4 and larger p as sd shrinks
    assert H.precision_from_relative_sd(0.4) == 4
    assert H.precision_from_relative_sd(0.01) == 14


def test_hll_register_pipeline_golden():
    """Hash -> register-index/rank derivation pinned bit-for-bit."""
    vals = np.arange(1.0, 33.0) * 1.5
    hashes = H.hash_numeric_device(vals, np)
    regs = H.registers_from_hashes(
        hashes, np.ones(32, bool), H.precision_from_relative_sd(), np
    )
    got = {int(i): int(r) for i, r in enumerate(regs) if r > 0}
    assert got == _HLL_FIXTURE_REGISTERS


def test_hll_estimator_golden():
    """Estimator outputs pinned on fixed register files (catches silent
    drift in the table-free Ertl correction)."""
    vals = np.arange(1.0, 33.0) * 1.5
    regs = H.registers_from_hashes(
        H.hash_numeric_device(vals, np), np.ones(32, bool), 9, np
    )
    # 32 distinct values in the near-exact linear-counting range
    assert H.estimate_cardinality(np.asarray(regs)) == 30.0
    assert H.estimate_cardinality(np.zeros(512, dtype=np.int64)) == 0.0
    assert H.estimate_cardinality(np.ones(512, dtype=np.int64)) == 739.0


@pytest.mark.parametrize("true_count", [100, 1_000, 10_000, 100_000])
def test_hll_documented_deviation_bound(true_count):
    """The accepted deviation of the table-free estimator vs TRUE
    cardinality: <= 6% across the reference's operating range (the
    reference's bias-table estimator targets ~5% at p = 9; measured
    values for these fixtures: 2.0%, 1.2%, 0.6%, 5.8%)."""
    x = np.arange(true_count, dtype=np.float64) * 0.7 + 3.0
    regs = H.registers_from_hashes(
        H.hash_numeric_device(x, np), np.ones(true_count, bool), 9, np
    )
    est = H.estimate_cardinality(np.asarray(regs))
    assert abs(est - true_count) / true_count <= 0.06


# -- KLL ---------------------------------------------------------------------

# quantiles of a fixed seeded normal(0,1) 100k sample through the host
# sketch (sketch_size 256, deterministic seeded compaction RNG) — exact
# values pinned; drift means the compaction or rank rule changed, which
# breaks persisted-sketch comparability across versions.
_KLL_GOLDEN = {
    0.01: -2.33797989959002,
    0.25: -0.6690293162886349,
    0.5: 0.0008542768130695202,
    0.75: 0.6836562750337061,
    0.99: 2.421409868961832,
}


def test_kll_quantile_golden():
    rng = np.random.default_rng(123)
    data = rng.normal(0.0, 1.0, 100_000)
    sk = KLLSketchState(256, 0.64)
    sk.update_batch(data)
    for q, want in _KLL_GOLDEN.items():
        assert sk.quantile(q) == want, q


def test_kll_documented_rank_error_bound():
    """Rank error of the compacted sketch <= 1% at sketch_size 256 (the
    reference's KLL targets the same class; measured on the golden
    fixture: 0.04%-0.26%). Bound asserted at 1% with margin."""
    rng = np.random.default_rng(123)
    data = rng.normal(0.0, 1.0, 100_000)
    sk = KLLSketchState(256, 0.64)
    sk.update_batch(data)
    sorted_d = np.sort(data)
    for q in (0.01, 0.25, 0.5, 0.75, 0.99):
        v = sk.quantile(q)
        rank = np.searchsorted(sorted_d, v, side="right") / len(data)
        assert abs(rank - q) <= 0.01, (q, rank)


def test_kll_exact_rank_rule_matches_reference():
    """Below the level-0 capacity the sketch is exact and must follow the
    reference's quantile rule (QuantileNonSample.scala:126-278):
    element at index ceil(q * n) - 1 of the sorted data."""
    import math

    data = np.arange(100, dtype=np.float64) + 0.5
    rng = np.random.default_rng(7)
    rng.shuffle(data)
    sk = KLLSketchState(256, 0.64)
    sk.update_batch(data)
    sorted_d = np.sort(data)
    for q in (0.01, 0.1, 0.25, 0.5, 0.75, 0.9, 0.99, 1.0):
        want = sorted_d[max(0, math.ceil(q * len(data)) - 1)]
        assert sk.quantile(q) == want, q


def test_string_hll_uses_xxhash64_reference_vectors():
    """The host string hash is xxHash64 (the reference's hash family,
    StatefulHyperloglogPlus.scala:89-115) — pinned against the public
    algorithm's known test vectors at seed 0 and our seed 42."""
    # public xxhash64 vectors (seed 0)
    assert H.xxhash64_bytes(b"", 0) == 0xEF46DB3751D8E999
    assert H.xxhash64_bytes(b"a", 0) == 0xD24EC4F1A98C6E5B
    # engine seed (42): pin current values so the seed can't drift
    h = H.hash_strings(np.array(["a", "b"], dtype=object))
    assert h.dtype == np.uint64
    assert int(h[0]) == H.xxhash64_bytes(b"a", 42)
    assert int(h[1]) == H.xxhash64_bytes(b"b", 42)


# -- HLL v2 (u32-native hash suite, round 5) ---------------------------------

# exact register file for the same 32 fixed doubles through the v2
# pipeline (two fmix32 lanes over the double-float split, seed 42).
# Same serde-breaking warning as the v1 fixture above: registers hashed
# with one suite must never merge with another's.
_HLL_V2_FIXTURE_REGISTERS = {
    7: 1, 43: 2, 70: 1, 85: 1, 108: 2, 128: 1, 149: 2, 170: 6, 171: 1,
    181: 1, 185: 1, 203: 4, 236: 1, 239: 2, 244: 2, 263: 3, 318: 2,
    332: 2, 333: 1, 337: 1, 352: 3, 366: 2, 369: 2, 391: 5, 405: 1,
    447: 1, 457: 1, 462: 1, 471: 1, 479: 1, 480: 3, 489: 1,
}


def test_hll_v2_register_pipeline_golden():
    p = H.precision_from_relative_sd()
    vals = np.arange(1.0, 33.0) * 1.5
    idx, rank = H.idx_rank_numeric(vals, p, np)
    regs = H.registers_from_idx_rank(idx, rank, np.ones(32, bool), p, np)
    got = {int(i): int(r) for i, r in enumerate(np.asarray(regs)) if r > 0}
    assert got == _HLL_V2_FIXTURE_REGISTERS
    assert H.estimate_cardinality(np.asarray(regs)) == 33.0


@pytest.mark.parametrize("true_count", [100, 1_000, 10_000, 100_000])
def test_hll_v2_documented_deviation_bound(true_count):
    """v2 accuracy stays within the same <= 6% envelope as v1 (measured:
    0.0%, 2.6%, 3.5%, 0.3%)."""
    x = np.arange(true_count, dtype=np.float64) * 0.7 + 3.0
    idx, rank = H.idx_rank_numeric(x, 9, np)
    regs = H.registers_from_idx_rank(
        idx, rank, np.ones(true_count, bool), 9, np
    )
    est = H.estimate_cardinality(np.asarray(regs))
    assert abs(est - true_count) / true_count <= 0.06


def test_hll_v2_device_matches_host_and_pair_matches_wide():
    """Cross-platform merge safety: device jnp and host numpy derive
    identical (idx, rank); the packer's pair planes derive the same as
    the from-f64 split."""
    import jax.numpy as jnp

    from deequ_tpu.ops.df32 import split_pair_np

    vals = np.concatenate([
        np.arange(1.0, 200.0) * 0.37,
        [0.0, -0.0, 1e300, -1e300, np.inf, -np.inf, np.nan, 2.5e-310],
    ])
    p = 9
    i_host, r_host = H.idx_rank_numeric(vals, p, np)
    i_dev, r_dev = H.idx_rank_numeric(jnp.asarray(vals), p, jnp)
    np.testing.assert_array_equal(np.asarray(i_dev), i_host)
    np.testing.assert_array_equal(np.asarray(r_dev), r_host)
    hi, lo = split_pair_np(vals)
    i_pair, r_pair = H.idx_rank_pair_device(
        jnp.asarray(hi), jnp.asarray(lo), p, jnp
    )
    np.testing.assert_array_equal(np.asarray(i_pair), i_host)
    np.testing.assert_array_equal(np.asarray(r_pair), r_host)


def test_hll_v2_string_registers_identical_to_v1_content():
    """String columns keep host xxhash64 + the u64 idx/rank derivation
    (packed into an i32 LUT): register CONTENT is identical to v1."""
    sv = np.array([f"s{i}" for i in range(1000)], dtype=object)
    lut = H.string_idx_rank_lut(sv, 9)
    i4, r4 = lut >> 6, lut & 63
    regs_v2 = H.registers_from_idx_rank(
        i4.astype(np.int64), r4.astype(np.int64),
        np.ones(len(lut), bool), 9, np,
    )
    regs_v1 = H.registers_from_hashes(
        H.hash_strings(sv), np.ones(1000, bool), 9, np
    )
    np.testing.assert_array_equal(np.asarray(regs_v2), np.asarray(regs_v1))


def test_hll_cross_version_merge_refused_and_serde_round_trips():
    from deequ_tpu.analyzers.sketches import ApproxCountDistinctState
    from deequ_tpu.states.serde import deserialize_state, serialize_state

    v2 = ApproxCountDistinctState((1, 2, 3))
    assert v2.hash_version == H.HASH_VERSION == 2
    legacy = ApproxCountDistinctState((1, 2, 3), hash_version=1)
    with pytest.raises(ValueError, match="different suites"):
        v2.sum(legacy)
    rt = deserialize_state(serialize_state(v2))
    assert rt == v2 and rt.hash_version == 2
    # pre-v4 blob (no trailing hash_version) decodes as suite v1
    old = bytes.fromhex(
        "44515453" "0300" "0a00" "0300000000000000" "010203"
    )
    st = deserialize_state(old)
    assert st.hash_version == 1
    with pytest.raises(ValueError, match="different suites"):
        v2.sum(st)


def test_hll_string_states_stay_suite_v1_and_merge_with_old_blobs():
    """String-column HLL content is identical to v1, so its state is
    stamped suite 1 and a pre-v4 persisted blob still merges; numeric
    states are suite 2."""
    from deequ_tpu.analyzers import ApproxCountDistinct
    from deequ_tpu.analyzers.runner import AnalysisRunner
    from deequ_tpu.data.table import Column, ColumnarTable, DType
    from deequ_tpu.states import InMemoryStateProvider

    dic = np.array([f"v{i}" for i in range(50)])
    codes = np.arange(50, dtype=np.int32) % 50
    t = ColumnarTable([
        Column("s", DType.STRING, codes=codes, dictionary=dic),
        Column("x", DType.FRACTIONAL, values=np.arange(50, dtype=float)),
    ])
    states = InMemoryStateProvider()
    a_s, a_x = ApproxCountDistinct("s"), ApproxCountDistinct("x")
    AnalysisRunner.do_analysis_run(t, [a_s, a_x], save_states_with=states)
    st_s = states.load(a_s)
    st_x = states.load(a_x)
    assert st_s.hash_version == 1
    assert st_x.hash_version == 2
    # a v1-suite blob (e.g. decoded from a pre-v4 file) merges with the
    # fresh string state
    merged = st_s.sum(type(st_s)(st_s.registers, hash_version=1))
    assert merged.registers == st_s.registers
