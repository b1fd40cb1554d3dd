"""HLL++ accuracy and merge-algebra property tests."""

import numpy as np
import pytest

from deequ_tpu.analyzers import ApproxCountDistinct
from deequ_tpu.analyzers.sketches import ApproxCountDistinctState
from deequ_tpu.data.table import ColumnarTable
from deequ_tpu.ops import hll


def _estimate_for(values):
    t = ColumnarTable.from_pydict({"x": values})
    return ApproxCountDistinct("x").calculate(t).value.get()


@pytest.mark.parametrize("true_count", [10, 100, 1000, 20000])
def test_numeric_cardinality_accuracy(true_count):
    rng = np.random.default_rng(true_count)
    values = rng.choice(true_count * 10, true_count, replace=False).astype(float)
    repeated = np.tile(values, 3)
    rng.shuffle(repeated)
    est = _estimate_for(repeated.tolist())
    # default precision p=9 -> relative_sd ~0.046; allow 4 sigma + small-range slack
    assert abs(est - true_count) / true_count < 0.2, (true_count, est)


def test_string_cardinality_accuracy():
    values = [f"user-{i}" for i in range(5000)] * 2
    est = _estimate_for(values)
    assert abs(est - 5000) / 5000 < 0.2


def test_small_cardinalities_are_nearly_exact():
    for k in (1, 2, 5, 17):
        values = [float(i % k) for i in range(1000)]
        est = _estimate_for(values)
        assert abs(est - k) <= max(1, 0.05 * k), (k, est)


def test_register_merge_is_union():
    """Merging HLL states equals the state of the union of the data —
    the monoid law the distributed and incremental paths rely on."""
    a_vals = [float(i) for i in range(4000)]
    b_vals = [float(i) for i in range(2000, 6000)]

    def state_of(values):
        t = ColumnarTable.from_pydict({"x": values})
        analyzer = ApproxCountDistinct("x")
        return analyzer.compute_state_from(t)

    sa = state_of(a_vals)
    sb = state_of(b_vals)
    s_union = state_of(sorted(set(a_vals) | set(b_vals)))
    merged = sa.sum(sb)
    assert merged.registers == s_union.registers  # bitwise-exact merge
    assert abs(merged.metric_value() - 6000) / 6000 < 0.15


def test_merge_commutative_idempotent():
    t = ColumnarTable.from_pydict({"x": [float(i) for i in range(100)]})
    s = ApproxCountDistinct("x").compute_state_from(t)
    assert s.sum(s) == s  # idempotent
    t2 = ColumnarTable.from_pydict({"x": [float(i) for i in range(50, 150)]})
    s2 = ApproxCountDistinct("x").compute_state_from(t2)
    assert s.sum(s2) == s2.sum(s)  # commutative


def test_host_device_hash_consistency():
    """Host numpy and device jnp produce identical numeric hashes, so states
    computed on different platforms merge coherently."""
    import jax.numpy as jnp

    values = np.array([0.0, -0.0, 1.5, -273.15, 1e300, 12345.6789])
    host = hll.hash_numeric_device(values, np)
    device = np.asarray(hll.hash_numeric_device(jnp.asarray(values), jnp))
    assert host.tolist() == device.tolist()
    # -0.0 and +0.0 hash identically (canonicalization)
    assert host[0] == host[1]


def test_ertl_estimator_accuracy_across_range():
    """Relative error holds ~1.3/sqrt(m) across 100..1M cardinalities,
    including the classic 2.5m-5m band the raw+linear-counting estimator
    gets wrong without bias tables (round-1 review #6; reference
    StatefulHyperloglogPlus.scala:210-257)."""
    from deequ_tpu.ops import hll

    p = 9
    m = 1 << p
    bound = 1.3 / np.sqrt(m)

    def estimate(n, seed):
        rng = np.random.default_rng(seed)
        vals = np.unique(rng.integers(0, 1 << 62, n, dtype=np.uint64))
        h = hll.splitmix64(vals, np)
        regs = hll.registers_from_hashes(
            h, np.ones(len(h), dtype=bool), p, np
        )
        return hll.estimate_cardinality(np.asarray(regs))

    # mid band (2.5m..5m = 1280..2560 at p=9) — the regression target —
    # holds the tight bound; extremes allow 1.5/sqrt(m) (per-trial noise
    # at fixed seeds, not bias: the signed mean stays tight everywhere)
    cases = {
        100: (6, 1.5), 500: (6, 1.5),
        1280: (8, 1.3), 1600: (8, 1.3), 2000: (8, 1.3), 2560: (8, 1.3),
        5000: (6, 1.3), 50_000: (4, 1.5), 1_000_000: (6, 1.5),
    }
    for n, (trials, k) in cases.items():
        errs = [(estimate(n, 1000 + s) - n) / n for s in range(trials)]
        mean_abs = float(np.mean(np.abs(errs)))
        signed = float(np.mean(errs))
        assert mean_abs <= k / np.sqrt(m), (n, mean_abs, k)
        # no systematic bias: signed mean well inside the error bound
        assert abs(signed) <= bound, (n, signed, bound)


def test_mxu_fold_matches_segment_max():
    """The one-hot-matmul register fold (TPU path) must equal the
    scatter-max fold bit-for-bit; tested by calling the fold directly (the
    platform gate would otherwise keep it unreachable on the CPU suite)."""
    import jax.numpy as jnp

    from deequ_tpu.ops.hll import _MXU_FOLD_MIN_ROWS, _registers_mxu_fold

    rng = np.random.default_rng(5)
    n = _MXU_FOLD_MIN_ROWS + 12_345
    m = 512
    idx = jnp.asarray(rng.integers(0, m, n).astype(np.int32))
    # include rank 0 (invalid rows), sparse high ranks, and empty buckets
    rank = rng.integers(0, 4, n).astype(np.int32) * rng.integers(0, 2, n)
    rank[:50] = rng.integers(25, 57, 50)
    idx = idx.at[:100].set(0)
    rank = jnp.asarray(rank)

    import jax

    expected = np.zeros(m, np.int64)
    np.maximum.at(expected, np.asarray(idx), np.asarray(rank))
    got = np.asarray(_registers_mxu_fold(idx, rank, m, jnp))
    assert np.array_equal(got, expected.astype(np.int32))
