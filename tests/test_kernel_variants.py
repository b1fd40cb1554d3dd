"""Histogram kernel-variant tier suite (ops/histogram_device.py +
device_policy.resolve_hist_variant + the ScanPlan ``hist_variant`` seam).

Pins, against ``np.bincount`` as the reference:

- bit-exact parity of the one-hot-matmul and Pallas (interpret-mode)
  bincounts with the scatter baseline across dtypes, keyspace widths
  (including the one-hot block-boundary row counts and widths straddling
  the factored-radix split), empty segments, and null/invalid slots
  (negative sentinels AND the allocated trailing slot);
- integer-weighted segment-sum parity (the segment-fold form);
- policy resolution: CPU narrow-keyspace crossover, the row-count floor,
  accelerator cap, the DEEQU_TPU_HIST_VARIANT force knob (and its
  validation), and pallas never resolving without the knob;
- plan routing: a resident quantile scan forced onto each variant is
  bit-identical, keeps the zero-sort/one-fetch contracts, passes plan
  lint in error mode, and reports per-variant dispatch counts through
  ScanStats AND the obs registry's ``kernels`` section;
- the ``plan-hist-scatter`` lint rule firing on a simulated drift (a
  matmul-variant plan whose program still traces a scatter-add);
- the DEEQU_TPU_HOST_GROUP_LIMIT knob actually steering the grouping
  host-fallback threshold both directions;
- the abandoned-watchdog fetch-accounting guard (the historical
  oom_mid_fold cross-test device_fetches race).
"""

import threading
import time

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from deequ_tpu.analyzers import ApproxQuantile, Mean
from deequ_tpu.analyzers.runner import AnalysisRunner
from deequ_tpu.data.table import Column, ColumnarTable, DType
from deequ_tpu.envcfg import env_value
from deequ_tpu.exceptions import DeviceHangException, EnvConfigError
from deequ_tpu.ops.device_policy import (
    HIST_MIN_ROWS,
    HIST_ONEHOT_CPU_MAX_SEGMENTS,
    HIST_ONEHOT_MXU_MAX_SEGMENTS,
    resolve_hist_variant,
)
from deequ_tpu.ops.histogram_device import (
    HIST_VARIANTS,
    _onehot_geometry,
    active_hist_variant,
    bincount,
    bincount_onehot,
    bincount_variant,
    current_hist_variant,
)
from deequ_tpu.ops.scan_engine import SCAN_STATS, run_scan

pytestmark = pytest.mark.kernelv

VARIANTS = list(HIST_VARIANTS)


def _ref_bincount(seg: np.ndarray, m: int, weights=None) -> np.ndarray:
    """Host reference: counts over [0, m), everything else dropped."""
    keep = (seg >= 0) & (seg < m)
    if weights is None:
        return np.bincount(seg[keep], minlength=m)[:m].astype(np.int64)
    return np.bincount(
        seg[keep], weights=weights[keep], minlength=m
    )[:m].astype(np.int64)


# -- kernel parity -----------------------------------------------------------


@pytest.mark.parametrize("variant", VARIANTS)
@pytest.mark.parametrize(
    "n,m",
    [
        (0, 5),          # empty input
        (1, 1),          # single row, single segment
        (100, 7),        # negatives + tiny keyspace
        (4096, 16),      # exactly one one-hot block
        (4095, 33),      # one row short of the block boundary
        (4097, 33),      # one row past it (second block of 1)
        (8192, 300),     # width past the 128-lane radix (A > 2)
        (5000, 1 << 12), # square-ish factored split
    ],
)
def test_bincount_parity(variant, n, m):
    rng = np.random.default_rng(n * 31 + m)
    seg = rng.integers(-2, m, n).astype(np.int64)
    ref = _ref_bincount(seg, m)
    got = np.asarray(
        bincount_variant(variant, jnp.asarray(seg), m, jnp, dtype=jnp.int64)
    )
    np.testing.assert_array_equal(got, ref)


@pytest.mark.parametrize("variant", VARIANTS)
@pytest.mark.parametrize("dtype", [np.int32, np.int64])
def test_bincount_dtype_parity(variant, dtype):
    rng = np.random.default_rng(5)
    seg = rng.integers(0, 50, 3000).astype(dtype)
    ref = _ref_bincount(seg.astype(np.int64), 50)
    got = np.asarray(
        bincount_variant(variant, jnp.asarray(seg), 50, jnp, dtype=jnp.int64)
    )
    np.testing.assert_array_equal(got, ref)


@pytest.mark.parametrize("variant", VARIANTS)
def test_bincount_empty_segments_and_trailing_slot(variant):
    """Untouched segments stay zero; the engine's invalid-row idiom (an
    allocated trailing slot, sliced off by the caller) counts exactly."""
    m = 40
    seg = np.array([3, 3, 3, m - 1, m - 1], dtype=np.int64)
    got = np.asarray(
        bincount_variant(variant, jnp.asarray(seg), m, jnp, dtype=jnp.int64)
    )
    ref = np.zeros(m, dtype=np.int64)
    ref[3], ref[m - 1] = 3, 2
    np.testing.assert_array_equal(got, ref)


@pytest.mark.parametrize("variant", VARIANTS)
def test_weighted_segment_sum_parity(variant):
    rng = np.random.default_rng(9)
    seg = rng.integers(-1, 25, 2048).astype(np.int64)
    w = rng.integers(0, 7, 2048).astype(np.int64)
    ref = _ref_bincount(seg, 25, weights=w)
    got = np.asarray(
        bincount_variant(
            variant, jnp.asarray(seg), 25, jnp,
            weights=jnp.asarray(w), dtype=jnp.int64,
        )
    )
    np.testing.assert_array_equal(got, ref)


def test_weighted_onehot_exact_under_bf16_planes(monkeypatch):
    """Integer weights above bf16's 256-integer exact range stay exact
    even when the one-hot planes ride bf16 (the accelerator
    configuration, forced here on CPU): the weighted lo plane must
    widen to f32 before the multiply — a bf16 weight plane would round
    257 to 256 and silently break the exact-counts contract chip-side
    only, where the CPU parity suite never looks."""
    from deequ_tpu.ops import histogram_device as hd

    monkeypatch.setattr(hd, "_plane_dtype", lambda xp: xp.bfloat16)
    rng = np.random.default_rng(11)
    seg = rng.integers(-1, 9, 512).astype(np.int64)
    w = rng.integers(200, 5000, 512).astype(np.int64)
    ref = _ref_bincount(seg, 9, weights=w)
    got = np.asarray(
        bincount_variant(
            "onehot", jnp.asarray(seg), 9, jnp,
            weights=jnp.asarray(w), dtype=jnp.int64,
        )
    )
    np.testing.assert_array_equal(got, ref)


def test_onehot_block_boundary_exactness():
    """Row counts straddling the one-hot row-block boundary fold across
    blocks exactly (the f32-per-block / integer-cross-block invariant)."""
    m = 16
    _, _, block = _onehot_geometry(m)
    rng = np.random.default_rng(2)
    for n in (block - 1, block, block + 1, 2 * block + 3):
        seg = rng.integers(0, m, n).astype(np.int64)
        got = np.asarray(
            bincount_variant(
                "onehot", jnp.asarray(seg), m, jnp, dtype=jnp.int64
            )
        )
        np.testing.assert_array_equal(got, _ref_bincount(seg, m))


def test_bincount_inside_jit_all_variants():
    """Every variant traces inside jit (the position it occupies in the
    fused scan program) and stays exact."""
    rng = np.random.default_rng(3)
    seg = jnp.asarray(rng.integers(0, 12, 4096).astype(np.int32))
    ref = _ref_bincount(np.asarray(seg).astype(np.int64), 12)
    for variant in VARIANTS:
        fn = jax.jit(
            lambda s, v=variant: bincount_variant(v, s, 12, jnp, dtype=jnp.int32)
        )
        np.testing.assert_array_equal(np.asarray(fn(seg)).astype(np.int64), ref)


def test_host_numpy_path():
    seg = np.array([-1, 0, 2, 2, 9, 4], dtype=np.int64)
    got = bincount(seg, 5, np)
    np.testing.assert_array_equal(got, _ref_bincount(seg, 5))


# -- active-variant seam -----------------------------------------------------


def test_active_variant_binds_and_restores():
    assert current_hist_variant() == "scatter"
    with active_hist_variant("onehot"):
        assert current_hist_variant() == "onehot"
        with active_hist_variant("pallas"):
            assert current_hist_variant() == "pallas"
        assert current_hist_variant() == "onehot"
    assert current_hist_variant() == "scatter"


def test_active_variant_validates():
    with pytest.raises(ValueError, match="hist variant"):
        with active_hist_variant("mxu"):
            pass
    with pytest.raises(ValueError, match="hist variant"):
        bincount_variant("bogus", jnp.zeros(1, jnp.int32), 4, jnp)


# -- policy resolution -------------------------------------------------------


def test_policy_cpu_crossover():
    big = HIST_MIN_ROWS * 4
    assert resolve_hist_variant(
        (HIST_ONEHOT_CPU_MAX_SEGMENTS,), rows=big, platform="cpu"
    ) == "onehot"
    assert resolve_hist_variant(
        (HIST_ONEHOT_CPU_MAX_SEGMENTS + 1,), rows=big, platform="cpu"
    ) == "scatter"
    # the plan-level rule resolves over the WIDEST pass
    assert resolve_hist_variant(
        (8, HIST_ONEHOT_CPU_MAX_SEGMENTS * 4), rows=big, platform="cpu"
    ) == "scatter"


def test_policy_accelerator_cap():
    big = HIST_MIN_ROWS * 4
    assert resolve_hist_variant(
        (1 << 16,), rows=big, platform="tpu"
    ) == "onehot"
    assert resolve_hist_variant(
        (HIST_ONEHOT_MXU_MAX_SEGMENTS + 1,), rows=big, platform="tpu"
    ) == "scatter"


def test_policy_row_floor_and_unknown_rows():
    assert resolve_hist_variant(
        (16,), rows=HIST_MIN_ROWS - 1, platform="cpu"
    ) == "scatter"
    # rows=None means "large" (resident chunks)
    assert resolve_hist_variant((16,), rows=None, platform="cpu") == "onehot"


def test_policy_never_auto_pallas():
    """Pallas is force-knob-only (no measured width range yet, ROADMAP
    C2): no width/rows/platform combination resolves to it."""
    for platform in ("cpu", "tpu"):
        for width in (4, 1 << 16, 1 << 22):
            assert resolve_hist_variant(
                (width,), rows=1 << 22, platform=platform
            ) != "pallas"


def test_policy_force_knob(monkeypatch):
    monkeypatch.setenv("DEEQU_TPU_HIST_VARIANT", "pallas")
    assert resolve_hist_variant((1 << 22,), rows=10) == "pallas"
    monkeypatch.setenv("DEEQU_TPU_HIST_VARIANT", "onehot")
    assert resolve_hist_variant((1 << 22,), rows=10) == "onehot"
    monkeypatch.setenv("DEEQU_TPU_HIST_VARIANT", "mxu")
    with pytest.raises(EnvConfigError):
        env_value("DEEQU_TPU_HIST_VARIANT")
    with pytest.raises(ValueError):
        resolve_hist_variant((4,), force="mxu")


def test_policy_no_widths_is_scatter():
    assert resolve_hist_variant((), rows=1 << 20) == "scatter"


# -- plan routing through the engine ----------------------------------------


def _quantile_table(n=20_000, seed=7):
    rng = np.random.default_rng(seed)
    return ColumnarTable(
        [Column("v", DType.FRACTIONAL, values=rng.normal(0.0, 1.0, n))]
    )


def _run_resident_quantile(monkeypatch, force=None, plan_lint="off"):
    if force is None:
        monkeypatch.delenv("DEEQU_TPU_HIST_VARIANT", raising=False)
    else:
        monkeypatch.setenv("DEEQU_TPU_HIST_VARIANT", force)
    table = _quantile_table()
    table.persist()
    analyzers = [ApproxQuantile("v", 0.5, relative_error=0.05), Mean("v")]
    SCAN_STATS.reset()
    if plan_lint != "off":
        monkeypatch.setenv("DEEQU_TPU_PLAN_LINT", plan_lint)
    ctx = AnalysisRunner.do_analysis_run(table, analyzers)
    snap = SCAN_STATS.snapshot()
    metrics = {
        str(a): m.value.get() for a, m in ctx.metric_map.items()
    }
    return metrics, snap


@pytest.mark.parametrize("force", ["scatter", "onehot", "pallas"])
def test_resident_quantile_bit_identical_per_variant(monkeypatch, force):
    """Each forced variant produces the exact metrics of the unforced
    run, keeps the config-3 zero-sort contract AND the one-fetch
    contract, and the per-variant dispatch census names the routed
    kernel (three histogram passes per selection summary)."""
    base, base_snap = _run_resident_quantile(monkeypatch, None)
    got, snap = _run_resident_quantile(monkeypatch, force)
    assert got == base
    assert snap["device_sort_passes"] == 0
    assert snap["device_select_passes"] >= 1
    assert snap["device_fetches"] == 1
    assert snap[f"hist_{force}_dispatches"] == 3 * snap["device_select_passes"]
    for other in set(VARIANTS) - {force}:
        assert snap[f"hist_{other}_dispatches"] == 0


def test_resident_quantile_plan_lint_clean_per_variant(monkeypatch):
    """Plan lint in ERROR mode accepts every variant's traced program:
    the matmul/pallas variants really trace scatter-add-free histogram
    passes (the plan-hist-scatter rule armed at zero findings)."""
    for force in ("scatter", "onehot", "pallas"):
        metrics, snap = _run_resident_quantile(
            monkeypatch, force, plan_lint="error"
        )
        assert snap["device_select_passes"] >= 1
        assert not snap["plan_lints"], (force, snap["plan_lints"])


def test_plan_declares_hist_variant(monkeypatch):
    from deequ_tpu.analyzers.sketches import _kll_scan_op
    from deequ_tpu.ops.scan_engine import _ChunkPacker
    from deequ_tpu.ops.scan_plan import plan_scan_ops

    table = _quantile_table(4096)
    op = _kll_scan_op(table, "v", 256)
    packer = _ChunkPacker({"v": table["v"]}, 4096)
    monkeypatch.setenv("DEEQU_TPU_HIST_VARIANT", "onehot")
    plan = plan_scan_ops([op], packer, resident=True, rows=4096)
    assert plan.hist_variant == "onehot"
    assert plan.select_ops == 1
    # non-resident plans run no histogram passes at all
    monkeypatch.delenv("DEEQU_TPU_HIST_VARIANT")
    plan = plan_scan_ops([op], packer, resident=False, rows=4096)
    assert plan.hist_variant == "none"
    # unforced on CPU: the select widths (2^16+) exceed the CPU one-hot
    # crossover, so the default policy keeps the scatter baseline
    plan = plan_scan_ops([op], packer, resident=True, rows=4096)
    assert plan.hist_variant == "scatter"


def test_plan_hist_scatter_rule_fires():
    """Simulated drift: a plan claiming the one-hot tier whose program
    still traces a scatter-add is rejected pre-dispatch."""
    from dataclasses import replace

    from deequ_tpu.lint.plan_lint import lint_plan
    from deequ_tpu.ops.scan_plan import plan_scan_ops

    plan = replace(plan_scan_ops([]), hist_variant="onehot")

    def drifted(seg):
        return jnp.zeros((8,), jnp.int32).at[seg].add(1, mode="drop")

    findings = lint_plan(
        plan, drifted, (jax.ShapeDtypeStruct((16,), jnp.int32),)
    )
    assert any(f.rule == "plan-hist-scatter" for f in findings)
    assert all(
        f.severity == "error"
        for f in findings
        if f.rule == "plan-hist-scatter"
    )
    # the same program under an honest scatter declaration is clean
    honest = replace(plan, hist_variant="scatter")
    findings = lint_plan(
        honest, drifted, (jax.ShapeDtypeStruct((16,), jnp.int32),)
    )
    assert not any(f.rule == "plan-hist-scatter" for f in findings)


def test_grouping_counts_identical_across_variants(monkeypatch):
    """The grouping path (dense bincount + top-k off resident/host codes)
    produces identical states under every forced variant."""
    from deequ_tpu.ops.segment import group_counts_state, group_top_k

    rng = np.random.default_rng(11)
    card = 20
    codes = rng.integers(0, card, 1 << 15).astype(np.int32)
    dic = np.array([f"s{i:03d}" for i in range(card)], dtype=object)
    results = {}
    for force in VARIANTS:
        monkeypatch.setenv("DEEQU_TPU_HIST_VARIANT", force)
        table = ColumnarTable(
            [Column("k", DType.STRING, codes=codes, dictionary=dic)]
        )
        SCAN_STATS.reset()
        state = group_counts_state(table, ["k"])
        top = group_top_k(table, "k", 5)
        assert getattr(SCAN_STATS, f"hist_{force}_dispatches") >= 1, force
        results[force] = (
            state.as_dict(), state.num_rows, top.num_groups, tuple(top.top)
        )
    assert results["scatter"] == results["onehot"] == results["pallas"]


def test_registry_kernels_section(monkeypatch):
    from deequ_tpu.obs.registry import REGISTRY

    monkeypatch.setenv("DEEQU_TPU_HIST_VARIANT", "onehot")
    SCAN_STATS.reset()
    SCAN_STATS.record_hist_dispatch("onehot", 4)
    section = REGISTRY.snapshot()["kernels"]
    assert section["hist_onehot_dispatches"] == 4
    assert section["hist_scatter_dispatches"] == 0
    assert section["hist_variant_forced"] == "onehot"


# -- DEEQU_TPU_HOST_GROUP_LIMIT knob -----------------------------------------


def test_host_group_limit_knob_sweeps_threshold(monkeypatch):
    from deequ_tpu.ops.segment import _device_bincount, host_group_limit

    keys = np.array([0, 1, 1, 2, -1, 2, 2], dtype=np.int64)
    ref = np.array([1, 2, 3], dtype=np.int64)

    monkeypatch.setenv("DEEQU_TPU_HOST_GROUP_LIMIT", "1000000")
    assert host_group_limit() == 1_000_000
    SCAN_STATS.reset()
    np.testing.assert_array_equal(_device_bincount(keys, 3, None), ref)
    host_dispatches = (
        SCAN_STATS.hist_scatter_dispatches
        + SCAN_STATS.hist_onehot_dispatches
        + SCAN_STATS.hist_pallas_dispatches
    )
    assert host_dispatches == 0  # host latency regime: no device kernel

    monkeypatch.setenv("DEEQU_TPU_HOST_GROUP_LIMIT", "0")
    assert host_group_limit() == 0
    SCAN_STATS.reset()
    np.testing.assert_array_equal(_device_bincount(keys, 3, None), ref)
    device_dispatches = (
        SCAN_STATS.hist_scatter_dispatches
        + SCAN_STATS.hist_onehot_dispatches
        + SCAN_STATS.hist_pallas_dispatches
    )
    assert device_dispatches == 1  # swept to 0: the device kernel ran

    monkeypatch.delenv("DEEQU_TPU_HOST_GROUP_LIMIT")
    from deequ_tpu.ops import segment

    assert host_group_limit() == segment.HOST_GROUP_LIMIT

    monkeypatch.setenv("DEEQU_TPU_HOST_GROUP_LIMIT", "not-a-number")
    with pytest.raises(EnvConfigError):
        host_group_limit()


# -- abandoned-watchdog fetch accounting (the oom_mid_fold deflake) ----------


def test_abandoned_watchdog_fetch_is_dropped():
    """A watchdog call that times out (DeviceHangException) and LATER
    wakes up must not bump the fetch ledger mid-way through whatever
    run is active by then — the cross-test device_fetches race behind
    the historical oom_mid_fold tier-1 flake."""
    from deequ_tpu.ops.device_policy import _WATCHDOG_POOL

    SCAN_STATS.reset()
    woke = threading.Event()

    def hung_fetch():
        time.sleep(0.4)
        SCAN_STATS.record_fetch(128)
        woke.set()

    with pytest.raises(DeviceHangException):
        _WATCHDOG_POOL.call(hung_fetch, 0.05, "hung probe", "fetch")
    assert woke.wait(5.0)
    # synchronized read: the late fetch was dropped, not raced
    assert SCAN_STATS.snapshot()["device_fetches"] == 0


def test_healthy_watchdog_fetch_still_counts():
    from deequ_tpu.ops.device_policy import _WATCHDOG_POOL

    SCAN_STATS.reset()

    def quick_fetch():
        SCAN_STATS.record_fetch(64)
        return "ok"

    assert _WATCHDOG_POOL.call(quick_fetch, 5.0, "probe", "fetch") == "ok"
    assert SCAN_STATS.snapshot()["device_fetches"] == 1
    assert SCAN_STATS.snapshot()["bytes_fetched"] == 64


def test_run_scan_unaffected_by_forced_variants(monkeypatch):
    """A plain non-resident scan (sort path, no histogram passes) is
    oblivious to the force knob — the binding only wraps select
    updates."""
    table = _quantile_table(2048, seed=3)
    ops = [ApproxQuantile("v", 0.5).scan_op(table)]
    base = run_scan(table, ops)
    monkeypatch.setenv("DEEQU_TPU_HIST_VARIANT", "onehot")
    forced = run_scan(table, ops)
    for b, f in zip(jax.tree.leaves(base), jax.tree.leaves(forced)):
        np.testing.assert_array_equal(np.asarray(b), np.asarray(f))
    assert SCAN_STATS.hist_onehot_dispatches == 0


# -- the one-hot matmul under vmap (PR 21) ------------------------------------


def _batched_dot_generals(fn, *args):
    """dot_general equations of ``fn``'s jaxpr that carry batch dims."""
    from deequ_tpu.lint.plan_lint import iter_eqns

    jaxpr = jax.make_jaxpr(fn)(*args).jaxpr
    return [
        eqn for eqn in iter_eqns(jaxpr)
        if eqn.primitive.name == "dot_general"
        and any(eqn.params["dimension_numbers"][1])
    ]


@pytest.mark.parametrize("batch", [2, 8])
def test_onehot_bincount_maps_under_vmap_instead_of_batching(batch):
    """XLA:TPU miscompiles the BATCHED one-hot matmul (batch 8 returned
    all-zero counts for half the batch on the v5e, PR 21): a vmapped
    one-hot bincount must map the unbatched matmul over the batch, and
    still count exactly."""
    rng = np.random.default_rng(batch)
    width, n = 700, 5000
    seg = rng.integers(-1, width + 2, (batch, n)).astype(np.int32)
    fn = jax.vmap(lambda s: bincount_onehot(s, width, jnp))
    got = np.asarray(fn(jnp.asarray(seg)))
    for k in range(batch):
        ok = (seg[k] >= 0) & (seg[k] < width)
        assert (got[k] == np.bincount(seg[k][ok], minlength=width)).all(), k
    assert not _batched_dot_generals(fn, jnp.asarray(seg))


def test_hll_mxu_fold_maps_under_vmap_instead_of_batching():
    """The HLL register fold shares that matmul: the coalesced service
    vmaps it over tenants (ApproxCountDistinct came back 0 for 4 of 8
    tenants on the chip before the fold stopped batching its dot)."""
    from deequ_tpu.ops import hll

    rng = np.random.default_rng(0)
    batch, n, m = 8, 4096, 512
    idx = rng.integers(0, m, (batch, n)).astype(np.int32)
    rank = rng.integers(1, 40, (batch, n)).astype(np.int32)
    fn = jax.vmap(lambda i, r: hll._registers_mxu_fold(i, r, m, jnp))
    got = np.asarray(fn(jnp.asarray(idx), jnp.asarray(rank)))
    for k in range(batch):
        want = np.zeros(m, dtype=np.int64)
        np.maximum.at(want, idx[k], rank[k])
        assert (got[k] == want).all(), k
    assert not _batched_dot_generals(fn, jnp.asarray(idx), jnp.asarray(rank))
