"""Benchmark harness — run on real TPU hardware by the driver.

Config: BASELINE.md #2 — profiler-style fused scan over 10M rows x 20
numeric columns (Completeness/Mean/StdDev/Min/Max per column + Size +
ApproxCountDistinct on 4 columns), all fused into ONE compiled device pass.

Prints ONE JSON line: {"metric", "value", "unit", "vs_baseline"}.

Baseline: the reference (deequ on Spark) publishes no numbers, and this
environment has no JVM, so Spark itself is unmeasurable here (BASELINE.md
round-4 section). The OFFICIAL denominator is therefore the MEASURED
single-vCPU numpy ceiling for the identical workload
(benchmarks/cpu_baseline.py; repeated runs on this 1-vCPU host measure
229k-384k rows/s depending on contention — the BEST, 384,443 rows/s, is
used, i.e. the most conservative TPU ratio): vs_baseline =
measured_rows_per_sec / 384_443 — both sides measured on this machine. The legacy Spark local[32] ESTIMATE (~1.0e6 rows/s, used
for vs_baseline through round 3) prints to stderr for continuity.
"""

import json
import sys
import time

import numpy as np

N_ROWS = 10_000_000
N_COLS = 20
# measured on this host by benchmarks/cpu_baseline.py (single vCPU,
# vectorized numpy over the identical 105-metric workload); best of
# repeated runs (range 229k-384k under host contention) — the most
# conservative denominator for the TPU ratio
CPU_MEASURED_ROWS_PER_SEC = 384_443.0
# legacy estimated denominator (rounds 1-3), kept for stderr continuity
SPARK_LOCAL32_ROWS_PER_SEC = 1.0e6
SMOKE_ROWS = 100_000


def build_table(n_rows: int = N_ROWS):
    from deequ_tpu.data.table import Column, ColumnarTable, DType

    rng = np.random.default_rng(7)
    cols = []
    for i in range(N_COLS):
        values = rng.normal(100.0 + i, 5.0, n_rows)
        mask = np.ones(n_rows, dtype=np.bool_)
        # sprinkle nulls so Completeness has work to do
        mask[rng.integers(0, n_rows, n_rows // 100)] = False
        cols.append(Column(f"c{i}", DType.FRACTIONAL, values=values, mask=mask))
    return ColumnarTable(cols)


def build_analyzers():
    from deequ_tpu.analyzers import (
        ApproxCountDistinct,
        Completeness,
        Maximum,
        Mean,
        Minimum,
        Size,
        StandardDeviation,
    )

    analyzers = [Size()]
    for i in range(N_COLS):
        c = f"c{i}"
        analyzers += [
            Completeness(c), Mean(c), StandardDeviation(c), Minimum(c), Maximum(c),
        ]
    analyzers += [ApproxCountDistinct(f"c{i}") for i in range(4)]
    return analyzers


def measure_checkpoint_overhead(n_rows: int):
    """Retry/checkpoint cost probe (resilience layer): the same streaming
    analysis timed plain vs checkpointed-every-4-batches, so the price of
    host-checkpointable folds shows up in BENCH_*.json as
    checkpoint_overhead_frac (fraction of plain wall added)."""
    import shutil
    import tempfile

    from deequ_tpu.analyzers import Completeness, Maximum, Mean, Minimum, Size
    from deequ_tpu.analyzers.runner import AnalysisRunner
    from deequ_tpu.data.streaming import stream_table
    from deequ_tpu.resilience import StreamCheckpointer

    table = build_table(n_rows)
    batch_rows = max(n_rows // 16, 1)
    analyzers = [Size()]
    for i in range(4):
        c = f"c{i}"
        analyzers += [Completeness(c), Mean(c), Minimum(c), Maximum(c)]

    def run(checkpoint=None):
        t0 = time.time()
        ctx = AnalysisRunner.do_analysis_run(
            stream_table(table, batch_rows),
            analyzers,
            checkpoint=checkpoint,
            # quarantine mode routes the plain run through the same
            # resilient loop, isolating the checkpoint WRITE cost from
            # the fold-path difference
            on_batch_error="skip",
        )
        wall = time.time() - t0
        assert all(m.value.is_success for m in ctx.all_metrics())
        return wall

    run()  # warmup: compile the per-batch fused program
    plain = min(run(), run())
    ckpt_dir = tempfile.mkdtemp(prefix="deequ_bench_ckpt_")
    try:
        # fresh checkpointer per rep so `saves` reports ONE run's count
        # (a completed run clears its directory, so reps don't resume)
        walls_saves = []
        for _ in range(2):
            ck = StreamCheckpointer(ckpt_dir, every_batches=4)
            walls_saves.append((run(ck), ck.saves))
        with_ckpt = min(w for w, _ in walls_saves)
        saves = walls_saves[0][1]
    finally:
        shutil.rmtree(ckpt_dir, ignore_errors=True)
    return {
        "checkpoint_overhead_frac": round(
            max(with_ckpt - plain, 0.0) / max(plain, 1e-9), 4
        ),
        "checkpoint_saves": saves,
    }


def measure_config3_selection(n_rows: int):
    """Config-3 probe (the 25-correlations + 50-quantile-columns shape of
    BASELINE config 3, scaled): the RESIDENT scan timed twice on the same
    harness run — histogram selection kernel (default) vs the batched
    device sort (DEEQU_TPU_SELECT_KERNEL=0) — so the recorded
    ``select_vs_sort_speedup`` compares the two quantile kernels on
    identical data and residency.

    Contract asserts (bench REFUSES to report config 3 on violation,
    like the one-fetch assert): the resident selection run must record
    ZERO device sort passes and at least one selection pass; the A/B
    sort run must record zero selection passes."""
    import os

    from deequ_tpu.analyzers.runner import AnalysisRunner
    from deequ_tpu.ops.scan_engine import SCAN_STATS

    # ONE workload definition, shared with run_configs.config3 so the
    # probe measures exactly the config it reports on
    sys.path.insert(
        0, os.path.join(os.path.dirname(os.path.abspath(__file__)), "benchmarks")
    )
    from run_configs import config3_workload, enforce_config3_contract

    table, analyzers = config3_workload(n_rows)
    try:
        table.persist()
    except MemoryError as e:
        # selection only routes on the RESIDENT path; without residency
        # there is nothing to contract-assert — skip the probe instead
        # of sinking the whole bench run (run_configs.config3 handles
        # the same case the same way)
        print(f"config-3 selection probe skipped: {e}", file=sys.stderr)
        return {
            "config3_select_rows_per_sec": None,
            "device_select_passes": None,
            "device_sort_passes": None,
            "sort_run_device_sort_passes": None,
            "select_vs_sort_speedup": None,
        }

    def run(select: bool):
        prev = os.environ.get("DEEQU_TPU_SELECT_KERNEL")
        os.environ["DEEQU_TPU_SELECT_KERNEL"] = "1" if select else "0"
        try:
            SCAN_STATS.reset()
            t0 = time.time()
            ctx = AnalysisRunner.do_analysis_run(table, analyzers)
            wall = time.time() - t0
        finally:
            if prev is None:
                os.environ.pop("DEEQU_TPU_SELECT_KERNEL", None)
            else:
                os.environ["DEEQU_TPU_SELECT_KERNEL"] = prev
        assert all(m.value.is_success for m in ctx.all_metrics())
        return wall, SCAN_STATS.snapshot()

    run(True)   # warmup/compile the selection program
    run(False)  # warmup/compile the sort program
    sel_wall, sel_snap = min(run(True), run(True), key=lambda r: r[0])
    sort_wall, sort_snap = min(run(False), run(False), key=lambda r: r[0])

    # the shared config-3 contract (one definition, run_configs.py;
    # select_enabled=True: the run() wrapper pinned the kernel on for
    # the selection reps); probe-local on top: the A/B sort run must
    # not have selected
    enforce_config3_contract(
        sel_snap, table.is_persisted, select_enabled=True
    )
    assert sort_snap["device_select_passes"] == 0, (
        "config-3 A/B violation: DEEQU_TPU_SELECT_KERNEL=0 still ran "
        "the selection kernel"
    )
    # both canonical counters come from the SELECTION run (matching
    # run_configs' emission semantics — zero sorts on a healthy resident
    # path); the A/B run's sort count gets its own name
    return {
        "config3_select_rows_per_sec": round(n_rows / max(sel_wall, 1e-9), 1),
        "device_select_passes": sel_snap["device_select_passes"],
        "device_sort_passes": sel_snap["device_sort_passes"],
        "sort_run_device_sort_passes": sort_snap["device_sort_passes"],
        "select_vs_sort_speedup": round(sort_wall / max(sel_wall, 1e-9), 3),
    }


def measure_kernel_ab(smoke: bool = False):
    """Histogram kernel-variant A/B probe (round 14,
    ops/histogram_device.py behind the ScanPlan ``hist_variant`` seam).

    Hard gates — the probe REFUSES to report (AssertionError) unless:

    - EXACTNESS: every variant (scatter / one-hot matmul / pallas
      interpret) reproduces ``np.bincount`` bit-for-bit on every probed
      shape, including null sentinels;
    - PLAN LINT: the resident quantile scan passes plan lint in ERROR
      mode under each forced variant (the plan-hist-scatter rule armed
      at zero findings) and stays bit-identical to the scatter baseline
      with ZERO device sort passes and ONE fetch (the config-3 contract
      pair under the new tier);
    - NO CPU REGRESSION: on every probed shape the DEFAULT policy's
      resolved kernel is within 25% of the scatter baseline (policy
      resolves scatter -> definitionally 0; the tolerance covers this
      container's documented +-10% single-pair A/B noise);
    - >=1.2x: the forced one-hot kernel beats scatter by >= 1.2x on at
      least one probed shape on THIS container (measured 5-8x at m=16
      on CPU — XLA's serial CPU scatter vs an sgemm).

    The chip-side >=2x acceptance (the MXU bf16 form vs the TPU scatter
    lowering, the ops/hll.py ~10x precedent) arms only on accelerator
    backends; CPU-only sessions bank it as ``pending-parallel-hw``,
    joining the config-3/4/5 banked list (rounds 6-10 were all
    CPU-only)."""
    import os
    from functools import partial

    import jax
    import jax.numpy as jnp

    from deequ_tpu.analyzers import ApproxQuantile, Mean
    from deequ_tpu.analyzers.runner import AnalysisRunner
    from deequ_tpu.data.table import Column, ColumnarTable, DType
    from deequ_tpu.obs.registry import REGISTRY
    from deequ_tpu.ops.device_policy import resolve_hist_variant
    from deequ_tpu.ops.histogram_device import bincount_variant
    from deequ_tpu.ops.scan_engine import SCAN_STATS

    on_cpu = jax.default_backend() == "cpu"
    rng = np.random.default_rng(14)

    # -- standalone kernel A/B over bincount shapes ----------------------
    shapes = [(1 << 16, 16), (1 << 18, 16)]
    if not smoke:
        shapes += [(1 << 20, 16), (1 << 18, 64)]

    def timed(fn, arg, reps=5):
        fn(arg).block_until_ready()
        best = float("inf")
        for _ in range(reps):
            t0 = time.perf_counter()
            fn(arg).block_until_ready()
            best = min(best, time.perf_counter() - t0)
        return best

    speedups = {}
    regression_frac = 0.0
    for n, m in shapes:
        seg_np = rng.integers(-1, m, n).astype(np.int64)
        ref = np.bincount(seg_np[seg_np >= 0], minlength=m)[:m]
        seg = jnp.asarray(seg_np)
        walls = {}
        for variant in ("scatter", "onehot"):
            fn = jax.jit(
                partial(
                    bincount_variant, variant,
                    num_segments=m, xp=jnp, dtype=jnp.int64,
                )
            )
            got = np.asarray(fn(seg))
            assert (got == ref).all(), (
                f"kernel A/B exactness violation: {variant} at "
                f"n={n} m={m} differs from np.bincount — refusing to "
                "report"
            )
            walls[variant] = timed(fn, seg)
        # pallas: interpret-mode correctness only (grid loops run in
        # python off-TPU — timing it would measure the interpreter)
        got = np.asarray(
            bincount_variant(
                "pallas", jnp.asarray(seg_np[: 1 << 12]), m, jnp,
                dtype=jnp.int64,
            )
        )
        pref = np.bincount(
            seg_np[: 1 << 12][seg_np[: 1 << 12] >= 0], minlength=m
        )[:m]
        assert (got == pref).all(), (
            f"kernel A/B exactness violation: pallas at m={m}"
        )
        label = f"n=2^{n.bit_length() - 1},m={m}"
        speedups[label] = round(
            walls["scatter"] / max(walls["onehot"], 1e-9), 2
        )
        # the default policy must never regress vs scatter: when it
        # resolves scatter the delta is definitionally zero, when it
        # resolves a routed kernel the routed wall must hold the line
        resolved = resolve_hist_variant((m,), rows=n)
        if resolved != "scatter":
            frac = (walls["onehot"] - walls["scatter"]) / max(
                walls["scatter"], 1e-9
            )
            regression_frac = max(regression_frac, frac)
    best_label = max(speedups, key=speedups.get)
    best_speedup = speedups[best_label]
    assert best_speedup >= 1.2, (
        f"kernel A/B gate violation: best one-hot speedup {best_speedup}x "
        f"< 1.2x across {speedups} — refusing to report"
    )
    assert regression_frac <= 0.25, (
        f"kernel A/B gate violation: default policy regresses "
        f"{regression_frac:.0%} vs the scatter baseline — refusing to "
        "report"
    )

    # -- engine integration: resident quantile scan per forced variant --
    q_rows = 16_384 if smoke else 50_000
    qrng = np.random.default_rng(3)
    table = ColumnarTable(
        [Column("v", DType.FRACTIONAL, values=qrng.normal(0, 1, q_rows))]
    )
    table.persist()
    analyzers = [ApproxQuantile("v", 0.5, relative_error=0.05), Mean("v")]

    def scan(force):
        prev = os.environ.get("DEEQU_TPU_HIST_VARIANT")
        prev_lint = os.environ.get("DEEQU_TPU_PLAN_LINT")
        if force is None:
            os.environ.pop("DEEQU_TPU_HIST_VARIANT", None)
        else:
            os.environ["DEEQU_TPU_HIST_VARIANT"] = force
        os.environ["DEEQU_TPU_PLAN_LINT"] = "error"
        try:
            SCAN_STATS.reset()
            ctx = AnalysisRunner.do_analysis_run(table, analyzers)
        finally:
            if prev_lint is None:
                os.environ.pop("DEEQU_TPU_PLAN_LINT", None)
            else:
                os.environ["DEEQU_TPU_PLAN_LINT"] = prev_lint
            if prev is None:
                os.environ.pop("DEEQU_TPU_HIST_VARIANT", None)
            else:
                os.environ["DEEQU_TPU_HIST_VARIANT"] = prev
        snap = SCAN_STATS.snapshot()
        metrics = {str(a): m.value.get() for a, m in ctx.metric_map.items()}
        return metrics, snap

    base_metrics, base_snap = scan("scatter")
    variants = ("onehot",) if smoke else ("onehot", "pallas")
    onehot_dispatches = 0
    for force in variants:
        metrics, snap = scan(force)
        assert metrics == base_metrics, (
            f"kernel A/B bit-identity violation under {force}: "
            f"{metrics} != {base_metrics} — refusing to report"
        )
        assert snap["device_sort_passes"] == 0, (
            f"zero-sort contract violation under {force}"
        )
        assert snap["device_select_passes"] >= 1, force
        assert snap["device_fetches"] == 1, (
            f"one-fetch contract violation under {force}: "
            f"{snap['device_fetches']} fetches"
        )
        assert not snap["plan_lints"], (force, snap["plan_lints"])
        # the per-variant dispatch census, read THROUGH the obs registry
        # (the "kernels" section is the probe's observable, not the raw
        # singleton)
        kernels = REGISTRY.snapshot()["kernels"]
        assert (
            kernels[f"hist_{force}_dispatches"]
            == 3 * snap["device_select_passes"]
        ), (force, kernels)
        if force == "onehot":
            onehot_dispatches = kernels["hist_onehot_dispatches"]

    # -- chip-side acceptance: >=2x on an accelerator, banked on CPU -----
    if on_cpu:
        chip_gate = "pending-parallel-hw"
    else:
        chip_gate = best_speedup
        assert best_speedup >= 2.0, (
            f"chip-side kernel gate violation: {best_speedup}x < 2x on "
            f"{jax.default_backend()} — refusing to report"
        )
    return {
        "kernel_ab_speedup_max": best_speedup,
        "kernel_ab_best_shape": best_label,
        "kernel_ab_speedups": speedups,
        "kernel_policy_regression_frac": round(regression_frac, 4),
        "kernel_ab_chip_gate": chip_gate,
        "kernel_hist_onehot_dispatches": onehot_dispatches,
        "kernel_variants_bit_identical": True,
    }


def measure_plan_fusion(n_rows: int = 1 << 16, n_tenants: int = 6):
    """Whole-run plan-optimizer probe (round 19, ops/segment
    ``fused_group_counts`` + serve/plan_cache ``SUBPLAN_CACHE`` + the
    ops/plan_cost admission pricing).

    Hard gates — the probe REFUSES to report (AssertionError) unless:

    - FUSION: a 3-grouping-pass suite under fusion makes ONE histogram
      dispatch with ONE counts fetch where ``DEEQU_TPU_PLAN_FUSION=0``
      makes three of each, and every metric is bit-identical between
      the two runs (exact float-bit compare);
    - SHARING: an overlapping-tenant mix (the same analyzer core
      submitted in permuted order per tenant) raises cache
      effectiveness ABOVE what exact-key hits alone give — every
      permuted suite misses its exact key yet adopts the shared
      sub-plan (``subplan_cache_hits`` == permuted submissions,
      ``programs_built`` == 1);
    - COST-PRICED ADMISSION: with the cost-drain rate trained,
      ``retry_after_s`` at the SAME queue depth is strictly larger for
      a heavier queued-cost mix — retries derive from predicted plan
      cost, not depth alone."""
    import os
    import struct

    from deequ_tpu.analyzers import Completeness, Mean, Minimum, Uniqueness
    from deequ_tpu.analyzers.runner import AnalysisRunner
    from deequ_tpu.data.table import Column, ColumnarTable, DType
    from deequ_tpu.obs.registry import REGISTRY
    from deequ_tpu.ops.plan_cost import PLAN_COST_MODEL
    from deequ_tpu.ops.scan_engine import SCAN_STATS
    from deequ_tpu.serve import VerificationService
    from deequ_tpu.serve.admission import AdmissionController
    from deequ_tpu.serve.plan_cache import SUBPLAN_CACHE

    rng = np.random.default_rng(19)

    # -- A: cross-pass fusion A/B over K=3 grouping passes ---------------
    table = ColumnarTable([
        Column("g1", DType.INTEGRAL,
               values=rng.integers(0, 1000, n_rows).astype(np.float64)),
        Column("g2", DType.INTEGRAL,
               values=rng.integers(0, 50, n_rows).astype(np.float64)),
        Column("g3", DType.INTEGRAL,
               values=rng.integers(0, 200, n_rows).astype(np.float64)),
    ])
    analyzers = [
        Uniqueness(("g1",)), Uniqueness(("g2",)), Uniqueness(("g3",)),
    ]

    def hist_dispatches(snap):
        return (
            snap["hist_scatter_dispatches"]
            + snap["hist_onehot_dispatches"]
            + snap["hist_pallas_dispatches"]
        )

    def run(fusion: str):
        prev = os.environ.get("DEEQU_TPU_PLAN_FUSION")
        os.environ["DEEQU_TPU_PLAN_FUSION"] = fusion
        try:
            SCAN_STATS.reset()
            t0 = time.time()
            ctx = AnalysisRunner.do_analysis_run(table, analyzers)
            wall = time.time() - t0
        finally:
            if prev is None:
                os.environ.pop("DEEQU_TPU_PLAN_FUSION", None)
            else:
                os.environ["DEEQU_TPU_PLAN_FUSION"] = prev
        metrics = {
            str(a): struct.pack("<d", m.value.get())
            for a, m in ctx.metric_map.items()
        }
        return metrics, SCAN_STATS.snapshot(), wall

    base_metrics, base_snap, base_wall = run("0")
    fused_metrics, fused_snap, fused_wall = run("1")
    assert fused_metrics == base_metrics, (
        "plan-fusion bit-identity violation — refusing to report"
    )
    assert hist_dispatches(base_snap) == len(analyzers), base_snap
    assert hist_dispatches(fused_snap) == 1, (
        f"fusion dispatch gate violation: {hist_dispatches(fused_snap)} "
        "dispatches for the fused 3-pass suite — refusing to report"
    )
    assert fused_snap["device_fetches"] < base_snap["device_fetches"], (
        "fusion fetch gate violation — refusing to report"
    )
    assert fused_snap["fused_group_passes"] == len(analyzers), fused_snap
    # the optimizer census reads THROUGH the obs registry section
    planner = REGISTRY.snapshot()["planner"]
    assert planner["fused_group_passes"] == len(analyzers), planner

    # -- B: cross-suite sub-plan sharing over an overlapping-tenant mix --
    SUBPLAN_CACHE.clear()
    SCAN_STATS.reset()
    core = [Completeness("x"), Mean("x"), Minimum("y")]
    small = ColumnarTable([
        Column("x", DType.FRACTIONAL, values=rng.normal(0, 1, 512)),
        Column("y", DType.FRACTIONAL, values=rng.normal(5, 2, 512)),
    ])
    orders = [
        [core[i % 3], core[(i + 1) % 3], core[(i + 2) % 3]]
        for i in range(n_tenants)
    ]
    svc = VerificationService(max_batch=1, coalesce_window=0.0)
    try:
        results = [
            svc.submit(
                small, required_analyzers=tuple(order), tenant=f"t{i}"
            ).result(timeout=120)
            for i, order in enumerate(orders)
        ]
    finally:
        svc.stop(drain=False)
    snap = SCAN_STATS.snapshot()
    distinct_orders = len({tuple(str(a) for a in o) for o in orders})
    # every permuted order past the first misses its exact key yet
    # adopts the shared sub-plan: sharing must beat exact hits alone
    assert snap["programs_built"] == 1, (
        f"sub-plan sharing gate violation: {snap['programs_built']} "
        "programs built for one shared analyzer core — refusing to report"
    )
    assert snap["subplan_cache_hits"] == distinct_orders - 1, snap
    assert snap["subplan_cache_hits"] > 0, "no sub-plan hits"
    exact_hits = snap["plan_cache_hits"] - snap["subplan_cache_hits"] * len(
        core
    )
    for a in core:
        vals = {
            res.metrics[a].value.get() for res in results
        }
        assert len(vals) == 1, (str(a), vals)

    # -- C: cost-priced retry_after ordering -----------------------------
    light = PLAN_COST_MODEL.estimate_suite([Completeness("x")], n_rows).total
    heavy = PLAN_COST_MODEL.estimate_suite(
        [Completeness("x"), Mean("x"), Uniqueness(("y",))], n_rows
    ).total
    ctl = AdmissionController(max_pending=64)
    for _ in range(4):
        ctl.note_served(1, 0.1, cost=light)
    retry_light = ctl.retry_after(3, queued_cost=3 * light)
    retry_heavy = ctl.retry_after(3, queued_cost=3 * heavy)
    assert retry_heavy > retry_light, (
        "cost-priced admission gate violation: same depth, heavier "
        "queued cost must schedule a later retry — refusing to report"
    )

    return {
        "plan_fusion_dispatch_reduction_x": round(
            hist_dispatches(base_snap) / hist_dispatches(fused_snap), 2
        ),
        "plan_fusion_fetches": (
            f"{fused_snap['device_fetches']} fused vs "
            f"{base_snap['device_fetches']} unfused"
        ),
        "plan_fusion_wall_speedup_x": round(
            base_wall / fused_wall, 2
        ) if fused_wall > 0 else float("inf"),
        "plan_fusion_bit_identical": True,
        "subplan_cache_hits": snap["subplan_cache_hits"],
        "subplan_programs_built": snap["programs_built"],
        "subplan_exact_hits_alone": max(int(exact_hits), 0),
        "cost_retry_light_s": round(retry_light, 4),
        "cost_retry_heavy_s": round(retry_heavy, 4),
        "cost_priced_admission": True,
    }


def measure_ingest_overlap(n_batches: int, batch_rows: int):
    """Columnar-ingest probe (round 8, the config-4/5 ingest-bound
    shape): ONE streaming analysis over ``n_batches`` dictionary-
    encodable Parquet files, A/B'd encoded vs raw staging
    (DEEQU_TPU_ENCODED_INGEST=0). Reports the host->device staging
    ledger (``bytes_staged``), the double-buffer's overlap fraction, and
    the encoded-vs-raw byte ratio.

    Contract asserts (the harness refuses to report the probe on
    violation, like the one-fetch and config-3 asserts): the streaming
    path must overlap staging with compute (``ingest_overlap_frac > 0``),
    encoded staging must ship >= 2x fewer bytes than raw on this
    dictionary-encodable workload, and both runs stay one-fetch."""
    import os
    import shutil
    import tempfile

    from deequ_tpu.analyzers import Completeness, Maximum, Mean, Minimum, Size
    from deequ_tpu.analyzers.runner import AnalysisRunner
    from deequ_tpu.data.io import stream_parquet, write_parquet
    from deequ_tpu.data.table import Column, ColumnarTable, DType
    from deequ_tpu.ops.scan_engine import SCAN_STATS

    rng = np.random.default_rng(88)
    workdir = tempfile.mkdtemp(prefix="deequ_bench_ingest_")
    analyzers = [Size(), Completeness("v"), Mean("v"), Minimum("v"), Maximum("v")]
    try:
        paths = []
        for b in range(n_batches):
            vals = (rng.integers(0, 512, batch_rows)).astype(np.float64) * 0.5
            mask = rng.random(batch_rows) > 0.05
            path = os.path.join(workdir, f"b{b:03d}.parquet")
            write_parquet(
                ColumnarTable(
                    [Column("v", DType.FRACTIONAL,
                            values=np.where(mask, vals, 0.0), mask=mask)]
                ),
                path,
            )
            paths.append(path)

        def run(encoded: bool):
            prev = os.environ.get("DEEQU_TPU_ENCODED_INGEST")
            os.environ["DEEQU_TPU_ENCODED_INGEST"] = "1" if encoded else "0"
            try:
                SCAN_STATS.reset()
                t0 = time.time()
                ctx = AnalysisRunner.do_analysis_run(
                    stream_parquet(paths, batch_rows=batch_rows), analyzers
                )
                wall = time.time() - t0
            finally:
                if prev is None:
                    os.environ.pop("DEEQU_TPU_ENCODED_INGEST", None)
                else:
                    os.environ["DEEQU_TPU_ENCODED_INGEST"] = prev
            assert all(m.value.is_success for m in ctx.all_metrics())
            return wall, SCAN_STATS.snapshot()

        run(True)   # warmup/compile the encoded streaming program
        run(False)  # warmup/compile the raw streaming program
        enc_wall, enc_snap = min(run(True), run(True), key=lambda r: r[0])
        raw_wall, raw_snap = min(run(False), run(False), key=lambda r: r[0])
    finally:
        shutil.rmtree(workdir, ignore_errors=True)

    total = n_batches * batch_rows
    assert enc_snap["ingest_overlap_frac"] > 0, (
        "ingest probe violation: the streaming path staged every chunk "
        "serially (ingest_overlap_frac == 0) — double buffering is dead"
    )
    assert enc_snap["bytes_staged"] * 2 <= raw_snap["bytes_staged"], (
        "ingest probe violation: encoded staging shipped "
        f"{enc_snap['bytes_staged']} bytes vs raw "
        f"{raw_snap['bytes_staged']} — the >= 2x reduction contract on "
        "dictionary-encodable columns is gone"
    )
    assert enc_snap["device_fetches"] == 1, (
        "one-fetch contract regression on the encoded streaming path"
    )
    assert raw_snap["device_fetches"] == 1, (
        "one-fetch contract regression on the raw streaming path"
    )
    return {
        "ingest_stream_rows_per_sec": round(total / max(enc_wall, 1e-9), 1),
        "ingest_overlap_frac": enc_snap["ingest_overlap_frac"],
        "bytes_staged_encoded": enc_snap["bytes_staged"],
        "bytes_staged_raw": raw_snap["bytes_staged"],
        "encoded_vs_raw_bytes": round(
            raw_snap["bytes_staged"] / max(enc_snap["bytes_staged"], 1), 3
        ),
        "encoded_vs_raw_speedup": round(raw_wall / max(enc_wall, 1e-9), 3),
        "ingest_effective_mb_per_sec": round(
            enc_snap["bytes_staged"] / max(enc_wall, 1e-9) / 1e6, 2
        ),
    }


def measure_plan_lint_overhead(table, analyzers):
    """Static plan-lint cost probe (deequ_tpu/lint) on the resident
    profile scan already warmed by the main bench: ``plan_lint_overhead_ms``
    is the wall added by the FIRST linted scan (which pays the one-time
    jaxpr trace + rule checks) over an unlinted scan of the same warmed
    program. The memoization contract is hard-asserted: a second linted
    scan of an identical plan must perform ZERO additional lint traces
    (``SCAN_STATS.plan_lint_traces``) — the lint result rides the
    program cache identity, so enforcement is one trace per
    (plan, kernel-variant), not per scan."""
    import os

    from deequ_tpu.analyzers.runner import AnalysisRunner
    from deequ_tpu.lint.plan_lint import clear_lint_memo
    from deequ_tpu.ops.scan_engine import SCAN_STATS

    def run():
        SCAN_STATS.reset()
        t0 = time.time()
        ctx = AnalysisRunner.do_analysis_run(table, analyzers)
        wall = time.time() - t0
        assert all(m.value.is_success for m in ctx.all_metrics())
        return wall, SCAN_STATS.plan_lint_traces, SCAN_STATS.plan_lints

    prev = os.environ.get("DEEQU_TPU_PLAN_LINT")
    try:
        os.environ["DEEQU_TPU_PLAN_LINT"] = "off"
        base, _, _ = run()
        os.environ["DEEQU_TPU_PLAN_LINT"] = "error"
        clear_lint_memo()
        first, traces_first, lints = run()
        assert traces_first >= 1, "plan lint armed but no lint trace ran"
        assert lints == [], f"resident profile scan has lint findings: {lints}"
        second, traces_second, _ = run()
        assert traces_second == 0, (
            "plan-lint memoization regression: a second scan of an "
            f"identical plan performed {traces_second} additional lint "
            "trace(s)"
        )
    finally:
        if prev is None:
            os.environ.pop("DEEQU_TPU_PLAN_LINT", None)
        else:
            os.environ["DEEQU_TPU_PLAN_LINT"] = prev
    return {
        "plan_lint_overhead_ms": round(max(first - base, 0.0) * 1000, 2),
        "plan_lint_memoized_overhead_ms": round(
            max(second - base, 0.0) * 1000, 2
        ),
        "plan_lint_traces_first_scan": traces_first,
    }


def _config1_suites(n_rows: int):
    """The config-1 probe shape shared by the governance and obs
    overhead probes: one table, 17 analyzers, a ``run_suites()`` that
    times 4 back-to-back runs."""
    from deequ_tpu.analyzers import Completeness, Maximum, Mean, Minimum, Size
    from deequ_tpu.analyzers.runner import AnalysisRunner

    table = build_table(n_rows)
    analyzers = [Size()]
    for i in range(4):
        c = f"c{i}"
        analyzers += [Completeness(c), Mean(c), Minimum(c), Maximum(c)]
    suites_per_rep = 4

    def run_suites():
        t0 = time.time()
        for _ in range(suites_per_rep):
            ctx = AnalysisRunner.do_analysis_run(table, analyzers)
        wall = time.time() - t0
        assert all(m.value.is_success for m in ctx.all_metrics())
        return wall

    return run_suites


def _stable_overhead_frac(plain_fn, treated_fn, gate: float, what: str):
    """Overhead measurement hardened for 1-vCPU containers (the
    measure_governance_overhead flake, pre-round-11): scheduler noise
    there is BIMODAL — a rep that loses its timeslice mid-run reads as
    5-10% 'overhead' on either side, so min-of-reps across sides still
    trips the gate a few runs in a hundred. Discipline now:

    - per TRIAL, 3 interleaved plain/treated pairs; the trial's frac is
      computed from the MIN wall of each side (a descheduled rep
      vanishes into the other two; interleaving means drift hits both
      sides alike — single-pair fracs measured ±10% on this container,
      far above the 1% gate);
    - the probe's verdict is the MEDIAN of 5 such trials (a noise burst
      spanning a whole trial lands in the tail, not the median);
    - one DISCARD-AND-RETRY pass before the gate fires: a median over
      the gate re-measures 5 fresh trials once (a burst spanning most
      of a 5-trial window passes; a real regression fails twice).

    A sustained-load tail remains (a busy container can keep EVERY
    treated rep 3-5% 'slow' for seconds at a stretch), so the verdict
    also admits the BEST-OF-ALL-REPS floor: the frac between the
    fastest treated and fastest plain wall across every rep measured.
    Noise cannot make that floor large (15+ reps per side see at least
    one clean window each), while a real regression inflates every
    treated rep — floor included.

    Last resort (round 17): if even the floor trips the gate, decide
    whether the SCHEDULER was starved before blaming the treatment.
    Two independent starvation signatures, either of which converts the
    failure into a typed SKIP verdict (``(None, reason)`` — the reason
    lands in the bench record):

    - CLEAN WINDOW: some trial measured a frac under the gate. Each
      trial interleaves its pairs in one tight time window, so a real
      gate-sized regression inflates EVERY trial's treated min — a
      near-zero trial proves the treatment can be free and the
      over-gate median is load that the floor's global minima happened
      to straddle;
    - SAME-SIDE SPREAD: the same code on the same data spreading
      (max-min)/min beyond ``max(10*gate, 0.10)`` across its own
      per-trial walls — the measurement cannot resolve a gate-sized
      effect at all.

    An actual regression on a healthy container still fails: steady
    timeslices keep every trial frac over the gate and the same-side
    spread tight while every treated rep stays inflated.

    Returns ``(frac, None)`` and asserts ``frac < gate`` on a
    resolvable measurement; ``(None, reason)`` on a starved one."""
    all_plain: list = []
    all_treated: list = []
    all_fracs: list = []

    def median_frac():
        fracs = []
        for _ in range(5):
            plain = float("inf")
            treated = float("inf")
            for _ in range(3):
                plain = min(plain, plain_fn())
                treated = min(treated, treated_fn())
            all_plain.append(plain)
            all_treated.append(treated)
            fracs.append(max(treated - plain, 0.0) / max(plain, 1e-9))
        all_fracs.extend(fracs)
        fracs.sort()
        return fracs[2], fracs

    def floor_frac():
        best_plain = min(all_plain)
        return max(min(all_treated) - best_plain, 0.0) / max(
            best_plain, 1e-9
        )

    frac, trials = median_frac()
    if min(frac, floor_frac()) >= gate:
        print(
            f"{what}: median {frac:.4f} >= {gate:g} "
            f"(trials={['%.4f' % f for f in trials]}) — discarding and "
            "retrying once (bimodal scheduler noise on small containers)",
            file=sys.stderr,
        )
        retry, trials = median_frac()
        # the verdict is the BETTER of the two medians: a noise burst
        # spanning one whole 5-trial window passes on the clean window,
        # while a real regression measures over the gate in both
        frac = min(frac, retry)
    frac = min(frac, floor_frac())
    if frac >= gate:
        spreads = {
            side: (max(walls) - min(walls)) / max(min(walls), 1e-9)
            for side, walls in (
                ("plain", all_plain), ("treated", all_treated),
            )
        }
        starved_at = max(10 * gate, 0.10)
        reason = None
        if min(all_fracs) < gate:
            reason = (
                f"starved scheduler (bimodal): a clean trial measured "
                f"{min(all_fracs):.4f} < {gate:g} while the median read "
                f"{frac:.4f} — the treatment can be free, the container "
                "cannot hold a timeslice"
            )
        elif max(spreads.values()) > starved_at:
            reason = (
                f"starved scheduler: same-side spread "
                f"plain={spreads['plain']:.3f} "
                f"treated={spreads['treated']:.3f} > {starved_at:g} — "
                f"a {gate:g} effect is unresolvable on this container"
            )
        if reason is not None:
            print(f"{what}: SKIP — {reason}", file=sys.stderr)
            return None, reason
    assert frac < gate, (
        f"{what} overhead {frac:.4f} >= {gate:g} of healthy wall after "
        f"discard-and-retry (trials={['%.4f' % f for f in trials]})"
    )
    return frac, None


def measure_governance_overhead(n_rows: int):
    """Run-governance cost probe (resilience/governance.py): the
    config-1 shape — several small/medium suites back to back — timed
    ungoverned vs under an armed RunBudget (wall deadline + attempt
    cap, both far from binding). The healthy path must charge NOTHING
    (hard-asserted via ``ScanStats.budget_charges``) and cost <1% of
    wall: budget resolution is two dict lookups per run, and the
    remaining-wall watchdog cap is one subtraction per scan attempt.
    Noise discipline: median-of-5 interleaved trials with one
    discard-and-retry pass (``_stable_overhead_frac``)."""
    from deequ_tpu.ops.scan_engine import SCAN_STATS
    from deequ_tpu.resilience.governance import RunPolicy, run_budget_scope

    run_suites = _config1_suites(n_rows)

    def governed():
        budget = RunPolicy(
            run_deadline=600.0, max_total_attempts=1 << 16
        ).arm()
        with run_budget_scope(budget):
            wall = run_suites()
        assert budget.attempts == 0, (
            f"healthy run charged the budget: {budget.charges}"
        )
        return wall

    run_suites()  # warmup: compile the fused program
    charges_before = SCAN_STATS.budget_charges
    frac, skip = _stable_overhead_frac(
        run_suites, governed, gate=0.01, what="governance"
    )
    assert SCAN_STATS.budget_charges == charges_before, (
        "healthy-path scans must not charge the budget ledger"
    )
    if skip is not None:
        return {
            "governance_overhead_frac": None,
            "governance_overhead_skipped": skip,
        }
    return {
        "governance_overhead_frac": round(frac, 4),
    }


def measure_oom_bisection_overhead(n_rows: int):
    """Device-fault degradation cost probe: the same in-memory analysis
    timed clean vs with a seeded device OOM injected on its first attempt
    (forcing one chunk bisection — the scan restarts at half the chunk).
    oom_bisection_overhead_frac = fraction of clean wall the bisected run
    adds; the price of surviving an HBM OOM instead of dying on it."""
    from deequ_tpu.analyzers import Completeness, Maximum, Mean, Minimum, Size
    from deequ_tpu.analyzers.runner import AnalysisRunner
    from deequ_tpu.ops.device_policy import DEVICE_HEALTH
    from deequ_tpu.ops.scan_engine import SCAN_STATS, install_scan_fault_hook
    from deequ_tpu.resilience import FaultInjectingScanHook

    table = build_table(n_rows)
    analyzers = [Size()]
    for i in range(4):
        c = f"c{i}"
        analyzers += [Completeness(c), Mean(c), Minimum(c), Maximum(c)]

    def run(hook=None):
        prev = install_scan_fault_hook(hook)
        DEVICE_HEALTH.reset()
        t0 = time.time()
        try:
            ctx = AnalysisRunner.do_analysis_run(table, analyzers)
        finally:
            install_scan_fault_hook(prev)
        wall = time.time() - t0
        assert all(m.value.is_success for m in ctx.all_metrics())
        return wall

    run()  # warmup: compile the fused program
    clean = min(run(), run())
    SCAN_STATS.reset()
    bisected = min(
        run(FaultInjectingScanHook(faults={0: ("oom", 1)})),
        run(FaultInjectingScanHook(faults={0: ("oom", 1)})),
    )
    assert SCAN_STATS.oom_bisections >= 1, "probe failed to trigger bisection"
    return {
        "oom_bisection_overhead_frac": round(
            max(bisected - clean, 0.0) / max(clean, 1e-9), 4
        ),
    }


def measure_reshard_overhead(n_rows: int):
    """Mesh-fault degradation cost probe (requires >= 2 devices): the
    same in-memory analysis timed (a) clean on the full N-device mesh,
    (b) with a scripted chip loss on its first attempt — the scan
    reshards onto N-1 devices mid-flight — and (c) healthy on an N-1
    mesh. reshard_overhead_frac is the one-time recovery cost vs the
    clean wall; degraded_mesh_rows_per_sec is the steady-state N-1
    throughput — what a chip loss actually costs next to the
    healthy-mesh number."""
    from deequ_tpu.analyzers import Completeness, Maximum, Mean, Minimum, Size
    from deequ_tpu.analyzers.runner import AnalysisRunner
    from deequ_tpu.ops.device_policy import DEVICE_HEALTH, MESH_HEALTH
    from deequ_tpu.ops.scan_engine import SCAN_STATS, install_scan_fault_hook
    from deequ_tpu.parallel.mesh import (
        current_mesh,
        mesh_device_ids,
        mesh_excluding,
        use_mesh,
    )
    from deequ_tpu.resilience import FaultInjectingScanHook, FaultSchedule

    mesh = current_mesh()
    ids = mesh_device_ids(mesh)
    if len(ids) < 2:
        print(
            "reshard probe skipped: needs >= 2 devices", file=sys.stderr
        )
        return {
            "reshard_overhead_frac": None,
            "degraded_mesh_rows_per_sec": None,
        }
    lost_id = ids[-1]

    table = build_table(n_rows)
    analyzers = [Size()]
    for i in range(4):
        c = f"c{i}"
        analyzers += [Completeness(c), Mean(c), Minimum(c), Maximum(c)]

    def run(hook=None):
        prev = install_scan_fault_hook(hook)
        DEVICE_HEALTH.reset()
        MESH_HEALTH.reset()  # each rep must reshard live, not pre-shrink
        t0 = time.time()
        try:
            ctx = AnalysisRunner.do_analysis_run(table, analyzers)
        finally:
            install_scan_fault_hook(prev)
        wall = time.time() - t0
        assert all(m.value.is_success for m in ctx.all_metrics())
        return wall

    run()  # warmup: compile the fused program on the full mesh
    clean = min(run(), run())
    SCAN_STATS.reset()
    resharded = min(
        run(FaultInjectingScanHook(
            faults={0: ("lost", FaultSchedule.PERMANENT, lost_id)}
        )),
        run(FaultInjectingScanHook(
            faults={0: ("lost", FaultSchedule.PERMANENT, lost_id)}
        )),
    )
    assert SCAN_STATS.mesh_reshards >= 2, "probe failed to trigger reshard"
    assert SCAN_STATS.fallback_scans == 0, "probe fell back to CPU"
    MESH_HEALTH.reset()
    with use_mesh(mesh_excluding(mesh, {lost_id})):
        run()  # warmup: the N-1 program is a fresh compile
        degraded = min(run(), run())
    return {
        "reshard_overhead_frac": round(
            max(resharded - clean, 0.0) / max(clean, 1e-9), 4
        ),
        "degraded_mesh_rows_per_sec": round(n_rows / max(degraded, 1e-9), 1),
    }


def measure_serving_load(n_tenants: int, rows_per_tenant: int = 256):
    """Serving-layer probe (round 10, deequ_tpu/serve — the config-1
    millions-of-users shape): a synthetic ``n_tenants``-tenant OPEN-LOOP
    load of small verification suites over a mix of REPEAT schemas (a
    handful of suite shapes shared by many tenants — the plan-cache hot
    path) and FRESH schemas (unique per tenant — the build path),
    submitted all-at-once to a :class:`VerificationService` and served
    coalesced. Reports sustained suites/sec, p50/p99 submit->resolve
    latency, the plan-cache hit rate, and coalesced batch occupancy.

    Contract asserts (the probe REFUSES to report on violation, like the
    one-fetch and config-3 asserts):

    - BIT-IDENTITY: every sampled tenant's coalesced metrics equal its
      serial per-tenant ``VerificationSuite`` run bit-for-bit;
    - REPEAT-TENANT ZERO TRACES: with plan lint armed, a repeat suite
      after warmup adds zero ``programs_built`` and zero
      ``plan_lint_traces`` and counts a ``plan_cache_hit``;
    - ONE FETCH PER COALESCED BATCH: the load's device-fetch delta
      equals its coalesced-batch delta exactly;
    - >= 5x: sustained coalesced suites/sec over the serial
      submit-per-run baseline (direct ``VerificationSuite.run`` per
      tenant — what a caller without the serving layer does) measured
      on the same harness, tables, and suites."""
    import struct

    from deequ_tpu import Check, CheckLevel, VerificationSuite
    from deequ_tpu.data.table import Column, ColumnarTable, DType
    from deequ_tpu.ops.scan_engine import SCAN_STATS
    from deequ_tpu.parallel.mesh import use_mesh
    from deequ_tpu.serve import VerificationService

    from deequ_tpu.obs.registry import SERVE_LATENCY

    # clean histogram window: the emitted p50/p95/p99 snapshot covers
    # THIS probe's submissions (the registry instrument is process-wide)
    SERVE_LATENCY.reset()

    rng = np.random.default_rng(17)
    REPEAT_SHAPES = 8  # distinct suite shapes shared by repeat tenants
    FRESH_FRAC = 0.02  # tenants with a one-off schema (plan builds)

    def tenant_table(shape: int, seed: int, fresh_id=None):
        r = np.random.default_rng(seed)
        n = rows_per_tenant
        cols = [
            Column("x", DType.FRACTIONAL, values=r.normal(100 + shape, 5, n),
                   mask=r.random(n) > 0.05),
            Column("i", DType.INTEGRAL,
                   values=r.integers(0, 40 + shape, n).astype(np.float64),
                   mask=np.ones(n, dtype=np.bool_)),
        ]
        if fresh_id is not None:
            # a fresh schema: a uniquely named extra column the suite
            # reads, so the plan fingerprint cannot collide
            cols.append(Column(
                f"f{fresh_id}", DType.FRACTIONAL,
                values=r.normal(0, 1, n), mask=np.ones(n, dtype=np.bool_),
            ))
        return ColumnarTable(cols)

    def tenant_check(shape: int, fresh_id=None):
        check = (
            Check(CheckLevel.ERROR, f"suite-{shape}")
            .has_size(lambda n: n == rows_per_tenant)
            .is_complete("i")
            .has_completeness("x", lambda c: c > 0.5)
            .has_mean("x", lambda m, s=shape: 90 + s < m < 110 + s)
        )
        if fresh_id is not None:
            check = check.has_completeness(f"f{fresh_id}", lambda c: c == 1.0)
        return check

    n_fresh = max(1, int(n_tenants * FRESH_FRAC))
    load = []  # (tenant, table, checks)
    for t in range(n_tenants):
        if t < n_fresh:
            load.append((f"fresh-{t}", tenant_table(0, 1000 + t, t),
                         [tenant_check(0, t)]))
        else:
            shape = t % REPEAT_SHAPES
            load.append((f"tenant-{t}", tenant_table(shape, t),
                         [tenant_check(shape)]))

    sample = load[:: max(1, n_tenants // 32)]  # bit-identity sample

    def bits(v):
        return struct.pack("<d", v) if isinstance(v, float) else v

    with use_mesh(None):
        # serial submit-per-run baseline on the same harness: one direct
        # engine run per tenant. Run the slice twice and time the second
        # pass — the STEADY-STATE cost (programs compiled), the same
        # footing the sustained serving pass is gated on; an XLA compile
        # costs ~0.3s on either side and would otherwise measure the
        # compiler, not the serving layer.
        # 64 runs bound the baseline's wall while staying a stable
        # denominator on fast hosts
        baseline_slice = load[: min(64, n_tenants)]
        for _, table, checks in baseline_slice:
            VerificationSuite.run(table, checks)  # warm every program
        serial_wall = float("inf")
        for _ in range(3):  # min-of-reps, same as the sustained side
            t0 = time.time()
            for _, table, checks in baseline_slice:
                VerificationSuite.run(table, checks)
            serial_wall = min(serial_wall, time.time() - t0)
        serial_persec = len(baseline_slice) / serial_wall

        serial_sample = {
            tenant: VerificationSuite.run(table, checks)
            for tenant, table, checks in sample
        }

        # max_batch 256: the open-loop queue mixes REPEAT_SHAPES suite
        # shapes, so a drained batch splits into per-plan groups of
        # batch/shapes members — 256 keeps per-shape groups ~32 wide
        service = VerificationService(plan_lint="error", max_batch=256)
        try:
            def run_pass():
                t0 = time.time()
                futures = [
                    service.submit(table, checks, tenant=tenant)
                    for tenant, table, checks in load
                ]
                results = {
                    tenant: f.result(timeout=600)
                    for (tenant, _, _), f in zip(load, futures)
                }
                return time.time() - t0, futures, results

            # PASS 1 — cold: the mixed repeat/fresh load pays its plan
            # builds, program traces, and lint traces here; its cache
            # ledger is the reported hit rate for the mixed load
            cold_before = SCAN_STATS.snapshot()
            cold_wall, _, _ = run_pass()
            cold_after = SCAN_STATS.snapshot()

            # PASS 2/3 — sustained: every schema of the load is now
            # cached; this is the steady-state serving rate the >=5x
            # contract gates (fresh schemas of pass 1 are repeat
            # tenants by now — exactly the Flare amortization claim).
            # Min of three reps, the file's standard noise discipline.
            wall = float("inf")
            futures = results = None
            before = after = None
            for _ in range(3):
                rep_before = SCAN_STATS.snapshot()
                rep_wall, rep_futures, rep_results = run_pass()
                rep_after = SCAN_STATS.snapshot()
                if rep_wall < wall:
                    wall = rep_wall
                    futures, results = rep_futures, rep_results
                    before, after = rep_before, rep_after

            # repeat-tenant zero-trace contract (plan lint ARMED): the
            # SECOND identical lone suite must be a pure hit. The first
            # lone submit may trace the 1-wide tenant bucket (buckets
            # are program shapes; the load ran wider batches) — that is
            # the "first run" the contract's "second identical suite"
            # is measured against.
            service.submit(
                tenant_table(1, 8887), [tenant_check(1)],
                tenant="repeat-probe",
            ).result(timeout=120)
            built = SCAN_STATS.programs_built
            lint_traces = SCAN_STATS.plan_lint_traces
            hits = SCAN_STATS.plan_cache_hits
            service.submit(
                tenant_table(1, 8888), [tenant_check(1)],
                tenant="repeat-probe",
            ).result(timeout=120)
            assert SCAN_STATS.programs_built == built, (
                "serving violation: a repeat-tenant suite re-traced its "
                "program (the compiled-plan cache missed)"
            )
            assert SCAN_STATS.plan_lint_traces == lint_traces, (
                "serving violation: a repeat-tenant suite re-traced the "
                "plan lint"
            )
            assert SCAN_STATS.plan_cache_hits == hits + 1, (
                "serving violation: repeat-tenant suite did not count a "
                "plan-cache hit"
            )
        finally:
            service.stop(drain=False)

    # bit-identity: sampled tenants' coalesced results == serial runs
    for tenant, _, _ in sample:
        s, c = serial_sample[tenant], results[tenant]
        assert str(s.status) == str(c.status), (
            f"serving violation: {tenant} status {c.status} != serial "
            f"{s.status}"
        )
        for a, m1 in s.metrics.items():
            m2 = c.metrics[a]
            assert m1.value.is_success and m2.value.is_success, (tenant, a)
            assert bits(m1.value.get()) == bits(m2.value.get()), (
                f"serving violation: {tenant} {a} coalesced "
                f"{m2.value.get()!r} != serial {m1.value.get()!r} — "
                "coalesced results must be BIT-identical to per-tenant "
                "serial runs"
            )

    batches = after["coalesced_batches"] - before["coalesced_batches"]
    tenants_served = after["coalesced_tenants"] - before["coalesced_tenants"]
    padded = after["coalesce_padded_slots"] - before["coalesce_padded_slots"]
    fetches = after["device_fetches"] - before["device_fetches"]
    assert tenants_served == n_tenants, (
        f"serving violation: {n_tenants - tenants_served} of the load's "
        "suites did not ride a coalesced dispatch"
    )
    assert fetches == batches, (
        f"serving violation: {fetches} device fetches for {batches} "
        "coalesced batches — the one-fetch-per-batch contract is gone"
    )
    suites_persec = n_tenants / max(wall, 1e-9)
    speedup = suites_persec / max(serial_persec, 1e-9)
    # the >=5x contract is defined on the 1k-tenant load (acceptance
    # criterion); smaller (smoke-sized) loads amortize less — fewer,
    # narrower batches — and keep a 3x floor so a dead coalescer still
    # refuses while scheduler noise on a busy 1-vCPU host does not
    floor = 5.0 if n_tenants >= 1000 else 3.0
    assert speedup >= floor, (
        f"serving violation: coalesced throughput {suites_persec:.0f} "
        f"suites/s is only {speedup:.2f}x the serial submit-per-run "
        f"baseline ({serial_persec:.0f} suites/s) — the >={floor:g}x "
        f"serving contract ({n_tenants}-tenant load) is gone"
    )
    latencies = sorted(
        f.latency_seconds for f in futures if f.latency_seconds is not None
    )
    # the MIXED (cold) pass's cache ledger: fresh schemas miss, repeat
    # shapes hit — the hit rate the open-loop load actually saw
    cold_hits = cold_after["plan_cache_hits"] - cold_before["plan_cache_hits"]
    cold_misses = (
        cold_after["plan_cache_misses"] - cold_before["plan_cache_misses"]
    )
    # the unified registry's serving latency histogram (obs/registry,
    # round 11): the per-tenant submit->resolve distribution the service
    # feeds ALWAYS-ON — run_configs --config 6 banks these quantiles
    # next to the futures-derived p50/p99 above (the two views must
    # agree; tier-1 test_obs pins it)
    hist = SERVE_LATENCY.aggregate.snapshot()
    # live vs evicted label histograms reported separately: their SUM
    # counts label-(re)creation events, not distinct tenants (a tenant
    # re-observed after an LRU eviction creates a fresh label)
    latency_hist = {
        "count": hist["count"],
        "p50_ms": round((hist["p50"] or 0.0) * 1000, 2),
        "p95_ms": round((hist["p95"] or 0.0) * 1000, 2),
        "p99_ms": round((hist["p99"] or 0.0) * 1000, 2),
        "labels_live": len(SERVE_LATENCY.labels()),
        "labels_evicted": SERVE_LATENCY.evicted_labels,
    }
    return {
        "serving_suites_per_sec": round(suites_persec, 1),
        "serving_latency_hist": latency_hist,
        "serving_cold_suites_per_sec": round(
            n_tenants / max(cold_wall, 1e-9), 1
        ),
        "serving_serial_baseline_suites_per_sec": round(serial_persec, 1),
        "serving_speedup_vs_serial": round(speedup, 2),
        "serving_p50_latency_ms": round(
            latencies[len(latencies) // 2] * 1000, 2
        ),
        "serving_p99_latency_ms": round(
            latencies[int(len(latencies) * 0.99)] * 1000, 2
        ),
        "serving_plan_cache_hit_rate": round(
            cold_hits / max(cold_hits + cold_misses, 1), 4
        ),
        "serving_batch_occupancy": round(
            tenants_served / max(tenants_served + padded, 1), 4
        ),
        "serving_coalesced_batches": batches,
        "serving_mean_batch_size": round(
            tenants_served / max(batches, 1), 2
        ),
    }


def measure_fleet_failover(n_tenants: int, n_workers: int = 4):
    """Fleet-tier probe (round 12, deequ_tpu/serve/fleet.py — ROADMAP
    item 1's acceptance shape): an open-loop ``n_tenants``-tenant load
    of small suites over ``n_workers`` serving workers placed by the
    consistent-hash router, vs the SAME load through a single worker —
    then a scripted mid-load worker death with its failover re-dispatch.

    Contract asserts (the probe REFUSES to report on violation, like the
    serving/one-fetch/config-3 asserts):

    - DEATH DEGRADES ONLY ITS IN-FLIGHT TENANTS: killing one wedged
      worker re-dispatches exactly that worker's accepted requests (the
      fleet ledger count equals the victim's routed tenants) — no other
      tenant's request moves;
    - FAILOVER BIT-IDENTITY: every tenant of the death pass (the
      re-dispatched victims included) resolves bit-identical to its
      healthy per-tenant serial run — plans are deterministic;
    - EXACTLY-ONCE: every accepted future of every pass resolves exactly
      once (chaos oracle 8's observable) — none orphaned, none
      double-resolved;
    - NEAR-LINEAR SCALING — armed only on hardware that can express it:
      with >= ``n_workers`` devices AND cpu cores, sustained fleet
      suites/s must be >= 0.6 x n_workers x the single-worker rate. On
      this container's 1-device/2-vCPU shape the workers share one chip
      and the GIL, so the probe banks the measured ratio under
      ``fleet_scaling_gate: "pending-parallel-hw"`` (the config-3
      banked-acceptance idiom) and gates instead on NO COLLAPSE: the
      routed fleet must keep >= 0.5x the single-worker rate (placement,
      the shared quarantine ledger, and the fleet ledger cost bounded).
    """
    import os
    import struct

    import jax

    from deequ_tpu import VerificationSuite
    from deequ_tpu.analyzers import Completeness, Mean, Size, Sum
    from deequ_tpu.data.table import Column, ColumnarTable, DType
    from deequ_tpu.parallel.mesh import use_mesh
    from deequ_tpu.serve import VerificationFleet

    N_SHAPES = 12  # distinct row counts -> distinct digests -> ring spread

    def analyzers():
        return [Size(), Completeness("x"), Mean("x"), Sum("i")]

    def tenant_table(shape: int, seed: int):
        r = np.random.default_rng(seed)
        n = 64 + 16 * shape  # the shape's row count IS its routing key
        return ColumnarTable([
            Column("x", DType.FRACTIONAL, values=r.normal(100, 5, n),
                   mask=r.random(n) > 0.05),
            Column("i", DType.INTEGRAL,
                   values=r.integers(0, 50, n).astype(np.float64),
                   mask=np.ones(n, bool)),
        ])

    load = [
        (f"tenant-{t}", tenant_table(t % N_SHAPES, 7000 + t))
        for t in range(n_tenants)
    ]

    def bits(v):
        return struct.pack("<d", v) if isinstance(v, float) else v

    def run_pass(fleet):
        t0 = time.time()
        futures = [
            fleet.submit(table, required_analyzers=analyzers(), tenant=t)
            for t, table in load
        ]
        results = {
            t: f.result(timeout=600) for (t, _), f in zip(load, futures)
        }
        return time.time() - t0, futures, results

    def assert_exactly_once(futures, label):
        bad = [f.tenant for f in futures if f.resolve_count != 1]
        assert not bad, (
            f"fleet violation ({label}): futures resolved != exactly "
            f"once for {bad[:5]} — chaos oracle 8 is gone"
        )

    with use_mesh(None):
        serial_sample = {
            t: VerificationSuite.run(tbl, [], required_analyzers=analyzers())
            for t, tbl in load[:: max(1, n_tenants // 24)]
        }

        # -- single-worker denominator (same fleet machinery, 1 worker)
        one = VerificationFleet(
            n_workers=1, monitor=False, distinct_devices=False,
        )
        try:
            run_pass(one)  # warm: plan builds + compiles
            one_wall = float("inf")
            for _ in range(3):
                wall, futures, _ = run_pass(one)
                one_wall = min(one_wall, wall)
            assert_exactly_once(futures, "single-worker")
        finally:
            one.stop(drain=True)
        one_persec = n_tenants / max(one_wall, 1e-9)

        # -- the fleet: routed load, steady-state throughput
        fleet = VerificationFleet(
            n_workers=n_workers, monitor=False, distinct_devices=True,
        )
        try:
            run_pass(fleet)  # warm every worker's routed plans
            fleet.prewarm()  # survivors pre-hold each other's hot plans
            fleet_wall = float("inf")
            for _ in range(3):
                wall, futures, _ = run_pass(fleet)
                fleet_wall = min(fleet_wall, wall)
            assert_exactly_once(futures, "fleet-healthy")
            routed = {
                t: fleet.route(tbl, required_analyzers=analyzers())
                for t, tbl in load
            }
            occupancy = {w: 0 for w in range(n_workers)}
            for w in routed.values():
                occupancy[w] += 1
            workers_hit = sum(1 for n in occupancy.values() if n)

            # -- scripted mid-load death: wedge the busiest worker so
            # its queue holds, submit the load, kill it, gather
            victim = max(occupancy, key=occupancy.get)
            victims = [t for t, w in routed.items() if w == victim]
            # the bit-identity gate must cover EVERY re-dispatched
            # victim, not just the stride sample (shape = t % N_SHAPES
            # and a stride can systematically miss every shape the
            # victim worker owns): add the victims' serial references
            tables_by_tenant = dict(load)
            for t in victims:
                if t not in serial_sample:
                    serial_sample[t] = VerificationSuite.run(
                        tables_by_tenant[t], [],
                        required_analyzers=analyzers(),
                    )
            fleet.stall_worker(victim, seconds=600.0)
            time.sleep(0.1)
            death_t0 = time.time()
            futures = [
                fleet.submit(tbl, required_analyzers=analyzers(), tenant=t)
                for t, tbl in load
            ]
            redispatched = fleet.kill_worker(victim)
            results = {
                t: f.result(timeout=600) for (t, _), f in zip(load, futures)
            }
            death_wall = time.time() - death_t0
            assert_exactly_once(futures, "death-pass")
            assert redispatched == len(victims), (
                f"fleet violation: worker {victim} owned {len(victims)} "
                f"accepted requests but {redispatched} were re-dispatched "
                "— failover must move exactly the dead worker's in-flight "
                "tenants, no more, no fewer"
            )
            assert fleet.requests_redispatched == redispatched, (
                "fleet violation: a healthy worker's request was "
                "re-dispatched — death must degrade ONLY the dead "
                "worker's in-flight tenants"
            )
            for t, serial in serial_sample.items():
                served = results[t]
                assert str(serial.status) == str(served.status), t
                for a, m1 in serial.metrics.items():
                    m2 = served.metrics[a]
                    assert m1.value.is_success and m2.value.is_success, (t, a)
                    assert bits(m1.value.get()) == bits(m2.value.get()), (
                        f"fleet violation: {t} {a} after scripted death "
                        f"{m2.value.get()!r} != serial {m1.value.get()!r} "
                        "— failover re-dispatch must be BIT-identical"
                    )
            stats = fleet.stats()
        finally:
            fleet.stop(drain=True)

    fleet_persec = n_tenants / max(fleet_wall, 1e-9)
    scaling = fleet_persec / max(one_persec, 1e-9)
    parallel_hw = (
        len(jax.devices()) >= n_workers
        and (os.cpu_count() or 1) >= n_workers
    )
    if parallel_hw:
        floor = 0.6 * n_workers
        gate = "armed"
        assert scaling >= floor, (
            f"fleet violation: {n_workers} workers over "
            f"{len(jax.devices())} devices sustain only {scaling:.2f}x "
            f"the single-worker rate — the near-linear (> {floor:.1f}x) "
            "fleet scaling contract is gone"
        )
    else:
        floor = 0.5
        gate = "pending-parallel-hw"
        assert scaling >= floor, (
            f"fleet violation: the routed fleet collapsed to "
            f"{scaling:.2f}x the single-worker rate on the shared-device "
            "container — placement/ledger overhead must stay bounded "
            f"(>= {floor}x) even without parallel hardware"
        )
    return {
        "fleet_suites_per_sec": round(fleet_persec, 1),
        "fleet_single_worker_suites_per_sec": round(one_persec, 1),
        "fleet_scaling_x": round(scaling, 2),
        "fleet_scaling_gate": gate,
        "fleet_n_workers": n_workers,
        "fleet_workers_occupied": workers_hit,
        "fleet_death_pass_wall_s": round(death_wall, 3),
        "fleet_failover_victim_tenants": len(victims),
        "fleet_failover_redispatched": redispatched,
        "fleet_failovers_total": stats["failovers"],
        "fleet_workers_alive_after_death": stats["workers_alive"],
    }


def measure_process_fleet(n_tenants: int, n_workers: int = 4):
    """Process-fleet probe (round 17, deequ_tpu/serve/pfleet.py — the
    ROADMAP item-1 acceptance crossed over the process boundary): an
    open-loop ``n_tenants``-tenant load over ``n_workers`` worker
    PROCESSES (real subprocess transport, durable accept-time ledger
    armed) vs the SAME load through one worker process — then a real
    mid-load ``SIGKILL`` of the busiest worker.

    Contract asserts (the probe REFUSES to report on violation):

    - SIGKILL DEGRADES ONLY ITS IN-FLIGHT TENANTS: the death pass
      re-dispatches at most the victim's routed requests (no healthy
      worker's request moves) and at least one (the kill is scripted to
      land while the victim's queue holds: its tenants submit LAST);
    - FAILOVER BIT-IDENTITY: every tenant of the death pass — the
      re-dispatched victims included — resolves bit-identical to its
      healthy run through ONE worker process;
    - EXACTLY-ONCE: every accepted future of every pass resolves
      exactly once (chaos oracle 8's observable, now across a real
      process boundary with the fsynced ledger on the accept path);
    - NO COLLAPSE: the routed process fleet keeps >= 0.5x the
      single-worker-process rate (framing, blob serde, acks, and the
      fsynced ledger all priced in). The near-linear gate is NOT stated
      here: it needs one chip per worker process (a chip serves one
      process; ROADMAP B7), and this coordinator must not even count
      devices — that would initialise the backend its workers need.
      When the gate trips while either side's own passes spread >10%
      (the same code on the same data — the measurement cannot resolve
      a 0.5x effect), the verdict banks as a typed ``starved-scheduler``
      skip instead of a flaky failure (round
      18; the ``_stable_overhead_frac`` same-side-spread signature
      applied to the rate ratio)."""
    import os
    import shutil
    import struct
    import tempfile

    from deequ_tpu.analyzers import Completeness, Mean, Size, Sum
    from deequ_tpu.data.table import Column, ColumnarTable, DType
    from deequ_tpu.serve.pfleet import ProcessFleet

    # THIS process is the coordinator: it must never initialise a jax
    # backend — a chip serves one process, and the workers it spawns need
    # it. The bit-identity baseline is therefore the single-worker-process
    # pass (same machinery, one worker), not an in-process serial run.
    N_SHAPES = 12  # distinct row counts -> distinct digests -> ring spread

    def analyzers():
        return [Size(), Completeness("x"), Mean("x"), Sum("i")]

    def tenant_table(shape: int, seed: int):
        r = np.random.default_rng(seed)
        n = 64 + 16 * shape
        return ColumnarTable([
            Column("x", DType.FRACTIONAL, values=r.normal(100, 5, n),
                   mask=r.random(n) > 0.05),
            Column("i", DType.INTEGRAL,
                   values=r.integers(0, 50, n).astype(np.float64),
                   mask=np.ones(n, bool)),
        ])

    load = [
        (f"ptenant-{t}", tenant_table(t % N_SHAPES, 9000 + t))
        for t in range(n_tenants)
    ]

    def bits(v):
        return struct.pack("<d", v) if isinstance(v, float) else v

    def run_pass(fleet, ordered=None):
        t0 = time.time()
        futures = {
            t: fleet.submit(table, required_analyzers=analyzers(), tenant=t)
            for t, table in (ordered if ordered is not None else load)
        }
        results = {t: f.result(timeout=600) for t, f in futures.items()}
        return time.time() - t0, futures, results

    def assert_exactly_once(futures, label):
        bad = [t for t, f in futures.items() if f.resolve_count != 1]
        assert not bad, (
            f"process-fleet violation ({label}): futures resolved != "
            f"exactly once for {bad[:5]} — chaos oracle 8 is gone"
        )

    ledger_root = tempfile.mkdtemp(prefix="deequ-bench-pfleet-")
    try:
        # -- single-worker-process denominator (same machinery:
        # proc transport, frames, blobs, fsynced ledger)
        one = ProcessFleet(
            n_workers=1, transport="proc", monitor=False,
            ledger_dir=os.path.join(ledger_root, "one"),
        )
        try:
            run_pass(one)  # warm: each worker traces its plans once
            one_walls = []
            for _ in range(3):
                wall, futures, serial_sample = run_pass(one)
                one_walls.append(wall)
            assert_exactly_once(futures, "single-worker")
        finally:
            one.stop(drain=True)
        one_persec = n_tenants / max(min(one_walls), 1e-9)

        # -- the process fleet: routed load, steady-state rate
        fleet = ProcessFleet(
            n_workers=n_workers, transport="proc", monitor=False,
            ledger_dir=os.path.join(ledger_root, "fleet"),
        )
        try:
            run_pass(fleet)  # warm every worker's routed plans
            fleet.prewarm()  # ship hot fingerprints fleet-wide
            fleet_walls = []
            for _ in range(3):
                wall, futures, _ = run_pass(fleet)
                fleet_walls.append(wall)
            fleet_wall = min(fleet_walls)
            assert_exactly_once(futures, "fleet-healthy")
            routed = {
                t: fleet.route(tbl, required_analyzers=analyzers())
                for t, tbl in load
            }
            occupancy = {w: 0 for w in range(n_workers)}
            for w in routed.values():
                occupancy[w] += 1
            workers_hit = sum(1 for n in occupancy.values() if n)

            # -- scripted mid-load SIGKILL: the victim's tenants
            # submit LAST so its accepted queue provably holds work
            # at the kill (there is no stall seam across a process
            # boundary — ordering is the wedge)
            victim = max(occupancy, key=occupancy.get)
            victims = [t for t, w in routed.items() if w == victim]
            tables_by_tenant = dict(load)
            ordered = (
                [(t, tbl) for t, tbl in load if routed[t] != victim]
                + [(t, tables_by_tenant[t]) for t in victims]
            )
            before = fleet.requests_redispatched
            death_t0 = time.time()
            futures = {
                t: fleet.submit(
                    tbl, required_analyzers=analyzers(), tenant=t
                )
                for t, tbl in ordered
            }
            fleet.kill_worker(victim)
            results = {
                t: f.result(timeout=600) for t, f in futures.items()
            }
            death_wall = time.time() - death_t0
            redispatched = fleet.requests_redispatched - before
            assert_exactly_once(futures, "death-pass")
            assert 1 <= redispatched <= len(victims), (
                f"process-fleet violation: worker {victim} owned "
                f"{len(victims)} accepted requests but {redispatched} "
                "were re-dispatched — SIGKILL must move only (and "
                "some of) the dead worker's in-flight tenants"
            )
            for t, serial in serial_sample.items():
                served = results[t]
                assert str(serial.status) == str(served.status), t
                for a, m1 in serial.metrics.items():
                    m2 = served.metrics[a]
                    assert m1.value.is_success and m2.value.is_success, (
                        t, a,
                    )
                    assert bits(m1.value.get()) == bits(m2.value.get()), (
                        f"process-fleet violation: {t} {a} after "
                        f"SIGKILL {m2.value.get()!r} != serial "
                        f"{m1.value.get()!r} — failover re-dispatch "
                        "must be BIT-identical"
                    )
            stats = fleet.stats()
            assert stats["workers_alive"] == n_workers - 1, (
                "process-fleet violation: SIGKILL must retire exactly "
                "the victim"
            )
        finally:
            fleet.stop(drain=True)
    finally:
        shutil.rmtree(ledger_root, ignore_errors=True)

    fleet_persec = n_tenants / max(fleet_wall, 1e-9)
    scaling = fleet_persec / max(one_persec, 1e-9)
    # the near-linear gate needs a device count, and counting devices
    # initialises the backend this coordinator must stay off: the gate that
    # can be stated from here is NO COLLAPSE (one chip serves one worker
    # process; N workers on N chips is ROADMAP B7)
    floor = 0.5
    gate = "pending-parallel-hw"
    # starved-scheduler verdict (the round-17 _stable_overhead_frac
    # discipline, applied to the rate ratio): N+1 processes time-
    # slicing one core make the ratio bimodal — a pass that loses
    # its timeslice reads as a collapse on either side. If the gate
    # trips while the SAME code on the SAME data spreads >10%
    # across its own passes, the container cannot resolve a
    # 0.5x-sized effect; bank a typed skip. A real serde/framing
    # collapse keeps every pass slow on one side — tight spreads —
    # and still asserts.
    spreads = {
        side: (max(walls) - min(walls)) / max(min(walls), 1e-9)
        for side, walls in (
            ("one", one_walls), ("fleet", fleet_walls),
        )
    }
    if scaling < floor and max(spreads.values()) > 0.10:
        gate = (
            f"starved-scheduler (spread one={spreads['one']:.3f} "
            f"fleet={spreads['fleet']:.3f})"
        )
        print(
            f"process-fleet no-collapse gate: SKIP — measured "
            f"{scaling:.2f}x under same-side spread "
            f"{max(spreads.values()):.3f} > 0.10 (a {floor}x effect "
            "is unresolvable on this container)",
            file=sys.stderr,
        )
    else:
        assert scaling >= floor, (
            f"process-fleet violation: the routed process fleet "
            f"collapsed to {scaling:.2f}x the single-worker rate on "
            "the shared-core container — framing/serde/ledger "
            f"overhead must stay bounded (>= {floor}x) even without "
            "parallel hardware"
        )
    return {
        "pfleet_suites_per_sec": round(fleet_persec, 1),
        "pfleet_single_worker_suites_per_sec": round(one_persec, 1),
        "pfleet_scaling_x": round(scaling, 2),
        "pfleet_scaling_gate": gate,
        "pfleet_n_workers": n_workers,
        "pfleet_workers_occupied": workers_hit,
        "pfleet_death_pass_wall_s": round(death_wall, 3),
        "pfleet_failover_victim_tenants": len(victims),
        "pfleet_failover_redispatched": redispatched,
        "pfleet_workers_alive_after_death": stats["workers_alive"],
        "pfleet_ledger_appends": stats["ledger_appends"],
        "pfleet_resumed": stats["resumed"],
    }


def measure_fencing_overhead(n_tenants: int = 24):
    """Epoch-fencing cost probe (round 18, deequ_tpu/serve/lease.py):
    the SAME loopback fleet + durable-ledger load timed with fencing
    OFF vs ON. Fencing's hot-path cost is one lease ``check()`` per
    submit — a disk re-read of the checksummed lease file plus the
    epoch stamp on the accept frame — so the gate is <1% of healthy
    wall (median-of-5 interleaved trials with one discard-and-retry
    pass, the governance probe's harness; a starved scheduler banks a
    typed skip instead of a flaky failure).

    Contract asserts (the probe refuses to report on violation):

    - the fenced fleet actually holds an epoch (>= 1) and the unfenced
      one holds none (0);
    - the healthy A/B rejects NOTHING: ``fencing_rejections`` must not
      move — a fenced coordinator that fences itself is a bug, not
      overhead;
    - exactly-once on both sides, every rep."""
    import os
    import shutil
    import tempfile

    from deequ_tpu.data.table import Column, ColumnarTable, DType
    from deequ_tpu.obs.registry import FENCING_REJECTIONS
    from deequ_tpu.parallel.mesh import use_mesh
    from deequ_tpu.serve.pfleet import ProcessFleet

    def analyzers():
        from deequ_tpu.analyzers import Completeness, Mean, Size, Sum

        return [Size(), Completeness("x"), Mean("x"), Sum("i")]

    def tenant_table(shape: int, seed: int):
        r = np.random.default_rng(seed)
        n = 64 + 16 * shape
        return ColumnarTable([
            Column("x", DType.FRACTIONAL, values=r.normal(100, 5, n),
                   mask=r.random(n) > 0.05),
            Column("i", DType.INTEGRAL,
                   values=r.integers(0, 50, n).astype(np.float64),
                   mask=np.ones(n, bool)),
        ])

    load = [
        (f"ftenant-{t}", tenant_table(t % 8, 11000 + t))
        for t in range(n_tenants)
    ]

    def run_pass(fleet):
        t0 = time.time()
        futures = {
            t: fleet.submit(tbl, required_analyzers=analyzers(), tenant=t)
            for t, tbl in load
        }
        for t, f in futures.items():
            f.result(timeout=600)
        wall = time.time() - t0
        bad = [t for t, f in futures.items() if f.resolve_count != 1]
        assert not bad, (
            f"fencing probe violation: futures resolved != exactly once "
            f"for {bad[:5]}"
        )
        return wall

    ledger_root = tempfile.mkdtemp(prefix="deequ-bench-fencing-")
    try:
        with use_mesh(None):
            plain = ProcessFleet(
                n_workers=2, transport="loopback", monitor=False,
                ledger_dir=os.path.join(ledger_root, "plain"),
                fencing=False,
                worker_knobs={"coalesce_window": 0.0},
            )
            fenced = ProcessFleet(
                n_workers=2, transport="loopback", monitor=False,
                ledger_dir=os.path.join(ledger_root, "fenced"),
                fencing=True,
                worker_knobs={"coalesce_window": 0.0},
            )
            try:
                assert plain.epoch == 0 and plain._lease is None, (
                    "fencing probe: the unfenced side armed a lease"
                )
                assert fenced.epoch >= 1, (
                    "fencing probe: the fenced fleet holds no epoch"
                )
                run_pass(plain)  # warm both sides' traced plans
                run_pass(fenced)
                rejections_before = FENCING_REJECTIONS.value
                frac, skip = _stable_overhead_frac(
                    lambda: run_pass(plain),
                    lambda: run_pass(fenced),
                    gate=0.01, what="fencing",
                )
                assert FENCING_REJECTIONS.value == rejections_before, (
                    "fencing probe: the healthy A/B fenced something — "
                    "a coordinator that rejects its own submits is a "
                    "bug, not overhead"
                )
            finally:
                fenced.stop(drain=True)
                plain.stop(drain=True)
    finally:
        shutil.rmtree(ledger_root, ignore_errors=True)
    if skip is not None:
        return {
            "fencing_overhead_frac": None,
            "fencing_overhead_skipped": skip,
            "fencing_epoch": fenced.epoch,
        }
    return {
        "fencing_overhead_frac": round(frac, 4),
        "fencing_epoch": fenced.epoch,
    }


def measure_overload_shedding(n_submissions: int = 2400):
    """Overload-tier probe (round 15, deequ_tpu/serve/admission.py —
    the ROADMAP item-1 per-tenant-SLO acceptance shape): the 4-worker
    forced-host fleet under paced OPEN-LOOP load — first at ~0.5x its
    measured unloaded capacity, then at ~2x — with every submission
    carrying a real SLO class (25% critical, 25% standard with no
    deadline, 50% best_effort with a tight one).

    Contract asserts (the probe REFUSES to report on violation, like
    the serving/fleet/one-fetch asserts):

    - ZERO SHEDS AT <= 0.5x: the paced half-load pass must complete
      every submission (no deadline sheds, no admission refusals) —
      the overload tier must be INERT when there is no overload;
    - CRITICAL SURVIVES 2x: under ~2x open-loop overload, zero
      critical-class sheds and critical p99 submit->resolve latency
      within its SLO deadline (strict class priority + reserved
      admission headroom are what buy this);
    - BEST_EFFORT SHEDS TYPED: the 2x pass must shed best_effort
      requests pre-dispatch as typed ``DeadlineExceededException``
      resolutions on their original futures (exactly once each);
    - GOODPUT HOLDS: completed suites/sec of the 2x pass >= 0.8x the
      unloaded capacity — shedding is cheap, and the work that runs is
      the work that still has a caller. "Unloaded capacity" is
      CALIBRATED by the same open-loop pacing harness the load passes
      use (the highest paced rate with flat p95 and zero sheds): a
      closed-loop deep-queue rate would overstate what any arrival
      process can reach and understate per-arrival costs;
    - BIT-IDENTITY: every COMPLETED result of the overload pass equals
      its tenant's unloaded serial run bit for bit — brownout/shedding
      change WHICH requests run, never how;
    - CHAOS QUICK-SOAK CLEAN: a 4-seed ``load``-seam chaos soak
      (scripted spikes + slow-tenant stalls) reports zero oracle
      violations (exactly-once incl. typed sheds, no priority
      inversion)."""
    import struct

    from deequ_tpu import VerificationSuite
    from deequ_tpu.analyzers import Completeness, Mean, Size, Sum
    from deequ_tpu.data.table import Column, ColumnarTable, DType
    from deequ_tpu.exceptions import (
        DeadlineExceededException,
        ServiceOverloadedException,
    )
    from deequ_tpu.parallel.mesh import use_mesh
    from deequ_tpu.resilience.chaos import soak
    from deequ_tpu.serve import Slo, VerificationFleet

    CRITICAL_DEADLINE_MS = 5_000.0
    BEST_EFFORT_DEADLINE_MS = 500.0
    #: the calibration ramp's stability bar: a rate counts as
    #: sustainable only while paced p95 latency stays under this and
    #: nothing sheds (well under the tightest deadline, so the half
    #: pass inherits a ~8x margin; tight on purpose — a generous bar
    #: admits rates already trading latency for throughput, and 2x of
    #: THOSE is a submission storm that measures the pacing thread's
    #: GIL contention, not the admission tier)
    CALIBRATION_P95_S = 0.12
    N_TENANTS = 4  # distinct row counts -> distinct digests -> ring spread

    def analyzers():
        return [Size(), Completeness("x"), Mean("x"), Sum("i")]

    def tenant_table(t: int):
        r = np.random.default_rng(9000 + t)
        # ~16k rows per suite: enough device compute per dispatch that
        # the fleet's hard service ceiling sits well BELOW what one
        # pacing thread can emit — 2x of the calibrated rate is then a
        # genuine arrival-rate overload, not a GIL-starved submit storm
        n = 16384 + 2048 * t
        return ColumnarTable([
            Column("x", DType.FRACTIONAL, values=r.normal(100, 5, n),
                   mask=r.random(n) > 0.05),
            Column("i", DType.INTEGRAL,
                   values=r.integers(0, 50, n).astype(np.float64),
                   mask=np.ones(n, bool)),
        ])

    tables = [tenant_table(t) for t in range(N_TENANTS)]
    # the load mix, cycled round-robin so every pacing window carries
    # every class: 25% critical, 25% standard, 50% best_effort
    def slo_of(t: int) -> Slo:
        if t == 0:
            return Slo(deadline_ms=CRITICAL_DEADLINE_MS, cls="critical")
        if t == 1:
            return Slo(cls="standard")
        return Slo(deadline_ms=BEST_EFFORT_DEADLINE_MS, cls="best_effort")

    def bits(v):
        return struct.pack("<d", v) if isinstance(v, float) else v

    def submit_one(fleet, i):
        t = i % N_TENANTS
        return t, fleet.submit(
            tables[t], required_analyzers=analyzers(),
            tenant=f"t{t}", slo=slo_of(t),
        )

    def paced_pass(fleet, rate, count):
        """Open-loop: submit ``count`` suites at ``rate``/s (absolute
        schedule, no waiting on results), then gather every future.
        Returns (wall from first submit to last resolution, outcomes)
        where outcomes is a list of (tenant, slo_class, future|None,
        refusal|None)."""
        interval = 1.0 / rate
        out = []
        t0 = time.time()
        for i in range(count):
            lag = (t0 + i * interval) - time.time()
            if lag > 0:
                time.sleep(lag)
            t = i % N_TENANTS
            try:
                _, future = submit_one(fleet, i)
                out.append((t, slo_of(t).cls, future, None))
            except ServiceOverloadedException as e:
                out.append((t, slo_of(t).cls, None, e))
        for _, _, future, _ in out:
            if future is not None:
                try:
                    future.result(timeout=600)
                except Exception:  # noqa: BLE001 — outcomes categorized below
                    pass
        return time.time() - t0, out

    def categorize(outcomes):
        ok, shed, refused, failed = [], [], [], []
        for t, cls, future, refusal in outcomes:
            if refusal is not None:
                refused.append((t, cls, refusal))
            elif not future.done():
                # gather timed out on an unresolved future: an orphan
                # is THE bug this probe exists to catch — report it as
                # a failure, don't crash the ok-path asserts on it
                failed.append((t, cls, TimeoutError(
                    f"future for t{t}/{cls} never resolved (orphan)"
                )))
            elif isinstance(future._error, DeadlineExceededException):
                shed.append((t, cls, future))
            elif future._error is not None:
                failed.append((t, cls, future._error))
            else:
                ok.append((t, cls, future))
        return ok, shed, refused, failed

    with use_mesh(None):
        serial_ref = {
            t: VerificationSuite.run(
                tables[t], [], required_analyzers=analyzers()
            )
            for t in range(N_TENANTS)
        }
        # the chaos fleet shape: 4 forced-host workers sharing one
        # compile cache (membership off — overload is not death)
        fleet = VerificationFleet(
            n_workers=4, monitor=False, distinct_devices=False,
            worker_knobs={"coalesce_window": 0.01},
        )
        try:
            # warm every plan AND every pow2 tenant-width bucket the
            # load can pop (width-bucket programs compile per shape):
            # width w is warmed by submitting exactly w copies of one
            # plan back-to-back so they coalesce into a w-wide dispatch
            for width in (1, 1, 2, 4, 8, 16):
                for t in range(N_TENANTS):
                    warm = [
                        fleet.submit(
                            tables[t], required_analyzers=analyzers(),
                            tenant=f"t{t}",
                        )
                        for _ in range(width)
                    ]
                    for f in warm:
                        f.result(timeout=600)
            fleet.prewarm()

            # -- calibrate the UNLOADED OPEN-LOOP capacity: ramp the
            # paced rate until p95 latency degrades or anything sheds.
            # A closed-loop deep-queue rate is NOT the right
            # denominator here — with the whole load pre-queued the
            # coalescer runs max-width batches no paced arrival
            # process reaches, and on a shared-vCPU container the
            # pacing thread itself contends with the workers — so
            # "capacity" is the highest ARRIVAL rate the fleet serves
            # with flat latency, measured by the same pacing harness
            # the load passes use.
            capacity = None
            rate = 50.0
            retried = False
            while rate <= 1200.0:
                # each rung sustains its rate for ~0.8s of wall: a
                # short burst absorbs into the queue and reads as
                # sustainable no matter the rate
                wall, out = paced_pass(
                    fleet, rate=rate, count=int(max(96, rate * 0.8))
                )
                ok, shed, refused, failed = categorize(out)
                lats = sorted(
                    f.latency_seconds for _, _, f, _ in out
                    if f is not None and f.latency_seconds is not None
                )
                p95 = lats[min(len(lats) - 1, int(0.95 * len(lats)))]
                if shed or refused or failed or p95 > CALIBRATION_P95_S:
                    # one retry per ramp: a scheduler stall can fail a
                    # genuinely sustainable rung, under-calibrating
                    # capacity so far that 2x of it never overloads
                    if not retried:
                        retried = True
                        time.sleep(0.5)
                        continue
                    break
                capacity = rate
                rate *= 1.5
            assert capacity is not None, (
                "overload violation: the fleet cannot sustain even "
                "50 paced suites/s unloaded — no capacity to gate "
                "shedding against"
            )

            # -- <= 0.5x: the overload tier must be inert. One retry:
            # on a shared-vCPU container a single scheduler stall can
            # blow one pass's latencies through a deadline — a real
            # inertness regression fails BOTH passes
            half_count = min(n_submissions // 2, 240)
            for attempt in (0, 1):
                half_wall, half_out = paced_pass(
                    fleet, rate=0.5 * capacity, count=half_count
                )
                ok, shed, refused, failed = categorize(half_out)
                if not (shed or refused or failed) or attempt:
                    break
                time.sleep(0.5)
            assert not failed, (
                f"overload violation: {len(failed)} untyped/unexpected "
                f"failures at half load: {failed[:3]}"
            )
            assert not shed and not refused, (
                f"overload violation: {len(shed)} sheds + {len(refused)} "
                "admission refusals at <= 0.5x load — the overload tier "
                "must be inert without overload"
            )

            # -- ~2x open-loop overload, long enough that queue wait
            # outgrows the best_effort deadline (backlog accrues at
            # (offered - served) per wall second)
            over_count = int(min(
                max(400, 2.0 * capacity * 3.0), max(n_submissions, 400),
            ))
            over_wall, over_out = paced_pass(
                fleet, rate=2.0 * capacity, count=over_count
            )
            ok, shed, refused, failed = categorize(over_out)
            assert not failed, (
                f"overload violation: {len(failed)} untyped/unexpected "
                f"failures under 2x overload: {failed[:3]}"
            )
            crit_shed = [s for s in shed if s[1] == "critical"]
            be_shed = [s for s in shed if s[1] == "best_effort"]
            assert not crit_shed, (
                f"overload violation: {len(crit_shed)} critical-class "
                "requests shed under 2x overload — strict class priority "
                "+ reserved admission headroom must keep critical clean"
            )
            assert be_shed, (
                "overload violation: 2x open-loop overload shed zero "
                "best_effort requests — the deadline-aware queue never "
                "engaged (not actually overloaded, or sheds are broken)"
            )
            exactly_once = [
                f for _, _, f, r in over_out
                if f is not None and f.resolve_count != 1
            ]
            assert not exactly_once, (
                f"overload violation: {len(exactly_once)} accepted "
                "futures resolved != exactly once under overload"
            )
            crit_lat = sorted(
                f.latency_seconds for t, cls, f in ok if cls == "critical"
            )
            assert crit_lat, "no critical completions under overload"
            crit_p99 = crit_lat[min(len(crit_lat) - 1,
                                    int(0.99 * len(crit_lat)))]
            assert crit_p99 * 1000 <= CRITICAL_DEADLINE_MS, (
                f"overload violation: critical p99 {crit_p99 * 1000:.0f}ms "
                f"exceeded its {CRITICAL_DEADLINE_MS:g}ms SLO under 2x "
                "overload"
            )
            goodput = len(ok) / max(over_wall, 1e-9)
            assert goodput >= 0.8 * capacity, (
                f"overload violation: goodput {goodput:.1f} suites/s under "
                f"2x overload is below 0.8x the unloaded capacity "
                f"({capacity:.1f}) — shedding must protect throughput, "
                "not replace it"
            )
            for t, cls, future in ok:
                served, serial = future._result, serial_ref[t]
                assert str(served.status) == str(serial.status), (t, cls)
                for a, m1 in serial.metrics.items():
                    m2 = served.metrics[a]
                    assert m1.value.is_success and m2.value.is_success, (t, a)
                    assert bits(m1.value.get()) == bits(m2.value.get()), (
                        f"overload violation: tenant t{t} {a} under load "
                        f"{m2.value.get()!r} != unloaded serial "
                        f"{m1.value.get()!r} — overload must never degrade "
                        "computation"
                    )
        finally:
            fleet.stop(drain=True)

    # chaos load-seam quick-soak: scripted spikes + slow-tenant stalls,
    # zero oracle violations (exactly-once incl. typed sheds, no
    # priority inversion)
    soak_summary = soak(n=4, seed0=0, verbose=False, load=True)
    assert soak_summary["failures"] == [], (
        "overload violation: the chaos load-seam quick-soak reported "
        f"oracle violations: {soak_summary['failures']}"
    )

    return {
        "overload_goodput_frac": round(goodput / capacity, 3),
        "overload_unloaded_suites_per_sec": round(capacity, 1),
        "overload_goodput_suites_per_sec": round(goodput, 1),
        "overload_offered_x": 2.0,
        "overload_submissions": over_count,
        "overload_completed": len(ok),
        "overload_shed_best_effort": len(be_shed),
        "overload_shed_critical": 0,
        "overload_refused_typed": len(refused),
        "overload_critical_p99_ms": round(crit_p99 * 1000, 2),
        "overload_critical_slo_ms": CRITICAL_DEADLINE_MS,
        "overload_halfload_sheds": 0,
        "overload_chaos_load_soak": soak_summary["outcomes"],
    }


def measure_suggestion_loop(n_windows: int = 6):
    """Control-plane probe (round 16, deequ_tpu/control — the ROADMAP
    closed-loop acceptance shape): a COLD tenant driven window by
    window through serving-backed profiling -> recorded history ->
    constraint suggestion -> best_effort shadow evaluation ->
    anomaly-gated promotion, until its first enforcing check set —
    with verification traffic sharing the service throughout.

    Contract asserts (the probe REFUSES to report on violation, like
    the serving/overload/one-fetch asserts):

    - PROFILING COALESCES: with verification submissions in flight,
      the profile passes ride the same coalescer under the
      one-fetch-per-batch contract — device fetches == coalesced
      batches across the mixed phase (profiling adds no extra
      round-trips);
    - REPEAT PROFILES ZERO-TRACE: once a tenant shape is warm, further
      profile windows add ZERO compiled programs and ZERO plan-lint
      traces (plan lint in ``error`` mode) — profiling inherits the
      repeat-tenant plan-cache contract;
    - SHADOW LOAD NEVER SHEDS CRITICAL: under a queue-saturating
      critical flood the shadow evaluation sheds TYPED (streaks
      untouched) while ZERO critical requests are shed or refused and
      every completed critical result is bit-identical to its
      unloaded serial run — vetting work never displaces enforcing
      traffic;
    - THE LOOP CLOSES: the cold tenant reaches a non-empty enforcing
      set with zero hand-written constraints inside ``n_windows``, and
      a second registry re-minting from the RECORDED history alone
      reproduces the identical check ids + codes."""
    import struct

    from deequ_tpu import VerificationSuite
    from deequ_tpu.analyzers import Completeness, Mean, Size, Sum
    from deequ_tpu.anomaly import OnlineNormalStrategy
    from deequ_tpu.control import (
        CONTROL_STATS,
        CheckRegistry,
        PromotionGate,
        SuggestionEngine,
    )
    from deequ_tpu.data.table import Column, ColumnarTable, DType
    from deequ_tpu.ops.scan_engine import SCAN_STATS
    from deequ_tpu.parallel.mesh import use_mesh
    from deequ_tpu.repository import (
        InMemoryMetricsRepository,
        QualityMonitor,
    )
    from deequ_tpu.serve import Slo, VerificationService

    PROMOTE_WINDOWS = 3

    def bits(v):
        return struct.pack("<d", v) if isinstance(v, float) else v

    def window_table(w: int, n: int = 4096):
        """One observation window of multi-family tenant data:
        categorical string, fractional, nullable fractional, unique
        integral — the shape every suggestion rule can bite on."""
        r = np.random.default_rng(1600 + w)
        vals = r.uniform(1.0, 5.0, size=n)
        return ColumnarTable.from_pydict({
            "cat": r.choice(["a", "b", "c"], size=n).tolist(),
            "value": vals.tolist(),
            "maybe": [
                float(v) if i % 10 else None for i, v in enumerate(vals)
            ],
            "ident": list(range(n)),
        })

    def verif_table(t: int, n: int = 4096):
        r = np.random.default_rng(1700 + t)
        return ColumnarTable([
            Column("x", DType.FRACTIONAL, values=r.normal(100, 5, n),
                   mask=r.random(n) > 0.05),
            Column("i", DType.INTEGRAL,
                   values=r.integers(0, 50, n).astype(np.float64),
                   mask=np.ones(n, bool)),
        ])

    verif_analyzers = [Size(), Completeness("x"), Mean("x"), Sum("i")]
    vtables = [verif_table(t) for t in range(3)]

    with use_mesh(None):
        serial_ref = [
            VerificationSuite.run(t, [], required_analyzers=verif_analyzers)
            for t in vtables
        ]
        repo = InMemoryMetricsRepository()
        registry = CheckRegistry()
        monitor = QualityMonitor()
        monitor.watch(
            OnlineNormalStrategy(), metric_name="Completeness",
            tags={"kind": "profile"}, warmup=4 * n_windows,
            name="bench-profile-completeness",
        )
        svc = VerificationService(plan_lint="error", coalesce_window=0.01)
        svc.start()
        try:
            engine = SuggestionEngine(repo, registry, service=svc)
            gate = PromotionGate(
                registry, monitor=monitor, windows=PROMOTE_WINDOWS
            )

            # -- the closed loop (ControlLoop.step unrolled so the
            # coalescing ledger can scope to the PROFILING passes: the
            # shadow evaluation legitimately carries group analyzers —
            # Uniqueness — whose serial group scans fetch outside the
            # coalescer), with verification traffic in flight during
            # every profile window
            windows_to_enforcing = None
            mixed_fetches = mixed_batches = 0
            repeat_built0 = repeat_lint0 = None
            for w in range(1, n_windows + 1):
                inflight = [
                    svc.submit(
                        vtables[t], required_analyzers=verif_analyzers,
                        tenant=f"v{t}", slo=Slo(cls="standard"),
                    )
                    for t in range(len(vtables))
                ]
                if w == 2:
                    # tenant shape is warm after window 1: from here
                    # every profile pass must be a pure plan-cache hit
                    repeat_built0 = SCAN_STATS.programs_built
                    repeat_lint0 = SCAN_STATS.plan_lint_traces
                data = window_table(w)
                fetch0 = SCAN_STATS.device_fetches
                batch0 = SCAN_STATS.coalesced_batches
                group0 = SCAN_STATS.seam_grouping_count
                engine.profile_tenant(data, "cold", w, monitor=monitor)
                # a resident string column's histogram pass is one fetch
                # of its own, outside the coalescer (counted since PR 32)
                mixed_fetches += SCAN_STATS.device_fetches - fetch0 - (
                    SCAN_STATS.seam_grouping_count - group0
                )
                mixed_batches += SCAN_STATS.coalesced_batches - batch0
                engine.suggest("cold", w)
                shadow = None
                if registry.checks("cold", "shadow"):
                    shadow = engine.evaluate_shadow(data, "cold", w)
                gate.observe_window("cold", w, shadow)
                for t, f in enumerate(inflight):
                    got = f.result(timeout=600).metrics
                    for a in verif_analyzers:
                        assert bits(got[a].value.get()) == bits(
                            serial_ref[t].metrics[a].value.get()
                        ), (
                            "suggestion-loop violation: verification "
                            f"tenant v{t} {a} degraded while sharing the "
                            "service with profile traffic"
                        )
                if registry.checks("cold", "enforcing"):
                    windows_to_enforcing = w
                    break
            assert windows_to_enforcing is not None, (
                "suggestion-loop violation: the cold tenant never "
                f"reached an enforcing check set in {n_windows} windows"
            )
            enforcing = registry.checks("cold", "enforcing")
            assert all(c.rule for c in enforcing), (
                "suggestion-loop violation: an enforcing check was not "
                "minted by a suggestion rule"
            )
            assert mixed_fetches == mixed_batches, (
                "suggestion-loop violation: "
                f"{mixed_fetches} device fetches for {mixed_batches} "
                "coalesced batches with profile traffic in the mix — "
                "profiling must obey the one-fetch-per-batch contract"
            )
            # shadow-check shapes mint during window 2, so the warm
            # window is allowed its first-eval compiles; windows >= 3
            # (there are >= PROMOTE_WINDOWS of them) must add none, and
            # the REPEAT PROFILE phase below pins the pure-profile case
            repeat_built = SCAN_STATS.programs_built - repeat_built0
            repeat_lint = SCAN_STATS.plan_lint_traces - repeat_lint0

            # -- repeat-profile zero-trace, isolated: two more profile
            # windows of the warm tenant shape, nothing else in flight
            built0 = SCAN_STATS.programs_built
            lint0 = SCAN_STATS.plan_lint_traces
            for w in (n_windows + 1, n_windows + 2):
                engine.profile_tenant(window_table(w), "cold", w)
            assert SCAN_STATS.programs_built == built0, (
                "suggestion-loop violation: "
                f"{SCAN_STATS.programs_built - built0} programs built "
                "re-profiling a warm tenant shape — profiling must "
                "inherit the repeat-tenant plan-cache contract"
            )
            assert SCAN_STATS.plan_lint_traces == lint0, (
                "suggestion-loop violation: "
                f"{SCAN_STATS.plan_lint_traces - lint0} plan-lint "
                "traces re-profiling a warm tenant shape"
            )

            # -- replay reproducibility: a second registry re-minting
            # from the recorded history alone produces the identical
            # check set
            replayed = CheckRegistry()
            replayed.note_tenant_schema(
                "cold", registry.tenant_schema("cold")
            )
            engine2 = SuggestionEngine(repo, replayed)
            # replay exactly the windows the loop consumed (history
            # also holds the repeat-profile windows appended above)
            for w in sorted(engine.history("cold")):
                if w <= windows_to_enforcing:
                    engine2.suggest("cold", w)
            orig = {c.check_id: c.code for c in registry.checks("cold")}
            mint = {c.check_id: c.code for c in replayed.checks("cold")}
            assert orig == mint and orig, (
                "suggestion-loop violation: replaying the recorded "
                "profile history minted a different check set "
                f"({sorted(set(orig) ^ set(mint))[:4]}...)"
            )
        finally:
            svc.stop(drain=False)

        # -- the shed phase: an unstarted service holds a
        # queue-saturating critical flood; the best_effort shadow
        # evaluation must shed typed while zero criticals are touched
        if not registry.checks("cold", "shadow"):
            # every mint promoted: put one check back through the
            # demoted -> shadow re-trial path so there is shadow work
            # to shed
            victim = registry.checks("cold", "enforcing")[0]
            registry.demote(
                victim.check_id, n_windows + 2, "bench-shed-retrial"
            )
            registry.to_shadow(victim.check_id)
        pending = 10
        shed_svc = VerificationService(
            start=False, max_pending=pending, coalesce_window=0.0,
        )
        try:
            flood = [
                shed_svc.submit(
                    vtables[i % len(vtables)],
                    required_analyzers=verif_analyzers,
                    tenant=f"crit{i}", slo=Slo(cls="critical"),
                )
                for i in range(pending)
            ]
            shed0 = CONTROL_STATS.shadow_evals_shed
            streaks = {
                c.check_id: c.clean_windows
                for c in registry.checks("cold", "shadow")
            }
            outcome = engine.evaluate_shadow(
                window_table(99), "cold", n_windows + 3, service=shed_svc,
            )
            assert outcome.status == "shed", (
                "suggestion-loop violation: the shadow evaluation was "
                f"admitted ({outcome.status}) through a saturated queue "
                "— best_effort shadow traffic must shed first"
            )
            assert CONTROL_STATS.shadow_evals_shed == shed0 + 1
            assert streaks == {
                c.check_id: c.clean_windows
                for c in registry.checks("cold", "shadow")
            }, (
                "suggestion-loop violation: a SHED shadow window moved "
                "a promotion streak — shed must mean no evidence"
            )
            shed_svc.start()
            for i, f in enumerate(flood):
                got = f.result(timeout=600).metrics
                serial = serial_ref[i % len(vtables)]
                for a in verif_analyzers:
                    assert bits(got[a].value.get()) == bits(
                        serial.metrics[a].value.get()
                    ), (
                        "suggestion-loop violation: critical request "
                        f"crit{i} {a} degraded under shadow-class load"
                    )
        finally:
            shed_svc.stop(drain=False)

    return {
        "suggestion_windows_to_enforcing": windows_to_enforcing,
        "suggestion_promote_windows": PROMOTE_WINDOWS,
        "suggestion_enforcing_checks": len(enforcing),
        "suggestion_candidates_registered": (
            CONTROL_STATS.candidates_registered
        ),
        "suggestion_mixed_fetches": mixed_fetches,
        "suggestion_mixed_batches": mixed_batches,
        "suggestion_warm_window_programs": repeat_built,
        "suggestion_warm_window_lint_traces": repeat_lint,
        "suggestion_repeat_profile_programs": 0,
        "suggestion_repeat_profile_lint_traces": 0,
        "suggestion_shadow_sheds": 1,
        "suggestion_critical_sheds": 0,
        "suggestion_replay_identical": True,
    }


def measure_repository_query(n_tenants: int, n_dates: int = 32):
    """Repository-query probe (round 13, deequ_tpu/repository — ROADMAP
    item 5's acceptance shape): an ``n_tenants x n_dates`` metric
    history (4 Completeness series per tenant per date, dict-heavy
    values) ingested into the columnar backend with an online
    :class:`QualityMonitor` watching one series, then ONE cross-tenant
    aggregate query ("completeness of column a across all tenants in
    this window") answered two ways:

    - COMPILED: ``RepositoryQuery`` lowered onto the repository's own
      history table through the ordinary fused-scan path
      (plan-lint ``error``, encoded int16 planes);
    - LOADER-SIDE: the pre-columnar baseline — decode every save
      through the loader DSL, filter by Python iteration, re-scan a
      decoded table.

    Contract asserts (the probe REFUSES to report on violation, like
    the serving/one-fetch/config-3 asserts):

    - BIT-IDENTITY: both paths produce bit-identical aggregates (same
      engine arithmetic — the columnar path only skips the decode);
    - ONE FETCH: the compiled query materializes exactly one
      device->host result (the one-fetch-per-scan contract applies to
      L9 like any scan);
    - ENCODED STAGING: the compiled query's encoded planes stage >= 2x
      fewer bytes than the same query forced decoded (the PR-8 gate);
    - O(result) APPEND: bytes appended across the load grow linearly
      (second half <= 1.05x first half), never the fs backend's
      quadratic wall;
    - ONLINE ALERTS: the scripted spike emits exactly one QualityAlert
      at ingest time (no batch pull) and it reads through the
      ``repository`` registry section.
    """
    from deequ_tpu.ops.scan_engine import SCAN_STATS
    from deequ_tpu.anomaly.strategies import OnlineNormalStrategy
    from deequ_tpu.metrics import DoubleMetric, Entity
    from deequ_tpu.analyzers import Completeness
    from deequ_tpu.analyzers.runner import AnalyzerContext
    from deequ_tpu.repository import (
        AnalysisResult,
        ColumnarMetricsRepository,
        QualityMonitor,
        RepositoryQuery,
        ResultKey,
    )
    from deequ_tpu.repository.columnar import REPO_STATS
    from deequ_tpu.repository.monitor import MONITOR_STATS
    from deequ_tpu.repository.query import (
        loader_side_aggregates,
        run_repository_query,
    )
    from deequ_tpu.tryresult import Success

    import shutil
    import struct
    import tempfile

    def bits(v):
        return struct.pack("<d", float(v))

    monitor = QualityMonitor()
    monitor.watch(
        OnlineNormalStrategy(
            lower_deviation_factor=3.0, upper_deviation_factor=3.0
        ),
        metric_name="Completeness", instance="a",
        tags={"tenant": "tenant-0"}, warmup=8, name="bench-watch",
    )
    # a PERSISTED repository: the O(result) append gate measures
    # bytes_appended, which only moves on the persisted path — an
    # in-memory repo would make that assert vacuously 0 <= 0
    repo_dir = tempfile.mkdtemp(prefix="deequ_tpu_bench_repo_")
    try:
        repo = ColumnarMetricsRepository(repo_dir, monitor=monitor)
        alerts_before = MONITOR_STATS.alerts_emitted

        spike_date = n_dates - 2
        values = [0.91, 0.93, 0.95, 0.97]

        def result_for(tenant, date):
            metric_map = {}
            for i, col in enumerate("abcd"):
                v = values[(date + i) % 4]
                if col == "a" and tenant == 0 and date == spike_date:
                    v = 0.05  # the scripted spike the monitor must catch
                metric_map[Completeness(col)] = DoubleMetric(
                    Entity.COLUMN, "Completeness", col, Success(v)
                )
            return AnalysisResult(
                ResultKey(date, {"tenant": f"tenant-{tenant}"}),
                AnalyzerContext(metric_map),
            )

        bytes_mark = REPO_STATS.bytes_appended
        ingest_t0 = time.time()
        halves = []
        for half in range(2):
            for date in range(half * n_dates // 2, (half + 1) * n_dates // 2):
                for tenant in range(n_tenants):
                    repo.save(result_for(tenant, date))
            halves.append(REPO_STATS.bytes_appended - bytes_mark)
            bytes_mark = REPO_STATS.bytes_appended
        ingest_wall = time.time() - ingest_t0
        n_saves = n_tenants * n_dates
        assert halves[0] > 0, (
            "repository violation: no bytes appended — the append gate "
            "is measuring an unpersisted repository (vacuous 0 <= 0)"
        )
        assert halves[1] <= halves[0] * 1.05, (
            f"repository violation: append cost grew with history "
            f"({halves[0]}B -> {halves[1]}B across {n_saves} saves) — "
            "the fs backend's quadratic wall is back"
        )
        assert MONITOR_STATS.alerts_emitted - alerts_before == 1, (
            "repository violation: the scripted completeness spike did not "
            "emit exactly one online QualityAlert at ingest time"
        )

        query = RepositoryQuery(
            metric_name="Completeness", instance="a",
            after=2, before=n_dates - 3,
            aggregates=("count", "mean", "min", "max"),
        )

        # compiled path: warm (compile) then best-of-3, one-fetch asserted
        run_repository_query(repo, query, plan_lint="error")
        fused_wall = float("inf")
        for _ in range(3):
            SCAN_STATS.reset()
            t0 = time.time()
            fused = run_repository_query(repo, query, plan_lint="error")
            fused_wall = min(fused_wall, time.time() - t0)
        assert SCAN_STATS.device_fetches == 1, (
            f"repository violation: the compiled query paid "
            f"{SCAN_STATS.device_fetches} device fetches — one-fetch is the "
            "scan contract, repository table included"
        )
        enc_bytes = SCAN_STATS.bytes_packed

        # decoded A/B of the SAME compiled query: the PR-8 staging gate
        SCAN_STATS.reset()
        decoded = run_repository_query(repo, query, encoded_ingest=False)
        dec_bytes = SCAN_STATS.bytes_packed
        assert enc_bytes * 2 <= dec_bytes, (
            f"repository violation: encoded query staged {enc_bytes}B vs "
            f"{dec_bytes}B decoded — the >=2x dictionary-encoding win is gone"
        )

        # loader-side baseline: the pre-columnar answer, timed once (it is
        # the slow path by construction) and required BIT-identical
        t0 = time.time()
        baseline = loader_side_aggregates(repo, query)
        loader_wall = time.time() - t0
        assert fused.rows == baseline.rows
        for name, value in fused.aggregates.items():
            assert bits(value) == bits(baseline.aggregates[name]), (
                f"repository violation: compiled query {name}="
                f"{value!r} != loader-side {baseline.aggregates[name]!r} — "
                "the two paths must be BIT-identical"
            )
        for name, value in decoded.aggregates.items():
            assert bits(value) == bits(fused.aggregates[name])

        import deequ_tpu

        section = deequ_tpu.execution_report()["repository"]
        return {
            "repository_query_rows": fused.rows,
            "repository_query_wall_ms": round(fused_wall * 1000, 2),
            "repository_loader_side_wall_ms": round(loader_wall * 1000, 2),
            "repository_query_speedup_x": round(
                loader_wall / max(fused_wall, 1e-9), 1
            ),
            "repository_ingest_saves_per_sec": round(
                n_saves / max(ingest_wall, 1e-9), 1
            ),
            "repository_staged_bytes_encoded": int(enc_bytes),
            "repository_staged_bytes_decoded": int(dec_bytes),
            "repository_saves": section["saves"],
            "repository_segments_written": section["segments_written"],
            "repository_query_scan_passes": section["query_scan_passes"],
            "repository_alerts_emitted": section["alerts_emitted"],
        }
    finally:
        shutil.rmtree(repo_dir, ignore_errors=True)


def measure_windowed_stream(n_streams: int = 1000, n_batches: int = 4):
    """Continuous windowed verification probe (round 20,
    deequ_tpu/windows: the window fold axis + watermark close protocol
    under a ~1k-stream tenant fleet).

    Hard gates — the probe REFUSES to report (AssertionError) unless:

    - O(1) DISPATCHES PER BATCH: every stream's batch advances ALL of
      its open panes in exactly ONE device dispatch (``pane_dispatches``
      == streams x batches, including a sliding stream holding 4
      concurrently-open panes), and the whole fleet shares a handful of
      traced pane programs (``programs_built`` bounded by pane-bucket
      shapes, NOT by stream count);
    - BIT-IDENTITY: sampled streams' emitted windows are bit-identical
      (exact float-bit compare) to one-shot VerificationSuite runs over
      exactly those windows' rows;
    - CLOSE LATENCY UNDER LOAD: with the hub's overload level RAISED,
      on-time closes keep emitting (zero critical sheds, zero sheds at
      all for on-time closes) and the p99 close-batch wall stays under
      the 250ms SLO;
    - EXACTLY-ONCE THROUGH KILL-AND-RESUME: a scripted mid-window kill
      (hub rebuilt from the window-state store, twice) delivers every
      window close exactly once — alert deliveries match the
      uninterrupted reference with zero duplicates."""
    import shutil
    import struct
    import tempfile

    from deequ_tpu.analyzers import Completeness, Maximum, Mean, Minimum, Size
    from deequ_tpu.data.table import ColumnarTable
    from deequ_tpu.obs.registry import REGISTRY
    from deequ_tpu.serve.admission import Slo
    from deequ_tpu.verification import VerificationSuite
    from deequ_tpu.windows import (
        WINDOW_STATS,
        StreamHub,
        WatermarkPolicy,
        WindowSpec,
        WindowedStream,
        clear_program_cache,
    )

    analyzers = [Size(), Completeness("v"), Mean("v"), Minimum("v"), Maximum("v")]
    spec = WindowSpec(10.0, 10.0)
    policy = WatermarkPolicy(2.0, "drop")
    rows = 32

    def bits(v):
        return struct.pack("<d", float(v))

    def metric_rows(result):
        out = {}
        for analyzer, metric in result.metrics.items():
            assert metric.value.is_success, f"{analyzer} failed"
            out[str(analyzer)] = bits(metric.value.get())
        return out

    def stream_batches(si):
        rng = np.random.default_rng(20_000 + si)
        out = []
        for b in range(n_batches):
            ts = np.sort(rng.uniform(b * 5.0, (b + 1) * 5.0, rows))
            v = np.floor(rng.uniform(-40.0, 41.0, rows))
            v[rng.uniform(0.0, 1.0, rows) < 0.1] = np.nan
            out.append({"ts": ts, "v": v})
        return out

    # warm the pane programs out of the timed section (compile is a
    # one-time fleet cost, shared via the program cache)
    clear_program_cache()
    warm = WindowedStream("warm", analyzers, spec=spec, policy=policy)
    for batch in stream_batches(0):
        warm.process_batch(batch)
    warm.flush()

    # -- A: the fleet under raised overload, one dispatch per batch ------
    classes = ("critical", "standard", "best_effort")
    hub = StreamHub()
    hub.set_overload(1)  # brownout raised: on-time closes must survive
    for si in range(n_streams):
        hub.register_stream(
            f"s{si:04d}", analyzers,
            slo=Slo(deadline_ms=20_000.0, cls=classes[si % 3]),
            spec=spec, policy=policy,
        )
    before = WINDOW_STATS.snapshot()
    batch_walls = []
    emitted = 0
    t0 = time.time()
    for si in range(n_streams):
        sid = f"s{si:04d}"
        for batch in stream_batches(si):
            bt0 = time.time()
            closes = hub.process_batch(sid, batch)
            batch_walls.append(time.time() - bt0)
            emitted += sum(1 for c in closes if c.emitted)
    wall = time.time() - t0
    snap = WINDOW_STATS.snapshot()

    dispatches = snap["pane_dispatches"] - before["pane_dispatches"]
    assert dispatches == n_streams * n_batches, (
        f"O(1)-dispatch regression: {dispatches} dispatches for "
        f"{n_streams * n_batches} stream-batches"
    )
    built = snap["programs_built"]
    assert built <= 4, (
        f"program-cache regression: {built} pane programs traced for "
        f"{n_streams} streams sharing one (signature, geometry, shape)"
    )
    assert not hub.sheds, (
        f"{len(hub.sheds)} on-time closes shed under overload — sheds are "
        "for LATE closes only"
    )
    assert emitted >= n_streams, "fleet closed fewer windows than streams"
    batch_walls.sort()
    p99_ms = batch_walls[int(0.99 * (len(batch_walls) - 1))] * 1000.0
    assert p99_ms < 250.0, f"close-batch p99 {p99_ms:.1f}ms breaches 250ms SLO"

    # a sliding stream holding 4 open panes still pays ONE dispatch/batch
    slide_before = WINDOW_STATS.snapshot()["pane_dispatches"]
    slider = WindowedStream(
        "slider", analyzers, spec=WindowSpec(20.0, 5.0), policy=policy,
    )
    for batch in stream_batches(1):
        slider.process_batch(batch)
    assert len(slider.open_panes) >= 4
    slide_d = WINDOW_STATS.snapshot()["pane_dispatches"] - slide_before
    assert slide_d == n_batches, (
        f"sliding stream made {slide_d} dispatches for {n_batches} batches"
    )

    # -- B: sampled bit-identity vs one-shot suites ----------------------
    checked = 0
    for si in range(0, n_streams, max(1, n_streams // 5)):
        batches = stream_batches(si)
        probe = WindowedStream(f"id{si}", analyzers, spec=spec, policy=policy)
        closes = []
        for batch in batches:
            closes.extend(probe.process_batch(batch))
        closes.extend(probe.flush())
        ts = np.concatenate([b["ts"] for b in batches])
        v = np.concatenate([b["v"] for b in batches])
        for c in closes:
            if not c.emitted:
                continue
            keep = (ts >= c.start) & (ts < c.end)
            vals = [None if np.isnan(x) else float(x) for x in v[keep]]
            ref = (
                VerificationSuite()
                .on_data(ColumnarTable.from_pydict({"v": vals}))
                .add_required_analyzers(analyzers)
                .run()
            )
            assert metric_rows(c.result) == metric_rows(ref), (
                f"stream {si} window [{c.start},{c.end}) drifted from the "
                "one-shot suite — windows must be BIT-identical"
            )
            checked += 1
    assert checked >= 5

    # -- C: exactly-once alerts through a scripted double kill -----------
    class Recorder:
        def __init__(self):
            self.seen = []

        def observe_verification(self, stream_id, result):
            self.seen.append(stream_id)

    kr_streams = 8
    ref_monitor = Recorder()
    for si in range(kr_streams):
        probe = WindowedStream(
            f"kr{si}", analyzers, spec=spec, policy=policy, monitor=ref_monitor,
        )
        for batch in stream_batches(si):
            probe.process_batch(batch)
        probe.flush()

    state_root = tempfile.mkdtemp(prefix="bench_wstream_")
    try:
        monitor = Recorder()

        def new_hub():
            hub = StreamHub(
                monitor=monitor, state_root=state_root, checkpoint_every=2,
            )
            for si in range(kr_streams):
                hub.register_stream(
                    f"kr{si}", analyzers, spec=spec, policy=policy,
                    batch_rows=rows,
                )
            return hub

        khub = new_hub()
        resumes = 0
        for kill_at in (2, 3):  # mid-window on the 10s tumbling grid
            for si in range(kr_streams):
                sid = f"kr{si}"
                stream = khub.stream(sid)
                while stream.next_batch_index < kill_at:
                    khub.process_batch(
                        sid, stream_batches(si)[stream.next_batch_index]
                    )
            del khub  # kill: process state gone, window-state store survives
            khub = new_hub()
            resumes += 1
        for si in range(kr_streams):
            sid = f"kr{si}"
            stream = khub.stream(sid)
            while stream.next_batch_index < n_batches:
                khub.process_batch(
                    sid, stream_batches(si)[stream.next_batch_index]
                )
            stream.flush()
        assert sorted(monitor.seen) == sorted(ref_monitor.seen), (
            "kill-and-resume alert drift: "
            f"{len(monitor.seen)} deliveries vs {len(ref_monitor.seen)} "
            "reference — every window close must alert EXACTLY once"
        )
    finally:
        shutil.rmtree(state_root, ignore_errors=True)

    obs = REGISTRY.snapshot()["windows"]
    assert obs["active"] and obs["closes_emitted"] >= emitted

    return {
        "wstream_streams": n_streams,
        "wstream_closes_per_sec": round(emitted / max(wall, 1e-9), 1),
        "wstream_batches_per_sec": round(
            (n_streams * n_batches) / max(wall, 1e-9), 1
        ),
        "wstream_dispatches_per_batch": 1.0,
        "wstream_programs_built": int(built),
        "wstream_close_p99_ms": round(p99_ms, 2),
        "wstream_windows_emitted": int(emitted),
        "wstream_identity_windows_checked": int(checked),
        "wstream_resumes": int(resumes),
        "wstream_suppressed": int(
            WINDOW_STATS.snapshot()["closes_suppressed"]
        ),
    }


#: published peaks by ``device_kind`` (Google Cloud documentation, "TPU
#: v5e": 819 GB/s HBM) — a device that is not in the table prints no peak
HBM_PEAK_GB_PER_SEC = {"TPU v5 lite": 819.0, "TPU v5e": 819.0}


def main():
    # --smoke: pre-commit gate (<10s): same program shape at 100k rows,
    # asserts the fused scan still runs green end-to-end (the round-1
    # regression shipped because no cheap bench check existed)
    smoke = "--smoke" in sys.argv
    if "--process-fleet" in sys.argv:
        # the process-fleet probe spawns worker PROCESSES that need the
        # device, and a chip serves one process: it runs alone, from a
        # coordinator that never touches jax — never after the scan below
        print(json.dumps(measure_process_fleet(24 if smoke else 72)))
        return

    import jax

    import deequ_tpu  # noqa: F401 — enables x64, places the compile cache
    from deequ_tpu.analyzers.runner import AnalysisRunner
    from deequ_tpu.ops.scan_engine import SCAN_STATS

    # name the device before anything else: every number below is a
    # reading on THIS device and nothing else
    device = {
        "platform": jax.devices()[0].platform,
        "kind": jax.devices()[0].device_kind,
        "count": len(jax.devices()),
    }
    print(f"device: {json.dumps(device)}", file=sys.stderr)
    if device["platform"] != "tpu" and not smoke:
        raise SystemExit(
            "bench.py: refusing to report "
            "resident_profile_scan_10Mx20_rows_per_sec from platform "
            f"{device['platform']!r}: a CPU timing is not a speed of this "
            "system (only `--smoke`, a correctness gate, runs without a TPU)"
        )
    n_rows = SMOKE_ROWS if smoke else N_ROWS
    table = build_table(n_rows)
    analyzers = build_analyzers()

    # The Spark local[32] estimate (~1M rows/s) is for a fused aggregation
    # over an IN-MEMORY DataFrame (Spark caches the scan input; its job
    # timing excludes the initial load). The like-for-like TPU measurement
    # is therefore the device-resident scan: persist() ships the table to
    # HBM once (untimed, analogous to df.cache()), the timed run streams
    # from HBM.
    table.persist()

    # warmup: compile the fused program with the persisted chunk geometry
    AnalysisRunner.do_analysis_run(table, analyzers)

    # best of 3 identical runs (ROADMAP A0 replaces this with medians and
    # quartiles of >= 10 readings)
    reps = 1 if smoke else 3
    wall = float("inf")
    snap = None
    for _ in range(reps):
        SCAN_STATS.reset()
        t0 = time.time()
        ctx = AnalysisRunner.do_analysis_run(table, analyzers)
        rep_wall = time.time() - t0
        if rep_wall < wall:
            # keep the breakdown of the SAME rep the headline wall comes
            # from, so drain-wait fractions are internally consistent
            wall = rep_wall
            snap = SCAN_STATS.snapshot()

    # measured fetch-latency floor: ONE trivial dispatch+fetch round trip —
    # the hard lower bound any single scan pays on this host<->device link
    import jax.numpy as jnp

    probe = jax.jit(lambda a: a * 2.0)
    arg = jnp.ones((8,), jnp.float32)
    np.asarray(probe(arg))
    t0 = time.time()
    np.asarray(probe(arg))
    floor = time.time() - t0
    print(
        f"dispatch+fetch floor: {floor*1000:.3f}ms (caps 10M rows at "
        f"{10_000_000/max(floor,1e-9)/1e6:.0f}M rows/s regardless of compute)",
        file=sys.stderr,
    )

    n_failed = sum(1 for m in ctx.all_metrics() if m.value.is_failure)
    assert n_failed == 0, f"{n_failed} metrics failed"
    assert snap["scan_passes"] == 1, "fusion regression: expected 1 pass"
    assert snap["resident_passes"] == 1, "resident-path regression"
    assert snap["bytes_packed"] == 0, "unexpected host re-transfer"
    # the one-fetch-per-scan contract: every op of this workload is
    # device-foldable, so the whole fused pass materializes exactly one
    # device->host result regardless of chunk count
    assert snap["device_fetches"] == 1, (
        "one-fetch contract regression: "
        f"{snap['device_fetches']} fetches for 1 scan pass"
    )

    rows_per_sec = n_rows / wall
    # floor-normalized telemetry: the link's fetch floor is a property of
    # the machine, compute above it is the engine work runs can compare
    fetch_floor_ms = round(floor * 1000, 2)
    compute_above_floor_ms = round(max(wall - floor, 0.0) * 1000, 2)
    # total link traffic both ways: host->device packing (0 on the
    # resident path, asserted above) + device->host result fetches
    bytes_shipped = int(snap["bytes_packed"]) + int(snap["bytes_fetched"])
    # fetch-floor amortization record: fetches per fused pass (the
    # one-fetch contract) and the fraction of wall spent blocked on the
    # device
    device_fetches_per_scan = round(
        snap["device_fetches"] / max(snap["scan_passes"], 1), 3
    )
    drain_wait_frac = round(
        min(snap["drain_wait_seconds"] / max(wall, 1e-9), 1.0), 4
    )
    # execution breakdown to stderr (the driver parses stdout's single line)
    print(
        f"breakdown: wall={wall:.3f}s dispatch={snap['dispatch_seconds']:.3f}s "
        f"drain_wait={snap['drain_wait_seconds']:.3f}s "
        f"device_fetches={snap['device_fetches']} "
        f"bytes_resident={snap['bytes_resident']/1e9:.2f}GB "
        f"effective={(snap['bytes_packed'] + snap['bytes_resident']) / max(snap['scan_seconds'], 1e-9)/1e9:.1f}GB/s"
        + (
            f" ({device['kind']} HBM peak "
            f"{HBM_PEAK_GB_PER_SEC[device['kind']]:.0f}GB/s)"
            if device["kind"] in HBM_PEAK_GB_PER_SEC else ""
        ),
        file=sys.stderr,
    )
    # resilience-layer cost probes (small: 1/50th of the main config)
    ckpt_probe = measure_checkpoint_overhead(SMOKE_ROWS if smoke else 200_000)
    print(f"checkpoint probe: {ckpt_probe}", file=sys.stderr)
    oom_probe = measure_oom_bisection_overhead(SMOKE_ROWS if smoke else 200_000)
    print(f"oom bisection probe: {oom_probe}", file=sys.stderr)
    reshard_probe = measure_reshard_overhead(SMOKE_ROWS if smoke else 200_000)
    print(f"reshard probe: {reshard_probe}", file=sys.stderr)
    select_probe = measure_config3_selection(
        SMOKE_ROWS if smoke else 200_000
    )
    print(f"config-3 selection probe: {select_probe}", file=sys.stderr)
    # plan-lint cost + memoization contract on the ALREADY-WARMED
    # resident profile table (no extra data gen; the probe's unlinted
    # baseline reuses the compiled program)
    lint_probe = measure_plan_lint_overhead(table, analyzers)
    print(f"plan-lint probe: {lint_probe}", file=sys.stderr)
    # columnar-ingest probe (round 8): streaming config-5 shape, encoded
    # vs raw staging + overlap contract
    ingest_probe = measure_ingest_overlap(
        n_batches=4 if smoke else 8,
        batch_rows=SMOKE_ROWS // 4 if smoke else 100_000,
    )
    print(f"ingest probe: {ingest_probe}", file=sys.stderr)
    # run-governance probe (round 9): the healthy config-1 shape under an
    # armed RunBudget must cost <1% of wall and charge nothing (asserted
    # inside the probe)
    governance_probe = measure_governance_overhead(
        SMOKE_ROWS if smoke else 200_000
    )
    print(f"governance probe: {governance_probe}", file=sys.stderr)
    # serving-layer probe (round 10): the 1k-tenant open-loop load with
    # the bit-identity / zero-trace / one-fetch-per-batch / >=5x gates
    # asserted inside
    serving_probe = measure_serving_load(200 if smoke else 1000)
    print(f"serving probe: {serving_probe}", file=sys.stderr)
    # fleet probe (round 12): routed multi-worker load + scripted-death
    # failover with the degrades-only-in-flight / bit-identity /
    # exactly-once gates asserted inside (the near-linear scaling gate
    # arms itself only on >= 4-device hardware)
    fleet_probe = measure_fleet_failover(48 if smoke else 144)
    print(f"fleet probe: {fleet_probe}", file=sys.stderr)
    # (the process-fleet probe is NOT run here: this process has touched
    # the device its worker processes would need — `--process-fleet`)
    # fencing probe (round 18): the same loopback-fleet load with epoch
    # fencing off vs on — the per-submit lease check must cost <1% of
    # healthy wall and reject nothing (asserted inside; a starved
    # scheduler banks a typed skip)
    fencing_probe = measure_fencing_overhead(12 if smoke else 24)
    print(f"fencing probe: {fencing_probe}", file=sys.stderr)
    # repository probe (round 13): columnar metric history, the compiled
    # fused-scan query vs the loader-side decode A/B (bit-identity /
    # one-fetch / >=2x encoded staging / O(result) append / online-alert
    # gates asserted inside)
    repo_probe = measure_repository_query(12 if smoke else 48)
    print(f"repository probe: {repo_probe}", file=sys.stderr)
    # kernel-variant probe (round 14): scatter vs one-hot-matmul vs
    # pallas histogram tier — exactness / plan-lint / one-fetch /
    # no-CPU-regression / >=1.2x gates asserted inside; the chip-side
    # >=2x acceptance banks as pending-parallel-hw on CPU sessions
    kernel_probe = measure_kernel_ab(smoke=smoke)
    print(f"kernel A/B probe: {kernel_probe}", file=sys.stderr)
    # round-20 windowed-verification probe (one-dispatch-per-batch /
    # shared programs / bit-identity / exactly-once resume asserted)
    wstream_probe = measure_windowed_stream(48 if smoke else 192)
    print(f"windowed-stream probe: {wstream_probe}", file=sys.stderr)
    ckpt_probe = {
        **ckpt_probe, **oom_probe, **reshard_probe, **select_probe,
        **lint_probe, **ingest_probe, **governance_probe,
        **serving_probe, **fleet_probe, **fencing_probe,
        **repo_probe, **kernel_probe, **wstream_probe,
    }

    if smoke:
        print(
            json.dumps(
                {
                    "metric": "smoke_profile_scan_100kx20_ok",
                    "device": device,
                    "value": round(rows_per_sec, 1),
                    "unit": "rows/sec",
                    "vs_baseline": 1.0,
                    "fetch_floor_ms": fetch_floor_ms,
                    "compute_above_floor_ms": compute_above_floor_ms,
                    "bytes_shipped": bytes_shipped,
                    "device_fetches_per_scan": device_fetches_per_scan,
                    "drain_wait_frac": drain_wait_frac,
                    **ckpt_probe,
                }
            )
        )
        return
    print(
        f"legacy vs Spark-local[32] ESTIMATE (rounds 1-3 denominator): "
        f"{rows_per_sec / SPARK_LOCAL32_ROWS_PER_SEC:.1f}x",
        file=sys.stderr,
    )
    print(
        json.dumps(
            {
                "metric": "resident_profile_scan_10Mx20_rows_per_sec",
                "device": device,
                "value": round(rows_per_sec, 1),
                "unit": "rows/sec",
                "vs_baseline": round(rows_per_sec / CPU_MEASURED_ROWS_PER_SEC, 3),
                "fetch_floor_ms": fetch_floor_ms,
                "compute_above_floor_ms": compute_above_floor_ms,
                "bytes_shipped": bytes_shipped,
                "device_fetches_per_scan": device_fetches_per_scan,
                "drain_wait_frac": drain_wait_frac,
                **ckpt_probe,
            }
        )
    )


if __name__ == "__main__":
    main()
