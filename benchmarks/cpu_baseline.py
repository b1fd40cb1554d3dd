"""Measured single-host CPU baseline for the headline workload (config 2).

Round-2 review noted the Spark local[32] denominator (~1M rows/s) was a
documented ESTIMATE with no in-repo measurement. This script anchors it:
the same 105-metric workload (Size + per-column Completeness/Mean/StdDev/
Min/Max over 10M x 20 f64 + HLL distinct on 4 columns) implemented
directly in vectorized numpy — the fastest plausible single-threaded CPU
engine (no Python-per-row overhead, data already in RAM, single pass of
vectorized reductions per column).

Prints one JSON line {metric, value, unit, host_cpus}. Interpretation:
numpy on ONE core measures X rows/s; Spark local[32] on 32 cores with
whole-stage codegen lands within a small factor of 32x a single numpy
core for this embarrassingly-parallel scan — so the ~1M rows/s estimate
can be sanity-checked as (this measurement) x cores / JVM overhead.
"""

import json
import os
import time

import numpy as np

N_ROWS = 10_000_000
N_COLS = 20


def build():
    rng = np.random.default_rng(7)
    cols = []
    for i in range(N_COLS):
        values = rng.normal(100.0 + i, 5.0, N_ROWS)
        mask = np.ones(N_ROWS, dtype=np.bool_)
        mask[rng.integers(0, N_ROWS, N_ROWS // 100)] = False
        cols.append((values, mask))
    return cols


def hll_registers(values: np.ndarray, p: int = 9) -> np.ndarray:
    """Same HLL algebra as the engine, in numpy (uses the engine's own
    host-path kernels so the workload is identical)."""
    import sys

    sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
    from deequ_tpu.ops import hll as hll_ops

    idx, rank = hll_ops.idx_rank_numeric(values, p, np)
    valid = np.ones(len(values), dtype=bool)
    return hll_ops.registers_from_idx_rank(idx, rank, valid, p, np)


def run_once(cols) -> dict:
    out = {}
    for i, (values, mask) in enumerate(cols):
        n_valid = int(mask.sum())
        out[f"c{i}.completeness"] = n_valid / N_ROWS
        masked = np.where(mask, values, 0.0)
        s = masked.sum()
        out[f"c{i}.mean"] = s / n_valid
        d = np.where(mask, values - s / n_valid, 0.0)
        out[f"c{i}.std"] = float(np.sqrt((d * d).sum() / n_valid))
        out[f"c{i}.min"] = float(np.where(mask, values, np.inf).min())
        out[f"c{i}.max"] = float(np.where(mask, values, -np.inf).max())
    for i in range(4):
        values, mask = cols[i]
        out[f"c{i}.hll"] = hll_registers(values[mask])
    out["size"] = N_ROWS
    return out


def main():
    cols = build()
    run_once(cols)  # warm numpy caches
    t0 = time.time()
    run_once(cols)
    wall = time.time() - t0
    rows_per_sec = N_ROWS / wall
    print(
        json.dumps(
            {
                "metric": "cpu_numpy_profile_scan_10Mx20_rows_per_sec",
                "value": round(rows_per_sec, 1),
                "unit": "rows/sec",
                "wall_seconds": round(wall, 3),
                "host_cpus": os.cpu_count(),
            }
        )
    )




# -- measured CPU denominators for the remaining BASELINE configs ------------
#
# Round-5: every benchmark config gets a measured-vs-measured ratio
# (r4 verdict item 2). Each function mirrors its TPU config's metric set
# with the strongest plausible single-threaded vectorized-numpy kernels —
# exact bincount instead of HLL where exact counting is FASTER on CPU, so
# the denominator is conservative (biased toward the CPU).


def cpu_config1():
    """Config 1: Size + Completeness x2 + Uniqueness on titanic (891 rows).
    The parse is untimed (the TPU config times suite.run() on a parsed
    table)."""
    import sys

    sys.path.insert(
        0, os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    )
    from deequ_tpu.data.io import read_csv

    table = read_csv("/root/reference/test-data/titanic.csv")
    pid = table["PassengerId"]
    age = table["Age"]

    def run():
        n = table.num_rows
        size_ok = n == 891
        complete_pid = float(pid.mask.sum()) / n
        complete_age = float(age.mask.sum()) / n
        vals = pid.values[pid.mask]
        _, counts = np.unique(vals, return_counts=True)
        uniq = float((counts == 1).sum()) / max(len(counts), 1)
        return size_ok and complete_pid == 1.0 and complete_age > 0.7 and uniq == 1.0

    assert run()
    t0 = time.time()
    ok = run()
    wall = time.time() - t0
    assert ok
    print(json.dumps({
        "metric": "cpu_numpy_config1_titanic_verification_wall",
        "value": round(wall, 6), "unit": "seconds", "rows": table.num_rows,
    }))
    return wall


def cpu_config3(n_rows: int):
    """Config 3: 25 correlations + 50 medians over 50 f64 columns (same
    generator as run_configs.config3). Median via introselect
    (np.partition) — the engine-grade exact kernel; correlations via
    vectorized moment sums."""
    n_cols = 50
    rng = np.random.default_rng(42)
    base = rng.normal(0, 1, n_rows)
    cols = [
        base * (0.5 + 0.01 * i) + rng.normal(0, 1, n_rows)
        for i in range(n_cols)
    ]

    def run():
        out = {}
        for i in range(n_cols // 2):
            x, y = cols[2 * i], cols[2 * i + 1]
            mx, my = x.mean(), y.mean()
            dx, dy = x - mx, y - my
            out[f"corr{i}"] = float(
                (dx * dy).sum() / np.sqrt((dx * dx).sum() * (dy * dy).sum())
            )
        for i in range(n_cols):
            out[f"q{i}"] = float(np.quantile(cols[i], 0.5))
        return out

    run()  # warm
    t0 = time.time()
    run()
    wall = time.time() - t0
    print(json.dumps({
        "metric": "cpu_numpy_config3_corr_quantile_rows_per_sec",
        "value": round(n_rows / wall, 1), "unit": "rows/sec",
        "rows": n_rows, "wall_seconds": round(wall, 3),
    }))
    return n_rows / wall


def cpu_config4(n_rows: int):
    """Config 4: distinct count + histogram top-30 + uniqueness over a
    high-cardinality dictionary-encoded string column (same generator as
    run_configs.config4). Exact bincount beats HLL hashing on CPU, so
    this denominator is the FAST exact path."""
    rng = np.random.default_rng(43)
    cardinality = max(n_rows // 3, 1)
    codes = rng.integers(0, cardinality, n_rows).astype(np.int32)
    dictionary = np.array(
        [f"id_{i:09d}" for i in range(cardinality)], dtype=object
    )

    def run():
        counts = np.bincount(codes, minlength=cardinality)
        present = counts > 0
        distinct = int(present.sum())
        k = min(30, cardinality - 1)
        top = np.argpartition(-counts, k)[:30] if k > 0 else np.arange(cardinality)
        hist = {dictionary[j]: int(counts[j]) for j in top}
        singles = int((counts == 1).sum())
        uniqueness = singles / n_rows
        return distinct, hist, uniqueness

    run()  # warm
    t0 = time.time()
    run()
    wall = time.time() - t0
    print(json.dumps({
        "metric": "cpu_numpy_config4_distinct_histogram_rows_per_sec",
        "value": round(n_rows / wall, 1), "unit": "rows/sec",
        "rows": n_rows, "wall_seconds": round(wall, 3),
    }))
    return n_rows / wall


def cpu_config5(n_batches: int, batch_rows: int):
    """Config 5: incremental Size/Mean/StdDev over arriving batches with
    exact Chan state merges (same loop shape as run_configs.config5;
    batches pre-generated, the timed loop is scan + merge)."""
    rng = np.random.default_rng(44)
    batches = [
        rng.normal(100.0, 5.0, batch_rows) for _ in range(n_batches)
    ]

    def run():
        N, MU, M2 = 0.0, 0.0, 0.0
        series = []
        for v in batches:
            c = float(len(v))
            mu = float(v.mean())
            m2 = float(((v - mu) ** 2).sum())
            d = mu - MU
            tot = N + c
            MU = MU + d * c / tot if tot else mu
            M2 = M2 + m2 + d * d * N * c / tot if N else m2
            N = tot
            series.append(MU)
        return N, MU, M2, series

    run()  # warm
    t0 = time.time()
    run()
    wall = time.time() - t0
    total = n_batches * batch_rows
    print(json.dumps({
        "metric": "cpu_numpy_config5_incremental_rows_per_sec",
        "value": round(total / wall, 1), "unit": "rows/sec",
        "rows": total, "wall_seconds": round(wall, 3),
    }))
    return total / wall


def main_configs(argv):
    import argparse

    ap = argparse.ArgumentParser()
    ap.add_argument("--config", type=int, required=True)
    ap.add_argument("--rows", type=int, default=None)
    args = ap.parse_args(argv)
    if args.config == 1:
        cpu_config1()
    elif args.config == 2:
        main()
    elif args.config == 3:
        cpu_config3(args.rows or 4_000_000)
    elif args.config == 4:
        cpu_config4(args.rows or 4_000_000)
    elif args.config == 5:
        cpu_config5(50, (args.rows or 10_000_000) // 50)


if __name__ == "__main__":
    import sys as _sys

    if len(_sys.argv) > 1:
        main_configs(_sys.argv[1:])
    else:
        main()
