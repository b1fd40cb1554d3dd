"""Spec-scale out-of-core run records for BASELINE configs 3 and 4.

BASELINE.md demands 100M rows for config 3 (Correlation + ApproxQuantile
over 50 numeric columns) and config 4 (ApproxCountDistinct + Histogram +
Uniqueness over high-cardinality strings); earlier measured curves
stopped at 16M resident rows. The out-of-core streaming path exists precisely to decouple scale from
residency, so this harness proves each config at spec scale the
billion_row_proof.py way:

  - data arrives from a deterministic synthetic BatchSource (batch k
    regenerates from seed+k; nothing is materialized);
  - the dataset runs as SEGMENTS chained through
    ``aggregate_with``/``save_states_with`` (incremental), then ONCE as
    a single streaming pass (batch);
  - INCREMENTAL == BATCH asserted — exactly for the algebraic states
    (correlation moments, frequency tables), within documented rank
    error for quantile sketches (KLL merge trees differ by fold order);
  - host RSS sampled per segment; the frequency table of config 4 is
    inherently O(#distinct) host state (the reference's shuffle group-by
    materializes the same G rows cluster-wide), so its bound scales with
    G while config 3's stays flat.

Run on the CPU backend (the proof is about scale + correctness; per-pass
throughput on the TPU is not measured):

    JAX_PLATFORMS=cpu python benchmarks/config_scale_proof.py --config 3 --rows 100000000
    JAX_PLATFORMS=cpu python benchmarks/config_scale_proof.py --config 4 --rows 100000000

The harness prints its record; none is committed (the earlier ones were
taken through a link that no longer exists and were removed in PR 21).
"""

from __future__ import annotations

import argparse
import json
import os
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

import numpy as np  # noqa: E402


def rss_mb() -> float:
    with open("/proc/self/status") as f:
        for line in f:
            if line.startswith("VmRSS:"):
                return int(line.split()[1]) / 1024.0
    return float("nan")


def numeric_source(n_cols, total_rows, batch_rows, row_offset, seed):
    """Config-3 shape: 50 correlated f64 columns, regenerated per batch."""
    from deequ_tpu.data.source import BatchSource
    from deequ_tpu.data.table import Column, ColumnarTable, DType, Field, Schema

    class Synthetic(BatchSource):
        preferred_batch_rows = batch_rows

        @property
        def schema(self):
            return Schema([Field(f"c{i}", DType.FRACTIONAL) for i in range(n_cols)])

        @property
        def num_rows(self):
            return total_rows

        def batches(self, columns=None, batch_rows=None):
            names = columns or [f"c{i}" for i in range(n_cols)]
            for start in range(0, total_rows, Synthetic.preferred_batch_rows):
                n = min(Synthetic.preferred_batch_rows, total_rows - start)
                gbi = (row_offset + start) // Synthetic.preferred_batch_rows
                rng = np.random.default_rng(seed + gbi)
                base = rng.normal(0, 1, n)
                cols = []
                for name in names:
                    i = int(name[1:])
                    # per-column noise streams must be independent of
                    # which columns are requested: draw from a
                    # column-specific generator
                    crng = np.random.default_rng(seed + 7919 * (i + 1) + gbi)
                    cols.append(
                        Column(name, DType.FRACTIONAL,
                               values=base * (0.5 + 0.01 * i) + crng.normal(0, 1, n))
                    )
                yield ColumnarTable(cols)

    return Synthetic()


def string_source(total_rows, batch_rows, row_offset, seed, global_card):
    """Config-4 shape: one high-cardinality dictionary-encoded string
    column. ``global_card`` is the DATASET-wide id space (total/3): every
    segment draws from the same space so the segmented and single-pass
    streams see identical data."""
    from deequ_tpu.data.source import BatchSource
    from deequ_tpu.data.table import Column, ColumnarTable, DType, Field, Schema

    class Synthetic(BatchSource):
        preferred_batch_rows = batch_rows

        @property
        def schema(self):
            return Schema([Field("key", DType.STRING)])

        @property
        def num_rows(self):
            return total_rows

        def batches(self, columns=None, batch_rows=None):
            for start in range(0, total_rows, Synthetic.preferred_batch_rows):
                n = min(Synthetic.preferred_batch_rows, total_rows - start)
                gbi = (row_offset + start) // Synthetic.preferred_batch_rows
                rng = np.random.default_rng(seed + gbi)
                ids = rng.integers(0, global_card, n)
                uniq, codes = np.unique(ids, return_inverse=True)
                dictionary = np.char.add(
                    "id_", np.char.zfill(uniq.astype("U9"), 9)
                )
                yield ColumnarTable([
                    Column("key", DType.STRING,
                           codes=codes.astype(np.int32),
                           dictionary=dictionary)
                ])

    return Synthetic()


def run_config(config: int, total: int, segments: int, batch_rows: int,
               rss_limit_mb: float) -> None:
    from deequ_tpu.analyzers import (
        ApproxCountDistinct,
        ApproxQuantile,
        Correlation,
        Histogram,
        Uniqueness,
    )
    from deequ_tpu.analyzers.runner import AnalysisRunner
    from deequ_tpu.data.streaming import StreamingTable
    from deequ_tpu.states import InMemoryStateProvider

    seg_rows = total // segments
    assert total % segments == 0 and seg_rows % batch_rows == 0

    if config == 3:
        n_cols = 50
        analyzers = (
            [Correlation(f"c{2*i}", f"c{2*i+1}") for i in range(n_cols // 2)]
            + [ApproxQuantile(f"c{i}", 0.5) for i in range(n_cols)]
        )
        make = lambda rows, off: numeric_source(  # noqa: E731
            n_cols, rows, batch_rows, off, seed=300
        )
    elif config == 4:
        analyzers = [
            ApproxCountDistinct("key"), Histogram("key"), Uniqueness(("key",)),
        ]
        global_card = max(total // 3, 1)
        make = lambda rows, off: string_source(  # noqa: E731
            rows, batch_rows, off, seed=400, global_card=global_card
        )
    else:
        raise SystemExit("--config 3 or 4")

    states = InMemoryStateProvider()
    rss_curve = []
    t0 = time.time()
    rows_done = 0
    per_segment = []
    for seg in range(segments):
        src = make(seg_rows, seg * seg_rows)
        ctx = AnalysisRunner.do_analysis_run(
            StreamingTable(src), analyzers,
            aggregate_with=states, save_states_with=states,
        )
        per_segment.append(ctx)
        rows_done += seg_rows
        elapsed = time.time() - t0
        sample = {
            "segment": seg, "rows_done": rows_done,
            "elapsed_s": round(elapsed, 1),
            "rows_per_sec": round(rows_done / elapsed, 1),
            "rss_mb": round(rss_mb(), 1),
        }
        rss_curve.append(sample)
        print(json.dumps(sample), flush=True)
        assert sample["rss_mb"] < rss_limit_mb, sample
    wall = time.time() - t0
    inc = {
        a: per_segment[-1].metric_map[a].value.get() for a in analyzers
    }

    # batch: ONE streaming pass over the whole dataset
    t1 = time.time()
    batch_ctx = AnalysisRunner.do_analysis_run(
        StreamingTable(make(total, 0)), analyzers
    )
    batch_wall = time.time() - t1

    exact_mismatch = []
    sketch_gap = 0.0
    for a in analyzers:
        vi, vb = inc[a], batch_ctx.metric_map[a].value.get()
        if isinstance(a, ApproxQuantile):
            # KLL merge trees differ by fold order; both sketches carry
            # the same <=1% rank-error contract — compare within it.
            # Values are ~N(0, ~1.1): 1% of rank around the median is
            # ~0.03 in value.
            sketch_gap = max(sketch_gap, abs(vi - vb))
            if abs(vi - vb) > 0.05:
                exact_mismatch.append((str(a), vi, vb))
        elif isinstance(a, Histogram):
            di, db = vi, vb
            if di.number_of_bins != db.number_of_bins:
                exact_mismatch.append(
                    (str(a), di.number_of_bins, db.number_of_bins)
                )
        else:
            tol = 1e-9 * max(1.0, abs(vb)) if isinstance(vb, float) else 0
            if abs(vi - vb) > tol:
                exact_mismatch.append((str(a), vi, vb))
    assert not exact_mismatch, exact_mismatch[:5]

    print(json.dumps({
        "metric": f"config{config}_scale_proof",
        "rows": total,
        "segments": segments,
        "incremental_wall_s": round(wall, 1),
        "incremental_rows_per_sec": round(total / wall, 1),
        "batch_wall_s": round(batch_wall, 1),
        "batch_rows_per_sec": round(total / batch_wall, 1),
        "peak_rss_mb": round(max(s["rss_mb"] for s in rss_curve), 1),
        "rss_bound_mb": rss_limit_mb,
        "incremental_equals_batch": True,
        "max_quantile_gap": round(sketch_gap, 5),
    }), flush=True)


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--config", type=int, required=True)
    ap.add_argument("--rows", type=int, default=100_000_000)
    ap.add_argument("--segments", type=int, default=20)
    ap.add_argument("--batch-rows", type=int, default=1_000_000)
    ap.add_argument("--rss-limit-mb", type=float, default=None)
    args = ap.parse_args()
    # config 4's frequency table is inherently O(#distinct) host state;
    # config 3's states are O(1)
    default_limit = 6144.0 if args.config == 3 else 24576.0
    run_config(
        args.config, args.rows, args.segments, args.batch_rows,
        args.rss_limit_mb or default_limit,
    )


if __name__ == "__main__":
    main()
