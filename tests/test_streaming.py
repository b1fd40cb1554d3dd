"""Out-of-core streaming ingestion: a StreamingTable must produce the same
metrics as the materialized table (the monoid fold across batches IS the
monoid fold across partitions/devices), with host memory bounded by the
batch size — the TB-scale design intent of the reference
(profiles/ColumnProfiler.scala:57-68)."""

import os

import numpy as np
import pytest

from deequ_tpu.analyzers import (
    ApproxCountDistinct,
    Completeness,
    CountDistinct,
    DataType,
    Distinctness,
    Entropy,
    Histogram,
    KLLSketch,
    Maximum,
    Mean,
    Minimum,
    MutualInformation,
    PatternMatch,
    Size,
    StandardDeviation,
    Sum,
    Uniqueness,
)
from deequ_tpu.analyzers.runner import AnalysisRunner
from deequ_tpu.data.io import stream_parquet, write_parquet, write_parquet_stream
from deequ_tpu.data.streaming import StreamingTable, stream_table
from deequ_tpu.data.table import ColumnarTable


@pytest.fixture(scope="module")
def mixed_table():
    rng = np.random.default_rng(11)
    n = 30_000
    v = rng.normal(10.0, 3.0, n)
    mask_holes = rng.integers(0, n, n // 50)
    vals = [None if i in set(mask_holes.tolist()) else float(x)
            for i, x in enumerate(v)]
    return ColumnarTable.from_pydict({
        "id": list(range(n)),
        "v": vals,
        "cat": [f"c{i % 13}" for i in range(n)],
        "email": [
            "a@b.com" if i % 3 == 0 else "nope" for i in range(n)
        ],
    })


ANALYZERS = [
    Size(),
    Completeness("v"),
    Mean("v"),
    Sum("v"),
    Minimum("v"),
    Maximum("v"),
    StandardDeviation("v"),
    ApproxCountDistinct("id"),
    DataType("email"),
    PatternMatch("email", r"^[a-z]+@[a-z]+\.[a-z]+$"),
    Uniqueness(["id"]),
    Distinctness(["cat"]),
    CountDistinct(["cat"]),
    Entropy("cat"),
    MutualInformation("cat", "email"),
]


def _values(ctx):
    out = {}
    for a, m in ctx.metric_map.items():
        assert m.value.is_success, (a, m.value)
        v = m.value.get()
        out[repr(a)] = v if isinstance(v, float) else repr(v)
    return out


def test_streamed_equals_materialized(mixed_table):
    batch = stream_table(mixed_table, batch_rows=7_000)  # uneven batches
    ctx_mem = AnalysisRunner.do_analysis_run(mixed_table, ANALYZERS)
    ctx_stream = AnalysisRunner.do_analysis_run(batch, ANALYZERS)
    mem, stream = _values(ctx_mem), _values(ctx_stream)
    assert set(mem) == set(stream)
    for k in mem:
        if isinstance(mem[k], float):
            assert mem[k] == pytest.approx(stream[k], rel=1e-9, nan_ok=True), k
        else:
            assert mem[k] == stream[k], k


def test_streamed_histogram_and_kll(mixed_table):
    stream = stream_table(mixed_table, batch_rows=9_000)
    h_mem = Histogram("cat").calculate(mixed_table).value.get()
    h_stream = Histogram("cat").calculate(stream).value.get()
    assert h_mem.values == h_stream.values
    assert h_mem.number_of_bins == h_stream.number_of_bins

    k_stream = KLLSketch("v").calculate(stream)
    assert k_stream.value.is_success
    dist = k_stream.value.get()
    # bucket counts must sum to the non-null count
    total = sum(b.count for b in dist.buckets)
    assert total == mixed_table["v"].num_valid


def test_parquet_round_trip_and_stream(tmp_path, mixed_table):
    path = str(tmp_path / "t.parquet")
    write_parquet(mixed_table, path, row_group_rows=8_192)
    stream = stream_parquet(path, batch_rows=6_000)
    assert stream.num_rows == mixed_table.num_rows
    assert set(stream.column_names) == set(mixed_table.column_names)

    ctx_mem = AnalysisRunner.do_analysis_run(mixed_table, ANALYZERS)
    ctx_pq = AnalysisRunner.do_analysis_run(stream, ANALYZERS)
    mem, pq = _values(ctx_mem), _values(ctx_pq)
    for k in mem:
        if isinstance(mem[k], float):
            assert mem[k] == pytest.approx(pq[k], rel=1e-9, nan_ok=True), k
        else:
            assert mem[k] == pq[k], k


def test_write_parquet_stream_bounded(tmp_path):
    """write_parquet_stream + stream_parquet: build a dataset bigger than
    any single batch without ever materializing it, then analyze it."""
    path = str(tmp_path / "big.parquet")
    n_batches, rows = 10, 5_000

    def gen():
        rng = np.random.default_rng(0)
        for i in range(n_batches):
            yield ColumnarTable.from_pydict({
                "x": list(rng.normal(float(i), 1.0, rows)),
                "k": list(range(i * rows, (i + 1) * rows)),
            })

    written = write_parquet_stream(gen(), path)
    assert written == n_batches * rows

    stream = stream_parquet(path, batch_rows=4_000)
    ctx = AnalysisRunner.do_analysis_run(
        stream, [Size(), Mean("x"), Uniqueness(["k"])]
    )
    vals = _values(ctx)
    assert vals[repr(Size())] == written
    assert vals[repr(Uniqueness(["k"]))] == 1.0
    # mean of batch means 0..9 = 4.5 (exact batch sizes equal)
    assert vals[repr(Mean("x"))] == pytest.approx(4.5, abs=0.05)


def test_streaming_table_never_materializes(mixed_table):
    """The guard: full-column access on a StreamingTable raises instead of
    silently materializing."""
    stream = stream_table(mixed_table)
    col = stream["v"]
    assert col.dtype.name == "FRACTIONAL"
    with pytest.raises(AttributeError, match="never materialized"):
        _ = col.values
    with pytest.raises(TypeError, match="cannot be persisted"):
        stream.persist()


def test_streaming_verification_suite(mixed_table):
    from deequ_tpu import Check, CheckLevel, VerificationSuite

    stream = stream_table(mixed_table, batch_rows=8_000)
    check = (
        Check(CheckLevel.ERROR, "stream")
        .has_size(lambda n: n == mixed_table.num_rows)
        .is_complete("id")
        .is_unique("id")
        .has_mean("v", lambda m: 9.5 < m < 10.5)
        .has_number_of_distinct_values("cat", lambda n: n == 13)
    )
    result = VerificationSuite.on_data(stream).add_check(check).run()
    assert result.status.name == "SUCCESS"


def test_streaming_profiler(tmp_path, mixed_table):
    """3-pass profiler over a Parquet stream: numeric stats, inferred types
    (string col of numbers cast per batch), low-cardinality histograms."""
    from deequ_tpu.profiles import ColumnProfiler

    n = 10_000
    rng = np.random.default_rng(5)
    t = ColumnarTable.from_pydict({
        "num": list(rng.normal(5.0, 1.0, n)),
        "numstr": [str(i % 997) for i in range(n)],
        "cat": [f"g{i % 7}" for i in range(n)],
    })
    path = str(tmp_path / "p.parquet")
    write_parquet(t, path, row_group_rows=2_048)

    profiles_mem = ColumnProfiler.profile(t)
    profiles_stream = ColumnProfiler.profile(stream_parquet(path, batch_rows=3_000))

    assert profiles_stream.num_records == n
    for name in ("num", "numstr", "cat"):
        pm = profiles_mem.profiles[name]
        ps = profiles_stream.profiles[name]
        assert pm.data_type == ps.data_type, name
        assert pm.completeness == ps.completeness, name
        assert (
            pm.approximate_num_distinct_values
            == ps.approximate_num_distinct_values
        ), name
    # numstr was inferred Integral -> numeric profile exists with stats
    ps = profiles_stream.profiles["numstr"]
    assert ps.mean == pytest.approx(
        profiles_mem.profiles["numstr"].mean, rel=1e-9
    )
    # cat is low-cardinality -> histogram present and equal
    assert (
        profiles_stream.profiles["cat"].histogram.values
        == profiles_mem.profiles["cat"].histogram.values
    )


def test_empty_stream():
    t = ColumnarTable.from_pydict({"x": [1.0, 2.0]}).head(0)
    stream = stream_table(t)
    ctx = AnalysisRunner.do_analysis_run(stream, [Size(), Completeness("x")])
    assert ctx.metric_map[Size()].value.get() == 0.0


def test_size_only_stream_counts_rows():
    """Row-count-only pruning regression (found by the round-9 chaos
    probes): a LONE Size() prunes the stream read to zero columns, and a
    zero-column batch cannot carry its row count — both streaming paths
    must read one column to keep batch geometry, never fold Size=0."""
    t = ColumnarTable.from_pydict({"x": [float(i) for i in range(97)]})
    # fused streaming engine
    ctx = AnalysisRunner.do_analysis_run(stream_table(t, 25), [Size()])
    assert ctx.metric_map[Size()].value.get() == 97.0
    # resilient per-batch loop (quarantine mode routes through it)
    ctx = AnalysisRunner.do_analysis_run(
        stream_table(t, 25), [Size()], on_batch_error="skip"
    )
    assert ctx.metric_map[Size()].value.get() == 97.0


def test_streaming_incremental_states(mixed_table):
    """Streaming + save_states_with: states persisted from a streamed run
    must merge with later batches exactly like materialized ones."""
    from deequ_tpu.states import InMemoryStateProvider

    half = mixed_table.num_rows // 2
    first = mixed_table.filter_rows(np.arange(mixed_table.num_rows) < half)
    second = mixed_table.filter_rows(np.arange(mixed_table.num_rows) >= half)

    analyzers = [Size(), Mean("v"), Uniqueness(["id"])]
    provider = InMemoryStateProvider()
    AnalysisRunner.do_analysis_run(
        stream_table(first, batch_rows=5_000), analyzers,
        save_states_with=provider,
    )
    ctx = AnalysisRunner.do_analysis_run(
        stream_table(second, batch_rows=5_000), analyzers,
        aggregate_with=provider,
    )
    full = AnalysisRunner.do_analysis_run(mixed_table, analyzers)
    for a in analyzers:
        assert ctx.metric_map[a].value.get() == pytest.approx(
            full.metric_map[a].value.get(), rel=1e-9
        ), a


def test_stream_csv_matches_read_csv(tmp_path):
    """Out-of-core CSV: streamed metrics equal the in-memory reader's on
    the same file (incl. type inference and nulls)."""
    from deequ_tpu.analyzers import Completeness, Mean, Size, Uniqueness
    from deequ_tpu.data.io import read_csv, stream_csv

    path = str(tmp_path / "t.csv")
    rng = np.random.default_rng(8)
    with open(path, "w") as f:
        f.write("id,score,grade\n")
        for i in range(20_000):
            score = "" if i % 97 == 0 else f"{rng.normal(70, 10):.4f}"
            f.write(f"{i},{score},g{i % 5}\n")

    analyzers = [Size(), Completeness("score"), Mean("score"), Uniqueness(["id"])]
    mem = AnalysisRunner.do_analysis_run(read_csv(path), analyzers)
    stream = AnalysisRunner.do_analysis_run(
        stream_csv(path, batch_rows=3_000), analyzers
    )
    for a in analyzers:
        vm = mem.metric_map[a].value.get()
        vs = stream.metric_map[a].value.get()
        assert vs == pytest.approx(vm, rel=1e-9), a

    # titanic.csv from the reference's test data also streams (skipped
    # where the external reference checkout is not mounted)
    titanic = "/root/reference/test-data/titanic.csv"
    if os.path.exists(titanic):
        t = stream_csv(titanic, batch_rows=256)
        ctx = AnalysisRunner.do_analysis_run(t, [Size(), Completeness("Age")])
        assert ctx.metric_map[Size()].value.get() == 891.0
        assert 0.7 < ctx.metric_map[Completeness("Age")].value.get() < 0.9


def test_stream_csv_null_and_widening_semantics(tmp_path):
    """read_csv parity cases the first CSV streamer got wrong (r3 review):
    empty string cells are null (and ONLY empty cells — 'NA' is data), and
    a type-widening value late in the file must not crash the stream."""
    from deequ_tpu.analyzers import Completeness, DataType, Mean, Size
    from deequ_tpu.data.io import read_csv, stream_csv

    path = str(tmp_path / "w.csv")
    with open(path, "w") as f:
        f.write("name,score\n")
        for i in range(50_000):
            f.write(f"user{i},{i}\n")
        f.write(",NA\n")          # empty name -> null; 'NA' score -> data
        f.write("z,3.5\n")        # float late in an int-so-far column

    analyzers = [Size(), Completeness("name"), Completeness("score")]
    mem = AnalysisRunner.do_analysis_run(read_csv(path), analyzers)
    stream = AnalysisRunner.do_analysis_run(
        stream_csv(path, batch_rows=8_000), analyzers
    )
    for a in analyzers:
        assert stream.metric_map[a].value.get() == pytest.approx(
            mem.metric_map[a].value.get(), rel=1e-12
        ), a
    # widened column is usable as numeric downstream
    st = stream_csv(path, batch_rows=8_000)
    assert st["score"].dtype.name == "STRING"  # 'NA' forces string, like read_csv


def test_stream_csv_multiblock_widening(tmp_path):
    """ADVICE r3 (high): the inference pass must survive a type-widening
    value PAST the first reader block. pyarrow's open_csv pins each
    column's type from its first ~4MB block, so the schema pass now reads
    every column as string and widens on host — a late '3.5' in an int
    column must widen to float, not raise ArrowInvalid."""
    from deequ_tpu.analyzers import Completeness, Mean, Size
    from deequ_tpu.data.io import read_csv, stream_csv

    path = str(tmp_path / "big.csv")
    with open(path, "w") as f:
        f.write("id,score,flag\n")
        # ~6MB: well past the 4MB inference block; int-looking until the end
        for i in range(400_000):
            f.write(f"{i},{i % 1000},true\n")
        f.write("400000,3.5,false\n")  # float only in the LAST block

    st = stream_csv(path, batch_rows=100_000)
    assert st["score"].dtype.name == "FRACTIONAL"
    assert st["flag"].dtype.name == "BOOLEAN"

    analyzers = [Size(), Completeness("score"), Mean("score")]
    mem = AnalysisRunner.do_analysis_run(read_csv(path), analyzers)
    stream = AnalysisRunner.do_analysis_run(st, analyzers)
    for a in analyzers:
        assert stream.metric_map[a].value.get() == pytest.approx(
            mem.metric_map[a].value.get(), rel=1e-12
        ), a


def test_prefetch_delivers_late_exception():
    """ADVICE r3 (medium): a reader-thread exception raised while the
    queue is full must reach the consumer even when the consumer takes
    longer than any single put timeout to free a slot (previously the
    1s-timeout put dropped the exception and the consumer hung forever)."""
    import time

    from deequ_tpu.ops.scan_engine import _prefetch

    def source():
        yield 1
        yield 2  # fills the depth-1 queue while the consumer sleeps
        raise RuntimeError("reader died")

    gen = _prefetch(source(), depth=1)
    assert next(gen) == 1
    time.sleep(1.5)  # consumer stalls past the old 1.0s put timeout
    assert next(gen) == 2
    with pytest.raises(RuntimeError, match="reader died"):
        next(gen)


def test_parquet_source_rejects_schema_mismatch(tmp_path):
    """ADVICE r3 (low): a later file with a different schema fails at
    construction with a clear error, not deep inside packing."""
    from deequ_tpu.data.source import ParquetBatchSource

    a = str(tmp_path / "a.parquet")
    b = str(tmp_path / "b.parquet")
    write_parquet(ColumnarTable.from_pydict({"x": [1, 2], "y": [1.0, 2.0]}), a)
    write_parquet(ColumnarTable.from_pydict({"x": [1, 2], "y": ["s", "t"]}), b)
    ParquetBatchSource([a, a])  # identical schemas are fine
    with pytest.raises(ValueError, match="schema mismatch"):
        ParquetBatchSource([a, b])


def test_kll_midscan_compaction_bounds_gather():
    """ADVICE r3 (medium): gathered KLL summaries fold into a bounded
    sketch mid-scan instead of accumulating one summary per chunk on
    host. Quantiles with compaction must track the uncompacted fold."""
    from deequ_tpu.analyzers.sketches import _make_kll_compact
    from deequ_tpu.ops.kll_device import fold_summaries

    rng = np.random.default_rng(5)
    k = 256
    # simulate 64 gathered chunk summaries of 64 weight-4 strata each
    items = rng.normal(50.0, 10.0, (64, 64)).ravel()
    weights = np.full(64 * 64, 4.0)
    result = {"items": items, "weights": weights,
              "count": np.float64(items.size * 4), "min": items.min(),
              "max": items.max()}

    compacted = _make_kll_compact(1, k)(result)
    assert compacted["items"].size < items.size  # actually bounded
    assert compacted["weights"].sum() == weights.sum()  # total weight exact

    ref = fold_summaries(items, weights, k, 0.64)
    got = fold_summaries(compacted["items"], compacted["weights"], k, 0.64)
    assert got.count == ref.count
    for q in (0.1, 0.5, 0.9):
        # both are ~1/k-accurate rank estimates of the same stream
        assert abs(got.quantile(q) - ref.quantile(q)) < 2.0


def test_kll_multi_compact_preserves_extraction_layout():
    """Coalesced (batched) KLL ops gather (n_chunks*K, T) blocks and
    extract column j at rows j::K — compaction must preserve that layout
    and the trailing dim so later chunks still concatenate."""
    from deequ_tpu.analyzers.sketches import (
        _kll_multi_extract,
        _make_kll_compact,
    )
    from deequ_tpu.ops.kll_device import fold_summaries

    rng = np.random.default_rng(6)
    K, T, chunks, k = 3, 32, 40, 128
    # column j's values centered at 100*j so mixing layouts is detectable
    items = np.zeros((chunks * K, T))
    weights = np.full((chunks * K, T), 2.0)
    for j in range(K):
        items[j::K] = rng.normal(100.0 * (j + 1), 5.0, (chunks, T))
    result = {"items": items, "weights": weights,
              "count": np.full(K, chunks * T * 2.0),
              "min": items.min(axis=0), "max": items.max(axis=0),
              "summaries": np.full(K, chunks)}

    compacted = _make_kll_compact(K, k)(result)
    assert compacted["items"].shape[-1] == T  # trailing dim preserved
    assert compacted["items"].shape[0] % K == 0
    assert compacted["items"].shape[0] < chunks * K
    for j in range(K):
        ex = _kll_multi_extract(compacted, j, K)
        sk = fold_summaries(ex["items"], ex["weights"], k, 0.64)
        # median lands near column j's center -> layout survived
        assert abs(sk.quantile(0.5) - 100.0 * (j + 1)) < 5.0
        assert sk.count == chunks * T * 2


def test_kll_compaction_in_streaming_scan(tmp_path):
    """End-to-end: the _PartialFolder applies op.compact during a
    many-chunk streaming scan (threshold lowered to force it), and the
    resulting quantiles match the uncompacted scan closely."""
    from deequ_tpu.analyzers.sketches import _kll_scan_op, _kll_state_from_result
    from deequ_tpu.ops.scan_engine import run_scan

    rng = np.random.default_rng(7)
    n = 60_000
    table = ColumnarTable.from_pydict({"v": rng.normal(0.0, 1.0, n).tolist()})
    path = str(tmp_path / "v.parquet")
    write_parquet(table, path)

    def scan(threshold):
        st = stream_parquet(path, batch_rows=2_000)
        op = _kll_scan_op(st, "v", 256)
        if threshold is not None:
            op.compact_threshold = threshold
        (result,) = run_scan(st, [op], chunk_rows=2_000)
        return _kll_state_from_result(result, 256, 0.64)

    compacted = scan(threshold=2_000)   # forces many mid-scan folds
    plain = scan(threshold=None)
    assert compacted.sketch.count == plain.sketch.count == n
    for q in (0.05, 0.5, 0.95):
        assert abs(compacted.sketch.quantile(q) - plain.sketch.quantile(q)) < 0.1


def test_parquet_source_mismatch_scoped_to_selected_columns(tmp_path):
    """The per-file schema check only covers SELECTED columns, by name:
    extra/reordered unselected columns in a later file stream fine."""
    from deequ_tpu.data.source import ParquetBatchSource

    a = str(tmp_path / "a.parquet")
    b = str(tmp_path / "b.parquet")
    write_parquet(ColumnarTable.from_pydict({"x": [1, 2], "y": [1.0, 2.0]}), a)
    write_parquet(ColumnarTable.from_pydict({"y": ["s"], "x": [3]}), b)
    src = ParquetBatchSource([a, b], columns=["x"])  # 'y' differs; unselected
    total = sum(batch.num_rows for batch in src.batches())
    assert total == 3
    with pytest.raises(ValueError, match="schema mismatch"):
        ParquetBatchSource([a, b])  # selecting 'y' too -> type conflict


def test_kll_compact_all_null_column_bounded():
    """An all-null/fully-filtered KLL column must not keep growing its
    zero-weight padding through compaction (review r4 finding)."""
    from deequ_tpu.analyzers.sketches import _make_kll_compact

    result = {"items": np.zeros(10_000), "weights": np.zeros(10_000),
              "count": np.float64(0), "min": np.inf, "max": -np.inf}
    compacted = _make_kll_compact(1, 256)(result)
    assert compacted["items"].size == 0
    assert compacted["weights"].size == 0


def test_stream_csv_bool_mixed_literal_parity(tmp_path):
    """A bool column mixing '1'/'true' literals: pyarrow read_csv infers
    BOOLEAN (int64 fails on 'true', bool literal set includes '1'), and
    stream_csv must agree (round-4 review finding)."""
    p = tmp_path / "mixed_bool.csv"
    rows = ["b"] + ["true", "1", "false", "0", "TRUE"] * 200
    p.write_text("\n".join(rows) + "\n")

    from deequ_tpu.data.io import read_csv
    from deequ_tpu.data.io import stream_csv
    from deequ_tpu.data.table import DType

    batch_table = read_csv(str(p))
    stream = stream_csv(str(p))
    assert batch_table.schema["b"].dtype == DType.BOOLEAN
    assert stream.schema["b"].dtype == DType.BOOLEAN

    from deequ_tpu.analyzers import Completeness, Size
    from deequ_tpu.analyzers.runner import AnalysisRunner

    sctx = AnalysisRunner.do_analysis_run(stream, [Size(), Completeness("b")])
    bctx = AnalysisRunner.do_analysis_run(batch_table, [Size(), Completeness("b")])
    assert sctx.metric_map[Size()].value.get() == bctx.metric_map[Size()].value.get()
    assert (
        sctx.metric_map[Completeness("b")].value.get()
        == bctx.metric_map[Completeness("b")].value.get()
    )


def test_billion_row_proof_harness_scaled():
    """The committed 1B-row proof harness (benchmarks/BILLION_ROW_PROOF.md)
    must keep passing at a scaled size: segmented incremental == one-pass
    streaming, RSS bound asserted internally."""
    import os
    import sys

    root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    sys.path.insert(0, os.path.join(root, "benchmarks"))
    import billion_row_proof

    argv = sys.argv
    try:
        sys.argv = [
            "p", "--rows", "8000000", "--segments", "4",
            "--batch-rows", "1000000",
        ]
        billion_row_proof.main()
    finally:
        sys.argv = argv


def test_stream_state_folding_is_tree_shaped():
    """All three stream-fold sites use the mergesort-style tree: with B
    batches each state merges O(log B) times, never into a full-size
    accumulator per batch (the linear chain measured HOURS at config-4
    spec scale). Verified by counting .sum() calls on a spy state."""
    from deequ_tpu.analyzers.base import StreamStateFolder

    class Spy:
        merges = 0

        def __init__(self, depth=0):
            self.depth = depth

        def sum(self, other):
            Spy.merges += 1
            return Spy(max(self.depth, other.depth) + 1)

    B = 64
    folder = StreamStateFolder()
    for _ in range(B):
        folder.add(Spy())
    out = folder.result()
    # B-1 merges total (a full binary tree), depth log2(B), not B-1 deep
    assert Spy.merges == B - 1
    assert out.depth == 6  # log2(64)

    # None states (all-null batches) are skipped
    folder2 = StreamStateFolder()
    folder2.add(None)
    assert folder2.result() is None


def test_histogram_on_stream_equals_materialized(mixed_table):
    """Histogram takes its own streaming pass (not the shared grouping
    path); the tree fold must produce the same distribution as the
    in-memory run (review finding: the linear chain lived on here)."""
    from deequ_tpu.analyzers import Histogram

    h = Histogram("cat")
    mem = h.calculate(mixed_table).value.get()
    stream = h.calculate(stream_table(mixed_table, batch_rows=7_000)).value.get()
    assert mem.number_of_bins == stream.number_of_bins
    assert {k: v.absolute for k, v in mem.values.items()} == {
        k: v.absolute for k, v in stream.values.items()
    }
