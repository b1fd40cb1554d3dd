"""Columnar ingest pipeline (round 8): dictionary-encoded device
residency + double-buffered host->device staging.

The contract under test: the ENCODED ingest path (int16 dictionary codes
+ dictionary + validity bitmap as the Column payload, decode fused into
the scan program as a gather) is bit-identical to the decoded path for
every analyzer family, ships >= 2x fewer host->device bytes on
dictionary-encodable columns, preserves the one-fetch contract, and
composes with the fault ladder (an OOM mid-encoded-scan demotes onto the
decoded path like PR 6's selection->sort re-plan). The double-buffered
stager's ``ingest_overlap_frac``/``bytes_staged`` observables are pinned
structurally (docs/ingest.md)."""

import os

import numpy as np
import pytest

from deequ_tpu.analyzers import (
    ApproxCountDistinct,
    ApproxQuantile,
    Completeness,
    Histogram,
    Maximum,
    Mean,
    Minimum,
    Size,
    StandardDeviation,
    Sum,
    Uniqueness,
)
from deequ_tpu.analyzers.runner import AnalysisRunner
from deequ_tpu.data.source import (
    ParquetBatchSource,
    batch_rows_for_schema,
)
from deequ_tpu.data.streaming import StreamingTable, stream_table
from deequ_tpu.data.table import (
    MAX_ENCODED_CARDINALITY,
    Column,
    ColumnarTable,
    ColumnChunk,
    DType,
    Field,
    Schema,
)
from deequ_tpu.ops.scan_engine import (
    SCAN_STATS,
    install_scan_fault_hook,
)
from deequ_tpu.ops.device_policy import DEVICE_HEALTH
from deequ_tpu.resilience import FaultInjectingScanHook

pytestmark = pytest.mark.ingest


@pytest.fixture(autouse=True)
def _encoded_default():
    """Tests pin the switch explicitly; make sure ambient env state
    can't leak between them."""
    prev = os.environ.pop("DEEQU_TPU_ENCODED_INGEST", None)
    yield
    if prev is None:
        os.environ.pop("DEEQU_TPU_ENCODED_INGEST", None)
    else:
        os.environ["DEEQU_TPU_ENCODED_INGEST"] = prev


def _metrics(ctx):
    out = {}
    for a, m in ctx.metric_map.items():
        assert m.value.is_success, (a, m.value)
        out[repr(a)] = m.value.get()
    return out


def _decoded_run(table, analyzers):
    os.environ["DEEQU_TPU_ENCODED_INGEST"] = "0"
    try:
        return _metrics(AnalysisRunner.do_analysis_run(table, analyzers))
    finally:
        os.environ.pop("DEEQU_TPU_ENCODED_INGEST")


# -- table shapes ------------------------------------------------------------


def _dict_heavy(n=20000, seed=11):
    """Low-cardinality fractional + integral columns (the encodable
    shape) next to a string column (already code-planed)."""
    rng = np.random.default_rng(seed)
    f = (rng.integers(0, 50, n) * 0.25 - 3.0).astype(np.float64)
    i = rng.integers(-20, 20, n)
    s_card = 30
    return ColumnarTable(
        [
            Column("f", DType.FRACTIONAL, values=f),
            Column("i", DType.INTEGRAL, values=i),
            Column(
                "s",
                DType.STRING,
                codes=rng.integers(0, s_card, n).astype(np.int32),
                dictionary=np.array([f"v{k}" for k in range(s_card)]),
            ),
        ]
    )


def _null_heavy(n=20000, seed=12):
    rng = np.random.default_rng(seed)
    f = (rng.integers(0, 25, n)).astype(np.float64) * 1.5
    mask = rng.random(n) > 0.6  # 60% null
    return ColumnarTable(
        [
            Column(
                "f", DType.FRACTIONAL, values=np.where(mask, f, 0.0),
                mask=mask,
            ),
        ]
    )


def _all_unique(n=5000, seed=13):
    rng = np.random.default_rng(seed)
    return ColumnarTable(
        [Column("f", DType.FRACTIONAL, values=rng.normal(size=n))]
    )


FAMILIES = [
    Size(),
    Completeness("f"),
    Mean("f"),
    StandardDeviation("f"),
    Minimum("f"),
    Maximum("f"),
    Sum("f"),                     # monoid family
    ApproxQuantile("f", 0.5),     # KLL family
    ApproxCountDistinct("f"),     # HLL family
    Histogram("f"),               # grouping family
]


# -- ColumnChunk / Column encoding ------------------------------------------


def test_column_chunk_roundtrip_with_nulls():
    values = np.array([1.5, 0.0, 2.5, 1.5, 0.0])
    mask = np.array([True, False, True, True, False])
    enc = ColumnChunk.from_values(values, mask)
    assert enc is not None
    assert enc.codes.dtype == np.int16
    assert list(enc.codes >= 0) == list(mask)
    dec_values, dec_mask = enc.decode(np.float64)
    assert np.array_equal(dec_mask, mask)
    assert np.array_equal(dec_values, np.where(mask, values, 0.0))
    # validity bitmap is packed bits, 8x smaller than a bool mask
    assert enc.validity is not None
    assert enc.validity.nbytes == (len(values) + 7) // 8


def test_column_chunk_valid_nan_round_trips():
    values = np.array([1.0, np.nan, 1.0, np.nan])
    mask = np.array([True, True, True, False])
    enc = ColumnChunk.from_values(values, mask)
    dec_values, dec_mask = enc.decode(np.float64)
    assert list(dec_mask) == [True, True, True, False]
    assert dec_values[0] == 1.0 and np.isnan(dec_values[1])


def test_all_unique_column_refuses_encoding():
    col = Column("u", DType.FRACTIONAL, values=np.arange(40000.0))
    assert col.encode() is False
    assert col.encoding is None
    # strings and booleans never encode through this path either
    b = Column("b", DType.BOOLEAN, values=np.array([True, False]))
    assert b.encode() is False


def test_encoded_take_stays_encoded():
    t = _dict_heavy(1000)
    t.encode()
    sliced = t["f"].take(np.arange(100, 200))
    assert sliced.encoding is not None
    assert np.array_equal(sliced.values, t["f"].values[100:200])


def test_lazy_decode_mask_without_values():
    t = _null_heavy(256)
    ref_mask = t["f"].mask.copy()
    t2 = _null_heavy(256)
    t2.encode()
    enc_col = Column("f", DType.FRACTIONAL, encoded=t2["f"].encoding)
    # reading the mask must not force a value decode
    assert np.array_equal(enc_col.mask, ref_mask)
    assert enc_col._values is None


# -- source satellites -------------------------------------------------------


def test_batch_rows_sized_by_encoded_bytes():
    schema = Schema([Field("a", DType.FRACTIONAL), Field("b", DType.FRACTIONAL)])
    plain = batch_rows_for_schema(schema, target_bytes=4 << 20)
    enc = batch_rows_for_schema(
        schema, target_bytes=4 << 20, encoded=("a", "b")
    )
    # 9B/row decoded vs 2B/row encoded: encoded batches carry ~4.5x the
    # rows for the same host budget (the satellite fix: full-width
    # sizing under-filled dictionary-heavy batches 2-8x)
    assert enc > 4 * plain


def test_parquet_source_detects_and_carries_encoding(tmp_path):
    from deequ_tpu.data.io import write_parquet

    t = _dict_heavy(8000)
    path = str(tmp_path / "enc.parquet")
    write_parquet(t, path)
    src = ParquetBatchSource(path)
    assert {"f", "i"} <= set(src.encoded_column_names)
    batches = list(src.batches(batch_rows=2048))
    assert all(b["f"].encoding is not None for b in batches)
    assert all(b["i"].encoding is not None for b in batches)
    merged = batches[0]
    for b in batches[1:]:
        merged = merged.concat(b)
    assert np.array_equal(merged["f"].values, t["f"].values)
    assert np.array_equal(merged["i"].values, t["i"].values)


def test_parquet_near_unique_column_stays_plain(tmp_path):
    """The density rule: a column the writer happened to dictionary-
    encode but whose cardinality ~ rows decodes to the plain path."""
    from deequ_tpu.data.io import write_parquet

    rng = np.random.default_rng(7)
    t = ColumnarTable(
        [Column("u", DType.FRACTIONAL, values=rng.normal(size=4000))]
    )
    path = str(tmp_path / "uniq.parquet")
    write_parquet(t, path)
    src = ParquetBatchSource(path)
    batches = list(src.batches())
    assert all(b["u"].encoding is None for b in batches)
    assert np.array_equal(batches[0]["u"].values[:10], t["u"].values[:10])


def _write_csv(path, n=4000, card=13):
    """Low-cardinality int + float columns, a near-unique float, a
    string column, and empty-cell nulls every 53rd row."""
    with open(path, "w") as f:
        f.write("i,f,u,s\n")
        for k in range(n):
            i = "" if k % 53 == 0 else str(k % card)
            f.write(f"{i},{(k % card) / 2},{k * 1.5},s{k % 5}\n")


def test_csv_source_sniffs_and_carries_encoding(tmp_path):
    """PR-8 follow-up: CSV has no encoding metadata, so the source
    sniffs cardinality on the FIRST block and opts qualifying numeric
    columns into the encoded plane, mirroring the Parquet path."""
    from deequ_tpu.data.io import read_csv
    from deequ_tpu.data.source import CSVBatchSource

    path = str(tmp_path / "enc.csv")
    _write_csv(path)
    src = CSVBatchSource(path)
    assert src.encoded_column_names == frozenset({"i", "f"})
    batches = list(src.batches(batch_rows=1024))
    assert all(b["i"].encoding is not None for b in batches)
    assert all(b["f"].encoding is not None for b in batches)
    assert all(b["u"].encoding is None for b in batches)
    merged = batches[0]
    for b in batches[1:]:
        merged = merged.concat(b)
    ref = read_csv(path)
    assert merged.num_rows == ref.num_rows
    for name in ("i", "f", "u"):
        assert np.array_equal(merged[name].values, ref[name].values)
        assert np.array_equal(merged[name].mask, ref[name].mask)


def test_csv_density_rule_keeps_near_unique_plain(tmp_path):
    """The density rule mirrored from Parquet: a numeric column whose
    first-block cardinality exceeds 1 distinct per 4 rows stays plain,
    and encoded batch SIZING engages for the qualifying columns."""
    from deequ_tpu.data.source import CSVBatchSource

    path = str(tmp_path / "uniq.csv")
    _write_csv(path)
    src = CSVBatchSource(path)
    assert "u" not in src.encoded_column_names  # ~unique: fails density
    assert "s" not in src.encoded_column_names  # strings have their own plane
    # empty file (header only): nothing qualifies, nothing crashes
    empty = str(tmp_path / "empty.csv")
    with open(empty, "w") as f:
        f.write("a,b\n")
    assert CSVBatchSource(empty).encoded_column_names == frozenset()


def test_csv_encoded_stream_metrics_match_decoded(tmp_path):
    """Encoded CSV ingest is bit-identical to the in-memory decoded run
    for the scan-shareable families (the ingest contract, now over the
    CSV source)."""
    from deequ_tpu.data.io import read_csv, stream_csv
    from deequ_tpu.verification import VerificationSuite

    path = str(tmp_path / "m.csv")
    _write_csv(path)
    analyzers = [
        Size(), Completeness("i"), Mean("f"), Minimum("f"), Maximum("f"),
        Sum("i"),
    ]
    ref = AnalysisRunner.do_analysis_run(read_csv(path), analyzers)
    got = AnalysisRunner.do_analysis_run(stream_csv(path, batch_rows=1000), analyzers)
    for a in analyzers:
        assert got.metric_map[a].value == ref.metric_map[a].value, a


# -- encoded-vs-decoded bit-identity ----------------------------------------


@pytest.mark.parametrize(
    "build", [_dict_heavy, _null_heavy, _all_unique],
    ids=["dict_heavy", "null_heavy", "all_unique"],
)
def test_encoded_bit_identical_all_families(build):
    analyzers = list(FAMILIES)
    if build is _dict_heavy:
        analyzers += [Mean("i"), Uniqueness(("i",)), Completeness("s")]
    ref = _decoded_run(build(), analyzers)
    enc_table = build()
    enc_table.encode()
    got = _metrics(AnalysisRunner.do_analysis_run(enc_table, analyzers))
    assert got == ref


def test_encoded_resident_bit_identical_and_one_fetch():
    """Multi-chunk encoded residency: same metrics, exactly one
    device->host fetch, and the resident footprint is the ENCODED one."""
    monoid = [Size(), Completeness("f"), Mean("f"), Minimum("f"), Maximum("f")]
    t = _dict_heavy(20000)
    ref = _decoded_run(t, monoid)

    enc = _dict_heavy(20000)
    enc.encode()
    from deequ_tpu.ops.scan_engine import persist_table

    persist_table(enc, chunk_rows=4096)  # 5 resident chunks
    SCAN_STATS.reset()
    got = _metrics(AnalysisRunner.do_analysis_run(enc, monoid))
    assert got == ref
    assert SCAN_STATS.device_fetches == 1
    assert SCAN_STATS.encoded_scan_passes >= 1
    enc.unpersist()

    # residency footprint: compare on the encodABLE columns (the string
    # column's code plane and row_valid are identical either way)
    num = _dict_heavy(20000).select(["f", "i"])
    num.encode()
    persist_table(num, chunk_rows=4096)
    enc_bytes = num._device_cache.nbytes
    num.unpersist()
    dec = _dict_heavy(20000).select(["f", "i"])
    persist_table(dec, chunk_rows=4096, encode=False)
    dec_bytes = dec._device_cache.nbytes
    dec.unpersist()
    # f: 8B -> 2B, i: 4B -> 2B (+1B row_valid each): >= 2x smaller HBM
    assert enc_bytes * 2 <= dec_bytes, (enc_bytes, dec_bytes)


def test_encoded_transfer_bytes_reduced_2x():
    """Acceptance: host->device bytes per run reduced >= 2x on
    dictionary-encodable columns (bytes_packed is the packed-transfer
    ledger on the non-resident path)."""
    monoid = [Mean("f"), Minimum("f"), Maximum("f")]
    t = _null_heavy(30000)

    os.environ["DEEQU_TPU_ENCODED_INGEST"] = "0"
    try:
        SCAN_STATS.reset()
        AnalysisRunner.do_analysis_run(_null_heavy(30000), monoid)
        raw = SCAN_STATS.bytes_packed
    finally:
        os.environ.pop("DEEQU_TPU_ENCODED_INGEST")

    t.encode()
    SCAN_STATS.reset()
    AnalysisRunner.do_analysis_run(t, monoid)
    enc = SCAN_STATS.bytes_packed
    assert enc * 2 <= raw, (enc, raw)
    assert SCAN_STATS.bytes_staged == enc


def test_quantiles_encoded_within_kll_envelope():
    """Encoded vs decoded quantiles: same kernel path, same chunking =>
    the summaries are bit-identical; assert the documented envelope as
    the hard bound and exact equality as the expected case."""
    t = _dict_heavy(20000)
    ref = _decoded_run(t, [ApproxQuantile("f", q) for q in (0.1, 0.5, 0.9)])
    enc = _dict_heavy(20000)
    enc.encode()
    got = _metrics(
        AnalysisRunner.do_analysis_run(
            enc, [ApproxQuantile("f", q) for q in (0.1, 0.5, 0.9)]
        )
    )
    assert got == ref


# -- double-buffered staging -------------------------------------------------


def test_stream_overlap_and_bit_identity():
    """The streaming loop double-buffers: every chunk transfer after the
    first is issued while the previous chunk is still staged-
    undispatched, so ingest_overlap_frac = (n-1)/n >= 0.5 (a serial
    loop would report 0); encoded and decoded streaming runs agree
    bit-for-bit (same chunk boundaries, same fold order)."""
    monoid = [Size(), Completeness("f"), Mean("f"), Minimum("f"), Maximum("f")]

    def stream(encode):
        t = _dict_heavy(16000)
        if encode:
            t.encode()
        return stream_table(t, batch_rows=2048)

    os.environ["DEEQU_TPU_ENCODED_INGEST"] = "0"
    try:
        SCAN_STATS.reset()
        ref = _metrics(AnalysisRunner.do_analysis_run(stream(False), monoid))
        raw_staged = SCAN_STATS.bytes_staged
        assert SCAN_STATS.ingest_overlap_frac >= 0.5
    finally:
        os.environ.pop("DEEQU_TPU_ENCODED_INGEST")

    SCAN_STATS.reset()
    got = _metrics(AnalysisRunner.do_analysis_run(stream(True), monoid))
    snap = SCAN_STATS.snapshot()
    assert got == ref
    assert snap["chunks_staged"] == 8
    assert snap["ingest_overlap_frac"] >= 0.5
    assert 0 < snap["bytes_staged"] * 2 <= raw_staged
    # the one-fetch contract holds on the encoded streaming path too
    # (monoid-only ops fold on device across the whole stream)
    assert snap["device_fetches"] == 1


def test_stream_layout_demotes_encoding_lost_midstream():
    """A source whose later batches lose the encoding (high-cardinality
    fallback mid-stream) upgrades the pinned layout monotonically
    (enc -> wide) and still produces correct metrics."""
    rng = np.random.default_rng(21)
    f1 = (rng.integers(0, 10, 4000)).astype(np.float64)
    f2 = rng.normal(size=4000)  # not encodable

    b1 = ColumnarTable([Column("f", DType.FRACTIONAL, values=f1)])
    b1.encode()
    b2 = ColumnarTable([Column("f", DType.FRACTIONAL, values=f2)])

    class TwoBatchSource:
        schema = Schema([Field("f", DType.FRACTIONAL)])
        num_rows = 8000
        _batch_rows = 4000

        def batches(self, columns=None, batch_rows=None):
            yield b1
            yield b2

    got = _metrics(
        AnalysisRunner.do_analysis_run(
            StreamingTable(TwoBatchSource()), [Size(), Mean("f"), Minimum("f")]
        )
    )
    full = np.concatenate([f1, f2])
    assert got[repr(Size())] == 8000
    assert got[repr(Minimum("f"))] == full.min()


# -- fault-ladder composition ------------------------------------------------


def test_oom_mid_encoded_scan_demotes_to_decoded():
    """The selection->sort analogue: a device OOM during an encoded
    attempt re-plans the run onto the decoded path (recorded as an
    encoded_demote degradation) and the result is bit-identical to a
    clean decoded run."""
    monoid = [Size(), Completeness("f"), Mean("f"), Minimum("f"), Maximum("f")]
    ref = _decoded_run(_null_heavy(10000), monoid)

    t = _null_heavy(10000)
    t.encode()
    DEVICE_HEALTH.reset()
    SCAN_STATS.reset()
    prev = install_scan_fault_hook(
        FaultInjectingScanHook(faults={0: ("oom", 1)})
    )
    try:
        got = _metrics(AnalysisRunner.do_analysis_run(t, monoid))
    finally:
        install_scan_fault_hook(prev)
    assert got == ref
    assert SCAN_STATS.encoded_demotions == 1
    kinds = [e["kind"] for e in SCAN_STATS.degradation_events]
    assert "encoded_demote" in kinds
    # the demotion is NOT a bisection: chunk size untouched on the retry
    assert "oom_bisect" not in kinds


def test_second_oom_after_demotion_bisects():
    """Ladder composition: demote first, bisect after — a second OOM on
    the decoded retry halves the chunk like any PR-3 OOM."""
    monoid = [Size(), Mean("f")]
    ref = _decoded_run(_null_heavy(10000), monoid)
    t = _null_heavy(10000)
    t.encode()
    DEVICE_HEALTH.reset()
    SCAN_STATS.reset()
    prev = install_scan_fault_hook(
        FaultInjectingScanHook(faults={0: ("oom", 2)})
    )
    try:
        got = _metrics(AnalysisRunner.do_analysis_run(t, monoid))
    finally:
        install_scan_fault_hook(prev)
    assert got == ref
    assert SCAN_STATS.encoded_demotions == 1
    assert SCAN_STATS.oom_bisections >= 1


def test_stream_fault_mid_stage_fails_typed_cleanly():
    """A fault while a staged chunk is in flight (the hook fires at
    chunk 0's dispatch, which the double buffer issues AFTER chunk 1's
    transfer) must surface as a typed failure — and must not corrupt
    the staging pipeline for subsequent runs."""
    monoid = [Size(), Mean("f")]
    t = _dict_heavy(16000)
    t.encode()
    DEVICE_HEALTH.reset()
    prev = install_scan_fault_hook(
        FaultInjectingScanHook(faults={0: ("oom", 1)})
    )
    try:
        ctx = AnalysisRunner.do_analysis_run(
            stream_table(t, batch_rows=2048), monoid
        )
    finally:
        install_scan_fault_hook(prev)
    # streams cannot rewind, so the typed device fault lands as failure
    # metrics (the runner's per-analyzer capture), never a silent wrong
    # value
    failures = [m for m in ctx.metric_map.values() if m.value.is_failure]
    assert failures, "injected OOM mid-stage vanished"
    # the pipeline state is per-scan: a clean rerun is unaffected
    DEVICE_HEALTH.reset()
    SCAN_STATS.reset()
    got = _metrics(
        AnalysisRunner.do_analysis_run(stream_table(t, batch_rows=2048), monoid)
    )
    assert got[repr(Size())] == 16000
    assert SCAN_STATS.ingest_overlap_frac >= 0.5


def test_encoded_persist_bypassed_when_switched_off():
    """run_scan(encoded_ingest=False) over an encoded-persisted table
    must not serve encoded residency to the decoded plan."""
    monoid = [Size(), Mean("f")]
    t = _dict_heavy(8000)
    ref = _decoded_run(_dict_heavy(8000), monoid)
    t.encode()
    t.persist()
    os.environ["DEEQU_TPU_ENCODED_INGEST"] = "0"
    try:
        SCAN_STATS.reset()
        got = _metrics(AnalysisRunner.do_analysis_run(t, monoid))
    finally:
        os.environ.pop("DEEQU_TPU_ENCODED_INGEST")
    assert got == ref
    assert SCAN_STATS.resident_passes == 0  # bypassed, not misused
    t.unpersist()


# -- kill-and-resume through an encoded checkpoint ---------------------------


class _KillSwitch(BaseException):
    """Out-of-band abort (not an Exception): no isolation layer
    converts it — the runner dies as if SIGKILLed."""


class _KillingSource:
    def __init__(self, inner, kill_at):
        self.inner = inner
        self.kill_at = kill_at

    @property
    def schema(self):
        return self.inner.schema

    @property
    def num_rows(self):
        return self.inner.num_rows

    @property
    def encoded_column_names(self):
        return self.inner.encoded_column_names

    @property
    def _batch_rows(self):
        return getattr(self.inner, "_batch_rows", None)

    def batches(self, columns=None, batch_rows=None):
        yield from self.batches_from(0, columns=columns, batch_rows=batch_rows)

    def batches_from(self, start=0, columns=None, batch_rows=None):
        idx = start
        for batch in self.inner.batches_from(
            start, columns=columns, batch_rows=batch_rows
        ):
            if self.kill_at is not None and idx == self.kill_at:
                raise _KillSwitch(f"killed at batch {idx}")
            yield batch
            idx += 1


def test_kill_and_resume_through_encoded_checkpoint(tmp_path):
    """Flagship resilience composition: a checkpointed streaming
    verification over a dictionary-ENCODED Parquet source, killed
    mid-stream, resumes bit-identically to an uninterrupted run — the
    encoded read path (codes + dictionary per batch) feeds the resumed
    fold exactly like the original one."""
    from deequ_tpu.checks import Check, CheckLevel, CheckStatus
    from deequ_tpu.data.io import write_parquet
    from deequ_tpu.verification import VerificationSuite

    t = _dict_heavy(2000)
    path = str(tmp_path / "stream.parquet")
    write_parquet(t, path)

    def fresh_source():
        return ParquetBatchSource(path, batch_rows=100)  # 20 batches

    assert "f" in fresh_source().encoded_column_names

    def check():
        return (
            Check(CheckLevel.ERROR, "ingest")
            .is_complete("f")
            .has_size(lambda s: s == 2000)
        )

    ref = (
        VerificationSuite.on_data(StreamingTable(fresh_source()))
        .add_check(check())
        .with_checkpoint(str(tmp_path / "ref"), every_batches=4)
        .run()
    )
    assert ref.status == CheckStatus.SUCCESS

    ckpt = str(tmp_path / "run")
    with pytest.raises(_KillSwitch):
        (
            VerificationSuite.on_data(
                StreamingTable(_KillingSource(fresh_source(), kill_at=10))
            )
            .add_check(check())
            .with_checkpoint(ckpt, every_batches=4)
            .run()
        )
    assert sorted(os.listdir(ckpt)), "kill left no checkpoints behind"

    resumed = (
        VerificationSuite.on_data(StreamingTable(fresh_source()))
        .add_check(check())
        .with_checkpoint(ckpt, every_batches=4)
        .run()
    )
    assert resumed.status == CheckStatus.SUCCESS

    def values(result):
        return {
            repr(a): m.value.get()
            for a, m in result.metrics.items()
            if m.value.is_success
        }

    assert values(resumed) == values(ref)


# -- plan lint ---------------------------------------------------------------


def test_encoded_plan_lints_clean_at_error():
    monoid = [Size(), Mean("f"), Minimum("f")]
    t = _dict_heavy(8000)
    t.encode()
    from deequ_tpu.lint.plan_lint import clear_lint_memo

    clear_lint_memo()
    os.environ["DEEQU_TPU_PLAN_LINT"] = "error"
    try:
        SCAN_STATS.reset()
        _metrics(AnalysisRunner.do_analysis_run(t, monoid))
    finally:
        os.environ.pop("DEEQU_TPU_PLAN_LINT")
    assert SCAN_STATS.plan_lints == []
    assert SCAN_STATS.plan_lint_traces >= 1


def test_encoded_and_decoded_variants_lint_separately():
    """The lint memo keys on the ingest variant: the same analyzer set
    over the same table lints once per variant, not once total."""
    monoid = [Size(), Mean("f")]
    from deequ_tpu.lint.plan_lint import clear_lint_memo

    clear_lint_memo()
    os.environ["DEEQU_TPU_PLAN_LINT"] = "error"
    try:
        t = _dict_heavy(8000)
        t.encode()
        SCAN_STATS.reset()
        AnalysisRunner.do_analysis_run(t, monoid)
        first = SCAN_STATS.plan_lint_traces
        assert first >= 1
        os.environ["DEEQU_TPU_ENCODED_INGEST"] = "0"
        SCAN_STATS.reset()
        AnalysisRunner.do_analysis_run(_dict_heavy(8000), monoid)
        assert SCAN_STATS.plan_lint_traces >= 1  # fresh trace, new variant
        os.environ.pop("DEEQU_TPU_ENCODED_INGEST")
        # and a repeat encoded run is fully memoized
        t2 = _dict_heavy(8000)
        t2.encode()
        SCAN_STATS.reset()
        AnalysisRunner.do_analysis_run(t2, monoid)
        assert SCAN_STATS.plan_lint_traces == 0
    finally:
        os.environ.pop("DEEQU_TPU_PLAN_LINT")
        os.environ.pop("DEEQU_TPU_ENCODED_INGEST", None)


def test_plan_encoded_decode_rule_catches_drift():
    from deequ_tpu.lint.plan_lint import lint_plan
    from deequ_tpu.ops.scan_plan import ScanPlan

    base = dict(
        ops=(), resident=False, ingest_variant="encoded",
        encoded_columns=("x",),
    )
    routed_wide = ScanPlan(
        layout=(
            ("enc", ()), ("wide", ("x",)), ("pair", ()),
            ("narrow_i32", ()), ("masked", ()),
        ),
        **base,
    )
    findings = lint_plan(routed_wide)
    assert [f.rule for f in findings] == ["plan-encoded-decode"]
    missing = ScanPlan(
        layout=(
            ("enc", ()), ("wide", ()), ("pair", ()),
            ("narrow_i32", ()), ("masked", ()),
        ),
        **base,
    )
    assert [f.rule for f in lint_plan(missing)] == ["plan-encoded-decode"]
    healthy = ScanPlan(
        layout=(
            ("enc", ("x",)), ("wide", ()), ("pair", ()),
            ("narrow_i32", ()), ("masked", ()),
        ),
        **base,
    )
    assert lint_plan(healthy) == []


# -- switch validation -------------------------------------------------------


def test_encoded_ingest_switch_validation():
    from deequ_tpu.ops.scan_plan import encoded_ingest_enabled

    assert encoded_ingest_enabled(True) is True
    assert encoded_ingest_enabled(False) is False
    with pytest.raises(ValueError):
        encoded_ingest_enabled("yes")
    os.environ["DEEQU_TPU_ENCODED_INGEST"] = "maybe"
    try:
        with pytest.raises(ValueError):
            encoded_ingest_enabled()
    finally:
        os.environ.pop("DEEQU_TPU_ENCODED_INGEST")
    assert encoded_ingest_enabled() is True  # default on
