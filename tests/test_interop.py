"""deequ interop: import the reference's persisted artifacts.

Fixtures are hand-built from the reference format spec — BIG-endian
binary states per StateProvider.scala:186-311 and Gson repository JSON
per AnalysisResultSerde.scala:38-635 — NOT copied files."""

import json
import struct

import numpy as np
import pytest

from deequ_tpu.analyzers import (
    ApproxCountDistinct,
    ApproxQuantile,
    Completeness,
    Correlation,
    DataType,
    Mean,
    Minimum,
    Size,
    StandardDeviation,
    Uniqueness,
)
from deequ_tpu.interop import (
    import_repository_json,
    load_reference_state,
    reference_state_identifier,
    scala_murmur3_string_hash,
)


def test_scala_murmur3_known_relations():
    """Pin the implementation's behavior: deterministic, seed-sensitive,
    pair-wise char mixing (odd/even lengths take different paths)."""
    h1 = scala_murmur3_string_hash("Size(None)", 42)
    assert h1 == scala_murmur3_string_hash("Size(None)", 42)
    assert h1 != scala_murmur3_string_hash("Size(None)", 43)
    assert h1 != scala_murmur3_string_hash("Size(None) ", 42)
    assert -(2 ** 31) <= h1 < 2 ** 31  # signed 32-bit like Scala Int
    # identifier is the decimal string of the signed value
    assert reference_state_identifier(Size()) == str(h1)
    # raw Scala toString accepted verbatim
    assert reference_state_identifier("Size(None)") == str(h1)


def _write(prefix, analyzer, payload, tmp_path):
    ident = reference_state_identifier(analyzer)
    path = tmp_path / f"{prefix}-{ident}.bin"
    path.write_bytes(payload)
    return str(tmp_path / prefix)


def test_portable_binary_states_round_trip(tmp_path):
    """Every portable state decodes to the exact values a reference
    deployment persisted (big-endian, per-analyzer layout)."""
    cases = [
        (Size(), struct.pack(">q", 12345), ("num_matches", 12345)),
        (
            Completeness("att1"),
            struct.pack(">qq", 80, 100),
            ("num_matches", 80),
        ),
        (Mean("price"), struct.pack(">dq", 199.5, 42), ("total", 199.5)),
        (Minimum("x"), struct.pack(">d", -3.25), ("min_value", -3.25)),
        (
            StandardDeviation("x"),
            struct.pack(">ddd", 100.0, 5.5, 250.0),
            ("m2", 250.0),
        ),
        (
            Correlation("a", "b"),
            struct.pack(">6d", 10.0, 1.0, 2.0, 3.0, 4.0, 5.0),
            ("ck", 3.0),
        ),
        (
            DataType("mixed"),
            struct.pack(">i", 40) + struct.pack(">5q", 1, 2, 3, 4, 5),
            ("num_string", 5),
        ),
    ]
    for analyzer, payload, (attr, want) in cases:
        prefix = _write("states", analyzer, payload, tmp_path)
        state = load_reference_state(prefix, analyzer)
        assert getattr(state, attr) == want, analyzer


def test_mean_state_metric_matches_reference_semantics(tmp_path):
    prefix = _write("s", Mean("p"), struct.pack(">dq", 15.0, 6), tmp_path)
    state = load_reference_state(prefix, Mean("p"))
    assert state.metric_value() == 15.0 / 6


def test_sketch_states_refuse_with_algebra_rationale(tmp_path):
    with pytest.raises(ValueError, match="algebra differs"):
        load_reference_state(str(tmp_path / "s"), ApproxCountDistinct("x"))
    with pytest.raises(ValueError, match="algebra differs"):
        load_reference_state(str(tmp_path / "s"), ApproxQuantile("x", 0.5))


def test_frequency_state_from_parquet(tmp_path):
    """FrequenciesAndNumRows via the reference's Parquet + num_rows.bin
    (persistDataframeLongState)."""
    from deequ_tpu.data.io import write_parquet
    from deequ_tpu.data.table import ColumnarTable

    analyzer = Uniqueness(["att1"])
    ident = reference_state_identifier(analyzer)
    freq_table = ColumnarTable.from_pydict({
        "att1": ["a", "b", "c"],
        "absolute": [5, 1, 1],
    })
    write_parquet(freq_table, str(tmp_path / f"s-{ident}-frequencies.pqt"))
    (tmp_path / f"s-{ident}-num_rows.bin").write_bytes(struct.pack(">q", 7))

    state = load_reference_state(str(tmp_path / "s"), analyzer)
    assert state.num_rows == 7
    assert state.as_dict() == {("a",): 5, ("b",): 1, ("c",): 1}
    # the imported state computes metrics like a native one
    m = analyzer.compute_metric_from(state)
    assert m.value.get() == 2 / 7  # two singleton groups of 7 rows


_GSON_FIXTURE = [
    {
        "resultKey": {"dataSetDate": 1630000000000, "tags": {"env": "prod"}},
        "analyzerContext": {
            "metricMap": [
                {
                    "analyzer": {"analyzerName": "Size", "where": None},
                    "metric": {
                        "metricName": "DoubleMetric",
                        "entity": "Dataset",
                        "instance": "*",
                        "name": "Size",
                        "value": 1000.0,
                    },
                },
                {
                    "analyzer": {
                        "analyzerName": "Compliance",
                        "instance": "rule-1",
                        "predicate": "att1 > 0",
                        "where": None,
                    },
                    "metric": {
                        "metricName": "DoubleMetric",
                        # the reference's enum spells it this way
                        # (metrics/Metric.scala:22)
                        "entity": "Mutlicolumn",
                        "instance": "rule-1",
                        "name": "Compliance",
                        "value": 0.95,
                    },
                },
                {
                    "analyzer": {
                        "analyzerName": "Histogram",
                        "column": "cat",
                        "maxDetailBins": 10,
                    },
                    "metric": {
                        "metricName": "HistogramMetric",
                        "column": "cat",
                        "numberOfBins": 2,
                        "value": {
                            "numberOfBins": 2,
                            "values": {
                                "a": {"absolute": 6, "ratio": 0.6},
                                "b": {"absolute": 4, "ratio": 0.4},
                            },
                        },
                    },
                },
            ]
        },
    },
    {
        "resultKey": {"dataSetDate": 1630000100000, "tags": {"env": "prod"}},
        "analyzerContext": {
            "metricMap": [
                {
                    "analyzer": {"analyzerName": "Size", "where": None},
                    "metric": {
                        "metricName": "DoubleMetric",
                        "entity": "Dataset",
                        "instance": "*",
                        "name": "Size",
                        "value": 1010.0,
                    },
                }
            ]
        },
    },
]


def test_repository_json_import_and_anomaly_continuity():
    """The migrated metric history feeds anomaly detection on day one —
    the round-5 review's 'existing deployment switches over' workflow."""
    from deequ_tpu.anomaly import AnomalyDetector, RelativeRateOfChangeStrategy
    from deequ_tpu.anomaly.history import DataPoint
    from deequ_tpu.metrics import Entity
    from deequ_tpu.repository import InMemoryMetricsRepository

    repo = InMemoryMetricsRepository()
    n = import_repository_json(json.dumps(_GSON_FIXTURE), repo)
    assert n == 2

    loaded = repo.load().with_tag_values({"env": "prod"}).get()
    assert len(loaded) == 2
    by_date = {r.result_key.data_set_date: r for r in loaded}
    first = by_date[1630000000000].analyzer_context.metric_map
    assert first[Size()].value.get() == 1000.0
    comp = [m for a, m in first.items() if type(a).__name__ == "Compliance"][0]
    assert comp.value.get() == 0.95
    assert comp.entity == Entity.MULTICOLUMN  # typo'd spelling mapped
    hist = [m for a, m in first.items() if type(a).__name__ == "Histogram"][0]
    assert hist.value.get().values["a"].absolute == 6

    # anomaly detection straight off the imported history + a new point
    sizes = sorted(
        (r.result_key.data_set_date, r.analyzer_context.metric_map[Size()])
        for r in loaded
    )
    history = [DataPoint(t, m.value.get()) for t, m in sizes]
    detector = AnomalyDetector(
        RelativeRateOfChangeStrategy(max_rate_decrease=0.5, max_rate_increase=2.0)
    )
    ok = detector.is_new_point_anomalous(
        history, DataPoint(1630000200000, 1005.0)
    )
    assert len(ok.anomalies) == 0
    bad = detector.is_new_point_anomalous(
        history, DataPoint(1630000300000, 10.0)
    )
    assert len(bad.anomalies) == 1


def test_scala_murmur3_utf16_surrogates_and_null_count_rows(tmp_path):
    """Non-BMP chars hash as TWO UTF-16 code units with length counted in
    units (JVM String semantics); a null count row in the frequencies
    Parquet drops the whole row, keeping keys and counts aligned."""
    # surrogate-pair handling: the 2-unit emoji must hash differently
    # from any single-unit char and take the even-length (pairwise) path
    h_emoji = scala_murmur3_string_hash("\U0001F600", 42)   # 2 units
    h_bmp2 = scala_murmur3_string_hash("ab", 42)            # 2 units
    h_bmp1 = scala_murmur3_string_hash("a", 42)             # 1 unit
    assert len({h_emoji, h_bmp2, h_bmp1}) == 3
    # explicit unit math: the emoji equals hashing its surrogate pair
    hi, lo = 0xD83D, 0xDE00
    assert h_emoji == scala_murmur3_string_hash(chr(hi) + chr(lo), 42)

    from deequ_tpu.data.io import write_parquet
    from deequ_tpu.data.table import ColumnarTable

    analyzer = Uniqueness(["k"])
    ident = reference_state_identifier(analyzer)
    t = ColumnarTable.from_pydict({
        "k": ["a", "b", "c"],
        "absolute": [5, None, 2],  # middle row: null count -> dropped
    })
    write_parquet(t, str(tmp_path / f"s-{ident}-frequencies.pqt"))
    (tmp_path / f"s-{ident}-num_rows.bin").write_bytes(struct.pack(">q", 7))
    state = load_reference_state(str(tmp_path / "s"), analyzer)
    assert state.as_dict() == {("a",): 5, ("c",): 2}


def test_murmur3_x86_32_published_vectors():
    """Pin the murmur primitives against the canonical MurmurHash3 x86_32
    test vectors published for Austin Appleby's reference MurmurHash3.cpp
    (SMHasher repo) and transcribed in the widely-cited canonical-vector
    set (see e.g. the cross-implementation suites of pymmh3 and Guava's
    Murmur3_32HashFunctionTest). Scala's MurmurHash3 implements the same
    constants/rotations, so these vectors pin the ``_mix``/``_mix_last``/
    ``_fmix`` wiring the state-file identifier hash is built from."""
    from deequ_tpu.interop import murmur3_x86_32

    vectors = [
        # (data, seed, expected unsigned 32-bit)
        (b"", 0x00000000, 0x00000000),          # empty, zero seed
        (b"", 0x00000001, 0x514E28B7),          # empty, seed 1
        (b"", 0xFFFFFFFF, 0x81F16F39),          # empty, all-bits seed
        (b"\x00\x00\x00\x00", 0x00000000, 0x2362F9DE),  # one zero block
        (b"\x21\x43\x65\x87", 0x00000000, 0xF55B516B),  # full 4-byte block
        (b"\x21\x43\x65\x87", 0x5082EDEE, 0x2362F9DE),  # block + seed
        (b"\x21\x43\x65", 0x00000000, 0x7E4A8634),      # 3-byte tail
        (b"\x21\x43", 0x00000000, 0xA0F7B07A),          # 2-byte tail
        (b"\x21", 0x00000000, 0x72661CF4),              # 1-byte tail
    ]
    for data, seed, want in vectors:
        assert murmur3_x86_32(data, seed) == want, (data, hex(seed))
    # the mmh3 package's README example (signed form): hash("foo") ==
    # -156908512 with seed 0 over UTF-8 bytes
    h = murmur3_x86_32(b"foo", 0)
    assert (h - (1 << 32) if h >= (1 << 31) else h) == -156908512


def test_scala_murmur3_composition_from_verified_primitives():
    """stringHash's wiring, transcribed from the published Scala source
    (scala/src/library/scala/util/hashing/MurmurHash3.scala, stringHash +
    finalizeHash): chars combine PAIRWISE as ``(c0 << 16) | c1`` per mix
    step, a trailing odd char goes through mixLast, and finalizeHash
    XORs the length in UTF-16 units before the avalanche. With the
    primitives pinned by the Appleby vectors above, these compositions
    pin the string path across the length/surrogate edge cases."""
    from deequ_tpu.interop.deequ_import import _fmix, _mix, _mix_last

    def expect(units, seed):
        h = seed & 0xFFFFFFFF
        i = 0
        while i + 1 < len(units):
            h = _mix(h, ((units[i] << 16) + units[i + 1]) & 0xFFFFFFFF)
            i += 2
        if i < len(units):
            h = _mix_last(h, units[i])
        return _fmix((h ^ len(units)) & 0xFFFFFFFF)

    def signed(h):
        return h - (1 << 32) if h >= (1 << 31) else h

    cases = [
        ("", []),                                    # len-0 finalize only
        ("a", [0x61]),                               # lone mixLast char
        ("ab", [0x61, 0x62]),                        # one full pair block
        ("abc", [0x61, 0x62, 0x63]),                 # pair + odd tail
        ("Size(None)", [ord(c) for c in "Size(None)"]),  # even, multi-block
        ("\U0001D11E", [0xD834, 0xDD1E]),            # surrogate PAIR = 2 units
        ("\U0001D11Ex", [0xD834, 0xDD1E, 0x78]),     # pair + BMP tail (odd)
        ("\ud834", [0xD834]),                        # lone surrogate (legal
                                                     # in a JVM String)
    ]
    for s, units in cases:
        for seed in (42, 0, 1):
            assert scala_murmur3_string_hash(s, seed) == signed(
                expect(units, seed)
            ), (s, seed)


def test_frequency_state_multicolumn_mixed_dtypes(tmp_path):
    """Frequency-table import breadth: a 2-key grouping whose key columns
    mix STRING and INTEGRAL dtypes (the common country x status_code
    shape), including a null string key, round-tripped through the
    reference's Parquet + num_rows.bin layout and on into metric math."""
    from deequ_tpu.analyzers import CountDistinct, Uniqueness
    from deequ_tpu.data.io import write_parquet
    from deequ_tpu.data.table import ColumnarTable

    analyzer = Uniqueness(["cat", "num"])
    ident = reference_state_identifier(analyzer)
    freq_table = ColumnarTable.from_pydict({
        "cat": ["a", "a", "b", None],
        "num": [1, 2, 1, 3],
        "absolute": [4, 1, 1, 2],
    })
    write_parquet(freq_table, str(tmp_path / f"s-{ident}-frequencies.pqt"))
    (tmp_path / f"s-{ident}-num_rows.bin").write_bytes(struct.pack(">q", 8))

    state = load_reference_state(str(tmp_path / "s"), analyzer)
    assert state.columns == ("cat", "num")
    assert state.num_rows == 8
    d = state.as_dict()
    assert d[("a", 1)] == 4
    assert d[("a", 2)] == 1
    assert d[("b", 1)] == 1
    assert d[(None, 3)] == 2
    # metric math over the imported mixed-dtype state: 3 of 4 groups are
    # singletons (count == 1 never happens for ("a",1) or (None,3))
    m = analyzer.compute_metric_from(state)
    assert m.value.get() == 2 / 8
    # the same state answers a different count-derived analyzer
    cd = CountDistinct(["cat", "num"]).compute_metric_from(state)
    assert cd.value.get() == 4.0
    # and merges with a natively computed state over the same columns
    native = ColumnarTable.from_pydict({
        "cat": ["a", "z"], "num": [1, 9],
    })
    from deequ_tpu.ops.segment import group_counts_state

    merged = state.sum(group_counts_state(native, ["cat", "num"]))
    md = merged.as_dict()
    assert md[("a", 1)] == 5
    assert md[("z", 9)] == 1
    assert merged.num_rows == 10


def test_histogram_state_round_trip_compute_metric_from(tmp_path):
    """A reference-persisted Histogram frequency state (stringified
    labels, num_rows counts ALL rows) feeds compute_metric_from and
    yields the exact Distribution the reference would rebuild."""
    from deequ_tpu.analyzers import Histogram
    from deequ_tpu.data.io import write_parquet
    from deequ_tpu.data.table import ColumnarTable

    analyzer = Histogram("cat", max_detail_bins=2)
    ident = reference_state_identifier(analyzer)
    freq_table = ColumnarTable.from_pydict({
        "cat": ["x", "y", "NullValue", "z"],
        "absolute": [5, 3, 1, 1],
    })
    write_parquet(freq_table, str(tmp_path / f"s-{ident}-frequencies.pqt"))
    (tmp_path / f"s-{ident}-num_rows.bin").write_bytes(struct.pack(">q", 10))

    state = load_reference_state(str(tmp_path / "s"), analyzer)
    m = analyzer.compute_metric_from(state)
    dist = m.value.get()
    assert dist.number_of_bins == 4  # bins count ALL groups, not just top-N
    assert set(dist.values) == {"x", "y"}  # top max_detail_bins=2 by count
    assert dist.values["x"].absolute == 5
    assert dist.values["x"].ratio == 0.5
    assert dist.values["y"].absolute == 3
    # and the imported state serializes through the native serde
    from deequ_tpu.states.serde import deserialize_state, serialize_state

    back = deserialize_state(serialize_state(state))
    assert back.as_dict() == state.as_dict()
    assert analyzer.compute_metric_from(back).value.get().values == dist.values
