"""The reference against the engine at tiny sizes, the hash against public
vectors, and the control (the reference in float32) coming out not correct."""

import numpy as np

from chipbench import cells, compare, reference
from chipbench.generators import profile_table


def test_xxhash64_matches_the_public_vectors_and_a_second_implementation():
    # xxHash64's published test values, seed 0
    got = reference.xxhash64_short(["", "a", "abc"], 0)
    assert [f"{int(h):016x}" for h in got] == [
        "ef46db3751d8e999", "d24ec4f1a98c6e5b", "44bc2cf5ad770999"]
    # every length under 32 (8-byte lanes, the 4-byte lane, the byte tail)
    # against the program's scalar implementation: written apart, they agree
    from deequ_tpu.ops.hll import xxhash64_bytes

    rng = np.random.default_rng(3)
    words = ["".join(chr(c) for c in rng.integers(33, 127, n))
             for n in range(32) for _ in range(3)]
    for seed in (0, 42):
        ours = reference.xxhash64_short(words, seed)
        assert [int(h) for h in ours] == [
            xxhash64_bytes(w.encode(), seed) for w in words]


def test_hll_registers_and_estimate_on_known_cardinalities():
    p = reference.hll_precision()
    assert p == 9
    values = np.arange(50_000, dtype=np.float64) * 1.5
    idx, rank = reference.idx_rank_numbers(np.concatenate([values, values]), p)
    regs = reference.registers(idx, rank, p)
    want = np.zeros(1 << p, dtype=np.int64)
    np.maximum.at(want, idx, rank)
    assert (regs == want).all()
    assert abs(reference.hll_estimate(regs) / 50_000 - 1.0) < 0.15


def test_same_seed_same_table_whatever_the_pool(tiny_cell):
    config = tiny_cell("rich.scan")["config"]
    assert len(profile_table.generate(
        100, 1, tiny_cell("profile10m.scan")["config"]["generator_params"]
    )["columns"]) == 20
    a = profile_table.generate(4000, 2**31 + 11, config["generator_params"], threads=1)
    b = profile_table.generate(4000, 2**31 + 11, config["generator_params"], threads=8)
    c = profile_table.generate(4000, 12, config["generator_params"])
    for x, y, z in zip(a["columns"], b["columns"], c["columns"]):
        key = "codes" if x["kind"] == "string" else "values"
        assert (x[key] == y[key]).all() and not (x[key] == z[key]).all()


def test_the_engine_agrees_with_the_reference_on_both_cells(run_tiny):
    for name in ("profile10m.scan", "append1b.serial", "rich.scan",
                 "rich.serial", "rich.grouping"):
        # 24k rows: above the engine's host-grouping limit, so the device
        # sorts of the grouping cell run
        result = run_tiny(name, seed=2**31 + 5, rows=24_000)
        assert result["correct"] is True, (name, result["notes"], result["checks"])
        assert result["failed"] == 0 and result["attempted"] >= 1
        for check in result["checks"].values():
            assert check["value"] <= check["limit"]


def test_append_verdicts_follow_the_history(run_tiny):
    """The anomaly check fails on append 0 (no history), fires on appends 1
    and 2 (Size x2.0, x1.5 > 1.4) and not after; the engine said the same
    (verdict_mismatches is 0 over a window of 4+ appends)."""
    cell = cells.load_cell("append1b.serial")
    suite = cell["suite"]
    answers = [[float(n)] + [0.0] * (len(suite["analyzers"]) - 1)
               for n in (10, 20, 30, 40)]
    only_anomaly = dict(suite, check=dict(suite["check"], constraints=[]))
    rows = [reference.verdict_rows(only_anomaly, a, 10,
                                   history=[x[0] for x in answers[:i]])
            for i, a in enumerate(answers)]
    assert [r[-1][1] for r in rows] == ["Failure", "Failure", "Failure", "Success"]
    result = run_tiny("append1b.serial", seconds=1.0)
    assert result["window"]["operations"] >= 4
    assert result["checks"]["verdict_mismatches"]["value"] == 0


def test_the_control_in_float32_is_not_correct(tiny_cell):
    """The reference computed in float32, put in the program's place,
    fails the comparison: lower precision cannot pass."""
    for name in ("profile10m.scan", "append1b.serial", "rich.scan",
                 "rich.serial", "rich.grouping"):
        cell = tiny_cell(name, rows=200_000)
        config, suite = cell["config"], cell["suite"]
        data = profile_table.generate(config["rows"], 99, config["generator_params"])
        driver = cells.plugin("drivers", cell["traffic"]["driver"])
        records = [{"k": k, "rows": driver.rows_per_operation(config)}
                   for k in range(3)]
        verdict = compare.control_verdict(driver.slices, config, suite, data,
                                          records)
        assert verdict["correct"] is False
        checks = verdict["checks"]
        assert checks["moment_rel"]["value"] > 3 * checks["moment_rel"]["limit"]
        if name != "rich.grouping":  # its exact answers are counts
            assert checks["exact_mismatches"]["value"] > 0
