"""chip_smoke.py — the quickest proof that deequ_tpu still starts on the chip.

Drives the main path once, in ONE process, through the entry points a user
calls (``table.persist()``, ``VerificationSuite``, ``stream_table`` /
``AnalysisRunner``, state providers, ``VerificationService``, ``StreamHub``)
and checks every answer against a numpy reference written here. All data
comes from ``--seed``. It refuses to run without a TPU: there is no CPU
continuation, and a phase that fails fails the run.

    python chip_smoke.py              # one chip: every phase
    python chip_smoke.py --chips 4    # four chips: the row-sharded resident
                                      # suite against the same suite on one
                                      # chip, and nothing else

Earlier lines carry facts (resident bytes, fetches, kernel census, walls);
the LAST line of stdout is exactly
``{"ok": true, "device": {"platform": "tpu", "kind": "...", "count": N}}``.
"""

from __future__ import annotations

import argparse
import json
import struct
import sys
import time

import numpy as np

# -- sizes (each phase takes its sizes as arguments; these are main()'s) ------

RESIDENT_ROWS = 10_000_000  # BASELINE config 2: 10M rows x 20 f64 columns
N_NUMERIC = 20
WIDE_CARD = 1_000_000       # the high-cardinality dictionary string column
STREAM_BATCHES = 8
SERVE_TENANTS = 8
SERVE_SUBMITS = 32
SERVE_ROWS = 100_000
WINDOW_STREAMS = 4
WINDOW_BATCHES = 8
WINDOW_BATCH_ROWS = 100_000
KERNEL_ROWS = 1 << 20

N_CATS = 20
# HLL at p=9 has sigma = 1.04/sqrt(512) = 4.6%; the repo pins <= 6% on its
# fixtures (tests/test_reference_conformance.py) and the default seed meets
# it at this size on every column. The sharp check is the exact one against
# the host registers (reference_metrics).
HLL_REL_BOUND = 0.06
QUANTILE_REL_ERROR = 0.01   # ApproxQuantile's default relative_error
MOMENT_REL = 1e-9

#: any of these on SCAN_STATS means the run did NOT take the device path it
#: claims — here that is a failure, not resilience (the check below refuses
#: EVERY degradation event; these are the ones it names in its message)
DEGRADATION_KINDS = (
    "cpu_fallback", "oom_bisect", "encoded_demote", "mesh_reshard",
    "watchdog_timeout",
)


class SmokeFailure(Exception):
    """A phase's answer was wrong, or the run degraded."""


def _check(cond: bool, what: str) -> None:
    if not cond:
        raise SmokeFailure(what)


def _say(**facts) -> None:
    print(json.dumps(facts, default=str), flush=True)


def _bits(v) -> bytes:
    return struct.pack("<d", float(v))


def _rel_err(got: float, want: float) -> float:
    return abs(got - want) / max(1.0, abs(want))


def _rank_err(ordered: np.ndarray, q: float, value: float) -> float:
    """How far ``value`` sits from rank ``q`` of the sorted valid values."""
    return abs(np.searchsorted(ordered, value, side="right") / len(ordered) - q)


def _pair_round(v: np.ndarray) -> np.ndarray:
    """What a fractional value IS on the device: the packer ships f64 as
    an (hi, lo) f32 pair (~48 mantissa bits, README "native-dtype
    compute"), so min/max are exact on these values, not on the f64
    originals (they differ from them by < 1e-14 relative)."""
    hi = v.astype(np.float32)
    lo = (v - hi.astype(np.float64)).astype(np.float32)
    return hi.astype(np.float64) + lo.astype(np.float64)


# -- data ---------------------------------------------------------------------


def build_table(n_rows: int, seed: int, n_numeric: int = N_NUMERIC,
                wide_card: int = WIDE_CARD):
    """The BASELINE config-2 shape plus the three key/string columns:
    ``n_numeric`` f64 columns with 1% nulls (c1 correlated with c0), one
    near-unique integer key, one ~20-value and one ``wide_card``-distinct
    dictionary string column."""
    from deequ_tpu.data.table import Column, ColumnarTable, DType

    rng = np.random.default_rng(seed)
    cols = []
    c0 = None
    for i in range(n_numeric):
        values = rng.normal(100.0 + i, 5.0, n_rows)
        if i == 0:
            c0 = values
        elif i == 1:
            values = 0.6 * c0 + 0.4 * values
        mask = np.ones(n_rows, dtype=np.bool_)
        mask[rng.integers(0, n_rows, max(n_rows // 100, 1))] = False
        cols.append(Column(f"c{i}", DType.FRACTIONAL, values=values, mask=mask))
    key = rng.permutation(n_rows).astype(np.int64)
    dup = rng.integers(0, n_rows, max(n_rows // 1000, 1))
    key[dup] = key[(dup + 1) % n_rows]
    cols.append(Column("key", DType.INTEGRAL, values=key))
    weights = 1.0 / np.arange(1, N_CATS + 1)
    cat = rng.choice(N_CATS, n_rows, p=weights / weights.sum()).astype(np.int32)
    cat[rng.integers(0, n_rows, max(n_rows // 200, 1))] = -1
    cols.append(Column(
        "cat", DType.STRING, codes=cat,
        dictionary=np.array([f"cat_{j:02d}" for j in range(N_CATS)], dtype=object),
    ))
    card = max(min(wide_card, n_rows // 4), 2)
    cols.append(Column(
        "ustr", DType.STRING,
        codes=rng.integers(0, card, n_rows).astype(np.int32),
        dictionary=np.array([f"user_{j:07d}" for j in range(card)], dtype=object),
    ))
    return ColumnarTable(cols)


WHERE_CAT = "cat_03"
KEY_FLOOR = 1000


def suite_analyzers(n_numeric: int = N_NUMERIC):
    """The analyzer set of the resident suite, in reference order."""
    from deequ_tpu.analyzers import (
        ApproxCountDistinct, ApproxQuantile, Completeness, Compliance,
        Correlation, Entropy, Histogram, Maximum, Mean, Minimum, Size,
        StandardDeviation, Uniqueness,
    )

    out = [Size()]
    for i in range(n_numeric):
        c = f"c{i}"
        out += [Completeness(c), Mean(c), StandardDeviation(c), Minimum(c),
                Maximum(c)]
    out += [ApproxCountDistinct(c) for c in ("c0", "c2", "key", "ustr")]
    out += [ApproxQuantile("c0", 0.5), ApproxQuantile("c5", 0.9)]
    out += [
        Correlation("c0", "c1"),
        Uniqueness(["key"]),
        Histogram("cat"),
        Entropy("cat"),
        Compliance("key above floor", f"key >= {KEY_FLOOR}"),
        Mean("c3", where=f"cat = '{WHERE_CAT}'"),
        Mean("c4", where=f"key >= {KEY_FLOOR}"),
    ]
    return out


def stream_analyzers(n_numeric: int = N_NUMERIC):
    """The suite as the streaming phases run it: without Uniqueness(key)
    (its two device sorts compile per batch SHAPE, minutes each) and
    without the string-literal ``where`` (it bakes the dictionary into the
    program, which then re-traces per batch instead of being reused)."""
    return [
        a for a in suite_analyzers(n_numeric)
        if type(a).__name__ != "Uniqueness"
        and "cat" not in (getattr(a, "where", None) or "")
    ]


def suite_check(n_rows: int):
    """The declarative half: a Check whose constraints resolve to analyzers
    of ``suite_analyzers`` (one ``satisfies`` predicate, one ``where``)."""
    from deequ_tpu import Check, CheckLevel

    return (
        Check(CheckLevel.ERROR, "chip smoke")
        .has_size(lambda n: n == n_rows)
        .has_completeness("c0", lambda v: v > 0.98)
        .has_min("c0", lambda v: v > 0.0)
        .has_approx_count_distinct("ustr", lambda v: v > 1.0)
        .has_approx_quantile("c0", 0.5, lambda v: 95.0 < v < 105.0)
        .has_correlation("c0", "c1", lambda v: v > 0.5)
        .has_uniqueness(["key"], lambda v: v > 0.99)
        .has_entropy("cat", lambda v: v > 1.0)
        .satisfies(f"key >= {KEY_FLOOR}", "key above floor", lambda v: v > 0.9)
        .has_mean("c3", lambda v: 95.0 < v < 110.0)
        .where(f"cat = '{WHERE_CAT}'")
    )


# -- the numpy reference ------------------------------------------------------


def reference_metrics(table, analyzers) -> dict:
    """A plain numpy implementation of the same semantics, independent of
    the engine: analyzer -> float | dict (histogram counts) | ("distinct",
    exact count) for HLL | ("rank", sorted valid values, q) for quantiles."""
    ref = {}
    cat = table["cat"]
    for a in analyzers:
        kind = type(a).__name__
        if kind == "Size":
            ref[a] = float(table.num_rows)
            continue
        if kind == "Histogram":
            counts = np.bincount(cat.codes + 1, minlength=len(cat.dictionary) + 1)
            hist = {str(cat.dictionary[j]): int(counts[j + 1])
                    for j in range(len(cat.dictionary)) if counts[j + 1]}
            if counts[0]:
                hist["NullValue"] = int(counts[0])
            ref[a] = hist
            continue
        if kind == "Entropy":
            counts = np.bincount(cat.codes[cat.codes >= 0]).astype(np.float64)
            p = counts[counts > 0] / counts.sum()
            ref[a] = float(-(p * np.log(p)).sum())
            continue
        if kind == "Uniqueness":
            _, counts = np.unique(table["key"].values, return_counts=True)
            ref[a] = float((counts == 1).sum() / table.num_rows)
            continue
        if kind == "Compliance":
            ref[a] = float((table["key"].values >= KEY_FLOOR).mean())
            continue
        if kind == "Correlation":
            x, y = table[a.first_column], table[a.second_column]
            ok = x.mask & y.mask
            ref[a] = float(np.corrcoef(x.values[ok], y.values[ok])[0, 1])
            continue
        col = table[a.column]
        if kind == "ApproxCountDistinct":
            present = (
                col.codes[col.codes >= 0] if col.codes is not None
                else col.values[col.mask]
            )
            ref[a] = ("distinct", float(len(np.unique(present))),
                      _hll_host_estimate(col))
            continue
        ok = col.mask
        where = getattr(a, "where", None) or ""
        if "cat" in where:
            ok = ok & (cat.codes == list(cat.dictionary).index(WHERE_CAT))
        elif "key" in where:
            ok = ok & (table["key"].values >= KEY_FLOOR)
        v = col.values[ok]
        if kind == "Completeness":
            ref[a] = float(col.mask.mean())
        elif kind == "Mean":
            ref[a] = float(v.mean())
        elif kind == "StandardDeviation":
            ref[a] = float(v.std())
        elif kind == "Minimum":
            ref[a] = float(_pair_round(v).min())
        elif kind == "Maximum":
            ref[a] = float(_pair_round(v).max())
        elif kind == "ApproxQuantile":
            ref[a] = ("rank", np.sort(v), float(a.quantile))
        else:
            raise SmokeFailure(f"no numpy reference for {a}")
    return ref


def _hll_host_estimate(col) -> float:
    """The HLL estimate as the repo's HOST formulas give it (numpy, the
    ``xp is np`` branches of ops/hll.py): the device's u32 hash + register
    fold must reproduce it exactly, register for register."""
    from deequ_tpu.ops import hll

    p = hll.precision_from_relative_sd()
    if col.codes is not None:
        packed = hll.string_idx_rank_lut(col.dictionary, p)[
            col.codes[col.codes >= 0]
        ]
        idx, rank = packed >> 6, packed & 0x3F
    else:
        idx, rank = hll.idx_rank_numeric(
            col.values[col.mask].astype(np.float64), p, np
        )
    registers = np.zeros(1 << p, dtype=np.int64)
    np.maximum.at(registers, idx, rank)
    return hll.estimate_cardinality(registers)


#: analyzers whose fold is exactly associative (counts, min/max, HLL
#: registers): equal to the reference, and bit-identical between two runs
_EXACT = ("Size", "Completeness", "Minimum", "Maximum", "Uniqueness",
          "Compliance", "ApproxCountDistinct", "Histogram")


def check_against_reference(metrics: dict, ref: dict, what: str) -> dict:
    """Every metric ``is_success`` (the engine stores failures as VALUES,
    so an exit code proves nothing) and equals the reference: exact for
    counts/min/max/histogram, <= 1e-9 relative for moments, correlation and
    entropy, HLL within the repo's pinned bound, quantiles within the
    analyzer's rank error. Returns the worst observed errors."""
    worst = {"hll_rel": 0.0, "quantile_rank": 0.0, "moment_rel": 0.0}
    for a, want in ref.items():
        _check(a in metrics, f"{what}: no metric for {a}")
        value = metrics[a].value
        _check(value.is_success,
               f"{what}: {a} FAILED: {getattr(value, 'exception', value)!r}")
        got = value.get()
        kind = type(a).__name__
        if kind == "Histogram":
            have = {k: v.absolute for k, v in got.values.items()}
            _check(have == want, f"{what}: {a}: {have} != {want}")
        elif isinstance(want, tuple) and want[0] == "distinct":
            _check(got == want[2],
                   f"{what}: {a}: {got} != host registers' {want[2]}")
            err = abs(got - want[1]) / want[1]
            worst["hll_rel"] = max(worst["hll_rel"], err)
            _check(err <= HLL_REL_BOUND,
                   f"{what}: {a}: {got} vs {want[1]} exact ({err:.3%})")
        elif isinstance(want, tuple) and want[0] == "rank":
            err = _rank_err(want[1], want[2], got)
            worst["quantile_rank"] = max(worst["quantile_rank"], err)
            _check(err <= QUANTILE_REL_ERROR,
                   f"{what}: {a}: value {got} is {err:.4f} off its rank")
        elif kind in _EXACT:
            _check(got == want, f"{what}: {a}: {got!r} != {want!r}")
        else:
            err = _rel_err(got, want)
            worst["moment_rel"] = max(worst["moment_rel"], err)
            _check(err <= MOMENT_REL, f"{what}: {a}: {got!r} vs {want!r}")
    return worst


def check_same(got: dict, want: dict, analyzers, what: str, ref=None) -> None:
    """Two engine runs over the same rows agree: bit-identical where the
    fold is exactly associative (counts, min/max, HLL registers -> the same
    estimate), <= 1e-9 relative for float moments (chunking moves the
    reduction order by ulps), quantiles within rank error of the truth."""
    for a in analyzers:
        kind = type(a).__name__
        gv, wv = got[a].value, want[a].value
        _check(gv.is_success and wv.is_success,
               f"{what}: {a} failed: {gv!r} / {wv!r}")
        g, w = gv.get(), wv.get()
        if kind == "Histogram":
            _check(g.values == w.values, f"{what}: {a} differs")
        elif kind == "ApproxQuantile":
            err = _rank_err(ref[a][1], ref[a][2], g)
            _check(err <= QUANTILE_REL_ERROR,
                   f"{what}: {a}: value {g} is {err:.4f} off its rank")
        elif kind in _EXACT:
            _check(_bits(g) == _bits(w), f"{what}: {a}: {g!r} != {w!r}")
        else:
            _check(_rel_err(g, w) <= MOMENT_REL,
                   f"{what}: {a}: {g!r} vs {w!r}")


def _delta(before: dict, after: dict, keys) -> dict:
    return {k: after[k] - before[k] for k in keys}


_COUNTERS = (
    "scan_passes", "resident_passes", "chunks_processed", "grouping_passes",
    "device_fetches", "bytes_fetched", "programs_built", "programs_reused",
    "device_sort_passes", "device_select_passes", "hist_scatter_dispatches",
    "hist_onehot_dispatches", "hist_pallas_dispatches", "bytes_packed",
    "scan_seconds", "dispatch_seconds", "drain_wait_seconds",
)


# -- phases -------------------------------------------------------------------


def phase_device(want_count: int) -> dict:
    """Name the device before anything else; refuse anything but a TPU."""
    import jax

    first = jax.devices()[0]
    device = {
        "platform": first.platform,
        "kind": first.device_kind,
        "count": len(jax.devices()),
    }
    _say(device=device, jax=jax.__version__)
    if device["platform"] != "tpu":
        raise SmokeFailure(
            f"no TPU: jax.devices()[0].platform is {first.platform!r}; "
            "chip_smoke.py has no CPU continuation"
        )
    _check(device["count"] >= want_count,
           f"{want_count} chip(s) asked, {device['count']} visible")
    return device


def phase_round_trip(reps: int = 20) -> float:
    """One trivial dispatch + fetch, median of ``reps``: the floor any
    result pays on this machine's host<->device link (ROADMAP A1)."""
    import jax
    import jax.numpy as jnp

    probe = jax.jit(lambda a: a * 2.0)
    arg = jnp.ones((8,), jnp.float32)
    np.asarray(probe(arg))
    walls = []
    for _ in range(reps):
        t0 = time.perf_counter()
        np.asarray(probe(arg))
        walls.append(time.perf_counter() - t0)
    floor_ms = float(np.median(walls)) * 1e3
    _say(phase="round_trip", dispatch_fetch_floor_ms=floor_ms, reps=reps)
    return floor_ms


def phase_kernel_tier(rows: int, seed: int) -> None:
    """The three bincount variants on the device, exact against
    np.bincount — the one Pallas kernel has no policy route, so this is the
    only place the chip runs it."""
    import jax
    import jax.numpy as jnp

    from deequ_tpu.ops.histogram_device import bincount_variant

    rng = np.random.default_rng(seed)
    for width in (40, 5000):
        seg = rng.integers(-1, width + 2, rows).astype(np.int32)
        ok = (seg >= 0) & (seg < width)
        want = np.bincount(seg[ok], minlength=width)
        for variant in ("scatter", "onehot", "pallas"):
            fn = jax.jit(
                lambda s, v=variant: bincount_variant(v, s, width, jnp)
            )
            got = np.asarray(fn(jnp.asarray(seg)))
            _check(bool((got == want).all()),
                   f"bincount {variant} width {width} differs from numpy")
    # the one-hot matmul under vmap, at the tenant-axis width the chip got
    # wrong before it mapped instead of batching (histogram_device.py): the
    # HLL register fold and the one-hot bincount, 8 members each
    from deequ_tpu.ops import hll

    batch, n, m = 8, max(rows // 8, hll._MXU_FOLD_MIN_ROWS), 512
    idx = rng.integers(0, m, (batch, n)).astype(np.int32)
    rank = rng.integers(1, 40, (batch, n)).astype(np.int32)
    regs = np.asarray(jax.jit(jax.vmap(
        lambda i, r: hll._registers_mxu_fold(i, r, m, jnp)
    ))(jnp.asarray(idx), jnp.asarray(rank)))
    seg = rng.integers(-1, 5002, (batch, n)).astype(np.int32)
    counts = np.asarray(jax.jit(jax.vmap(
        lambda s: bincount_variant("onehot", s, 5000, jnp)
    ))(jnp.asarray(seg)))
    for k in range(batch):
        want = np.zeros(m, dtype=np.int64)
        np.maximum.at(want, idx[k], rank[k])
        _check(bool((regs[k] == want).all()),
               f"vmapped HLL register fold: member {k} of {batch} is wrong")
        ok = (seg[k] >= 0) & (seg[k] < 5000)
        _check(bool((counts[k] == np.bincount(seg[k][ok], minlength=5000)).all()),
               f"vmapped one-hot bincount: member {k} of {batch} is wrong")
    _say(phase="kernel_tier", rows=rows, variants="scatter,onehot,pallas",
         vmapped_members=batch, exact=True)


def run_suite(table, n_numeric: int):
    from deequ_tpu import CheckStatus, VerificationSuite

    result = (
        VerificationSuite.on_data(table)
        .add_check(suite_check(table.num_rows))
        .add_required_analyzers(suite_analyzers(n_numeric))
        .run()
    )
    _check(result.status == CheckStatus.SUCCESS,
           f"check status {result.status}: {_failed_constraints(result)}")
    return result


def _failed_constraints(result):
    from deequ_tpu import VerificationResult

    return [r for r in VerificationResult.check_results_as_rows(result)
            if r["constraint_status"] != "Success"]


def phase_resident(table, ref: dict, n_numeric: int) -> dict:
    """persist() once, run the suite twice: the second run builds no
    program; every metric equals the numpy reference."""
    from deequ_tpu import native
    from deequ_tpu.ops.scan_engine import SCAN_STATS, total_resident_bytes

    t0 = time.perf_counter()
    table.persist()
    persist_s = time.perf_counter() - t0
    _check(table.is_persisted, "persist() left the table non-resident")

    walls, snaps = [], [SCAN_STATS.snapshot()]
    result = None
    for _ in range(2):
        t0 = time.perf_counter()
        result = run_suite(table, n_numeric)
        walls.append(time.perf_counter() - t0)
        snaps.append(SCAN_STATS.snapshot())
    first = _delta(snaps[0], snaps[1], _COUNTERS)
    second = _delta(snaps[1], snaps[2], _COUNTERS)
    _check(second["programs_built"] == 0,
           f"second run built {second['programs_built']} program(s)")
    _check(second["resident_passes"] >= 1 and second["bytes_packed"] == 0,
           f"second run left the resident path: {second}")
    worst = check_against_reference(result.metrics, ref, "resident")
    _say(
        phase="resident", rows=table.num_rows, persist_s=persist_s,
        resident_bytes=total_resident_bytes(),
        cold_run_s=walls[0], warm_run_s=walls[1],
        first_run=first, second_run=second,
        native_available=native.available(), worst_error=worst,
    )
    return result.metrics


def phase_streaming(table, resident_metrics: dict, ref: dict,
                    n_numeric: int, n_batches: int) -> None:
    """The same rows as >= 8 batches through stream_table/AnalysisRunner
    equal the resident run; two partitions (each its own stream of the same
    batch shape) through save_states_with + run_on_aggregated_states equal
    the whole."""
    from deequ_tpu.analyzers.runner import AnalysisRunner
    from deequ_tpu.data.streaming import stream_table
    from deequ_tpu.ops.scan_engine import SCAN_STATS
    from deequ_tpu.states import InMemoryStateProvider

    analyzers = stream_analyzers(n_numeric)
    batch_rows = -(-table.num_rows // n_batches)
    before = SCAN_STATS.snapshot()
    t0 = time.perf_counter()
    ctx = AnalysisRunner.do_analysis_run(
        stream_table(table, batch_rows), analyzers
    )
    stream_s = time.perf_counter() - t0
    stream_stats = _delta(before, SCAN_STATS.snapshot(), _COUNTERS)
    check_same(ctx.metric_map, resident_metrics, analyzers,
               "streamed vs resident", ref)

    half = (n_batches // 2) * batch_rows
    keep = np.zeros(table.num_rows, dtype=bool)
    keep[:half] = True
    providers = []
    before = SCAN_STATS.snapshot()
    t0 = time.perf_counter()
    for part in (table.filter_rows(keep), table.filter_rows(~keep)):
        providers.append(InMemoryStateProvider())
        AnalysisRunner.do_analysis_run(
            stream_table(part, batch_rows), analyzers,
            save_states_with=providers[-1],
        )
    merged = AnalysisRunner.run_on_aggregated_states(
        table.schema, analyzers, providers
    )
    states_s = time.perf_counter() - t0
    states_stats = _delta(before, SCAN_STATS.snapshot(), _COUNTERS)
    check_same(merged.metric_map, resident_metrics, analyzers,
               "merged partition states vs whole", ref)
    _say(phase="streaming", batches=n_batches, batch_rows=batch_rows,
         stream_s=stream_s, stream=stream_stats,
         partitions=2, states_s=states_s, states=states_stats)


def _tenant_table(rows: int, seed: int):
    from deequ_tpu.data.table import Column, ColumnarTable, DType

    r = np.random.default_rng(seed)
    return ColumnarTable([
        Column("x", DType.FRACTIONAL, values=r.normal(100.0, 5.0, rows),
               mask=r.random(rows) > 0.02),
        Column("y", DType.FRACTIONAL, values=r.normal(0.0, 1.0, rows)),
        Column("i", DType.INTEGRAL, values=r.integers(0, 1000, rows)),
    ])


def _tenant_check(rows: int):
    from deequ_tpu import Check, CheckLevel

    return (
        Check(CheckLevel.ERROR, "tenant suite")
        .has_size(lambda n: n == rows)
        .is_complete("i")
        .has_completeness("x", lambda c: c > 0.9)
        .has_mean("x", lambda m: 99.0 < m < 101.0)
        .has_standard_deviation("y", lambda s: 0.9 < s < 1.1)
        .has_min("i", lambda v: v >= 0)
        .has_max("i", lambda v: v < 1000)
        .has_approx_count_distinct("i", lambda v: v > 500)
        # a fractional comparison routes x over the exact wide-f64 plane
        .satisfies("x > 90.0", "x above 90", lambda v: v > 0.9)
    )


def phase_serving(n_tenants: int, n_submits: int, rows: int, seed: int) -> None:
    """One VerificationService answers ``n_submits`` suites from
    ``n_tenants`` tenants; coalesced results are bit-identical to serial
    VerificationSuite.run."""
    from deequ_tpu import CheckStatus, VerificationSuite
    from deequ_tpu.ops.scan_engine import SCAN_STATS
    from deequ_tpu.parallel.mesh import use_mesh
    from deequ_tpu.serve import VerificationService

    load = [
        (f"tenant-{s % n_tenants}", _tenant_table(rows, seed + 1 + s))
        for s in range(n_submits)
    ]
    checks = [_tenant_check(rows)]

    def rows_of(result):
        out = {}
        for a, m in result.metrics.items():
            _check(m.value.is_success, f"serving: {a} failed: {m.value!r}")
            out[str(a)] = m.value.get()
        return out

    # coalescing needs the single-device view (docs/serving.md): a no-op on
    # one chip, explicit where more devices are visible
    with use_mesh(None):
        serial = [
            rows_of(VerificationSuite.run(table, checks)) for _, table in load
        ]
        before = SCAN_STATS.snapshot()
        service = VerificationService(max_batch=n_submits)
        try:
            t0 = time.perf_counter()
            futures = [
                service.submit(table, checks, tenant=tenant)
                for tenant, table in load
            ]
            results = [f.result(timeout=600) for f in futures]
            wall = time.perf_counter() - t0
        finally:
            service.stop()
    after = SCAN_STATS.snapshot()
    # Exactly-associative folds (counts, min/max, HLL registers) must be
    # BIT-identical to serial. Float moments need not be on the chip: the
    # vmapped program reduces its f64 tails in another order than the 1-D
    # one (XLA:TPU, measured PR 21: <= 3e-15 relative on Mean/StdDev) —
    # bit-identity there is a CPU-backend fact (docs/serving.md); they are
    # held to 1e-12 and their ulp drift is reported, not hidden.
    wrong, drift = [], []
    for (tenant, _), result, want in zip(load, results, serial):
        _check(result.status == CheckStatus.SUCCESS,
               f"serving: {tenant} status {result.status}")
        got = rows_of(result)
        for name in want:
            if _bits(got[name]) == _bits(want[name]):
                continue
            rel = abs(got[name] - want[name]) / max(abs(want[name]), 1e-300)
            exact = name.split("(")[0] in _EXACT
            (wrong if exact or rel > 1e-12 else drift).append(
                (tenant, name, got[name], want[name])
            )
    _check(not wrong,
           f"serving: {len(wrong)} coalesced metric(s) differ from serial "
           f"(tenant, analyzer, coalesced, serial): {wrong[:8]}")
    max_drift = max(
        (abs(g - w) / max(abs(w), 1e-300) for _, _, g, w in drift), default=0.0
    )
    stats = _delta(before, after, (
        "coalesced_batches", "coalesced_tenants", "coalesce_padded_slots",
        "device_fetches", "programs_built", "plan_cache_hits",
    ))
    _check(stats["coalesced_tenants"] >= n_submits // 2,
           f"serving: only {stats['coalesced_tenants']} suites coalesced")
    _say(phase="serving", tenants=n_tenants, submits=n_submits, rows=rows,
         wall_s=wall, float_metrics_not_bit_identical=len(drift),
         max_float_drift_rel=max_drift, **stats)


def phase_windows(n_streams: int, n_batches: int, batch_rows: int,
                  seed: int) -> None:
    """A StreamHub takes in a few windows of events per stream and closes
    them; each close equals the one-shot suite over the same rows
    (integer-valued data, so sums are exactly associative)."""
    from deequ_tpu import Check, CheckLevel, CheckStatus, VerificationSuite
    from deequ_tpu.analyzers import (
        Completeness, Maximum, Mean, Minimum, Size, Sum,
    )
    from deequ_tpu.data.table import ColumnarTable
    from deequ_tpu.windows import StreamHub, WatermarkPolicy, WindowSpec

    analyzers = [Size(), Completeness("v"), Mean("v"), Minimum("v"),
                 Maximum("v"), Sum("v")]
    check = Check(CheckLevel.ERROR, "window").has_completeness(
        "v", lambda c: c > 0.8
    )
    span = 10.0  # seconds of event time per batch; windows are 20 s tumbling
    hub = StreamHub()
    t0 = time.perf_counter()
    closed = 0
    for s in range(n_streams):
        rng = np.random.default_rng(seed + 100 + s)
        stream_id = f"stream-{s}"
        hub.register_stream(
            stream_id, analyzers, checks=[check],
            spec=WindowSpec(size_s=2 * span, slide_s=2 * span,
                            time_column="ts"),
            policy=WatermarkPolicy(lag_s=1.0, late_policy="drop"),
            batch_rows=batch_rows,
        )
        batches, closes = [], []
        for b in range(n_batches):
            ts = np.sort(rng.uniform(b * span, (b + 1) * span, batch_rows))
            v = np.floor(rng.uniform(-40.0, 41.0, batch_rows))
            v[rng.random(batch_rows) < 0.1] = np.nan
            batches.append({"ts": ts, "v": v})
            closes += hub.process_batch(stream_id, batches[-1])
        closes += hub.stream(stream_id).flush()
        ts = np.concatenate([b["ts"] for b in batches])
        v = np.concatenate([b["v"] for b in batches])
        emitted = [c for c in closes if c.emitted]
        _check(len(emitted) == -(-n_batches // 2) and len(emitted) == len(closes),
               f"windows: {stream_id} emitted {len(emitted)} of {len(closes)}")
        for c in emitted:
            keep = (ts >= c.start) & (ts < c.end)
            one_shot = VerificationSuite.on_data(ColumnarTable.from_pydict({
                "v": [None if np.isnan(x) else float(x) for x in v[keep]]
            })).add_check(check).add_required_analyzers(analyzers).run()
            _check(c.result.status == CheckStatus.SUCCESS
                   and one_shot.status == CheckStatus.SUCCESS,
                   f"windows: {stream_id} [{c.start}, {c.end}) check failed")
            for a in analyzers:
                got, want = c.result.metrics[a].value, one_shot.metrics[a].value
                _check(got.is_success and want.is_success
                       and _bits(got.get()) == _bits(want.get()),
                       f"windows: {stream_id} [{c.start}, {c.end}) {a}: "
                       f"{got!r} != {want!r}")
            closed += 1
    _say(phase="windows", streams=n_streams, batches=n_batches,
         batch_rows=batch_rows, closes_checked=closed,
         wall_s=time.perf_counter() - t0)


def phase_sharded(rows: int, seed: int, devices, n_numeric: int = N_NUMERIC,
                  wide_card: int = WIDE_CARD) -> None:
    """The resident suite row-sharded over a mesh of ``devices`` against
    the same suite on one of them: equal answers, and every device's
    addressable shard holds ~1/len(devices) of the packed rows."""
    from jax.sharding import Mesh

    from deequ_tpu.ops.scan_engine import SCAN_STATS
    from deequ_tpu.parallel.mesh import ROW_AXIS, mesh_device_ids, use_mesh

    table = build_table(rows, seed, n_numeric, wide_card)
    analyzers = suite_analyzers(n_numeric)
    ref = reference_metrics(table, analyzers)
    mesh = Mesh(np.array(devices), (ROW_AXIS,))
    n_dev = len(devices)
    with use_mesh(mesh):
        t0 = time.perf_counter()
        table.persist()
        persist_s = time.perf_counter() - t0
        cache = table._device_cache
        _check(cache.mesh is not None and cache.device_count == n_dev,
               f"persist() placed the table on {cache.device_count} device(s)")
        per_device = {int(d.id): 0 for d in devices}
        for chunk in cache.device_chunks:
            for buf in chunk:
                for shard in buf.addressable_shards:
                    per_device[int(shard.device.id)] += int(shard.data.nbytes)
        for chunk in cache.device_chunks:
            row_valid = chunk[6]
            shard_rows = sorted(
                (int(s.device.id), int(s.data.shape[0]))
                for s in row_valid.addressable_shards
            )
            _check(
                [d for d, _ in shard_rows] == sorted(per_device)
                and all(r == cache.chunk // n_dev for _, r in shard_rows),
                f"row shards are not 1/{n_dev} each: {shard_rows}",
            )
        total = sum(per_device.values())
        _check(all(abs(b / total - 1.0 / n_dev) < 0.02
                   for b in per_device.values()),
               f"packed bytes are not spread evenly: {per_device}")
        walls = []
        for _ in range(2):
            t0 = time.perf_counter()
            sharded = run_suite(table, n_numeric)
            walls.append(time.perf_counter() - t0)
        worst = check_against_reference(sharded.metrics, ref, "sharded")
        table.unpersist()
    with use_mesh(None):
        table.persist()
        _check(table._device_cache.mesh is None, "single-chip persist sharded")
        solo_walls = []
        for _ in range(2):
            t0 = time.perf_counter()
            solo = run_suite(table, n_numeric)
            solo_walls.append(time.perf_counter() - t0)
        check_against_reference(solo.metrics, ref, "single chip")
        table.unpersist()
    check_same(sharded.metrics, solo.metrics, analyzers,
               "sharded vs single chip", ref)
    _check(not SCAN_STATS.degradation_events,
           f"degraded: {SCAN_STATS.degradation_events}")
    _say(phase="sharded", rows=rows, mesh_device_ids=mesh_device_ids(mesh),
         per_device_shard_bytes=per_device, persist_s=persist_s,
         sharded_run_s=walls, single_chip_run_s=solo_walls,
         worst_error=worst, metrics_equal=True)


def check_no_degradation() -> None:
    from deequ_tpu.ops.scan_engine import SCAN_STATS

    events = SCAN_STATS.degradation_events
    _check(not events,
           "the run degraded (none of "
           f"{DEGRADATION_KINDS} or any other event may occur here): {events}")
    _check(SCAN_STATS.fallback_scans == 0, "a scan ran on the CPU fallback")


def compile_cache_census():
    """Count persistent-compile-cache hits and writes through jax's own
    monitoring events: a warm second run in the same checkout writes 0."""
    import jax

    counts = {"requests": 0, "hits": 0, "writes": 0}
    names = {
        "/jax/compilation_cache/compile_requests_use_cache": "requests",
        "/jax/compilation_cache/cache_hits": "hits",
        "/jax/compilation_cache/cache_misses": "writes",
    }

    def listener(event, **_kwargs):
        if event in names:
            counts[names[event]] += 1

    jax.monitoring.register_event_listener(listener)
    return counts


def main(argv=None) -> int:
    parser = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    parser.add_argument("--seed", type=int, default=21)
    parser.add_argument(
        "--chips", type=int, choices=(1, 4), default=1,
        help="4: only the row-sharded resident suite against one chip",
    )
    args = parser.parse_args(argv)
    t_start = time.perf_counter()

    import jax

    import deequ_tpu  # noqa: F401 — x64 + the compile-cache placement

    cache = compile_cache_census()
    device = phase_device(args.chips)
    _say(compile_cache_dir=jax.config.jax_compilation_cache_dir)
    if args.chips == 4:
        _check(device["count"] == 4, f"--chips 4 with {device['count']} chips")
        phase_sharded(RESIDENT_ROWS, args.seed, jax.devices(), N_NUMERIC,
                      WIDE_CARD)
    else:
        phase_round_trip()
        phase_kernel_tier(KERNEL_ROWS, args.seed)
        t0 = time.perf_counter()
        table = build_table(RESIDENT_ROWS, args.seed, N_NUMERIC, WIDE_CARD)
        ref = reference_metrics(table, suite_analyzers(N_NUMERIC))
        _say(phase="data", rows=table.num_rows,
             build_and_reference_s=time.perf_counter() - t0)
        resident = phase_resident(table, ref, N_NUMERIC)
        phase_streaming(table, resident, ref, N_NUMERIC, STREAM_BATCHES)
        table.unpersist()
        phase_serving(SERVE_TENANTS, SERVE_SUBMITS, SERVE_ROWS, args.seed)
        phase_windows(WINDOW_STREAMS, WINDOW_BATCHES, WINDOW_BATCH_ROWS,
                      args.seed)
        check_no_degradation()
    _say(wall_s=time.perf_counter() - t_start, compile_cache=cache)
    print(json.dumps({"ok": True, "device": device}), flush=True)
    return 0


if __name__ == "__main__":
    try:
        sys.exit(main())
    except SmokeFailure as failure:
        print(f"chip_smoke: FAILED: {failure}", file=sys.stderr)
        sys.exit(1)
