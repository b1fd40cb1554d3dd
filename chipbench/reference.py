"""The plain reference: what a suite (``chipbench/suites/*.json``) means on
the generated columns, in straightforward numpy and float64.

It imports nothing of the program and takes nothing the program has made.
The HLL hash (the analyzer's state format: murmur3 ``fmix32`` over the
double-float split for numbers, xxHash64 for strings, Ertl's estimator) is
written out here from the public algorithms, so the device's register fold
is held to it register for register.

One path serves both drivers: ``summarize`` reduces one slice of rows to
mergeable summaries per suite entry, ``combine`` merges slices with
multiplicities (a resident table is one slice taken once; after k appends
the data set is the multiset of the slices appended so far).

``precision="float32"`` is the CONTROL, never the reference: the same
arithmetic with every fractional value and accumulator in float32, the
nearest precision below what the configurations state.
"""

from __future__ import annotations

import math
import re
from concurrent.futures import ThreadPoolExecutor

import numpy as np

from chipbench.columns import pool_size, slice_rows

HLL_RELATIVE_SD = 0.05
HLL_SEED = 42

EXACT = ("Size", "Completeness", "Minimum", "Maximum", "Compliance",
         "ApproxCountDistinct", "Uniqueness", "Histogram")


class NoReference(Exception):
    """The reference has no meaning for what a suite file asks."""


# -- columns and predicates ---------------------------------------------------


def _column(data: dict, name: str) -> dict:
    for c in data["columns"]:
        if c["name"] == name:
            return c
    raise NoReference(f"no column {name!r}")


def _valid(col: dict) -> np.ndarray:
    if col["kind"] == "string":
        return col["codes"] >= 0
    if col["mask"] is None:
        return np.ones(len(col["values"]), dtype=np.bool_)
    return col["mask"]


_PRED = re.compile(
    r"^\s*(\w+)\s*(>=|<=|=|>|<)\s*(?:'([^']*)'|(-?\d+(?:\.\d+)?))\s*$")
_OPS = {">=": np.greater_equal, "<=": np.less_equal, ">": np.greater,
        "<": np.less, "=": np.equal}


def predicate(data: dict, expr: str) -> np.ndarray:
    """Rows where ``<column> <op> <literal>`` holds (null rows: False)."""
    m = _PRED.match(expr)
    if not m:
        raise NoReference(f"predicate {expr!r} is beyond the reference")
    col = _column(data, m.group(1))
    if m.group(3) is not None:
        if col["kind"] != "string" or m.group(2) != "=":
            raise NoReference(f"predicate {expr!r}: strings compare by =")
        hits = np.flatnonzero(col["dictionary"] == m.group(3))
        return col["codes"] == (int(hits[0]) if len(hits) else -2)
    return _OPS[m.group(2)](col["values"], float(m.group(4))) & _valid(col)


# -- the HLL state format, from the public algorithms -------------------------


def hll_precision(relative_sd: float = HLL_RELATIVE_SD) -> int:
    return max(4, math.ceil(2.0 * math.log(1.106 / relative_sd) / math.log(2.0)))


def _fmix32(x):
    x = x ^ (x >> np.uint32(16))
    x = x * np.uint32(0x85EBCA6B)
    x = x ^ (x >> np.uint32(13))
    x = x * np.uint32(0xC2B2AE35)
    return x ^ (x >> np.uint32(16))


def _clz(x, bits: int):
    """Leading zeros of unsigned ``bits``-wide integers (``bits`` for 0)."""
    if bits == 32:  # exact in float64: frexp's exponent is the bit length
        return (32 - np.frexp(x.astype(np.float64))[1]).astype(np.int32)
    dtype = x.dtype.type
    n = np.full(x.shape, bits, dtype=np.int32)
    shift = bits // 2
    while shift:
        y = x >> dtype(shift)
        hit = y != 0
        x = np.where(hit, y, x)
        n = n - np.where(hit, np.int32(shift), np.int32(0))
        shift //= 2
    return n - (x != 0).astype(np.int32)


def idx_rank_numbers(values: np.ndarray, p: int):
    """(register index, rank) of f64 values: the value is split into the
    (hi, lo) float32 pair it is on the device, the two bit patterns are
    mixed both ways with murmur3's finalizer."""
    canonical = values.astype(np.float64) + 0.0
    with np.errstate(over="ignore", invalid="ignore"):
        hi = canonical.astype(np.float32)
        diff = canonical - hi.astype(np.float64)
        lo = np.where(np.isfinite(diff), diff, 0.0).astype(np.float32)
        lo = np.where(np.isfinite(hi), lo, np.float32(np.nan))
    hb, lb = hi.view(np.uint32), lo.view(np.uint32)
    s = np.uint32(HLL_SEED)
    a = _fmix32(_fmix32(hb ^ s) ^ lb)
    b = _fmix32(_fmix32(lb ^ s ^ np.uint32(0x9E3779B9)) ^ hb)
    idx = (a >> np.uint32(32 - p)).astype(np.int32)
    w1 = a << np.uint32(p)
    rank = _clz(w1, 32) + 1
    tail = np.flatnonzero(w1 == 0)  # one row in 2^(32-p): the second word
    rank[tail] = (32 - p) + _clz(b[tail], 32) + 1
    return idx, np.minimum(rank, 64 - p + 1)


_P1 = np.uint64(0x9E3779B185EBCA87)
_P2 = np.uint64(0xC2B2AE3D27D4EB4F)
_P3 = np.uint64(0x165667B19E3779F9)
_P4 = np.uint64(0x85EBCA77C2B2AE63)
_P5 = np.uint64(0x27D4EB2F165667C5)


def _rotl64(x, r: int):
    return (x << np.uint64(r)) | (x >> np.uint64(64 - r))


def xxhash64_short(strings, seed: int = HLL_SEED) -> np.ndarray:
    """xxHash64 (public algorithm) of utf-8 strings under 32 bytes,
    vectorised over all strings of one length."""
    encoded = [str(s).encode("utf-8") for s in strings]
    lengths = np.array([len(b) for b in encoded], dtype=np.int64)
    out = np.zeros(len(encoded), dtype=np.uint64)
    with np.errstate(over="ignore"):
        for n in np.unique(lengths):
            n = int(n)
            if n >= 32:
                raise NoReference("xxhash64_short: a string of 32+ bytes")
            rows = np.flatnonzero(lengths == n)
            raw = np.frombuffer(b"".join(encoded[i] for i in rows),
                                dtype=np.uint8).reshape(len(rows), n)
            h = np.full(len(rows), np.uint64(seed) + _P5 + np.uint64(n),
                        dtype=np.uint64)
            i = 0
            while i + 8 <= n:
                lane = np.ascontiguousarray(raw[:, i:i + 8]).view("<u8")[:, 0]
                k = _rotl64(lane * _P2, 31) * _P1
                h = _rotl64(h ^ k, 27) * _P1 + _P4
                i += 8
            if i + 4 <= n:
                lane = np.ascontiguousarray(raw[:, i:i + 4]).view("<u4")[:, 0]
                h = _rotl64(h ^ (lane.astype(np.uint64) * _P1), 23) * _P2 + _P3
                i += 4
            while i < n:
                h = _rotl64(h ^ (raw[:, i].astype(np.uint64) * _P5), 11) * _P1
                i += 1
            h ^= h >> np.uint64(33)
            h *= _P2
            h ^= h >> np.uint64(29)
            h *= _P3
            h ^= h >> np.uint64(32)
            out[rows] = h
    return out


def idx_rank_strings(dictionary, p: int):
    """(index, rank) per dictionary entry from its 64-bit hash."""
    hashes = xxhash64_short(dictionary)
    idx = (hashes >> np.uint64(64 - p)).astype(np.int32)
    rank = _clz(hashes << np.uint64(p), 64) + 1
    return idx, np.minimum(rank, 64 - p + 1).astype(np.int32)


def registers(idx: np.ndarray, rank: np.ndarray, p: int) -> np.ndarray:
    """Max rank per index, by a count over (index, rank) pairs."""
    seen = np.bincount(idx.astype(np.int64) * 64 + rank,
                       minlength=(1 << p) * 64).reshape(1 << p, 64) > 0
    return (seen * np.arange(64)).max(axis=1).astype(np.int64)


def _sigma(x: float) -> float:
    if x == 1.0:
        return float("inf")
    y, z = 1.0, x
    while True:
        x = x * x
        z_prev = z
        z += x * y
        y += y
        if z == z_prev:
            return z


def _tau(x: float) -> float:
    if x in (0.0, 1.0):
        return 0.0
    y, z = 1.0, 1.0 - x
    while True:
        x = math.sqrt(x)
        z_prev = z
        y *= 0.5
        z -= (1.0 - x) ** 2 * y
        if z == z_prev:
            return z / 3.0


def hll_estimate(regs: np.ndarray) -> float:
    """Ertl's improved estimator (2017), rounded half up like deequ's."""
    m = len(regs)
    p = int(round(math.log2(m)))
    q = 64 - p
    counts = np.bincount(regs, minlength=q + 2).astype(np.float64)
    z = m * _tau(1.0 - counts[q + 1] / m)
    for k in range(q, 0, -1):
        z = 0.5 * (z + counts[k])
    z += m * _sigma(counts[0] / m)
    return float(math.floor(m * m / (2.0 * math.log(2.0)) / z + 0.5))


# -- summaries of one slice, per suite entry ----------------------------------


def _pair_round(v: np.ndarray) -> np.ndarray:
    """What an f64 IS on the device: an (hi, lo) float32 pair."""
    hi = v.astype(np.float32)
    lo = (v - hi.astype(np.float64)).astype(np.float32)
    return hi.astype(np.float64) + lo.astype(np.float64)


BLOCK_ROWS = 1 << 19  # temporaries stay small enough for the heap to reuse


def _moments(v: np.ndarray, f):
    n = len(v)
    if n == 0:
        return {"n": 0, "mean": 0.0, "m2": 0.0}
    mean = v.mean(dtype=f)
    d = v - mean
    return {"n": n, "mean": float(mean), "m2": float(np.dot(d, d))}


def _group_key(entry: dict):
    """Entries that read the same valid values of one column share them."""
    if entry["analyzer"] in ("Mean", "StandardDeviation", "Sum", "Minimum",
                             "Maximum", "ApproxQuantile"):
        return (entry["args"][0], entry.get("where") or None)
    return None


def _summarize_group(data: dict, entries: list, f) -> list:
    """Mean, StandardDeviation, Minimum, Maximum, quantiles of one column
    under one ``where``: the valid values are taken out once."""
    col = _column(data, entries[0]["args"][0])
    ok = _valid(col)
    if entries[0].get("where"):
        ok = ok & predicate(data, entries[0]["where"])
    v = col["values"][ok]
    vf = v if f is np.float64 else v.astype(f)
    moments = None
    out = []
    for entry in entries:
        kind = entry["analyzer"]
        if kind in ("Mean", "StandardDeviation", "Sum"):
            moments = moments or _moments(vf, f)
            out.append(moments)
        elif kind in ("Minimum", "Maximum"):
            # rounding is monotone: the extreme of the rounded values is the
            # rounded extreme
            pick = np.min if kind == "Minimum" else np.max
            extreme = pick(vf) if len(vf) else None
            if extreme is not None:
                extreme = float(extreme) if f is np.float32 else float(
                    _pair_round(np.array([extreme]))[0])
            out.append({"extreme": extreme})
        else:
            out.append({"values": [(vf, 1)]})
    return out


def _summarize_entry(data: dict, entry: dict, f) -> dict:
    kind, args = entry["analyzer"], entry["args"]
    rows = data["rows"]
    if kind == "Size":
        return {"n": rows}
    if kind == "Compliance":
        return {"hits": int(predicate(data, args[1]).sum()), "n": rows}
    if kind == "Correlation":
        x, y = _column(data, args[0]), _column(data, args[1])
        ok = _valid(x) & _valid(y)
        xv, yv = x["values"][ok].astype(f), y["values"][ok].astype(f)
        if not len(xv):
            return {"n": 0, "mx": 0.0, "my": 0.0, "cxy": 0.0, "cxx": 0.0,
                    "cyy": 0.0}
        mx, my = xv.mean(dtype=f), yv.mean(dtype=f)
        dx, dy = xv - mx, yv - my
        return {"n": len(xv), "mx": float(mx), "my": float(my),
                "cxy": float(np.dot(dx, dy)), "cxx": float(np.dot(dx, dx)),
                "cyy": float(np.dot(dy, dy))}
    if kind == "Uniqueness":
        if len(args[0]) != 1:
            raise NoReference("Uniqueness over several columns")
        col = _column(data, args[0][0])
        return {"keys": [(col["values"][_valid(col)], 1)], "n": rows}
    col = _column(data, args[0])
    ok = _valid(col)
    if kind in ("Histogram", "Entropy"):
        if col["kind"] != "string":
            raise NoReference(f"{kind} of a column that is not a string")
        return {"counts": np.bincount(col["codes"] + 1,
                                      minlength=len(col["dictionary"]) + 1),
                "dictionary": col["dictionary"], "f": f}
    if kind == "Completeness":
        return {"hits": int(ok.sum()), "n": rows}
    if kind == "ApproxCountDistinct":
        p = hll_precision()
        if col["kind"] == "string":
            idx, rank = data["_luts"][args[0]]
            codes = col["codes"][ok]
            idx, rank = idx[codes], rank[codes]
        else:
            values = col["values"] if ok.all() else col["values"][ok]
            if f is np.float32 and col["kind"] == "fractional":
                values = values.astype(f)
            idx, rank = idx_rank_numbers(values, p)
        return {"registers": registers(idx, rank, p)}
    raise NoReference(f"no reference for analyzer {kind!r}")


def _summarize_block(data: dict, entries: list, groups: dict, f) -> list:
    out = [None] * len(entries)
    for key, members in groups.items():
        if key[0] == "entry":
            out[members[0]] = _summarize_entry(data, entries[members[0]], f)
        else:
            for i, summary in zip(members, _summarize_group(
                    data, [entries[i] for i in members], f)):
                out[i] = summary
    return out


def summarize(data: dict, suite: dict, precision: str = "float64",
              threads: int = None) -> list:
    """One mergeable summary per analyzer entry of ``suite``, in order: the
    rows are summarized block by block and the blocks merged like slices."""
    f = {"float64": np.float64, "float32": np.float32}[precision]
    entries = suite["analyzers"]
    groups = {}
    for i, e in enumerate(entries):
        groups.setdefault(_group_key(e) or ("entry", i), []).append(i)
    p = hll_precision()
    luts = {
        e["args"][0]: idx_rank_strings(_column(data, e["args"][0])["dictionary"], p)
        for e in entries if e["analyzer"] == "ApproxCountDistinct"
        and _column(data, e["args"][0])["kind"] == "string"
    }
    blocks = []
    for start in range(0, max(data["rows"], 1), BLOCK_ROWS):
        block = slice_rows(data, start, min(start + BLOCK_ROWS, data["rows"]))
        block["_luts"] = luts
        blocks.append(block)
    with ThreadPoolExecutor(max_workers=threads or pool_size()) as pool:
        parts = list(pool.map(
            lambda b: _summarize_block(b, entries, groups, f), blocks))
    return [merge(e["analyzer"], [(part[i], 1) for part in parts])
            for i, e in enumerate(entries)]


# -- merging slices and reading the answers -----------------------------------


def merge(kind: str, parts: list) -> dict:
    """One summary of the multiset that takes each ``(summary, m)`` m times."""
    parts = [(s, m) for s, m in parts if m]
    if kind == "Size":
        return {"n": sum(m * s["n"] for s, m in parts)}
    if kind in ("Compliance", "Completeness"):
        return {"hits": sum(m * s["hits"] for s, m in parts),
                "n": sum(m * s["n"] for s, m in parts)}
    if kind == "Correlation":
        n = sum(m * s["n"] for s, m in parts)
        if not n:
            return dict(parts[0][0])
        mx = sum(m * s["n"] * s["mx"] for s, m in parts) / n
        my = sum(m * s["n"] * s["my"] for s, m in parts) / n
        return {
            "n": n, "mx": mx, "my": my,
            "cxy": sum(m * (s["cxy"] + s["n"] * (s["mx"] - mx) * (s["my"] - my))
                       for s, m in parts),
            "cxx": sum(m * (s["cxx"] + s["n"] * (s["mx"] - mx) ** 2)
                       for s, m in parts),
            "cyy": sum(m * (s["cyy"] + s["n"] * (s["my"] - my) ** 2)
                       for s, m in parts),
        }
    if kind == "ApproxCountDistinct":
        regs = parts[0][0]["registers"]
        for s, _ in parts[1:]:
            regs = np.maximum(regs, s["registers"])
        return {"registers": regs}
    if kind in ("Mean", "StandardDeviation", "Sum"):
        n = sum(m * s["n"] for s, m in parts)
        if not n:
            return {"n": 0, "mean": 0.0, "m2": 0.0}
        mean = sum(m * s["n"] * s["mean"] for s, m in parts) / n
        return {"n": n, "mean": mean,
                "m2": sum(m * (s["m2"] + s["n"] * (s["mean"] - mean) ** 2)
                          for s, m in parts)}
    if kind in ("Minimum", "Maximum"):
        seen = [s["extreme"] for s, _ in parts if s["extreme"] is not None]
        pick = min if kind == "Minimum" else max
        return {"extreme": pick(seen) if seen else None}
    if kind == "ApproxQuantile":
        return {"values": [(v, m * k) for s, m in parts
                           for v, k in s["values"] if len(v)]}
    if kind == "Uniqueness":
        return {"keys": [(v, m * k) for s, m in parts for v, k in s["keys"]],
                "n": sum(m * s["n"] for s, m in parts)}
    if kind in ("Histogram", "Entropy"):
        return {"counts": sum(m * s["counts"] for s, m in parts),
                "dictionary": parts[0][0]["dictionary"], "f": parts[0][0]["f"]}
    raise NoReference(f"no reference for analyzer {kind!r}")


def answer(entry: dict, s: dict):
    """The entry's answer from its merged summary: a float, or ``("rank",
    count_le, n, q, min, max)`` for a quantile, where ``count_le(v)``
    counts the values not above ``v``."""
    kind = entry["analyzer"]
    if kind == "Size":
        return float(s["n"])
    if kind in ("Compliance", "Completeness"):
        return s["hits"] / s["n"]
    if kind == "Correlation":
        return s["cxy"] / math.sqrt(s["cxx"] * s["cyy"])
    if kind == "ApproxCountDistinct":
        return hll_estimate(s["registers"])
    if kind == "Mean":
        return s["mean"]
    if kind == "Sum":
        return s["mean"] * s["n"]
    if kind == "StandardDeviation":
        return math.sqrt(s["m2"] / s["n"]) if s["n"] else 0.0
    if kind in ("Minimum", "Maximum"):
        return s["extreme"]
    if kind == "Uniqueness":
        values = np.concatenate([v for v, _ in s["keys"]])
        times = np.concatenate([np.full(len(v), k) for v, k in s["keys"]])
        _, inverse = np.unique(values, return_inverse=True)
        return float(np.count_nonzero(
            np.bincount(inverse, weights=times) == 1)) / s["n"]
    if kind == "Histogram":
        hist = {str(s["dictionary"][j]): int(c)
                for j, c in enumerate(s["counts"][1:]) if c}
        if s["counts"][0]:
            hist["NullValue"] = int(s["counts"][0])
        return hist
    if kind == "Entropy":
        counts = s["counts"][1:].astype(s["f"])
        p = counts[counts > 0] / counts.sum(dtype=s["f"])
        return float(-(p * np.log(p)).sum(dtype=s["f"]))
    if kind == "ApproxQuantile":
        return ("rank", _count_le(s["values"]),
                sum(k * len(v) for v, k in s["values"]),
                float(entry["args"][1]),
                float(min(v.min() for v, _ in s["values"])),
                float(max(v.max() for v, _ in s["values"])))
    raise NoReference(f"no reference for analyzer {kind!r}")


def combine(suite: dict, slices: list, multiplicity: list) -> list:
    """The suite's answers over the multiset of slices (``summarize``d)."""
    return [
        answer(e, merge(e["analyzer"],
                        [(s[i], m) for s, m in zip(slices, multiplicity)]))
        for i, e in enumerate(suite["analyzers"])
    ]


def _count_le(values: list):
    def count_le(v: float) -> int:
        return sum(k * int(np.count_nonzero(a <= v)) for a, k in values)
    return count_le


def quantile_value(answer) -> float:
    """The exact quantile of a ``("rank", ...)`` answer, by bisection on
    ``count_le``: what a constraint is judged on where the program reads
    its sketch."""
    _, count_le, n, q, lo, hi = answer
    while hi - lo > 1e-12 * max(1.0, abs(hi)):
        mid = 0.5 * (lo + hi)
        if count_le(mid) >= q * n:
            hi = mid
        else:
            lo = mid
    return hi


# -- verdicts -----------------------------------------------------------------


def _entry_key(analyzer: str, args, where=None):
    return (analyzer,
            tuple(tuple(a) if isinstance(a, list) else a for a in args),
            where or None)


_CHECK_METHODS = {
    "has_size": "Size", "has_completeness": "Completeness",
    "has_min": "Minimum", "has_max": "Maximum", "has_mean": "Mean",
    "has_sum": "Sum", "has_standard_deviation": "StandardDeviation",
    "has_approx_count_distinct": "ApproxCountDistinct",
    "has_approx_quantile": "ApproxQuantile", "has_correlation": "Correlation",
    "has_uniqueness": "Uniqueness", "has_entropy": "Entropy",
}


def constraint_analyzer(constraint: dict):
    """The key of the analyzer entry a Check method resolves to."""
    method, args = constraint["method"], list(constraint["args"])
    where = constraint.get("where")
    if method == "satisfies":
        return _entry_key("Compliance", [args[1], args[0]], where)
    if method not in _CHECK_METHODS:
        raise NoReference(f"no reference for Check.{method}")
    return _entry_key(_CHECK_METHODS[method], args, where)


def anomaly_value(suite: dict, answers: list):
    """The answer the suite's anomaly check watches (None: no such check)."""
    anomaly = suite.get("anomaly_check")
    if not anomaly:
        return None
    a = anomaly["analyzer"]
    want = _entry_key(a["analyzer"], a["args"], a.get("where"))
    return next(v for e, v in zip(suite["analyzers"], answers)
                if _entry_key(e["analyzer"], e["args"], e.get("where")) == want)


def _within(value: float, lo, hi) -> bool:
    return (lo is None or value >= lo) and (hi is None or value <= hi)


def _quantile_within(answer, lo, hi) -> bool:
    """lo <= the exact q-quantile <= hi, by two counts and no search: the
    quantile is not under ``lo`` where fewer than q*n values lie under it,
    and not over ``hi`` where q*n values or more lie at or under it."""
    _, count_le, n, q = answer[:4]
    need = q * n
    above_lo = lo is None or count_le(np.nextafter(lo, -np.inf)) < need
    below_hi = hi is None or count_le(hi) >= need
    return above_lo and below_hi


def anomalous(strategy: str, params: dict, history: list, value: float):
    """True/False, or None where the strategy cannot judge (no history:
    deequ's detector raises, and the constraint fails)."""
    if not history:
        return None
    if strategy == "RelativeRateOfChangeStrategy":
        change = value / history[-1]
    elif strategy == "AbsoluteChangeStrategy":
        change = value - history[-1]
    elif strategy == "SimpleThresholdStrategy":
        return not _within(value, params.get("lower_bound"),
                           params.get("upper_bound"))
    else:
        raise NoReference(f"no reference for strategy {strategy!r}")
    return not _within(change, params.get("max_rate_decrease"),
                       params.get("max_rate_increase"))


def verdict_rows(suite: dict, answers: list, rows: int, history=None) -> list:
    """``[(check_status, constraint_status), ...]`` as the suite's checks
    must come out on these answers: the Check's constraints in order, then
    the anomaly check's one constraint. ``history`` is the anomaly
    analyzer's metric over the appends before this one."""
    by_key = {
        _entry_key(e["analyzer"], e["args"], e.get("where")): a
        for e, a in zip(suite["analyzers"], answers)
    }
    passed = []
    for c in suite["check"]["constraints"]:
        answer = by_key[constraint_analyzer(c)]
        lo, hi = (rows if b == "rows" else b for b in (c["lo"], c["hi"]))
        if isinstance(answer, tuple):
            passed.append(_quantile_within(answer, lo, hi))
        else:
            passed.append(_within(answer, lo, hi))
    level = suite["check"]["level"].capitalize()
    status = "Success" if all(passed) else level
    out = [(status, "Success" if ok else "Failure") for ok in passed]
    anomaly = suite.get("anomaly_check")
    if anomaly:
        bad = anomalous(anomaly["strategy"], anomaly["params"],
                        list(history or []), anomaly_value(suite, answers))
        ok = bad is False
        out.append(("Success" if ok else anomaly["level"].capitalize(),
                    "Success" if ok else "Failure"))
    return out
