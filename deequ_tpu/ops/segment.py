"""Group-by counts via dictionary codes + device segment reduction.

The reference shuffles rows for ``GROUP BY`` (GroupingAnalyzers.scala:66-78).
The TPU-native design avoids a shuffle entirely: every column is already
dictionary-encoded, so a group key is a mixed-radix packing of per-column
codes and the frequency table is one ``segment_sum`` of ones — a single
device pass, with ``psum`` merging per-device count vectors across the mesh
(this IS the monoid merge of the frequency state).

For pathological key-space sizes (product of per-column cardinalities too
large to materialize as a dense count vector) we fall back to host
``np.unique`` over the packed keys, which is the sparse equivalent.
"""

from __future__ import annotations

from dataclasses import dataclass
from functools import partial
from typing import Dict, List, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import PartitionSpec as P

from deequ_tpu.data.table import Column, ColumnarTable, DType
from deequ_tpu.obs.recorder import seam
from deequ_tpu.ops import hll
from deequ_tpu.ops.device_policy import device_call, device_fetch
from deequ_tpu.ops.lut_cache import dictionary_lut_device
from deequ_tpu.ops.scan_engine import SCAN_STATS
from deequ_tpu.parallel.mesh import ROW_AXIS, current_mesh, shard_map

# dense device count vectors are used up to this key-space size
DENSE_KEYSPACE_LIMIT = 1 << 22

# below this row count the single-phase fetch (O(n) bytes) is cheaper than
# the extra device round trip the two-phase O(G) fetch path pays
SMALL_N_FETCH_LIMIT = 1 << 16

# below this row count grouping work runs entirely on HOST: a tiny input's
# device pass costs a dispatch+fetch round trip (~1 ms on the v5e host,
# chip_smoke.py, PR 21) for microseconds of host work — the
# latency-dominated regime of BASELINE config 1.
# Promoted to a sweepable knob in round 14: the kernel A/B probe sweeps
# DEEQU_TPU_HOST_GROUP_LIMIT to measure the crossover on its own
# hardware; this constant is the unset-knob default (tests monkeypatch
# it directly, which the helper below honors)
HOST_GROUP_LIMIT = 1 << 14


def host_group_limit() -> int:
    """The effective host-fallback row threshold: the registered
    DEEQU_TPU_HOST_GROUP_LIMIT knob when set, else the module default
    (``HOST_GROUP_LIMIT`` — still a plain module attribute so existing
    monkeypatch-based tests keep steering the un-swept default)."""
    from deequ_tpu.envcfg import env_value

    value = env_value("DEEQU_TPU_HOST_GROUP_LIMIT")
    return HOST_GROUP_LIMIT if value is None else value


def _pad_group_count(g: int) -> int:
    """Static gather size for a data-dependent group count: next power of
    two (>= 64) so jit programs are shared across nearby G and the fetched
    bytes stay within 2x of the exact O(G) bound."""
    size = 64
    while size < g:
        size <<= 1
    return size


def _record_fetch(*arrays) -> None:
    # one logical device->host materialization (the arrays come back in
    # one round trip at each call site)
    SCAN_STATS.record_fetch(sum(int(a.size) * a.itemsize for a in arrays))


@jax.jit
def _unique_inverse_kernel(v, m):
    """Module-level jitted body (a nested closure would retrace per call).

    Every sort operand is kept 32-bit where the data allow: XLA:TPU's
    compile time for a multi-operand sort multiplies with each 64-bit
    operand (compiled for a described v5e at 10M rows, PR 21: three keys +
    an i64 index payload over i64 values ~400 s; two keys + an i32 payload
    over i32 values ~80 s). The index payload is i32 (a device array holds
    < 2^31 rows), integer dtypes carry no NaN key, and the caller narrows
    i32-safe integers before the call."""
    n = v.shape[0]
    iota = jax.lax.iota(jnp.int32, n)
    # primary key: validity (valid rows first), then NaN-ness (all NaNs
    # group together), then the value; lax.sort is stable, so ties keep
    # row order exactly as the lexsort formulation did
    with jax.named_scope("deequ.sort.unique_inverse"):
        if jnp.issubdtype(v.dtype, jnp.floating):
            nm, snan, sv, perm = jax.lax.sort(
                (~m, v != v, v, iota), num_keys=3
            )
            neq = (sv[1:] != sv[:-1]) & ~(snan[1:] & snan[:-1])
        else:
            nm, sv, perm = jax.lax.sort((~m, v, iota), num_keys=2)
            neq = sv[1:] != sv[:-1]
    sm = ~nm
    neq = jnp.concatenate([jnp.array([True]), neq])
    starts = neq & sm  # a new distinct value, among valid rows only
    ids = jnp.cumsum(starts.astype(jnp.int32))
    codes_sorted = jnp.where(sm, ids, 0)
    inv = jnp.zeros_like(ids).at[perm].set(codes_sorted)
    return sv, starts, inv, ids[-1]  # ids[-1] == number of distinct values


@partial(jax.jit, static_argnames=("size",))
def _gather_at_starts_kernel(sv, starts, size):
    positions = jnp.nonzero(starts, size=size, fill_value=0)[0]
    return sv[positions]


def _device_unique_inverse(
    values: np.ndarray, mask: np.ndarray
) -> Tuple[np.ndarray, np.ndarray]:
    """Sort-based unique on DEVICE (the shuffle-sort of SURVEY §2.14.2):
    one lexsort puts valid values in order, adjacent-compare marks group
    starts, a cumsum assigns dense ids, and a scatter maps them back to row
    order. NaN values (possible when a caller builds columns with explicit
    masks) collapse into ONE distinct group, matching np.unique's
    equal_nan semantics. Returns (uniques, codes) with codes 0 = null,
    1..K = distinct.

    Fetch discipline: the row codes (O(n)) must come to host — they feed
    the host-side key packing — but the distinct values are gathered at
    group starts ON DEVICE so only O(U) values are fetched (plus one
    scalar round trip for U), not the full sorted column. Small inputs
    keep the single-phase fetch (the extra round trip would dominate)."""
    n = len(values)
    if n == 0:
        return np.empty(0, dtype=values.dtype), np.zeros(0, dtype=np.int64)
    if n <= host_group_limit() and values.dtype != np.float64:
        # latency-dominated regime: a tiny input's device sort costs one
        # dispatch+fetch round trip for microseconds of work — run the
        # identical unique/inverse on host. FRACTIONAL columns stay on
        # the device path at EVERY size: the TPU backend's f64 emulation
        # (an f64 device_put round trip comes back up to 1.4e-15 relative
        # off on the v5e, my chip run, PR 21) decodes values a few ulps
        # off the host's bit-exact ones, so a size-dependent choice would
        # make the same value produce two different group keys across
        # batch sizes (review catch) — consistency beats latency there.
        vals = values[mask]
        uniques = np.unique(vals)
        codes = np.zeros(n, dtype=np.int64)
        if len(uniques):
            codes[mask] = np.searchsorted(uniques, vals) + 1
        return uniques, codes
    SCAN_STATS.device_sort_passes += 1
    dtype = values.dtype
    if (
        dtype == np.int64
        and -(2 ** 31) < values.min()
        and values.max() < 2 ** 31
    ):
        # i32-safe integers sort as i32 keys (see the kernel's doc)
        values = values.astype(np.int32)
    sv_dev, starts_dev, inv_dev, nu_dev = _unique_inverse_kernel(values, mask)

    # the device keeps values and codes 32-bit (ranks <= n < 2^31); the
    # host hands back the column's dtype and int64 codes (the key packing
    # downstream is int64 arithmetic)
    def single_phase():
        sv, starts, inv = (
            np.asarray(x) for x in (sv_dev, starts_dev, inv_dev)
        )
        _record_fetch(sv, starts, inv)
        return sv[starts].astype(dtype), inv.astype(np.int64)

    if n <= SMALL_N_FETCH_LIMIT:
        return single_phase()
    num_uniques = int(nu_dev)
    SCAN_STATS.record_fetch(8)
    size = _pad_group_count(num_uniques)
    if size >= n:
        # nearly-all-distinct column: the padded gather fetches more
        # than the sorted values themselves
        return single_phase()
    uniques = np.asarray(_gather_at_starts_kernel(sv_dev, starts_dev, size))
    inv = np.asarray(inv_dev)
    _record_fetch(uniques, inv)
    return uniques[:num_uniques].astype(dtype), inv.astype(np.int64)


def _sorted_starts(mat, va):
    """Traced helper shared by every sparse-grouping kernel: sort the
    (k, n) code matrix with valid rows first (last row least significant,
    as lexsort ordered it), mark run starts among valid rows. Returns
    (sorted matrix, sorted validity, starts).

    Codes are dense ranks (0 = null, 1..K <= rows < 2^31), so they sort as
    i32, and the operands ride through the sort themselves — no i64 index
    permutation to gather by (each 64-bit sort operand multiplies
    XLA:TPU's compile time; see _unique_inverse_kernel)."""
    k = mat.shape[0]
    mat = mat.astype(jnp.int32)
    with jax.named_scope("deequ.sort.group_codes"):
        out = jax.lax.sort(
            (~va,) + tuple(mat[i] for i in range(k - 1, -1, -1)),
            num_keys=k + 1,
        )
    sva = ~out[0]
    smat = jnp.stack(out[:0:-1])
    neq = jnp.any(smat[:, 1:] != smat[:, :-1], axis=0)
    starts = jnp.concatenate([jnp.array([True]), neq]) & sva
    return smat, sva, starts


def _run_lengths(positions, n, m):
    """Traced helper: run lengths from ascending start positions (padded
    slots hold ``n``); valid rows occupy the sorted prefix [0, m). Padded
    slots produce count 0."""
    nxt = jnp.minimum(
        jnp.concatenate(
            [positions[1:], jnp.full((1,), n, dtype=positions.dtype)]
        ),
        m,
    )
    return jnp.maximum(nxt - jnp.minimum(positions, m), 0)


@jax.jit
def _matrix_rle_kernel(mat, va):
    smat, sva, starts = _sorted_starts(mat, va)
    # scalars ride back in ONE fetch: [num_groups, num_valid]
    scalars = jnp.stack(
        [jnp.sum(starts.astype(jnp.int64)), jnp.sum(sva.astype(jnp.int64))]
    )
    return smat, sva, starts, scalars


@partial(jax.jit, static_argnames=("size",))
def _rle_gather_kernel(smat, starts, m, size):
    """Gather group representatives + run lengths for the first ``size``
    group starts, entirely on device. Padded slots (beyond the true group
    count) gather index 0 and produce count 0 — the host filters them."""
    n = smat.shape[1]
    positions = jnp.nonzero(starts, size=size, fill_value=n)[0]
    counts = _run_lengths(positions, n, m)
    reps = smat[:, jnp.minimum(positions, n - 1)]
    return reps, counts


def _device_matrix_rle(
    code_matrix: np.ndarray, valid: np.ndarray
) -> Tuple[np.ndarray, np.ndarray]:
    """Run-length-encode the distinct rows of a (k, n) code matrix via one
    device lexsort + adjacent-compare (the sparse/high-cardinality group-by;
    replaces a host np.unique(axis=0) which is a full host sort). Returns
    (groups (k, G), counts (G,)) for valid rows.

    Device-bounded fetch: the sorted (k, n) matrix never leaves the device.
    One scalar round trip reads the group count G, then a second kernel
    gathers the (k, G) representatives + (G,) run lengths on device, so
    fetched bytes are O(k*G) — not O(k*n) — matching the reference's
    shuffle group-by output size (GroupingAnalyzers.scala:66-78). Small
    inputs keep the single-phase fetch."""
    k, n = code_matrix.shape
    if n == 0:
        return code_matrix[:, :0], np.zeros(0, dtype=np.int64)
    if n <= host_group_limit():
        # latency-dominated regime (see _device_unique_inverse): the same
        # lexsort + adjacent-compare on host, identical results, zero
        # device round trips
        perm = np.lexsort(tuple(code_matrix) + (~valid,))
        smat = code_matrix[:, perm]
        sva = valid[perm]
        neq = np.any(smat[:, 1:] != smat[:, :-1], axis=0)
        starts = np.concatenate([[True], neq]) & sva
        m = int(sva.sum())
        positions = np.nonzero(starts)[0]
        groups = smat[:, positions]
        counts = np.diff(np.append(positions, m)).astype(np.int64)
        return groups, counts
    SCAN_STATS.device_sort_passes += 1

    smat_dev, sva_dev, starts_dev, scalars_dev = _matrix_rle_kernel(
        code_matrix, valid
    )

    def single_phase(m=None):
        smat, starts = np.asarray(smat_dev), np.asarray(starts_dev)
        if m is None:
            sva = np.asarray(sva_dev)
            _record_fetch(smat, sva, starts)
            m = int(sva.sum())  # valid rows occupy the sorted prefix
        else:
            _record_fetch(smat, starts)
        positions = np.nonzero(starts)[0]
        groups = smat[:, positions].astype(code_matrix.dtype)
        counts = np.diff(np.append(positions, m)).astype(np.int64)
        return groups, counts

    if n <= SMALL_N_FETCH_LIMIT:
        return single_phase()

    num_groups, m = (int(x) for x in np.asarray(scalars_dev))
    SCAN_STATS.record_fetch(16)
    size = _pad_group_count(num_groups)
    if size >= n:
        # nearly-all-distinct data: the pow2-padded gather would fetch
        # MORE than the plain sorted matrix (up to 2n slots); m is
        # already known from the scalar fetch
        return single_phase(m)
    reps, counts = (
        np.asarray(x)
        for x in _rle_gather_kernel(smat_dev, starts_dev, m, size)
    )
    _record_fetch(reps, counts)
    keep = counts > 0
    return (
        reps[:, keep].astype(code_matrix.dtype),
        counts[keep].astype(np.int64),
    )


def column_key_codes(col: Column) -> Tuple[np.ndarray, List]:
    """Per-row integer codes (0 = null, 1..K = distinct values) + the
    decoded distinct values in code order. Numeric columns build codes via
    a device sort (see _device_unique_inverse); strings are already
    dictionary-encoded at ingest."""
    if col.dtype == DType.STRING:
        codes = col.codes.astype(np.int64) + 1
        return codes, list(col.dictionary)
    if col.dtype == DType.BOOLEAN:
        # 2-value domain: no sort needed at all
        uniques = np.unique(col.values[col.mask])
        # deequ-lint: ignore[host-fetch] -- uniques is host np.unique output over host column values
        lut = {v: i + 1 for i, v in enumerate(uniques.tolist())}
        codes = np.where(
            col.mask, np.where(col.values, lut.get(True, 0), lut.get(False, 0)), 0
        ).astype(np.int64)
        return codes, [bool(v) for v in uniques]
    uniques, codes = _device_unique_inverse(col.values, col.mask)
    if col.dtype == DType.INTEGRAL:
        values = [int(v) for v in uniques]
    else:
        values = [float(v) for v in uniques]
    return codes, values


from functools import lru_cache


def _count_slots(slot, num_segments: int, variant: str):
    """Traced: counts over ``num_segments + 1`` slots under the routed
    kernel tier (ops/histogram_device.py): the scatter variant is a
    ``segment_sum`` of ones, the one-hot/pallas variants replace the
    scatter-add with the blocked matmul / Mosaic grid kernel, exact by
    the tier's integer-count contract. Slots and counts are int32
    whenever the rows of the call fit (always, on a device): a 64-bit
    scatter-add is emulated on the v5e, 16-22x the int32 one on the chip
    (PERF.md section 6, PR 32). Callers widen before they add calls up or
    ``psum``."""
    dtype = jnp.int32 if slot.shape[0] < (1 << 31) else jnp.int64
    slot = slot.astype(dtype)
    if variant == "scatter":
        with jax.named_scope("deequ.bincount.scatter"):
            return jax.ops.segment_sum(
                jnp.ones_like(slot), slot, num_segments=num_segments + 1,
            )
    from deequ_tpu.ops.histogram_device import bincount_variant

    return bincount_variant(
        variant, slot, num_segments + 1, jnp, dtype=dtype
    )


def _shard_map_kwargs(variant: str) -> dict:
    """``pallas_call`` has no shard_map replication rule in this jax
    (NotImplementedError at trace time), so the pallas variant disables
    the replication check — sound here because every grouping kernel
    psums its counts to an explicitly replicated output anyway."""
    return {"check_vma": False} if variant == "pallas" else {}


@lru_cache(maxsize=64)
def _bincount_fn(num_segments: int, mesh, variant: str = "scatter"):
    """Jitted (and mesh-wrapped) bincount kernel, cached so repeated runs
    with the same cardinality/mesh/kernel-variant reuse the traced
    program instead of retracing per call (the variant is part of the
    cache key — a one-hot program must never serve a scatter dispatch
    or vice versa)."""

    def count(k):
        slot = jnp.where(k < 0, num_segments, k)
        counts = _count_slots(slot, num_segments, variant).astype(jnp.int64)
        if mesh is not None:
            counts = jax.lax.psum(counts, ROW_AXIS)
        return counts

    if mesh is not None:
        return jax.jit(
            shard_map(
                count, mesh=mesh, in_specs=P(ROW_AXIS), out_specs=P(),
                **_shard_map_kwargs(variant),
            )
        )
    return jax.jit(count)


@lru_cache(maxsize=64)
def _topk_fn(
    num_segments: int, kk: int, mesh, merge_null_into: int = -1,
    variant: str = "scatter",
):
    """Jitted dense-count + device top-k kernel (cached like _bincount_fn,
    kernel variant in the cache key). ``merge_null_into`` as in
    _topk_from_counts_fn."""

    def kernel(c):
        slot = jnp.where(c < 0, num_segments, c)
        counts = _count_slots(slot, num_segments, variant).astype(jnp.int64)
        if mesh is not None:
            counts = jax.lax.psum(counts, ROW_AXIS)
        counts = counts[:num_segments]
        if merge_null_into >= 0:
            counts = counts.at[merge_null_into].add(counts[0])
            counts = counts.at[0].set(0)
        num_groups = (counts > 0).sum()
        with jax.named_scope("deequ.topk"):
            top_counts, top_idx = jax.lax.top_k(counts, kk)
        return num_groups, top_counts, top_idx

    if mesh is not None:
        return jax.jit(
            shard_map(
                kernel, mesh=mesh, in_specs=P(ROW_AXIS), out_specs=P(),
                **_shard_map_kwargs(variant),
            )
        )
    return jax.jit(kernel)


# -- device-resident grouping (persisted tables) ----------------------------
#
# When the table is persist()ed, a string column's codes already live in
# HBM inside the packed chunks; the grouping kernels then read them there
# instead of re-shipping O(rows) host bytes per analysis run. Only the tiny
# counts-derived results (top-k bins, scalar stats) ever leave the device.


def _resident_counts(args, row: int, num_segments: int, include_null: bool,
                     variant: str, dtype):
    """Traced: counts of row ``row`` of the resident code planes over
    ``num_segments`` slots (slot 0 = null), chunk after chunk; ``args`` is
    ``codes_0, rv_0, codes_1, rv_1, ...``. Rows that do not count land in
    the trailing slot ``num_segments`` and are dropped."""
    counts = jnp.zeros(num_segments + 1, dtype=dtype)
    for codes, rv in zip(args[::2], args[1::2]):
        c = codes[row]
        on = rv if include_null else rv & (c >= 0)
        slot = jnp.where(on, c + 1, num_segments)
        counts = counts + _count_slots(slot, num_segments, variant).astype(
            dtype
        )
    return counts[:num_segments]


def _resident_jit(kernel, n_chunks: int, mesh, variants, n_luts: int = 0):
    """``kernel(codes_0, rv_0, codes_1, rv_1, ..., lut_0, ...)`` jitted,
    under ``mesh`` over the row-sharded resident planes (and ``n_luts``
    replicated dictionary LUTs behind them) with a replicated result."""
    if mesh is None:
        return jax.jit(kernel)
    in_specs = (P(None, ROW_AXIS), P(ROW_AXIS)) * n_chunks + (P(),) * n_luts
    return jax.jit(
        shard_map(
            kernel, mesh=mesh, in_specs=in_specs, out_specs=P(),
            **_shard_map_kwargs(
                "pallas" if "pallas" in variants else "scatter"
            ),
        )
    )


@lru_cache(maxsize=64)
def _resident_bincount_fn(
    num_segments: int, n_chunks: int, row: int, include_null: bool, mesh,
    variant: str = "scatter",
):
    def kernel(*args):  # codes_0, rv_0, codes_1, rv_1, ...
        counts = _resident_counts(
            args, row, num_segments, include_null, variant, jnp.int64
        )
        if mesh is not None:
            counts = jax.lax.psum(counts, ROW_AXIS)
        return counts

    return _resident_jit(kernel, n_chunks, mesh, (variant,))


def _resident_strings(table, columns, mesh):
    """The table's device cache where every one of ``columns`` is a string
    column of its resident code plane under ``mesh``, else None."""
    cache = getattr(table, "_device_cache", None)
    if cache is None or not cache.device_chunks:
        return None
    if not cache.matches(mesh, list(columns)):
        return None
    if any(c not in cache.packer.string_names for c in columns):
        return None
    return cache


def _resident_args(cache) -> list:
    args = []
    for chunk in cache.device_chunks:
        args.append(chunk[5])  # codes buffer
        args.append(chunk[6])  # row_valid
    return args


def _resolve_resident_variant(table, cache, column: str) -> str:
    """The bincount variant of one resident string column, recorded in the
    kernel census: one pass per resident chunk, all inside one dispatch."""
    from deequ_tpu.ops.device_policy import hist_is_wide, resolve_hist_variant

    widths = (len(cache.packer.col_dict[column]) + 2,)
    variant = resolve_hist_variant(widths, rows=table.num_rows)
    SCAN_STATS.record_hist_dispatch(
        variant, len(cache.device_chunks), wide=hist_is_wide(widths)
    )
    return variant


def _resident_string_bincount(table, column: str, include_null: bool, mesh):
    """Counts per code slot (slot 0 = null when include_null) straight from
    the persisted chunks, or None when the table/column is not resident.
    Returns a DEVICE array of length cardinality+1."""
    cache = _resident_strings(table, [column], mesh)
    if cache is None:
        return None
    packer = cache.packer
    fn = _resident_bincount_fn(
        len(packer.col_dict[column]) + 1, len(cache.device_chunks),
        packer.string_names.index(column), include_null, mesh,
        _resolve_resident_variant(table, cache, column),
    )
    return device_call(
        lambda: fn(*_resident_args(cache)), "execute",
        what="resident bincount",
    )


@lru_cache(maxsize=64)
def _resident_topk_fn(specs: tuple, n_chunks: int, mesh, wide_rows: bool,
                      registers: tuple = ()):
    """The top-k summaries of several resident string columns as ONE
    program with ONE output vector: per ``(row, num_segments, kk,
    merge_null_into, variant)`` of ``specs`` its ``[num_groups, kk top
    counts, kk top slots]``, concatenated in order. When
    ``merge_null_into`` >= 0, slot 0 (the null group) folds into that slot
    BEFORE ranking: the Histogram metric stringifies groups (null ->
    "NullValue"), so a literal "NullValue" string and actual nulls are ONE
    bin — merging after truncation would undercount whenever one of the
    pair straddles the k boundary. Counts and ``top_k`` are int32 unless
    the table holds 2^31 rows or more (``wide_rows``).

    ``registers`` holds per spec the HLL precision asked of that column (0:
    none; ``()``: nobody asks). An asking column's packed (idx, rank) LUT
    follows the chunks as a run-time argument, in spec order, so the
    program serves every dictionary of one size; its ``2^p`` registers
    follow its top-k in the output vector. They are the fold of the
    entries PRESENT in the counts (``hll.registers_from_present``), read
    BEFORE the null merge: a null must not mark a literal "NullValue"
    entry present. One column after another: the one-hot fold is not safe
    to batch."""
    dtype = jnp.int64 if wide_rows else jnp.int32
    registers = registers or (0,) * len(specs)

    def kernel(*args):  # codes_0, rv_0, codes_1, rv_1, ..., lut_0, ...
        planes, luts = args[:2 * n_chunks], iter(args[2 * n_chunks:])
        parts = []
        for (row, num_segments, kk, merge_null_into, variant), p in zip(
                specs, registers):
            counts = _resident_counts(
                planes, row, num_segments, True, variant, dtype
            )
            if mesh is not None:
                counts = jax.lax.psum(counts, ROW_AXIS)
            if p:
                # slot 0 is the null group and never counts; the LUT is
                # padded to a power of two past its num_segments - 1 entries
                regs = hll.registers_from_present(
                    next(luts)[:num_segments - 1], counts[1:] > 0, p, jnp
                )
            if merge_null_into >= 0:
                counts = counts.at[merge_null_into].add(counts[0])
                counts = counts.at[0].set(0)
            num_groups = (counts > 0).sum(dtype=dtype)
            with jax.named_scope("deequ.topk"):
                top_counts, top_idx = jax.lax.top_k(counts, kk)
            parts += [num_groups[None], top_counts, top_idx.astype(dtype)]
            if p:
                parts.append(regs.astype(dtype))
        return jnp.concatenate(parts)

    return _resident_jit(
        kernel, n_chunks, mesh, tuple(spec[4] for spec in specs),
        n_luts=sum(map(bool, registers)),
    )


def _null_value_slot(dictionary) -> int:
    """The counts slot of a literal "NullValue" entry (-1: none): the
    Histogram metric stringifies nulls to that label, so the two are ONE
    bin. O(dictionary) on the host, memoized per dictionary."""
    from deequ_tpu.ops.lut_cache import dictionary_lut

    hits = dictionary_lut(
        dictionary, "null_value_slot",
        lambda d: np.nonzero(d == "NullValue")[0][:1] + 1,
    )
    return int(hits[0]) if len(hits) else -1


def resident_top_k(
    table: ColumnarTable, requests: Sequence[Tuple[str, int]], mesh=None,
    registers_of: Sequence[str] = (),
) -> Optional[Tuple[List["TopKCounts"], Dict[str, np.ndarray]]]:
    """``group_top_k`` of several string columns of a persist()ed table at
    once: every column's counts, null merge, group count and top-k in ONE
    dispatch over the HBM-resident code planes and ONE fetch of ``1 + 2k``
    integers a column. ``requests`` is ``[(column, k), ...]``; None when
    the table, or one of the columns, is not resident under ``mesh`` (the
    caller then goes column by column).

    For each column of ``registers_of`` among the requests, the same
    dispatch folds the HLL registers of the column out of the dictionary
    entries PRESENT in its counts (where-free ApproxCountDistinct of a
    string column: the registers are a function of the set of values, and
    the counts already say which entries some row holds), and the same
    fetch brings them: the second element of the result, ``{column:
    registers}``. The LUT is the memoized device array the fused scan
    gathers from."""
    if mesh is None:
        mesh = current_mesh()
    columns = [column for column, _ in requests]
    cache = _resident_strings(table, columns, mesh)
    if cache is None:
        return None
    with seam("grouping", columns=",".join(columns)):
        packer = cache.packer
        p = hll.precision_from_relative_sd()
        asking = set(registers_of)
        specs, precisions = [], []
        for column, k in requests:
            dictionary = table[column].dictionary
            specs.append((
                packer.string_names.index(column), len(dictionary) + 1,
                min(k, len(dictionary) + 1), _null_value_slot(dictionary),
                _resolve_resident_variant(table, cache, column),
            ))
            precisions.append(p if column in asking else 0)
            asking.discard(column)  # once, whatever the requests repeat
        with seam("plan", what="hll luts"):
            luts = [
                dictionary_lut_device(
                    table[column].dictionary, f"hll_ir_p{p}",
                    lambda d: hll.string_idx_rank_lut(d, p), mesh,
                )
                for (column, _), p_col in zip(requests, precisions) if p_col
            ]
        fn = _resident_topk_fn(
            tuple(specs), len(cache.device_chunks), mesh,
            table.num_rows >= (1 << 31), tuple(precisions) if luts else (),
        )
        out = device_call(
            lambda: fn(*_resident_args(cache), *luts), "execute",
            what="resident top-k",
        )
        # the own pass's one fetch: its dispatch is the thread's newest
        flat = device_fetch(out, "resident top-k", newest=True)
        _record_fetch(flat)
        SCAN_STATS.grouping_passes += len(requests)
        SCAN_STATS.hll_presence_folds += len(luts)
        SCAN_STATS.rows_scanned += table.num_rows * len(requests)
        results, registers, at = [], {}, 0
        for (column, _), (_, _, kk, _, _), p_col in zip(
                requests, specs, precisions):
            num_groups = int(flat[at])
            top_counts = flat[at + 1:at + 1 + kk]
            top_idx = flat[at + 1 + kk:at + 1 + 2 * kk]
            at += 1 + 2 * kk
            if p_col:
                registers[column] = flat[at:at + (1 << p_col)]
                at += 1 << p_col
            dictionary = table[column].dictionary
            keep = top_counts > 0
            results.append(TopKCounts(table.num_rows, num_groups, tuple(
                (None if idx == 0 else dictionary[idx - 1], cnt)
                for idx, cnt in zip(top_idx[keep].tolist(),
                                    top_counts[keep].tolist())
            )))
        return results, registers


def resident_string_columns(table) -> Tuple[str, ...]:
    """The string columns in the code planes of ``table`` as it is resident
    under the current mesh: what ``resident_top_k`` can serve. Empty when
    the table is not persist()ed, or under another mesh."""
    cache = _resident_strings(table, (), current_mesh())
    return () if cache is None else tuple(cache.packer.string_names)


@jax.jit
def _rle_stats_kernel(mat, va):
    """Sparse group-by count-distribution aggregates entirely on device:
    lexsort + run starts as in _matrix_rle_kernel, then run lengths via a
    positions-diff over a full-length (static-shape) sorted position
    vector — no data-dependent shapes, so num_groups, singletons, and the
    entropy numerator sum(c*log c) come back as FOUR SCALARS regardless of
    how many distinct groups the data has."""
    _smat, sva, starts = _sorted_starts(mat, va)
    n = mat.shape[1]
    m = jnp.sum(sva)  # valid rows occupy the sorted prefix
    # positions are row indices (< 2^31): an i32 sort, not an emulated i64 one
    with jax.named_scope("deequ.sort.run_positions"):
        pos = jnp.sort(jnp.where(starts, jnp.arange(n, dtype=jnp.int32), n))
    counts = _run_lengths(pos, n, m.astype(jnp.int32))
    num_groups = jnp.sum(starts)
    singletons = jnp.sum(counts == 1)
    c = counts.astype(jnp.float64)
    clogc = jnp.sum(jnp.where(counts > 0, c, 0.0) * jnp.log(jnp.where(counts > 0, c, 1.0)))
    return m, num_groups, singletons, clogc


@jax.jit
def _stats_from_counts(counts):
    """``[total, groups, singletons, entropy]`` as ONE f64 vector (the
    three counts are exact there: under 2^53), so one fetch brings all."""
    total = counts.sum()
    groups = (counts > 0).sum()
    singles = (counts == 1).sum()
    p = counts / jnp.maximum(total, 1)
    ent = -jnp.where(counts > 0, p * jnp.log(jnp.where(p > 0, p, 1.0)), 0.0).sum()
    return jnp.stack([
        total.astype(jnp.float64), groups.astype(jnp.float64),
        singles.astype(jnp.float64), ent,
    ])


def _device_bincount(keys: np.ndarray, num_segments: int, mesh) -> np.ndarray:
    """Count key occurrences on device; psum across the mesh if present.

    ``keys`` may contain -1 for rows to ignore (filtered / padding); those
    land in an extra trailing slot that is dropped.
    """
    n = len(keys)
    if n <= host_group_limit():
        # latency-dominated regime: host bincount (totals are identical —
        # the mesh merge only re-sums the same rows)
        slots = np.where(keys >= 0, keys, num_segments)
        counts = np.bincount(slots, minlength=num_segments + 1)
        return counts[:num_segments].astype(np.int64)
    n_dev = int(np.prod(mesh.devices.shape)) if mesh is not None else 1
    padded = max(n_dev, ((n + n_dev - 1) // n_dev) * n_dev)
    if padded != n:
        keys = np.concatenate([keys, np.full(padded - n, -1, dtype=np.int64)])

    # histogram kernel tier (round 14): scatter vs one-hot matmul vs
    # pallas, resolved per dispatch from keyspace width / rows / platform
    from deequ_tpu.ops.device_policy import hist_is_wide, resolve_hist_variant

    variant = resolve_hist_variant((num_segments + 1,), rows=n)
    SCAN_STATS.record_hist_dispatch(
        variant, wide=hist_is_wide((num_segments + 1,))
    )
    counts = np.asarray(_bincount_fn(num_segments, mesh, variant)(keys))
    _record_fetch(counts)
    return counts[:num_segments]


def _typed_values(col_dtype: DType, values: List) -> np.ndarray:
    """Distinct values (code order) -> a typed numpy array the columnar
    frequency state can factorize with vectorized np.unique."""
    if col_dtype == DType.STRING:
        # deequ-lint: ignore[host-fetch] -- `values` is a host python list (dictionary order), never a device array
        return np.asarray(values, dtype=np.str_) if values else np.empty(
            0, dtype=np.str_
        )
    if col_dtype == DType.BOOLEAN:
        # deequ-lint: ignore[host-fetch] -- `values` is a host python list (dictionary order), never a device array
        return np.asarray(values, dtype=np.bool_)
    if col_dtype == DType.INTEGRAL:
        # deequ-lint: ignore[host-fetch] -- `values` is a host python list (dictionary order), never a device array
        return np.asarray(values, dtype=np.int64)
    # deequ-lint: ignore[host-fetch] -- `values` is a host python list (dictionary order), never a device array
    return np.asarray(values, dtype=np.float64)


@dataclass
class _GroupPrep:
    """One grouping set's PREPARED key material — the planning/packing
    half of ``group_counts_state``/``group_count_stats``, split out
    (round 19) so the fused multi-pass dispatch and the per-set paths
    share one derivation and can never drift. ``keys`` (dense only) is
    the mixed-radix packed int64 vector with -1 marking excluded rows —
    exactly what ``_device_bincount`` consumes, offsettable for
    fusion."""

    columns: Tuple[str, ...]
    code_arrays: List[np.ndarray]
    value_arrays: Optional[List[np.ndarray]]
    radices: List[int]
    any_non_null: Optional[np.ndarray]
    num_rows: int
    keyspace: int
    dense: bool
    keys: Optional[np.ndarray]


def _prepare_grouping(
    table: ColumnarTable,
    columns: Sequence[str],
    require_any_non_null: bool = True,
    with_values: bool = True,
) -> _GroupPrep:
    """Derive one grouping set's codes/radices/packed keys.
    ``with_values=False`` skips the typed distinct-value arrays (the
    count-stats path never decodes group values)."""
    with seam("grouping.host", columns=",".join(columns)):
        return _pack_group_keys(
            table, columns, require_any_non_null, with_values
        )


def _pack_group_keys(
    table, columns, require_any_non_null, with_values
) -> _GroupPrep:
    code_arrays = []
    value_arrays: Optional[List[np.ndarray]] = [] if with_values else None
    radices = []
    for name in columns:
        col = table[name]
        codes, values = column_key_codes(col)
        if with_values:
            # memoize the typed distinct-value array per column: for
            # string columns this converts the whole dictionary
            # (O(cardinality)); repeated runs (incremental monitoring)
            # reuse it
            typed = getattr(col, "_typed_distinct", None)
            if typed is None or len(typed) != len(values):
                typed = _typed_values(col.dtype, values)
                col._typed_distinct = typed
            value_arrays.append(typed)
        code_arrays.append(codes)
        radices.append(len(values) + 1)

    if require_any_non_null and len(columns) > 0:
        any_non_null = np.zeros(table.num_rows, dtype=bool)
        for codes in code_arrays:
            any_non_null |= codes > 0
        num_rows = int(any_non_null.sum())
    else:
        any_non_null = None
        num_rows = table.num_rows

    # Python-int product: mixed-radix packing into int64 silently wraps when
    # the key space exceeds 2^63, so overflow must be checked BEFORE packing
    keyspace = 1
    for radix in radices:
        keyspace *= radix

    dense = keyspace <= DENSE_KEYSPACE_LIMIT
    keys = None
    if dense:
        keys = np.zeros(table.num_rows, dtype=np.int64)
        for codes, radix in zip(code_arrays, radices):
            keys = keys * radix + codes
        if any_non_null is not None:
            keys = np.where(any_non_null, keys, -1)
    return _GroupPrep(
        tuple(columns), code_arrays, value_arrays, radices, any_non_null,
        num_rows, keyspace, dense, keys,
    )


def _dense_digits(
    prep: _GroupPrep, counts: np.ndarray
) -> Tuple[List[np.ndarray], np.ndarray]:
    """Dense counts vector -> (per-column digit codes of the present
    groups, their counts) via vectorized mixed-radix decode."""
    present = np.nonzero(counts)[0]
    group_counts_vec = counts[present].astype(np.int64)
    digit_cols = []
    rest = present
    for radix in reversed(prep.radices):
        digit_cols.append(rest % radix)
        rest = rest // radix
    digit_cols.reverse()
    return digit_cols, group_counts_vec


def _freq_state_from_digits(
    columns: Sequence[str],
    digit_cols: List[np.ndarray],
    group_counts_vec: np.ndarray,
    value_arrays: List[np.ndarray],
    num_rows: int,
    canonicalize: bool,
):
    """Digit codes + counts -> columnar ``FrequenciesAndNumRows`` (the
    finalize half shared by the dense, sparse, and fused paths)."""
    from deequ_tpu.analyzers.grouping import FrequenciesAndNumRows

    key_values = []
    key_nulls = []
    for digits, values in zip(digit_cols, value_arrays):
        nulls = digits == 0
        if len(values):
            key_values.append(values[np.maximum(digits - 1, 0)])
        else:
            key_values.append(np.zeros(len(digits), dtype=values.dtype))
        key_nulls.append(nulls)
    if canonicalize:
        # lazy import: spill depends on analyzers.grouping which imports
        # this module; at call time everything is loaded
        from deequ_tpu.spill.order import is_strictly_ascending, merge_add_sorted

        if not is_strictly_ascending(key_values, key_nulls):
            kv, kn, group_counts_vec = merge_add_sorted(
                [(tuple(key_values), tuple(key_nulls), group_counts_vec)]
            )
            key_values, key_nulls = list(kv), list(kn)
    return FrequenciesAndNumRows(
        tuple(columns), tuple(key_values), tuple(key_nulls),
        group_counts_vec, num_rows,
    )


def group_counts_state(
    table: ColumnarTable,
    columns: Sequence[str],
    mesh=None,
    require_any_non_null: bool = True,
    canonicalize: bool = False,
):
    """Compute the frequency table for a set of grouping columns as a
    COLUMNAR ``FrequenciesAndNumRows`` (reference
    GroupingAnalyzers.scala:53-79): counts come off the device and group
    keys decode via vectorized gathers into the per-column distinct-value
    arrays — no per-group python loop, so 100M-distinct groupings stay in
    array ops end to end.

    ``canonicalize=True`` emits the state as a SORTED delta in canonical
    key order (first column most significant, nulls first, values
    ascending, NaN last — the order ``FrequenciesAndNumRows.sum``
    produces): the out-of-core spill engine (deequ_tpu/spill) folds these
    per-chunk sorted deltas straight into budget-bounded runs without
    re-sorting. Numeric columns come out of the device paths already in
    that order (codes are value-ascending ranks); string columns carry
    ingest-dictionary codes in arbitrary dictionary order, so the emitted
    delta is VERIFIED (O(G) adjacent-row compare) and host sort+dedup'd
    only when the order actually fails.
    """
    if mesh is None:
        mesh = current_mesh()
    SCAN_STATS.grouping_passes += 1
    SCAN_STATS.rows_scanned += table.num_rows

    prep = _prepare_grouping(
        table, columns, require_any_non_null, with_values=True
    )

    if prep.dense:
        counts = _device_bincount(prep.keys, prep.keyspace, mesh)
        digit_cols, group_counts_vec = _dense_digits(prep, counts)
    else:
        # sparse path for huge key spaces: device lexsort + run-length
        # encoding over the code matrix — no packing (no overflow regardless
        # of cardinality product), no host sort
        matrix = np.stack(prep.code_arrays, axis=0)
        valid = (
            prep.any_non_null
            if prep.any_non_null is not None
            else np.ones(table.num_rows, dtype=bool)
        )
        groups_mat, group_counts_vec = _device_matrix_rle(matrix, valid)
        digit_cols = [groups_mat[i] for i in range(groups_mat.shape[0])]
        if canonicalize and len(digit_cols) > 1:
            # the RLE kernels lexsort last-column-major; re-order the O(G)
            # digit codes first-column-major (digits ARE canonical ranks:
            # 0 = null, then value-ascending np.unique codes)
            order = np.lexsort(tuple(reversed(digit_cols)))
            digit_cols = [d[order] for d in digit_cols]
            group_counts_vec = group_counts_vec[order]

    return _freq_state_from_digits(
        columns, digit_cols, group_counts_vec, prep.value_arrays,
        prep.num_rows, canonicalize,
    )


def group_counts(
    table: ColumnarTable,
    columns: Sequence[str],
    mesh=None,
    require_any_non_null: bool = True,
) -> Tuple[Dict[tuple, int], int]:
    """Dict-shaped compatibility wrapper around ``group_counts_state``:
    maps each tuple of group values (None = null) to its count."""
    state = group_counts_state(table, columns, mesh, require_any_non_null)
    return state.as_dict(), state.num_rows


@dataclass(frozen=True)
class TopKCounts:
    """Device-computed histogram summary: total rows, distinct-group count,
    and only the top-k (group value, count) pairs decoded to host — the
    analogue of the reference computing top-maxDetailBins in the engine
    (Histogram.scala:97-103) instead of collecting every group."""

    num_rows: int
    num_groups: int
    top: Tuple[Tuple[object, int], ...]  # (value-or-None, count), count desc


def group_top_k(
    table: ColumnarTable,
    column: str,
    k: int,
    mesh=None,
) -> TopKCounts:
    """Top-k most frequent values of ONE column, counts computed and ranked
    on device; only k codes+counts are fetched and only those k distinct
    values are decoded. Nulls form their own group (value None). Ties at
    the k-boundary break by first-seen code order (the reference's top() is
    similarly tie-unstable)."""
    if mesh is None:
        mesh = current_mesh()
    col = table[column]
    if col.dtype == DType.STRING:
        # persisted table: counts + top-k entirely from HBM-resident codes
        resident = resident_top_k(table, [(column, k)], mesh)
        if resident is not None:
            return resident[0][0]
    SCAN_STATS.grouping_passes += 1
    SCAN_STATS.rows_scanned += table.num_rows

    nv_code = -1
    if col.dtype == DType.STRING:
        # the Histogram metric stringifies nulls to "NullValue": if that
        # literal also appears in the data, the two slots are ONE bin and
        # must merge on device BEFORE top-k truncation
        nv_code = _null_value_slot(col.dictionary)
        codes = col.codes.astype(np.int64) + 1
        decode = lambda idx: col.dictionary[idx - 1]  # noqa: E731
        card = len(col.dictionary)
    elif col.dtype == DType.BOOLEAN:
        codes, values = column_key_codes(col)
        decode = lambda idx: values[idx - 1]  # noqa: E731
        card = len(values)
    else:
        uniques, codes = _device_unique_inverse(col.values, col.mask)
        cast = int if col.dtype == DType.INTEGRAL else float
        decode = lambda idx: cast(uniques[idx - 1])  # noqa: E731
        card = len(uniques)

    n_dev = int(np.prod(mesh.devices.shape)) if mesh is not None else 1
    n = len(codes)
    num_segments = card + 1  # slot 0 = null group
    kk = min(k, num_segments)

    if n <= host_group_limit():
        # latency-dominated regime: counts + top-k on host (identical
        # ordering: argsort(-counts) stable == top_k's rank order up to
        # count ties, which are unstable on both sides by contract)
        slots = np.where(codes >= 0, codes, num_segments)
        counts = np.bincount(slots, minlength=num_segments + 1)[
            :num_segments
        ].astype(np.int64)
        if nv_code >= 0:
            counts[nv_code] += counts[0]
            counts[0] = 0
        num_groups = int((counts > 0).sum())
        order = np.argsort(-counts, kind="stable")[:kk]
        top_idx, top_counts = order, counts[order]
    else:
        padded = max(n_dev, ((n + n_dev - 1) // n_dev) * n_dev)
        if padded != n:
            codes = np.concatenate(
                [codes, np.full(padded - n, -1, dtype=np.int64)]
            )
        from deequ_tpu.ops.device_policy import (
            hist_is_wide,
            resolve_hist_variant,
        )

        variant = resolve_hist_variant((num_segments + 1,), rows=n)
        SCAN_STATS.record_hist_dispatch(
            variant, wide=hist_is_wide((num_segments + 1,))
        )
        num_groups, top_counts, top_idx = (
            np.asarray(x)
            for x in _topk_fn(num_segments, kk, mesh, nv_code, variant)(codes)
        )
        _record_fetch(num_groups, top_counts, top_idx)

    top = []
    for idx, cnt in zip(top_idx.tolist(), top_counts.tolist()):
        if cnt <= 0:
            continue
        top.append((None if idx == 0 else decode(idx), int(cnt)))
    return TopKCounts(table.num_rows, int(num_groups), tuple(top))


def _count_stats_from_counts(counts: np.ndarray, num_rows: int) -> "CountStats":
    """Host counts vector -> CountStats (shared by the dense path and the
    small-input host path so the entropy/singleton definitions cannot
    drift apart)."""
    num_groups = int(len(counts))
    singletons = int((counts == 1).sum())
    if num_rows > 0 and num_groups > 0:
        p = counts.astype(np.float64) / num_rows
        entropy = float(-(p * np.log(p)).sum())
    else:
        entropy = float("nan")
    return CountStats(num_rows, num_groups, singletons, entropy)


@dataclass(frozen=True)
class CountStats:
    """Scalar aggregates of the group-count distribution — everything the
    count-only grouping analyzers (Uniqueness, UniqueValueRatio,
    Distinctness, CountDistinct, Entropy) need, WITHOUT materializing the
    frequency table on host. For high-cardinality groupings (#groups ~ n)
    this skips the O(n) group decode + dict build entirely."""

    num_rows: int
    num_groups: int
    singletons: int
    entropy: float


def group_count_stats(
    table: ColumnarTable,
    columns: Sequence[str],
    mesh=None,
    require_any_non_null: bool = True,
) -> CountStats:
    """Count-distribution aggregates for a grouping, group values never
    leaving the device (sparse path) / never decoded (dense path)."""
    if mesh is None:
        mesh = current_mesh()
    SCAN_STATS.grouping_passes += 1
    SCAN_STATS.rows_scanned += table.num_rows

    # single resident string column: all four aggregates from HBM-resident
    # codes — only 4 scalars leave the device
    if _resident_stats_eligible(table, columns, mesh):
        with seam("grouping", columns=columns[0]):
            resident = _resident_string_bincount(
                table, columns[0], not require_any_non_null, mesh
            )
            out = device_call(
                lambda: _stats_from_counts(resident), "execute",
                what="resident count stats",
            )
            stats = device_fetch(out, "resident count stats", newest=True)
            _record_fetch(stats)
            total, groups, singles = (int(x) for x in stats[:3])
            return CountStats(
                total, groups, singles,
                float(stats[3]) if total > 0 and groups > 0
                else float("nan"),
            )

    prep = _prepare_grouping(
        table, columns, require_any_non_null, with_values=False
    )
    num_rows = prep.num_rows

    if prep.dense:
        counts = _device_bincount(prep.keys, prep.keyspace, mesh)
        return _count_stats_from_counts(counts[counts > 0], num_rows)

    # sparse path: every aggregate reduces ON DEVICE — only four scalars
    # are fetched, regardless of group count (the former implementation
    # fetched two n-length boolean vectors)
    matrix = np.stack(prep.code_arrays, axis=0)
    valid = (
        prep.any_non_null
        if prep.any_non_null is not None
        else np.ones(table.num_rows, dtype=bool)
    )
    if table.num_rows <= host_group_limit():
        # latency-dominated regime: _device_matrix_rle takes its host
        # path below this size — derive the stats from its counts
        _groups, counts = _device_matrix_rle(matrix, valid)
        return _count_stats_from_counts(counts, num_rows)
    SCAN_STATS.device_sort_passes += 1
    m, num_groups, singletons, clogc = (
        float(x) for x in _rle_stats_kernel(matrix, valid)
    )
    SCAN_STATS.record_fetch(4 * 8)
    num_groups = int(num_groups)
    if num_rows > 0 and num_groups > 0:
        # entropy = -sum (c/N) log(c/N) = log N - (sum c*log c)/N, N = m
        entropy = float(np.log(m) - clogc / m)
    else:
        entropy = float("nan")
    return CountStats(num_rows, num_groups, singletons, entropy)


# -- cross-pass grouping fusion (round 19, the whole-run plan optimizer) ----


@dataclass(frozen=True)
class GroupRequest:
    """One grouping pass the plan optimizer may fuse: its sorted column
    set and which finalize shape the caller needs — ``"freq"`` (the full
    columnar ``FrequenciesAndNumRows``) or ``"stats"`` (count-distribution
    scalars only, the ``group_count_stats`` fast path)."""

    columns: Tuple[str, ...]
    mode: str = "freq"
    canonicalize: bool = False


def _resident_stats_eligible(table, columns, mesh) -> bool:
    """True when a stats-mode set takes ``group_count_stats``'s
    resident-string fast path (all four aggregates from HBM-resident
    codes, one small vector fetched) — cheaper than any fusion, and its
    device-side entropy reduction is not bit-guaranteed against the host
    finalize, so the optimizer must leave such sets on the per-set
    path."""
    return (
        len(columns) == 1
        and table[columns[0]].dtype == DType.STRING
        and _resident_strings(table, columns, mesh) is not None
    )


def _maybe_lint_fused(
    keyspaces: Tuple[int, ...], n: int, mesh, variant: str
) -> None:
    """Static lint of the fused multi-pass bincount program under the
    ambient DEEQU_TPU_PLAN_LINT mode — the ``plan-fusion-refetch`` rule
    armed against the exact jitted program the dispatch will run (one
    concatenated counts output, no host callbacks). Memoized under the
    fusion signature so fused and unfused variants of the same sets lint
    separately, and repeated fused dispatches add zero traces."""
    from deequ_tpu.lint.plan_lint import (
        enforce_plan_lint,
        lint_plan_cached,
        plan_lint_mode,
    )

    mode = plan_lint_mode(None)
    if mode == "off":
        return
    from deequ_tpu.ops.scan_plan import plan_fused_grouping

    total = sum(keyspaces)
    plan_ir = plan_fused_grouping(keyspaces, rows=n, hist_variant=variant)
    fn = _bincount_fn(total, mesh, variant)
    avals = (jax.ShapeDtypeStruct((int(n),), np.int64),)
    mesh_sig = (
        None
        if mesh is None
        else tuple(int(d.id) for d in np.ravel(mesh.devices))
    )
    memo_key = ("fused_group", keyspaces, int(n), variant, mesh_sig)
    findings, traced = lint_plan_cached(plan_ir, fn, avals, memo_key)
    if traced:
        SCAN_STATS.plan_lint_traces += 1
    if findings:
        SCAN_STATS.plan_lints.extend(f.as_dict() for f in findings)
    enforce_plan_lint(findings, mode)


def fused_group_counts(
    table: ColumnarTable,
    requests: Sequence[GroupRequest],
    mesh=None,
) -> Dict[int, object]:
    """Cross-pass grouping FUSION: execute several dense grouping passes
    in ONE device dispatch (round 19, the tentpole observable — K
    grouping passes, one ``record_hist_dispatch``, one fetch).

    Each dense-eligible request's packed keys are offset by the
    cumulative keyspace of the requests fused before it and concatenated
    into one key vector; a single ``_device_bincount`` over the summed
    keyspace then counts every sub-pass at once, and the counts vector
    slices back per request. Integer bincounts are exact under any
    kernel variant or concatenation order, so each slice is bit-identical
    to the counts the per-set dispatch would have produced — the fusion
    legality rule (docs/planner.md).

    Returns ``{request_index: state}`` for the requests computed here
    (``FrequenciesAndNumRows`` for freq mode, ``CountStats`` for stats
    mode), with per-request ``grouping_passes``/``rows_scanned``
    accounting identical to the per-set path. A request ABSENT from the
    result falls back to the ordinary per-set path: sparse keyspaces,
    resident-string stats sets, sets whose preparation failed (the
    per-set path re-raises into the analyzer's failure metric), and sets
    whose fused group faulted twice.

    Fault ladder: a typed device fault (or an armed plan-lint rejection)
    during the FUSED dispatch demotes that group — recorded as a
    ``fusion_demote`` degradation — and each member re-plans UNFUSED
    from its own prepared keys, exactly the re-plan-per-attempt contract
    the scan ladder keeps."""
    from deequ_tpu.exceptions import DeviceException, PlanLintError

    if mesh is None:
        mesh = current_mesh()

    preps: Dict[int, _GroupPrep] = {}
    for i, req in enumerate(requests):
        if req.mode == "stats" and _resident_stats_eligible(
            table, req.columns, mesh
        ):
            continue
        try:
            prep = _prepare_grouping(
                table, list(req.columns), True,
                with_values=req.mode == "freq",
            )
        # deequ-lint: ignore[bare-except] -- a failed preparation falls back to the per-set path, which re-raises into the analyzer's typed failure metric
        except Exception:  # noqa: BLE001
            continue
        if prep.dense:
            preps[i] = prep

    # greedy keyspace packing: fuse runs of dense sets whose SUMMED
    # counts vector still fits the dense limit (the fused dispatch
    # materializes one vector of the total width)
    groups: List[List[int]] = []
    cur: List[int] = []
    cur_space = 0
    for i in sorted(preps):
        k = preps[i].keyspace
        if cur and cur_space + k > DENSE_KEYSPACE_LIMIT:
            groups.append(cur)
            cur, cur_space = [], 0
        cur.append(i)
        cur_space += k
    if cur:
        groups.append(cur)

    results: Dict[int, object] = {}
    for group in groups:
        group_counts: Optional[List[np.ndarray]] = None
        if len(group) >= 2:
            keyspaces = tuple(preps[i].keyspace for i in group)
            total = sum(keyspaces)
            offsets = np.cumsum((0,) + keyspaces[:-1])
            combined = np.concatenate([
                np.where(
                    preps[i].keys >= 0,
                    preps[i].keys + np.int64(off),
                    np.int64(-1),
                )
                for i, off in zip(group, offsets)
            ])
            try:
                if len(combined) > host_group_limit():
                    from deequ_tpu.ops.device_policy import (
                        resolve_hist_variant,
                    )

                    variant = resolve_hist_variant(
                        (total + 1,), rows=len(combined)
                    )
                    _maybe_lint_fused(
                        keyspaces, len(combined), mesh, variant
                    )
                all_counts = _device_bincount(combined, total, mesh)
                group_counts = [
                    all_counts[off:off + k]
                    for off, k in zip(offsets, keyspaces)
                ]
                SCAN_STATS.record_fused_group_pass(len(group))
            except (DeviceException, PlanLintError) as e:
                # the demotion rung: re-plan each member UNFUSED below
                SCAN_STATS.record_degradation(
                    "fusion_demote", passes=len(group),
                    keyspace=int(total), reason=str(e),
                )
                group_counts = None
        for j, i in enumerate(group):
            req, prep = requests[i], preps[i]
            try:
                counts = (
                    group_counts[j]
                    if group_counts is not None
                    else _device_bincount(prep.keys, prep.keyspace, mesh)
                )
                if req.mode == "stats":
                    state = _count_stats_from_counts(
                        counts[counts > 0], prep.num_rows
                    )
                else:
                    digit_cols, vec = _dense_digits(prep, counts)
                    state = _freq_state_from_digits(
                        req.columns, digit_cols, vec, prep.value_arrays,
                        prep.num_rows, req.canonicalize,
                    )
            # deequ-lint: ignore[bare-except] -- an unfused retry that still fails falls back to the per-set path for its typed failure metric
            except Exception:  # noqa: BLE001
                continue
            # per-request census parity with the per-set path
            SCAN_STATS.grouping_passes += 1
            SCAN_STATS.rows_scanned += table.num_rows
            results[i] = state
    return results
