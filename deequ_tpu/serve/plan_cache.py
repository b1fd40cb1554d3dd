"""The compiled-plan cache — repeat tenants skip tracing entirely.

A small verification suite costs microseconds of device compute but a
fresh submission pays: ScanOp construction, kernel-variant planning, a
plan-lint jaxpr trace, a program trace + XLA compile, and the dispatch +
fetch round trip. For the config-1 serving shape those fixed costs ARE
the latency. This module caches everything above the dispatch:

- :class:`PlanKey` — the suite fingerprint: needed-column schema
  signature, the DEDUPLICATED analyzer tuple (analyzers are hashable
  value objects whose identity includes their ``where`` predicates — the
  predicate fingerprint rides here), the packer LAYOUT signature (which
  planes each column routes over — data-dependent: the same schema with
  out-of-range values routes differently and must not share a program),
  and the member row count (the packed chunk width is static shape).
- :class:`ServePlan` — one cached entry: the built exec ops + extract
  plan, the shared packer layout, admission verdict (coalescable or the
  reason not), and the traced-program table keyed by (tenant-axis
  bucket, LUT signature) — the LUT signature is the dictionary-derived
  argument shapes, so a batch whose stacked LUTs grew re-traces while
  dictionary CONTENT rides as runtime arguments (the lut_cache design).
- :class:`PlanCache` — bounded LRU over ServePlans.

``ScanStats.plan_cache_hits`` counts suites served from a fully cached
plan — the batch found the traced program for its (tenant bucket, LUT
signature) and ran with zero op builds, zero traces, zero compiles,
zero plan-lint traces; ``plan_cache_misses`` counts suites whose batch
had to build/trace any of it (the executor accounts both,
suite-weighted). The repeat-tenant contract (the tier-1 ``serve``
suite): a second identical suite is a hit and adds zero
``plan_lint_traces`` / ``programs_built``.
"""

from __future__ import annotations

import threading
from dataclasses import dataclass, field
from typing import Any, Dict, List, Optional, Tuple

from deequ_tpu.ops.scan_engine import _BoundedLRU


@dataclass(frozen=True)
class PlanKey:
    """Suite fingerprint (see module doc). ``schema_sig`` is
    ((column, dtype), ...) over the NEEDED columns sorted by name;
    ``analyzer_sig`` the deduplicated analyzer tuple in evaluation order
    (value objects — parameters and ``where`` predicates included);
    ``layout_sig`` the packer plane routing; ``chunk`` the member row
    count every coalesced slice of this plan shares."""

    schema_sig: Tuple
    analyzer_sig: Tuple
    layout_sig: Tuple
    chunk: int


@dataclass
class ServePlan:
    """One cached suite plan (built once per PlanKey; see module doc)."""

    key: PlanKey
    #: the dedup'd analyzers in evaluation order (the runner's order)
    analyzers: Tuple
    #: scannable analyzers (op construction succeeded), their exec ops
    #: after kll coalescing, and plan[i] = (exec_idx, extractor|None)
    scannable: Tuple
    exec_ops: Tuple
    extract_plan: Tuple
    #: op-construction failures {analyzer: exception} — deterministic
    #: per plan, replayed as failure metrics for every member
    op_failures: Dict
    #: schema-precondition failures {analyzer: exception} (the runner's
    #: step-2 partition) — schema-determined, so identical for every
    #: member sharing this plan's schema signature
    precondition_failures: Dict = field(default_factory=dict)
    #: the shared packer layout dict every member packs against
    layout: Dict = field(default_factory=dict)
    #: needed column names (sorted)
    needed: Tuple = ()
    #: a metadata-only unpack view (_ChunkPacker.unpack_view) captured at
    #: build time — what the traced program closes over
    unpack_view: Any = None
    #: traced vmapped programs: (k_bucket, lut_sig) -> (vstep, shapes)
    programs: Dict = field(default_factory=dict)
    #: False + reason when members of this plan cannot coalesce (own-pass
    #: or grouping analyzers, dictionary-baked ops, streaming/oversized
    #: tables) — the service then runs them per-tenant on the serial path
    coalescable: bool = True
    why_not: str = ""
    #: True when the REASON is intrinsic to the analyzer set (grouping /
    #: own-pass members, dictionary-baked or uncacheable ops) rather
    #: than to the table it was built from — only class-level verdicts
    #: may be cached per analyzer signature (the service's _families);
    #: a table-level verdict (missing column, empty/oversized table,
    #: op-build failure) must never poison other tenants' admissions
    serial_class: bool = False

    def program_for(self, k_bucket: int, lut_sig: Tuple):
        return self.programs.get((k_bucket, lut_sig))

    def put_program(self, k_bucket: int, lut_sig: Tuple, prog) -> None:
        self.programs[(k_bucket, lut_sig)] = prog


class PlanCache:
    """Bounded LRU of ServePlans (the serve layer's one entry point to
    plan reuse). ``get`` / ``put`` mirror the hit/miss ledger into
    ``ScanStats`` — a hit here is the "skip tracing, compilation and
    plan-lint entirely" fast path ONLY if the program table also has the
    batch's (K, luts) program; the executor accounts that split."""

    def __init__(self, cap: int = 256):
        self._lru = _BoundedLRU(cap)

    def get(self, key: PlanKey) -> Optional[ServePlan]:
        return self._lru.get(key)

    def put(self, plan: ServePlan) -> None:
        self._lru.put(plan.key, plan)

    def __len__(self) -> int:
        return len(self._lru)

    def entries(self) -> List[ServePlan]:
        """The cached plans in LRU order (most recently used LAST) — the
        fleet's hot-plan feed for worker-join warmup."""
        return list(self._lru._d.values())

    def clear(self) -> None:
        self._lru.clear()


@dataclass(frozen=True)
class SubPlanKey:
    """Cross-suite SUB-PLAN identity (round 19, the plan optimizer):
    the traced packed program's identity BELOW the exact :class:`PlanKey`.

    Two tenants whose analyzer sets are permutations (or whose suites
    dedupe to the same op set) get DISTINCT PlanKeys — ``analyzer_sig``
    preserves submission order, which the result path needs — but trace
    to the same program once ops are put in canonical order. This key
    names that shared program: the canonical (sorted by op identity)
    exec-op tuple, the schema/layout signatures, the chunk width, the
    tenant bucket + LUT signature (the traced shapes), and every kernel
    variant that steers codegen. ``lint.plan_lint.check_subplan_key``
    (the ``plan-fusion-refetch`` rule's sharing half) rejects any key
    that drops an identity component."""

    ops_sig: Tuple
    schema_sig: Tuple
    layout_sig: Tuple
    chunk: int
    k_bucket: int
    lut_sig: Tuple
    variant: str
    hist_variant: str
    ingest_variant: str


class SubPlanCache:
    """Bounded LRU of traced packed programs keyed by
    :class:`SubPlanKey` — lock-serialized (the serving workers share the
    process singleton, like the PR-14 census counters). Stored entries
    are (single_flat, vstep, shapes, recipes) in CANONICAL op order;
    each borrowing plan keeps its own exec-order permutation alongside
    its ``ServePlan.programs`` entry."""

    def __init__(self, cap: int = 128):
        self._lru = _BoundedLRU(cap)
        self._lock = threading.Lock()

    def get(self, key: SubPlanKey):
        with self._lock:
            return self._lru.get(key)

    def put(self, key: SubPlanKey, prog) -> None:
        with self._lock:
            self._lru.put(key, prog)

    def __len__(self) -> int:
        with self._lock:
            return len(self._lru)

    def clear(self) -> None:
        with self._lock:
            self._lru.clear()


#: the process-wide cross-suite program cache (serve/executor.py reads
#: it on every exact-PlanKey program miss before paying a trace)
SUBPLAN_CACHE = SubPlanCache()


def canonical_op_order(exec_ops: Tuple) -> Tuple[Tuple[int, ...], Tuple[int, ...]]:
    """The canonical op ordering shared programs are traced in: exec-op
    indices sorted by the op's cache-key identity (analyzers are value
    objects; their string form is a stable total order for any one op
    set). Returns ``(canon, perm)`` — ``canon[pos]`` is the exec index
    at canonical position ``pos``, and ``perm[exec_idx]`` the canonical
    position of exec op ``exec_idx`` (the result-path inverse)."""
    canon = tuple(
        sorted(range(len(exec_ops)), key=lambda i: str(exec_ops[i].cache_key))
    )
    perm = [0] * len(canon)
    for pos, i in enumerate(canon):
        perm[i] = pos
    return canon, tuple(perm)


def subplan_key(
    plan: ServePlan,
    canon: Tuple[int, ...],
    k_bucket: int,
    lut_sig: Tuple,
    variant: str,
    hist_variant: str,
    ingest_variant: str,
) -> SubPlanKey:
    """Build the :class:`SubPlanKey` for ``plan``'s packed program at
    this (bucket, LUT) shape. ``ops_sig`` carries the analyzer value
    objects themselves (full identity: parameters and ``where``
    predicates included), in canonical order."""
    return SubPlanKey(
        ops_sig=tuple(plan.exec_ops[i].cache_key for i in canon),
        schema_sig=plan.key.schema_sig,
        layout_sig=layout_signature(plan.layout),
        chunk=plan.key.chunk,
        k_bucket=k_bucket,
        lut_sig=lut_sig,
        variant=variant,
        hist_variant=hist_variant,
        ingest_variant=ingest_variant,
    )


def schema_signature(table, needed) -> Tuple:
    """((column, dtype), ...) over ``needed`` (sorted) — the schema half
    of the plan fingerprint."""
    return tuple((n, table[n].dtype) for n in needed)


def layout_signature(layout: Dict) -> Tuple:
    return tuple(sorted((k, tuple(v)) for k, v in layout.items()))


def build_serve_plan(table, analyzers: List, key_hint=None) -> ServePlan:
    """Build the ServePlan for ``analyzers`` over ``table``'s shape — op
    construction (failure-isolated per analyzer, the runner's rule), kll
    coalescing, layout derivation, and coalescability admission. The
    hit/miss ledger is accounted by the executor (program granularity),
    not here."""
    from deequ_tpu.analyzers.base import (
        ScanShareableAnalyzer,
        find_first_failing,
    )
    from deequ_tpu.analyzers.runner import AnalysisRunner, _is_grouping_shared
    from deequ_tpu.ops.scan_engine import _ChunkPacker, _auto_chunk_rows

    analyzers = tuple(analyzers)
    # precondition partition first (the runner's step 2): schema
    # violations become failure metrics per member, never scan ops
    precondition_failures: Dict = {}
    passed = []
    for a in analyzers:
        exc = find_first_failing(table.schema, a.preconditions())
        if exc is None:
            passed.append(a)
        else:
            precondition_failures[a] = exc
    scanning = [
        a for a in passed
        if isinstance(a, ScanShareableAnalyzer) and not _is_grouping_shared(a)
    ]
    non_scan = [a for a in passed if a not in scanning]

    coalescable = True
    why = ""
    serial_class = False
    if non_scan:
        # grouping/own-pass members need their own passes (frequency
        # folds, spill budgets) — the standard runner handles them; a
        # suite containing any is served per-tenant. CLASS-level: true
        # for every table this analyzer set ever meets
        coalescable = False
        serial_class = True
        why = f"non-scan-shareable analyzers: {[str(a) for a in non_scan]}"

    ops, scannable, op_failures = AnalysisRunner._build_scan_ops(
        table, scanning
    )
    exec_ops: Tuple = ()
    extract_plan: Tuple = ()
    layout: Dict = {}
    needed: Tuple = ()
    view = None
    if scannable:
        exec_list, plan_list = AnalysisRunner._coalesce_scan_ops(ops)
        exec_ops = tuple(exec_list)
        extract_plan = tuple(plan_list)
        if any(op.dictionary_baked for op in exec_ops):
            # trace-time dictionary constants bake the FIRST table's
            # values into the program — never reusable across tenants
            # (class-level: the predicate, not the table, is baked)
            coalescable = False
            serial_class = True
            why = why or "dictionary-baked ops (trace-time constants)"
        if any(op.cache_key is None for op in exec_ops):
            coalescable = False
            serial_class = True
            why = why or "uncacheable ops (no program identity)"
        needed = tuple(sorted({c for op in exec_ops for c in op.columns}))
        cols = {n: table[n] for n in needed}
        n_rows = int(table.num_rows)
        if n_rows == 0:
            coalescable = False
            why = why or "empty table"
        elif n_rows > _auto_chunk_rows(cols):
            # multi-chunk members would change the serial path's
            # reduction association (the group path's single-chunk
            # guard) — big tables go through the ordinary engine
            coalescable = False
            why = why or "table exceeds the single-chunk coalesce bound"
        if n_rows > 0:
            # same encode routing as the serial baseline (run_scan
            # resolves the same switch): an encoded member must ride the
            # code plane coalesced exactly as it would serially, or the
            # bit-identity contract compares different compute paths
            from deequ_tpu.ops.scan_plan import encoded_ingest_enabled

            packer = _ChunkPacker(
                cols, max(n_rows, 1),
                encode_ingest=encoded_ingest_enabled(None),
            )
            layout = packer.layout()
            view = packer.unpack_view()
            # members pack against this layout, so what the planner
            # reads off it (the plane route) is resolved once, here: the
            # coalesced program then runs the arithmetic a serial run of
            # one member runs (the bit-identity contract)
            from deequ_tpu.ops.scan_plan import plan_scan_ops

            exec_ops = plan_scan_ops(exec_ops, packer, resident=False).ops
    elif scanning:
        # every scan op failed to build: nothing to coalesce
        coalescable = False
        why = why or "no scannable ops"

    return ServePlan(
        key=key_hint,
        analyzers=analyzers,
        scannable=tuple(scannable),
        exec_ops=exec_ops,
        extract_plan=extract_plan,
        op_failures=dict(op_failures),
        precondition_failures=precondition_failures,
        layout=layout,
        needed=needed,
        unpack_view=view,
        coalescable=coalescable and bool(scannable),
        why_not=why,
        serial_class=serial_class,
    )
