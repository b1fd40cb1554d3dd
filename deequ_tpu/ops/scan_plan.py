"""ScanPlan: kernel-variant resolution for the fused scan — the first
slice of the plan/executor/policy split (ROADMAP item 5).

``run_scan``'s fault ladder (reshard -> bisect -> CPU fallback) retries
``_run_scan_once`` with changed *resources* (smaller chunks, a smaller
mesh, evicted residency, another backend). Kernel choices that depend on
those resources must therefore be (re-)derived INSIDE each attempt, from
the attempt's own packer/residency state — never threaded through the
ladder as sticky state. ``plan_scan_ops`` is that derivation point: it
takes the ops as the analyzers built them and returns the concrete ops
the executor will trace, with per-op kernel variants resolved.

Today the planner makes one decision: route KLL/quantile summary ops
through the batched histogram SELECTION kernel (ops/select_device.py)
instead of the full device sort (ops/kll_device.py) when

  - the op offers a selection variant (``ScanOp.select_update``),
  - the kernel is enabled (``run_scan(select_kernel=...)`` /
    ``DEEQU_TPU_SELECT_KERNEL``, default on),
  - the table is RESIDENT (persisted in HBM): the selection kernel's
    win is redesigning the memory path of multi-pass rank queries over
    data already sitting in HBM; streaming/non-resident chunks keep the
    sort path (same summaries either way — the two kernels are
    exact-rank interchangeable, docs/numerics.md), and
  - every column the kernel selects over rides a two-float/i32 plane in
    the packer layout (wide-f64 columns have no u32 key domain).

Because an OOM retry evicts residency before re-planning, a fault during
a selection pass lands the next attempt on the sort path automatically —
the ladder needs no knowledge of kernel variants at all.

A second decision rides the same seam: an op whose partial is made of
the statistics of ONE where-free column (``ScanOp.plane_update``:
Completeness, Mean, Sum, StandardDeviation, Minimum, Maximum) is routed
onto the batched plane statistics when that column lies on the (hi, lo)
pair planes of THIS attempt's packer layout. All such columns are then
reduced where they lie, in one batched reduction along the rows of the
planes (``scan_engine.PlaneStats``), and the routed ops read their
scalars out of its result. Read from the layout alone: no switch.

The resolved plan also carries the per-chunk kernel census
(``sort_ops``/``select_ops``/``plane_ops``) that the executor turns into
``ScanStats.device_sort_passes`` / ``device_select_passes`` /
``plane_ops``.
"""

from __future__ import annotations

from dataclasses import dataclass, replace
from typing import Optional, Sequence, Tuple

import jax

#: the reduction tags the fold layer knows how to merge (scan_engine's
#: _tag_reduce_np / _DeviceFoldPlan); a declared tag outside this set is
#: a planner bug the plan lint rejects before dispatch
KNOWN_FOLD_TAGS = frozenset(("sum", "min", "max", "gather"))


def encoded_ingest_enabled(param: Optional[bool] = None) -> bool:
    """Resolve the encoded-ingest switch: explicit argument wins, then
    the DEEQU_TPU_ENCODED_INGEST env var ('0' disables — the A/B and
    regression-triage escape hatch, mirroring DEEQU_TPU_SELECT_KERNEL;
    parsed via the deequ_tpu/envcfg registry), then on. When on, columns
    carrying a dictionary encoding ride the int16 ``enc`` plane (codes
    only over the host->device link; decode is a dictionary gather fused
    into the scan program); off routes every column through the decoded planes
    exactly as before round 8."""
    from deequ_tpu.envcfg import env_value

    if param is not None:
        if not isinstance(param, (bool, int)) or param not in (0, 1):
            raise ValueError(
                f"encoded_ingest must be True/False, got {param!r}"
            )
        return bool(param)
    return env_value("DEEQU_TPU_ENCODED_INGEST")


def select_kernel_enabled(param: Optional[bool] = None) -> bool:
    """Resolve the selection-kernel switch: explicit argument wins, then
    the DEEQU_TPU_SELECT_KERNEL env var ('0' disables — the A/B and
    regression-triage escape hatch; parsed via the deequ_tpu/envcfg
    registry), then on. Validated: the argument must be bool-like, the
    env var one of '', '0', '1'."""
    from deequ_tpu.envcfg import env_value

    if param is not None:
        if not isinstance(param, (bool, int)) or param not in (0, 1):
            raise ValueError(
                f"select_kernel must be True/False, got {param!r}"
            )
        return bool(param)
    return env_value("DEEQU_TPU_SELECT_KERNEL")


@dataclass(frozen=True)
class ScanPlan:
    """One attempt's resolved op list + kernel census + declared contracts.

    ``ops`` are the concrete ScanOps the executor traces (variant
    substitutions applied, cache keys rewritten so traced-program caches
    can never serve a sort-path program to a selection-path scan or vice
    versa). ``sort_ops``/``select_ops`` count the column summaries per chunk
    dispatch that a device sort / a histogram selection computes (a
    coalesced KLL op counts each member column) — the executor
    multiplies by chunks processed into ScanStats.

    The remaining fields are the plan's DECLARED contracts — the metadata
    the static plan lint (deequ_tpu/lint/plan_lint.py) checks the traced
    jaxpr against, so a planner/packer drift is caught at trace time
    instead of after a bench run:

    - ``variant`` — ``"select"`` (every summary op routed through the
      histogram selection kernel: the traced program must contain ZERO
      ``sort`` primitives, the static twin of the
      ``device_select_passes``/``device_sort_passes`` runtime pair),
      ``"sort"`` (sort path), ``"mixed"`` (both kernels present), or
      ``"none"`` (no summary kernels at all);
    - ``fold_tags`` — per resolved op, the tuple of reduction-tag leaves
      the planner declares for the fold layer; the lint re-derives the
      actual leaves from ``ops[i].tags`` and rejects any disagreement (an
      ``add``-declared leaf actually merged with ``max`` silently
      corrupts every cross-chunk merge);
    - ``fetch_contract`` — ``"one-fetch"`` when every op is
      device-foldable (the whole scan pays one device->host fetch) else
      ``"per-chunk"``; traced programs must contain no host callbacks
      either way;
    - ``hist_variant`` — the histogram/segment-fold kernel tier the
      plan's bincount passes ride (ops/histogram_device.py, round 14):
      ``"scatter"`` (the XLA lowering), ``"onehot"`` (blocked one-hot
      matmul — MXU on chip, sgemm on CPU), ``"pallas"`` (force-knob
      only), or ``"none"`` when the plan runs no histogram passes. A
      matmul/pallas-variant plan must trace to a jaxpr with ZERO
      ``scatter-add`` primitives — the ``plan-hist-scatter`` lint rule,
      the static twin of the per-variant dispatch counters on
      ScanStats. Resolved per attempt by
      ``device_policy.resolve_hist_variant`` from the select ops'
      declared histogram widths (``ScanOp.hist_widths``), the chunk row
      count, and the platform, and BOUND around the resolved update at
      trace time (``histogram_device.active_hist_variant``) so the
      traced program and the declaration can never drift;
    - ``ingest_variant`` — ``"encoded"`` when at least one column rides
      the packer's int16 ``enc`` plane (dictionary codes on device,
      decode gathered inside the fused program), else ``"decoded"``.
      ``encoded_columns`` names them and ``layout`` snapshots the full
      packer plane routing — the ``plan-encoded-decode`` lint rule
      (deequ_tpu/lint/plan_lint.py) rejects an encoded-variant plan
      whose declared encoded column actually arrives pre-decoded on a
      full-width plane, or whose program smuggles a host callback."""

    ops: Tuple
    resident: bool
    select_ops: int = 0
    sort_ops: int = 0
    #: ops that read their scalars out of the batched plane statistics
    plane_ops: int = 0
    #: HLL register folds per chunk dispatch (``ScanOp.hll_folds`` summed)
    hll_folds: int = 0
    variant: str = "none"
    #: histogram kernel tier of the plan's bincount passes ("none" when
    #: the plan runs no histogram passes at all) — see class doc
    hist_variant: str = "none"
    fold_tags: Tuple[Tuple[str, ...], ...] = ()
    fetch_contract: str = "per-chunk"
    ingest_variant: str = "decoded"
    encoded_columns: Tuple[str, ...] = ()
    #: hashable snapshot of the packer layout (tuple of (plane, names)),
    #: None when the attempt has no packer yet (streams before batch 1)
    layout: Optional[Tuple] = None
    #: multi-tenant PACKED plan (deequ_tpu/serve, round 10): the number
    #: of tenant slices (padded slots included) the executor vmaps the
    #: shared program over. 0 = an ordinary single-tenant plan. A packed
    #: plan's one-fetch contract is per coalesced BATCH: one (K, S)
    #: result materialization for K tenant suites.
    tenants: int = 0
    #: per-member declared contracts (PackedMember rows) the plan lint
    #: re-checks against the SHARED traced program — a sort smuggled in
    #: while any member declares the selection contract, or a member's
    #: encoded column arriving pre-decoded on the group layout, is a
    #: per-slice violation even though the program is shared
    members: Tuple = ()
    #: cross-pass FUSION signature (round 19): the per-sub-pass keyspace
    #: widths of a fused multi-grouping dispatch, in sub-pass order; ()
    #: = an ordinary unfused plan. A fused plan's traced program must
    #: produce exactly ONE output (the concatenated counts vector — one
    #: fetch for all sub-passes) and smuggle no host callbacks: the
    #: ``plan-fusion-refetch`` lint rule. Also a lint-memo-key component
    #: so fused and unfused variants of the same op set lint separately.
    fusion: Tuple[int, ...] = ()
    #: WINDOWED plan (deequ_tpu/windows, round 20): the declared window
    #: geometry ``(size_s, slide_s, time_column)`` of a
    #: ``variant="windowed"`` plan, whose program advances every open
    #: pane in ONE dispatch per batch (the window fold axis). ``tenants``
    #: doubles as the pane-bucket count for such plans. None = not a
    #: windowed plan. The ``plan-window-refeed`` lint rule checks the
    #: declared geometry, the pane-count/fold-tag consistency, and that
    #: the traced pane fold smuggles no host callbacks; the window
    #: signature is also a lint-memo-key component.
    window_spec: Optional[Tuple] = None
    #: the declared watermark policy ``(lag_s, late_policy)`` riding a
    #: windowed plan (None otherwise) — late routing is part of the
    #: plan's contract: a windowed program with no declared policy would
    #: silently fold late rows into closed panes
    watermark_policy: Optional[Tuple] = None


@dataclass(frozen=True)
class PackedMember:
    """One tenant slice's DECLARED contracts inside a packed plan.

    ``label`` identifies the member in lint findings (tenant id / slice
    index); the remaining fields mirror the ScanPlan contract fields the
    lint checks per slice. In a healthy coalesced batch every member's
    declaration equals the shared plan's (the coalescer admits only
    same-plan suites); a disagreement is planner drift the
    ``plan-select-sort`` / ``plan-encoded-decode`` rules reject
    pre-dispatch, per member."""

    label: str
    variant: str = "sort"
    ingest_variant: str = "decoded"
    encoded_columns: Tuple[str, ...] = ()
    #: True marks a PADDING slot (an all-invalid dummy slice the
    #: executor appends to reach the tenant-axis bucket; its result is
    #: discarded) — the lint skips contract checks for it
    padding: bool = False


def plan_packed_scan(
    ops: Sequence,
    packer=None,
    members: Sequence[PackedMember] = (),
    select_kernel: Optional[bool] = None,
) -> "ScanPlan":
    """Resolve the multi-tenant PACKED plan (deequ_tpu/serve): one shared
    op list the coalesced executor vmaps over a leading tenant axis,
    ``members`` declaring each slice's contracts.

    Packed members are packed fresh per batch and never device-resident,
    so kernel resolution always lands on the sort path (exactly what the
    serial baseline runs for a non-persisted table — the bit-identity
    contract's requirement); the tenant axis rides vmap, whose per-slice
    independence is what makes padding slots provably inert. The plan's
    fetch contract is one fetch per coalesced BATCH."""
    base = plan_scan_ops(
        ops, packer, resident=False, select_kernel=select_kernel
    )
    return replace(
        base,
        tenants=len(members),
        members=tuple(members),
    )


def plan_fusion_enabled(param: Optional[bool] = None) -> bool:
    """Resolve the cross-pass fusion switch: explicit argument wins,
    then DEEQU_TPU_PLAN_FUSION ('0' disables — the plan-optimizer A/B
    hatch, round 19), then on. Validated like the sibling switches."""
    from deequ_tpu.envcfg import env_value

    if param is not None:
        if not isinstance(param, (bool, int)) or param not in (0, 1):
            raise ValueError(
                f"plan_fusion must be True/False, got {param!r}"
            )
        return bool(param)
    return env_value("DEEQU_TPU_PLAN_FUSION")


def plan_fused_grouping(
    keyspaces: Sequence[int],
    rows: Optional[int] = None,
    hist_variant: Optional[str] = None,
) -> ScanPlan:
    """Resolve the FUSED multi-grouping plan (round 19): K dense
    grouping passes sharing one dispatch. The plan carries no ScanOps —
    its program is the offset-bincount the segment layer builds — but it
    declares the contracts the ``plan-fusion-refetch`` lint rule checks:
    the ``fusion`` signature (per-sub-pass keyspaces), the one-fetch
    contract (ONE concatenated counts output for all K sub-passes), and
    the histogram kernel tier the single dispatch rides. Re-derived per
    attempt, like every plan: a fault that demotes the fused dispatch
    re-plans the sub-passes unfused (``fusion=()``) automatically."""
    from deequ_tpu.ops.device_policy import resolve_hist_variant

    widths = tuple(int(k) for k in keyspaces)
    if len(widths) < 2:
        raise ValueError(
            f"a fused grouping plan needs >= 2 sub-passes, got {widths!r}"
        )
    if hist_variant is None:
        # the fused dispatch is ONE bincount over the summed keyspace —
        # the variant policy prices that total width, not the sub-passes
        hist_variant = resolve_hist_variant((sum(widths) + 1,), rows=rows)
    return ScanPlan(
        ops=(),
        resident=False,
        variant="none",
        hist_variant=hist_variant,
        fetch_contract="one-fetch",
        fusion=widths,
    )


def plan_windowed_scan(
    fold_tags: Sequence[str],
    panes: int,
    window_spec: Tuple,
    watermark_policy: Tuple,
) -> ScanPlan:
    """Resolve the WINDOWED plan (round 20): sliding/tumbling event-time
    windows as an extra fold dimension of the device program. Like the
    fused-grouping plan, it carries no ScanOps — the program is the pane
    step the windows engine builds — but it declares the contracts the
    ``plan-window-refeed`` lint rule checks: the window geometry
    ``(size_s, slide_s, time_column)``, the watermark policy
    ``(lag_s, late_policy)``, the pane-bucket count (``tenants``), the
    per-pane fold tags (every leaf a KNOWN_FOLD_TAGS monoid, so
    per-window metrics stay bit-identical to a one-shot run), and the
    one-fetch contract (ONE (panes, leaves) materialization per batch,
    no host callbacks inside the pane fold)."""
    tags = tuple(str(t) for t in fold_tags)
    if not tags:
        raise ValueError("a windowed plan needs at least one fold leaf")
    unknown = sorted(set(tags) - KNOWN_FOLD_TAGS)
    if unknown:
        raise ValueError(
            f"windowed plan declares unknown fold tags {unknown!r}; "
            f"known: {sorted(KNOWN_FOLD_TAGS)}"
        )
    if int(panes) < 1:
        raise ValueError(f"a windowed plan needs >= 1 pane, got {panes!r}")
    spec = tuple(window_spec)
    if len(spec) != 3:
        raise ValueError(
            f"window_spec must be (size_s, slide_s, time_column), got {spec!r}"
        )
    size_s, slide_s = float(spec[0]), float(spec[1])
    if not (size_s > 0.0 and slide_s > 0.0 and slide_s <= size_s):
        raise ValueError(
            f"window_spec needs 0 < slide_s <= size_s, got {spec!r}"
        )
    policy = tuple(watermark_policy)
    if len(policy) != 2:
        raise ValueError(
            f"watermark_policy must be (lag_s, late_policy), got {policy!r}"
        )
    return ScanPlan(
        ops=(),
        resident=False,
        variant="windowed",
        fold_tags=(tags,),
        fetch_contract="one-fetch",
        tenants=int(panes),
        window_spec=spec,
        watermark_policy=policy,
    )


def _selectable(op, packer) -> bool:
    """True when every column the op's selection kernel keys on rides a
    (hi, lo) plane in this packer layout: two-float pairs or i32-split
    integrals — anything but the wide-f64 plane, whose 64-bit keys the
    u32 radix passes cannot cover."""
    if packer is None:
        return False
    # encoded columns qualify: the dictionary gather reconstructs the
    # SAME (hi, lo) plane Val the pair/i32 routes produce, so the
    # selection kernel's u32 key space is identical
    keyed = (
        set(packer.pair_names)
        | set(packer.narrow_i32)
        | set(getattr(packer, "enc_names", ()))
    )
    return all(c in keyed for c in op.select_columns)


def _plane_route(ops: Sequence, packer):
    """The PlaneRoute of one attempt (None when nothing routes): every op
    that declares a plane variant and whose column lies on the pair
    planes of this packer layout, the columns in the order of their
    plane rows, each with the union of what its ops ask for."""
    from deequ_tpu.ops.scan_engine import PlaneRoute

    if packer is None:
        return None
    row = {name: i for i, name in enumerate(packer.pair_names)}
    wanted = {}
    for op in ops:
        if op.plane_update is not None and op.plane_column in row:
            wanted.setdefault(op.plane_column, set()).update(op.plane_stats)
    if not wanted:
        return None
    return PlaneRoute(
        tuple(
            (name, tuple(sorted(wanted[name])))
            for name in sorted(wanted, key=row.__getitem__)
        )
    )


def _plane_routed(op, route):
    """``op`` with its update replaced by the read of its column's
    statistics out of the trace's PlaneStats. Place, tags, leaves and
    extractor stay; the cache key changes so a cached per-column program
    is never taken for this one."""
    column, from_stats = op.plane_column, op.plane_update

    def plane_update(vals, row_valid, xp, n):
        return from_stats(vals.plane.of(route)[column])

    # a field-for-field copy: dataclasses.replace re-runs __init__ over
    # every field, and a profiler suite routes a hundred ops each run
    routed = object.__new__(type(op))
    routed.__dict__.update(op.__dict__)
    routed.update = plane_update
    routed.plane_route = route
    if op.cache_key is not None:
        routed.cache_key = ("plane", op.cache_key)
    return routed


def _summary_members(op) -> int:
    """Column summaries one dispatch of a KLL op computes: a coalesced
    op (``_kll_multi_scan_op``) sorts or selects each member column."""
    return max(len(op.select_columns), 1)


def _bind_hist_variant(update, variant: str):
    """Wrap a resolved update so the ambient histogram variant is bound
    exactly while THIS op's portion of the program traces — the traced
    bincount passes (select_device._segment_count ->
    histogram_device.bincount) read it there, and nowhere else. Binding
    at plan time (not executor time) means plan lint's own trace of the
    program sees the identical kernels the executor will jit."""
    from deequ_tpu.ops.histogram_device import active_hist_variant

    def bound_update(vals, row_valid, xp, n):
        with active_hist_variant(variant):
            return update(vals, row_valid, xp, n)

    return bound_update


def plan_scan_ops(
    ops: Sequence,
    packer=None,
    resident: bool = False,
    select_kernel: Optional[bool] = None,
    rows: Optional[int] = None,
) -> ScanPlan:
    """Resolve kernel variants for one scan attempt (see module doc).
    ``rows`` is the attempt's chunk row count when the caller knows it
    (the resident path does) — one input to the histogram-variant
    policy; ``None`` means "large"."""
    from deequ_tpu.ops.device_policy import resolve_hist_variant

    use_select = resident and select_kernel_enabled(select_kernel)
    # ONE routing predicate, evaluated once per op: the flags below
    # drive BOTH the histogram-variant decision and the routing loop,
    # so the declared variant can never drift from the ops that
    # actually trace it
    routed = [
        op.select_update is not None and use_select and _selectable(
            op, packer
        )
        for op in ops
    ]
    # the histogram-variant decision is PER PLAN, over the widest
    # histogram any select-routed op will run: a multi-pass program must
    # never mix variants or the plan-hist-scatter lint contract (and the
    # per-variant dispatch census) would be unstatable
    hist_variant = "none"
    if any(routed):
        hist_variant = resolve_hist_variant(
            tuple(
                w
                for op, sel in zip(ops, routed)
                if sel
                for w in (op.hist_widths or ())
            ),
            rows=rows,
        )
    route = _plane_route(ops, packer)
    on_plane = (
        {name for name, _ in route.columns} if route is not None else ()
    )
    resolved = []
    n_select = 0
    n_sort = 0
    for op, sel in zip(ops, routed):
        if op.plane_update is not None and op.plane_column in on_plane:
            resolved.append(_plane_routed(op, route))
        elif sel:
            key = (
                ("select", hist_variant, op.cache_key)
                if op.cache_key is not None
                else None
            )
            resolved.append(
                replace(
                    op,
                    update=_bind_hist_variant(
                        op.select_update, hist_variant
                    ),
                    cache_key=key,
                )
            )
            n_select += _summary_members(op)
        else:
            resolved.append(op)
            if op.sorts_chunk:
                n_sort += _summary_members(op)
    if n_select and not n_sort:
        variant = "select"
    elif n_sort and not n_select:
        variant = "sort"
    elif n_sort and n_select:
        variant = "mixed"
    else:
        variant = "none"
    enc_cols = (
        tuple(getattr(packer, "enc_names", ()) or ())
        if packer is not None
        else ()
    )
    layout = (
        tuple(sorted((k, tuple(v)) for k, v in packer.layout().items()))
        if packer is not None
        else None
    )
    return ScanPlan(
        ops=tuple(resolved),
        resident=resident,
        select_ops=n_select,
        sort_ops=n_sort,
        plane_ops=sum(op.plane_route is not None for op in resolved),
        hll_folds=sum(op.hll_folds for op in resolved),
        variant=variant,
        hist_variant=hist_variant,
        fold_tags=tuple(
            tuple(str(t) for t in jax.tree.leaves(op.tags))
            for op in resolved
        ),
        fetch_contract=(
            "one-fetch"
            if all(op.compact is None for op in resolved)
            else "per-chunk"
        ),
        ingest_variant="encoded" if enc_cols else "decoded",
        encoded_columns=enc_cols,
        layout=layout,
    )
