"""The coalesced packed executor — N tenant suites, ONE dispatch, ONE fetch.

``run_coalesced`` takes K admitted members of one ServePlan (same schema
signature, analyzer set, packer layout, and row count), packs each into
the plan's single-chunk layout, stacks the buffers along a leading
TENANT axis padded to a pow2 bucket, and runs one vmapped fused program
— the ``run_scan_group`` construction (tests pin it bit-identical to
per-tenant serial scans) extended with:

- tenant-axis PADDING: dummy all-invalid slices (row_valid False, masks
  False, codes/enc -1) fill the bucket so the program-per-batch-size
  count stays O(log max_batch) instead of O(max_batch). vmap maps each
  slice independently — a padding slice can influence no real member's
  result by construction, which is what makes padding provably inert
  (the real rows are never padded: members coalesce only on EXACT row
  count, because chunk padding shifts the f32-pair reduction
  association at the ulp level — measured, and exactly what
  ``group_scannable`` forbids);
- per-tenant dictionary LUT stacking for string AND encoded columns
  (each member's LUT pads to the group max pow2; gathers never touch
  padding — codes index below each member's own cardinality);
- the packed PLAN-LINT pass: the shared program lints under its own
  memo key (tenant-axis bucket + member contract fingerprints on top of
  the program identity) with per-member slice checks
  (lint/plan_lint.py:_check_packed_members);
- fault-ladder seams: the dispatch runs under ``device_call`` at the
  execute boundary (watchdog + chaos-hook injection), the single fetch
  at the fetch boundary; a classified device fault raises out to the
  service, which BISECTS the tenant axis (isolation in O(log K)).

The one-fetch contract here is per coalesced BATCH: exactly one
device->host materialization of the (K, S) state matrix regardless of K.
"""

from __future__ import annotations

from typing import Any, Dict, List, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from deequ_tpu.obs.recorder import seam
from deequ_tpu.ops.scan_engine import (
    SCAN_STATS,
    _ChunkPacker,
    _scoped_update,
    _split_lut_key,
)
from deequ_tpu.ops.device_policy import device_call, device_fetch


def _member_packer(plan, table) -> _ChunkPacker:
    """A packer for one member's columns pinned to the PLAN layout (the
    admission key guarantees the member classifies onto it)."""
    cols = {n: table[n] for n in plan.needed}
    return _ChunkPacker(cols, plan.key.chunk, layout=plan.layout)


def _pad_slice(shapes: Sequence[Tuple], chunk: int):
    """One all-invalid padding slice: value planes zero, masks False,
    string/enc codes -1 (null), row_valid all False — the neutral fill
    ``_ChunkPacker.pack`` uses for the tail of a short chunk, applied to
    every row."""
    values, hi, lo, narrow_i, masks, codes, row_valid, enc = shapes
    return (
        np.zeros(values, dtype=np.float64),
        np.zeros(hi, dtype=np.float32),
        np.zeros(lo, dtype=np.float32),
        np.zeros(narrow_i, dtype=np.int32),
        np.zeros(masks, dtype=np.bool_),
        np.full(codes, -1, dtype=np.int32),
        np.zeros((chunk,), dtype=np.bool_),
        np.full(enc, -1, dtype=np.int16),
    )


def _stack_member_buffers(
    plan, tables: Sequence, k_bucket: int, packers: Sequence = (),
):
    """Pack every member with the shared layout and stack to (K, ...)
    buffers, padding the tenant axis to ``k_bucket``. ``packers`` may
    carry each member's admission-time packer (its layout signature
    already matched the plan key) to skip a second classification."""
    chunk = plan.key.chunk
    stacked: Optional[List[List[np.ndarray]]] = None
    for j, t in enumerate(tables):
        packer = packers[j] if j < len(packers) and packers[j] is not None \
            else _member_packer(plan, t)
        args = packer.pack(0, int(t.num_rows))
        SCAN_STATS.bytes_packed += sum(a.nbytes for a in args)
        if stacked is None:
            stacked = [[a] for a in args]
        else:
            for lst, a in zip(stacked, args):
                lst.append(a)
    assert stacked is not None
    n_pad = k_bucket - len(tables)
    if n_pad > 0:
        pad = _pad_slice([lst[0].shape for lst in stacked], chunk)
        for lst, p in zip(stacked, pad):
            lst.extend([p] * n_pad)
    return tuple(np.stack(lst) for lst in stacked)


def _enc_lut_specs(plan) -> List[Tuple[str, str, Any]]:
    """(column, kind, builder) rows for the plan's ENCODED columns —
    mirrors ``scan_engine._collect_enc_luts`` but emits specs the
    per-member stacking loop below consumes uniformly with ``op.luts``."""
    from deequ_tpu.data.table import DType
    from deequ_tpu.ops.scan_engine import (
        _enc_hi_lut,
        _enc_i32_lut,
        _enc_lo_lut,
    )

    specs: List[Tuple[str, str, Any]] = []
    enc_names = plan.layout.get("enc", ())
    dtypes = (plan.unpack_view.col_dtype if plan.unpack_view else {})
    for name in enc_names:
        if dtypes.get(name) == DType.INTEGRAL:
            specs.append((name, "_enc_i32", _enc_i32_lut))
        else:
            specs.append((name, "_enc_hi", _enc_hi_lut))
            specs.append((name, "_enc_lo", _enc_lo_lut))
    return specs


def _member_lut(table, col: str, kind: str, builder) -> np.ndarray:
    """One member's host LUT array (memoized per dictionary identity by
    lut_cache). Encoded kinds build from the column's ENCODING
    dictionary; string kinds from the string dictionary."""
    from deequ_tpu.ops.lut_cache import dictionary_lut

    if kind.startswith("_enc_"):
        d = table[col].encoding.dictionary
    else:
        d = table[col].dictionary
    return dictionary_lut(d, kind, builder)


def stack_luts(plan, tables: Sequence, k_bucket: int):
    """Per-tenant LUT arguments stacked to (K, L_groupmax): every
    member's LUT pads to the group max pow2 (padding rows are zeros and
    never gathered — each member's codes index below its own
    cardinality, so per-slice results equal the serial path's
    individually-padded LUTs). Padding SLICES get zero LUTs (their codes
    are all -1 → masked; gathers clamp to index 0 of a zero row, and
    the slice's result is discarded anyway). Returns (host dict,
    lut_sig)."""
    specs: Dict[str, Tuple[str, str, Any]] = {}
    for op in plan.exec_ops:
        for col, kind, builder in op.luts:
            specs.setdefault(col + "\x00" + kind, (col, kind, builder))
    for col, kind, builder in _enc_lut_specs(plan):
        specs.setdefault(col + "\x00" + kind, (col, kind, builder))

    lut_stacked: Dict[str, np.ndarray] = {}
    for key, (col, kind, builder) in sorted(specs.items()):
        per_member = [
            _member_lut(t, col, kind, builder) for t in tables
        ]
        target = 1
        while target < max(len(a) for a in per_member):
            target <<= 1
        padded = []
        for a in per_member:
            if len(a) < target:
                out = np.zeros(target, dtype=a.dtype)
                out[: len(a)] = a
                a = out
            padded.append(a)
        for _ in range(k_bucket - len(tables)):
            padded.append(np.zeros(target, dtype=padded[0].dtype))
        lut_stacked[key] = np.stack(padded)
    lut_sig = tuple(
        sorted(
            (key, tuple(int(d) for d in arr.shape), str(arr.dtype))
            for key, arr in lut_stacked.items()
        )
    )
    return lut_stacked, lut_sig


def _build_packed_program(plan, lut_keys: Tuple[str, ...], op_order=None):
    """Trace the shared single-member flat step and vmap it over the
    tenant axis — the run_scan_group program shape, built from the
    plan's metadata-only unpack view (never pinning member tables).
    ``op_order`` (round 19) traces the ops in CANONICAL order so the
    program is shareable across suites below the exact PlanKey; the
    caller permutes results back to exec order."""
    view = plan.unpack_view
    ops = (
        plan.exec_ops
        if op_order is None
        else tuple(plan.exec_ops[i] for i in op_order)
    )
    chunk = plan.key.chunk

    def single_tree(values, hi, lo, narrow_i, masks, codes, row_valid, enc, luts):
        from deequ_tpu.ops.scan_engine import _tag_identity_wrap

        col_luts: Dict[str, Dict[str, Any]] = {}
        for key, arr in luts.items():
            lcol, lkind = _split_lut_key(key)
            col_luts.setdefault(lcol, {})[lkind] = arr
        vals = view.unpack_vals(
            values, hi, lo, narrow_i, masks, codes, jnp, row_valid,
            col_luts=col_luts, enc=enc,
        )
        return tuple(
            jax.tree.map(
                _tag_identity_wrap,
                op.tags,
                _scoped_update(op, vals, row_valid, chunk),
            )
            for op in ops
        )

    def single_flat(*args):
        leaves = jax.tree.leaves(single_tree(*args))
        return jnp.concatenate(
            [jnp.ravel(leaf).astype(jnp.float64) for leaf in leaves]
        )

    return single_tree, single_flat, jax.jit(jax.vmap(single_flat))


def _unflatten_recipe(shapes):
    """Precompute the per-op slice/reshape/dtype plan for unflattening
    one member's flat f64 state vector — built once per traced program
    (alongside it in the plan's program table) instead of re-deriving
    dtype promotions per member per batch. Integer leaves widen to i64
    exactly like ``scan_engine._unflatten_partials``."""
    recipes = []
    offset = 0
    for op_shapes in shapes:
        leaves, treedef = jax.tree.flatten(op_shapes)
        specs = []
        for sd in leaves:
            size = int(np.prod(sd.shape)) if sd.shape else 1
            dtype = (
                np.int64 if np.issubdtype(sd.dtype, np.integer)
                else sd.dtype
            )
            specs.append((offset, size, sd.shape, dtype))
            offset += size
        recipes.append((specs, treedef))
    return recipes


def _unflatten_member(flat: np.ndarray, recipes) -> List[Any]:
    out = []
    for specs, treedef in recipes:
        leaves = []
        for offset, size, shape, dtype in specs:
            leaf = flat[offset:offset + size].astype(dtype)
            leaves.append(
                leaf.reshape(shape) if shape else leaf.reshape(())
            )
        out.append(jax.tree.unflatten(treedef, leaves))
    return out


def packed_lint_memo_key(plan, k_bucket: int, lut_sig, members) -> Tuple:
    """The packed program's OWN lint memo identity: tenant-axis bucket +
    per-member contract fingerprints on top of the plan fingerprint —
    a packed plan never inherits its single-tenant twin's verdict, and a
    batch with different member contracts lints fresh. The canonical op
    ordering (round 19: the traced program runs ops in shareable
    canonical order, not submission order) rides in the key too, so a
    verdict memoized against the canonical program can never be replayed
    against a differently-ordered one."""
    from deequ_tpu.serve.plan_cache import canonical_op_order

    canon, _ = canonical_op_order(getattr(plan, "exec_ops", ()))
    member_fp = tuple(
        (m.label if m.padding else "", m.variant, m.ingest_variant,
         m.encoded_columns, m.padding)
        for m in members
    )
    return ("packed", plan.key, canon, k_bucket, lut_sig, member_fp)


def run_coalesced(
    plan,
    tables: Sequence,
    labels: Sequence[str],
    plan_lint: str = "off",
    device_deadline: Optional[float] = None,
    attempt: int = 0,
    packers: Sequence = (),
) -> List[List[Any]]:
    """Execute K member tables of ``plan`` as ONE padded vmapped dispatch
    + ONE fetch. Returns per-member results lists (exec-op order, the
    shape ``run_scan`` returns), real members only — padding slices are
    computed and discarded. Raises typed ``Device*Exception`` on device
    faults (the service's tenant-axis bisection catches them) and
    ``PlanLintError`` when an armed lint rejects the packed program.

    Cache accounting (per coalesced batch): a ``plan_cache_hit`` found
    the traced program for this plan's (tenant bucket, LUT signature) —
    the batch runs with zero op builds, zero traces, zero compiles, and
    zero plan-lint traces (lint verdicts memoize under the packed key);
    a ``plan_cache_miss`` paid the one-time trace."""
    with seam("scan_attempt", coalesced=len(tables), attempt=attempt):
        return _run_coalesced(
            plan, tables, labels, plan_lint, device_deadline, attempt,
            packers,
        )


def _run_coalesced(
    plan, tables, labels, plan_lint, device_deadline, attempt, packers,
) -> List[List[Any]]:
    K = len(tables)
    assert K == len(labels) and K > 0
    if device_deadline is None:
        from deequ_tpu.ops.device_policy import default_device_deadline

        device_deadline = default_device_deadline()
    k_bucket = 1
    while k_bucket < K:
        k_bucket <<= 1

    from deequ_tpu.lint.plan_lint import enforce_plan_lint, lint_plan_cached
    from deequ_tpu.ops.scan_plan import PackedMember, plan_packed_scan

    # coalesced-batch assembly is host work worth its own seam: K tables
    # pack + stack + LUTs pad to the group max — the serving path's one
    # per-batch host cost that scales with K
    with seam("pack", what="coalesce_assembly", tenants=K, bucket=k_bucket):
        bufs = _stack_member_buffers(plan, tables, k_bucket, packers)
        lut_host, lut_sig = stack_luts(plan, tables, k_bucket)

    # plan_scan_ops with no packer (members pack host-side, fresh per
    # batch): carry the GROUP layout + encoded declaration explicitly so
    # the per-member encoded checks see the real routing
    from dataclasses import replace as _replace

    from deequ_tpu.serve.plan_cache import layout_signature

    base_ir = plan_packed_scan(plan.exec_ops, packer=None)
    enc_cols = tuple(plan.layout.get("enc", ()))
    members = [
        PackedMember(
            label=str(label),
            variant=base_ir.variant,
            ingest_variant="encoded" if enc_cols else "decoded",
            encoded_columns=enc_cols,
        )
        for label in labels
    ] + [
        PackedMember(label=f"pad[{i}]", padding=True)
        for i in range(k_bucket - K)
    ]
    plan_ir = _replace(
        base_ir,
        tenants=len(members),
        members=tuple(members),
        ingest_variant="encoded" if enc_cols else "decoded",
        encoded_columns=enc_cols,
        layout=layout_signature(plan.layout),
    )

    from deequ_tpu.serve.plan_cache import (
        SUBPLAN_CACHE,
        canonical_op_order,
        subplan_key,
    )

    # programs are traced in CANONICAL op order (round 19) so suites
    # that dedupe to the same op set — permuted submissions included —
    # share ONE traced program below the exact PlanKey; `perm` maps
    # canonical result positions back to this plan's exec order
    canon, perm = canonical_op_order(plan.exec_ops)
    sub_key = subplan_key(
        plan, canon, k_bucket, lut_sig,
        base_ir.variant, base_ir.hist_variant,
        "encoded" if enc_cols else "decoded",
    )
    if plan_lint != "off":
        # the sharing half of plan-fusion-refetch: a sub-plan key that
        # dropped an identity component would alias different programs
        from deequ_tpu.lint.plan_lint import check_subplan_key

        key_findings = check_subplan_key(sub_key)
        if key_findings:
            SCAN_STATS.plan_lints.extend(f.as_dict() for f in key_findings)
            enforce_plan_lint(key_findings, plan_lint)

    cached = plan.program_for(k_bucket, lut_sig)
    if cached is not None:
        single_flat, vstep, shapes, recipes, perm = cached
        SCAN_STATS.programs_reused += 1
        # suite-weighted ledger: every member of this batch was served
        # from the compiled-plan cache (zero builds/traces/compiles/lint
        # traces) — the hit RATE reads as "fraction of suites served
        # from cache", the serving-layer observable
        SCAN_STATS.plan_cache_hits += K
    else:
        shared = SUBPLAN_CACHE.get(sub_key)
        if shared is not None:
            # cross-suite hit: another PlanKey already traced this
            # canonical program at this (bucket, LUT) shape — adopt it
            # with our own exec-order permutation, zero traces paid
            single_flat, vstep, shapes, recipes = shared
            SCAN_STATS.programs_reused += 1
            SCAN_STATS.plan_cache_hits += K
            SCAN_STATS.record_subplan_hit(K)
            plan.put_program(
                k_bucket, lut_sig,
                (single_flat, vstep, shapes, recipes, perm),
            )
        else:
            SCAN_STATS.programs_built += 1
            SCAN_STATS.plan_cache_misses += K
            _tree, single_flat, vstep = _build_packed_program(
                plan, tuple(sorted(lut_host)), op_order=canon
            )
            shapes = device_call(
                lambda: jax.eval_shape(
                    _tree,
                    *(b[0] for b in bufs),
                    {k: v[0] for k, v in lut_host.items()},
                ),
                "trace", what="packed scan trace", deadline=device_deadline,
            )
            recipes = _unflatten_recipe(shapes)
            plan.put_program(
                k_bucket, lut_sig,
                (single_flat, vstep, shapes, recipes, perm),
            )
            SUBPLAN_CACHE.put(
                sub_key, (single_flat, vstep, shapes, recipes)
            )

    # packed plan lint BEFORE dispatch, memoized under the packed key:
    # a cache-hit batch (plan + program + lint verdict all memoized)
    # performs ZERO lint traces — the repeat-tenant contract
    if plan_lint != "off":
        # the LUTs must enter the lint trace as ARGUMENTS (abstract),
        # exactly like the build trace above passes them: closing over
        # the concrete host arrays routes encoded-column ingest through
        # numpy fancy indexing on a traced codes buffer, which raises
        # TracerArrayConversionError for any plan with encoded columns
        # (e.g. ApproxCountDistinct/DataType on strings — the profile
        # pass-1 shape)
        lut_items = sorted(lut_host.items())
        lut_keys = tuple(k for k, _ in lut_items)
        n_bufs = len(bufs)
        avals = tuple(
            jax.ShapeDtypeStruct(b.shape[1:], b.dtype) for b in bufs
        ) + tuple(
            jax.ShapeDtypeStruct(v.shape[1:], v.dtype)
            for _, v in lut_items
        )
        findings, traced = lint_plan_cached(
            plan_ir,
            lambda *a: single_flat(
                *a[:n_bufs], dict(zip(lut_keys, a[n_bufs:]))
            ),
            avals,
            packed_lint_memo_key(plan, k_bucket, lut_sig, members),
        )
        if traced:
            SCAN_STATS.plan_lint_traces += 1
        if findings:
            SCAN_STATS.plan_lints.extend(f.as_dict() for f in findings)
        enforce_plan_lint(findings, plan_lint)

    SCAN_STATS.scan_passes += 1
    SCAN_STATS.rows_scanned += sum(int(t.num_rows) for t in tables)
    SCAN_STATS.coalesced_batches += 1
    SCAN_STATS.coalesced_tenants += K
    SCAN_STATS.coalesce_padded_slots += k_bucket - K
    # kernel census per REAL member (the serial-equivalence accounting
    # run_scan_group uses; padding slices are overhead, visible via
    # coalesce_padded_slots, not kernel passes)
    from deequ_tpu.ops.scan_engine import _record_kernel_passes

    _record_kernel_passes(base_ir, K)

    # one logical scan id per coalesced dispatch — the chaos engine's
    # FaultInjectingScanHook scripts by scan id, so a scripted fault can
    # target a coalesced batch exactly like any other scan; bisection
    # retries arrive as fresh dispatches (fresh ids) with `attempt`
    # carrying the service's tenant-axis split depth
    from deequ_tpu.ops.scan_engine import _SCAN_IDS

    scan_id = next(_SCAN_IDS)
    hook_ctx = {
        "scan_id": scan_id, "attempt": attempt, "fallback": False,
        "chunk_index": 0, "device_ids": (), "coalesced": K,
    }
    with seam("stage", what="coalesced luts"):
        lut_dev = {k: jax.device_put(v) for k, v in lut_host.items()}
    device_out = device_call(
        lambda: vstep(*bufs, lut_dev),
        "execute", what=f"coalesced dispatch (K={K}/{k_bucket})",
        deadline=device_deadline, hook_ctx=hook_ctx,
    )

    # the batch's ONE round trip: its dispatch is this thread's newest
    host = device_fetch(
        device_out, "coalesced drain", device_deadline, newest=True,
    )
    SCAN_STATS.record_fetch(host.nbytes)
    out: List[List[Any]] = []
    for k in range(K):  # padding slices [K:] are discarded
        canonical = _unflatten_member(host[k], recipes)
        # the program computed ops in canonical (shareable) order;
        # callers consume exec-op order — permute back
        out.append([canonical[perm[i]] for i in range(len(canonical))])
    SCAN_STATS.chunks_processed += K
    return out
