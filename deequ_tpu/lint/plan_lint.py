"""Plan lint — static contract checking over the jaxpr of scan programs.

Every contract the engine lives by is (so far) enforced at runtime, by
counters and asserts that fire AFTER a bad program has compiled and
dispatched: the zero-sort selection contract is a bench assert over
``device_sort_passes``, the one-fetch contract an assert over
``device_fetches``, fold-order bit-identity a documented invariant. This
module is their static twin: it walks the closed jaxpr of a
``ScanPlan``-built program (``jax.make_jaxpr`` on the fused flat step,
BEFORE any dispatch) and checks the IR against the contracts the plan
*declares* (``ScanPlan.variant`` / ``fold_tags`` / ``fetch_contract`` —
ops/scan_plan.py), so planner/packer drift is caught at trace time.

Rules (ids are stable; severities per ``findings.LintFinding``):

- ``plan-select-sort`` (error) — a plan declared ``variant="select"``
  (every summary op routed through the histogram selection kernel) whose
  traced program contains a ``sort`` primitive. The runtime pair
  ``device_select_passes``/``device_sort_passes`` would catch this after
  a full bench run; the lint rejects the program before dispatch.
- ``plan-host-callback`` (error) — the traced program contains a host
  callback / infeed / outfeed primitive. Fused scan programs are
  transfer-free by construction (the one-fetch contract pays its single
  device->host fetch OUTSIDE the program, at the drain); a callback
  smuggled into the IR re-introduces per-chunk host round trips that
  ``device_fetches`` cannot even see.
- ``plan-fold-tag`` (error) — the plan's declared ``fold_tags`` disagree
  with the reduction-tag leaves actually registered on its ops, or name
  a tag outside the known monoid set. An ``add``-declared leaf whose op
  actually merges with ``max`` silently corrupts every cross-chunk and
  cross-shard merge.
- ``plan-fold-merge`` (error) — the traced merge kernel
  (``ops/df32.merge_tags_f64``, the jaxpr the device fold compiles)
  evaluated on probe values disagrees with a leaf's registered tag: the
  IR-level check that a 'sum' leaf adds, a 'min' leaf takes minima, a
  'max' leaf maxima.
- ``plan-nondet-scatter`` (warning) — a floating-point ``scatter-add``
  with ``unique_indices=False`` on a path documented bit-identical
  (docs/numerics.md): unsorted float scatter accumulation order is
  backend-dependent. Integer scatter-adds are exempt (integer addition
  is exactly associative — the selection kernel's histogram passes).
- ``plan-hist-scatter`` (error) — a plan declaring a matmul/pallas
  histogram kernel variant (``ScanPlan.hist_variant`` in
  ``("onehot", "pallas")``, ops/histogram_device.py) whose traced
  program still contains a ``scatter-add`` primitive. The histogram
  passes are the ONLY scatter-adds a fused scan program ever traces, so
  any scatter-add under a non-scatter variant means the planner's
  binding and the traced kernels drifted — the whole claimed MXU/Pallas
  win silently reverted to the scatter lowering while the per-variant
  dispatch census (ScanStats.hist_*_dispatches) still reports the
  routed tier. The runtime census would only show the lie after a bench
  run; the lint rejects the program before dispatch.
- ``plan-encoded-decode`` (error) — an encoded-ingest plan
  (``ingest_variant="encoded"``, docs/ingest.md) whose declared encoded
  column is actually routed over a pre-decoded full-width plane
  (wide/pair/hi-only/narrow) or missing from the code plane entirely —
  the 2-8x transfer/residency win silently gone while ScanStats still
  reports an encoded pass — or whose traced program contains a host
  callback (an in-program decode round trip the fused-gather contract
  forbids; re-asserted here per encoded program on top of
  ``plan-host-callback`` so the encoded rule is self-contained).
- ``plan-window-refeed`` (error) — a WINDOWED plan
  (``variant="windowed"``, the round-20 continuous-verification pane
  fold, deequ_tpu/windows) whose declared window geometry
  (``ScanPlan.window_spec`` / ``watermark_policy``), pane-bucket count
  (``tenants``) or pane fold tags are inconsistent, or whose traced
  pane program contains a host-boundary primitive. The pane fold
  advances W concurrently-open panes in ONE dispatch per batch and
  merges per-pane scalars host-side by monoid tag; a malformed
  geometry re-derives DIFFERENT pane starts on resume (the same row
  re-fed into a different pane set — silent cross-window corruption),
  a non-elementwise tag has no pane merge at all, and a callback in
  the pane program re-feeds rows through the host per batch. Also
  fires on a NON-windowed plan that declares window geometry (planner
  drift in the other direction).
- ``plan-fusion-refetch`` (error) — a FUSED multi-pass plan
  (``ScanPlan.fusion`` non-empty, the round-19 cross-pass grouping
  fusion) whose traced program produces more than one output (each
  sub-pass would materialize — fetch — separately, silently reverting
  fusion's one-fetch-for-K-passes contract while
  ``fused_group_passes`` still reports the fused census) or smuggles a
  host-boundary primitive (a per-sub-pass host round trip). The
  companion :func:`check_subplan_key` guards the cross-suite SHARED
  sub-plan cache under the same rule id: a sub-plan memo key that
  omits its layout or kernel-variant components would let tenants with
  different packer layouts or kernel tiers share one traced program.

PACKED multi-tenant plans (``ScanPlan.tenants > 0`` — the serve layer's
coalesced dispatch, deequ_tpu/serve) run the same rules PLUS a
per-member pass: each tenant slice's ``PackedMember`` declaration is
re-checked against the shared vmapped program and group layout, so
``plan-select-sort`` and ``plan-encoded-decode`` hold per slice (a
finding names the member). Packed programs memoize under their OWN key
— tenant-axis width + the member contract fingerprints on top of the
program identity — so a packed plan can never inherit the verdict of
its single-tenant twin or of a batch with different member contracts.

Results are memoized per (program identity, variant, mesh) so
enforcement costs one trace per plan/kernel-variant, not one per scan —
the engine observes actual traces via ``ScanStats.plan_lint_traces``.
"""

from __future__ import annotations

from collections import Counter, OrderedDict
from typing import Any, Callable, Dict, List, Optional, Sequence, Tuple

import numpy as np

from deequ_tpu.exceptions import PlanLintError, PlanLintWarning
from deequ_tpu.lint.findings import LintFinding

#: enforcement modes run_scan accepts (DEEQU_TPU_PLAN_LINT takes the
#: same values); "off" is the default — lint is opt-in per run/process
PLAN_LINT_MODES = ("error", "warn", "off")

#: primitives that ARE a device sort (the zero-sort contract's subject —
#: matches what ScanOp.sorts_chunk counts at runtime)
_SORT_PRIMITIVES = frozenset(("sort",))

#: primitives that cross the host boundary from inside a traced program
_CALLBACK_PRIMITIVES = frozenset(
    (
        "pure_callback",
        "io_callback",
        "debug_callback",
        "callback",
        "host_callback",
        "infeed",
        "outfeed",
    )
)

#: float-accumulating scatter primitives whose unsorted reduction order
#: is backend-dependent (scatter-min/max and integer adds are exact)
_ORDER_SENSITIVE_SCATTERS = frozenset(("scatter-add", "scatter-mul"))

#: the scatter class the histogram kernel tier replaces: every bincount
#: / segment-sum lowers to scatter-add, and nothing else in a fused
#: scan program does (scatter-min/max LUT builds and the remainder
#: compaction ``scatter`` are tiny and not histogram-shaped) — so
#: zero scatter-adds IS the static form of "the matmul/pallas variant
#: actually traced"
_HIST_SCATTER_PRIMITIVES = frozenset(("scatter-add",))

#: ScanPlan.hist_variant values that promise a scatter-free histogram
_NONSCATTER_HIST_VARIANTS = frozenset(("onehot", "pallas"))

#: probe values distinguishing the three elementwise monoid merges:
#: merge(2, 3) is 5 under sum, 2 under min, 3 under max
_MERGE_PROBES = {"sum": 5.0, "min": 2.0, "max": 3.0}


def plan_lint_mode(param: Optional[str] = None) -> str:
    """Resolve the plan-lint enforcement mode: explicit argument wins,
    then the DEEQU_TPU_PLAN_LINT env var (envcfg registry), then "off".
    Validated against PLAN_LINT_MODES (typed ValueError, like the
    select-kernel switch)."""
    from deequ_tpu.envcfg import env_value

    if param is not None:
        if param not in PLAN_LINT_MODES:
            raise ValueError(
                f"plan_lint must be one of {PLAN_LINT_MODES}, got {param!r}"
            )
        return param
    return env_value("DEEQU_TPU_PLAN_LINT")


def iter_eqns(jaxpr):
    """Yield every equation of ``jaxpr`` INCLUDING nested sub-jaxprs
    (pjit bodies, scan/while/cond branches, shard_map bodies, custom-call
    envelopes) — jnp-level code routinely wraps its primitives in a pjit
    equation, so a flat walk would see almost nothing."""
    for eqn in jaxpr.eqns:
        yield eqn
        for param in eqn.params.values():
            for sub in _subjaxprs(param):
                yield from iter_eqns(sub)


def _subjaxprs(value) -> List[Any]:
    out: List[Any] = []
    stack = [value]
    while stack:
        v = stack.pop()
        if hasattr(v, "jaxpr") and hasattr(v, "consts"):  # ClosedJaxpr
            out.append(v.jaxpr)
        elif hasattr(v, "eqns"):  # raw Jaxpr
            out.append(v)
        elif isinstance(v, (tuple, list)):
            stack.extend(v)
    return out


def primitive_census(closed_jaxpr) -> Counter:
    """Recursive primitive-name counts of a (closed) jaxpr."""
    jaxpr = getattr(closed_jaxpr, "jaxpr", closed_jaxpr)
    return Counter(eqn.primitive.name for eqn in iter_eqns(jaxpr))


def _float_unsorted_scatters(jaxpr) -> int:
    n = 0
    for eqn in iter_eqns(jaxpr):
        if eqn.primitive.name not in _ORDER_SENSITIVE_SCATTERS:
            continue
        if eqn.params.get("unique_indices", False):
            continue
        if any(
            np.issubdtype(v.aval.dtype, np.floating) for v in eqn.outvars
        ):
            n += 1
    return n


def _check_fold_tags(plan_ir) -> List[LintFinding]:
    """Declared fold tags vs the tags actually registered on the resolved
    ops — the planner metadata the executor's fold layer will obey."""
    import jax

    from deequ_tpu.ops.scan_plan import KNOWN_FOLD_TAGS

    findings: List[LintFinding] = []
    declared = plan_ir.fold_tags
    if getattr(plan_ir, "variant", None) == "windowed":
        # windowed plans declare the pane fold on an ops=() contract
        # plan (ops/scan_plan.plan_windowed_scan) — there are no
        # resolved ops to compare against; their declared tags are
        # checked by plan-window-refeed against the pane-merge monoids
        return findings
    if len(declared) != len(plan_ir.ops):
        findings.append(
            LintFinding(
                "plan-fold-tag",
                "error",
                f"plan declares fold tags for {len(declared)} ops but "
                f"resolved {len(plan_ir.ops)} ops",
            )
        )
        return findings
    for i, (op, tags) in enumerate(zip(plan_ir.ops, declared)):
        label = f"op[{i}]={op.cache_key!r}"
        actual = tuple(str(t) for t in jax.tree.leaves(op.tags))
        bad = [t for t in tags if t not in KNOWN_FOLD_TAGS]
        if bad:
            findings.append(
                LintFinding(
                    "plan-fold-tag",
                    "error",
                    f"unknown reduction tag(s) {bad} declared "
                    f"(known: {sorted(KNOWN_FOLD_TAGS)})",
                    location=label,
                )
            )
        if tags != actual:
            findings.append(
                LintFinding(
                    "plan-fold-tag",
                    "error",
                    f"declared fold tags {tags} != tags registered on the "
                    f"op {actual}: the fold layer would merge with the "
                    "declared monoid while the op computes the other — a "
                    "silent cross-chunk corruption",
                    location=label,
                )
            )
    return findings


def _check_fold_merge(plan_ir) -> List[LintFinding]:
    """Evaluate the device merge kernel per elementwise leaf tag:
    compose ``merge_tags_f64`` exactly as ``_DeviceFoldPlan`` does
    (boolean tag masks) and evaluate it on probe values — a 'sum' leaf
    must add, 'min' must take minima, 'max' maxima. Evaluated by direct
    call (semantically the traced program — the function is pure jnp),
    not via jaxpr interpretation: ``jax.core.eval_jaxpr`` is an
    internal API newer jax releases remove, and an armed lint must not
    crash on a jax upgrade."""
    import jax.numpy as jnp

    from deequ_tpu.ops.df32 import merge_tags_f64

    elem_tags = sorted(
        {
            t
            for tags in plan_ir.fold_tags
            for t in tags
            if t in _MERGE_PROBES
        }
    )
    if not elem_tags:
        return []
    is_sum = np.array([t == "sum" for t in elem_tags])
    is_min = np.array([t == "min" for t in elem_tags])
    acc = np.full(len(elem_tags), 2.0)
    new = np.full(len(elem_tags), 3.0)
    merged = np.asarray(merge_tags_f64(is_sum, is_min, acc, new, jnp))
    findings: List[LintFinding] = []
    for i, tag in enumerate(elem_tags):
        expect = _MERGE_PROBES[tag]
        if merged[i] != expect:
            findings.append(
                LintFinding(
                    "plan-fold-merge",
                    "error",
                    f"merge kernel evaluates a '{tag}' leaf to "
                    f"{merged[i]} on probe (2, 3); expected {expect} — "
                    "the compiled fold merge disagrees with the "
                    "registered monoid",
                    location=f"tag={tag}",
                )
            )
    return findings


#: the packer's pre-decoded full-width planes — an encoded column found
#: on one of these defeats the encoded-ingest contract
_DECODED_PLANES = ("wide", "pair", "narrow_i32")


def _check_encoded_ingest(plan_ir, census: Optional[Counter]) -> List[LintFinding]:
    """The ``plan-encoded-decode`` rule: declared encoded columns must
    ride the code plane (and only it), and an encoded program must be
    free of host callbacks."""
    findings: List[LintFinding] = []
    if getattr(plan_ir, "ingest_variant", "decoded") != "encoded":
        return findings
    layout = dict(plan_ir.layout or ())
    enc_plane = set(layout.get("enc", ()))
    for col in plan_ir.encoded_columns:
        on_decoded = [
            p for p in _DECODED_PLANES if col in layout.get(p, ())
        ]
        if on_decoded:
            findings.append(
                LintFinding(
                    "plan-encoded-decode",
                    "error",
                    f"encoded-variant plan routes declared encoded column "
                    f"{col!r} over pre-decoded full-width plane(s) "
                    f"{on_decoded}: the decoded values would ship to "
                    "the device while the plan claims the 2-8x encoded "
                    "form",
                    location=f"column={col}",
                )
            )
        elif col not in enc_plane:
            findings.append(
                LintFinding(
                    "plan-encoded-decode",
                    "error",
                    f"declared encoded column {col!r} is on no packer "
                    "plane at all: planner/packer drift",
                    location=f"column={col}",
                )
            )
    if census is not None:
        callbacks = {
            p: census[p] for p in _CALLBACK_PRIMITIVES if census.get(p)
        }
        if callbacks:
            findings.append(
                LintFinding(
                    "plan-encoded-decode",
                    "error",
                    f"encoded-ingest program contains host-boundary "
                    f"primitive(s) {callbacks}: decode must be a fused "
                    "on-device dictionary gather, never a host round "
                    "trip",
                )
            )
    return findings


def _check_windowed(plan_ir, census: Optional[Counter]) -> List[LintFinding]:
    """The ``plan-window-refeed`` rule: a windowed plan's declared pane
    geometry and fold tags must be internally consistent (same-geometry
    resume re-derives the SAME pane starts; every leaf has a pane
    merge), and the traced pane program must be host-callback-free —
    the fold advances every open pane in one dispatch, so a callback
    re-feeds rows through the host per batch."""
    import math

    from deequ_tpu.ops.scan_plan import KNOWN_FOLD_TAGS

    findings: List[LintFinding] = []
    spec = getattr(plan_ir, "window_spec", None)
    policy = getattr(plan_ir, "watermark_policy", None)
    if getattr(plan_ir, "variant", None) != "windowed":
        if spec is not None or policy is not None:
            findings.append(
                LintFinding(
                    "plan-window-refeed",
                    "error",
                    f"non-windowed plan (variant={plan_ir.variant!r}) "
                    f"declares window geometry (window_spec={spec!r}, "
                    f"watermark_policy={policy!r}): the executor would "
                    "route it past the pane fold while the plan claims "
                    "windowed semantics — planner drift",
                )
            )
        return findings
    panes = int(getattr(plan_ir, "tenants", 0) or 0)
    if panes < 1:
        findings.append(
            LintFinding(
                "plan-window-refeed",
                "error",
                f"windowed plan declares pane-bucket count {panes}: a "
                "pane fold needs at least one concurrently-open pane "
                "slot (ScanPlan.tenants doubles as the bucket width)",
            )
        )
    if not (isinstance(spec, tuple) and len(spec) == 3):
        findings.append(
            LintFinding(
                "plan-window-refeed",
                "error",
                f"windowed plan declares malformed window_spec {spec!r}: "
                "expected the (size_s, slide_s, time_column) signature "
                "of windows/spec.WindowSpec",
            )
        )
    else:
        size_s, slide_s = float(spec[0]), float(spec[1])
        if not (
            math.isfinite(size_s)
            and math.isfinite(slide_s)
            and 0.0 < slide_s <= size_s
        ):
            findings.append(
                LintFinding(
                    "plan-window-refeed",
                    "error",
                    f"windowed plan declares window geometry size_s="
                    f"{size_s!r} slide_s={slide_s!r}: pane starts are "
                    "re-derived from this geometry on every batch AND on "
                    "resume, so it must satisfy 0 < slide <= size (finite) "
                    "or the same row re-feeds into a different pane set",
                )
            )
    if not (isinstance(policy, tuple) and len(policy) == 2):
        findings.append(
            LintFinding(
                "plan-window-refeed",
                "error",
                f"windowed plan declares malformed watermark_policy "
                f"{policy!r}: expected the (lag_s, late_policy) signature "
                "of windows/spec.WatermarkPolicy",
            )
        )
    else:
        from deequ_tpu.windows.spec import LATE_POLICIES

        lag_s, late_policy = policy
        if not (math.isfinite(float(lag_s)) and float(lag_s) >= 0.0):
            findings.append(
                LintFinding(
                    "plan-window-refeed",
                    "error",
                    f"windowed plan declares watermark lag {lag_s!r}: the "
                    "close fence must advance monotonically, which needs "
                    "a finite non-negative lag",
                )
            )
        if late_policy not in LATE_POLICIES:
            findings.append(
                LintFinding(
                    "plan-window-refeed",
                    "error",
                    f"windowed plan declares late policy {late_policy!r} "
                    f"(known: {LATE_POLICIES}): late rows would route "
                    "through no typed path at all",
                )
            )
    for tags in plan_ir.fold_tags:
        bad = [t for t in tags if t not in KNOWN_FOLD_TAGS]
        if bad:
            findings.append(
                LintFinding(
                    "plan-window-refeed",
                    "error",
                    f"windowed plan declares unknown pane fold tag(s) "
                    f"{bad} (known: {sorted(KNOWN_FOLD_TAGS)})",
                )
            )
        nonelem = [
            t for t in tags if t in KNOWN_FOLD_TAGS and t not in _MERGE_PROBES
        ]
        if nonelem:
            findings.append(
                LintFinding(
                    "plan-window-refeed",
                    "error",
                    f"windowed plan declares non-elementwise pane fold "
                    f"tag(s) {nonelem}: the pane fold merges per-pane "
                    "scalars by elementwise monoid "
                    f"({sorted(_MERGE_PROBES)}); a gather-class leaf has "
                    "no pane merge and would silently drop state at the "
                    "checkpoint boundary",
                )
            )
    if census is not None:
        callbacks = {
            p: census[p] for p in _CALLBACK_PRIMITIVES if census.get(p)
        }
        if callbacks:
            findings.append(
                LintFinding(
                    "plan-window-refeed",
                    "error",
                    f"windowed pane program contains host-boundary "
                    f"primitive(s) {callbacks}: the pane fold advances "
                    "every open pane in ONE transfer-free dispatch per "
                    "batch — a callback re-feeds rows through the host "
                    "per batch (re-asserted here per windowed program on "
                    "top of plan-host-callback so the windowed rule is "
                    "self-contained)",
                )
            )
    return findings


def _check_packed_members(plan_ir, census: Optional[Counter]) -> List[LintFinding]:
    """Per-tenant-slice contract checks for a PACKED multi-tenant plan
    (``ScanPlan.tenants > 0``, deequ_tpu/serve): every member shares ONE
    vmapped program and ONE packer layout, so each member's DECLARED
    contracts (``PackedMember``) are re-checked against that shared
    reality — a sort primitive in the program while any member declares
    the selection contract, or a member's declared encoded column riding
    a pre-decoded plane of the group layout, is that member's violation
    (location names the slice). Padding slots (all-invalid dummy slices)
    declare nothing and are skipped."""
    findings: List[LintFinding] = []
    members = getattr(plan_ir, "members", ()) or ()
    if not members:
        return findings
    layout = dict(plan_ir.layout or ())
    enc_plane = set(layout.get("enc", ()))
    sorts = (
        sum(census.get(p, 0) for p in _SORT_PRIMITIVES)
        if census is not None
        else 0
    )
    for k, m in enumerate(members):
        if getattr(m, "padding", False):
            continue
        where = f"member[{k}]={m.label}"
        if m.variant == "select" and sorts:
            findings.append(
                LintFinding(
                    "plan-select-sort",
                    "error",
                    f"packed tenant slice declares the selection contract "
                    f"but the SHARED vmapped program contains {sorts} sort "
                    "primitive(s): the zero-sort contract is violated for "
                    "this member before dispatch",
                    location=where,
                )
            )
        if m.ingest_variant == "encoded":
            for col in m.encoded_columns:
                on_decoded = [
                    p for p in _DECODED_PLANES if col in layout.get(p, ())
                ]
                if on_decoded:
                    findings.append(
                        LintFinding(
                            "plan-encoded-decode",
                            "error",
                            f"packed tenant slice declares encoded column "
                            f"{col!r} but the GROUP layout routes it over "
                            f"pre-decoded plane(s) {on_decoded}: this "
                            "member's decoded values would ship while its "
                            "plan claims the encoded form",
                            location=f"{where} column={col}",
                        )
                    )
                elif col not in enc_plane:
                    findings.append(
                        LintFinding(
                            "plan-encoded-decode",
                            "error",
                            f"packed tenant slice declares encoded column "
                            f"{col!r} which is on no plane of the group "
                            "layout: coalescer/packer drift",
                            location=f"{where} column={col}",
                        )
                    )
    return findings


def lint_plan(
    plan_ir,
    trace_fn: Optional[Callable] = None,
    avals: Sequence[Any] = (),
) -> List[LintFinding]:
    """Run every plan-lint rule against ``plan_ir`` (a
    ``ops/scan_plan.ScanPlan``) and, when ``trace_fn`` is given, the
    jaxpr of ``trace_fn(*avals)`` — the fused flat step the executor
    will jit. Returns the findings, errors first; empty means the
    program satisfies every declared contract. Packed multi-tenant
    plans (``tenants > 0``) additionally re-check each member slice's
    declared contracts against the shared program/layout
    (:func:`_check_packed_members`)."""
    import jax

    findings: List[LintFinding] = []
    findings += _check_fold_tags(plan_ir)
    # a corrupt tag declaration makes the merge probe meaningless — and
    # the probe would crash on an unknown tag before reporting cleanly
    if not findings:
        findings += _check_fold_merge(plan_ir)
    if trace_fn is None:
        # layout-only encoded checks still run without a traced program
        findings += _check_encoded_ingest(plan_ir, None)
        findings += _check_packed_members(plan_ir, None)
        findings += _check_windowed(plan_ir, None)

    if trace_fn is not None:
        closed = jax.make_jaxpr(trace_fn)(*avals)
        census = primitive_census(closed)
        findings += _check_encoded_ingest(plan_ir, census)
        findings += _check_packed_members(plan_ir, census)
        findings += _check_windowed(plan_ir, census)
        sorts = sum(census.get(p, 0) for p in _SORT_PRIMITIVES)
        if plan_ir.variant == "select" and sorts:
            findings.append(
                LintFinding(
                    "plan-select-sort",
                    "error",
                    f"selection-variant plan traces to a program with "
                    f"{sorts} sort primitive(s): the zero-sort contract "
                    "(device_sort_passes == 0 on the resident selection "
                    "path) is violated before dispatch",
                )
            )
        callbacks = {
            p: census[p] for p in _CALLBACK_PRIMITIVES if census.get(p)
        }
        if callbacks:
            findings.append(
                LintFinding(
                    "plan-host-callback",
                    "error",
                    f"scan program contains host-boundary primitive(s) "
                    f"{callbacks}: fused programs must be transfer-free "
                    f"(fetch contract: {plan_ir.fetch_contract}; the one "
                    "fetch happens at the drain, outside the program)",
                )
            )
        hist_variant = getattr(plan_ir, "hist_variant", "none")
        if hist_variant in _NONSCATTER_HIST_VARIANTS:
            hist_scatters = sum(
                census.get(p, 0) for p in _HIST_SCATTER_PRIMITIVES
            )
            if hist_scatters:
                findings.append(
                    LintFinding(
                        "plan-hist-scatter",
                        "error",
                        f"plan declares the {hist_variant!r} histogram "
                        f"kernel variant but its traced program contains "
                        f"{hist_scatters} scatter-add primitive(s): the "
                        "bincount passes reverted to the XLA scatter "
                        "lowering while the plan (and the per-variant "
                        "dispatch census) claim the matmul/pallas tier — "
                        "planner binding drift, rejected before dispatch",
                    )
                )
        fusion = getattr(plan_ir, "fusion", ()) or ()
        if fusion:
            outs = len(closed.jaxpr.outvars)
            if outs != 1:
                findings.append(
                    LintFinding(
                        "plan-fusion-refetch",
                        "error",
                        f"fused {len(fusion)}-pass plan traces to a "
                        f"program with {outs} outputs: each sub-pass "
                        "would materialize (fetch) separately — fusion's "
                        "one-fetch contract requires ONE concatenated "
                        "counts output for all sub-passes",
                    )
                )
            if callbacks:
                findings.append(
                    LintFinding(
                        "plan-fusion-refetch",
                        "error",
                        f"fused multi-pass program contains host-boundary "
                        f"primitive(s) {callbacks}: a per-sub-pass host "
                        "round trip defeats the single fused dispatch",
                    )
                )
        nondet = _float_unsorted_scatters(closed.jaxpr)
        if nondet:
            findings.append(
                LintFinding(
                    "plan-nondet-scatter",
                    "warning",
                    f"{nondet} floating-point scatter-add(s) with "
                    "unsorted, non-unique indices: accumulation order is "
                    "backend-dependent on a path documented bit-identical "
                    "(docs/numerics.md, fold order and determinism)",
                )
            )
    findings.sort(key=lambda f: (f.severity != "error", f.rule))
    return findings


#: the components a cross-suite sub-plan cache key must carry: dropping
#: any of them would let suites with different packer layouts / kernel
#: tiers / ingest routing share one traced program
_SUBPLAN_KEY_FIELDS = ("ops_sig", "layout_sig", "variant", "hist_variant",
                       "ingest_variant")


def check_subplan_key(key) -> List[LintFinding]:
    """The shared-sub-plan half of ``plan-fusion-refetch``: validate
    that a cross-suite sub-plan cache key (serve/plan_cache.SubPlanKey)
    carries every identity component. A key whose layout or variant
    field is empty/None would hash suites with DIFFERENT packer layouts
    or kernel variants onto the same traced program — the packed twin
    of serving a sort-path program to a selection-path scan. Called by
    the serve executor before a shared sub-plan is admitted (when lint
    is armed) and by the drift sims."""
    missing = [
        f for f in _SUBPLAN_KEY_FIELDS if not getattr(key, f, None)
    ]
    if not missing:
        return []
    return [
        LintFinding(
            "plan-fusion-refetch",
            "error",
            f"shared sub-plan cache key omits identity component(s) "
            f"{missing}: suites with different layouts/kernel variants "
            "would share one traced program",
        )
    ]


# -- memoization --------------------------------------------------------
#
# one lint trace per (program identity, variant, mesh, backend), mirroring
# the executor's program caches: repeated scans of an identical plan pay
# a dict lookup, not a retrace. Bounded like _GLOBAL_PROGRAMS.

_MEMO_CAP = 256
_LINT_MEMO: "OrderedDict[Any, Tuple[LintFinding, ...]]" = OrderedDict()


def lint_plan_cached(
    plan_ir,
    trace_fn: Optional[Callable],
    avals: Sequence[Any],
    memo_key: Any,
) -> Tuple[List[LintFinding], bool]:
    """Memoizing wrapper around :func:`lint_plan`. Returns
    ``(findings, traced)`` — ``traced`` is False on a memo hit (the
    observable behind ``ScanStats.plan_lint_traces`` and the bench
    memoization assert). ``memo_key=None`` disables memoization (plans
    whose ops opted out of program caching re-lint per scan)."""
    if memo_key is not None:
        cached = _LINT_MEMO.get(memo_key)
        if cached is not None:
            _LINT_MEMO.move_to_end(memo_key)
            return list(cached), False
    findings = lint_plan(plan_ir, trace_fn, avals)
    if memo_key is not None:
        _LINT_MEMO[memo_key] = tuple(findings)
        while len(_LINT_MEMO) > _MEMO_CAP:
            _LINT_MEMO.popitem(last=False)
    return findings, True


def clear_lint_memo() -> None:
    """Drop every memoized lint result (tests; also the right response
    to hot-swapping op update fns in a long-lived process)."""
    _LINT_MEMO.clear()


def enforce_plan_lint(
    findings: Sequence[LintFinding], mode: str
) -> None:
    """Apply an enforcement mode to a finding list: ``"error"`` raises
    ``PlanLintError`` on the first error-severity finding (warnings still
    warn), ``"warn"`` warns for everything, ``"off"`` is a no-op. Always
    call BEFORE dispatch — the whole point is rejecting the program while
    it is still just IR."""
    import warnings

    if mode == "off" or not findings:
        return
    errors = [f for f in findings if f.severity == "error"]
    warnings_only = [f for f in findings if f.severity != "error"]
    for f in warnings_only:
        warnings.warn(str(f), PlanLintWarning, stacklevel=3)
    if not errors:
        return
    if mode == "error":
        raise PlanLintError(
            "plan lint rejected the scan program before dispatch:\n"
            + "\n".join(str(f) for f in errors),
            findings=findings,
        )
    for f in errors:
        warnings.warn(str(f), PlanLintWarning, stacklevel=3)
