"""Repo lint — an AST pass enforcing the codebase conventions the engine
PRs established by hand (``python -m deequ_tpu.lint``).

The conventions are load-bearing: device->host transfers must be
accounted at ``record_fetch`` boundaries or the one-fetch contract's
observable lies; raw ``except Exception`` around device seams swallows
the XLA faults ``classify_device_error`` exists to type; wall-clock/RNG
inside traced code bakes a trace-time value into a cached program (the
peer-probe barrier-tag bug of PR 5 was exactly this class); untyped
raises inside the engine bypass the exception taxonomy callers dispatch
on.

Rules (stable ids; all severity "error" — the repo pass is a CI gate):

- ``host-fetch`` — device->host materialization shapes in the
  device-adjacent modules (``ops/``, ``parallel/``, ``anomaly/``)
  outside a fetch-accounting boundary: ``np.asarray(...)`` /
  ``np.array(...)`` / ``jax.device_get(...)`` / ``.item()`` /
  ``.tolist()``, plus ``float(...)``/``int(...)`` of a ``jax``/``jnp``-
  rooted expression and ITERATION over one (``for x in jnp.f(...)``
  transfers per element — the Holt-Winters fit bug class). The
  enclosing function (or an enclosing function of it) must reference
  ``record_fetch`` / ``_record_fetch`` / ``device_fetches`` /
  ``bytes_fetched``, i.e. the materialization is charged to the
  one-fetch telemetry. Local aliases escape (``s = jnp.f(x);
  float(s)``) — the rule is a convention checker, not dataflow
  analysis.
- ``bare-except`` — ``except Exception:`` / bare ``except:`` in
  ``ops/``, ``parallel/``, ``resilience/`` whose handler neither
  references ``classify_device_error`` nor re-raises: a swallow at a
  transfer/trace/execute seam turns a typed device fault into silence.
- ``jit-impure`` — wall-clock (``time.time``/``monotonic``/…,
  ``datetime.now``) or host RNG (``random.*``, ``np.random.*`` —
  ``jax.random`` is keyed and exempt) inside a function that is jitted
  or traced (decorated with / passed to ``jax.jit``, ``vmap``,
  ``shard_map``, ``lax.scan``, ``grad``/``value_and_grad``,
  ``eval_shape``, ``make_jaxpr``, including module-local transitive
  callees): the value is baked at trace time and replayed from the
  program cache.
- ``typed-raise`` — ``raise Exception(...)`` / ``raise
  RuntimeError(...)`` / ``raise BaseException(...)`` in ``ops/`` or
  ``resilience/``: failures inside the engine must use the
  ``deequ_tpu.exceptions`` taxonomy (or a precise builtin like
  ``ValueError`` for argument validation), never the generic classes the
  fault ladder cannot dispatch on.
- ``span-in-jit`` — seam or flight-recorder emission (``seam(...)``,
  ``<recorder>.span(...)`` / ``.event(...)`` / ``.record_span(...)``,
  ``current_recorder()``, ``recording_scope(...)``) inside a function
  that is jitted or traced
  (the same traced-function set ``jit-impure`` computes): a span
  emitted from traced code is a host callback by another name — it
  bakes one trace-time record into the cached program and re-fires (or
  worse, doesn't) on every replay, exactly the ``jit-impure`` failure
  class. Seams belong at the HOST boundaries around the program
  (``device_call``, the packing loops), never inside it; what names the
  device side is ``jax.named_scope`` (metadata only, never flagged).
- ``durable-write`` — raw durable-write shapes in ``serve/``,
  ``repository/``, ``control/``, ``resilience/``: ``open(..., "w"/"wb")``
  (any write-mode open, builtin or ``fs.open``), ``os.fsync(...)``, and
  ``os.rename``/``os.replace``. Durable state must route through the
  shared atomic helper (``resilience/atomic.py``'s
  ``atomic_write_bytes``: temp + fsync + rename under the checksum
  envelope) so every store gets the same torn-write recovery story; the
  legitimate exceptions (the helper's own internals, append-only
  ledgers, forensic ``.corrupt`` sidecars) carry annotated ignores with
  reasons.
- ``suppress-reason`` — a ``# deequ-lint: ignore[rule]`` suppression
  without a reason. Suppressions are triage records; a bare one is a
  finding itself AND grants no suppression (the underlying finding
  still reports, so ``--rules`` subset runs cannot be silenced by an
  invalid annotation).

Suppression syntax (same line as the finding, or a standalone comment on
the line directly above)::

    flat = np.asarray(vec)  # deequ-lint: ignore[host-fetch] -- host list input

The reason after ``--`` is REQUIRED.
"""

from __future__ import annotations

import ast
import os
import re
from typing import Dict, Iterable, List, Optional, Sequence, Set, Tuple

from deequ_tpu.lint.findings import LintFinding

#: rule id -> package-relative path prefixes it applies to ("" = whole
#: package). Paths use "/" regardless of platform.
RULE_SCOPES: Dict[str, Tuple[str, ...]] = {
    # serve/ is device-adjacent (round 10): its coalesced executor
    # fetches and its worker loop wraps device seams, so the host-fetch
    # accounting and typed-raise disciplines apply there in full.
    # obs/ joins the same three scopes in round 11: the flight recorder
    # sits beside every device seam, and an accidental fetch or
    # swallowed fault in the observability layer would be the least
    # observable bug of all.
    # The round-12 fleet modules (serve/fleet.py, serve/membership.py,
    # serve/router.py) ride the existing serve/ prefix: failover and
    # membership code wraps the same device-adjacent seams, so the
    # host-fetch / bare-except / typed-raise disciplines apply there
    # unchanged — a swallowed WorkerLostException would strand futures.
    # repository/ joins all three in round 13: the columnar backend's
    # query path dispatches real engine scans (host-fetch accounting
    # applies), its segment recovery must surface CorruptStateException
    # typed rather than swallow it, and its append/compaction code sits
    # on the same atomic-persistence seams as resilience/.
    # The round-14 histogram kernel tier (ops/histogram_device.py) rides
    # the existing ops/ prefix in every scope: its dispatcher sits
    # directly on traced device seams, so the host-fetch / bare-except /
    # typed-raise disciplines apply in full — a swallowed availability
    # probe there would silently reroute every histogram to scatter.
    # The round-15 overload tier (serve/admission.py) rides the serve/
    # prefix unchanged: admission refusals and deadline sheds MUST stay
    # typed (a bare except around a shed would orphan the future it was
    # about to resolve), so all three disciplines apply in full.
    # Round 16 widens all three scopes to profiles/, suggestions/, and
    # the new control/: the profiler now emits its passes through the
    # serving seam (host-fetch accounting applies to its pass plumbing),
    # the control plane's registry persists lifecycle state on the same
    # atomic seams as resilience/ (a swallowed CorruptStateException
    # would silently double promotion events), and its typed lifecycle /
    # shed handling must never degrade to untyped raises.
    # Round 20 adds windows/ to host-fetch, bare-except, typed-raise and
    # durable-write: the pane-fold engine fetches per-pane leaves from
    # the device every batch (accounting applies in full), late-data
    # routing and window sheds MUST stay typed (an untyped raise where
    # LateDataException belongs silently changes a stream's policy), and
    # the window-state store persists the exactly-once close fence on
    # the same atomic seams the crashpoint matrix exercises.
    "host-fetch": (
        "ops/", "parallel/", "anomaly/", "serve/", "obs/", "repository/",
        "profiles/", "suggestions/", "control/", "windows/",
    ),
    "bare-except": (
        "ops/", "parallel/", "resilience/", "serve/", "obs/", "repository/",
        "profiles/", "suggestions/", "control/", "windows/",
    ),
    "jit-impure": ("",),
    "typed-raise": (
        "ops/", "resilience/", "serve/", "obs/", "repository/",
        "profiles/", "suggestions/", "control/", "windows/",
    ),
    "span-in-jit": ("",),
    # PR 18: every module that persists durable state (the fleet ledger
    # and lease, repository segments, the control-plane registry,
    # checkpoint/chaos/atomic code itself) must write through the shared
    # atomic temp+fsync+rename helper — a hand-rolled open("wb") there
    # is a torn-write hazard the crashpoint matrix cannot vouch for.
    "durable-write": (
        "serve/", "repository/", "control/", "resilience/", "windows/",
    ),
    "suppress-reason": ("",),
}

#: names whose presence marks an enclosing function as a fetch-accounting
#: boundary for the host-fetch rule. Deliberately NOT extended with the
#: round-8 staging ledger (``record_staged``/``bytes_staged``): staging
#: moves bytes HOST->DEVICE via ``jax.device_put``, which matches none of
#: the fetch shapes, so no carve-out is needed — and adding one would
#: exempt the entire scan-loop functions (the code most likely to grow
#: an accidental fetch) from this rule. Host-side dictionary work inside
#: staging code uses per-line ``deequ-lint: ignore`` annotations instead.
_FETCH_BOUNDARY_NAMES = frozenset(
    ("record_fetch", "_record_fetch", "device_fetches", "bytes_fetched")
)

#: transform entry points whose function arguments become traced code
_TRACING_CALLS = frozenset(
    (
        "jit",
        "vmap",
        "pmap",
        "shard_map",
        "scan",
        "while_loop",
        "fori_loop",
        "cond",
        "switch",
        "grad",
        "value_and_grad",
        "eval_shape",
        "make_jaxpr",
        "checkpoint",
        "remat",
        "custom_jvp",
        "custom_vjp",
    )
)

_WALLCLOCK_ATTRS = frozenset(
    (
        "time",
        "time_ns",
        "monotonic",
        "monotonic_ns",
        "perf_counter",
        "perf_counter_ns",
        "process_time",
        "process_time_ns",
        "now",
        "utcnow",
    )
)
_WALLCLOCK_BASES = frozenset(("time", "_time", "datetime", "dt"))

#: receivers a dotted tracing call must hang off — `scanner.scan(cb)` or
#: `checkpointer.checkpoint(fn)` are ordinary method calls, not traces;
#: bare names (`jit(f)`, `shard_map(f, ...)` — the from-import idiom)
#: stay matched by name alone
_TRACING_BASES = frozenset(("jax", "lax", "jnp"))


def _is_tracing_ref(parts: List[str]) -> bool:
    if not parts or parts[-1] not in _TRACING_CALLS:
        return False
    return len(parts) == 1 or parts[0] in _TRACING_BASES

_GENERIC_RAISES = frozenset(("Exception", "RuntimeError", "BaseException"))

#: emission shapes for the span-in-jit rule: ``seam(...)`` (the one
#: duration primitive), attribute calls any recorder object exposes
#: (``rec.span`` / ``.event`` / ``.record_span``) and the
#: ambient-arming module functions. Like
#: host-fetch, a convention checker over names — an unrelated
#: ``.event()`` method on another object inside traced code would
#: false-positive and takes a per-line annotated ignore.
_SPAN_EMIT_ATTRS = frozenset(("span", "event", "record_span"))
_SPAN_EMIT_FNS = frozenset(
    ("seam", "worker_seams", "current_recorder", "recording_scope",
     "maybe_arm_from_env")
)


def _span_emission(parts: List[str]) -> Optional[str]:
    """A human label when the dotted call is a flight-recorder emission
    shape, else None."""
    if not parts:
        return None
    if parts[-1] in _SPAN_EMIT_FNS:
        return f"{parts[-1]}(...)"
    if len(parts) > 1 and parts[-1] in _SPAN_EMIT_ATTRS:
        return f"<recorder>.{parts[-1]}(...)"
    return None

_SUPPRESS_RE = re.compile(
    r"#\s*deequ-lint:\s*ignore\[([a-z0-9_,\s-]+)\]\s*(?:(?:--|—)\s*(\S.*))?"
)


#: jax.* namespaces that return HOST values (pytree utilities, device
#: handles, shape-only tracing) — iterating or float()-ing these is not
#: a device->host transfer
_JAX_HOST_NAMESPACES = frozenset(
    (
        "tree",
        "tree_util",
        "devices",
        "local_devices",
        "device_count",
        "local_device_count",
        "process_count",
        "process_index",
        "sharding",
        "ShapeDtypeStruct",
        "eval_shape",
        "make_jaxpr",
    )
)


def _device_expr(node: ast.AST) -> bool:
    """True when the expression is rooted in a device-array-producing
    jax/jnp call chain: ``jnp.sort(x)``, ``jax.nn.sigmoid(p)[0]`` —
    but NOT host-side jax utilities (``jax.tree.leaves(...)``,
    ``jax.devices()``, ``jax.eval_shape(...)``)."""
    while isinstance(node, ast.Subscript):
        node = node.value
    parts = _dotted(node.func) if isinstance(node, ast.Call) else _dotted(node)
    if not parts:
        return False
    if parts[0] == "jnp":
        return True
    if parts[0] == "jax":
        return len(parts) < 2 or parts[1] not in _JAX_HOST_NAMESPACES
    return False


def _dotted(node: ast.AST) -> List[str]:
    """['np', 'random', 'seed'] for np.random.seed — empty when the
    expression is not a plain dotted name."""
    parts: List[str] = []
    while isinstance(node, ast.Attribute):
        parts.append(node.attr)
        node = node.value
    if isinstance(node, ast.Name):
        parts.append(node.id)
        return parts[::-1]
    return []


def _names_in(node: ast.AST) -> Set[str]:
    out: Set[str] = set()
    for sub in ast.walk(node):
        if isinstance(sub, ast.Name):
            out.add(sub.id)
        elif isinstance(sub, ast.Attribute):
            out.add(sub.attr)
    return out


class _Suppressions:
    """Per-file map of ``# deequ-lint: ignore[...]`` comments. Scanned
    from real COMMENT tokens (not raw lines), so the suppression syntax
    can be *mentioned* in docstrings — like this module's rule catalog —
    without registering."""

    def __init__(self, source: str):
        import io
        import tokenize

        # line number (1-based) -> (rule ids, has_reason, standalone)
        self.by_line: Dict[int, Tuple[Set[str], bool, bool]] = {}
        try:
            tokens = list(tokenize.generate_tokens(io.StringIO(source).readline))
        except (tokenize.TokenError, IndentationError):
            return  # ast.parse will have raised already for real breakage
        for tok in tokens:
            if tok.type != tokenize.COMMENT:
                continue
            m = _SUPPRESS_RE.search(tok.string)
            if not m:
                continue
            line = tok.start[0]
            rules = {r.strip() for r in m.group(1).split(",") if r.strip()}
            has_reason = bool(m.group(2))
            standalone = tok.line.strip().startswith("#")
            self.by_line[line] = (rules, has_reason, standalone)

    def suppressed(self, rule: str, line: int) -> bool:
        for cand in (line, line - 1):
            entry = self.by_line.get(cand)
            if entry is None:
                continue
            rules, has_reason, standalone = entry
            if cand == line - 1 and not standalone:
                continue  # a trailing comment annotates ITS line only
            # a reason-less suppression is INVALID and grants nothing:
            # otherwise `--rules <rule>` subset runs would hide both the
            # violation and the missing-reason finding and exit 0
            if rule in rules and has_reason:
                return True
        return False

    def missing_reasons(self) -> List[int]:
        return [
            line
            for line, (_, has_reason, _) in sorted(self.by_line.items())
            if not has_reason
        ]


class _FunctionIndex(ast.NodeVisitor):
    """Function defs + the metadata the rules need: enclosing chains,
    fetch-boundary membership, traced-function set."""

    def __init__(self, tree: ast.Module):
        self.defs: List[ast.AST] = []
        self.parents: Dict[ast.AST, Optional[ast.AST]] = {}
        self._stack: List[ast.AST] = []
        # node -> innermost enclosing function def (None at module level)
        self.enclosing: Dict[ast.AST, Optional[ast.AST]] = {}
        self.visit(tree)
        self._boundary_cache: Dict[ast.AST, bool] = {}

    def generic_visit(self, node):
        self.enclosing[node] = self._stack[-1] if self._stack else None
        if isinstance(node, (ast.FunctionDef, ast.AsyncFunctionDef)):
            self.defs.append(node)
            self.parents[node] = self._stack[-1] if self._stack else None
            self._stack.append(node)
            super().generic_visit(node)
            self._stack.pop()
        else:
            super().generic_visit(node)

    def chain(self, node: ast.AST) -> Iterable[ast.AST]:
        fn = self.enclosing.get(node)
        while fn is not None:
            yield fn
            fn = self.parents.get(fn)

    def in_fetch_boundary(self, node: ast.AST) -> bool:
        for fn in self.chain(node):
            hit = self._boundary_cache.get(fn)
            if hit is None:
                hit = bool(_names_in(fn) & _FETCH_BOUNDARY_NAMES)
                self._boundary_cache[fn] = hit
            if hit:
                return True
        return False


def _traced_function_names(tree: ast.Module) -> Set[str]:
    """Names of module functions that become traced/jitted code:
    decorated with a tracing transform, passed as an argument to one, or
    (transitively) called from such a function within this module."""
    local_defs: Dict[str, ast.AST] = {}
    for node in ast.walk(tree):
        if isinstance(node, (ast.FunctionDef, ast.AsyncFunctionDef)):
            local_defs[node.name] = node

    traced: Set[str] = set()

    def _is_tracing_callable(expr: ast.AST) -> bool:
        if _is_tracing_ref(_dotted(expr)):
            return True
        # partial(jax.jit, ...) used as a decorator factory
        if isinstance(expr, ast.Call):
            inner = _dotted(expr.func)
            if inner and inner[-1] == "partial" and expr.args:
                return _is_tracing_callable(expr.args[0])
            return _is_tracing_callable(expr.func)
        return False

    for node in ast.walk(tree):
        if isinstance(node, (ast.FunctionDef, ast.AsyncFunctionDef)):
            if any(_is_tracing_callable(d) for d in node.decorator_list):
                traced.add(node.name)
        elif isinstance(node, ast.Call):
            if not _is_tracing_ref(_dotted(node.func)):
                continue
            for arg in list(node.args) + [kw.value for kw in node.keywords]:
                argparts = _dotted(arg)
                if argparts and argparts[-1] in local_defs:
                    traced.add(argparts[-1])

    # transitive: a traced function's module-local callees are traced too
    changed = True
    while changed:
        changed = False
        for name in list(traced):
            fn = local_defs.get(name)
            if fn is None:
                continue
            for sub in ast.walk(fn):
                if isinstance(sub, ast.Call):
                    parts = _dotted(sub.func)
                    if (
                        parts
                        and parts[-1] in local_defs
                        and parts[-1] not in traced
                    ):
                        traced.add(parts[-1])
                        changed = True
    return traced


def _impure_call(parts: List[str]) -> Optional[str]:
    """'wall-clock' / 'rng' when the dotted call is impure inside traced
    code, else None."""
    if not parts:
        return None
    if (
        parts[-1] in _WALLCLOCK_ATTRS
        and parts[0] in _WALLCLOCK_BASES
        and len(parts) > 1
    ):
        return "wall-clock"
    if "random" in parts[:-1] and parts[0] not in ("jax", "jrandom"):
        return "rng"
    if parts[0] == "random" and len(parts) > 1:
        return "rng"
    return None


def lint_source(
    source: str,
    rel_path: str,
    rules: Optional[Sequence[str]] = None,
) -> List[LintFinding]:
    """Lint one module's source. ``rel_path`` is the path RELATIVE to the
    package root (e.g. ``"ops/scan_engine.py"``) — it selects which rules
    apply via RULE_SCOPES. Findings carry ``rel_path:line`` locations."""
    active = set(rules) if rules is not None else set(RULE_SCOPES)
    rel = rel_path.replace(os.sep, "/")

    def in_scope(rule: str) -> bool:
        return rule in active and any(
            rel.startswith(p) or p == "" for p in RULE_SCOPES[rule]
        )

    tree = ast.parse(source, filename=rel)
    sup = _Suppressions(source)
    findings: List[LintFinding] = []

    def add(rule: str, node: ast.AST, message: str) -> None:
        line = getattr(node, "lineno", 0)
        if sup.suppressed(rule, line):
            return
        findings.append(
            LintFinding(rule, "error", message, location=f"{rel}:{line}")
        )

    index = _FunctionIndex(tree) if in_scope("host-fetch") else None

    # -- host-fetch ------------------------------------------------------
    if index is not None:
        def _fetch_shape(node: ast.AST) -> Optional[str]:
            """A human label when ``node`` is a device->host
            materialization shape, else None."""
            if isinstance(node, ast.Call):
                parts = _dotted(node.func)
                if (
                    parts[-2:] in (["np", "asarray"], ["numpy", "asarray"])
                    or parts[-2:] in (["np", "array"], ["numpy", "array"])
                    or parts[-2:] == ["jax", "device_get"]
                ):
                    return ".".join(parts) + "()"
                if isinstance(node.func, ast.Attribute) and not node.args:
                    if node.func.attr in ("item", "tolist"):
                        return f"<expr>.{node.func.attr}()"
                # float(jnp.f(x)) / int(jax.g(y)[0]): the conversion IS
                # the fetch when the argument is device-rooted
                if (
                    isinstance(node.func, ast.Name)
                    and node.func.id in ("float", "int")
                    and len(node.args) == 1
                    and _device_expr(node.args[0])
                ):
                    return f"{node.func.id}(<device expr>)"
                return None
            # iterating a device array transfers per element — the
            # `[float(x) for x in jax.nn.sigmoid(p)]` bug class
            if isinstance(node, (ast.comprehension, ast.For)):
                if _device_expr(node.iter):
                    return "iteration over <device expr>"
            return None

        for node in ast.walk(tree):
            what = _fetch_shape(node)
            if what is None:
                continue
            # comprehension clauses carry no lineno of their own —
            # anchor the finding (and its suppression) on the iterable
            anchor = (
                node.iter
                if isinstance(node, (ast.comprehension, ast.For))
                else node
            )
            if index.in_fetch_boundary(anchor):
                continue
            add(
                "host-fetch",
                anchor,
                f"{what} is a device->host materialization outside a "
                "record_fetch-accounted boundary: charge it via "
                "SCAN_STATS.record_fetch (or annotate why no device "
                "value can reach it)",
            )

    # -- bare-except -----------------------------------------------------
    if in_scope("bare-except"):
        for node in ast.walk(tree):
            if not isinstance(node, ast.ExceptHandler):
                continue
            broad = node.type is None or (
                isinstance(node.type, ast.Name)
                and node.type.id in ("Exception", "BaseException")
            )
            if not broad:
                continue
            body_names = set()
            reraises = False
            for sub in node.body:
                body_names |= _names_in(sub)
                for s in ast.walk(sub):
                    if isinstance(s, ast.Raise):
                        reraises = True
            if "classify_device_error" in body_names or reraises:
                continue
            add(
                "bare-except",
                node,
                "broad except swallows device-seam failures without "
                "classify_device_error or a re-raise: a typed XLA fault "
                "becomes silence here (annotate best-effort handlers "
                "with a reason)",
            )

    # -- jit-impure / span-in-jit ---------------------------------------
    traced: Set[str] = set()
    if in_scope("jit-impure") or in_scope("span-in-jit"):
        traced = _traced_function_names(tree)
    if in_scope("jit-impure"):
        for node in ast.walk(tree):
            if (
                isinstance(node, (ast.FunctionDef, ast.AsyncFunctionDef))
                and node.name in traced
            ):
                for sub in ast.walk(node):
                    if not isinstance(sub, ast.Call):
                        continue
                    kind = _impure_call(_dotted(sub.func))
                    if kind is None:
                        continue
                    add(
                        "jit-impure",
                        sub,
                        f"{kind} call inside traced function "
                        f"'{node.name}': the value is baked at trace "
                        "time and replayed from the program cache",
                    )

    # -- span-in-jit -----------------------------------------------------
    if in_scope("span-in-jit"):
        for node in ast.walk(tree):
            if (
                isinstance(node, (ast.FunctionDef, ast.AsyncFunctionDef))
                and node.name in traced
            ):
                for sub in ast.walk(node):
                    if not isinstance(sub, ast.Call):
                        continue
                    what = _span_emission(_dotted(sub.func))
                    if what is None:
                        continue
                    add(
                        "span-in-jit",
                        sub,
                        f"{what} inside traced function '{node.name}': "
                        "span/event emission in jitted code is a host "
                        "callback by another name — it bakes a "
                        "trace-time record into the cached program "
                        "(emit at the host seams around the dispatch "
                        "instead)",
                    )

    # -- typed-raise -----------------------------------------------------
    if in_scope("typed-raise"):
        for node in ast.walk(tree):
            if not isinstance(node, ast.Raise) or node.exc is None:
                continue
            exc = node.exc
            name = None
            if isinstance(exc, ast.Call):
                parts = _dotted(exc.func)
                name = parts[-1] if parts else None
            elif isinstance(exc, ast.Name):
                name = exc.id
            if name in _GENERIC_RAISES:
                add(
                    "typed-raise",
                    node,
                    f"raise {name} inside the engine: use the "
                    "deequ_tpu.exceptions taxonomy (Device*/"
                    "MetricCalculation*) or a precise builtin so the "
                    "fault ladder can dispatch on the type",
                )

    # -- durable-write ---------------------------------------------------
    if in_scope("durable-write"):
        def _write_mode(call: ast.Call) -> Optional[str]:
            """The literal mode string when this is a write-mode open,
            else None (reads, appends, and computed modes pass)."""
            mode: Optional[ast.AST] = None
            if len(call.args) >= 2:
                mode = call.args[1]
            for kw in call.keywords:
                if kw.arg == "mode":
                    mode = kw.value
            if not isinstance(mode, ast.Constant) or not isinstance(
                mode.value, str
            ):
                return None
            return mode.value if "w" in mode.value else None

        for node in ast.walk(tree):
            if not isinstance(node, ast.Call):
                continue
            parts = _dotted(node.func)
            if not parts:
                continue
            if parts[-1] == "open":
                mode = _write_mode(node)
                if mode is not None:
                    add(
                        "durable-write",
                        node,
                        f"raw open(..., {mode!r}) in a durable-state "
                        "module: route the write through "
                        "resilience/atomic.atomic_write_bytes (temp + "
                        "fsync + rename) so it gets torn-write recovery "
                        "(or annotate why this write is not durable "
                        "state)",
                    )
            elif parts[-2:] == ["os", "fsync"]:
                add(
                    "durable-write",
                    node,
                    "raw os.fsync in a durable-state module: the shared "
                    "atomic helper owns the flush+fsync+rename sequence "
                    "(annotate append-only protocols with a reason)",
                )
            elif parts[-2:] in (["os", "rename"], ["os", "replace"]):
                add(
                    "durable-write",
                    node,
                    f"raw {'.'.join(parts[-2:])} in a durable-state "
                    "module: commit renames belong inside "
                    "resilience/atomic.atomic_write_bytes (annotate "
                    "non-durable file shuffling with a reason)",
                )

    # -- suppress-reason -------------------------------------------------
    if in_scope("suppress-reason"):
        for line in sup.missing_reasons():
            findings.append(
                LintFinding(
                    "suppress-reason",
                    "error",
                    "deequ-lint suppression without a reason: append "
                    "'-- <why this is legitimate>'",
                    location=f"{rel}:{line}",
                )
            )

    findings.sort(key=lambda f: f.location)
    return findings


def _package_root() -> str:
    return os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def lint_paths(
    paths: Sequence[str] = (),
    rules: Optional[Sequence[str]] = None,
) -> List[LintFinding]:
    """Lint every ``.py`` file under ``paths`` (default: the installed
    ``deequ_tpu`` package). Files are addressed relative to the package
    root so RULE_SCOPES apply regardless of invocation cwd."""
    root = _package_root()
    targets: List[str] = []
    for p in paths or (root,):
        p = os.path.abspath(p)
        if os.path.isfile(p):
            targets.append(p)
        else:
            for dirpath, dirnames, filenames in os.walk(p):
                dirnames[:] = [
                    d for d in dirnames if d != "__pycache__"
                ]
                targets.extend(
                    os.path.join(dirpath, f)
                    for f in sorted(filenames)
                    if f.endswith(".py")
                )
    findings: List[LintFinding] = []
    for path in sorted(targets):
        rel = os.path.relpath(path, root)
        if rel.startswith(".."):
            rel = os.path.basename(path)
        with open(path, "r", encoding="utf-8") as fh:
            source = fh.read()
        findings.extend(lint_source(source, rel, rules))
    return findings
