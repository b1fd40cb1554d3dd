"""Scan executors — the run strategies behind ``run_scan``, split out of
the engine by the round-19 plan optimizer.

``ops/scan_engine.run_scan`` owns RESOLUTION (switch/env/deadline/budget
resolution, recorder scoping, mesh quarantine) and then hands the scan to
one of the two executors here, by :func:`classify`:

- ``"streaming"`` — :func:`run_streaming_scan`: one governed pass over a
  streaming table (no retry ladder: a half-consumed stream cannot
  rewind);
- ``"resident"`` / ``"sharded"`` — :func:`run_laddered_scan`: the
  in-memory fault ladder (encoded-demote -> OOM-bisect -> CPU-fallback
  rungs), on one device or on a multi-chip mesh with the mesh rungs
  (reshard/straggler) armed.

One ladder body serves both on purpose — the mesh rungs self-gate on mesh
size, and splitting the loop would fork the re-plan-per-attempt contract
into two copies that drift. Every rung re-enters
``_engine._run_scan_once``, which re-plans (selection variant, encoded
ingest, chunk shape, lint) per attempt. The serving coalescer
(``serve/executor.run_coalesced``) and the windows engine
(``windows/engine.drive``) are called by their own layers, not from here.

Engine internals are reached via the lazy module attribute
(``_engine()._run_scan_once`` etc.), never ``from``-imported: tests
monkeypatch names on ``scan_engine`` and the executors must see the
patched values.
"""

from __future__ import annotations

import math
from typing import Any, Dict, List, Optional, Sequence

import jax

from deequ_tpu.exceptions import (
    DeviceException,
    DeviceHangException,
    DeviceOOMException,
)
from deequ_tpu.obs.recorder import seam


def _engine():
    from deequ_tpu.ops import scan_engine

    return scan_engine


def _mesh_size(m) -> int:
    return math.prod(m.devices.shape) if m is not None else 1


def classify(table, mesh=None) -> str:
    """The executor-selection policy: which run strategy this scan takes,
    derived from the table and the mesh shape."""
    if getattr(table, "is_streaming", False):
        return "streaming"
    if _mesh_size(mesh) > 1:
        return "sharded"
    return "resident"


def run_streaming_scan(
    table,
    ops: Sequence,
    *,
    chunk_rows: Optional[int],
    mesh,
    defer: bool,
    device_deadline: Optional[float],
    shard_deadline: Optional[float],
    window: int,
    select_kernel: bool,
    plan_lint: str,
    encoded_ingest: bool,
    budget,
    scan_id: int,
) -> List[Any]:
    """One governed pass over a streaming table. Streams never retry in
    here (no rewind), so the whole scan is ONE attempt span; a run budget
    with a wall deadline arms one attempt-level watchdog around it."""
    eng = _engine()
    if defer:
        raise ValueError(
            "defer=True is for in-memory batch tables; streaming scans "
            "already pipeline internally"
        )
    # the straggler deadline arms the stream's mesh dispatches too: a
    # half-consumed stream cannot reshard (no rewind), but a stalled
    # collective must still become a TYPED DeviceHangException rather
    # than a frozen run — use the tighter of the two deadlines
    stream_deadline = device_deadline
    if shard_deadline is not None and mesh is not None and (
        math.prod(mesh.devices.shape) > 1
    ):
        stream_deadline = (
            shard_deadline
            if device_deadline is None
            else min(device_deadline, shard_deadline)
        )
    with seam("scan_attempt", scan_id=scan_id, attempt=0, stream=True):
        return eng._governed_attempt(
            budget,
            lambda: eng._run_scan_stream(
                table, ops, chunk_rows, mesh,
                scan_id=scan_id, device_deadline=stream_deadline,
                window=window, select_kernel=select_kernel,
                plan_lint=plan_lint, encoded=encoded_ingest,
            ),
            f"stream scan {scan_id} (run budget)",
        )


def run_laddered_scan(
    table,
    ops: Sequence,
    *,
    chunk_rows: Optional[int],
    mesh,
    defer: bool,
    on_device_error: str,
    device_deadline: Optional[float],
    shard_deadline: Optional[float],
    window: int,
    select_kernel: bool,
    plan_lint: str,
    encoded_ingest: bool,
    budget,
    scan_id: int,
    fallback: bool,
) -> List[Any]:
    """The in-memory fault ladder — resident and sharded scans alike
    (mesh rungs self-gate on mesh size). Each rung re-enters
    ``_run_scan_once``, which RE-PLANS per attempt: encoded->decoded
    demotion first, then chunk bisection, then mesh reshard, then CPU
    fallback, with every retry charging the run budget before it
    spends a rung."""
    eng = _engine()
    can_fallback = (
        on_device_error == "fallback" and eng._cpu_fallback_device() is not None
    )
    chunk_override = chunk_rows
    attempt = 0
    depth = 0
    while True:
        # one enclosing seam per ladder attempt (its wall is
        # scan_seconds): the plan/pack/stage/dispatch/fetch seams nest
        # under it and inherit its scan_id, and a rung firing in the
        # except blocks below records its instant event INSIDE the
        # attempt span it degraded
        with seam(
            "scan_attempt", scan_id=scan_id, attempt=attempt,
            fallback=fallback,
        ):
            n_dev = _mesh_size(mesh)
            floor = max(
                n_dev,
                min(eng.MIN_BISECT_CHUNK_ROWS, max(table.num_rows, 1)),
            )
            # straggler watchdog: on a MULTI-chip dispatch the per-shard
            # deadline bounds how long one stalled chip may hold a
            # collective
            straggler_armed = shard_deadline is not None and n_dev > 1
            attempt_deadline = device_deadline
            if straggler_armed:
                attempt_deadline = (
                    shard_deadline
                    if device_deadline is None
                    else min(device_deadline, shard_deadline)
                )
            scan_ctx = {
                "scan_id": scan_id, "attempt": attempt, "fallback": fallback,
                "device_ids": eng.mesh_device_ids(mesh),
            }
            report: Dict[str, Any] = {}

            def _reshard_after(e: DeviceException) -> bool:
                """Shrink the mesh around the chip(s) ``e`` implicates;
                True when a healthy accelerator subset remains and the
                scan should re-dispatch on it."""
                nonlocal mesh, chunk_override, depth
                mesh_ids = set(eng.mesh_device_ids(mesh))
                lost = [
                    d for d in getattr(e, "device_ids", ()) if d in mesh_ids
                ]
                if not lost or len(mesh_ids) <= 1:
                    return False
                eng.SCAN_STATS.mesh_faults += 1
                eng.MESH_HEALTH.record_fault(e)
                new_mesh = eng.mesh_excluding(
                    mesh, set(lost) | set(eng.MESH_HEALTH.quarantined())
                )
                if new_mesh is None:
                    return False
                # residency is pinned (sharded) onto the OLD mesh —
                # including the dead chip(s); it cannot serve the shrunken
                # mesh
                freed = eng._evict_device_cache(table)
                eng.SCAN_STATS.mesh_reshards += 1
                eng.SCAN_STATS.record_degradation(
                    "mesh_reshard", scan_id=scan_id,
                    lost_devices=sorted(lost),
                    mesh_from=len(mesh_ids), mesh_to=_mesh_size(new_mesh),
                    evicted_bytes=freed, error=str(e),
                )
                mesh = new_mesh
                # the pressure that drove any bisection left with the
                # chip: restart at the caller's chunk size, or a per-chip
                # OOM that bottomed out at the ~64-row floor would pin the
                # WHOLE rest of the scan at floor-sized dispatches on a
                # healthy mesh (a recurring OOM on the survivors simply
                # re-bisects)
                chunk_override = chunk_rows
                depth = 0
                return True

            try:
                if fallback:
                    eng.SCAN_STATS.fallback_scans += 1
                    eng.SCAN_STATS.fallback_backend = "cpu"
                    # the resident chunks (and on single-device setups
                    # even a mesh=None cache) are committed to the
                    # ACCELERATOR — jax.default_device cannot move
                    # committed arrays, so the fallback must drop
                    # residency or it would dispatch right back onto the
                    # device it is fleeing
                    eng._evict_device_cache(table)

                    def _fallback_once():
                        # jax.default_device is THREAD-LOCAL: the context
                        # must open inside the (possibly watchdog-worker)
                        # thread that runs the attempt. The per-call
                        # watchdog stays disarmed here — it exists to
                        # detect a hung ACCELERATOR, and the CPU re-jit
                        # legitimately pays a fresh compile — but the run
                        # budget's attempt-level watchdog still bounds the
                        # whole rung, so termination within run_deadline
                        # covers the fallback too
                        with jax.default_device(eng._cpu_fallback_device()):
                            return eng._run_scan_once(
                                table, ops, chunk_override, None, defer,
                                None, scan_ctx, report, window,
                                select_kernel=select_kernel,
                                plan_lint=plan_lint,
                                encoded=encoded_ingest,
                            )

                    return eng._governed_attempt(
                        budget, _fallback_once,
                        f"scan {scan_id} CPU fallback (run budget)",
                    )
                result = eng._governed_attempt(
                    budget,
                    lambda: eng._run_scan_once(
                        table, ops, chunk_override, mesh, defer,
                        attempt_deadline, scan_ctx, report, window,
                        select_kernel=select_kernel, plan_lint=plan_lint,
                        encoded=encoded_ingest,
                    ),
                    f"scan {scan_id} attempt {attempt} (run budget)",
                )
                eng.DEVICE_HEALTH.record_success()
                if n_dev > 1:
                    eng.MESH_HEALTH.record_success(eng.mesh_device_ids(mesh))
                return result
            except DeviceOOMException as e:
                eng.SCAN_STATS.device_faults += 1
                if not fallback:  # CPU faults are not accelerator health
                    eng.DEVICE_HEALTH.record_fault(e)
                used = (
                    report.get("chunk")
                    or chunk_override
                    or eng.DEFAULT_CHUNK_ROWS
                )
                freed = eng._evict_device_cache(table)
                # encoded -> decoded demotion FIRST, like the PR-6
                # selection -> sort re-plan: the encoded attempt's decode
                # gathers/dictionary LUTs are the allocations the fault
                # implicates that the decoded program simply doesn't
                # have — retry on the known-good decoded path at the same
                # chunk size; a recurring OOM there bisects as before
                if not fallback and encoded_ingest and report.get("encoded"):
                    # every ladder retry charges the run budget FIRST: an
                    # exhausted budget raises typed here instead of
                    # spending another rung (the charge exception carries
                    # the ledger)
                    if budget is not None:
                        budget.charge("encoded_demote", scan_id=scan_id)
                    encoded_ingest = False
                    eng.SCAN_STATS.encoded_demotions += 1
                    eng.SCAN_STATS.record_degradation(
                        "encoded_demote", scan_id=scan_id, chunk=int(used),
                        evicted_bytes=freed, error=str(e),
                    )
                    attempt += 1
                    continue
                halved = max(floor, used // 2)
                halved = max(n_dev, (halved // n_dev) * n_dev)
                if halved < used and not fallback:
                    if budget is not None:
                        budget.charge("oom_bisect", scan_id=scan_id)
                    depth += 1
                    eng.SCAN_STATS.oom_bisections += 1
                    eng.SCAN_STATS.bisection_depth = max(
                        eng.SCAN_STATS.bisection_depth, depth
                    )
                    eng.SCAN_STATS.record_degradation(
                        "oom_bisect", scan_id=scan_id, chunk_from=int(used),
                        chunk_to=int(halved), depth=depth,
                        evicted_bytes=freed, error=str(e),
                    )
                    chunk_override = halved
                    attempt += 1
                    continue
                # at the bisection floor: a per-CHIP OOM (the message
                # named its device) can still shed the sick member and
                # retry on the healthy remainder before any CPU fallback
                if not fallback and _reshard_after(e):
                    if budget is not None:
                        budget.charge("mesh_reshard", scan_id=scan_id)
                    attempt += 1
                    continue
                # bisection and resharding cannot help any further
                if can_fallback and not fallback:
                    if budget is not None:
                        budget.charge("cpu_fallback", scan_id=scan_id)
                    fallback = True
                    attempt += 1
                    eng.SCAN_STATS.record_degradation(
                        "cpu_fallback", scan_id=scan_id,
                        reason="oom_at_bisection_floor", chunk=int(used),
                        error=str(e),
                    )
                    continue
                raise
            except DeviceException as e:
                eng.SCAN_STATS.device_faults += 1
                if isinstance(e, DeviceHangException):
                    eng.SCAN_STATS.watchdog_timeouts += 1
                    # a hang on a multi-chip dispatch is a straggling
                    # collective only when the PER-SHARD deadline was the
                    # one that bound (attempt_deadline = min of the two):
                    # a hang tripping a tighter device_deadline is a
                    # general watchdog timeout and must not be mislabeled
                    # as a straggler
                    if straggler_armed and (
                        device_deadline is None
                        or shard_deadline <= device_deadline
                    ):
                        eng.SCAN_STATS.mesh_stragglers += 1
                        eng.SCAN_STATS.record_degradation(
                            "mesh_straggler", scan_id=scan_id,
                            deadline=e.deadline, mesh_size=n_dev,
                            error=str(e),
                        )
                    else:
                        eng.SCAN_STATS.record_degradation(
                            "watchdog_timeout", scan_id=scan_id,
                            deadline=e.deadline, error=str(e),
                        )
                # the degraded-mesh ladder comes BEFORE the whole-backend
                # ladder: a fault attributable to specific mesh members
                # costs those members, never the backend — the run
                # continues on the largest healthy subset, and the CPU
                # fallback is reached only when no accelerator subset
                # remains
                if not fallback and _reshard_after(e):
                    if budget is not None:
                        budget.charge("mesh_reshard", scan_id=scan_id)
                    attempt += 1
                    continue
                if not fallback:  # CPU faults are not accelerator health
                    eng.DEVICE_HEALTH.record_fault(e)
                # compile / lost / hang with no healthy subset left:
                # retrying the same program on the same backend cannot
                # help — fall back or raise typed
                if can_fallback and not fallback:
                    if budget is not None:
                        budget.charge("cpu_fallback", scan_id=scan_id)
                    fallback = True
                    attempt += 1
                    eng.SCAN_STATS.record_degradation(
                        "cpu_fallback", scan_id=scan_id,
                        reason=type(e).__name__, error=str(e),
                    )
                    continue
                raise
