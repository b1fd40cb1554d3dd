"""Process-fleet worker: one VerificationService behind a frame
transport (``python -m deequ_tpu.serve.pworker --fd N --idx I``).

The protocol loop here is the ONLY worker implementation: the
coordinator (:mod:`deequ_tpu.serve.pfleet`) runs it in a spawned
process over a :class:`~deequ_tpu.serve.transport.SocketTransport`
(production shape: process isolation, one host/chip per worker) or in
a thread over a :class:`~deequ_tpu.serve.transport.LoopbackTransport`
(deterministic tests, single-process deployments) — the frames, acks,
typed refusals, and quarantine merges are identical in both.

Protocol (coordinator -> worker):

- ``submit`` — one suite: ``work_blob`` carries (data, checks,
  required_analyzers); ``slo`` the class/deadline/weight; an optional
  ``quarantine_blob`` merges the coordinator's fleet-wide quarantine
  view in BEFORE admission, so a tenant poisoned on another worker is
  serial-only here too. Answered by ``accept`` or a typed ``refuse``.
- ``warm`` — plan FINGERPRINTS (schema + row count + pickled
  analyzers). Traced programs don't serialize; the joiner replays each
  fingerprint through ``build_serve_plan`` over a synthetic table of
  the same shape, so its own cache traces once — warm join without
  shipping compiled artifacts.
- ``ping`` -> ``pong`` (service-thread heartbeat age + queue depth +
  quarantine snapshot): the membership probe's transport leg.
- ``stop`` — drain (or not) and exit the loop.

Worker -> coordinator: ``hello`` at ready, ``accept``/``refuse`` per
submit, ``result`` per resolution (success or typed failure, plus the
worker's quarantine snapshot so verdicts flow back), ``pong``,
``warm_ack``, ``stopped``.

Backpressure stays TYPED across the wire: a
:class:`~deequ_tpu.exceptions.ServiceOverloadedException` family
refusal serializes its structured fields (``retry_after_s``,
``queue_depth``, ``slo_class``, admission ``reason``) — not a pickled
exception — and the coordinator reconstructs the same type, so the
PR-15 admission semantics survive serialization byte-for-byte where it
matters: in the fields callers schedule retries from.
"""

from __future__ import annotations

import argparse
import os
import socket
import time
from typing import Any, Dict, Optional

from deequ_tpu.exceptions import (
    ServiceClosedException,
    ServiceOverloadedException,
    StaleEpochException,
)
from deequ_tpu.serve.transport import (
    Transport,
    TransportClosedError,
    dump_blob,
    load_blob,
)


def _column_facts(col) -> "tuple[bool, bool]":
    """(has_nulls, fits_i32) — the VALUE facts the chunk packer routes
    on (null-free columns ship no mask row; narrow integrals ride the
    i32 buffer). The fingerprint must carry them or the warm replay's
    synthetic table lands in a different layout group and mints a key
    no real tenant ever matches."""
    import numpy as np

    codes = getattr(col, "codes", None)
    if codes is not None:
        # deequ-lint: ignore[host-fetch] -- fingerprinting reads the Column's host numpy codes, never a device array
        return bool((np.asarray(codes) < 0).any()), True
    mask = getattr(col, "mask", None)
    # deequ-lint: ignore[host-fetch] -- the Column's validity mask is a host numpy array by construction
    has_nulls = mask is not None and not bool(np.asarray(mask).all())
    fits_i32 = True
    values = getattr(col, "values", None)
    if values is not None:
        # deequ-lint: ignore[host-fetch] -- Column.values is the host-side staging array, never a device array
        arr = np.asarray(values)
        if arr.size and np.issubdtype(arr.dtype, np.number):
            finite = arr[np.isfinite(arr)]
            if finite.size:
                fits_i32 = bool(np.abs(finite).max() < 2**31 - 1)
    return has_nulls, fits_i32


def plan_fingerprint(data, analyzers) -> Optional[dict]:
    """The shippable identity of a plan: schema (with the
    layout-routing value facts) + rows + analyzers. None for sources
    that don't expose a columnar schema (count-less streams serve on
    the serial path — nothing to warm)."""
    try:
        schema = []
        for name in data.column_names:
            col = data[name]
            has_nulls, fits_i32 = _column_facts(col)
            schema.append([name, col.dtype.name, has_nulls, fits_i32])
        rows = int(data.num_rows or 0)
    except (AttributeError, TypeError):
        return None
    if rows <= 0:
        return None
    return {
        "schema": schema,
        "rows": rows,
        "analyzers_blob": dump_blob(tuple(analyzers)),
    }


def _synthetic_table(schema, rows: int):
    """A table matching a fingerprint's shape AND layout routing —
    what the warm replay builds its plan (and first trace) against.
    Values are inert placeholders except for the two packer-visible
    facts: a single null when the real column had any, and a value
    outside int32 when the real column's did not fit."""
    import numpy as np

    from deequ_tpu.data.table import Column, ColumnarTable, DType

    columns = []
    for entry in schema:
        name, dtype_name = entry[0], entry[1]
        has_nulls = bool(entry[2]) if len(entry) > 2 else False
        fits_i32 = bool(entry[3]) if len(entry) > 3 else True
        dtype = DType[dtype_name]
        if dtype == DType.STRING:
            codes = np.zeros(rows, dtype=np.int32)
            if has_nulls:
                codes[0] = -1
            # deequ-lint: ignore[host-fetch] -- builds a fresh host numpy dictionary for the synthetic table
            dictionary = np.asarray(["a"], dtype=object)
            columns.append(Column(
                name, dtype, codes=codes, dictionary=dictionary,
            ))
        else:
            values = np.zeros(rows)
            if not fits_i32:
                values[:] = float(2**33)
            mask = np.ones(rows, dtype=bool)
            if has_nulls:
                mask[0] = False
            columns.append(Column(
                name, dtype, values=values, mask=mask,
            ))
    return ColumnarTable(columns)


def replay_fingerprints(service, plans) -> int:
    """Warm a service's plan cache from shipped fingerprints: build
    each plan over a synthetic same-shape table and mint the same
    :class:`~deequ_tpu.serve.plan_cache.PlanKey` the service would
    (the PlanKey replay — this cache traces once, on arrival, instead
    of per first tenant). Best-effort per entry: a fingerprint that no
    longer builds (or is serial-class) leaves the joiner cold for that
    one plan, never broken."""
    from deequ_tpu.serve.plan_cache import (
        PlanKey,
        build_serve_plan,
        layout_signature,
        schema_signature,
    )

    warmed = 0
    for fp in plans:
        try:
            analyzers = load_blob(fp["analyzers_blob"], "warm fingerprint")
            rows = int(fp["rows"])
            table = _synthetic_table(fp["schema"], rows)
            plan = build_serve_plan(table, list(analyzers))
            if (
                not plan.coalescable
                or plan.serial_class
                or plan.op_failures
                or plan.precondition_failures
            ):
                continue  # serial-path plans have no cache identity
            plan.key = PlanKey(
                schema_sig=schema_signature(table, plan.needed),
                analyzer_sig=tuple(analyzers),
                layout_sig=layout_signature(plan.layout),
                chunk=rows,
            )
            service.plan_cache.put(plan)
            warmed += 1
        # deequ-lint: ignore[bare-except] -- best-effort warm replay: a stale/undecodable fingerprint leaves the joiner cold for that one plan, never broken
        except Exception:  # noqa: BLE001
            continue
    return warmed


def _refusal_fields(e) -> dict:
    """Decompose a typed refusal (the ServiceOverloadedException family
    OR a fencing StaleEpochException) into structured wire fields — the
    coordinator reconstructs the same type from them."""
    return {
        "cls": type(e).__name__,
        "message": str(e),
        "queue_depth": getattr(e, "queue_depth", None),
        "retry_after_s": getattr(e, "retry_after_s", None),
        "slo_class": getattr(e, "slo_class", None),
        "reason": getattr(e, "reason", None),
        "stale_epoch": getattr(e, "stale_epoch", None),
        "current_epoch": getattr(e, "current_epoch", None),
        "holder": getattr(e, "holder", None),
    }


class WorkerLoop:
    """The protocol loop over one transport endpoint (see module doc)."""

    def __init__(self, transport: Transport, idx: int = 0,
                 worker_knobs: Optional[Dict[str, Any]] = None,
                 service=None):
        from deequ_tpu.parallel.mesh import use_mesh
        from deequ_tpu.serve.service import ServeConfig, VerificationService

        self.transport = transport
        self.idx = int(idx)
        if service is not None:
            self.service = service
        else:
            knobs = dict(worker_knobs or {})
            # the worker IS one host/chip: construct under the
            # single-device view (the fleet's _spawn_service rule)
            with use_mesh(None):
                self.service = VerificationService(
                    config=ServeConfig(**knobs) if knobs else ServeConfig(),
                    start=True,
                )
        self._stopping = False
        #: epoch fencing (serve/lease.py): the highest coordinator
        #: epoch this worker has witnessed; dispatches stamped older
        #: are refused typed before ANY side effect. 0 = unfenced.
        self._highest_epoch = 0
        #: accept_id -> the epoch that dispatched it, echoed on results
        #: so a resumed coordinator can spot zombie-epoch result frames
        self._accept_epochs: Dict[str, int] = {}

    # -- frame handlers --------------------------------------------------

    def _send(self, msg: dict) -> bool:
        try:
            self.transport.send(msg)
            return True
        except TransportClosedError:
            # the coordinator is gone: a worker with no coordinator has
            # nobody to resolve to — finish quietly, the durable ledger
            # on the coordinator side owns recovery
            self._stopping = True
            return False

    def _quarantine_blob(self) -> str:
        return dump_blob(self.service.tenant_health.snapshot())

    def _send_result(self, accept_id: str, future) -> None:
        ok = future._error is None and not future.cancelled()
        payload = future._result if ok else future._error
        self._send({
            "t": "result",
            "id": accept_id,
            "ok": bool(ok),
            "epoch": self._accept_epochs.pop(
                accept_id, self._highest_epoch
            ),
            "payload_blob": dump_blob(payload),
            "quarantine_blob": self._quarantine_blob(),
        })

    def _on_submit(self, msg: dict) -> None:
        from deequ_tpu.serve.admission import Slo

        accept_id = str(msg["id"])
        epoch = int(msg.get("epoch") or 0)
        if epoch and epoch < self._highest_epoch:
            # a fenced-out (zombie) coordinator's dispatch: refuse it
            # typed BEFORE any side effect — no quarantine restore, no
            # blob decode, no admission
            exc = StaleEpochException(
                f"dispatch from stale epoch {epoch} refused: worker "
                f"{self.idx} has seen epoch {self._highest_epoch}",
                stale_epoch=epoch,
                current_epoch=self._highest_epoch,
            )
            self._send({"t": "refuse", "id": accept_id,
                        **_refusal_fields(exc)})
            return
        if epoch:
            self._highest_epoch = epoch
            self._accept_epochs[accept_id] = epoch
        snap_blob = msg.get("quarantine_blob")
        if snap_blob:
            self.service.tenant_health.restore(
                load_blob(snap_blob, "submit quarantine snapshot")
            )
        data, checks, required_analyzers = load_blob(
            msg["work_blob"], "submit work"
        )
        tenant = load_blob(msg["tenant_blob"], "submit tenant")
        slo_raw = msg.get("slo") or {}
        deadline_left = msg.get("deadline_left_s")
        slo = Slo(
            deadline_ms=(
                max(float(deadline_left), 1e-3) * 1000.0
                if deadline_left is not None else None
            ),
            weight=float(slo_raw.get("weight", 1.0)),
            cls=str(slo_raw.get("cls", "standard")),
        )
        try:
            future = self.service.submit(
                data,
                checks=checks,
                required_analyzers=required_analyzers,
                tenant=tenant,
                slo=slo,
            )
        except ServiceOverloadedException as e:
            # typed backpressure, serialized structurally (module doc)
            self._send({"t": "refuse", "id": accept_id,
                        **_refusal_fields(e)})
            return
        except ServiceClosedException as e:
            self._send({
                "t": "refuse", "id": accept_id,
                "cls": "ServiceClosedException", "message": str(e),
            })
            return
        prev = future._on_done

        def _done(f, ok, _prev=prev, _id=accept_id):
            if _prev is not None:
                _prev(f, ok)
            self._send_result(_id, f)

        future._on_done = _done
        self._send({"t": "accept", "id": accept_id})
        if future.done():
            # resolved between submit and chaining: the service's own
            # seam already ran on the unwrapped callback — ship the
            # result directly (never re-run the observation seam)
            self._send_result(accept_id, future)

    def _on_ping(self, msg: dict) -> None:
        self._send({
            "t": "pong",
            "seq": msg.get("seq"),
            "heartbeat_age_s": max(
                time.monotonic() - self.service.heartbeat, 0.0
            ),
            "queue_depth": self.service.pending_count(),
            "quarantine_blob": self._quarantine_blob(),
        })

    def _on_warm(self, msg: dict) -> None:
        warmed = replay_fingerprints(self.service, msg.get("plans") or ())
        self._send({"t": "warm_ack", "warmed": warmed})

    def _on_stop(self, msg: dict) -> None:
        self._stopping = True
        pending = self.service.stop(drain=bool(msg.get("drain", True)))
        self._send({
            "t": "stopped",
            "pending": len(pending),
            "quarantine_blob": dump_blob(
                pending.tenant_health or
                self.service.tenant_health.snapshot()
            ),
        })

    # -- the loop --------------------------------------------------------

    def run(self) -> None:
        self._send({"t": "hello", "pid": os.getpid(), "idx": self.idx})
        handlers = {
            "submit": self._on_submit,
            "ping": self._on_ping,
            "warm": self._on_warm,
            "stop": self._on_stop,
        }
        while not self._stopping:
            try:
                msg = self.transport.recv(timeout=0.25)
            except TransportClosedError:
                # coordinator death: stop serving. Accepted-but-unsent
                # work dies with this worker BY DESIGN — the durable
                # ledger on the coordinator side replays it at resume
                break
            if msg is None:
                continue
            handler = handlers.get(str(msg.get("t")))
            if handler is None:
                self._send({
                    "t": "error",
                    "message": f"unknown frame type {msg.get('t')!r}",
                })
                continue
            handler(msg)
        if not self.service._closed:
            self.service.stop(drain=False, join=False)
        self.transport.close()


def main(argv=None) -> int:
    parser = argparse.ArgumentParser(
        description="deequ-tpu process-fleet worker (spawned by "
                    "serve/pfleet.py; not a user entry point)"
    )
    parser.add_argument("--fd", type=int, required=True,
                        help="inherited socketpair fd to the coordinator")
    parser.add_argument("--idx", type=int, default=0)
    parser.add_argument("--knobs", type=str, default=None,
                        help="JSON ServeConfig overrides")
    args = parser.parse_args(argv)
    import json

    from deequ_tpu.serve.transport import SocketTransport

    knobs = json.loads(args.knobs) if args.knobs else None
    # hello means "I hold my device": initialise the backend BEFORE the
    # loop says it, so a worker that cannot get the chip (a chip serves
    # ONE process; its parent or a sibling holds it) fails the
    # coordinator's spawn, typed, instead of failing every request later
    import jax

    jax.devices()
    sock = socket.socket(fileno=args.fd)
    WorkerLoop(SocketTransport(sock), idx=args.idx,
               worker_knobs=knobs).run()
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
