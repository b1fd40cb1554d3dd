"""ProcessFleet — the multi-HOST fleet: process-isolated workers, a
durable request ledger, and coordinator kill-and-resume.

:class:`~deequ_tpu.serve.fleet.VerificationFleet` (PR 14) runs its
workers as threads of one process: a wedged C extension, a heap
corruption, or an OOM kill takes the whole fleet — and every accepted
future — down at once. This module is the same fleet control plane
re-seated on PROCESS boundaries (the production multi-host shape, one
worker process per host/chip):

- **Workers are processes** — each spawned as ``python -m
  deequ_tpu.serve.pworker`` over one end of a ``socketpair`` (or, with
  ``transport="loopback"``, as a thread over an in-process queue pair
  running the IDENTICAL protocol loop — same frames, acks, refusals).
  ``kill -9`` on a worker is a real SIGKILL; its loss surfaces as
  transport EOF, exactly like host death in a real fleet.
- **Membership on the check_peers seam** — the same
  :class:`~deequ_tpu.serve.membership.FleetMembership` monitor, with a
  ping/pong probe over the transport: each pong carries the worker's
  own service-thread heartbeat age, so a process that is alive but
  WEDGED mid-batch is declared lost just like a dead one.
- **Plan warmup ships FINGERPRINTS, not programs** — traced/compiled
  executables do not serialize across processes. Submits record each
  routing digest's plan fingerprint (schema + rows + analyzers);
  prewarm/rejoin ship the hottest fingerprints and the worker REPLAYS
  the PlanKey (:func:`deequ_tpu.serve.pworker.replay_fingerprints`),
  tracing once on arrival instead of per first tenant.
- **Typed backpressure crosses the wire** — a worker's
  ``ServiceOverloadedException`` family refusal travels as structured
  fields and is RECONSTRUCTED as the same type coordinator-side, so
  ring-walk spill and caller retry schedules work unchanged.
- **The durable ledger** (:mod:`deequ_tpu.serve.ledger`) — every
  acceptance is fsynced as a checksummed frame BEFORE its submit
  returns, every resolution appends a tombstone. SIGKILL the
  coordinator and a fresh ``ProcessFleet(ledger_dir=...,
  resume_futures=...)`` replays accepted-minus-tombstoned onto the
  ORIGINAL futures — the ``stop(drain=False)``/``resume``
  kill-and-resume contract extended across coordinator death, with the
  futures' first-resolution-wins gate keeping exactly-once (chaos
  oracle 8 across the process boundary). Deadlines resume HONESTLY: a
  record's remaining budget is its accept-time remainder minus the
  wall-clock the coordinator spent dead; an expired victim is shed
  typed, never replayed stale.

- **Epoch fencing** (:mod:`deequ_tpu.serve.lease`) — resume assumed the
  old coordinator was DEAD; fencing makes a merely-stalled one
  harmless. When fencing is on (default whenever a ``ledger_dir`` is
  configured), the coordinator acquires a durable lease whose epoch
  strictly exceeds everything the ledger has witnessed, stamps every
  submit frame, ledger record, and reaccept with it, and checks the
  lease on every submit: a zombie that wakes after a takeover raises
  :class:`~deequ_tpu.exceptions.StaleEpochException` on its next
  submit, permanently, and IGNORES result frames once fenced (counted
  on ``zombie_results_ignored``). Workers refuse stale-epoch dispatches
  typed before any side effect; ledger replay reconciles cross-epoch
  duplicates by epoch precedence — exactly-once stays the futures'
  first-resolution-wins gate, now with the zombie unable to add new
  effects at all.

Chaos seams: :meth:`kill_worker` (real SIGKILL),
:meth:`rejoin_worker`, ledger-backed resume, and the zombie-coordinator
``partition`` seam — scripted by ``resilience/chaos.py``'s ``kill9`` /
``coord_kill9`` / ``partition`` events under the fleet oracles.
"""

from __future__ import annotations

import os
import signal
import subprocess
import sys
import threading
import time
import uuid
import weakref
from dataclasses import dataclass, field
from typing import Any, Dict, List, Optional, Sequence

from deequ_tpu.exceptions import (
    AdmissionRejectedException,
    CorruptStateException,
    DeadlineExceededException,
    ServiceClosedException,
    ServiceOverloadedException,
    StaleEpochException,
    WorkerLostException,
)
from deequ_tpu.serve.admission import Slo, resolve_slo
from deequ_tpu.serve.lease import CoordinatorLease
from deequ_tpu.serve.ledger import RequestLedger
from deequ_tpu.serve.membership import FleetMembership
from deequ_tpu.serve.router import ConsistentHashRouter, route_digest
from deequ_tpu.serve.service import VerificationFuture, _TenantHealth
from deequ_tpu.serve.transport import (
    LoopbackTransport,
    SocketTransport,
    Transport,
    TransportClosedError,
    dump_blob,
    load_blob,
)


def _host_tpu_chips() -> int:
    """TPU chips this host exposes, counted WITHOUT jax (the coordinator
    never initialises a backend): one device node per chip."""
    import glob

    return len(glob.glob("/dev/accel[0-9]*")) or len(
        glob.glob("/dev/vfio/[0-9]*")
    )


def _worker_env(idx: int, n_workers: int) -> Dict[str, str]:
    """The environment of worker process ``idx``: the coordinator's own
    (workers inherit the compile-cache placement and every DEEQU_TPU_*
    setting), plus — where the host has a TPU chip for EACH of several
    workers — the libtpu settings that give this process chip ``idx`` and
    nothing else. A chip serves one process (docs/serving.md); with fewer
    chips than workers nothing is set and the surplus workers fail their
    spawn typed."""
    env = dict(os.environ)
    if n_workers > 1 and _host_tpu_chips() >= n_workers:
        env.update(
            TPU_VISIBLE_CHIPS=str(idx),
            TPU_CHIPS_PER_PROCESS_BOUNDS="1,1,1",
            TPU_PROCESS_BOUNDS="1,1,1",
            TPU_MESH_CONTROLLER_ADDRESS=f"localhost:{8476 + idx}",
            TPU_MESH_CONTROLLER_PORT=str(8476 + idx),
        )
    return env


@dataclass
class ProcessFleetConfig:
    """ProcessFleet knobs. ``transport`` / ``ledger_dir`` default from
    DEEQU_TPU_FLEET_TRANSPORT / DEEQU_TPU_FLEET_LEDGER_DIR; the shared
    fleet knobs default from the same envcfg vars the in-process fleet
    reads. ``ack_timeout`` bounds how long a submit waits for a
    worker's accept/refuse before declaring it lost (a worker that
    cannot even ack is not serving); ``spawn_timeout`` bounds worker
    startup (process spawn + import + hello)."""

    n_workers: Optional[int] = None
    transport: Optional[str] = None
    ledger_dir: Optional[str] = None
    ledger_mode: str = "recover"
    heartbeat_interval: Optional[float] = None
    stall_timeout: Optional[float] = None
    failover_retries: Optional[int] = None
    warm_plans: int = 8
    monitor: bool = True
    quarantine_after: int = 2
    worker_knobs: Optional[Dict[str, Any]] = None
    ack_timeout: float = 10.0
    spawn_timeout: float = 60.0
    lease_dir: Optional[str] = None
    lease_ttl: Optional[float] = None
    fencing: Optional[bool] = None

    def __post_init__(self):
        from deequ_tpu.envcfg import env_value

        if self.transport is None:
            self.transport = env_value("DEEQU_TPU_FLEET_TRANSPORT")
        if self.transport not in ("proc", "loopback"):
            raise ValueError(
                f"transport must be 'proc' or 'loopback', "
                f"got {self.transport!r}"
            )
        if self.ledger_dir is None:
            self.ledger_dir = env_value("DEEQU_TPU_FLEET_LEDGER_DIR")
        if self.heartbeat_interval is None:
            self.heartbeat_interval = env_value(
                "DEEQU_TPU_HEARTBEAT_INTERVAL"
            )
        self.heartbeat_interval = float(self.heartbeat_interval)
        if self.heartbeat_interval <= 0:
            raise ValueError("heartbeat_interval must be > 0 seconds")
        if self.failover_retries is None:
            self.failover_retries = env_value("DEEQU_TPU_FAILOVER_RETRIES")
        self.failover_retries = int(self.failover_retries)
        if self.failover_retries < 0:
            raise ValueError("failover_retries must be >= 0")
        if self.n_workers is None:
            self.n_workers = env_value("DEEQU_TPU_FLEET_WORKERS")
        if self.n_workers is not None and int(self.n_workers) < 1:
            raise ValueError("n_workers must be >= 1")
        if self.stall_timeout is None:
            # the ping/pong heartbeat lags one monitor tick behind the
            # worker's actual state; keep the stall verdict comfortably
            # past that lag
            self.stall_timeout = max(8 * self.heartbeat_interval, 2.0)
        self.stall_timeout = float(self.stall_timeout)
        if self.warm_plans < 0:
            raise ValueError("warm_plans must be >= 0")
        if self.ack_timeout <= 0:
            raise ValueError("ack_timeout must be > 0 seconds")
        self.worker_knobs = dict(self.worker_knobs or {})
        if self.lease_dir is None:
            self.lease_dir = env_value("DEEQU_TPU_LEASE_DIR")
        if self.lease_dir is None:
            # the natural home: the lease fences the same durable state
            # the ledger holds
            self.lease_dir = self.ledger_dir
        if self.lease_ttl is None:
            self.lease_ttl = env_value("DEEQU_TPU_LEASE_TTL")
        self.lease_ttl = float(self.lease_ttl)
        if self.lease_ttl <= 0:
            raise ValueError("lease_ttl must be > 0 seconds")
        if self.fencing is None:
            self.fencing = env_value("DEEQU_TPU_FENCING")
        if self.fencing is None:
            # default ON exactly when there is durable state to fence
            self.fencing = (
                self.ledger_dir is not None and self.lease_dir is not None
            )
        self.fencing = bool(self.fencing)
        if self.fencing and not self.lease_dir:
            raise ValueError(
                "fencing requires a lease_dir (or a ledger_dir to "
                "default it from)"
            )


class _Ack:
    """One in-flight submit offer's accept/refuse rendezvous. The
    receiver thread (or a loss handler) fills ``status``/``fields``
    exactly once and sets the event."""

    __slots__ = ("event", "status", "fields", "worker")

    def __init__(self, worker: int):
        self.event = threading.Event()
        self.status: Optional[str] = None
        self.fields: Optional[dict] = None
        self.worker = worker


@dataclass
class _PAssignment:
    """The coordinator's authoritative record of one accepted request —
    the in-RAM twin of its durable ledger frame. Blobs are pickled once
    at submit (a failover re-offer must not re-serialize a mutated
    table)."""

    accept_id: str
    future: Any
    tenant: Any
    digest: str
    work_blob: str
    tenant_blob: str
    slo: Any
    deadline_at: Optional[float]
    worker: int = -1
    failovers: int = 0


class _PWorker:
    """One process-fleet member: a transport endpoint plus the process
    (or loopback thread) behind it and its liveness state."""

    def __init__(self, idx: int, transport: Transport,
                 proc: Optional[subprocess.Popen] = None,
                 thread: Optional[threading.Thread] = None,
                 peer: Optional[Transport] = None):
        self.idx = idx
        self.transport = transport
        self.proc = proc
        self.thread = thread
        #: the worker-side loopback endpoint (None for processes) — the
        #: kill seam closes IT so the worker loop dies from its own side
        self.peer = peer
        self.pid: Optional[int] = None
        self.alive = True
        self.ready = threading.Event()
        self.warm_ack = threading.Event()
        self.stopped = threading.Event()
        self.last_pong = time.monotonic()
        self.queue_depth = 0
        self.receiver: Optional[threading.Thread] = None

    def process_alive(self) -> bool:
        if self.proc is not None:
            return self.proc.poll() is None
        return self.thread is not None and self.thread.is_alive()


#: the most recent process fleet, for the obs registry section
_ACTIVE_PFLEET: Optional[weakref.ReferenceType] = None


def _pfleet_section() -> dict:
    from deequ_tpu.obs.registry import (
        FENCING_REJECTIONS,
        LEDGER_APPENDS,
        PFLEET_REDISPATCHES,
        ZOMBIE_RESULTS_IGNORED,
    )

    fleet = _ACTIVE_PFLEET() if _ACTIVE_PFLEET is not None else None
    if fleet is None:
        return {
            "workers_alive": 0,
            "redispatches": PFLEET_REDISPATCHES.value,
            "ledger_appends": LEDGER_APPENDS.value,
            "fencing_rejections": FENCING_REJECTIONS.value,
            "zombie_results_ignored": ZOMBIE_RESULTS_IGNORED.value,
        }
    return fleet._section()


class ProcessFleet:
    """The process-isolated serving fleet (see module doc).

    ``resume_futures`` maps ledger accept ids to the ORIGINAL
    :class:`VerificationFuture` objects when the driver survived the
    coordinator (same-process resume); absent entries get fresh
    futures, exposed via :attr:`resumed`."""

    def __init__(self, config: Optional[ProcessFleetConfig] = None,
                 start: bool = True,
                 resume_futures: Optional[Dict[str, Any]] = None,
                 **knobs):
        global _ACTIVE_PFLEET

        self.config = (
            config if config is not None else ProcessFleetConfig(**knobs)
        )
        n = self.config.n_workers
        self.n_workers = int(n) if n is not None else 4
        self._tenant_health = _TenantHealth(self.config.quarantine_after)
        self._router = ConsistentHashRouter()
        self._workers: Dict[int, _PWorker] = {}
        self._assignments: Dict[str, _PAssignment] = {}
        self._acks: Dict[str, _Ack] = {}
        self._fingerprints: Dict[str, dict] = {}
        self._heat: Dict[str, int] = {}
        self._lock = threading.Lock()
        self._ack_lock = threading.Lock()
        # same discipline as the in-process fleet: loss handling and
        # submission serialize against each other (reentrant — an offer
        # that discovers a dead transport retires the worker inline)
        self._failover_lock = threading.RLock()
        self._closed = False
        self._ping_seq = 0
        self.workers_lost = 0
        self.requests_redispatched = 0
        #: accept_id -> future for ledger records replayed at startup
        self.resumed: Dict[str, Any] = {}
        self._ledger: Optional[RequestLedger] = None
        if self.config.ledger_dir:
            self._ledger = RequestLedger(
                self.config.ledger_dir, mode=self.config.ledger_mode
            )
        #: epoch fencing (serve/lease.py): acquire strictly above both
        #: the stored lease AND everything the ledger has witnessed, so
        #: a takeover outranks the previous holder even if the lease
        #: file itself was destroyed. 0 = fencing off.
        self._lease: Optional[CoordinatorLease] = None
        self._fenced: Optional[StaleEpochException] = None
        self.epoch = 0
        if self.config.fencing and self.config.lease_dir:
            self._lease = CoordinatorLease(
                self.config.lease_dir, ttl=self.config.lease_ttl
            )
            self.epoch = self._lease.acquire(
                min_epoch=(
                    self._ledger.max_epoch()
                    if self._ledger is not None else 0
                )
            )
        self.membership = FleetMembership(
            members=self._alive_ids,
            probe_of=self._probe_worker,
            on_loss=self._handle_loss,
            interval=self.config.heartbeat_interval,
            stall_timeout=self.config.stall_timeout,
        )
        for idx in range(self.n_workers):
            worker = self._spawn(idx)
            self._workers[idx] = worker
            self._router.add_worker(idx)
        _ACTIVE_PFLEET = weakref.ref(self)
        from deequ_tpu.obs.registry import REGISTRY

        REGISTRY.register_collector("pfleet", _pfleet_section)
        self._update_alive_gauge()
        self._replay_ledger(resume_futures or {})
        if start and self.config.monitor:
            self.membership.start()

    # -- spawning --------------------------------------------------------

    def _spawn(self, idx: int) -> _PWorker:
        if self.config.transport == "loopback":
            worker = self._spawn_loopback(idx)
        else:
            worker = self._spawn_proc(idx)
        worker.receiver = threading.Thread(
            target=self._receive_loop, args=(worker,), daemon=True,
            name=f"deequ-tpu-pfleet-rx-{idx}",
        )
        worker.receiver.start()
        # wait for hello, but not past the worker's own death: a process
        # that cannot start (on a TPU host, one that cannot get the chip —
        # a chip serves ONE process, docs/serving.md) exits at once, and
        # the spawn must fail typed then, not spawn_timeout later
        deadline = time.monotonic() + self.config.spawn_timeout
        while not worker.ready.wait(0.05):
            if worker.proc is not None and worker.proc.poll() is not None:
                why = (
                    "exited before saying hello "
                    f"(exit code {worker.proc.returncode})"
                )
            elif worker.stopped.is_set():
                why = "closed its channel before saying hello"
            elif time.monotonic() >= deadline:
                why = (
                    "did not say hello within "
                    f"{self.config.spawn_timeout:g}s of spawn"
                )
            else:
                continue
            self._retire_endpoint(worker)
            raise WorkerLostException(
                f"worker {idx} {why}", worker_ids=(idx,),
            )
        return worker

    def _spawn_proc(self, idx: int) -> _PWorker:
        import json
        import socket as socket_mod

        parent, child = socket_mod.socketpair()
        argv = [
            sys.executable, "-m", "deequ_tpu.serve.pworker",
            "--fd", str(child.fileno()), "--idx", str(idx),
        ]
        if self.config.worker_knobs:
            argv += ["--knobs", json.dumps(self.config.worker_knobs)]
        proc = subprocess.Popen(
            argv, pass_fds=(child.fileno(),),
            env=_worker_env(idx, self.n_workers),
        )
        child.close()
        return _PWorker(idx, SocketTransport(parent), proc=proc)

    def _spawn_loopback(self, idx: int) -> _PWorker:
        coord_end, worker_end = LoopbackTransport.pair()
        knobs = dict(self.config.worker_knobs)

        def _run():
            from deequ_tpu.serve.pworker import WorkerLoop

            WorkerLoop(worker_end, idx=idx, worker_knobs=knobs).run()

        thread = threading.Thread(
            target=_run, daemon=True, name=f"deequ-tpu-pworker-{idx}"
        )
        thread.start()
        return _PWorker(idx, coord_end, thread=thread, peer=worker_end)

    def _retire_endpoint(self, worker: _PWorker) -> None:
        """Tear down one worker's transport/process without failover
        bookkeeping (spawn failure, final stop)."""
        worker.transport.close()
        if worker.proc is not None and worker.proc.poll() is None:
            worker.proc.terminate()
            try:
                worker.proc.wait(timeout=5.0)
            except subprocess.TimeoutExpired:
                worker.proc.kill()
                worker.proc.wait(timeout=5.0)

    # -- the receiver ----------------------------------------------------

    def _receive_loop(self, worker: _PWorker) -> None:
        """One thread per worker: drains its transport and dispatches
        frames. Transport death (EOF, ECONNRESET — what SIGKILL looks
        like from here) or a torn frame retires the worker through the
        normal loss path."""
        while True:
            try:
                msg = worker.transport.recv(timeout=0.25)
            except TransportClosedError:
                break
            except CorruptStateException as e:
                # a torn mid-stream frame means the channel can never
                # re-synchronize (frames are sequential): worker loss,
                # recorded as such
                from deequ_tpu.ops.scan_engine import SCAN_STATS

                SCAN_STATS.record_degradation(
                    "pfleet_torn_frame", worker=worker.idx, error=str(e),
                )
                break
            if msg is None:
                continue
            self._dispatch_frame(worker, msg)
        worker.stopped.set()
        if worker.alive and not self._closed:
            self._handle_loss(worker.idx, WorkerLostException(
                f"worker {worker.idx} transport died "
                "(process killed or channel torn)",
                worker_ids=(worker.idx,),
            ), expected=worker)

    def _dispatch_frame(self, worker: _PWorker, msg: dict) -> None:
        kind = str(msg.get("t"))
        if kind == "hello":
            worker.pid = msg.get("pid")
            worker.last_pong = time.monotonic()
            worker.ready.set()
        elif kind in ("accept", "refuse"):
            with self._ack_lock:
                ack = self._acks.get(str(msg.get("id")))
                if ack is not None and not ack.event.is_set():
                    ack.status = kind
                    ack.fields = msg
                    ack.event.set()
        elif kind == "result":
            self._on_result(msg)
        elif kind == "pong":
            age = float(msg.get("heartbeat_age_s") or 0.0)
            worker.last_pong = time.monotonic() - age
            worker.queue_depth = int(msg.get("queue_depth") or 0)
            self._merge_quarantine(msg.get("quarantine_blob"))
        elif kind == "warm_ack":
            worker.warm_ack.set()
        elif kind == "stopped":
            self._merge_quarantine(msg.get("quarantine_blob"))
            worker.stopped.set()

    def _merge_quarantine(self, blob: Optional[str]) -> None:
        if not blob:
            return
        try:
            self._tenant_health.restore(
                load_blob(blob, "worker quarantine snapshot")
            )
        except CorruptStateException:
            # a quarantine snapshot that cannot decode merges nothing —
            # the next pong carries a fresh one
            pass

    def _on_result(self, msg: dict) -> None:
        accept_id = str(msg.get("id"))
        frame_epoch = int(msg.get("epoch") or 0)
        if self._lease is not None and (
            self._fenced is not None
            or (frame_epoch and frame_epoch < self.epoch)
        ):
            # a fenced-out coordinator must add NO effects — its
            # successor re-dispatched this work and owns its resolution
            # (the futures' gate would keep exactly-once regardless;
            # ignoring keeps the zombie's effect count at zero) — and a
            # result stamped with a predecessor's epoch is a zombie
            # worker's late echo
            from deequ_tpu.obs.registry import ZOMBIE_RESULTS_IGNORED
            from deequ_tpu.ops.scan_engine import SCAN_STATS

            ZOMBIE_RESULTS_IGNORED.inc()
            SCAN_STATS.record_degradation(
                "zombie_result_ignored", id=accept_id,
                frame_epoch=frame_epoch, epoch=self.epoch,
                fenced=self._fenced is not None,
            )
            return
        with self._lock:
            asg = self._assignments.get(accept_id)
        if asg is None:
            # late duplicate (the request was already resolved, shed,
            # or failed over and resolved elsewhere): the future's gate
            # would drop it anyway; the ledger already has its tombstone
            return
        self._merge_quarantine(msg.get("quarantine_blob"))
        payload = load_blob(msg["payload_blob"], "result payload")
        if msg.get("ok"):
            asg.future._resolve(payload)
        else:
            asg.future._reject(
                payload if isinstance(payload, BaseException)
                else WorkerLostException(
                    f"worker {asg.worker} reported a non-exception "
                    f"failure payload: {payload!r}",
                    worker_ids=(asg.worker,),
                )
            )

    # -- bookkeeping -----------------------------------------------------

    def _make_done(self, accept_id: str):
        """The future's coordinator-side resolution hook: drop the
        assignment and tombstone the ledger — wherever the resolution
        came from (worker result, failover shed, typed reject)."""

        def _done(f, ok):
            with self._lock:
                popped = self._assignments.pop(accept_id, None)
            if popped is not None and self._ledger is not None:
                try:
                    self._ledger.append_resolve(accept_id,
                                                epoch=self.epoch)
                except (OSError, ValueError):
                    # a tombstone lost to a closing/full ledger costs
                    # one redundant (gated) replay at resume, never a
                    # lost result
                    pass

        return _done

    _HEAT_CAP = 1024

    def _record_heat(self, digest: str, data, analyzers) -> None:
        """Caller holds ``self._lock``. Tracks digest heat AND the plan
        fingerprint warmup ships (programs don't serialize; shapes
        do)."""
        self._heat[digest] = self._heat.get(digest, 0) + 1
        if digest not in self._fingerprints:
            from deequ_tpu.serve.pworker import plan_fingerprint

            fp = plan_fingerprint(data, analyzers)
            if fp is not None:
                self._fingerprints[digest] = fp
        if len(self._heat) > self._HEAT_CAP:
            keep = dict(sorted(
                self._heat.items(), key=lambda kv: kv[1], reverse=True
            )[: self._HEAT_CAP // 2])
            self._heat = keep
            self._fingerprints = {
                d: fp for d, fp in self._fingerprints.items() if d in keep
            }

    def _alive_ids(self) -> List[int]:
        with self._lock:
            return sorted(i for i, w in self._workers.items() if w.alive)

    # -- membership probe ------------------------------------------------

    def _probe_worker(self, idx: int):
        """The FleetMembership probe leg: (process alive AND channel
        open, last heartbeat on the coordinator clock). Each probe also
        launches the next ping — the pong lands asynchronously via the
        receiver, so freshness lags one tick (stall_timeout covers
        that)."""
        with self._lock:
            worker = self._workers.get(idx)
        if worker is None or not worker.alive:
            return False, 0.0
        self._ping_seq += 1
        try:
            worker.transport.send({"t": "ping", "seq": self._ping_seq})
        except TransportClosedError:
            return False, 0.0
        return worker.process_alive(), worker.last_pong

    # -- fencing ---------------------------------------------------------

    def _fence(self, cause: StaleEpochException) -> None:
        """Fence PERMANENTLY: a coordinator that has been outranked once
        stays outranked (un-fencing would re-open split brain). Every
        subsequent submit re-raises typed from the stored cause."""
        if self._fenced is not None:
            return
        self._fenced = cause
        from deequ_tpu.obs.registry import FENCING_REJECTIONS
        from deequ_tpu.ops.scan_engine import SCAN_STATS

        FENCING_REJECTIONS.inc()
        SCAN_STATS.record_degradation(
            "stale_epoch_fenced", epoch=self.epoch,
            current_epoch=cause.current_epoch, holder=cause.holder,
        )

    def _check_fence(self) -> None:
        """The per-submit fencing guard: re-read the lease (cheap next
        to the fsync every durable accept pays) and refuse typed when a
        successor outranks us. No-op when fencing is off."""
        if self._lease is None:
            return
        if self._fenced is not None:
            from deequ_tpu.obs.registry import FENCING_REJECTIONS

            FENCING_REJECTIONS.inc()
            raise StaleEpochException(
                str(self._fenced),
                stale_epoch=self._fenced.stale_epoch,
                current_epoch=self._fenced.current_epoch,
                holder=self._fenced.holder,
            )
        try:
            self._lease.check()
        except StaleEpochException as e:
            self._fence(e)
            raise

    # -- submission ------------------------------------------------------

    def route(self, data, checks: Sequence = (),
              required_analyzers: Sequence = ()) -> Optional[int]:
        """The worker id a submission would land on (tests/bench script
        deterministic deaths against this)."""
        analyzers = list(required_analyzers)
        for check in checks:
            analyzers.extend(check.required_analyzers())
        return self._router.place(route_digest(data, analyzers))

    def submit(self, data, checks: Sequence = (),
               required_analyzers: Sequence = (), tenant=None, slo=None):
        """Enqueue one suite on its placed worker process; returns the
        future. Acceptance is DURABLE before this returns: the ledger
        frame fsyncs before the submit offer ships, so a coordinator
        killed at any later instant still owes (and can replay) exactly
        this request. Overload spill walks the ring exactly like the
        in-process fleet — every refusal is the worker's own typed
        backpressure, reconstructed from the wire. When fencing is on,
        a fenced-out (zombie) coordinator refuses here typed
        (:class:`StaleEpochException`) before any side effect."""
        self._check_fence()
        analyzers = list(required_analyzers)
        for check in checks:
            analyzers.extend(check.required_analyzers())
        digest = route_digest(data, analyzers)
        slo = resolve_slo(slo)
        with self._failover_lock:
            with self._lock:
                if self._closed:
                    raise ServiceClosedException(
                        "submit on a stopped ProcessFleet"
                    )
                self._record_heat(digest, data, analyzers)
            future = VerificationFuture(tenant)
            deadline_at = (
                future.submitted_at + slo.deadline_seconds
                if slo.deadline_seconds is not None else None
            )
            asg = _PAssignment(
                accept_id=uuid.uuid4().hex,
                future=future,
                tenant=tenant,
                digest=digest,
                work_blob=dump_blob(
                    (data, tuple(checks), tuple(required_analyzers))
                ),
                tenant_blob=dump_blob(tenant),
                slo=slo,
                deadline_at=deadline_at,
            )
            # record + chain BEFORE any frame ships: a worker fast
            # enough to answer with the result mid-submit must find the
            # assignment already registered
            future.accept_id = asg.accept_id
            future._on_done = self._make_done(asg.accept_id)
            with self._lock:
                self._assignments[asg.accept_id] = asg
            if self._ledger is not None:
                self._ledger.append_accept(
                    asg.accept_id,
                    tenant=tenant,
                    digest=digest,
                    slo_cls=slo.cls,
                    deadline_ms=slo.deadline_ms,
                    weight=slo.weight,
                    deadline_left_s=(
                        deadline_at - time.monotonic()
                        if deadline_at is not None else None
                    ),
                    work=(data, tuple(checks),
                          tuple(required_analyzers)),
                    quarantine=self._tenant_health.snapshot(),
                    epoch=self.epoch,
                )
            status, outcome = self._offer_walk(asg)
            if status == "accepted":
                return future
            # nobody took it: the acceptance is void — tombstone it and
            # surface the placed worker's typed refusal (or fleet death)
            with self._lock:
                self._assignments.pop(asg.accept_id, None)
            if self._ledger is not None:
                self._ledger.append_resolve(asg.accept_id,
                                            epoch=self.epoch)
            if status == "refused":
                raise outcome
            raise ServiceClosedException(
                "no alive workers in the process fleet "
                "(all lost; rejoin_worker or restart)"
            )

    def verify(self, data, checks: Sequence = (), **kw):
        """Synchronous convenience: submit + wait."""
        return self.submit(data, checks, **kw).result()

    def _offer_walk(self, asg: _PAssignment):
        """Offer one assignment around the ring from its digest, each
        alive worker once. Returns ``("accepted", wid)``, ``("refused",
        exc)`` (the FIRST — placed — worker's typed refusal), or
        ``("dead", None)``. Caller holds the failover lock."""
        refusal: Optional[ServiceOverloadedException] = None
        with self._lock:
            order = list(self._router.walk(asg.digest))
        for wid in order:
            with self._lock:
                worker = self._workers.get(wid)
            if worker is None or not worker.alive:
                continue
            outcome = self._offer(worker, asg)
            if outcome == "accept":
                asg.worker = wid
                return "accepted", wid
            if isinstance(outcome, StaleEpochException):
                # a WORKER fenced us: our epoch is stale for every
                # worker, not just this one — stop the walk, fence
                # permanently
                self._fence(outcome)
                return "refused", outcome
            if isinstance(outcome, ServiceOverloadedException):
                if refusal is None:
                    refusal = outcome
                continue
            # None / ServiceClosed: the worker was retired mid-offer —
            # keep walking the survivors
        if refusal is not None:
            return "refused", refusal
        return "dead", None

    def _offer(self, worker: _PWorker, asg: _PAssignment):
        """Ship one submit frame and wait for its accept/refuse.
        Returns ``"accept"``, a reconstructed typed refusal, or None
        when the worker died mid-offer (retired inline — caller holds
        the failover lock)."""
        frame = {
            "t": "submit",
            "id": asg.accept_id,
            "epoch": self.epoch,
            "work_blob": asg.work_blob,
            "tenant_blob": asg.tenant_blob,
            "slo": {"cls": asg.slo.cls, "weight": asg.slo.weight,
                    "deadline_ms": asg.slo.deadline_ms},
            "deadline_left_s": (
                max(asg.deadline_at - time.monotonic(), 1e-3)
                if asg.deadline_at is not None else None
            ),
            "quarantine_blob": dump_blob(self._tenant_health.snapshot()),
        }
        ack = _Ack(worker.idx)
        with self._ack_lock:
            self._acks[asg.accept_id] = ack
        try:
            try:
                worker.transport.send(frame)
            except TransportClosedError as e:
                self._handle_loss(worker.idx, WorkerLostException(
                    f"worker {worker.idx} channel died at offer: {e}",
                    worker_ids=(worker.idx,),
                ), skip=asg.accept_id)
                return None
            if not ack.event.wait(self.config.ack_timeout):
                # a worker that cannot even ACK within the window is
                # not serving: retire it (its other victims fail over;
                # THIS assignment continues its walk in the caller)
                self._handle_loss(worker.idx, WorkerLostException(
                    f"worker {worker.idx} did not ack within "
                    f"{self.config.ack_timeout:g}s",
                    worker_ids=(worker.idx,),
                ), skip=asg.accept_id)
                return None
        finally:
            with self._ack_lock:
                self._acks.pop(asg.accept_id, None)
        if ack.status == "accept":
            return "accept"
        if ack.status == "lost":
            return None
        return self._rebuild_refusal(ack.fields or {})

    @staticmethod
    def _rebuild_refusal(fields: dict):
        """Typed backpressure off the wire: same exception type, same
        structured retry fields, as if the worker's service had raised
        in-process."""
        cls = fields.get("cls")
        message = fields.get("message") or "worker refused admission"
        if cls == "ServiceClosedException":
            return ServiceClosedException(message)
        if cls == "StaleEpochException":
            return StaleEpochException(
                message,
                stale_epoch=fields.get("stale_epoch"),
                current_epoch=fields.get("current_epoch"),
                holder=fields.get("holder"),
            )
        kw = dict(
            queue_depth=fields.get("queue_depth"),
            retry_after_s=fields.get("retry_after_s"),
            slo_class=fields.get("slo_class"),
        )
        if cls == "AdmissionRejectedException":
            return AdmissionRejectedException(
                message, reason=fields.get("reason") or "class_budget",
                **kw,
            )
        return ServiceOverloadedException(message, **kw)

    # -- failover --------------------------------------------------------

    def kill_worker(self, idx: int, reason: str = "scripted kill -9"
                    ) -> int:
        """Chaos/ops seam — REAL process death: SIGKILL the worker
        process (loopback: sever its endpoint) and fail its accepted
        requests over. Returns how many were re-dispatched."""
        with self._lock:
            worker = self._workers.get(idx)
        if worker is None or not worker.alive:
            return 0
        if worker.proc is not None:
            if worker.proc.poll() is None:
                os.kill(worker.proc.pid, signal.SIGKILL)
                try:
                    worker.proc.wait(timeout=10.0)
                except subprocess.TimeoutExpired:
                    pass
        elif worker.peer is not None:
            worker.peer.close()
        return self._handle_loss(idx, WorkerLostException(
            f"worker {idx} died: {reason}", worker_ids=(idx,)
        ))

    def _abort_acks_for(self, idx: int) -> None:
        """Wake any offer waiting on a now-dead worker BEFORE the loss
        handler queues on the failover lock — the offering thread HOLDS
        that lock while it waits."""
        with self._ack_lock:
            for ack in self._acks.values():
                if ack.worker == idx and not ack.event.is_set():
                    ack.status = "lost"
                    ack.event.set()

    def _handle_loss(self, idx: int, cause: WorkerLostException,
                     skip: Optional[str] = None,
                     expected: Optional[_PWorker] = None) -> int:
        """Retire a dead worker and replay its unresolved assignments
        onto survivors on their ORIGINAL futures. ``skip`` names an
        assignment the caller is already walking (it must not be
        replayed underneath its own offer); ``expected`` guards a
        receiver thread's loss report against racing a rejoin under the
        same id."""
        self._abort_acks_for(idx)
        with self._failover_lock:
            with self._lock:
                worker = self._workers.get(idx)
                if (worker is None or not worker.alive or self._closed
                        or (expected is not None
                            and worker is not expected)):
                    return 0
                worker.alive = False
                self._router.remove_worker(idx)
                self.workers_lost += 1
            self._retire_endpoint(worker)
            self._update_alive_gauge()
            from deequ_tpu.obs.registry import FLEET_FAILOVERS
            from deequ_tpu.ops.scan_engine import SCAN_STATS

            FLEET_FAILOVERS.inc()
            with self._lock:
                victims = [
                    a for a in self._assignments.values()
                    if a.worker == idx and a.accept_id != skip
                    and not a.future.done()
                ]
            SCAN_STATS.record_degradation(
                "pworker_failover", worker=idx, tenants=len(victims),
                error=str(cause),
            )
            redispatched = 0
            for asg in victims:
                redispatched += self._redispatch(asg, idx, cause)
            self.requests_redispatched += redispatched
            return redispatched

    def _redispatch(self, asg: _PAssignment, lost_idx: int,
                    cause: WorkerLostException) -> int:
        """Replay ONE assignment onto a survivor. Deadline-expired
        victims shed typed on their original futures (never replayed
        stale); retries past ``failover_retries`` reject typed. Caller
        holds the failover lock."""
        from deequ_tpu.obs.registry import PFLEET_REDISPATCHES

        if (asg.deadline_at is not None
                and time.monotonic() >= asg.deadline_at):
            self._shed_expired_victim(asg, lost_idx)
            return 0
        asg.failovers += 1
        if asg.failovers > self.config.failover_retries:
            asg.future._reject(WorkerLostException(
                f"request for tenant {asg.tenant!r} lost worker "
                f"{lost_idx} and exhausted failover_retries="
                f"{self.config.failover_retries}",
                worker_ids=cause.worker_ids,
            ))
            return 0
        status, outcome = self._offer_walk(asg)
        if status == "accepted":
            PFLEET_REDISPATCHES.inc()
            return 1
        if status == "refused":
            asg.future._reject(outcome)
            return 0
        asg.future._reject(WorkerLostException(
            f"request for tenant {asg.tenant!r} lost worker {lost_idx} "
            "and no survivor remains",
            worker_ids=cause.worker_ids,
        ))
        return 0

    def _shed_expired_victim(self, asg: _PAssignment, lost_idx: int
                             ) -> None:
        from deequ_tpu.obs.registry import SERVE_SHED_BY_CLASS
        from deequ_tpu.ops.scan_engine import SCAN_STATS

        cls = asg.slo.cls if asg.slo is not None else "standard"
        waited = time.monotonic() - asg.future.submitted_at
        SCAN_STATS.record_degradation(
            "deadline_shed", tenant=asg.tenant, slo_class=cls,
            worker=lost_idx, at="pfleet_failover",
            waited_s=round(waited, 4),
        )
        SERVE_SHED_BY_CLASS[cls].inc()
        asg.future._reject(DeadlineExceededException(
            f"request for tenant {asg.tenant!r} lost worker {lost_idx} "
            f"after its {cls!r} SLO deadline already passed — shed at "
            "failover instead of replayed stale",
            tenant=asg.tenant, slo_class=cls,
            deadline_ms=(asg.slo.deadline_ms if asg.slo else None),
            waited_s=waited,
        ))

    # -- warmup ----------------------------------------------------------

    def _hot_fingerprints(self) -> List[dict]:
        with self._lock:
            hot = sorted(
                self._heat.items(), key=lambda kv: kv[1], reverse=True
            )
            return [
                self._fingerprints[d] for d, _ in hot
                if d in self._fingerprints
            ][: self.config.warm_plans]

    def _warm_worker(self, worker: _PWorker, plans: List[dict]) -> None:
        if not plans:
            return
        worker.warm_ack.clear()
        try:
            worker.transport.send({"t": "warm", "plans": plans})
        except TransportClosedError:
            return
        # best-effort: a joiner that never acks is caught by membership
        worker.warm_ack.wait(self.config.ack_timeout)

    def prewarm(self) -> None:
        """Ship every alive worker the fleet's hottest plan
        fingerprints; each replays the PlanKeys into its own cache.
        After a prewarm any survivor serves a dead worker's tenants
        without a first-request trace storm."""
        plans = self._hot_fingerprints()
        with self._lock:
            alive = [w for w in self._workers.values() if w.alive]
        for worker in alive:
            self._warm_worker(worker, plans)

    def rejoin_worker(self, idx: int) -> Optional[_PWorker]:
        """Bring a lost worker id back as a FRESH process, warmed from
        the coordinator's hot-fingerprint feed BEFORE it owns any ring
        arc."""
        with self._failover_lock:
            with self._lock:
                if self._closed:
                    raise ServiceClosedException("process fleet is stopped")
                existing = self._workers.get(idx)
                if existing is not None and existing.alive:
                    return existing
            worker = self._spawn(idx)
            self._warm_worker(worker, self._hot_fingerprints())
            with self._lock:
                self._workers[idx] = worker
                self._router.add_worker(idx)
            self._update_alive_gauge()
            from deequ_tpu.ops.scan_engine import SCAN_STATS

            SCAN_STATS.record_degradation(
                "pworker_rejoin", worker=idx, pid=worker.pid,
            )
            return worker

    # -- coordinator resume ----------------------------------------------

    def _replay_ledger(self, resume_futures: Dict[str, Any]) -> None:
        """Kill-and-resume: re-dispatch every accepted-but-untombstoned
        ledger record (the work a dead coordinator still owed) onto
        this fleet's workers — original futures where the driver
        survived, fresh ones otherwise. Exactly-once rides the futures'
        first-resolution-wins gate; deadlines resume minus the
        wall-clock spent dead."""
        if self._ledger is None:
            return
        outstanding = self._ledger.outstanding()
        if not outstanding:
            return
        from deequ_tpu.envcfg import env_value

        if not env_value("DEEQU_TPU_COORD_RESUME"):
            from deequ_tpu.ops.scan_engine import SCAN_STATS

            SCAN_STATS.record_degradation(
                "coord_resume_disabled", outstanding=len(outstanding),
            )
            return
        from deequ_tpu.obs.registry import PFLEET_RESUMED
        from deequ_tpu.ops.scan_engine import SCAN_STATS

        snap = self._ledger.latest_quarantine()
        if snap is not None:
            self._tenant_health.restore(snap)
        now_wall = time.time()
        with self._failover_lock:
            for accept_id, rec in outstanding.items():
                try:
                    tenant = RequestLedger.load_tenant(rec)
                    data, checks, required = RequestLedger.load_work(rec)
                except CorruptStateException as e:
                    # checksum passed but the pickle no longer decodes
                    # (e.g. a class renamed between incarnations):
                    # surface typed per-record, keep replaying the rest
                    SCAN_STATS.record_degradation(
                        "ledger_undecodable_record", id=accept_id,
                        error=str(e),
                    )
                    continue
                future = resume_futures.get(accept_id)
                if future is None:
                    future = VerificationFuture(tenant)
                slo_rec = rec.get("slo") or {}
                slo = Slo(
                    deadline_ms=slo_rec.get("deadline_ms"),
                    weight=float(slo_rec.get("weight", 1.0)),
                    cls=str(slo_rec.get("cls", "standard")),
                )
                left = None
                if rec.get("deadline_left_s") is not None:
                    dead_for = now_wall - float(
                        rec.get("accepted_wall", now_wall)
                    )
                    left = float(rec["deadline_left_s"]) - max(
                        dead_for, 0.0
                    )
                analyzers = list(required)
                for check in checks:
                    analyzers.extend(check.required_analyzers())
                asg = _PAssignment(
                    accept_id=accept_id,
                    future=future,
                    tenant=tenant,
                    digest=rec.get("digest")
                    or route_digest(data, analyzers),
                    work_blob=rec["work_blob"],
                    tenant_blob=rec["tenant_blob"],
                    slo=slo,
                    deadline_at=(
                        time.monotonic() + left
                        if left is not None else None
                    ),
                )
                future.accept_id = accept_id
                future._on_done = self._make_done(accept_id)
                with self._lock:
                    self._assignments[accept_id] = asg
                    self._record_heat(asg.digest, data, analyzers)
                if self.epoch and (
                    RequestLedger._epoch_of(rec) < self.epoch
                ):
                    # durable ownership claim BEFORE re-dispatch: the
                    # record's effective epoch becomes ours, so the
                    # zombie that accepted it loses every epoch-
                    # precedence comparison from here on
                    self._ledger.append_reaccept(accept_id, self.epoch)
                PFLEET_RESUMED.inc()
                self.resumed[accept_id] = future
                if left is not None and left <= 0:
                    self._shed_expired_victim(asg, -1)
                    continue
                status, outcome = self._offer_walk(asg)
                if status == "refused":
                    future._reject(outcome)
                elif status == "dead":
                    future._reject(WorkerLostException(
                        "resume replay found no alive workers",
                        worker_ids=(),
                    ))
        SCAN_STATS.record_degradation(
            "coord_resume", replayed=len(self.resumed),
        )

    # -- lifecycle -------------------------------------------------------

    def abandon(self) -> None:
        """Chaos/ops seam — simulated coordinator ``kill -9``, scoped to
        this object: freeze the bookkeeping (no drains, no tombstones,
        no failovers), sever every worker channel, drop the ledger
        handle. This is exactly what the OS does to a SIGKILLed
        coordinator's threads, sockets, and file handles — accepted
        futures stay unresolved, and only the durable ledger knows what
        was owed. A fresh ``ProcessFleet(ledger_dir=...,
        resume_futures=...)`` is the recovery path."""
        self.membership.stop()
        with self._lock:
            self._closed = True
            workers = list(self._workers.values())
        for worker in workers:
            self._retire_endpoint(worker)
            if worker.receiver is not None:
                worker.receiver.join(timeout=5.0)
        if self._ledger is not None:
            self._ledger.close()
        self._update_alive_gauge(0)

    def stop(self, drain: bool = True) -> List:
        """Stop the whole fleet: drain (or not) every worker, reap the
        processes, close the ledger. Returns the futures still
        unresolved."""
        self.membership.stop()
        with self._lock:
            if self._closed:
                return []
            self._closed = True
            workers = [w for w in self._workers.values() if w.alive]
        for worker in workers:
            worker.stopped.clear()
            try:
                worker.transport.send({"t": "stop", "drain": drain})
            except TransportClosedError:
                worker.stopped.set()
        deadline = time.monotonic() + (60.0 if drain else 10.0)
        for worker in workers:
            worker.stopped.wait(max(deadline - time.monotonic(), 0.1))
            self._retire_endpoint(worker)
            if worker.receiver is not None:
                worker.receiver.join(timeout=5.0)
        if self._ledger is not None:
            self._ledger.close()
        self._update_alive_gauge(0)
        with self._lock:
            return [
                a.future for a in self._assignments.values()
                if not a.future.done()
            ]

    def __enter__(self) -> "ProcessFleet":
        return self

    def __exit__(self, exc_type, exc, tb) -> None:
        self.stop(drain=exc_type is None)

    # -- introspection ---------------------------------------------------

    def _update_alive_gauge(self, value: Optional[int] = None) -> None:
        from deequ_tpu.obs.registry import PFLEET_WORKERS_ALIVE

        PFLEET_WORKERS_ALIVE.set(
            value if value is not None else len(self._alive_ids())
        )

    def _section(self) -> dict:
        from deequ_tpu.obs.registry import (
            FENCING_REJECTIONS,
            LEDGER_APPENDS,
            ZOMBIE_RESULTS_IGNORED,
        )

        with self._lock:
            workers = {
                str(i): {
                    "alive": w.alive,
                    "pid": w.pid,
                    "transport": (
                        "proc" if w.proc is not None else "loopback"
                    ),
                    "queue_depth": w.queue_depth if w.alive else 0,
                }
                for i, w in self._workers.items()
            }
            pending = sum(
                1 for a in self._assignments.values()
                if not a.future.done()
            )
        return {
            "workers_alive": sum(
                1 for w in workers.values() if w["alive"]
            ),
            "workers_lost": self.workers_lost,
            "redispatches": self.requests_redispatched,
            "requests_outstanding": pending,
            "resumed": len(self.resumed),
            "epoch": self.epoch,
            "fencing": self._lease is not None,
            "fenced": self._fenced is not None,
            "fencing_rejections": FENCING_REJECTIONS.value,
            "zombie_results_ignored": ZOMBIE_RESULTS_IGNORED.value,
            "ledger_appends": LEDGER_APPENDS.value,
            "ledger_path": (
                self._ledger.path if self._ledger is not None else None
            ),
            "workers": workers,
        }

    def stats(self) -> dict:
        return self._section()
