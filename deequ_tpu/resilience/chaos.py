"""Deterministic chaos engine — seeded fault schedules + invariant oracles.

The fault ladder is six rungs deep (I/O retry -> quarantine -> OOM bisect
-> encoded demote -> mesh reshard -> CPU fallback) and, until now, every
injector seam was exercised one at a time by hand-written tests. The only
credible way to trust the ladder under COMBINED faults is to fuzz it:
generate a seeded, replayable :class:`ChaosSchedule` that scripts every
existing injector seam into one timeline —

- ``scan``  — device faults at the scan engine's execute seam
  (``FaultInjectingScanHook``: oom / compile / lost / hang, optionally
  pinned to one mesh member the way per-chip XLA failures name chips);
- ``batch`` — transient/permanent batch-read faults
  (``FlakyBatchSource`` + ``FaultSchedule``);
- ``staging`` — slow reads stalling the ingest/staging pipeline
  (``FaultSchedule.delay_seconds``);
- ``fs``    — seeded I/O faults on the checkpoint filesystem
  (``FaultInjectingFileSystem``; the schedule's fs event also switches
  the run to checkpointed mode so the persistence seam is in play);
- ``worker`` — fleet-tier worker faults (round 12): scripted
  death / stall / rejoin of serving workers in a
  :class:`~deequ_tpu.serve.fleet.VerificationFleet`. A schedule with
  any worker event runs the FLEET scenario instead of the streaming
  one: the same batch partition becomes per-tenant suites submitted in
  waves to a 4-worker fleet, with the events applied between waves.
  Two PROCESS-fleet kinds ride the same seam (round 17): ``kill9``
  (a REAL ``kill -9`` on a worker process of a ledger-backed
  :class:`~deequ_tpu.serve.pfleet.ProcessFleet` — loss surfaces as
  transport EOF, failover must re-dispatch bit-identically) and
  ``coord_kill9`` (the COORDINATOR dies mid-wave and a fresh one
  resumes off the durable request ledger, onto the original futures).
  A third kind, ``partition`` (round 18), is the SPLIT-BRAIN seam:
  the coordinator is stalled-not-dead — a fresh coordinator resumes
  off the ledger while the old incarnation stays alive with live
  workers, then wakes mid-resume and tries to keep serving. The epoch
  fence (serve/lease.py) must refuse every zombie dispatch typed
  (``StaleEpochException``), with zero double-resolutions and the
  completed results bit-identical. Any schedule with those kinds runs
  the process-fleet scenario;
- ``load``  — overload faults (round 15, the admission tier): scripted
  OPEN-LOOP SPIKES (a flood tenant bursts tight-deadline best_effort
  submissions mid-wave, no pacing) and SLOW-TENANT stalls (the worker
  a tenant routes to wedges briefly — queue depth builds, deadlines
  expire) over the same 4-worker fleet scenario, with every wave
  submission carrying a real SLO class (t0 critical, t1/t2 standard,
  t3 best_effort). No worker dies: the seam fuzzes admission control,
  the deadline-aware fair queue, and the brownout ladder, not
  failover;
- ``window`` — continuous-verification faults (round 20, the windowed
  streaming tier, deequ_tpu/windows): scripted LATE BURSTS (a slab of
  a batch's rows rewound behind the stream's watermark — the typed
  late-routing seam), DISORDER SPIKES (event-time jitter inside a
  batch), KILLS mid-window (the stream objects are dropped and
  resumed from the checksummed window-state store, replaying the
  checkpoint interval), RESUME REPLAYS (a DOUBLE kill-and-resume —
  the same closes replay twice through the exactly-once fence) and
  OVERLOAD spikes (the hub's brownout level rises, demoting late
  closes of non-critical streams to typed ``window_shed`` records).
  A schedule with any window event runs the STREAM scenario: three
  SLO-classed windowed streams (critical / standard / best_effort)
  folding seeded event-time batches, checked against a fault-free
  windowed reference over the SAME (late-burst/disorder-modified)
  batch timeline —

run one governed verification under it (``on_batch_error="skip"``,
``on_device_error="fallback"``, a `RunPolicy` budget), and then check the
system's OWN cross-cutting invariants as oracles:

1. typed outcome — the run returns a result or raises from the
   MetricCalculationException taxonomy; never a raw error;
2. termination — wall clock bounded by ``run_deadline`` (+ slack for
   host overhead);
3. bit-identity-or-degraded — every successful metric equals, bit for
   bit, the fault-free reference over exactly the rows the result claims
   verified (full table, or total minus quarantined batches minus
   ``unverified_row_ranges``); failure metrics must be typed;
4. row accounting — unverified ranges well-formed, batch-aligned, and
   disjoint from quarantined batches;
5. fetch contract — device fetches never exceed scan passes (the PR-4
   one-fetch discipline under the fault ladder);
6. HBM ledger — ``total_resident_bytes()`` (the sum over all devices)
   returns to zero;
7. ledger consistency — quarantined batches all trace to injected
   faults; the run budget's total equals the sum of its per-rung
   charges; its ``io_retry`` charges equal the run's retry-telemetry
   attempts;
8. exactly-once futures (worker seam) — every future the fleet accepted
   resolves exactly once (a result or a typed error): none orphaned by
   a dead worker, none double-resolved by a stalled worker waking after
   its requests failed over (``VerificationFuture.resolve_count``);
9. exactly-once under overload (load seam) — every future the fleet
   ACCEPTED (admission refusals raise typed at submit and mint no
   future) still resolves exactly once, where an in-queue deadline
   SHED — a typed ``DeadlineExceededException`` on the original
   future — counts as a resolution: overload may change a request's
   outcome, never orphan or double-resolve it;
10. no priority inversion (load seam) — no ``critical`` request is
   shed while a same-plan ``best_effort`` request DISPATCHED on the
   same worker: a best_effort that resolved successfully before a
   co-queued critical's shed popped while that critical still waited,
   which the class-tiered queue's strict priority forbids;
11. exactly-once window closes (window seam) — every window the
   fault-free reference closes is, in the chaos run, emitted EXACTLY
   once (bit-identical metrics, kills/replays included) or shed TYPED
   (non-critical streams, only under a scripted overload spike);
   nothing emits twice through any number of kill-and-resume cycles,
   the critical stream's close set never shrinks, watermarks never
   regress, and a scripted late burst shows up in the typed late
   ledgers (dropped counts / quarantined side-output ranges), never
   in a closed window's rows.

Worker-seam schedules check oracles 1/2/3/5/8 (the streaming-specific
row-accounting and fetch/ledger oracles have no fleet analogue — a
tenant's suite either completes bit-identically after failover or
rejects typed); load-seam schedules check 1/2/3/9/10; window-seam
schedules check 1/2 plus oracle 11.

A failing schedule is reduced by :func:`shrink_schedule` — classic
delta debugging (ddmin) over the event list, re-running the oracles per
candidate — to a minimal reproducer serializable as a JSON fixture
(``tests/fixtures/chaos/``) that tier-1 replays bit-identically.
``simulate_drift=True`` deliberately perturbs the results of a faulted
run (a stand-in for a ladder bug that breaks recovery bit-identity), so
the oracle->shrink loop itself is testable end to end.

CLI::

    JAX_PLATFORMS=cpu XLA_FLAGS=--xla_force_host_platform_device_count=8 \
        python -m deequ_tpu.resilience.chaos --soak --n 200

runs N seeded schedules and exits nonzero on any oracle violation.
"""

from __future__ import annotations

import json
import math
import time
from dataclasses import dataclass, field
from random import Random
from typing import Callable, Dict, List, Optional, Tuple

#: scenario geometry — small enough that one schedule runs in ~a second
#: on the 8-virtual-device CPU mesh, large enough for several batches
N_ROWS = 1600
BATCH_ROWS = 400
TABLE_SEED = 11

#: injected hangs sleep this long, then RAISE (hang_release="error") —
#: self-terminating, so chaos runs need no per-call device watchdog. A
#: tight per-call deadline on this loaded CPU emulation fires spuriously
#: on healthy 8-device dispatches, and the abandoned worker then
#: deadlocks the shared collective thread pool against the next dispatch
#: (a CPU-backend artifact; disjoint device sets run independently on
#: real hardware). Termination is still bounded: the run budget's
#: attempt-level watchdog covers genuinely-stuck attempts.
HANG_SECONDS = 0.6

#: wall-clock slack the termination oracle grants over run_deadline
#: (host-side packing/trace work is not budget-preemptible)
TERMINATION_SLACK = 2.0

_SCAN_KINDS = ("oom", "compile", "lost", "hang")
_SEAMS = ("scan", "batch", "staging", "fs", "worker", "load", "window")

#: fleet scenario geometry (worker seam): the scenario table splits into
#: one slice per tenant, each submitted once per wave; worker events
#: apply WHILE a wave is in flight (submitted, not yet gathered), so
#: "mid-load" is scripted, not racy. Slice sizes are deliberately
#: UNEQUAL: the fleet routes by (schema, analyzers, rows), and equal
#: slices would share one digest — every tenant on one worker, the
#: other three untouchable by any schedule.
FLEET_N_WORKERS = 4
FLEET_WAVES = 3
FLEET_TENANT_ROWS = (250, 350, 450, 550)  # sums to N_ROWS
_WORKER_KINDS = ("death", "stall", "rejoin")

#: process-fleet scenario (round 17, kill -9 seam): fewer waves than
#: the in-process fleet — every worker is a real spawned process
#: (fork + import + per-process compiles), so each wave costs real
#: wall-clock; the scripted kills are the expensive part being tested
PFLEET_WAVES = 2
#: worker-seam kinds that select the PROCESS-fleet scenario
_PWORKER_KINDS = ("kill9", "rejoin", "coord_kill9", "partition")
_PWORKER_ONLY_KINDS = ("kill9", "coord_kill9", "partition")

#: fleet membership knobs for the scenario: a heartbeat probe every
#: 50ms, a worker declared lost after 0.3s of silence
FLEET_HEARTBEAT = 0.05
FLEET_STALL_TIMEOUT = 0.3

#: scripted worker stalls wedge the worker thread this long — longer
#: than FLEET_STALL_TIMEOUT, so membership declares the worker lost and
#: failover runs while it sleeps; when it wakes, its late resolutions
#: are dropped (oracle 8 watches the count)
WORKER_STALL_SECONDS = 0.8

#: load-seam (round 15) scenario geometry: the same 4-tenant slices,
#: each wave submission carrying an SLO class — t0 is the critical
#: tenant (generous deadline: it must survive anything the seam
#: scripts), t1/t2 standard, t3 best_effort with a deadline tight
#: enough that scripted stalls expire it in-queue
LOAD_TENANT_SLO = (
    ("critical", 20_000.0),
    ("standard", 10_000.0),
    ("standard", 10_000.0),
    ("best_effort", 1_500.0),
)
_LOAD_KINDS = ("spike", "slow_tenant")
#: spike submissions (the flood tenant's open-loop burst) are
#: best_effort with a deadline this tight — under the stall-built queue
#: most of a burst expires pre-dispatch, which is the point
LOAD_SPIKE_DEADLINE_MS = 500.0
#: per-worker queue bound for the load scenario: small enough that a
#: scripted burst reaches admission pressure (class budgets, brownout)
LOAD_MAX_PENDING = 24

#: window-seam (round 20) scenario geometry: three SLO-classed windowed
#: streams over seeded event-time batches — tumbling 10s windows,
#: watermark lag 2s, batches spanning 5s of event time each. The
#: best_effort deadline is tight enough that ordinary close lateness
#: (up to ~one batch span + lag) sheds it under a scripted overload
#: spike; standard sheds only on the latest closes; critical never
#: sheds by class. The standard stream runs the side_output late
#: policy so a late burst exercises the quarantine route too.
WINDOW_N_BATCHES = 12
WINDOW_BATCH_ROWS = 24
WINDOW_BATCH_SPAN_S = 5.0
WINDOW_SIZE_S = 10.0
WINDOW_LAG_S = 2.0
WINDOW_STREAM_SLO = (
    ("w_crit", "critical", 20_000.0, "drop"),
    ("w_std", "standard", 4_000.0, "side_output"),
    ("w_be", "best_effort", 400.0, "drop"),
)
_WINDOW_KINDS = (
    "late_burst", "disorder_spike", "kill", "resume_replay", "overload",
)


def _fast_retry():
    from deequ_tpu.resilience.retry import RetryPolicy

    return RetryPolicy(max_attempts=3, base_delay=0.0005, max_delay=0.002)


# -- schedule ----------------------------------------------------------------


@dataclass(frozen=True)
class ChaosSchedule:
    """One seeded, serializable fault timeline over the fixed scenario.

    ``events`` is a list of plain dicts (see the module docstring's seam
    catalog) — the unit the shrinker removes. Two runs of the same
    schedule inject the identical fault pattern (``FaultSchedule`` /
    ``FaultInjectingScanHook`` are pure functions of (seed, operation
    sequence)), which is what makes shrunk reproducers replayable."""

    seed: int
    events: Tuple[dict, ...] = ()
    run_deadline: float = 20.0
    max_total_attempts: int = 12
    on_budget_exhausted: str = "degrade"

    @property
    def n_batches(self) -> int:
        return (N_ROWS + BATCH_ROWS - 1) // BATCH_ROWS

    def with_events(self, events) -> "ChaosSchedule":
        return ChaosSchedule(
            seed=self.seed,
            events=tuple(dict(e) for e in events),
            run_deadline=self.run_deadline,
            max_total_attempts=self.max_total_attempts,
            on_budget_exhausted=self.on_budget_exhausted,
        )

    # -- (de)serialization — the fixture format --------------------------

    def to_dict(self) -> dict:
        return {
            "seed": self.seed,
            "events": [dict(e) for e in self.events],
            "run_deadline": self.run_deadline,
            "max_total_attempts": self.max_total_attempts,
            "on_budget_exhausted": self.on_budget_exhausted,
        }

    def to_json(self) -> str:
        # math.inf serializes as the JSON extension literal Infinity,
        # which json.loads round-trips — permanent faults survive disk
        return json.dumps(self.to_dict(), indent=2, sort_keys=True)

    @staticmethod
    def from_dict(raw: dict) -> "ChaosSchedule":
        return ChaosSchedule(
            seed=int(raw["seed"]),
            events=tuple(dict(e) for e in raw.get("events", ())),
            run_deadline=float(raw.get("run_deadline", 20.0)),
            max_total_attempts=int(raw.get("max_total_attempts", 12)),
            on_budget_exhausted=str(
                raw.get("on_budget_exhausted", "degrade")
            ),
        )

    @staticmethod
    def from_json(text: str) -> "ChaosSchedule":
        return ChaosSchedule.from_dict(json.loads(text))

    # -- generation ------------------------------------------------------

    @staticmethod
    def generate(seed: int) -> "ChaosSchedule":
        """Seeded schedule: 1-4 events drawn across the four seams, a
        run budget sized so that most schedules complete but heavy ones
        exhaust it (both outcomes are oracle-checked)."""
        from deequ_tpu.resilience.faults import FaultSchedule

        rng = Random(seed)
        n_batches = (N_ROWS + BATCH_ROWS - 1) // BATCH_ROWS
        events: List[dict] = []
        for _ in range(1 + rng.randrange(3)):
            seam = rng.choice(("scan", "scan", "batch", "batch", "fs"))
            if seam == "scan":
                kind = rng.choice(_SCAN_KINDS)
                times = (
                    FaultSchedule.PERMANENT
                    if rng.random() < 0.15
                    else 1 + rng.randrange(3)
                )
                device = (
                    rng.randrange(8) if rng.random() < 0.3 else None
                )
                events.append(
                    {
                        "seam": "scan",
                        "scan": rng.randrange(n_batches),
                        "kind": kind,
                        "times": times,
                        "device": device,
                    }
                )
            elif seam == "batch":
                times = (
                    FaultSchedule.PERMANENT
                    if rng.random() < 0.25
                    else 1 + rng.randrange(2)
                )
                events.append(
                    {
                        "seam": "batch",
                        "index": rng.randrange(n_batches),
                        "times": times,
                    }
                )
            else:
                events.append(
                    {"seam": "fs", "rate": round(0.05 + rng.random() * 0.1, 3)}
                )
        if rng.random() < 0.25:
            events.append(
                {
                    "seam": "staging",
                    "seconds": round(0.002 + rng.random() * 0.01, 4),
                    "rate": round(0.2 + rng.random() * 0.5, 3),
                }
            )
        return ChaosSchedule(
            seed=seed,
            events=tuple(events),
            run_deadline=20.0,
            max_total_attempts=6 + rng.randrange(9),
            on_budget_exhausted=(
                "raise" if rng.random() < 0.15 else "degrade"
            ),
        )

    @staticmethod
    def generate_worker(seed: int) -> "ChaosSchedule":
        """Seeded WORKER-seam schedule (the fleet scenario): scripted
        death / stall / rejoin events over the waves. Events are drawn
        in wave order (application order), tracking which workers are
        down so rejoins target actually-dead workers and at least one
        survivor always remains — a zero-survivor fleet is a separate
        typed-error path pinned by the fleet tests, not a fuzz target
        (every schedule here must have somewhere to fail over TO)."""
        rng = Random(seed)
        events: List[dict] = []
        down: set = set()
        for wave in range(FLEET_WAVES):
            if rng.random() >= 0.7 and events:
                continue
            up = [w for w in range(FLEET_N_WORKERS) if w not in down]
            kinds = []
            if len(up) > 1:
                # death and stall both retire the worker (a scripted
                # stall outlasts the membership timeout by design)
                kinds += ["death", "death", "stall"]
            if down:
                kinds += ["rejoin", "rejoin"]
            if not kinds:
                continue
            kind = rng.choice(kinds)
            if kind == "rejoin":
                worker = rng.choice(sorted(down))
                down.discard(worker)
            else:
                worker = rng.choice(up)
                down.add(worker)
            events.append(
                {
                    "seam": "worker",
                    "kind": kind,
                    "worker": worker,
                    "wave": wave,
                }
            )
        if not events:
            events.append(
                {"seam": "worker", "kind": "death",
                 "worker": rng.randrange(FLEET_N_WORKERS), "wave": 1}
            )
        # generous deadline: the fleet scenario pays per-worker program
        # compiles (4 distinct tenant shapes) before steady state
        return ChaosSchedule(
            seed=seed, events=tuple(events), run_deadline=30.0,
        )

    @staticmethod
    def generate_pworker(seed: int) -> "ChaosSchedule":
        """Seeded PROCESS-fleet schedule (the kill -9 seam): scripted
        ``kill9`` (real SIGKILL on a worker process), ``rejoin``, and
        at most one COORDINATOR event over the waves — ``coord_kill9``
        (coordinator death + ledger-backed resume) or ``partition``
        (split brain: the old coordinator survives as a zombie and
        must be epoch-fenced). Same survivor discipline as
        :meth:`generate_worker` — every schedule must leave somewhere
        to fail over TO. A coordinator event resets the down-set: the
        resumed coordinator spawns a full fresh fleet."""
        rng = Random(seed)
        events: List[dict] = []
        down: set = set()
        used_coord = False
        for wave in range(PFLEET_WAVES):
            if events and rng.random() < 0.5:
                continue
            up = [w for w in range(FLEET_N_WORKERS) if w not in down]
            kinds: List[str] = []
            if len(up) > 1:
                kinds += ["kill9", "kill9"]
            if down:
                kinds += ["rejoin"]
            if not used_coord:
                kinds += ["coord_kill9", "partition"]
            if not kinds:
                continue
            kind = rng.choice(kinds)
            if kind in ("coord_kill9", "partition"):
                used_coord = True
                down = set()
                events.append(
                    {"seam": "worker", "kind": kind, "wave": wave}
                )
                continue
            if kind == "rejoin":
                worker = rng.choice(sorted(down))
                down.discard(worker)
            else:
                worker = rng.choice(up)
                down.add(worker)
            events.append(
                {"seam": "worker", "kind": kind, "worker": worker,
                 "wave": wave}
            )
        if not events:
            events.append(
                {"seam": "worker", "kind": "kill9",
                 "worker": rng.randrange(FLEET_N_WORKERS),
                 "wave": PFLEET_WAVES - 1}
            )
        # process spawns + per-process compiles dominate the wall clock
        return ChaosSchedule(
            seed=seed, events=tuple(events), run_deadline=90.0,
        )

    @staticmethod
    def generate_load(seed: int) -> "ChaosSchedule":
        """Seeded LOAD-seam schedule (round 15): scripted open-loop
        spikes and slow-tenant stalls over the SLO-classed fleet
        scenario. Spikes name the tenant whose table floods (sharing
        its routing digest — so a spike on t0 co-queues best_effort
        floods with critical wave traffic, exactly what the
        no-priority-inversion oracle watches); slow_tenant events
        wedge the named tenant's placed worker briefly (queue depth
        builds, deadlines expire — load, not death: membership stays
        off and nothing fails over)."""
        rng = Random(seed)
        events: List[dict] = []
        for wave in range(FLEET_WAVES):
            if events and rng.random() < 0.3:
                continue
            tenant = rng.randrange(len(FLEET_TENANT_ROWS))
            roll = rng.random()
            if roll < 0.6:
                # a stall first: the wedge is what turns a burst into
                # queue depth (an unwedged CPU worker drains a spike
                # before any deadline can expire)
                events.append({
                    "seam": "load", "kind": "slow_tenant", "wave": wave,
                    "tenant": tenant,
                    "seconds": round(0.3 + rng.random() * 0.5, 3),
                })
            if roll >= 0.3:
                events.append({
                    "seam": "load", "kind": "spike", "wave": wave,
                    "tenant": tenant,
                    "burst": 6 + rng.randrange(12),
                })
        if not events:
            events.append({
                "seam": "load", "kind": "spike", "wave": 1,
                "tenant": rng.randrange(len(FLEET_TENANT_ROWS)),
                "burst": 8,
            })
        return ChaosSchedule(
            seed=seed, events=tuple(events), run_deadline=30.0,
        )

    @staticmethod
    def generate_window(seed: int) -> "ChaosSchedule":
        """Seeded WINDOW-seam schedule (round 20, the continuous
        windowed-verification tier): scripted late bursts, disorder
        spikes, mid-window kills (resume from the window-state store),
        resume replays (a DOUBLE kill — the same closes replay twice
        through the exactly-once fence) and overload spikes over the
        three-stream scenario. Data events (late_burst/disorder) draw
        batches >= 2 so the stream's watermark has actually advanced —
        a burst into a fresh stream is not late at all. At most one
        overload spike per schedule (the shed oracle wants an
        unambiguous window of legitimacy)."""
        rng = Random(seed)
        events: List[dict] = []
        used_overload = False
        for _ in range(1 + rng.randrange(3)):
            kind = rng.choice(
                ("late_burst", "late_burst", "disorder_spike", "kill",
                 "kill", "resume_replay", "overload")
            )
            if kind == "late_burst":
                events.append({
                    "seam": "window", "kind": "late_burst",
                    "batch": 2 + rng.randrange(WINDOW_N_BATCHES - 2),
                    "stream": rng.choice(
                        [s for s, _c, _d, _p in WINDOW_STREAM_SLO]
                    ),
                    "rows": 4 + rng.randrange(8),
                    "rewind_s": round(12.0 + rng.random() * 10.0, 3),
                })
            elif kind == "disorder_spike":
                events.append({
                    "seam": "window", "kind": "disorder_spike",
                    "batch": 2 + rng.randrange(WINDOW_N_BATCHES - 2),
                    "stream": rng.choice(
                        [s for s, _c, _d, _p in WINDOW_STREAM_SLO]
                    ),
                    "jitter_s": round(1.0 + rng.random() * 4.0, 3),
                })
            elif kind in ("kill", "resume_replay"):
                events.append({
                    "seam": "window", "kind": kind,
                    "batch": 1 + rng.randrange(WINDOW_N_BATCHES - 1),
                })
            elif not used_overload:
                used_overload = True
                events.append({
                    "seam": "window", "kind": "overload",
                    "batch": 1 + rng.randrange(WINDOW_N_BATCHES - 2),
                    "level": 1 + rng.randrange(2),
                    "batches": 2 + rng.randrange(4),
                })
        if not events:
            events.append({
                "seam": "window", "kind": "kill",
                "batch": WINDOW_N_BATCHES // 2,
            })
        return ChaosSchedule(
            seed=seed, events=tuple(events), run_deadline=30.0,
        )


# -- scenario ----------------------------------------------------------------


def _build_table():
    """Deterministic scenario table. Values are INTEGER-valued floats so
    every fold sum is exact in f64 regardless of merge order — the
    bit-identity oracle then holds across any chunking/bisection path
    the ladder takes."""
    import numpy as np

    from deequ_tpu.data.table import Column, ColumnarTable, DType

    rng = np.random.default_rng(TABLE_SEED)
    n = N_ROWS
    val = rng.integers(0, 1000, n).astype(np.float64)
    val_mask = np.ones(n, dtype=np.bool_)
    val_mask[rng.integers(0, n, n // 50)] = False
    cat = rng.integers(0, 8, n)
    return ColumnarTable(
        [
            Column(
                "id", DType.INTEGRAL,
                values=np.arange(n, dtype=np.int64),
                mask=np.ones(n, dtype=np.bool_),
            ),
            Column("val", DType.FRACTIONAL, values=val, mask=val_mask),
            Column(
                "cat", DType.INTEGRAL, values=cat,
                mask=np.ones(n, dtype=np.bool_),
            ),
        ]
    )


def _analyzers():
    """Analyzers whose fold algebra is EXACTLY associative on this
    integer-valued table (sums below 2^53, min/max, HLL register max):
    bit-identity then holds across ANY chunking/bisection/reshard path
    the ladder takes, which is what oracle 3 asserts. Welford-moment
    analyzers (StandardDeviation's (n, avg, m2) merge) are deliberately
    excluded — their merge is partition-sensitive at ulp scale by
    design (docs/numerics.md), so they cannot promise bit-identity
    across a bisected re-chunk and would fuzz the oracle."""
    from deequ_tpu.analyzers import (
        ApproxCountDistinct,
        Completeness,
        Maximum,
        Mean,
        Minimum,
        Size,
        Sum,
    )

    return [
        Size(),
        Completeness("val"),
        Mean("val"),
        Minimum("val"),
        Maximum("val"),
        ApproxCountDistinct("cat"),
        Sum("cat"),
    ]


def _check():
    from deequ_tpu.checks import Check, CheckLevel

    return Check(CheckLevel.ERROR, "chaos scenario").has_size(
        lambda s: s >= 0
    )


def _batch_slices(table, indices):
    """The scenario's batch partition: batch i = rows
    [i*BATCH_ROWS, min((i+1)*BATCH_ROWS, N_ROWS))."""
    import numpy as np

    out = []
    for i in indices:
        lo, hi = i * BATCH_ROWS, min((i + 1) * BATCH_ROWS, N_ROWS)
        idx = np.arange(lo, hi)
        out.append(
            type(table)([table[c].take(idx) for c in table.column_names])
        )
    return out


def _metric_rows(result) -> Dict[str, tuple]:
    """str(analyzer) -> ("ok", float) | ("fail", ExceptionTypeName)."""
    out = {}
    for analyzer, metric in result.metrics.items():
        if metric.value.is_success:
            out[str(analyzer)] = ("ok", metric.value.get())
        else:
            out[str(analyzer)] = (
                "fail", type(metric.value.exception).__name__,
            )
    return out


#: fault-free reference metrics per batch subset: the reference is a
#: pure, deterministic function of the fixed scenario and the batch
#: indices it covers (replay-determinism is separately asserted by the
#: fixture corpus), so a 200-schedule soak computes each distinct
#: partition once instead of once per schedule
_REF_CACHE: Dict[Tuple[int, ...], Dict[str, tuple]] = {}


def _reference_metrics(batches, num_rows, cache_key=None) -> Dict[str, tuple]:
    """Fault-free metrics over exactly ``batches`` through the SAME
    resilient per-batch pipeline the chaos run uses, so fold order — and
    therefore bits — match. Runs inside its own fault_state_scope;
    memoized per ``cache_key`` (the covered batch indices)."""
    from deequ_tpu.data.source import GeneratorBatchSource
    from deequ_tpu.data.streaming import StreamingTable
    from deequ_tpu.resilience.governance import fault_state_scope
    from deequ_tpu.verification import VerificationSuite

    if cache_key is not None and cache_key in _REF_CACHE:
        return _REF_CACHE[cache_key]
    if not batches:
        return {}
    schema = batches[0].schema
    source = GeneratorBatchSource(
        schema, lambda: iter(list(batches)), num_rows=num_rows
    )
    with fault_state_scope():
        result = VerificationSuite.do_verification_run(
            StreamingTable(source),
            [_check()],
            _analyzers(),
            on_batch_error="skip",
            on_device_error="fallback",
            retry_policy=_fast_retry(),
        )
    out = _metric_rows(result)
    if cache_key is not None:
        _REF_CACHE[cache_key] = out
    return out


# -- the run -----------------------------------------------------------------


@dataclass
class ChaosReport:
    """One schedule's run + oracle verdicts."""

    schedule: ChaosSchedule
    outcome: str  # "identical" | "degraded" | "exception:<Type>"
    violations: List[str] = field(default_factory=list)
    elapsed: float = 0.0
    metrics: Dict[str, tuple] = field(default_factory=dict)
    skipped: List[int] = field(default_factory=list)
    unverified: List[tuple] = field(default_factory=list)
    run_budget: dict = field(default_factory=dict)
    retry_stats: dict = field(default_factory=dict)
    scan_delta: dict = field(default_factory=dict)
    #: failed-I/O-try delta read THROUGH the unified obs registry
    #: (deequ_tpu/obs/registry — the read-through "retry" section):
    #: oracle 7 compares the budget's io_retry charges against this,
    #: proving the round-11 unification didn't fork the counters
    retry_observed: Optional[int] = None
    injected: List[tuple] = field(default_factory=list)
    resident_after: int = 0
    drifted: bool = False
    #: worker-seam (fleet scenario) future accounting — oracle 8's
    #: evidence: accepted / resolved-exactly-once / orphaned /
    #: multi-resolved counts plus the dropped late resolutions
    fleet: Dict[str, int] = field(default_factory=dict)
    #: load-seam per-future records (oracle 9/10's evidence): one dict
    #: per ACCEPTED submission — wave, tenant, SLO class, the worker it
    #: actually landed on, submit/resolve stamps, and the outcome
    #: ("ok" | "shed" | "fail:<Type>")
    load_records: List[dict] = field(default_factory=list)
    #: window-seam per-close records (oracle 11's evidence): one dict
    #: per window CLOSE observed across every resume — stream, SLO
    #: class, [start, end), and the outcome
    #: ("emitted" | "suppressed" | "shed")
    windows: List[dict] = field(default_factory=list)

    @property
    def failing(self) -> bool:
        return bool(self.violations)


def _install_fs_events(events, seed):
    """Register the ``chaosfs://`` scheme backed by a fault-injecting
    in-memory filesystem when the schedule has fs events. Returns
    (checkpoint_path, fs_schedule, restore_fn)."""
    from deequ_tpu.data.fs import _REGISTRY, register_filesystem
    from deequ_tpu.data.fs import InMemoryFileSystem
    from deequ_tpu.resilience.faults import (
        FaultInjectingFileSystem,
        FaultSchedule,
    )

    rates = [e["rate"] for e in events if e.get("seam") == "fs"]
    if not rates:
        return None, None, lambda: None
    fs_schedule = FaultSchedule(seed=seed, error_rate=max(rates))
    fs = FaultInjectingFileSystem(InMemoryFileSystem(), fs_schedule)
    prev = _REGISTRY.get("chaosfs")
    register_filesystem("chaosfs", lambda path: fs)

    def restore():
        if prev is None:
            _REGISTRY.pop("chaosfs", None)
        else:
            _REGISTRY["chaosfs"] = prev

    return "chaosfs://chaos/ck", fs_schedule, restore


def run_schedule(
    schedule: ChaosSchedule, simulate_drift: bool = False
) -> ChaosReport:
    """Run one schedule end to end: fault-free reference, chaos run under
    the composed injectors + run budget, then every invariant oracle.
    A schedule with any ``worker`` event runs the FLEET scenario
    (:func:`_run_worker_schedule`) instead of the streaming one.

    ``simulate_drift=True`` is the deliberately-broken-ladder mode: when
    any fault was injected, the run's successful metrics are perturbed
    by one ulp-scale epsilon before oracle checking — simulating a
    recovery path that silently loses bit-identity — so the oracles (and
    the shrinker on top of them) can be shown to catch a real ladder
    regression."""
    if any(e.get("seam") == "window" for e in schedule.events):
        return _run_window_schedule(
            schedule, simulate_drift=simulate_drift
        )
    if any(e.get("seam") == "load" for e in schedule.events):
        return _run_load_schedule(schedule, simulate_drift=simulate_drift)
    if any(
        e.get("seam") == "worker"
        and e.get("kind") in _PWORKER_ONLY_KINDS
        for e in schedule.events
    ):
        return _run_pworker_schedule(
            schedule, simulate_drift=simulate_drift
        )
    if any(e.get("seam") == "worker" for e in schedule.events):
        return _run_worker_schedule(schedule, simulate_drift=simulate_drift)
    from deequ_tpu.data.source import TableBatchSource
    from deequ_tpu.data.streaming import StreamingTable
    from deequ_tpu.ops.device_policy import install_scan_fault_hook
    from deequ_tpu.ops.scan_engine import SCAN_STATS, total_resident_bytes
    from deequ_tpu.resilience.faults import (
        FaultInjectingScanHook,
        FaultSchedule,
        FlakyBatchSource,
    )
    from deequ_tpu.resilience.governance import fault_state_scope
    from deequ_tpu.verification import VerificationSuite

    table = _build_table()
    n_batches = schedule.n_batches

    # fault-free reference over the full batch partition (same pipeline,
    # same fold order; memoized — every schedule shares it)
    ref = _reference_metrics(
        _batch_slices(table, range(n_batches)), N_ROWS,
        cache_key=tuple(range(n_batches)),
    )

    # compose the schedule's events into the injector seams
    batch_fail = {
        ("batch", int(e["index"])): float(e["times"])
        for e in schedule.events
        if e["seam"] == "batch"
    }
    staging = [e for e in schedule.events if e["seam"] == "staging"]
    batch_schedule = FaultSchedule(
        seed=schedule.seed,
        fail=batch_fail,
        delay_seconds=max((e["seconds"] for e in staging), default=0.0),
        delay_rate=max((e["rate"] for e in staging), default=1.0),
    )
    scan_faults = {}
    for e in schedule.events:
        if e["seam"] != "scan":
            continue
        scan_faults[int(e["scan"])] = (
            e["kind"],
            float(e["times"]),
            None if e.get("device") is None else int(e["device"]),
        )
    # hang_release="error": a hung call eventually surfaces UNAVAILABLE
    # instead of silently dispatching its stale program — on the CPU
    # test backend an abandoned worker's late mesh dispatch would
    # deadlock the shared collective thread pool against the resharded
    # mesh (see FaultInjectingScanHook docs)
    hook = FaultInjectingScanHook(
        scan_faults, hang_seconds=HANG_SECONDS, relative=True,
        hang_release="error",
    )
    ckpt, fs_schedule, restore_fs = _install_fs_events(
        schedule.events, schedule.seed
    )

    stream = StreamingTable(
        FlakyBatchSource(
            TableBatchSource(table, BATCH_ROWS), batch_schedule
        )
    )

    result = None
    exc: Optional[BaseException] = None
    from deequ_tpu.obs.registry import REGISTRY

    try:
        with fault_state_scope():
            install_scan_fault_hook(hook)
            # ledger capture goes THROUGH the unified registry (its
            # "scan"/"retry" sections are read-through views over
            # SCAN_STATS / RETRY_TELEMETRY): oracle 7 checking deltas
            # of THIS snapshot proves the unification didn't fork the
            # counters. Captured inside fault_state_scope — the scope
            # resets RETRY_TELEMETRY on entry and restores it on exit,
            # so the delta must bracket the run, not the scope.
            reg_before = REGISTRY.snapshot()
            t0 = time.monotonic()
            try:
                result = VerificationSuite.do_verification_run(
                    stream,
                    [_check()],
                    _analyzers(),
                    on_batch_error="skip",
                    on_device_error="fallback",
                    retry_policy=_fast_retry(),
                    checkpoint=ckpt,
                    run_deadline=schedule.run_deadline,
                    max_total_attempts=schedule.max_total_attempts,
                    on_budget_exhausted=schedule.on_budget_exhausted,
                )
            # deequ-lint: ignore[bare-except] -- the chaos driver's whole job is to observe ANY outcome; oracle 1 re-checks that it was typed
            except Exception as e:  # noqa: BLE001
                exc = e
            elapsed = time.monotonic() - t0
            reg_after = REGISTRY.snapshot()
    finally:
        # even a BaseException escaping the run (KeyboardInterrupt) must
        # not leave the fault-injecting chaosfs:// scheme registered
        restore_fs()
    scan_before = reg_before["scan"]
    scan_after = reg_after["scan"]

    injected = list(hook.injected) + list(batch_schedule.injected)
    if fs_schedule is not None:
        injected += list(fs_schedule.injected)

    report = ChaosReport(
        schedule=schedule,
        outcome=(
            f"exception:{type(exc).__name__}"
            if exc is not None
            else (
                "degraded"
                if (result.skipped_batches or result.unverified_row_ranges)
                else "identical"
            )
        ),
        elapsed=elapsed,
        metrics=_metric_rows(result) if result is not None else {},
        skipped=list(result.skipped_batches) if result is not None else [],
        unverified=(
            [tuple(r) for r in result.unverified_row_ranges]
            if result is not None
            else []
        ),
        run_budget=dict(result.run_budget) if result is not None else {},
        retry_stats=dict(result.retry_stats) if result is not None else {},
        retry_observed=(
            reg_after["retry"]["attempts"] - reg_before["retry"]["attempts"]
        ),
        scan_delta={
            k: scan_after[k] - scan_before[k]
            for k in (
                "scan_passes",
                "device_fetches",
                "budget_charges",
                "budget_exhaustions",
            )
        },
        injected=injected,
        resident_after=total_resident_bytes(),
    )

    if simulate_drift and injected and report.metrics:
        # deliberately-broken-ladder mode: nudge every successful metric
        # the way a recovery path that re-reads rows (or drops them)
        # would — the bit-identity oracle must catch this
        report.drifted = True
        report.metrics = {
            k: ("ok", v + 1e-9) if status == "ok" else (status, v)
            for k, (status, v) in report.metrics.items()
        }

    report.violations = _check_oracles(report, ref, exc, table)
    return report


# -- the fleet scenario (worker seam) ----------------------------------------


def _tenant_slices(table):
    """The fleet scenario's tenants: the scenario table split into
    ``FLEET_TENANT_ROWS``-sized slices (unequal on purpose — distinct
    row counts give distinct routing digests, so the tenants spread
    across the ring; see the geometry comment)."""
    import numpy as np

    out, lo = [], 0
    for rows in FLEET_TENANT_ROWS:
        idx = np.arange(lo, lo + rows)
        out.append(
            type(table)([table[c].take(idx) for c in table.column_names])
        )
        lo += rows
    return out


#: healthy per-tenant reference metrics, memoized across schedules (a
#: pure function of the fixed scenario slice)
_FLEET_REF_CACHE: Dict[int, Dict[str, tuple]] = {}


def _fleet_reference(tenant: int, table) -> Dict[str, tuple]:
    """Fault-free reference for one tenant: a direct per-tenant
    ``VerificationSuite`` run under the single-device view — the serial
    twin the serving layer's coalesced==serial contract (tier-1 `serve`)
    already pins bit-identical, and the fleet's failover re-dispatch
    must reproduce bit-for-bit (plans are deterministic)."""
    from deequ_tpu.parallel.mesh import use_mesh
    from deequ_tpu.resilience.governance import fault_state_scope
    from deequ_tpu.verification import VerificationSuite

    if tenant in _FLEET_REF_CACHE:
        return _FLEET_REF_CACHE[tenant]
    with fault_state_scope(), use_mesh(None):
        result = VerificationSuite.do_verification_run(
            table, [_check()], _analyzers()
        )
    out = _metric_rows(result)
    _FLEET_REF_CACHE[tenant] = out
    return out


def _apply_worker_event(fleet, event: dict) -> None:
    kind, worker = event["kind"], int(event["worker"])
    if kind == "death":
        fleet.kill_worker(worker, reason="chaos schedule")
    elif kind == "stall":
        fleet.stall_worker(worker, WORKER_STALL_SECONDS)
    elif kind == "rejoin":
        fleet.rejoin_worker(worker)
    else:
        raise ValueError(f"unknown worker event kind {kind!r}")


def _run_worker_schedule(
    schedule: ChaosSchedule, simulate_drift: bool = False
) -> ChaosReport:
    """The worker-seam scenario: ``FLEET_WAVES`` waves of per-tenant
    suites over a ``FLEET_N_WORKERS`` fleet, the schedule's worker
    events applied while their wave is in flight (submitted, not yet
    gathered), then oracles 1/2/3 + fetch contract + 8 — the
    streaming-specific row-accounting/ledger oracles have no fleet
    analogue (a tenant's suite either completes bit-identically after
    failover or rejects typed)."""
    from deequ_tpu.obs.registry import REGISTRY
    from deequ_tpu.serve.fleet import VerificationFleet

    table = _build_table()
    tenants = _tenant_slices(table)
    ref = {t: _fleet_reference(t, tbl) for t, tbl in enumerate(tenants)}

    by_wave: Dict[int, List[dict]] = {}
    for e in schedule.events:
        if e.get("seam") == "worker":
            by_wave.setdefault(int(e.get("wave", 0)), []).append(e)

    applied: List[tuple] = []
    gathered: List[tuple] = []  # (wave, tenant, future)
    exc: Optional[BaseException] = None
    reg_before = REGISTRY.snapshot()
    t0 = time.monotonic()
    # the scenario fleet: shared-compile-cache workers (see
    # FleetConfig.distinct_devices) so a steady-state dispatch is
    # milliseconds and FLEET_STALL_TIMEOUT cleanly separates "busy"
    # from "scripted stall"; the monitor arms only AFTER the warmup
    # wave + prewarm below — cold compiles would otherwise read as
    # stalls and every schedule would cascade into total fleet loss
    fleet = VerificationFleet(
        n_workers=FLEET_N_WORKERS,
        heartbeat_interval=FLEET_HEARTBEAT,
        stall_timeout=FLEET_STALL_TIMEOUT,
        distinct_devices=False,
        monitor=False,
    )
    try:
        warmup = [
            fleet.submit(
                tbl, [_check()],
                required_analyzers=_analyzers(), tenant=f"t{t}",
            )
            for t, tbl in enumerate(tenants)
        ]
        for future in warmup:
            future.result(timeout=schedule.run_deadline)
        fleet.prewarm()
        fleet.membership.start()
        for wave in range(FLEET_WAVES):
            wave_futures = []
            for t, tbl in enumerate(tenants):
                future = fleet.submit(
                    tbl, [_check()],
                    required_analyzers=_analyzers(), tenant=f"t{t}",
                )
                wave_futures.append((t, future))
            # the wave is in flight: apply this wave's scripted events
            for e in by_wave.get(wave, ()):
                _apply_worker_event(fleet, e)
                applied.append(
                    ("worker", e["kind"], int(e["worker"]), wave)
                )
            for t, future in wave_futures:
                gathered.append((wave, t, future))
                try:
                    future.result(timeout=schedule.run_deadline)
                # deequ-lint: ignore[bare-except] -- the chaos driver observes ANY per-future outcome; oracle 1 re-checks that it was typed
                except Exception:  # noqa: BLE001
                    pass
    # deequ-lint: ignore[bare-except] -- a submit on an all-dead fleet (or any driver error) becomes the report's outcome; oracle 1 checks it is typed
    except Exception as e:  # noqa: BLE001
        exc = e
    finally:
        fleet.stop(drain=True)
    elapsed = time.monotonic() - t0
    reg_after = REGISTRY.snapshot()

    metrics: Dict[str, tuple] = {}
    for wave, t, future in gathered:
        prefix = f"w{wave}/t{t}"
        if future._error is not None:
            metrics[prefix] = ("fail", type(future._error).__name__)
        elif future._result is not None:
            for name, row in _metric_rows(future._result).items():
                metrics[f"{prefix}/{name}"] = row
    rejected = sum(
        1 for _, _, f in gathered if f.done() and f._error is not None
    )
    scan_before, scan_after = reg_before["scan"], reg_after["scan"]
    report = ChaosReport(
        schedule=schedule,
        outcome=(
            f"exception:{type(exc).__name__}" if exc is not None
            else ("degraded" if rejected else "identical")
        ),
        elapsed=elapsed,
        metrics=metrics,
        scan_delta={
            k: scan_after[k] - scan_before[k]
            for k in ("scan_passes", "device_fetches")
        },
        injected=applied,
        fleet={
            "accepted": len(gathered),
            "resolved_once": sum(
                1 for _, _, f in gathered
                if f.done() and f.resolve_count == 1
            ),
            "orphaned": sum(1 for _, _, f in gathered if not f.done()),
            "multi_resolved": sum(
                1 for _, _, f in gathered if f.resolve_count > 1
            ),
            "late_resolutions": sum(
                f.late_resolutions for _, _, f in gathered
            ),
            "rejected": rejected,
            "workers_lost": fleet.workers_lost,
            "requests_redispatched": fleet.requests_redispatched,
        },
    )

    if simulate_drift and applied and report.metrics:
        report.drifted = True
        report.metrics = {
            k: ("ok", v + 1e-9) if status == "ok" else (status, v)
            for k, (status, v) in report.metrics.items()
        }

    report.violations = _check_worker_oracles(report, ref, exc)
    return report


def _check_worker_oracles(
    report: ChaosReport, ref: Dict[int, Dict[str, tuple]], exc
) -> List[str]:
    """The worker-seam oracle subset (see the module docstring)."""
    from deequ_tpu.exceptions import MetricCalculationException

    v: List[str] = []
    schedule = report.schedule

    # 1. typed outcome — the driver-level exception AND every rejected
    # future must come from the taxonomy
    if exc is not None and not isinstance(exc, MetricCalculationException):
        v.append(f"untyped outcome: {type(exc).__name__}: {exc}")
    for key, row in report.metrics.items():
        if row[0] == "fail" and not (
            row[1].endswith("Exception") or row[1].endswith("Error")
        ):
            v.append(f"future {key}: suspicious failure type {row[1]}")

    # 2. termination
    if report.elapsed > schedule.run_deadline * 1.5 + TERMINATION_SLACK:
        v.append(
            f"termination: {report.elapsed:.2f}s exceeded "
            f"run_deadline={schedule.run_deadline:g}s (+slack)"
        )

    # 8. exactly-once futures: every accepted future resolves exactly
    # once — none orphaned by a dead worker, none double-resolved by a
    # stalled worker waking after failover
    fl = report.fleet
    if fl.get("orphaned"):
        v.append(
            f"exactly-once: {fl['orphaned']} of {fl['accepted']} accepted "
            "futures never resolved (orphaned by a lost worker)"
        )
    if fl.get("multi_resolved"):
        v.append(
            f"exactly-once: {fl['multi_resolved']} futures applied more "
            "than one resolution"
        )
    if fl.get("resolved_once", 0) + fl.get("orphaned", 0) != fl.get(
        "accepted", 0
    ):
        v.append(
            "exactly-once: resolved_once + orphaned != accepted "
            f"({fl})"
        )

    # 8b. split-brain fencing (partition seam): every dispatch a zombie
    # coordinator attempted after losing the lease must have been
    # refused typed — zero stale-epoch effects reach the system
    if fl.get("zombie_unfenced"):
        v.append(
            f"fencing: {fl['zombie_unfenced']} zombie dispatches were "
            "ACCEPTED after a partition (epoch fence failed)"
        )
    n_partitions = sum(
        1 for row in report.injected if row[1] == "partition"
    )
    if n_partitions and fl.get("zombie_fenced", 0) < n_partitions:
        v.append(
            f"fencing: {n_partitions} partition(s) applied but only "
            f"{fl.get('zombie_fenced', 0)} zombie dispatches were fenced"
        )

    # fetch contract: the serving path's one-fetch-per-coalesced-batch
    # discipline bounds fetches by scan passes, failover included
    if report.scan_delta.get("device_fetches", 0) > report.scan_delta.get(
        "scan_passes", 0
    ):
        v.append(
            "fetch contract: "
            f"{report.scan_delta['device_fetches']} fetches > "
            f"{report.scan_delta['scan_passes']} scan passes"
        )

    # 3. bit-identity: every future that resolved with a result must
    # equal the tenant's healthy serial reference bit for bit —
    # re-dispatched or not (plans are deterministic)
    for key, (status, value) in report.metrics.items():
        if status != "ok":
            continue
        _, t_part, name = key.split("/", 2)
        exp = ref[int(t_part[1:])].get(name)
        if exp is None:
            v.append(f"metric {key}: no reference value")
        elif exp[0] != "ok":
            v.append(
                f"metric {key}: reference failed ({exp[1]}) but fleet "
                "run succeeded"
            )
        elif not _bit_identical(value, exp[1]):
            v.append(
                f"metric {key}: {value!r} != healthy reference "
                f"{exp[1]!r} (failover must be bit-identical)"
            )
    return v


# -- the process-fleet scenario (kill -9 seam, round 17) ---------------------


def _apply_pworker_event(state: dict, event: dict, resume_map) -> None:
    """One scripted process-fleet event, while its wave is in flight.
    ``kill9`` is a REAL SIGKILL on the worker process (the loss signal
    is transport EOF, exactly like host death); ``coord_kill9``
    abandons the coordinator object wholesale — what SIGKILL does to
    its threads, sockets, and ledger handle — and resumes a FRESH
    :class:`~deequ_tpu.serve.pfleet.ProcessFleet` off the durable
    ledger, onto the original futures (``resume_map``); ``partition``
    is the split-brain seam: the old coordinator is NOT abandoned — it
    survives with live workers while the fresh one resumes, then wakes
    mid-resume and attempts another dispatch, which the epoch fence
    must refuse typed (zombie accounting feeds the fencing oracle)."""
    from deequ_tpu.serve.pfleet import ProcessFleet

    kind = event["kind"]
    fleet = state["fleet"]
    if kind == "kill9":
        fleet.kill_worker(int(event["worker"]), reason="chaos kill -9")
    elif kind == "rejoin":
        fleet.rejoin_worker(int(event["worker"]))
    elif kind == "partition":
        from deequ_tpu.exceptions import StaleEpochException

        state["workers_lost"] += fleet.workers_lost
        state["redispatched"] += fleet.requests_redispatched
        # the zombie stays fully alive: threads, worker processes,
        # ledger handle — only the LEASE decides who owns the epoch
        state["zombies"].append(fleet)
        state["fleet"] = ProcessFleet(
            n_workers=FLEET_N_WORKERS,
            transport=state["transport"],
            ledger_dir=state["ledger_dir"],
            heartbeat_interval=FLEET_HEARTBEAT,
            stall_timeout=FLEET_STALL_TIMEOUT,
            monitor=False,
            resume_futures=resume_map(),
        )
        state["resumed"] += len(state["fleet"].resumed)
        # the zombie wakes mid-resume and tries to keep serving: its
        # dispatch must be refused by the epoch fence, not accepted
        try:
            state["zombies"][-1].submit(
                state["probe"], [_check()],
                required_analyzers=_analyzers(), tenant="t0",
            )
            state["zombie_unfenced"] += 1
        except StaleEpochException:
            state["zombie_fenced"] += 1
    elif kind == "coord_kill9":
        # the old incarnation's loss counters must survive the swap —
        # the report accounts for the whole timeline, not one fleet
        state["workers_lost"] += fleet.workers_lost
        state["redispatched"] += fleet.requests_redispatched
        fleet.abandon()
        state["fleet"] = ProcessFleet(
            n_workers=FLEET_N_WORKERS,
            transport=state["transport"],
            ledger_dir=state["ledger_dir"],
            heartbeat_interval=FLEET_HEARTBEAT,
            stall_timeout=FLEET_STALL_TIMEOUT,
            monitor=False,
            resume_futures=resume_map(),
        )
        state["resumed"] += len(state["fleet"].resumed)
    else:
        raise ValueError(f"unknown pworker event kind {kind!r}")


def _run_pworker_schedule(
    schedule: ChaosSchedule, simulate_drift: bool = False
) -> ChaosReport:
    """The PROCESS-fleet scenario (kill -9 seam): ``PFLEET_WAVES``
    waves of per-tenant suites over a ledger-backed
    :class:`~deequ_tpu.serve.pfleet.ProcessFleet` of REAL worker
    processes. ``kill9`` events SIGKILL a worker mid-wave — failover
    must re-dispatch its in-flight tenants bit-identically onto
    survivors; a ``coord_kill9`` kills the COORDINATOR mid-wave and
    resumes a fresh one off the durable request ledger, onto the
    original futures. Oracle 8 (exactly-once) then holds across BOTH
    process boundaries: no future orphaned by a dead worker OR a dead
    coordinator, none double-resolved by the ledger replay (the
    first-resolution-wins gate)."""
    import shutil
    import tempfile

    from deequ_tpu.obs.registry import REGISTRY
    from deequ_tpu.serve.pfleet import ProcessFleet

    table = _build_table()
    tenants = _tenant_slices(table)
    ref = {t: _fleet_reference(t, tbl) for t, tbl in enumerate(tenants)}

    by_wave: Dict[int, List[dict]] = {}
    for e in schedule.events:
        if e.get("seam") == "worker":
            by_wave.setdefault(int(e.get("wave", 0)), []).append(e)

    applied: List[tuple] = []
    gathered: List[tuple] = []  # (wave, tenant, future)
    all_futures: List = []
    exc: Optional[BaseException] = None
    ledger_dir = tempfile.mkdtemp(prefix="deequ-chaos-ledger-")
    state = {
        "fleet": None,
        "ledger_dir": ledger_dir,
        "transport": "proc",
        "workers_lost": 0,
        "redispatched": 0,
        "resumed": 0,
        # split-brain (partition) accounting: the surviving old
        # coordinators, and how their post-partition dispatches fared
        "zombies": [],
        "zombie_fenced": 0,
        "zombie_unfenced": 0,
        "probe": tenants[0],
    }

    def resume_map():
        # the driver survived the coordinator: resume onto the
        # ORIGINAL futures. Ids missing here (resolved in the race
        # window before the kill) are already tombstoned — the replay
        # skips them entirely
        return {
            f.accept_id: f for f in all_futures
            if not f.done() and getattr(f, "accept_id", None)
        }

    reg_before = REGISTRY.snapshot()
    t0 = time.monotonic()
    # monitor off: SIGKILL loss surfaces as transport EOF through the
    # receiver thread, which is immediate and deterministic — the
    # membership monitor's probe cadence would only add replay jitter
    state["fleet"] = ProcessFleet(
        n_workers=FLEET_N_WORKERS,
        transport="proc",
        ledger_dir=ledger_dir,
        heartbeat_interval=FLEET_HEARTBEAT,
        stall_timeout=FLEET_STALL_TIMEOUT,
        monitor=False,
    )
    try:
        # warmup wave: every worker process compiles its placed tenant
        # shapes before any scripted kill, then prewarm ships the hot
        # fingerprints fleet-wide so failover lands on warm survivors
        warmup = [
            state["fleet"].submit(
                tbl, [_check()],
                required_analyzers=_analyzers(), tenant=f"t{t}",
            )
            for t, tbl in enumerate(tenants)
        ]
        for future in warmup:
            future.result(timeout=schedule.run_deadline)
        state["fleet"].prewarm()
        for wave in range(PFLEET_WAVES):
            wave_futures = []
            for t, tbl in enumerate(tenants):
                future = state["fleet"].submit(
                    tbl, [_check()],
                    required_analyzers=_analyzers(), tenant=f"t{t}",
                )
                wave_futures.append((t, future))
                all_futures.append(future)
            # the wave is in flight: apply this wave's scripted events
            for e in by_wave.get(wave, ()):
                _apply_pworker_event(state, e, resume_map)
                applied.append(
                    ("worker", e["kind"], int(e.get("worker", -1)), wave)
                )
            for t, future in wave_futures:
                gathered.append((wave, t, future))
                try:
                    future.result(timeout=schedule.run_deadline)
                # deequ-lint: ignore[bare-except] -- the chaos driver observes ANY per-future outcome; oracle 1 re-checks that it was typed
                except Exception:  # noqa: BLE001
                    pass
    # deequ-lint: ignore[bare-except] -- a submit on an all-dead fleet (or any driver error) becomes the report's outcome; oracle 1 checks it is typed
    except Exception as e:  # noqa: BLE001
        exc = e
    finally:
        try:
            state["fleet"].stop(drain=True)
        finally:
            for zombie in state["zombies"]:
                try:
                    zombie.stop(drain=False)
                # deequ-lint: ignore[bare-except] -- zombie teardown is best-effort: a fenced coordinator's workers may already be gone
                except Exception:  # noqa: BLE001
                    pass
            shutil.rmtree(ledger_dir, ignore_errors=True)
    elapsed = time.monotonic() - t0
    reg_after = REGISTRY.snapshot()

    metrics: Dict[str, tuple] = {}
    for wave, t, future in gathered:
        prefix = f"w{wave}/t{t}"
        if future._error is not None:
            metrics[prefix] = ("fail", type(future._error).__name__)
        elif future._result is not None:
            for name, row in _metric_rows(future._result).items():
                metrics[f"{prefix}/{name}"] = row
    rejected = sum(
        1 for _, _, f in gathered if f.done() and f._error is not None
    )
    # scan deltas are coordinator-side only (the worker processes keep
    # their own registries): both stay 0 here, so the fetch-contract
    # oracle holds trivially — cross-process fetch accounting is the
    # worker tests' job, not the chaos driver's
    scan_before, scan_after = reg_before["scan"], reg_after["scan"]
    final = state["fleet"]
    report = ChaosReport(
        schedule=schedule,
        outcome=(
            f"exception:{type(exc).__name__}" if exc is not None
            else ("degraded" if rejected else "identical")
        ),
        elapsed=elapsed,
        metrics=metrics,
        scan_delta={
            k: scan_after[k] - scan_before[k]
            for k in ("scan_passes", "device_fetches")
        },
        injected=applied,
        fleet={
            "accepted": len(gathered),
            "resolved_once": sum(
                1 for _, _, f in gathered
                if f.done() and f.resolve_count == 1
            ),
            "orphaned": sum(1 for _, _, f in gathered if not f.done()),
            "multi_resolved": sum(
                1 for _, _, f in gathered if f.resolve_count > 1
            ),
            "late_resolutions": sum(
                f.late_resolutions for _, _, f in gathered
            ),
            "rejected": rejected,
            "workers_lost": state["workers_lost"] + final.workers_lost,
            "requests_redispatched": (
                state["redispatched"] + final.requests_redispatched
            ),
            "resumed": state["resumed"],
            "zombie_fenced": state["zombie_fenced"],
            "zombie_unfenced": state["zombie_unfenced"],
        },
    )

    if simulate_drift and applied and report.metrics:
        report.drifted = True
        report.metrics = {
            k: ("ok", v + 1e-9) if status == "ok" else (status, v)
            for k, (status, v) in report.metrics.items()
        }

    report.violations = _check_worker_oracles(report, ref, exc)
    return report


# -- the load scenario (overload seam, round 15) -----------------------------


def _apply_load_event(fleet, event: dict, submit_flood, applied) -> None:
    """One scripted load event, while its wave is in flight. ``spike``
    bursts open-loop flood submissions (no pacing, no gathering until
    the wave gathers); ``slow_tenant`` wedges the named tenant's PLACED
    worker briefly — queue pressure, not death (membership is off)."""
    kind = event["kind"]
    tenant = int(event["tenant"])
    if kind == "spike":
        for i in range(int(event["burst"])):
            submit_flood(tenant, i)
        applied.append(("load", "spike", tenant, int(event["burst"])))
    elif kind == "slow_tenant":
        seconds = float(event["seconds"])
        wid = fleet.route_of_tenant(tenant)
        if wid is not None:
            # the worker wedges at its NEXT batch take and the wave's
            # gather rides it out — anything queued behind the wedge
            # (this wave's traffic, a following spike) waits, and
            # tight-deadline requests expire in-queue while it sleeps
            fleet.stall_worker(wid, seconds)
        applied.append(("load", "slow_tenant", tenant, seconds))
    else:
        raise ValueError(f"unknown load event kind {kind!r}")


def _run_load_schedule(
    schedule: ChaosSchedule, simulate_drift: bool = False
) -> ChaosReport:
    """The load-seam scenario: the 4-tenant fleet waves with every
    submission carrying a real SLO class (:data:`LOAD_TENANT_SLO`),
    the schedule's spikes/stalls applied while their wave is in flight,
    then oracles 1/2/3/9/10. Admission refusals are TYPED submit-time
    outcomes (no future minted — counted, not gathered); in-queue
    deadline sheds are typed resolutions on accepted futures (oracle 9
    counts them as such)."""
    from deequ_tpu.exceptions import (
        DeadlineExceededException,
        ServiceOverloadedException,
    )
    from deequ_tpu.obs.registry import REGISTRY
    from deequ_tpu.serve.admission import Slo
    from deequ_tpu.serve.fleet import VerificationFleet

    table = _build_table()
    tenants = _tenant_slices(table)
    ref = {t: _fleet_reference(t, tbl) for t, tbl in enumerate(tenants)}

    by_wave: Dict[int, List[dict]] = {}
    for e in schedule.events:
        if e.get("seam") == "load":
            by_wave.setdefault(int(e.get("wave", 0)), []).append(e)

    records: List[dict] = []
    applied: List[tuple] = []
    refused = {cls: 0 for cls, _ in set(LOAD_TENANT_SLO)}
    exc: Optional[BaseException] = None
    reg_before = REGISTRY.snapshot()
    t0 = time.monotonic()
    # membership stays OFF: a scripted stall here is queue pressure the
    # admission tier must absorb, not a death for failover to mop up
    fleet = VerificationFleet(
        n_workers=FLEET_N_WORKERS,
        heartbeat_interval=FLEET_HEARTBEAT,
        stall_timeout=FLEET_STALL_TIMEOUT,
        distinct_devices=False,
        monitor=False,
        worker_knobs={
            "max_pending": LOAD_MAX_PENDING,
            "coalesce_window": 0.01,
        },
    )

    def route_of_tenant(t: int):
        # the digest must match the SUBMISSIONS' (checks included —
        # route_digest folds the check's analyzers in), or the stall
        # wedges a different worker than the tenant's traffic queues on
        return fleet.route(
            tenants[t], [_check()], required_analyzers=_analyzers()
        )

    fleet.route_of_tenant = route_of_tenant

    def submit(wave: int, t: int, cls: str, deadline_ms, tenant_name,
               kind: str):
        """One SLO-classed submission; records the ACTUAL worker it
        landed on (spill included) for the inversion oracle."""
        try:
            future = fleet.submit(
                tenants[t], [_check()],
                required_analyzers=_analyzers(), tenant=tenant_name,
                slo=Slo(deadline_ms=deadline_ms, cls=cls),
            )
        except ServiceOverloadedException as e:
            refused[cls] = refused.get(cls, 0) + 1
            records.append({
                "wave": wave, "tenant": t, "cls": cls, "kind": kind,
                "outcome": f"refused:{type(e).__name__}",
                "worker": None, "future": None,
            })
            return
        with fleet._lock:
            asg = fleet._assignments.get(future)
        records.append({
            "wave": wave, "tenant": t, "cls": cls, "kind": kind,
            "outcome": None,
            "worker": asg.worker if asg is not None else None,
            "future": future,
        })

    def submit_flood(t: int, i: int):
        submit(
            wave, t, "best_effort", LOAD_SPIKE_DEADLINE_MS,
            f"flood-t{t}-{i}", "spike",
        )

    try:
        # warmup wave: no deadlines, standard class — pays the compile
        # storms so scripted waves measure the admission tier, not XLA
        warmup = [
            fleet.submit(
                tbl, [_check()],
                required_analyzers=_analyzers(), tenant=f"t{t}",
                slo=Slo(cls="standard"),
            )
            for t, tbl in enumerate(tenants)
        ]
        for future in warmup:
            future.result(timeout=schedule.run_deadline)
        fleet.prewarm()
        for wave in range(FLEET_WAVES):
            wave_start = len(records)
            wave_events = by_wave.get(wave, ())
            # slow-tenant stalls apply BEFORE the wave submits: the
            # worker must already be wedged when traffic arrives, or an
            # instantaneous burst coalesces into one batch and drains
            # before the wedge takes effect (real overload is arrival
            # outpacing a slow server, not a fast server seeing a blip)
            for e in wave_events:
                if e["kind"] == "slow_tenant":
                    _apply_load_event(fleet, e, submit_flood, applied)
            # a beat for the idle worker to consume the wedge before
            # the wave queues behind it (deterministic ordering, not a
            # race: the un-wedged path is also correct, just unloaded)
            if any(e["kind"] == "slow_tenant" for e in wave_events):
                time.sleep(0.12)
            # class-priority submission order (critical first): the
            # inversion oracle's soundness leans on a critical having
            # been submitted BEFORE any best_effort it is compared to
            for t, (cls, deadline_ms) in enumerate(LOAD_TENANT_SLO):
                submit(wave, t, cls, deadline_ms, f"t{t}", "wave")
            for e in wave_events:
                if e["kind"] != "slow_tenant":
                    _apply_load_event(fleet, e, submit_flood, applied)
            for rec in records[wave_start:]:
                if rec["future"] is None:
                    continue
                try:
                    rec["future"].result(timeout=schedule.run_deadline)
                # deequ-lint: ignore[bare-except] -- the chaos driver observes ANY per-future outcome; oracles 1/9 re-check typedness and exactly-once
                except Exception:  # noqa: BLE001
                    pass
    # deequ-lint: ignore[bare-except] -- a driver-level error becomes the report's outcome; oracle 1 checks it is typed
    except Exception as e:  # noqa: BLE001
        exc = e
    finally:
        fleet.stop(drain=True)
    elapsed = time.monotonic() - t0
    reg_after = REGISTRY.snapshot()

    metrics: Dict[str, tuple] = {}
    sheds = {cls: 0 for cls, _ in LOAD_TENANT_SLO}
    for i, rec in enumerate(records):
        future = rec.pop("future")
        if future is None:
            continue  # refused at submit; outcome already recorded
        rec["submitted_at"] = future.submitted_at
        rec["resolved_at"] = future.resolved_at
        rec["resolve_count"] = future.resolve_count
        if not future.done():
            rec["outcome"] = "orphaned"
        elif isinstance(future._error, DeadlineExceededException):
            rec["outcome"] = "shed"
            sheds[rec["cls"]] = sheds.get(rec["cls"], 0) + 1
        elif future._error is not None:
            rec["outcome"] = f"fail:{type(future._error).__name__}"
        else:
            rec["outcome"] = "ok"
            prefix = f"w{rec['wave']}/{rec['kind']}{i}/t{rec['tenant']}"
            for name, row in _metric_rows(future._result).items():
                metrics[f"{prefix}/{name}"] = row

    accepted = [r for r in records if "resolve_count" in r]
    serve_b = reg_before.get("serve", {})
    serve_a = reg_after.get("serve", {})

    def serve_delta(key):
        b, a = serve_b.get(key) or {}, serve_a.get(key) or {}
        return {cls: a.get(cls, 0) - b.get(cls, 0) for cls in a}

    report = ChaosReport(
        schedule=schedule,
        outcome=(
            f"exception:{type(exc).__name__}" if exc is not None
            else (
                "degraded"
                if any(r["outcome"] != "ok" for r in records)
                else "identical"
            )
        ),
        elapsed=elapsed,
        metrics=metrics,
        injected=applied,
        load_records=records,
        fleet={
            "accepted": len(accepted),
            "resolved_once": sum(
                1 for r in accepted
                if r["outcome"] != "orphaned" and r["resolve_count"] == 1
            ),
            "orphaned": sum(
                1 for r in accepted if r["outcome"] == "orphaned"
            ),
            "multi_resolved": sum(
                1 for r in accepted if r["resolve_count"] > 1
            ),
            "shed": sum(sheds.values()),
            "shed_by_class": sheds,
            "refused": sum(refused.values()),
            "shed_counters": serve_delta("shed_by_class"),
            "admission_rejected_counters": serve_delta(
                "admission_rejected_by_class"
            ),
        },
    )

    if simulate_drift and applied and report.metrics:
        report.drifted = True
        report.metrics = {
            k: ("ok", v + 1e-9) if status == "ok" else (status, v)
            for k, (status, v) in report.metrics.items()
        }

    report.violations = _check_load_oracles(report, ref, exc)
    return report


def _check_load_oracles(
    report: ChaosReport, ref: Dict[int, Dict[str, tuple]], exc
) -> List[str]:
    """The load-seam oracle subset: 1 (typed), 2 (termination), 3
    (bit-identity of every COMPLETED result), 9 (exactly-once with shed
    counting as a typed resolution), 10 (no priority inversion)."""
    from deequ_tpu.exceptions import MetricCalculationException

    v: List[str] = []
    schedule = report.schedule

    # 1. typed outcome — driver exception, every rejected future, and
    # every admission refusal must come from the taxonomy
    if exc is not None and not isinstance(exc, MetricCalculationException):
        v.append(f"untyped outcome: {type(exc).__name__}: {exc}")
    for rec in report.load_records:
        out = rec["outcome"] or ""
        for tag in ("fail:", "refused:"):
            if out.startswith(tag):
                name = out[len(tag):]
                if not (
                    name.endswith("Exception") or name.endswith("Error")
                ):
                    v.append(
                        f"load future w{rec['wave']}/t{rec['tenant']}: "
                        f"suspicious {tag[:-1]} type {name}"
                    )

    # 2. termination
    if report.elapsed > schedule.run_deadline * 1.5 + TERMINATION_SLACK:
        v.append(
            f"termination: {report.elapsed:.2f}s exceeded "
            f"run_deadline={schedule.run_deadline:g}s (+slack)"
        )

    # 9. exactly-once under overload: every ACCEPTED future resolved
    # exactly once — a shed IS a typed resolution; none orphaned, none
    # double-resolved
    fl = report.fleet
    if fl.get("orphaned"):
        v.append(
            f"exactly-once: {fl['orphaned']} of {fl['accepted']} "
            "accepted futures never resolved under overload"
        )
    if fl.get("multi_resolved"):
        v.append(
            f"exactly-once: {fl['multi_resolved']} futures applied "
            "more than one resolution under overload"
        )
    if fl.get("resolved_once", 0) != fl.get("accepted", 0) - fl.get(
        "orphaned", 0
    ):
        v.append(f"exactly-once: accounting mismatch ({fl})")

    # 10. no priority inversion: a critical shed on worker w while a
    # best_effort submitted no earlier DISPATCHED on w before the shed
    # means the class-tiered queue popped past a waiting critical
    for c in report.load_records:
        if c["cls"] != "critical" or c["outcome"] != "shed":
            continue
        if c.get("worker") is None or c.get("resolved_at") is None:
            continue
        for b in report.load_records:
            if (
                b["cls"] == "best_effort"
                and b["outcome"] == "ok"
                and b.get("worker") == c["worker"]
                and b.get("resolved_at") is not None
                and b["submitted_at"] >= c["submitted_at"]
                and b["resolved_at"] < c["resolved_at"]
            ):
                v.append(
                    "priority inversion: critical request "
                    f"(w{c['wave']}/t{c['tenant']}) shed on worker "
                    f"{c['worker']} while best_effort "
                    f"(w{b['wave']}/{b['kind']}/t{b['tenant']}) "
                    "submitted after it dispatched there first"
                )

    # 3. bit-identity of every COMPLETED result: overload changes WHICH
    # requests run, never how
    for key, (status, value) in report.metrics.items():
        if status != "ok":
            continue
        t_part = key.split("/")[2]
        exp = ref[int(t_part[1:])].get(key.split("/", 3)[3])
        if exp is None:
            v.append(f"metric {key}: no reference value")
        elif exp[0] != "ok":
            v.append(
                f"metric {key}: reference failed ({exp[1]}) but the "
                "overloaded run succeeded"
            )
        elif not _bit_identical(value, exp[1]):
            v.append(
                f"metric {key}: {value!r} != unloaded serial reference "
                f"{exp[1]!r} (overload must never degrade computation)"
            )
    return v


# -- window scenario (round 20) ----------------------------------------------


def _window_analyzers():
    """The pane-fold analyzer set: every family the windowed engine's
    device fold supports (windows/engine.SUPPORTED_ANALYZERS), on
    integer-valued data so sums are exact and the per-window
    bit-identity half of oracle 11 holds across any kill/replay path."""
    from deequ_tpu.analyzers import (
        Completeness,
        Maximum,
        Mean,
        Minimum,
        Size,
        Sum,
    )

    return [
        Size(), Completeness("v"), Mean("v"), Minimum("v"), Maximum("v"),
        Sum("v"),
    ]


def _window_batches(schedule: ChaosSchedule) -> Dict[str, List[dict]]:
    """Per-stream event-time batch timelines with the schedule's DATA
    events (late_burst / disorder_spike) already applied — a pure
    function of the schedule, so the fault-free reference folds the
    SAME timeline and oracle 11's bit-identity is meaningful."""
    import numpy as np

    out: Dict[str, List[dict]] = {}
    for si, (sid, _cls, _dl, _pol) in enumerate(WINDOW_STREAM_SLO):
        rng = np.random.default_rng(schedule.seed * 7 + si)
        batches = []
        for b in range(WINDOW_N_BATCHES):
            lo = b * WINDOW_BATCH_SPAN_S
            ts = np.sort(
                rng.uniform(lo, lo + WINDOW_BATCH_SPAN_S, WINDOW_BATCH_ROWS)
            )
            v = np.floor(rng.uniform(-50.0, 51.0, WINDOW_BATCH_ROWS))
            v[rng.random(WINDOW_BATCH_ROWS) < 0.08] = np.nan
            batches.append({"ts": ts, "v": v})
        out[sid] = batches
    for e in schedule.events:
        if e.get("seam") != "window":
            continue
        sid = e.get("stream")
        b = int(e.get("batch", -1))
        if sid not in out or not (0 <= b < WINDOW_N_BATCHES):
            continue
        batch = out[sid][b]
        if e["kind"] == "late_burst":
            k = min(int(e.get("rows", 4)), WINDOW_BATCH_ROWS)
            ts = batch["ts"].copy()
            ts[:k] -= float(e.get("rewind_s", 12.0))
            batch["ts"] = ts
        elif e["kind"] == "disorder_spike":
            rng = np.random.default_rng(schedule.seed * 31 + b)
            batch["ts"] = batch["ts"] + rng.uniform(
                -float(e.get("jitter_s", 2.0)),
                float(e.get("jitter_s", 2.0)),
                WINDOW_BATCH_ROWS,
            )
    return out


def _window_spec_policy(stream_policy: str):
    from deequ_tpu.windows.spec import WatermarkPolicy, WindowSpec

    return (
        WindowSpec(WINDOW_SIZE_S, WINDOW_SIZE_S, time_column="ts"),
        WatermarkPolicy(WINDOW_LAG_S, stream_policy),
    )


def _window_reference(
    batch_map: Dict[str, List[dict]],
) -> Dict[str, Dict[float, dict]]:
    """Fault-free windowed reference: the same batch timelines through
    fresh streams — no kills, no state store, no overload. Returns
    stream id -> window end -> {"start", "metrics"} for every emitted
    close (the reference emits EVERY window: nothing sheds)."""
    from deequ_tpu.windows.engine import WindowedStream

    ref: Dict[str, Dict[float, dict]] = {}
    for sid, _cls, _dl, pol in WINDOW_STREAM_SLO:
        spec, policy = _window_spec_policy(pol)
        stream = WindowedStream(
            sid, _window_analyzers(), checks=[_check()],
            spec=spec, policy=policy, batch_rows=WINDOW_BATCH_ROWS,
        )
        closes = []
        for batch in batch_map[sid]:
            closes += stream.process_batch(batch)
        closes += stream.flush()
        ref[sid] = {
            c.end: {"start": c.start, "metrics": _metric_rows(c.result)}
            for c in closes
            if c.emitted
        }
    return ref


def _run_window_schedule(
    schedule: ChaosSchedule, simulate_drift: bool = False
) -> ChaosReport:
    """The window-seam scenario: three SLO-classed windowed streams
    fold the schedule's batch timelines through a StreamHub while the
    schedule scripts kills (resume from the window-state store),
    double-kill resume replays, and overload spikes; then oracle 11 +
    1/2. Each driver tick delivers one batch per stream; a freshly
    resumed stream catches up from its own ``next_batch_index``, so a
    replayed interval flows through the SAME per-batch path (and its
    already-emitted closes must hit the exactly-once fence)."""
    import tempfile

    from deequ_tpu.serve.admission import Slo
    from deequ_tpu.windows.service import StreamHub

    t0 = time.monotonic()
    report = ChaosReport(schedule=schedule, outcome="identical")
    batch_map = _window_batches(schedule)
    ref = _window_reference(batch_map)

    kills: Dict[int, int] = {}
    overloads: List[Tuple[int, int, int]] = []
    for e in schedule.events:
        if e.get("seam") != "window":
            continue
        if e["kind"] == "kill":
            kills[int(e["batch"])] = max(kills.get(int(e["batch"]), 0), 1)
        elif e["kind"] == "resume_replay":
            kills[int(e["batch"])] = 2
        elif e["kind"] == "overload":
            overloads.append((
                int(e["batch"]), int(e.get("level", 1)),
                int(e.get("batches", 2)),
            ))

    cls_of = {sid: cls for sid, cls, _dl, _pol in WINDOW_STREAM_SLO}
    closes_seen: List[dict] = []
    exc: Optional[BaseException] = None
    resumes = 0
    wm_regressions = 0
    final_state: Dict[str, dict] = {}

    with tempfile.TemporaryDirectory() as state_root:

        def new_hub() -> StreamHub:
            hub = StreamHub(state_root=state_root, checkpoint_every=2)
            for sid, cls, deadline_ms, pol in WINDOW_STREAM_SLO:
                spec, policy = _window_spec_policy(pol)
                hub.register_stream(
                    sid, _window_analyzers(), checks=[_check()],
                    slo=Slo(deadline_ms=deadline_ms, cls=cls),
                    spec=spec, policy=policy,
                    batch_rows=WINDOW_BATCH_ROWS,
                )
            return hub

        def record(sid: str, closes) -> None:
            for c in closes:
                closes_seen.append({
                    "stream": sid, "cls": cls_of[sid],
                    "start": c.start, "end": c.end,
                    "outcome": (
                        "emitted" if c.emitted
                        else "suppressed" if c.suppressed
                        else "shed"
                    ),
                    "metrics": (
                        _metric_rows(c.result) if c.emitted else None
                    ),
                })

        def feed_until(hub: StreamHub, tick: int, wm_seen: dict) -> None:
            """Deliver every batch <= ``tick`` a stream has not folded
            yet (one per tick in steady state; the catch-up replay
            after a resume)."""
            nonlocal wm_regressions
            for sid in hub.stream_ids:
                stream = hub.stream(sid)
                while stream.next_batch_index <= tick:
                    i = stream.next_batch_index
                    record(sid, hub.process_batch(sid, batch_map[sid][i]))
                    wm = stream.watermark
                    if wm < wm_seen.get(sid, float("-inf")):
                        wm_regressions += 1
                    wm_seen[sid] = wm

        hub = new_hub()
        wm_seen: Dict[str, float] = {}
        level_until = -1
        try:
            for tick in range(WINDOW_N_BATCHES):
                for (at, level, span) in overloads:
                    if at == tick:
                        hub.set_overload(level)
                        level_until = tick + span
                if tick == level_until:
                    hub.set_overload(0)
                feed_until(hub, tick, wm_seen)
                for _ in range(kills.get(tick, 0)):
                    # SIGKILL equivalent: the process state is GONE —
                    # only the window-state store survives
                    level = hub.overload_level
                    del hub
                    hub = new_hub()
                    hub.set_overload(level)
                    resumes += 1
                    wm_seen = {}
            feed_until(hub, WINDOW_N_BATCHES - 1, wm_seen)
            for sid in hub.stream_ids:
                record(sid, hub.stream(sid).flush())
                stream = hub.stream(sid)
                final_state[sid] = {
                    "late_rows": stream.late_rows,
                    "side_ranges": len(stream.side_ranges),
                    "sheds": len(stream.sheds),
                    "emitted": len(stream.emitted_windows),
                }
        # deequ-lint: ignore[bare-except] -- the chaos driver's whole job is to observe ANY outcome; oracle 1 re-checks that it was typed
        except BaseException as e:  # noqa: BLE001
            exc = e

    report.elapsed = time.monotonic() - t0
    report.windows = closes_seen
    emitted = [c for c in closes_seen if c["outcome"] == "emitted"]
    sheds = [c for c in closes_seen if c["outcome"] == "shed"]
    if simulate_drift and schedule.events and emitted:
        # deliberately-broken-resume mode: one emitted metric drifts by
        # one ulp — the bit-identity half of oracle 11 must catch it
        for c in emitted:
            for name, (status, value) in c["metrics"].items():
                if status == "ok" and isinstance(value, float) and value:
                    c["metrics"][name] = (
                        "ok", math.nextafter(value, math.inf)
                    )
                    report.drifted = True
                    break
            if report.drifted:
                break
    for c in emitted:
        for name, row in c["metrics"].items():
            report.metrics[f"w/{c['stream']}/{c['end']:g}/{name}"] = row
    report.fleet = {
        "emitted": len(emitted),
        "suppressed": sum(
            1 for c in closes_seen if c["outcome"] == "suppressed"
        ),
        "sheds": len(sheds),
        "resumes": resumes,
        "wm_regressions": wm_regressions,
        "late_rows": sum(s["late_rows"] for s in final_state.values()),
        "side_ranges": sum(
            s["side_ranges"] for s in final_state.values()
        ),
    }
    if exc is not None:
        report.outcome = f"exception:{type(exc).__name__}"
    elif sheds or report.fleet["suppressed"]:
        report.outcome = "degraded"
    report.violations = _check_window_oracles(report, ref, exc)
    return report


def _check_window_oracles(
    report: ChaosReport, ref: Dict[str, Dict[float, dict]], exc
) -> List[str]:
    """Oracle 11 (+ 1/2): every reference window emitted exactly once
    bit-identically or shed typed; critical never sheds; sheds only
    under a scripted overload spike; watermarks never regress; a
    scripted late burst lands in the typed late ledgers."""
    from deequ_tpu.exceptions import MetricCalculationException

    v: List[str] = []
    schedule = report.schedule

    # 1. typed outcome
    if exc is not None and not isinstance(exc, MetricCalculationException):
        v.append(f"untyped outcome: {type(exc).__name__}: {exc}")

    # 2. termination
    if report.elapsed > schedule.run_deadline * 1.5 + TERMINATION_SLACK:
        v.append(
            f"termination: {report.elapsed:.2f}s exceeded "
            f"run_deadline={schedule.run_deadline:g}s (+slack)"
        )
    if exc is not None:
        return v  # the rest of oracle 11 compares a COMPLETED run

    # 11. exactly-once window closes
    per_stream: Dict[str, Dict[str, List[dict]]] = {}
    for c in report.windows:
        per_stream.setdefault(c["stream"], {}).setdefault(
            c["outcome"], []
        ).append(c)
    had_overload = any(
        e.get("seam") == "window" and e.get("kind") == "overload"
        for e in schedule.events
    )
    for sid, expected in ref.items():
        buckets = per_stream.get(sid, {})
        emitted = buckets.get("emitted", [])
        shed = buckets.get("shed", [])
        emitted_ends = [c["end"] for c in emitted]
        if len(emitted_ends) != len(set(emitted_ends)):
            dupes = sorted(
                e for e in set(emitted_ends)
                if emitted_ends.count(e) > 1
            )
            v.append(
                f"exactly-once: stream {sid} emitted window(s) {dupes} "
                "more than once across kill-and-resume"
            )
        shed_ends = {c["end"] for c in shed}
        if set(emitted_ends) & shed_ends:
            v.append(
                f"exactly-once: stream {sid} both emitted and shed "
                f"window(s) {sorted(set(emitted_ends) & shed_ends)}"
            )
        covered = set(emitted_ends) | shed_ends
        if covered != set(expected):
            v.append(
                f"close completeness: stream {sid} covered "
                f"{sorted(covered)} but the fault-free reference closes "
                f"{sorted(expected)}"
            )
        cls = next(
            c for s, c, _d, _p in WINDOW_STREAM_SLO if s == sid
        )
        if cls == "critical" and shed:
            v.append(
                f"shed discipline: critical stream {sid} shed "
                f"{sorted(shed_ends)} — critical closes on deadline "
                "whatever the overload level"
            )
        if shed and not had_overload:
            v.append(
                f"shed discipline: stream {sid} shed {sorted(shed_ends)} "
                "with no overload event in the schedule"
            )
        # bit-identity of every emitted close against the reference
        for c in emitted:
            exp = expected.get(c["end"])
            if exp is None:
                continue  # already reported by completeness
            for name, row in (c["metrics"] or {}).items():
                want = exp["metrics"].get(name)
                if want is None:
                    v.append(
                        f"window {sid}/{c['end']:g}: metric {name} has "
                        "no reference value"
                    )
                elif row[0] != want[0] or (
                    row[0] == "ok" and not _bit_identical(row[1], want[1])
                ):
                    v.append(
                        f"window {sid}/{c['end']:g}: metric {name} "
                        f"{row!r} != fault-free reference {want!r}"
                    )

    # watermark monotonicity (within each stream incarnation)
    if report.fleet.get("wm_regressions"):
        v.append(
            f"watermark: {report.fleet['wm_regressions']} regression(s) "
            "observed — the close fence must be monotone"
        )

    # typed late routing: a scripted late burst must land in the late
    # ledgers (dropped counts / quarantined side-output ranges)
    had_burst = any(
        e.get("seam") == "window"
        and e.get("kind") == "late_burst"
        and int(e.get("batch", 0)) >= 2
        for e in schedule.events
    )
    if had_burst and not (
        report.fleet.get("late_rows") or report.fleet.get("side_ranges")
    ):
        v.append(
            "late routing: a scripted late burst left no trace in the "
            "typed late ledgers (late_rows / side-output ranges)"
        )
    return v


# -- oracles -----------------------------------------------------------------


def _check_oracles(
    report: ChaosReport, ref: Dict[str, tuple], exc, table
) -> List[str]:
    from deequ_tpu.exceptions import MetricCalculationException

    v: List[str] = []
    schedule = report.schedule

    # 1. typed outcome
    if exc is not None and not isinstance(exc, MetricCalculationException):
        v.append(
            f"untyped outcome: {type(exc).__name__}: {exc}"
        )

    # 2. termination within the run deadline (+ host slack)
    if report.elapsed > schedule.run_deadline * 1.5 + TERMINATION_SLACK:
        v.append(
            f"termination: {report.elapsed:.2f}s exceeded "
            f"run_deadline={schedule.run_deadline:g}s (+slack)"
        )

    # 5. HBM ledger returns to zero (nothing persisted may survive a
    # chaos run; bisection/fallback evictions must balance the ledger)
    if report.resident_after != 0:
        v.append(
            f"hbm ledger: {report.resident_after} resident bytes after "
            "the run"
        )

    if exc is not None:
        return v  # the remaining oracles compare a RESULT

    n_batches = schedule.n_batches

    # 4. row accounting: unverified ranges well-formed + batch-aligned,
    # quarantined indices valid, and the two never overlap
    skipped_rows = set()
    for i in report.skipped:
        if not (0 <= i < n_batches):
            v.append(f"quarantine: skipped batch {i} out of range")
            continue
        skipped_rows.update(
            range(i * BATCH_ROWS, min((i + 1) * BATCH_ROWS, N_ROWS))
        )
    if len(set(report.skipped)) != len(report.skipped):
        v.append("quarantine: duplicate skipped indices")
    unverified_rows = set()
    prev_stop = -1
    for start, stop in sorted(report.unverified):
        if not (0 <= start < stop <= N_ROWS):
            v.append(f"row accounting: malformed range ({start}, {stop})")
            continue
        if start < prev_stop:
            v.append("row accounting: overlapping unverified ranges")
        prev_stop = stop
        if start % BATCH_ROWS != 0:
            v.append(
                f"row accounting: range start {start} not batch-aligned"
            )
        unverified_rows.update(range(start, stop))
    if skipped_rows & unverified_rows:
        v.append(
            "row accounting: quarantined rows double-counted as "
            "unverified"
        )

    # 7a. quarantine consistency: every skipped batch traces to an
    # injected fault on that index
    injected_batches = {
        key[1]
        for (kind, key, _attempt) in (
            t for t in report.injected if len(t) == 3 and t[0] == "ioerror"
        )
        if isinstance(key, tuple) and key and key[0] == "batch"
    }
    for i in report.skipped:
        if i not in injected_batches:
            v.append(
                f"quarantine: batch {i} skipped without an injected fault"
            )

    # 7b. budget ledger consistency
    budget = report.run_budget
    if budget:
        charges = dict(budget.get("charges") or {})
        if budget.get("attempts") != sum(charges.values()):
            v.append(
                f"budget ledger: attempts={budget.get('attempts')} != "
                f"sum(charges)={sum(charges.values())}"
            )
        cap = budget.get("max_total_attempts")
        if (
            cap is not None
            and budget.get("exhausted") is None
            and budget.get("attempts", 0) > cap
        ):
            v.append("budget ledger: over cap without exhaustion")
        io_charged = charges.get("io_retry", 0)
        # read through the unified registry (report.retry_observed =
        # the registry "retry" section's attempts delta): if the
        # round-11 unification had forked the counters, the registry
        # view would drift from the budget ledger and this trips
        io_observed = (
            report.retry_observed
            if report.retry_observed is not None
            else report.retry_stats.get("attempts", 0)
        )
        if io_charged != io_observed:
            v.append(
                f"budget ledger: io_retry charges ({io_charged}) != "
                f"retry telemetry attempts ({io_observed})"
            )
        if report.retry_observed is not None and (
            report.retry_observed != report.retry_stats.get("attempts", 0)
        ):
            v.append(
                "budget ledger: registry retry view "
                f"({report.retry_observed}) != result.retry_stats "
                f"({report.retry_stats.get('attempts', 0)}) — the "
                "unified registry forked the counters"
            )

    # 6. fetch contract: at most one device->host fetch per scan pass
    # (the PR-4 discipline, preserved by every ladder rung)
    if report.scan_delta.get("device_fetches", 0) > report.scan_delta.get(
        "scan_passes", 0
    ):
        v.append(
            "fetch contract: "
            f"{report.scan_delta['device_fetches']} fetches > "
            f"{report.scan_delta['scan_passes']} scan passes"
        )

    # 3. bit-identity or exact degradation: successful metrics must equal
    # the fault-free reference over EXACTLY the verified rows; failure
    # metrics must be typed
    verified_batches = [
        i
        for i in range(n_batches)
        if i not in set(report.skipped)
        and not (
            unverified_rows
            & set(range(i * BATCH_ROWS, min((i + 1) * BATCH_ROWS, N_ROWS)))
        )
    ]
    if len(verified_batches) == n_batches:
        expected = ref
    else:
        surviving = _batch_slices(table, verified_batches)
        expected = _reference_metrics(
            surviving, sum(b.num_rows for b in surviving),
            cache_key=tuple(verified_batches),
        )
    for name, (status, value) in report.metrics.items():
        if status == "fail":
            # typed-failure names come from the taxonomy; anything else
            # leaked an unclassified error into a metric
            if not (
                value.endswith("Exception") or value.endswith("Error")
            ):
                v.append(f"metric {name}: suspicious failure type {value}")
            continue
        exp = expected.get(name)
        if exp is None:
            v.append(f"metric {name}: no reference value")
        elif exp[0] != "ok":
            v.append(
                f"metric {name}: reference failed ({exp[1]}) but chaos "
                "run succeeded"
            )
        elif not _bit_identical(value, exp[1]):
            v.append(
                f"metric {name}: {value!r} != reference {exp[1]!r} over "
                f"verified rows (batches {verified_batches})"
            )
    return v


def _bit_identical(a, b) -> bool:
    if isinstance(a, float) and isinstance(b, float):
        if math.isnan(a) and math.isnan(b):
            return True
        return a == b and math.copysign(1.0, a) == math.copysign(1.0, b)
    return a == b


# -- shrinker ----------------------------------------------------------------


def shrink_schedule(
    schedule: ChaosSchedule,
    failing: Optional[Callable[[ChaosSchedule], bool]] = None,
    simulate_drift: bool = False,
    max_runs: int = 48,
) -> Tuple[ChaosSchedule, int]:
    """Delta-debug a failing schedule down to a minimal reproducer.

    Classic ddmin over the event list: repeatedly try removing chunks of
    events, keeping any reduction that still fails the oracles (the
    ``failing`` predicate; default = ``run_schedule`` reports >= 1
    violation). Deterministic injection makes every candidate replayable,
    so the minimum found is a real reproducer, not a flake. Returns
    (minimal schedule, oracle runs spent)."""
    if failing is None:
        def failing(s: ChaosSchedule) -> bool:
            return run_schedule(s, simulate_drift=simulate_drift).failing

    runs = 1
    if not failing(schedule):
        return schedule, runs  # nothing to shrink
    events = list(schedule.events)
    granularity = 2
    while len(events) >= 2 and runs < max_runs:
        chunk = max(1, math.ceil(len(events) / granularity))
        reduced = False
        for lo in range(0, len(events), chunk):
            candidate = events[:lo] + events[lo + chunk:]
            if not candidate:
                continue
            runs += 1
            if failing(schedule.with_events(candidate)):
                events = candidate
                granularity = max(granularity - 1, 2)
                reduced = True
                break
            if runs >= max_runs:
                break
        if not reduced:
            if granularity >= len(events):
                break
            granularity = min(granularity * 2, len(events))
    return schedule.with_events(events), runs


# -- soak --------------------------------------------------------------------


def soak(
    n: int = 200,
    seed0: int = 0,
    simulate_drift: bool = False,
    verbose: bool = True,
    worker: bool = False,
    load: bool = False,
    window: bool = False,
) -> dict:
    """Run ``n`` seeded schedules; returns a summary with every failing
    seed and its shrunk reproducer. The CI entry point
    (``python -m deequ_tpu.resilience.chaos --soak``); ``worker=True``
    (CLI ``--worker``) soaks worker-seam schedules over the fleet
    scenario instead of the streaming one; ``load=True`` (CLI
    ``--load``) soaks load-seam schedules (scripted spikes +
    slow-tenant stalls under oracles 1/2/3/9/10); ``window=True``
    (CLI ``--window``) soaks window-seam schedules (round 20: late
    bursts, disorder, kill-and-resume, overload sheds under oracle
    11)."""
    import sys

    outcomes: Dict[str, int] = {}
    failures = []
    t0 = time.monotonic()
    if window:
        generate = ChaosSchedule.generate_window
    elif load:
        generate = ChaosSchedule.generate_load
    elif worker:
        generate = ChaosSchedule.generate_worker
    else:
        generate = ChaosSchedule.generate
    for seed in range(seed0, seed0 + n):
        schedule = generate(seed)
        report = run_schedule(schedule, simulate_drift=simulate_drift)
        outcomes[report.outcome] = outcomes.get(report.outcome, 0) + 1
        if report.failing:
            shrunk, runs = shrink_schedule(
                schedule, simulate_drift=simulate_drift
            )
            failures.append(
                {
                    "seed": seed,
                    "violations": report.violations,
                    "shrunk": shrunk.to_dict(),
                    "shrink_runs": runs,
                }
            )
            if verbose:
                print(
                    f"seed {seed}: FAIL {report.violations} "
                    f"(shrunk to {len(shrunk.events)} events)",
                    file=sys.stderr,
                )
        elif verbose and (seed - seed0) % 20 == 0:
            print(
                f"seed {seed}: {report.outcome} "
                f"({report.elapsed:.2f}s)",
                file=sys.stderr,
            )
    return {
        "schedules": n,
        "outcomes": outcomes,
        "failures": failures,
        "wall_seconds": round(time.monotonic() - t0, 2),
    }


def main(argv: Optional[List[str]] = None) -> int:
    import argparse
    import sys

    parser = argparse.ArgumentParser(
        prog="python -m deequ_tpu.resilience.chaos",
        description="deterministic chaos soak over the fault ladder",
    )
    parser.add_argument("--soak", action="store_true", help="run N seeds")
    parser.add_argument("--n", type=int, default=200)
    parser.add_argument("--seed", type=int, default=0)
    parser.add_argument(
        "--drift-sim", action="store_true",
        help="deliberately break bit-identity (oracle self-test: every "
        "faulted schedule must FAIL and shrink)",
    )
    parser.add_argument(
        "--replay", type=str, default=None,
        help="replay one schedule fixture (JSON path)",
    )
    parser.add_argument(
        "--worker", action="store_true",
        help="soak worker-seam schedules (fleet scenario: scripted "
        "worker death/stall/rejoin under oracles 1/2/3/fetch/8)",
    )
    parser.add_argument(
        "--load", action="store_true",
        help="soak load-seam schedules (round 15: scripted open-loop "
        "spikes + slow-tenant stalls over the SLO-classed fleet "
        "scenario under oracles 1/2/3/9/10 — exactly-once incl. typed "
        "sheds, no priority inversion)",
    )
    parser.add_argument(
        "--window", action="store_true",
        help="soak window-seam schedules (round 20: late bursts, "
        "disorder spikes, mid-window kill-and-resume and overload "
        "sheds over the three-stream windowed scenario under oracle "
        "11 — exactly-once bit-identical closes, typed late routing, "
        "critical streams never shed)",
    )
    args = parser.parse_args(argv)

    if args.replay:
        with open(args.replay) as f:
            schedule = ChaosSchedule.from_json(f.read())
        report = run_schedule(schedule)
        print(
            json.dumps(
                {
                    "outcome": report.outcome,
                    "violations": report.violations,
                    "elapsed": round(report.elapsed, 3),
                    "injected": [list(t) for t in report.injected],
                }
            )
        )
        return 1 if report.failing else 0

    n = args.n if args.soak else 20
    summary = soak(
        n=n, seed0=args.seed, simulate_drift=args.drift_sim,
        worker=args.worker, load=args.load, window=args.window,
    )
    print(json.dumps(summary, indent=2, default=str))
    if args.drift_sim:
        # self-test mode: every schedule that injected something must
        # have been CAUGHT — zero failures means the oracles went blind
        ok = len(summary["failures"]) > 0
        print(
            "drift-sim: oracles "
            + ("caught the broken ladder" if ok else "MISSED the drift"),
            file=sys.stderr,
        )
        return 0 if ok else 1
    return 1 if summary["failures"] else 0


if __name__ == "__main__":
    import os
    import sys

    code = main()
    sys.stdout.flush()
    sys.stderr.flush()
    # skip interpreter teardown: abandoned watchdog threads (hung-call
    # detection leaves them parked by design) can segfault inside XLA's
    # destructors at exit, turning a clean soak into a bogus nonzero
    os._exit(code)
