"""Process-fleet suite (serve/{transport,ledger,pworker,pfleet}.py,
round 17) — tier-1 `pfleet`.

Contracts pinned here:

- FRAME CODEC: the wire/ledger envelope is the resilience tier's
  checksummed format; a frame torn at ANY byte boundary (mid-header,
  mid-payload, bad magic, absurd length) surfaces typed
  ``CorruptStateException`` — never a hang, never garbage — while a
  clean EOF at a frame boundary reads as end-of-stream;
- BLOBS: lambda-bearing payloads (constraint closures) cross the
  process boundary (cloudpickle out, plain pickle in); undecodable
  blob bytes are typed state corruption;
- TYPED BACKPRESSURE OVER THE WIRE: a worker's
  ``ServiceOverloadedException`` family refusal serializes its
  STRUCTURED fields and the coordinator reconstructs the same type
  with the same retry schedule (``retry_after_s``, ``queue_depth``,
  ``slo_class``, admission ``reason``);
- DURABLE LEDGER: every acceptance is fsynced before its future is
  returned; accepted-minus-tombstoned is exactly what a dead
  coordinator still owed; a torn tail (crash mid-append) quarantines
  ONLY the damaged bytes to a ``.corrupt`` sidecar in recover mode
  (every prior record loads — the PR-13 torn-segment rule at frame
  granularity) and raises typed in raise mode;
- PLAN-FINGERPRINT WARMUP: traced programs don't serialize — warmup
  ships (schema, rows, analyzers) fingerprints and the joiner replays
  the PlanKey through its own ``build_serve_plan``;
- FLEET BIT-IDENTITY: loopback and subprocess fleets serve every
  tenant bit-identically to a healthy serial run; a REAL SIGKILL on a
  worker process degrades only its in-flight tenants, re-dispatched
  onto survivors on their ORIGINAL futures, exactly once;
- COORDINATOR KILL-AND-RESUME: abandoning the coordinator (the
  in-process twin of ``kill -9``: bookkeeping frozen, channels
  severed, ledger handle dropped without tombstones) and opening a
  fresh fleet on the same ledger replays every accepted future
  exactly once — with deadlines HONESTLY decayed by the wall-clock
  spent dead (an expired victim sheds typed, never replays stale).
"""

import io
import os
import time

import numpy as np
import pytest

from deequ_tpu import VerificationSuite
from deequ_tpu.analyzers import Completeness, Mean, Size, Sum
from deequ_tpu.data.table import Column, ColumnarTable, DType
from deequ_tpu.exceptions import (
    AdmissionRejectedException,
    CorruptStateException,
    DeadlineExceededException,
    ServiceClosedException,
    ServiceOverloadedException,
)
from deequ_tpu.parallel.mesh import use_mesh
from deequ_tpu.serve.ledger import (
    CORRUPT_SUFFIX,
    LEDGER_FILENAME,
    RequestLedger,
)
from deequ_tpu.serve.pfleet import ProcessFleet, ProcessFleetConfig
from deequ_tpu.serve.pworker import (
    _refusal_fields,
    plan_fingerprint,
    replay_fingerprints,
)
from deequ_tpu.serve.transport import (
    FRAME_HEADER_BYTES,
    LoopbackTransport,
    decode_frame,
    dump_blob,
    encode_frame,
    load_blob,
    read_frame,
)

pytestmark = pytest.mark.pfleet


# -- fixtures ----------------------------------------------------------------


def _table(n=64, seed=0):
    r = np.random.default_rng(seed)
    return ColumnarTable([
        Column("x", DType.FRACTIONAL, values=r.normal(100, 5, n),
               mask=r.random(n) > 0.05),
        Column("i", DType.INTEGRAL,
               values=r.integers(0, 50, n).astype(np.float64),
               mask=np.ones(n, bool)),
    ])


def _analyzers():
    return [Size(), Completeness("x"), Mean("x"), Sum("i")]


def _bits(value):
    import struct

    if isinstance(value, float):
        return struct.pack("<d", value)
    return value


def _assert_bit_identical(serial_result, served_result, label=""):
    assert serial_result.status == served_result.status, label
    for a, m1 in serial_result.metrics.items():
        m2 = served_result.metrics[a]
        assert m1.value.is_success == m2.value.is_success, (label, str(a))
        if m1.value.is_success:
            assert _bits(m1.value.get()) == _bits(m2.value.get()), (
                f"{label}: {a} serial={m1.value.get()!r} "
                f"fleet={m2.value.get()!r}"
            )


#: distinct row counts -> distinct routing digests, spreading tenants
#: across the ring (the fleet-test geometry rule)
def _tenant_tables(k=4, base=48):
    return {f"t{i}": _table(n=base + 16 * i, seed=300 + i)
            for i in range(k)}


def _loopback_fleet(**kw):
    kw.setdefault("transport", "loopback")
    kw.setdefault("n_workers", 2)
    kw.setdefault("monitor", False)
    kw.setdefault("worker_knobs", {"coalesce_window": 0.0})
    return ProcessFleet(**kw)


# -- frame codec -------------------------------------------------------------


def test_frame_roundtrip():
    for msg in (
        {"t": "ping", "seq": 7},
        {"t": "submit", "id": "a" * 32, "deadline_left_s": None,
         "slo": {"cls": "standard", "weight": 1.0, "deadline_ms": None}},
        {},
    ):
        assert decode_frame(encode_frame(msg)) == msg


def test_frame_stream_reads_to_clean_eof():
    a, b = {"t": "hello", "pid": 1}, {"t": "pong", "seq": 2}
    stream = io.BytesIO(encode_frame(a) + encode_frame(b))
    assert read_frame(stream) == a
    assert read_frame(stream) == b
    assert read_frame(stream) is None  # clean EOF at a frame boundary


def test_frame_torn_at_every_byte_boundary_is_typed():
    """A stream cut at ANY byte inside a frame is a typed torn frame —
    mid-header and mid-payload alike; only the zero-byte cut (a frame
    boundary) is a clean EOF."""
    whole = encode_frame({"t": "result", "id": "x" * 32, "ok": True,
                          "payload_blob": dump_blob({"k": 1.5})})
    for cut in range(len(whole)):
        stream = io.BytesIO(whole[:cut])
        if cut == 0:
            assert read_frame(stream) is None
            continue
        with pytest.raises(CorruptStateException):
            read_frame(stream)
    # a whole frame followed by a torn one: the first reads, the tear
    # is classified where it happens
    stream = io.BytesIO(whole + whole[: FRAME_HEADER_BYTES + 3])
    assert read_frame(stream) is not None
    with pytest.raises(CorruptStateException):
        read_frame(stream)


def test_frame_bad_magic_and_length_typed():
    whole = bytearray(encode_frame({"t": "ping"}))
    bad_magic = bytes([whole[0] ^ 0xFF]) + bytes(whole[1:])
    with pytest.raises(CorruptStateException):
        read_frame(io.BytesIO(bad_magic))
    bad_len = bytearray(whole)
    bad_len[8:16] = (1 << 40).to_bytes(8, "little")
    with pytest.raises(CorruptStateException):
        read_frame(io.BytesIO(bytes(bad_len)))
    flipped = bytearray(whole)
    flipped[-1] ^= 0x01  # payload bit flip -> crc mismatch
    with pytest.raises(CorruptStateException):
        read_frame(io.BytesIO(bytes(flipped)))


def test_blob_carries_closures_and_types_corruption():
    fn = load_blob(dump_blob(lambda x: x + 41))
    assert fn(1) == 42
    with pytest.raises(CorruptStateException):
        load_blob("!!not base64!!")
    with pytest.raises(CorruptStateException):
        load_blob(dump_blob({"k": 1})[:-10] + "AAAAAAAAAA")


def test_loopback_transport_close_semantics():
    a, b = LoopbackTransport.pair()
    a.send({"t": "ping", "seq": 1})
    assert b.recv(timeout=1.0) == {"t": "ping", "seq": 1}
    a.close()
    from deequ_tpu.serve.transport import TransportClosedError

    with pytest.raises(TransportClosedError):
        b.recv(timeout=1.0)
    with pytest.raises(TransportClosedError):
        b.send({"t": "pong"})


# -- typed backpressure over the wire ----------------------------------------


def test_refusal_fields_reconstruct_same_types():
    overload = ServiceOverloadedException(
        "queue full", queue_depth=17, retry_after_s=0.25,
        slo_class="standard",
    )
    rebuilt = ProcessFleet._rebuild_refusal(_refusal_fields(overload))
    assert type(rebuilt) is ServiceOverloadedException
    assert rebuilt.queue_depth == 17
    assert rebuilt.retry_after_s == 0.25
    assert rebuilt.slo_class == "standard"

    admission = AdmissionRejectedException(
        "class budget", reason="class_budget", queue_depth=9,
        retry_after_s=1.5, slo_class="best_effort",
    )
    rebuilt = ProcessFleet._rebuild_refusal(_refusal_fields(admission))
    assert type(rebuilt) is AdmissionRejectedException
    assert rebuilt.reason == "class_budget"
    assert rebuilt.slo_class == "best_effort"
    assert rebuilt.retry_after_s == 1.5

    closed = ProcessFleet._rebuild_refusal(
        {"cls": "ServiceClosedException", "message": "stopped"}
    )
    assert type(closed) is ServiceClosedException


# -- the durable ledger ------------------------------------------------------


def _mk_ledger(tmp_path, n_accepts=3, resolve_first=0, mode="recover"):
    ledger = RequestLedger(str(tmp_path), mode=mode)
    ids = []
    for i in range(n_accepts):
        accept_id = f"req{i:02d}" + "0" * 26
        ids.append(accept_id)
        ledger.append_accept(
            accept_id,
            tenant=f"t{i}",
            digest=f"d{i}",
            slo_cls="standard",
            deadline_ms=None,
            weight=1.0,
            deadline_left_s=None,
            work=(f"data{i}", (f"check{i}",), ()),
            quarantine={"t9": 3} if i == n_accepts - 1 else None,
        )
    for i in range(resolve_first):
        ledger.append_resolve(ids[i])
    ledger.close()
    return ids


def test_ledger_accept_tombstone_outstanding(tmp_path):
    ids = _mk_ledger(tmp_path, n_accepts=3, resolve_first=1)
    reopened = RequestLedger(str(tmp_path))
    out = reopened.outstanding()
    assert list(out) == ids[1:]  # accept order, tombstoned dropped
    rec = out[ids[1]]
    assert RequestLedger.load_tenant(rec) == "t1"
    assert RequestLedger.load_work(rec) == ("data1", ("check1",), ())
    assert rec["accepted_wall"] > 0
    assert reopened.latest_quarantine() == {"t9": 3}
    reopened.close()


def test_ledger_torn_tail_recovery_at_every_byte(tmp_path):
    """Crash-mid-append at EVERY byte offset inside the final frame:
    recover mode keeps every prior record, quarantines exactly the
    torn bytes to the ``.corrupt`` sidecar, and truncates the ledger
    to its last whole frame — the repository torn-segment rule at
    frame granularity."""
    ids = _mk_ledger(tmp_path, n_accepts=3)
    path = os.path.join(str(tmp_path), LEDGER_FILENAME)
    whole = open(path, "rb").read()
    # frame boundaries, recomputed off the file itself
    bounds = []
    stream = io.BytesIO(whole)
    while read_frame(stream) is not None:
        bounds.append(stream.tell())
    assert len(bounds) == 3
    last_start = bounds[1]
    for cut in range(last_start + 1, bounds[2]):
        with open(path, "wb") as f:
            f.write(whole[:cut])
        sidecar = path + CORRUPT_SUFFIX
        if os.path.exists(sidecar):
            os.unlink(sidecar)
        ledger = RequestLedger(str(tmp_path), mode="recover")
        assert [r["id"] for r in ledger.records] == ids[:2], cut
        assert ledger.torn_tail_bytes == cut - last_start, cut
        assert open(sidecar, "rb").read() == whole[last_start:cut], cut
        assert os.path.getsize(path) == last_start, cut
        # the recovered ledger keeps accepting past the tear
        ledger.append_resolve(ids[0])
        assert list(ledger.outstanding()) == [ids[1]]
        ledger.close()


def test_ledger_torn_tail_raise_mode_typed(tmp_path):
    _mk_ledger(tmp_path, n_accepts=2)
    path = os.path.join(str(tmp_path), LEDGER_FILENAME)
    with open(path, "ab") as f:
        f.write(b"\x00" * 7)  # a torn header tail
    with pytest.raises(CorruptStateException):
        RequestLedger(str(tmp_path), mode="raise")
    # recover mode on the same damage: both records intact
    ledger = RequestLedger(str(tmp_path), mode="recover")
    assert len(ledger.records) == 2
    assert ledger.torn_tail_bytes == 7
    ledger.close()


def test_ledger_mid_file_damage_distrusts_everything_after(tmp_path):
    """Frames are sequential: damage BEFORE valid frames makes the
    tail unreadable — recover mode keeps only the records before the
    first tear and quarantines the rest (never silently skips past
    damage)."""
    ids = _mk_ledger(tmp_path, n_accepts=3)
    path = os.path.join(str(tmp_path), LEDGER_FILENAME)
    whole = bytearray(open(path, "rb").read())
    stream = io.BytesIO(bytes(whole))
    read_frame(stream)
    first_end = stream.tell()
    whole[first_end + FRAME_HEADER_BYTES + 2] ^= 0xFF  # corrupt record 2
    with open(path, "wb") as f:
        f.write(bytes(whole))
    ledger = RequestLedger(str(tmp_path), mode="recover")
    assert [r["id"] for r in ledger.records] == ids[:1]
    assert ledger.torn_tail_bytes == len(whole) - first_end
    ledger.close()


# -- plan-fingerprint warmup -------------------------------------------------


def test_plan_fingerprint_replay_warms_a_fresh_service():
    from deequ_tpu.serve.service import ServeConfig, VerificationService

    table = _table(n=48)
    fp = plan_fingerprint(table, _analyzers())
    assert fp is not None
    assert fp["rows"] == 48
    assert [entry[0] for entry in fp["schema"]] == ["x", "i"]
    # the layout-routing value facts ride along: "x" carries nulls,
    # "i" is null-free, and both fit int32
    assert [entry[2] for entry in fp["schema"]] == [True, False]
    assert [entry[3] for entry in fp["schema"]] == [True, True]
    with use_mesh(None):
        service = VerificationService(
            config=ServeConfig(coalesce_window=0.0), start=True,
        )
        try:
            assert replay_fingerprints(service, [fp]) == 1
            assert len(service.plan_cache) == 1
            # the minted key must be the SAME identity the service
            # mints: a real tenant of that shape reuses the warmed
            # plan instead of inserting a second entry
            future = service.submit(
                table, required_analyzers=_analyzers(), tenant="t0",
            )
            future.result(timeout=120)
            assert len(service.plan_cache) == 1
        finally:
            service.stop(drain=True)
    # schemaless / zero-row sources have nothing to warm
    assert plan_fingerprint(object(), _analyzers()) is None


# -- config / env ------------------------------------------------------------


def test_pfleet_config_typed_validation():
    with pytest.raises(ValueError):
        ProcessFleetConfig(transport="carrier-pigeon")
    with pytest.raises(ValueError):
        ProcessFleetConfig(transport="loopback", n_workers=0)
    with pytest.raises(ValueError):
        ProcessFleetConfig(transport="loopback", ack_timeout=0.0)
    cfg = ProcessFleetConfig(transport="loopback")
    assert cfg.stall_timeout >= cfg.heartbeat_interval
    assert cfg.ledger_mode == "recover"


def test_fleet_transport_env_default(monkeypatch):
    from deequ_tpu.envcfg import env_value

    monkeypatch.delenv("DEEQU_TPU_FLEET_TRANSPORT", raising=False)
    assert env_value("DEEQU_TPU_FLEET_TRANSPORT") == "proc"
    monkeypatch.setenv("DEEQU_TPU_FLEET_TRANSPORT", "loopback")
    assert env_value("DEEQU_TPU_FLEET_TRANSPORT") == "loopback"
    monkeypatch.setenv("DEEQU_TPU_FLEET_TRANSPORT", "telepathy")
    from deequ_tpu.exceptions import EnvConfigError

    with pytest.raises(EnvConfigError):
        env_value("DEEQU_TPU_FLEET_TRANSPORT")


# -- the loopback fleet ------------------------------------------------------


def test_loopback_fleet_serves_bit_identical():
    tables = _tenant_tables(k=4)
    with use_mesh(None):
        serial = {
            t: VerificationSuite.run(tbl, [],
                                     required_analyzers=_analyzers())
            for t, tbl in tables.items()
        }
    fleet = _loopback_fleet()
    try:
        futures = {
            t: fleet.submit(tbl, required_analyzers=_analyzers(),
                            tenant=t)
            for t, tbl in tables.items()
        }
        for t, f in futures.items():
            _assert_bit_identical(serial[t], f.result(timeout=120),
                                  label=t)
            assert f.resolve_count == 1
        stats = fleet.stats()
        assert stats["workers_alive"] == 2
        assert stats["ledger_path"] is None
        assert all(w["transport"] == "loopback"
                   for w in stats["workers"].values())
    finally:
        fleet.stop(drain=True)


def test_loopback_fleet_worker_loss_redispatches_exactly_once():
    tables = _tenant_tables(k=6)
    with use_mesh(None):
        serial = {
            t: VerificationSuite.run(tbl, [],
                                     required_analyzers=_analyzers())
            for t, tbl in tables.items()
        }
    fleet = _loopback_fleet(n_workers=3)
    try:
        victim = fleet.route(next(iter(tables.values())),
                             required_analyzers=_analyzers())
        futures = {
            t: fleet.submit(tbl, required_analyzers=_analyzers(),
                            tenant=t)
            for t, tbl in tables.items()
        }
        fleet.kill_worker(victim, reason="scripted loss")
        for t, f in futures.items():
            _assert_bit_identical(serial[t], f.result(timeout=120),
                                  label=t)
            assert f.done() and f.resolve_count == 1, t
        assert fleet.workers_lost == 1
        assert fleet.stats()["workers_alive"] == 2
    finally:
        fleet.stop(drain=True)


def test_loopback_fleet_accept_ids_on_futures_and_ledger(tmp_path):
    """Accept-time durability: the ledger holds the accept frame (and
    its tombstone, once resolved) for every submit, and the future
    carries its ledger identity."""
    fleet = _loopback_fleet(ledger_dir=str(tmp_path))
    try:
        table = _table(n=48)
        future = fleet.submit(table, required_analyzers=_analyzers(),
                              tenant="t0")
        assert future.accept_id
        future.result(timeout=120)
        # the tombstone lands via _on_done on the receiver thread,
        # milliseconds after result() unblocks
        deadline = time.monotonic() + 10.0
        while time.monotonic() < deadline:
            reopened = RequestLedger(str(tmp_path))
            out = reopened.outstanding()
            reopened.close()
            if not out:
                break
            time.sleep(0.05)
        assert out == {}
    finally:
        fleet.stop(drain=True)


def test_coordinator_kill_and_resume_replays_onto_original_futures(
    tmp_path,
):
    """The prize: freeze the coordinator mid-flight (bookkeeping
    stopped, channels severed, no tombstones — what ``kill -9`` does),
    then open a FRESH fleet on the same ledger with the original
    futures. Every accepted future resolves exactly once,
    bit-identical to a healthy serial run."""
    tables = _tenant_tables(k=3)
    with use_mesh(None):
        serial = {
            t: VerificationSuite.run(tbl, [],
                                     required_analyzers=_analyzers())
            for t, tbl in tables.items()
        }
    # a 0.5s coalesce window holds accepted work in the worker queue
    # long enough that the abandon below lands before any resolution
    fleet = _loopback_fleet(
        ledger_dir=str(tmp_path),
        worker_knobs={"coalesce_window": 0.5},
    )
    futures = {}
    try:
        # abandon right after accept, while the work sits in the
        # coalesce window (abandon severs the channels, so any result
        # in flight dies with them)
        for t, tbl in tables.items():
            futures[t] = fleet.submit(
                tbl, required_analyzers=_analyzers(), tenant=t,
            )
    finally:
        fleet.abandon()
    unresolved = {f.accept_id: f for f in futures.values()
                  if not f.done()}
    assert unresolved, "abandon raced every resolution; nothing to resume"
    resumed = _loopback_fleet(
        ledger_dir=str(tmp_path), resume_futures=unresolved,
    )
    try:
        assert set(resumed.resumed) == set(unresolved)
        for accept_id, f in unresolved.items():
            assert resumed.resumed[accept_id] is f  # ORIGINAL futures
        for t, f in futures.items():
            _assert_bit_identical(serial[t], f.result(timeout=120),
                                  label=t)
            assert f.resolve_count == 1 and f.late_resolutions == 0, t
        assert resumed.stats()["resumed"] == len(unresolved)
    finally:
        resumed.stop(drain=True)


def test_resume_decays_deadlines_by_wall_clock_spent_dead(tmp_path):
    """A request whose deadline budget ran out while the coordinator
    was dead is SHED typed at resume — never replayed stale."""
    ledger = RequestLedger(str(tmp_path))
    table = _table(n=48)
    ledger.append_accept(
        "f" * 32,
        tenant="t0",
        digest="dX",
        slo_cls="standard",
        deadline_ms=50.0,
        weight=1.0,
        deadline_left_s=0.05,
        work=(table, (), tuple(_analyzers())),
    )
    ledger.close()
    time.sleep(0.2)  # the coordinator is "dead" past the deadline
    fleet = _loopback_fleet(ledger_dir=str(tmp_path))
    try:
        future = fleet.resumed["f" * 32]
        with pytest.raises(DeadlineExceededException):
            future.result(timeout=30)
        assert future.resolve_count == 1
    finally:
        fleet.stop(drain=True)


def test_resume_env_gate_leaves_ledger_untouched(tmp_path, monkeypatch):
    ledger = RequestLedger(str(tmp_path))
    ledger.append_accept(
        "e" * 32, tenant="t0", digest="dY", slo_cls="standard",
        deadline_ms=None, weight=1.0, deadline_left_s=None,
        work=(_table(n=48), (), tuple(_analyzers())),
    )
    ledger.close()
    monkeypatch.setenv("DEEQU_TPU_COORD_RESUME", "0")
    fleet = _loopback_fleet(ledger_dir=str(tmp_path))
    try:
        assert fleet.resumed == {}
    finally:
        fleet.stop(drain=True)
    reopened = RequestLedger(str(tmp_path))
    assert list(reopened.outstanding()) == ["e" * 32]  # still owed
    reopened.close()


# -- the subprocess fleet (real SIGKILL) -------------------------------------


def test_process_fleet_sigkill_failover_bit_identical():
    """REAL process isolation: 2 spawned worker processes, one
    SIGKILLed right after a wave of submits. Loss surfaces as
    transport EOF; every tenant still resolves bit-identically on its
    original future, exactly once."""
    tables = _tenant_tables(k=4)
    with use_mesh(None):
        serial = {
            t: VerificationSuite.run(tbl, [],
                                     required_analyzers=_analyzers())
            for t, tbl in tables.items()
        }
    fleet = ProcessFleet(transport="proc", n_workers=2, monitor=False)
    try:
        victim = fleet.route(next(iter(tables.values())),
                             required_analyzers=_analyzers())
        futures = {
            t: fleet.submit(tbl, required_analyzers=_analyzers(),
                            tenant=t)
            for t, tbl in tables.items()
        }
        fleet.kill_worker(victim)  # SIGKILL — not a drain
        for t, f in futures.items():
            _assert_bit_identical(serial[t], f.result(timeout=300),
                                  label=t)
            assert f.done() and f.resolve_count == 1, t
        assert fleet.workers_lost == 1
        stats = fleet.stats()
        assert stats["workers_alive"] == 1
        dead = stats["workers"][str(victim)]
        assert dead["alive"] is False
        assert all(w["transport"] == "proc"
                   for w in stats["workers"].values())
    finally:
        fleet.stop(drain=True)


# -- one process per chip (PR 21) --------------------------------------------


def test_spawn_fails_typed_and_promptly_when_a_worker_cannot_start(
    monkeypatch,
):
    """A worker process that cannot initialise its backend (on a TPU host:
    its parent, or another worker, holds the one chip) exits before hello;
    the spawn must fail TYPED when the process dies, not spawn_timeout
    (60 s) later."""
    from deequ_tpu.exceptions import WorkerLostException

    # inherited by the spawned worker only: this process's backend is up
    monkeypatch.setenv("JAX_PLATFORMS", "no_such_backend")
    t0 = time.monotonic()
    with pytest.raises(WorkerLostException, match="exited before saying hello"):
        ProcessFleet(transport="proc", n_workers=1, monitor=False)
    assert time.monotonic() - t0 < 45.0


def test_coordinator_never_initialises_a_jax_backend():
    """The coordinator of a proc-transport fleet routes, frames and
    ledgers on the host only: after serving a suite through a worker
    process its own jax has NO backend — so it never takes the chip its
    workers need."""
    import subprocess
    import sys

    code = """
import numpy as np
from deequ_tpu.analyzers import Completeness, Mean, Size
from deequ_tpu.data.table import Column, ColumnarTable, DType
from deequ_tpu.serve.pfleet import ProcessFleet
from jax._src import xla_bridge

table = ColumnarTable([
    Column("x", DType.FRACTIONAL, values=np.arange(64, dtype=np.float64)),
])
fleet = ProcessFleet(transport="proc", n_workers=1, monitor=False)
try:
    result = fleet.submit(
        table, required_analyzers=[Size(), Completeness("x"), Mean("x")],
        tenant="t",
    ).result(timeout=300)
finally:
    fleet.stop(drain=True)
assert all(m.value.is_success for m in result.metrics.values())
assert result.metrics[Mean("x")].value.get() == 31.5
print("BACKENDS_INITIALIZED", xla_bridge.backends_are_initialized())
"""
    done = subprocess.run(
        [sys.executable, "-c", code], capture_output=True, text=True,
        timeout=600, env=dict(os.environ, JAX_PLATFORMS="cpu"),
        cwd=os.path.dirname(os.path.dirname(os.path.abspath(__file__))),
    )
    assert done.returncode == 0, done.stderr[-2000:]
    assert "BACKENDS_INITIALIZED False" in done.stdout


@pytest.mark.parametrize(
    "chips,n_workers,pinned",
    [(4, 4, True), (4, 2, True), (1, 2, False), (4, 1, False), (0, 4, False)],
)
def test_worker_env_gives_each_worker_its_own_chip(
    monkeypatch, chips, n_workers, pinned
):
    """Where the host has a chip for each of several workers, worker i
    sees chip i only; otherwise nothing is set (one worker owns the one
    chip; surplus workers fail their spawn typed). The coordinator's
    environment — compile-cache placement included — always passes on."""
    from deequ_tpu.serve import pfleet

    monkeypatch.setattr(pfleet, "_host_tpu_chips", lambda: chips)
    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", "/somewhere/cache")
    monkeypatch.delenv("TPU_VISIBLE_CHIPS", raising=False)
    envs = [pfleet._worker_env(i, n_workers) for i in range(n_workers)]
    for i, env in enumerate(envs):
        assert env["JAX_COMPILATION_CACHE_DIR"] == "/somewhere/cache"
        if pinned:
            assert env["TPU_VISIBLE_CHIPS"] == str(i)
            assert env["TPU_CHIPS_PER_PROCESS_BOUNDS"] == "1,1,1"
        else:
            assert "TPU_VISIBLE_CHIPS" not in env
    if pinned:
        ports = {env["TPU_MESH_CONTROLLER_PORT"] for env in envs}
        assert len(ports) == n_workers
