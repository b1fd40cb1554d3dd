"""Multi-host initialization + peer-loss handling (the DCN story;
SURVEY.md §2.15).

The engine itself is topology-agnostic: it runs over whatever mesh
``parallel.mesh.current_mesh()`` resolves. On a multi-host TPU slice, call
``initialize_multi_host()`` once per process before building tables; the
default mesh then spans every chip in the slice and the scan engine's
collectives (psum/all_gather) ride ICI inside a slice and DCN
across slices — XLA routes them, exactly as the design requires (no NCCL/
MPI analogue needed).

Data distribution across hosts follows the standard jax convention: each
host feeds its local shard of rows (``host_row_range``), and the global
monoid merge makes per-host partial states combine exactly like per-device
partials.

This path is EXECUTED (not just asserted) by ``__graft_entry__.py:
dryrun_multihost`` and tests/test_fs_and_distributed.py::
test_multihost_cross_process_state_merge: two real processes join via
``jax.distributed.initialize``, ingest disjoint ``host_row_range`` shards,
run the fused scan on their local meshes, exchange flat state vectors with
an ``all_gather`` over the global cross-process mesh, and the folded
metrics are asserted equal to a single-host full-table run.

Peer loss: a host that dies mid-run stalls every cross-process collective.
``check_peers`` converts that stall into a typed ``PeerLostException``
(heartbeat + barrier timeout) — or, with ``on_peer_loss="degrade"``, into
a ``PeerLossReport`` naming the surviving processes and the lost hosts'
``host_row_range`` slices, which the caller completes WITHOUT and reports
as ``unverified_row_ranges`` (partial results are reported, never silent).
"""

from __future__ import annotations

import itertools
from dataclasses import dataclass, field
from typing import Callable, List, Optional, Sequence, Tuple

import jax

from deequ_tpu.exceptions import PeerLostException

#: default heartbeat/barrier timeout (seconds) before a peer is lost
DEFAULT_PEER_TIMEOUT = 60.0


def initialize_multi_host(
    coordinator_address: Optional[str] = None,
    num_processes: Optional[int] = None,
    process_id: Optional[int] = None,
) -> None:
    """Initialize jax.distributed for a multi-host run. On Cloud TPU the
    arguments are auto-detected from the environment; pass them explicitly
    elsewhere."""
    kwargs = {}
    if coordinator_address is not None:
        kwargs["coordinator_address"] = coordinator_address
    if num_processes is not None:
        kwargs["num_processes"] = num_processes
    if process_id is not None:
        kwargs["process_id"] = process_id
    jax.distributed.initialize(**kwargs)


def split_row_range(
    total_rows: int, n_parts: int, part: int
) -> Tuple[int, int]:
    """Balanced [start, stop) split of ``total_rows`` into ``n_parts``:
    the first ``total_rows % n_parts`` parts carry one extra row, so no
    part ever differs from another by more than one row — the old
    ceil-block split could hand trailing hosts ZERO rows (e.g. 10 rows /
    8 processes gave hosts 0-4 two rows each and hosts 5-7 nothing) while
    the early hosts carried the whole remainder."""
    if n_parts <= 0:
        raise ValueError(f"n_parts must be >= 1, got {n_parts}")
    if not 0 <= part < n_parts:
        raise ValueError(f"part must be in [0, {n_parts}), got {part}")
    base, rem = divmod(max(int(total_rows), 0), n_parts)
    start = part * base + min(part, rem)
    stop = start + base + (1 if part < rem else 0)
    return start, stop


def host_row_range(total_rows: int) -> Tuple[int, int]:
    """The [start, stop) slice of a globally-ordered dataset this host
    should ingest, balanced across processes (sizes differ by at most one
    row; see ``split_row_range``)."""
    return split_row_range(
        total_rows, jax.process_count(), jax.process_index()
    )


# -- peer loss ---------------------------------------------------------------


def probe_liveness(
    expected: Sequence[int],
    timeout: float,
    probe: Callable[[float], Sequence[int]],
) -> Tuple[List[int], List[int]]:
    """Run one injected liveness probe over the ``expected`` member ids
    and attribute the outcome: returns ``(alive, lost)``, both sorted.

    This is the ``check_peers`` probe seam factored out so OTHER
    membership tiers can ride it — the serving fleet's worker heartbeat
    (``serve/membership.py``) injects a thread-liveness probe here
    exactly the way tests inject deterministic peer probes, and the
    process fleet (``serve/pfleet.py``) injects a transport ping probe.
    The contract is the probe's: ``probe(timeout)`` returns the
    responsive member ids; a ``TimeoutError`` means the stall could not
    be attributed and propagates for the caller to convert into its
    typed loss exception (every member suspect)."""
    alive = sorted(int(p) for p in probe(timeout))
    expected_set = {int(i) for i in expected}
    lost = sorted(expected_set - set(alive))
    return [p for p in alive if p in expected_set], lost


def validate_loss_mode(value: str, param: str) -> None:
    """Shared argument validation for every liveness-check tier: the
    only loss policies are ``"fail"`` (raise typed) and ``"degrade"``
    (return a report for the caller's failover/partial-result path)."""
    if value not in ("fail", "degrade"):
        raise ValueError(
            f"{param} must be 'fail' or 'degrade', got {value!r}"
        )


def run_liveness_check(
    expected: Sequence[int],
    timeout: float,
    probe: Callable[[float], Sequence[int]],
    unattributable: Callable[[TimeoutError], BaseException],
) -> Tuple[List[int], List[int]]:
    """The shared core of every membership check — ``check_peers``
    (multi-host scan), ``FleetMembership.check_workers`` (in-process
    fleet), and the process fleet's transport membership all call THIS,
    so the three tiers cannot drift: run the injected probe, attribute
    losses, and convert an unattributable ``TimeoutError`` into the
    caller's typed loss exception (every member suspect — even a
    "degrade" caller cannot pick a failover target without
    attribution, so the typed raise is unconditional)."""
    try:
        return probe_liveness(expected, timeout, probe)
    except TimeoutError as e:
        raise unattributable(e) from e


@dataclass
class PeerLossReport:
    """The outcome of one peer-health check.

    ``lost`` names the process indices that stopped responding;
    ``unverified_row_ranges`` are those hosts' ``host_row_range`` slices —
    rows the degraded run completes WITHOUT, to be surfaced on
    ``VerificationResult.unverified_row_ranges``."""

    n_processes: int
    surviving: List[int] = field(default_factory=list)
    lost: List[int] = field(default_factory=list)
    unverified_row_ranges: List[Tuple[int, int]] = field(
        default_factory=list
    )

    @property
    def degraded(self) -> bool:
        return bool(self.lost)


def _distributed_client():
    """The process-wide jax.distributed client, or None outside a
    multi-host run (structure probed defensively: the module is private
    and has moved across jax releases)."""
    try:
        from jax._src import distributed

        return distributed.global_state.client
    # deequ-lint: ignore[bare-except] -- jax.distributed client probe: absence means single-host, not a fault
    except Exception:  # noqa: BLE001 — no client means single-host
        return None


# SPMD sequence for peer-probe barrier tags: every process runs the same
# driver program, so the k-th check_peers call on each host agrees on tag
# k — a DETERMINISTIC shared name. (Wall-clock tags cannot work: peers
# crossing a second boundary, or any skew, would wait at different
# barriers and declare each other lost.)
_PEER_PROBE_SEQ = itertools.count()


def _default_peer_probe(timeout: float) -> List[int]:
    """Best-effort liveness probe over the jax.distributed key-value
    store: this host publishes a heartbeat key, waits at a barrier, and —
    when the barrier times out — reads which peers' heartbeat keys exist.
    Returns the list of RESPONSIVE process indices (self always counts).
    Raises TimeoutError when the runtime exposes no way to attribute the
    stall (the caller then treats every peer as suspect).

    Tag agreement relies on the SPMD convention: all processes make the
    same sequence of check_peers calls, so the per-process counter yields
    the same tag everywhere."""
    client = _distributed_client()
    n_proc = jax.process_count()
    pid = jax.process_index()
    if client is None or n_proc <= 1:
        return list(range(n_proc))
    tag = f"deequ_tpu_peers_{next(_PEER_PROBE_SEQ)}"
    try:
        client.key_value_set(f"{tag}/heartbeat/{pid}", "alive")
    # deequ-lint: ignore[bare-except] -- KV-store probe falls through to the barrier path, which classifies typed
    except Exception:  # noqa: BLE001 — store refused; fall through
        pass
    try:
        client.wait_at_barrier(f"{tag}/barrier", int(timeout * 1000))
        return list(range(n_proc))
    except Exception:  # noqa: BLE001 — barrier timed out: attribute it
        alive = [pid]
        for peer in range(n_proc):
            if peer == pid:
                continue
            try:
                client.blocking_key_value_get(
                    f"{tag}/heartbeat/{peer}", 1000
                )
                alive.append(peer)
            # deequ-lint: ignore[bare-except] -- a missing heartbeat IS the signal; the caller raises typed PeerLostException
            except Exception:  # noqa: BLE001 — no heartbeat: peer is lost
                continue
        if len(alive) == n_proc:
            # every peer heartbeated yet the barrier stalled — the stall
            # is unattributable; let the caller decide
            raise TimeoutError(
                f"barrier timed out after {timeout:g}s with all "
                f"{n_proc} heartbeats present"
            )
        return alive


def check_peers(
    total_rows: int,
    timeout: float = DEFAULT_PEER_TIMEOUT,
    on_peer_loss: str = "fail",
    probe: Optional[Callable[[float], Sequence[int]]] = None,
) -> PeerLossReport:
    """Verify every peer process is still reachable; the multi-host
    analogue of the single-host watchdog.

    ``probe(timeout)`` returns the responsive process indices (default:
    heartbeat + barrier over the jax.distributed key-value store; tests
    inject a deterministic probe). On peer loss:

    - ``on_peer_loss="fail"`` (default): raise a typed
      ``PeerLostException`` naming the lost processes — the caller's cue
      to abort before a collective hangs forever;
    - ``on_peer_loss="degrade"``: return a ``PeerLossReport`` whose
      ``unverified_row_ranges`` are the lost hosts' ``host_row_range``
      slices; the surviving hosts complete the run over their own shards
      and the omission is REPORTED (``ScanStats.record_unverified`` →
      ``VerificationResult.unverified_row_ranges``), never silent.
    """
    validate_loss_mode(on_peer_loss, "on_peer_loss")
    n_proc = jax.process_count()
    report = PeerLossReport(n_processes=n_proc)
    if n_proc <= 1:
        report.surviving = list(range(n_proc))
        return report
    probe = probe or _default_peer_probe
    # unattributable stall: degrading would silently drop unknown
    # rows, so even "degrade" raises typed (run_liveness_check rule)
    alive, lost = run_liveness_check(
        range(n_proc), timeout, probe,
        lambda e: PeerLostException(
            f"multi-host barrier timed out after {timeout:g}s and the "
            f"stall could not be attributed to specific peers: {e}",
        ),
    )
    report.surviving = alive
    report.lost = lost
    if not lost:
        return report
    for peer in lost:
        start, stop = split_row_range(total_rows, n_proc, peer)
        if stop > start:
            report.unverified_row_ranges.append((start, stop))
    if on_peer_loss == "fail":
        raise PeerLostException(
            f"lost contact with process(es) {lost} after {timeout:g}s "
            f"(surviving: {alive}); rerun, or pass "
            f'on_peer_loss="degrade" to complete on the surviving hosts '
            "with the lost hosts' row ranges reported unverified",
            lost_processes=tuple(lost),
        )
    # degrade: the surviving hosts complete the run over their own
    # shards; the lost rows are recorded as unverified on ScanStats so
    # VerificationResult surfaces them
    from deequ_tpu.ops.scan_engine import SCAN_STATS

    SCAN_STATS.peer_losses += len(lost)
    for start, stop in report.unverified_row_ranges:
        SCAN_STATS.record_unverified(
            start, stop, reason=f"peer_lost:{','.join(map(str, lost))}"
        )
    if not report.unverified_row_ranges:
        # a count-less source can't map the lost hosts to row ranges,
        # but the loss itself must still be REPORTED, never silent
        SCAN_STATS.record_degradation(
            "peer_lost", lost_processes=sorted(lost),
            reason="unverified row ranges unknown (no source row count)",
        )
    return report
