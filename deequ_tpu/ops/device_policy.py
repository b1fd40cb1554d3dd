"""Device-fault policy for the scan engine — boundary classification,
compute watchdog, fault-injection seam, and backend health.

The reference inherits fault tolerance from Spark (a lost task re-executes
from lineage, so deequ never sees the fault); native-compilation engines
that trade that recovery model for speed get nothing (Flare,
arXiv:1703.08219). This module is the engine-side half of ours:

- :func:`device_call` wraps every blocking device call at one of the
  four boundaries (``transfer`` / ``trace`` / ``execute`` / ``fetch``),
  converting raw jaxlib errors into the typed taxonomy
  (``exceptions.py``) and — when a wall-clock ``deadline`` is set —
  running the call on a watchdog worker thread so a HUNG device becomes
  a typed ``DeviceHangException`` instead of a frozen run. With the
  on-device partial fold the ``fetch`` boundary (the scan's ONE
  device->host round trip) is where async execute faults surface, so
  the watchdog and the fault classification both stay armed there;
- :func:`install_scan_fault_hook` is the deterministic injection seam the
  resilience tests drive (``resilience/faults.py:FaultInjectingScanHook``);
- :class:`DeviceHealth` counts classified faults so a backend that
  REPEATEDLY faults routes subsequent scans straight to the CPU fallback
  instead of re-failing first every time;
- :class:`MeshHealth` is the same idea at MESH-MEMBER granularity: faults
  attributable to one chip (``DeviceException.device_ids``) cost that
  chip, not the backend — quarantined chips are excluded from future
  meshes up front, with half-open probes readmitting them periodically;
- :func:`resolve_hist_variant` is the histogram KERNEL-TIER policy
  (round 14, ops/histogram_device.py): which bincount/segment-fold
  formulation (scatter / one-hot matmul / pallas) a dispatch should
  run, decided from keyspace width, row count, and platform — the same
  driver the fault ladder already trusts for backend choices decides
  kernel shape too.

The degradation policies themselves (chunk bisection, degraded-mesh
re-sharding, CPU re-jit) live in ``ops/scan_engine.py:run_scan`` — this
module only decides *what* failed and *whether* the backend (or the
chip) is still trusted.
"""

from __future__ import annotations

import threading
from typing import Any, Callable, Dict, Optional

from deequ_tpu.exceptions import (
    DeviceException,
    DeviceHangException,
    classify_device_error,
)
from deequ_tpu.obs.recorder import (
    device_fed,
    device_ready,
    seam,
    worker_seams,
)

# -- fault-injection seam ----------------------------------------------------

# The installed hook is called as hook(boundary, ctx) immediately before
# the wrapped device call runs (INSIDE the watchdog, so injected hangs are
# converted like real ones). ctx carries {"scan_id", "attempt",
# "chunk_index", "fallback"} — see FaultInjectingScanHook.
_SCAN_FAULT_HOOK: Optional[Callable[[str, Dict[str, Any]], None]] = None


def install_scan_fault_hook(hook) -> Optional[Callable]:
    """Install (or, with None, remove) the scan-engine fault hook.
    Returns the previously installed hook so tests can restore it."""
    global _SCAN_FAULT_HOOK
    previous = _SCAN_FAULT_HOOK
    _SCAN_FAULT_HOOK = hook
    return previous


def current_scan_fault_hook():
    return _SCAN_FAULT_HOOK


# -- histogram kernel-variant policy -----------------------------------------

#: widest keyspace the one-hot matmul accepts on a CPU backend: the f32
#: sgemm form wins 5-8x over XLA's CPU scatter up to here (round-14
#: sweep on XLA-CPU) and LOSES beyond — the crossover is sharp
#: because the matmul's work is O(n * num_segments) while scatter's is
#: O(n)
HIST_ONEHOT_CPU_MAX_SEGMENTS = 32

#: widest keyspace the one-hot matmul accepts on an accelerator: the
#: factored (hi, lo) planes are n x (A + B) bf16 with A*B >= segments,
#: so 2^17 keeps A, B <= 1024 — covering the selection kernel's 2^16
#: pass-1 histogram and its default-k pass-2/3 width ((k+2)*256+1,
#: k=256) while bounding MXU work at ~128 MACs/row/lane
HIST_ONEHOT_MXU_MAX_SEGMENTS = 1 << 17

#: below this row count the dispatch itself dominates any kernel-shape
#: delta (the BASELINE config-1 latency regime) — the resolver keeps
#: the scatter baseline rather than trading noise
HIST_MIN_ROWS = 1 << 14


def hist_cpu_cap() -> int:
    """The CPU one-hot crossover cap: ``DEEQU_TPU_HIST_CPU_CAP`` when
    set, else the module constant (which tests may monkeypatch — the
    ``host_group_limit()`` idiom from ops/segment.py). Also a plan-cost
    model input (ops/plan_cost.py)."""
    from deequ_tpu.envcfg import env_value

    configured = env_value("DEEQU_TPU_HIST_CPU_CAP")
    return HIST_ONEHOT_CPU_MAX_SEGMENTS if configured is None else configured


def hist_accel_cap() -> int:
    """The accelerator one-hot crossover cap: ``DEEQU_TPU_HIST_ACCEL_CAP``
    when set, else the module constant."""
    from deequ_tpu.envcfg import env_value

    configured = env_value("DEEQU_TPU_HIST_ACCEL_CAP")
    return HIST_ONEHOT_MXU_MAX_SEGMENTS if configured is None else configured


def hist_is_wide(widths, platform: Optional[str] = None) -> bool:
    """True where the widest of ``widths`` is past the platform's one-hot
    cap: such a bincount resolves to the scatter whatever its rows."""
    if platform is None:
        import jax

        platform = jax.default_backend()
    cap = hist_cpu_cap() if platform == "cpu" else hist_accel_cap()
    return max(int(w) for w in widths) > cap


def resolve_hist_variant(
    widths,
    rows: Optional[int] = None,
    platform: Optional[str] = None,
    force: Optional[str] = None,
) -> str:
    """Resolve the histogram kernel variant for one dispatch or plan.

    ``widths`` — the histogram segment-counts the consumer will run
    (a plan lists every pass; a host-driven kernel its one width); the
    resolution is over the MAX, so a multi-pass program never mixes
    variants (the plan-hist-scatter lint contract is per program).
    ``rows`` — rows per dispatch; ``None`` means "large" (resident
    chunks). ``force`` overrides everything (explicit argument first,
    then the DEEQU_TPU_HIST_VARIANT env knob — the A/B hatch).

    The pallas variant NEVER resolves by default: Mosaic accepts it and
    it counts exactly on the v5e (chip_smoke.py, PR 21), but it costs
    O(n * num_segments) compares and no width range has been measured
    for it — force-knob-only until ROADMAP C2 gives it one or deletes
    it."""
    from deequ_tpu.envcfg import env_value

    if force is None:
        force = env_value("DEEQU_TPU_HIST_VARIANT")
    if force is not None:
        if force not in ("scatter", "onehot", "pallas"):
            raise ValueError(
                "hist variant must be one of ('scatter', 'onehot', "
                f"'pallas'), got {force!r}"
            )
        return force
    widths = tuple(int(w) for w in widths)
    if not widths:
        return "scatter"
    if rows is not None and rows < HIST_MIN_ROWS:
        return "scatter"
    return "scatter" if hist_is_wide(widths, platform) else "onehot"


# -- compute watchdog --------------------------------------------------------


def default_device_deadline() -> Optional[float]:
    """Process-wide watchdog deadline (seconds) from
    ``DEEQU_TPU_DEVICE_DEADLINE`` (envcfg registry); unset/empty/0
    disables the watchdog, malformed values raise typed
    ``EnvConfigError`` (pre-round-10 this silently disarmed the
    watchdog a deployment thought it had armed)."""
    from deequ_tpu.envcfg import env_value

    return env_value("DEEQU_TPU_DEVICE_DEADLINE")


def default_shard_deadline() -> Optional[float]:
    """Process-wide per-shard dispatch deadline (seconds) from
    ``DEEQU_TPU_SHARD_DEADLINE`` (envcfg registry), armed only on
    MULTI-CHIP mesh scans: a straggling chip that stalls a collective
    past it raises ``DeviceHangException`` (recorded as a
    ``mesh_straggler`` event) instead of freezing the whole mesh.
    Unset/empty/0 disables it; malformed values raise typed."""
    from deequ_tpu.envcfg import env_value

    return env_value("DEEQU_TPU_SHARD_DEADLINE")


#: worker-thread-local view of the watchdog call currently executing on
#: that thread. ScanStats' fetch accounting consults it: a late-waking
#: ABANDONED call (its caller already raised DeviceHangException and the
#: ladder moved on) must not bump process-global counters mid-way
#: through a LATER run — the cross-test device_fetches race the tier-1
#: oom_mid_fold deflake closes (round 14).
_WATCHDOG_TLS = threading.local()


def current_watchdog_call_abandoned() -> bool:
    """True iff the CALLING thread is a watchdog worker whose in-flight
    call timed out and was abandoned — its side effects on shared
    telemetry must be dropped, not recorded against whatever run is
    active by the time the hung call finally wakes."""
    state = getattr(_WATCHDOG_TLS, "state", None)
    return bool(state is not None and state.get("abandoned"))


class _WatchdogPool:
    """Reusable daemon workers for deadline-bounded calls.

    Spawning a fresh thread per watchdog-wrapped call costs ~1ms —
    enough to break the <1% governed-healthy-path contract when the run
    budget wraps every scan attempt. Workers here park on a per-worker
    inbox between calls, so the healthy path pays only a queue handoff.
    A worker whose call TIMED OUT is abandoned (a genuinely hung device
    call cannot be cancelled from Python, only detected): it is never
    returned to the idle stack, and exits on its own if the hung call
    ever finishes. Pool size is bounded by the peak number of
    concurrently armed watchdogs."""

    def __init__(self):
        self._lock = threading.Lock()
        self._idle: list = []

    def _spawn(self):
        import queue

        inbox: "queue.SimpleQueue" = queue.SimpleQueue()

        def loop():
            # a watchdog worker's seams are spans only: the caller's wait
            # for it is the counted seam (device_call opens it)
            with worker_seams():
                serve()

        def serve():
            while True:
                fn, box, done, state = inbox.get()
                # publish the call state to this thread before running:
                # telemetry written from INSIDE the call (record_fetch)
                # can then check whether the call was abandoned mid-way
                _WATCHDOG_TLS.state = state
                try:
                    box["value"] = fn()
                # deequ-lint: ignore[bare-except] -- watchdog worker forwards the exception to the caller thread via box['error'], re-raised there
                except BaseException as e:  # noqa: BLE001 — re-raised on
                    # the caller thread
                    box["error"] = e
                finally:
                    _WATCHDOG_TLS.state = None
                done.set()
                # drop the job references BEFORE parking: an idle worker
                # must not pin the last call's closure (which can hold a
                # whole in-memory table) or its result box until the
                # next job arrives
                fn = box = done = None
                with self._lock:
                    abandoned, state = state["abandoned"], None
                    if abandoned:
                        return  # timed out: this thread may be poisoned
                    self._idle.append(inbox)

        threading.Thread(
            target=loop, daemon=True, name="deequ-tpu-watchdog"
        ).start()
        return inbox

    def call(self, fn: Callable, deadline: float, what: str,
             boundary: str):
        with self._lock:
            inbox = self._idle.pop() if self._idle else None
        if inbox is None:
            inbox = self._spawn()
        box: Dict[str, Any] = {}
        done = threading.Event()
        state = {"abandoned": False}
        inbox.put((fn, box, done, state))
        if not done.wait(deadline):
            with self._lock:
                # the worker may have finished at the wire: only abandon
                # (and raise) if it is still genuinely in flight — the
                # lock orders this against the worker's requeue decision
                if not done.is_set():
                    state["abandoned"] = True
            if state["abandoned"]:
                raise DeviceHangException(
                    f"[{boundary}] {what} exceeded the {deadline:g}s "
                    "compute watchdog deadline — treating the device as "
                    "hung",
                    boundary=boundary,
                    deadline=deadline,
                )
        if "error" in box:
            raise box["error"]
        return box.get("value")


_WATCHDOG_POOL = _WatchdogPool()


def _call_with_deadline(fn: Callable, deadline: float, what: str,
                        boundary: str):
    """Run ``fn`` on a (pooled, reusable) watchdog worker thread; if it
    does not finish within ``deadline`` seconds, raise
    DeviceHangException. A timed-out worker is abandoned — a genuinely
    hung device call cannot be cancelled from Python, only *detected*."""
    return _WATCHDOG_POOL.call(fn, deadline, what, boundary)


#: the seam each device boundary is (obs/recorder.py:seam); an
#: ``execute`` call that only waits (a throttle) or that is a program's
#: first, compiling call names ``drain`` / ``build`` itself
_BOUNDARY_SEAM = {
    "transfer": "stage",
    "trace": "build",
    "execute": "dispatch",
    "fetch": "fetch",
}


def device_call(
    fn: Callable,
    boundary: str,
    what: str = "device call",
    deadline: Optional[float] = None,
    hook_ctx: Optional[Dict[str, Any]] = None,
    seam_name: Optional[str] = None,
    newest=False,
    **span_args,
):
    """Run one device-boundary call under classification (+ optional
    watchdog + optional fault injection), inside the boundary's seam.

    The feed gauge (obs/recorder.py) moves here, on the caller's thread:
    an ``execute`` call that enqueued something (``dispatch`` / ``build``;
    a ``drain`` only waits, on an older result) feeds the device once it
    returns; a ``fetch`` whose result is the scan's last names the
    dispatch it proves ready in ``newest`` (a :func:`fed_mark`, or True
    for the thread's newest). A ``transfer`` feeds nothing.

    Raw jaxlib/XLA failures re-raise as their typed DeviceException (with
    ``__cause__`` preserved); non-device errors propagate untouched.
    ``hook_ctx`` is passed only at the execute seam — the one place the
    deterministic fault hook fires. The seam (``seam_name``, else the
    boundary's: ``_BOUNDARY_SEAM``) opens on the CALLER thread (its
    track), wrapping the watchdog wait too, so a hang shows as a long
    span ending in a typed error; ``span_args`` go to the span.

    Cost note: an armed deadline hands the call to a pooled watchdog
    thread (~0.1ms) — noise next to a device round trip, but reason
    enough that the watchdog is opt-in and off by default."""
    hook = _SCAN_FAULT_HOOK if hook_ctx is not None else None

    def body():
        if hook is not None:
            hook(boundary, hook_ctx)
        return fn()

    name = seam_name or _BOUNDARY_SEAM[boundary]
    with seam(name, boundary=boundary, what=what, **span_args):
        try:
            if deadline is not None:
                value = _call_with_deadline(body, deadline, what, boundary)
            else:
                value = body()
            if boundary == "execute" and name != "drain":
                device_fed()
            elif newest:
                # without a watchdog wait_then_copy lowered the gauge
                # where the wait ended; under one the body ran on a
                # pooled thread and the whole fetch counted as fed
                device_ready(newest)
            return value
        except DeviceException:
            raise
        except Exception as e:  # noqa: BLE001 — classified below;
            # non-device errors (logic bugs; KeyboardInterrupt is not an
            # Exception) propagate exactly as before
            typed = classify_device_error(e, boundary)
            if typed is not None:
                raise typed from e
            raise


def wait_then_copy(result, newest=False):
    """The body of every fetch, inside its ``fetch`` seam: wait for the
    device, then copy the result (an array or a pytree of them) to the
    host under the child seam ``fetch.copy`` (what is left of the copy
    once the result is ready). The device works through the first and
    stands through the second; where the result is its
    scan's last (``newest``, as :func:`device_call` takes it) the gauge
    drops between the two, so the copy is unfed time."""
    import jax
    import numpy as np

    leaves = jax.tree.leaves(result)
    # ask for the copy BEFORE waiting, as a bare np.asarray does: it then
    # starts on the device the moment the result is ready. Asked for
    # behind the wait it costs one more host round trip (0.5 ms a scan
    # suite on the chip: PERF.md section 6, PR 34)
    for leaf in leaves:
        leaf.copy_to_host_async()
    jax.block_until_ready(result)
    if newest:
        device_ready(newest)
    with seam("fetch.copy", bytes=sum(int(a.nbytes) for a in leaves)):
        return jax.tree.map(np.asarray, result)


def device_fetch(
    result,
    what: str,
    deadline: Optional[float] = None,
    newest=False,
):
    """One device->host fetch at the ``fetch`` boundary: classification
    and the watchdog as :func:`device_call` gives them, the wait and the
    copy told apart as :func:`wait_then_copy` does. Under an armed
    ``deadline`` the body runs on a pooled thread, whose seams are spans
    only: the caller's ``fetch`` then holds all of it, unsplit."""
    return device_call(
        lambda: wait_then_copy(result, newest), "fetch", what=what,
        deadline=deadline, newest=newest,
    )


# -- backend health ----------------------------------------------------------


class DeviceHealth:
    """Consecutive-fault counter for the accelerator backend.

    After ``threshold`` consecutive classified device faults with no
    successful device pass in between, ``should_force_fallback()`` turns
    true and scans running with ``on_device_error="fallback"`` go
    STRAIGHT to the CPU backend — a flapping device must not re-fail
    every scan before each fallback. Forced fallback is never permanent:
    every ``probe_interval``-th forced scan probes the accelerator again
    (half-open, circuit-breaker style), and one successful accelerator
    pass resets the counter — transient weather forgives. Faults observed
    ON the CPU fallback attempt are the host's, not the accelerator's,
    and must not be recorded here."""

    def __init__(self, threshold: int = 3, probe_interval: int = 8):
        self.threshold = int(threshold)
        self.probe_interval = int(probe_interval)
        self.reset()

    def reset(self) -> None:
        self.consecutive_faults = 0
        self.total_faults = 0
        self._forced = 0

    def record_fault(self, exc: DeviceException) -> None:
        self.consecutive_faults += 1
        self.total_faults += 1

    def record_success(self) -> None:
        self.consecutive_faults = 0
        self._forced = 0

    def should_force_fallback(self) -> bool:
        if self.consecutive_faults < self.threshold:
            return False
        self._forced += 1
        if self.probe_interval and self._forced % self.probe_interval == 0:
            return False  # half-open probe: try the accelerator this once
        return True


#: process-wide accelerator health, read by run_scan's fallback policy
DEVICE_HEALTH = DeviceHealth()


# -- mesh health -------------------------------------------------------------


class MeshHealth:
    """Per-device fault registry for multi-chip meshes — ``DeviceHealth``
    at mesh-member granularity.

    Every classified device fault that NAMES its chip
    (``DeviceException.device_ids``) is recorded against that chip, not
    the whole backend: one flaky chip on an 8-chip mesh must cost one
    chip, never all eight. A chip whose consecutive faults reach
    ``threshold`` is quarantined — subsequent scans build their mesh over
    the healthy remainder up front instead of re-failing into the same
    dead member — with the same half-open circuit-breaker escape hatch as
    DeviceHealth: every ``probe_interval``-th quarantine decision
    readmits the quarantined chips for one probe scan, and a successful
    pass over a probed chip clears its record (transient weather
    forgives; a genuinely dead chip re-quarantines on the next fault).

    A ``DeviceLostException`` / ``MeshDegradedException`` quarantines its
    chips IMMEDIATELY (a lost chip is lost, not flaky); other attributable
    faults (per-chip OOM, stragglers) count one step toward the
    threshold."""

    def __init__(self, threshold: int = 2, probe_interval: int = 8):
        self.threshold = int(threshold)
        self.probe_interval = int(probe_interval)
        self.reset()

    def reset(self) -> None:
        self.consecutive_faults: Dict[int, int] = {}
        self.total_faults: Dict[int, int] = {}
        self._filtered = 0

    def record_fault(self, exc: "DeviceException") -> None:
        """Record one classified fault against every chip it implicates
        (no-op for unattributable faults — those are DeviceHealth's)."""
        from deequ_tpu.exceptions import (
            DeviceLostException,
            MeshDegradedException,
        )

        fatal = isinstance(exc, (DeviceLostException, MeshDegradedException))
        for did in getattr(exc, "device_ids", ()) or ():
            count = self.consecutive_faults.get(did, 0) + 1
            if fatal:
                count = max(count, self.threshold)
            self.consecutive_faults[did] = count
            self.total_faults[did] = self.total_faults.get(did, 0) + 1

    def record_success(self, device_ids) -> None:
        """A scan completed over these chips: their records clear. Only
        the chips that actually PARTICIPATED are forgiven — a success on
        the shrunken mesh says nothing about the quarantined member, and
        must not reset the probe cadence that will eventually retry it."""
        for did in device_ids:
            self.consecutive_faults.pop(int(did), None)

    def quarantined(self) -> frozenset:
        return frozenset(
            did
            for did, count in self.consecutive_faults.items()
            if count >= self.threshold
        )

    def healthy_subset(self, device_ids):
        """Partition ``device_ids`` into (healthy, excluded) for a scan
        about to build its mesh. Advances the half-open probe counter only
        when something would actually be excluded; on every
        ``probe_interval``-th such decision the quarantined chips are
        readmitted for one probe."""
        bad = self.quarantined()
        ids = [int(d) for d in device_ids]
        excluded = [d for d in ids if d in bad]
        if not excluded:
            return ids, []
        self._filtered += 1
        if self.probe_interval and self._filtered % self.probe_interval == 0:
            return ids, []  # half-open probe: trust the full mesh this once
        healthy = [d for d in ids if d not in bad]
        return healthy, excluded

    def snapshot(self) -> dict:
        return {
            "quarantined": sorted(self.quarantined()),
            "consecutive_faults": dict(self.consecutive_faults),
            "total_faults": dict(self.total_faults),
        }


#: process-wide per-chip health, read by run_scan's degraded-mesh policy
MESH_HEALTH = MeshHealth()
