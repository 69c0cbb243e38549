"""fbtpu-armor — the device fault domain (FAULTS.md "fbtpu-armor").

Every entry into the jit/pjit/shard_map plane goes through a
:class:`DeviceLane`: a per-plane wrapper that turns device failures into
bit-exact CPU fallbacks instead of lost records, stalled engine loops,
or a permanently pinned slow path. One lane exists per device plane
("grep" for the DFA filter matchers, "flux" for the sketch/window
kernels); lanes are process-global because the jax backend is.

What a lane guarantees per launch:

- **containment** — the launch runs on a watched worker thread; any
  exception (XlaRuntimeError, RESOURCE_EXHAUSTED, injected faults)
  resolves to the caller-supplied bit-exact host fallback. The verdict
  a caller commits comes from exactly ONE of {device result, fallback}
  — never both, never a partial.
- **launch deadline** — a launch that never returns (the wedged-device
  shape ``device.launch_hang`` injects) is soft-killed at
  ``FBTPU_LAUNCH_DEADLINE_S`` (default 120 s — first launches compile):
  the worker is abandoned (its eventual result is discarded, so a late
  completion can never commit a stale verdict) and the segment
  completes on the fallback. The fbtpu-guard watchdog pattern, applied
  to kernel launches.
- **re-staging on retry** — callers re-enter through their launch
  closure, which re-stages device buffers from host arrays on every
  attempt. A launch that consumed its donated staged buffers
  (``dispatch_mesh`` donates the lengths buffer) and THEN failed must
  never be retried against the deleted aval; the ``device.dispatch``
  failpoint fires at the post-launch boundary precisely to regression-
  test that hazard.
- **circuit breaking** — consecutive failures open a per-lane
  :class:`~fluentbit_tpu.core.guard.CircuitBreaker`
  (``FBTPU_DEVICE_BREAKER_FAILURES`` / ``_COOLDOWN``): while open,
  launches short-circuit straight to the fallback (no thread, no
  device touch); after the cooldown ONE probe launch re-tests the
  device, closing the breaker on success (and re-arming attach via
  ``device.reattach_async`` when the attach controller is exhausted).
- **mesh shrink/regrow** — a :class:`DeviceLostError` (real device
  loss, or the ``mesh.device_lost`` failpoint) shrinks the lane's mesh
  to the surviving devices (``ops.mesh.build_mesh(n_devices=...)``;
  per-``mesh_key`` handles recompile automatically, callers re-pad via
  ``pad_to_devices``) — bit-exact vs the full mesh. The mesh regrows
  to the full device set when the breaker re-closes, or — for a
  one-off loss that never opened the breaker — after
  ``FBTPU_DEVICE_REGROW_AFTER`` consecutive healthy launches on the
  survivors (a still-dead device just shrinks it back).

Observability: ``fluentbit_device_*`` metrics via the engine's
listener bridge (:func:`add_listener`), a ``"device"`` block in
``/api/v1/health`` (:func:`health_block`), and :func:`snapshot` (every
lane's stats: the benchmark's lane counters, ``chip_smoke.py``).

Cost model: each guarded launch runs on a fresh watched worker thread
(~50-100 µs spawn). That is a deliberate trade — it buys the deadline
+ hard-abandonment semantics with zero shared-worker state to wedge,
and it only applies to device paths, where a segment launch (thousands
of records through a compiled kernel) dwarfs the spawn; the 1-core CPU
bench hot path (native fused matcher, host sketch twins) never enters
a lane. If per-launch spawn ever shows up on a real-chip profile, a
persistent per-lane worker pair (keeping the depth-2 overlap) is the
upgrade path.
"""

from __future__ import annotations

import logging
import os
import threading
import time
from typing import Callable, Dict, List, Optional

from ..core.lockorder import make_lock
from ..core.spans import bind, current_ids, span

log = logging.getLogger("flb.device.fault")

#: a launch whose closure runs longer is counted (``launches_over_1s``)
#: and logged. 1.7x the slowest launch of any benchmark cell (0.58 s):
#: part of the counter's definition, like a histogram's edge — no knob
SLOW_LAUNCH_S = 1.0

__all__ = [
    "DeviceLane", "DeviceLostError", "lane", "lanes", "reset",
    "snapshot", "health_block", "add_listener", "remove_listener",
    "notify",
]


class DeviceLostError(RuntimeError):
    """A launch failed because a device dropped out of the mesh (not a
    transient kernel error): the lane shrinks the mesh before the next
    launch instead of burning the breaker budget against a dead chip."""


#: Error-text signatures that mark a runtime failure as device LOSS
#: rather than a transient kernel error. Real losses surface as
#: XlaRuntimeError with a DEVICE_LOST-flavored message (PJRT's status
#: code name), not as our DeviceLostError — without this mapping the
#: shrink-to-survivors path would only ever engage under the
#: mesh.device_lost failpoint.
_DEVICE_LOST_SIGNATURES = ("device_lost", "device lost", "device is lost")


def is_device_loss(err: BaseException) -> bool:
    """Classify a launch failure as device loss (shrink the mesh) vs a
    transient error (fallback + breaker only)."""
    if isinstance(err, DeviceLostError):
        return True
    text = repr(err).lower()
    return any(sig in text for sig in _DEVICE_LOST_SIGNATURES)


def launch_deadline() -> float:
    try:
        return max(0.1, float(
            os.environ.get("FBTPU_LAUNCH_DEADLINE_S", "120")))
    except ValueError:
        return 120.0


def _breaker_failures() -> int:
    try:
        return max(1, int(
            os.environ.get("FBTPU_DEVICE_BREAKER_FAILURES", "3")))
    except ValueError:
        return 3


def _breaker_cooldown() -> float:
    try:
        return max(0.01, float(
            os.environ.get("FBTPU_DEVICE_BREAKER_COOLDOWN", "5")))
    except ValueError:
        return 5.0


def _regrow_after() -> int:
    try:
        return max(1, int(
            os.environ.get("FBTPU_DEVICE_REGROW_AFTER", "64")))
    except ValueError:
        return 64


# -- listener bridge (the engine wires fluentbit_device_* here) --------

_listener_lock = make_lock("fault._listener_lock")
_listeners: List[Callable[[str, str, object], None]] = []


def add_listener(cb: Callable[[str, str, object], None]) -> None:
    """Register ``cb(lane_name, event, value)``. Events: ``fallback``,
    ``timeout``, ``failure``, ``slow_launch``, ``device_lost``,
    ``short_circuit``, ``breaker`` (value = new state name),
    ``mesh_devices`` (value = current device count), ``reattach``
    (value = attach generation)."""
    with _listener_lock:
        if cb not in _listeners:
            _listeners.append(cb)


def remove_listener(cb: Callable[[str, str, object], None]) -> None:
    with _listener_lock:
        if cb in _listeners:
            _listeners.remove(cb)


def notify(lane_name: str, event: str, value: object = 1) -> None:
    with _listener_lock:
        cbs = list(_listeners)
    for cb in cbs:
        try:
            cb(lane_name, event, value)
        except Exception:
            log.exception("device fault listener failed")


# -- one guarded launch ------------------------------------------------


class _Flight:
    """One in-flight watched launch (the lane's begin/finish handle)."""

    __slots__ = ("launch", "fallback", "denied", "deadline", "done",
                 "box", "t_begin", "t_done", "ids", "id")

    def __init__(self, launch, fallback, denied: bool, deadline: float,
                 t_begin: float = 0.0, id: int = 0):
        self.launch = launch
        # the lane's count of launches when this one was begun: the
        # ``launch`` of lane.begin/launch/wait and of every span the
        # closure opens (grep.dispatch/put/call/force), so a reader can
        # tell two open flights' spans apart
        self.id = id
        self.fallback = fallback
        self.denied = denied
        self.deadline = deadline
        self.done = threading.Event()
        self.box: dict = {}
        self.t_begin = t_begin  # begin() entered: spawn_s counts from it
        self.t_done = 0.0       # stamped before done.set(): wake_s
        # the caller's span ids (chunk, seg) while a profiler session
        # runs: the worker's spans carry them across the thread hop
        self.ids: dict = current_ids() or {}


class DeviceLane:
    """Fault domain for one device plane (see module docstring).

    ``begin``/``finish`` split the guarded launch so callers can keep
    work beside the kernel: ``begin`` starts the watched worker and
    returns immediately; ``finish`` waits (bounded), applies
    breaker/fallback policy, and returns the final host-side result.
    Two callers do: ``double_buffered`` stages a chunk's next segment
    between the two, and ``in_forward`` begins a connection's next
    frame while the frame before it is collected, committed and acked
    (``staged_match(begin=True)``), so the two halves of one launch may
    run on different threads and several flights of a lane may be open
    at once (``begun_in_flight`` counts the launches begun so; each
    flight's spans carry its ``launch`` number). Every flight that was
    begun is finished, used or not: ``ok`` + ``failures`` + ``timeouts``
    + ``short_circuits`` add up to ``launches``. ``run`` = begin +
    finish for unpipelined callers (the flux sketch updates).
    """

    def __init__(self, name: str, failures: Optional[int] = None,
                 cooldown: Optional[float] = None,
                 deadline: Optional[float] = None,
                 regrow_after: Optional[int] = None):
        from ..core.guard import CircuitBreaker

        self.name = name
        self.deadline = deadline if deadline is not None \
            else launch_deadline()
        self.regrow_after = regrow_after if regrow_after is not None \
            else _regrow_after()
        self.breaker = CircuitBreaker(
            f"device:{name}",
            failures=failures if failures is not None
            else _breaker_failures(),
            cooldown=cooldown if cooldown is not None
            else _breaker_cooldown(),
            on_transition=self._on_transition,
        )
        self._lock = make_lock("DeviceLane._lock")
        self._stats = {
            "launches": 0, "ok": 0, "failures": 0, "timeouts": 0,
            "fallback_segments": 0, "short_circuits": 0,
            "device_lost": 0, "breaker_trips": 0, "abandoned": 0,
            # seconds, summed over launches: begin() entered → the
            # worker running (thread create, start, wait to be
            # scheduled); the launch closure on the worker; finish()
            # blocked on the worker; and of that, done.set() → the
            # waiting thread running again (so blocked_s is what was
            # left of run_s, plus wake_s)
            "spawn_s": 0.0, "run_s": 0.0, "blocked_s": 0.0,
            "wake_s": 0.0,
            # launches whose closure ran past SLOW_LAUNCH_S, each
            # logged: one stalled launch among thousands is invisible
            # in the sums above
            "launches_over_1s": 0,
            # launches begun while another flight of the lane had not
            # been finished: a chunk's next segment (double_buffered),
            # or in_forward's next frame begun ahead of its absorb
            "begun_in_flight": 0,
        }
        self._open = 0           # flights begun and not finished
        self._lost = 0           # devices shrunk out of the mesh
        self._ok_since_shrink = 0  # healthy launches on the shrunk mesh
        self._mesh = None        # cached mesh for (_mesh_key)
        self._mesh_key = None    # (attach generation, lost, axis)

    # -- breaker transitions -------------------------------------------

    def _on_transition(self, _name: str, old: str, new: str) -> None:
        if new == "open":
            with self._lock:
                self._stats["breaker_trips"] += 1
        if new == "half-open":
            # the probe that would re-test a dead backend re-tests the
            # ATTACH when the controller is exhausted: success bumps
            # the generation and the mesh lane swaps back in live
            from . import device

            if device.failed():
                device.reattach_async()
        if old != "closed" and new == "closed":
            # recovery: regrow the mesh to the full device set
            with self._lock:
                self._lost = 0
                self._ok_since_shrink = 0
                self._mesh_key = None
        notify(self.name, "breaker", new)
        level = logging.WARNING if new != "closed" else logging.INFO
        log.log(level, "device lane %s: breaker %s -> %s",
                self.name, old, new)

    # -- mesh lifecycle ------------------------------------------------

    def current_mesh(self, axis: str = "batch"):
        """The mesh this lane launches over right now: the full device
        set normally; after device loss, the surviving devices (None
        when fewer than 2 survive — callers then run unsharded or on
        the host twin). Cached per (attach generation, lost, axis), so
        a re-attach or a shrink/regrow rebuilds exactly once."""
        from . import device
        from . import mesh as om

        gen = device.generation()
        with self._lock:
            lost = self._lost  # ONE read keys AND sizes the build: a
            # concurrent shrink between two reads must not cache a mesh
            # built over one device set under a key recording another
            key = (gen, lost, axis)
            if key == self._mesh_key:
                return self._mesh
        n = None
        if lost:
            n = max(0, device.device_count() - lost)
        mesh = om.build_mesh(n_devices=n, axis=axis)
        with self._lock:
            if self._lost == lost:  # loss state unchanged since keying
                self._mesh = mesh
                self._mesh_key = key
            # else: stale build — serve it once (the launch fails and
            # re-shrinks if it really is stale), never cache it
        notify(self.name, "mesh_devices",
               mesh.devices.size if mesh is not None else 1)
        return mesh

    def _device_lost(self) -> None:
        from . import device

        total = device.device_count()
        with self._lock:
            self._stats["device_lost"] += 1
            if self._lost < max(0, total - 1):
                self._lost += 1
            self._ok_since_shrink = 0
            self._mesh_key = None  # rebuild over the survivors
        notify(self.name, "device_lost", 1)
        log.warning("device lane %s: device lost — mesh shrinks to %d "
                    "device(s); regrows when the breaker re-closes or "
                    "after %d healthy launches",
                    self.name, max(1, total - self._lost),
                    self.regrow_after)

    # -- the guarded launch --------------------------------------------

    def _watched(self, flight: _Flight) -> None:
        """Worker-thread body: failpoint sites + the launch itself.
        ``device.launch_hang`` fires BEFORE the launch (a launch that
        never returns); ``mesh.device_lost`` marks the launch as device
        loss; ``device.dispatch`` fires at the POST-launch boundary —
        donated staged buffers are consumed by then, so a ``return``
        spec exercises exactly the re-stage-on-retry hazard."""
        from .. import failpoints as _fp

        t_run = time.perf_counter()
        try:
            if _fp.ACTIVE:
                _fp.fire("device.launch_hang")
                try:
                    _fp.fire("mesh.device_lost")
                except _fp.FailpointError as e:
                    raise DeviceLostError(str(e)) from None
            with bind(lane=self.name, launch=flight.id, **flight.ids), \
                    span("lane.launch"):
                out = flight.launch()
            if _fp.ACTIVE:
                _fp.fire("device.dispatch")
            flight.box["result"] = out
        except BaseException as e:  # noqa: BLE001 - resolves to fallback
            flight.box["error"] = e
        finally:
            t_done = time.perf_counter()
            slow = t_done - t_run > SLOW_LAUNCH_S
            with self._lock:
                self._stats["spawn_s"] += t_run - flight.t_begin
                self._stats["run_s"] += t_done - t_run
                self._stats["launches_over_1s"] += slow
            if slow:
                # (before the waiter is woken: whoever saw the launch
                # end finds it counted, heard and logged)
                notify(self.name, "slow_launch", 1)
                log.warning(
                    "device lane %s: a launch ran for %.3fs (spawn "
                    "%.4fs) — over %.0fs; ids %s", self.name,
                    t_done - t_run, t_run - flight.t_begin,
                    SLOW_LAUNCH_S, flight.ids or "none (no session)")
            flight.t_done = time.perf_counter()
            flight.done.set()

    def begin(self, launch, fallback,
              deadline: Optional[float] = None) -> _Flight:
        """Start one guarded launch. ``launch`` must run the device
        dispatch AND force the result to host (numpy) before returning
        — forcing inside the worker is what lets the deadline cover a
        wedged execution, and what keeps staging overlap alive when the
        caller pipelines begin/finish. ``fallback`` is the bit-exact
        host twin, called at ``finish`` time only."""
        t_begin = time.perf_counter()
        with self._lock:
            self._stats["launches"] += 1
            launch_id = self._stats["launches"]
            self._stats["begun_in_flight"] += self._open > 0
            self._open += 1
        if not self.breaker.allow():
            with self._lock:
                self._stats["short_circuits"] += 1
            notify(self.name, "short_circuit", 1)
            return _Flight(launch, fallback, denied=True, deadline=0.0,
                           id=launch_id)
        fl = _Flight(launch, fallback, denied=False,
                     deadline=self.deadline if deadline is None
                     else deadline, t_begin=t_begin, id=launch_id)
        with span("lane.begin", lane=self.name, launch=launch_id):
            threading.Thread(target=self._watched, args=(fl,),
                             daemon=True,
                             name=f"flb-lane-{self.name}").start()
        return fl

    def finish(self, flight: _Flight):
        """Resolve one guarded launch to its final host result: the
        device verdict on success, the bit-exact fallback on denial,
        failure, or deadline expiry. Nothing is committed until this
        returns — a soft-killed worker's late result is discarded. The
        deadline runs from here, not from ``begin``."""
        try:
            return self._resolve(flight)
        finally:
            with self._lock:
                self._open -= 1

    def _resolve(self, flight: _Flight):
        if flight.denied:
            return self._fall_back(flight, record=False)
        t_wait = time.perf_counter()
        with bind(lane=self.name, launch=flight.id, **flight.ids), \
                span("lane.wait"):
            done = flight.done.wait(flight.deadline)
        t_woke = time.perf_counter()
        with self._lock:
            self._stats["blocked_s"] += t_woke - t_wait
            if done:
                self._stats["wake_s"] += \
                    t_woke - max(flight.t_done, t_wait)
        if not done:
            # wedged launch: abandon the worker (daemon thread; its
            # eventual result lands in a box nobody reads) and serve
            # the segment on the host twin
            with self._lock:
                self._stats["timeouts"] += 1
                self._stats["abandoned"] += 1
                self._ok_since_shrink = 0
            notify(self.name, "timeout", 1)
            log.warning(
                "device lane %s: launch exceeded its %.1fs deadline — "
                "soft-killed to the CPU fallback (worker abandoned)",
                self.name, flight.deadline)
            self.breaker.record_failure()
            return self._fall_back(flight)
        err = flight.box.get("error")
        if err is None:
            regrow = False
            with self._lock:
                self._stats["ok"] += 1
                if self._lost:
                    # regrow probe: a one-off loss must not pin a
                    # shrunk mesh forever when the breaker never
                    # opened — after enough healthy launches on the
                    # survivors, try the full device set again (a
                    # still-dead device just shrinks it back)
                    self._ok_since_shrink += 1
                    if self._ok_since_shrink >= self.regrow_after:
                        self._lost = 0
                        self._ok_since_shrink = 0
                        self._mesh_key = None
                        regrow = True
            if regrow:
                log.info("device lane %s: %d healthy launches on the "
                         "shrunk mesh — probing a regrow to the full "
                         "device set", self.name, self.regrow_after)
            self.breaker.record_ok()
            return flight.box["result"]
        if is_device_loss(err):
            self._device_lost()
        with self._lock:
            self._stats["failures"] += 1
            self._ok_since_shrink = 0
        notify(self.name, "failure", 1)
        log.warning("device lane %s: launch failed (%r) — segment "
                    "completes on the CPU fallback", self.name, err)
        self.breaker.record_failure()
        return self._fall_back(flight)

    def _fall_back(self, flight: _Flight, record: bool = True):
        with self._lock:
            self._stats["fallback_segments"] += 1
        if record:
            notify(self.name, "fallback", 1)
        return flight.fallback()

    def run(self, launch, fallback, deadline: Optional[float] = None):
        """begin + finish: one guarded, deadline-bounded launch."""
        return self.finish(self.begin(launch, fallback, deadline))

    # -- introspection -------------------------------------------------

    def stats(self) -> dict:
        with self._lock:
            out = dict(self._stats)
            out["lost_devices"] = self._lost
        out["breaker"] = self.breaker.state_name()
        mesh = self._mesh
        out["mesh_devices"] = mesh.devices.size if mesh is not None \
            else None
        return out


# -- the process-global lane registry ----------------------------------

_registry_lock = make_lock("fault._registry_lock")
_lanes: Dict[str, DeviceLane] = {}


def lane(name: str) -> DeviceLane:
    """The named lane, created on first use (process-global — the jax
    backend the lanes guard is process-global too)."""
    with _registry_lock:
        ln = _lanes.get(name)
        if ln is None:
            ln = _lanes[name] = DeviceLane(name)
        return ln


def lanes() -> Dict[str, DeviceLane]:
    with _registry_lock:
        return dict(_lanes)


def reset() -> None:
    """Drop every lane (tests: breaker/shrink state must not leak
    between cases)."""
    with _registry_lock:
        _lanes.clear()


def snapshot() -> Dict[str, dict]:
    """Every lane's ``stats()``, by name."""
    return {name: ln.stats() for name, ln in lanes().items()}


def health_block() -> dict:
    """The ``"device"`` block of ``/api/v1/health``: attach lifecycle
    (retry-world status) + every lane's breaker/failover state."""
    from . import device

    st = device.status()
    return {
        "attach": {k: st.get(k) for k in (
            "state", "platform", "attempts", "retries_max",
            "next_retry_eta_s", "generation", "error")},
        "lanes": snapshot(),
    }
