"""Device attach controller — jax backend init must never block the pipeline.

The first backend touch — ``jax.devices()`` / the first ``jnp.asarray``
— blocks in C while the runtime brings the accelerator up (seconds on a
local chip, longer when the chip is slow to hand over), during which
Python signal handlers cannot run. The reference never has this problem
because its regex engine is host-side C (Onigmo); our device kernels
do, so every plugin that compiles a device program routes its first
backend touch through here:

- ``attach_async()`` starts backend init once, in a daemon thread.
- ``wait(timeout)`` joins it with a bounded, signal-interruptible wait.
- ``ready()`` is a cheap non-blocking probe.

Until ``ready()``, callers serve records on their (bit-exact) CPU
fallback path; when attach completes, compiled device programs
materialize lazily and the device path swaps in live.

Attach is RETRIED (fbtpu-armor): a failed backend init no longer pins
the CPU path for the process lifetime. The worker makes up to
``FBTPU_ATTACH_RETRIES`` attempts with jittered exponential backoff
(base ``FBTPU_ATTACH_BACKOFF_S``); ``failed()`` means *exhausted*, not
"tried once". Each successful attach bumps the attach **generation** —
mesh-lane consumers key their resolution on it, so an attach that
succeeds after earlier refusals (or after :func:`reattach_async`) swaps
the device path in live instead of staying pinned. ``status()`` reports
the attempt count, per-attempt error history and the next retry ETA.

``FBTPU_ATTACH_WAIT_S`` tunes how long plugin init waits synchronously
for the device before proceeding on CPU (default 2 s — right for an
agent, which must start serving; a run that NEEDS the device calls
``wait()`` with its own timeout before it starts the engine and checks
the lane counters afterwards, as ``chip_smoke.py`` does).

The persistent XLA compile cache is placed here too
(:func:`_configure_compile_cache`), before the first compile, because
every device user passes through this module.
"""

from __future__ import annotations

import logging
import os
import random
import threading
import time
from typing import List, Optional

from ..core.lockorder import make_lock

log = logging.getLogger("flb.device")

_lock = make_lock("device._lock")
_state = "unattached"  # unattached | attaching | ready | failed
_error: Optional[str] = None
_thread: Optional[threading.Thread] = None
_attach_seconds: Optional[float] = None
_platform: Optional[str] = None
_attempts = 0
_retry_history: List[dict] = []
_next_retry_at: Optional[float] = None
_generation = 0  # successful attaches; consumers re-resolve on change

#: History is bounded to the most recent attempts: a permanently-absent
#: backend re-attached by the fault domain every breaker cooldown would
#: otherwise grow the list (and every health/status copy) forever.
_RETRY_HISTORY_MAX = 20


def default_wait() -> float:
    try:
        return float(os.environ.get("FBTPU_ATTACH_WAIT_S", "2"))
    except ValueError:
        return 2.0


def attach_retries() -> int:
    """Max attach attempts before ``failed()`` (exhausted)."""
    try:
        return max(1, int(os.environ.get("FBTPU_ATTACH_RETRIES", "3")))
    except ValueError:
        return 3


def attach_backoff() -> float:
    """Base backoff between attempts (doubles per attempt, ±25%
    jitter so a fleet of restarting workers never thunders in step)."""
    try:
        return max(0.0, float(
            os.environ.get("FBTPU_ATTACH_BACKOFF_S", "0.5")))
    except ValueError:
        return 0.5


#: Fixed in-checkout home of the persistent compile cache when
#: ``JAX_COMPILATION_CACHE_DIR`` is unset — never a temp name, pid or
#: time: the directory is part of the cache key, so one that moves
#: never hits (.gitignore lists it).
COMPILE_CACHE_DIR = os.path.join(
    os.path.dirname(os.path.dirname(os.path.dirname(
        os.path.abspath(__file__)))), ".jax_cache")


def _configure_compile_cache(platform: str) -> None:
    """Point jax's persistent compilation cache somewhere that stays
    put, before this process compiles anything. An exported
    ``JAX_COMPILATION_CACHE_DIR`` wins untouched (jax reads it itself;
    no directory is set in code then). Otherwise an accelerator backend
    gets the fixed in-checkout directory; a CPU backend gets none — the
    cache exists for the chip's cold compiles, and XLA:CPU's reload
    path logs a machine-feature mismatch at error level on every hit.
    The minimum-compile-time threshold drops to zero so the sub-second
    kernels (sketch updates, small-rule grep children) are kept too —
    a cold pipeline compiles dozens of those under the launch
    deadline."""
    import jax

    if not os.environ.get("JAX_COMPILATION_CACHE_DIR"):
        if platform == "cpu":
            return
        jax.config.update("jax_compilation_cache_dir", COMPILE_CACHE_DIR)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", -1)


def _attach_once(attempt: int) -> None:
    """One backend-init attempt; raises on failure."""
    global _attach_seconds, _platform
    t0 = time.time()
    from .. import failpoints as _fp

    if _fp.ACTIVE:
        # delay(ms) simulates a slow attach; return(err) fails THIS
        # attempt (the retry loop decides whether the CPU fallback
        # pins)
        _fp.fire("device.attach")
    import jax
    import jax.numpy as jnp

    n = len(jax.devices())  # the (possibly minutes-long) backend init
    # backend init compiles nothing; the warm-up dispatch below is this
    # process's first compile, so the cache is placed just before it
    _configure_compile_cache(jax.default_backend())
    # one trivial dispatch so the runtime is fully warm before the
    # first real kernel
    jnp.zeros((8,), dtype=jnp.int32).block_until_ready()
    global _state, _generation
    with _lock:
        _attach_seconds = time.time() - t0
        _platform = jax.default_backend()
        _state = "ready"
        _generation += 1
        gen = _generation
    log.info("device backend attached: %d device(s) in %.1fs "
             "(attempt %d, generation %d)",
             n, _attach_seconds, attempt, gen)
    if gen > 1 or attempt > 1:
        # a late/re-attach: tell the fault domain so lanes can swap
        # the device path back in and the metric counts the event
        try:
            from . import fault as _fault

            _fault.notify("attach", "reattach", gen)
        except Exception:  # pragma: no cover - listener must not kill attach
            log.exception("reattach notification failed")


def _attach_worker() -> None:
    global _state, _error, _attempts, _next_retry_at
    retries = attach_retries()
    backoff = attach_backoff()
    for attempt in range(1, retries + 1):
        with _lock:
            _attempts = attempt
            _next_retry_at = None
        t0 = time.time()
        try:
            _attach_once(attempt)
            return
        except Exception as e:  # pragma: no cover - platform-dependent
            err = repr(e)
            with _lock:
                _error = err
                _retry_history.append({
                    "attempt": attempt,
                    "error": err,
                    "elapsed_s": round(time.time() - t0, 3),
                })
                del _retry_history[:-_RETRY_HISTORY_MAX]
            if attempt >= retries:
                break
            # jittered exponential backoff: base * 2^(attempt-1) ± 25%
            delay = backoff * (2.0 ** (attempt - 1))
            delay *= random.uniform(0.75, 1.25)
            with _lock:
                _next_retry_at = time.time() + delay
            log.warning("device attach attempt %d/%d failed (%r); "
                        "retrying in %.2fs", attempt, retries, e, delay)
            time.sleep(delay)
    with _lock:
        _state = "failed"
        _next_retry_at = None
    log.warning("device attach exhausted after %d attempt(s) "
                "(CPU path pinned until reattach_async): %s",
                retries, _error)


def attach_async() -> None:
    """Start backend init in the background (idempotent)."""
    global _state, _thread
    with _lock:
        if _state != "unattached":
            return
        _state = "attaching"
        _thread = threading.Thread(
            target=_attach_worker, daemon=True, name="flb-device-attach"
        )
        # start under the lock: wait() must never observe a created-but-
        # unstarted thread (is_alive False) and skip its join
        _thread.start()


def reattach_async() -> bool:
    """Re-arm attach after exhaustion (a new retry budget). True when a
    fresh attempt was started; False when attach is already running /
    ready. The fault domain calls this when a device-lane breaker
    half-opens against an exhausted attach — the probe that would
    otherwise test a dead backend instead re-tests the attach itself."""
    global _state, _thread
    with _lock:
        if _state != "failed":
            return False
        _state = "attaching"
        _thread = threading.Thread(
            target=_attach_worker, daemon=True,
            name="flb-device-reattach"
        )
        _thread.start()
    return True


def ready() -> bool:
    return _state == "ready"


def failed() -> bool:
    """True when attach EXHAUSTED its retry budget (terminal until
    :func:`reattach_async`) — a single failed attempt mid-retry-loop
    still reports attaching."""
    return _state == "failed"


def generation() -> int:
    """Successful-attach counter (0 until the first attach). Mesh-lane
    resolution is cached per generation: a bump means the device path
    must be re-probed (the PR-8 "resolution stays open until terminal"
    rule, extended to re-attach)."""
    return _generation


def wait(timeout: Optional[float] = None) -> bool:
    """Ensure attach is running and wait up to ``timeout`` seconds for
    it (None = the FBTPU_ATTACH_WAIT_S default). Returns ready()."""
    attach_async()
    t = _thread
    if t is not None and t.is_alive():
        t.join(default_wait() if timeout is None else timeout)
    return ready()


def platform() -> Optional[str]:
    """Attached backend name ('tpu', 'cpu', ...); None until ready."""
    return _platform


def device_count() -> int:
    """Attached backend's device count; 0 until ready. Under the
    simulated-mesh lane (``--xla_force_host_platform_device_count=8``)
    this reports the virtual devices — the mesh planes (ops.mesh,
    ops.grep mesh matcher, flux kernels) treat those exactly like
    chips. Safe after ready(): the first (possibly minutes-long)
    backend touch already happened in the attach worker."""
    if not ready():
        return 0
    import jax

    return len(jax.devices())


def status() -> dict:
    """Attach state for diagnostics and the benchmark's result: retry-world
    fields (attempt count, per-attempt error history — the most recent
    ``_RETRY_HISTORY_MAX`` entries — next retry ETA, attach
    generation) ride along with the original block."""
    with _lock:
        eta = None
        if _next_retry_at is not None:
            eta = round(max(0.0, _next_retry_at - time.time()), 3)
        return {
            "state": _state,
            "error": _error,
            "platform": _platform,
            "attach_seconds": _attach_seconds,
            "attempts": _attempts,
            "retries_max": attach_retries(),
            "retry_history": list(_retry_history),
            "next_retry_eta_s": eta,
            "generation": _generation,
        }
