"""Spans and timing counters inside the program.

**Spans** ride the profiler's own buffer and clock: ``span(name, **ids)``
is a ``jax.profiler.TraceAnnotation`` named ``fbtpu:<name>`` while a
profiler session is active (``jax.profiler.start_trace``, or a capture
through ``[SERVICE] profiler_port``) and one shared no-op otherwise, so
"tracing on" is exactly "a profiler session is active". While one runs,
the spans land in the trace's host plane beside the device's ``XLA Ops``
and ``XLA Modules`` lines, each with its ``ids`` as event stats; while
none runs a span costs one check. This module never imports jax:
``net_forward`` and the engine stay importable without it, and before
jax is loaded every span is the no-op.

Spans of one forward frame share ``chunk`` (the frame's ``chunk``
option), spans of one segment also ``seg``, spans of one launch also
``lane``. ``bind`` sets ids that every span opened beneath it inherits —
in the same asyncio task or thread (a ``ContextVar``, consulted only
while a session is active); the device lane copies ``current_ids()``
onto a launch and re-binds them on its worker thread. A ``ContextVar``
is copied by whatever a bound frame schedules (``call_later``,
``create_task``): such work would carry the frame's ids without being
caused by it, so nothing is scheduled beneath a ``bind`` — the engine's
flushes start from its own housekeeping task and carry no ``chunk``
(``tests/test_spans.py`` holds them to it). The engine thread is an
asyncio loop, so another task's span may open inside ``forward.read``
and end after it: readers take self time from interval arithmetic,
never from a stack.

Granularity rule: a span or a counter update per socket read, message,
chunk, segment or launch — never per record.

A pass of the cyclic collector is a span too (``gc.collect`` with
``gen`` and ``collected``), written by a ``gc.callbacks`` hook that
the engine installs at its start (:func:`watch_gc`) and takes out at
its stop: it lands on the thread the pass ran on, inside the frame it
delayed, and inherits that frame's ids. While no session is active the
hook returns after ``enabled()``.

**Counters** are always on: :class:`ShardedTimings` is the
``raw_timings`` object a filter plugin hangs on itself (seconds and
counts, summed across ingest threads on read). A key earns its place by
being read — a per-layer metric of the benchmark, a check, the smoke;
work that nothing reads over a whole run gets a span only.
"""

from __future__ import annotations

import contextvars
import functools
import gc
import sys
import threading
import time
from typing import Optional

PREFIX = "fbtpu:"


class _NoSpan:
    """The one shared span of a process that is not being traced."""

    __slots__ = ()

    def __enter__(self):
        return self

    def __exit__(self, *_exc):
        return False

    def set_metadata(self, **_ids) -> None:
        """What a live annotation takes once the work has run (the
        bytes a read returned)."""


NOOP = _NoSpan()

_trace_me = None  # jax.profiler.TraceAnnotation, once jax is loaded
_ids: contextvars.ContextVar = contextvars.ContextVar("fbtpu_span_ids",
                                                      default=None)


def enabled() -> bool:
    """True while a profiler session is active in this process."""
    global _trace_me
    tm = _trace_me
    if tm is None:
        profiler = sys.modules.get("jax.profiler") \
            if "jax" in sys.modules else None
        if profiler is None:
            return False
        tm = _trace_me = profiler.TraceAnnotation
    return tm.is_enabled()


def span(name: str, **ids):
    """Context manager around one piece of work: a profiler annotation
    ``fbtpu:<name>`` carrying the bound ids and ``ids``, or ``NOOP``."""
    if not enabled():
        return NOOP
    bound = _ids.get()
    if bound:
        ids = {**bound, **ids}
    return _trace_me(PREFIX + name, **ids)


def spanned(name: str):
    """Decorator: the whole call under ``span(name)``. The function
    keeps its name and place, so whoever looks it up by name (the
    benchmark's outside wrappers do) still finds it."""
    def deco(fn):
        @functools.wraps(fn)
        def call(*args, **kwargs):
            with span(name):
                return fn(*args, **kwargs)
        return call
    return deco


class _Bind:
    __slots__ = ("ids", "token")

    def __init__(self, ids: dict):
        self.ids = ids

    def __enter__(self):
        self.token = _ids.set(self.ids)
        return self

    def __exit__(self, *_exc):
        _ids.reset(self.token)
        return False


def bind(**ids):
    """Context manager: spans opened beneath it (same task or thread)
    inherit ``ids`` on top of those already bound. ``None`` values are
    left out."""
    if not enabled():
        return NOOP
    ids = {k: v for k, v in ids.items() if v is not None}
    return _Bind({**(_ids.get() or {}), **ids})


def current_ids() -> Optional[dict]:
    """The ids bound here, to carry onto another thread (None while no
    session is active)."""
    return _ids.get() if enabled() else None


# ------------------------------------------------------------ gc passes

_gc_span = None    # the span of the pass that is running (one at a time)


def _on_gc(phase: str, info: dict) -> None:
    global _gc_span
    if phase == "start":
        # (a second engine's hook finds the pass's span open already)
        if _gc_span is None and enabled():
            _gc_span = span("gc.collect", gen=info["generation"])
            _gc_span.__enter__()
    elif _gc_span is not None:
        sp, _gc_span = _gc_span, None
        sp.set_metadata(collected=info["collected"])
        sp.__exit__(None, None, None)


def watch_gc():
    """Install the ``gc.callbacks`` hook for one running engine → the
    handle :func:`unwatch_gc` takes. Each engine of a process has a
    hook object of its own (so one's stop leaves the other's in), and a
    pass is still one span: whichever runs first opens and closes it."""
    hook = functools.partial(_on_gc)
    gc.callbacks.append(hook)
    return hook


def unwatch_gc(hook) -> None:
    gc.callbacks.remove(hook)


class _Timed:
    __slots__ = ("tm", "key", "sp", "t0", "seconds")

    def __init__(self, tm, key: str, sp):
        self.tm, self.key, self.sp = tm, key, sp

    def __enter__(self):
        self.t0 = time.perf_counter()
        self.sp.__enter__()
        return self

    def __exit__(self, *exc):
        self.sp.__exit__(*exc)
        self.seconds = time.perf_counter() - self.t0
        self.tm.add(self.key, self.seconds)
        return False


class ShardedTimings:
    """Per-thread timing shards for a plugin's hot-loop accounting.

    Adds go to an uncontended thread-local shard and reads sum across
    shards (a shared dict + lock serialized every ingest worker on one
    mutex several times per chunk). One shard is made and kept per
    thread that ever adds: only long-lived ingest threads may call
    ``add`` — never a device-lane worker, which is a new thread per
    launch (what a worker measures goes to ``DeviceLane`` stats).

    The read-only mapping interface (iteration / item get) is what
    the benchmark's counter flattening uses: item reads return the
    cross-shard sum.
    """

    def __init__(self, keys: tuple):
        self._keys = tuple(keys)
        self._tls = threading.local()
        self._shards: list = []
        self._reg_lock = threading.Lock()  # shard registration (cold)

    def _shard(self) -> dict:
        d = getattr(self._tls, "d", None)
        if d is None:
            d = {k: 0 for k in self._keys}
            with self._reg_lock:
                self._shards.append(d)
            self._tls.d = d
        return d

    def add(self, key: str, value) -> None:
        self._shard()[key] += value

    def timed(self, key: str, name: str, **ids):
        """Context manager: the span ``name`` around the work, and its
        seconds added to ``key`` — one site, both readings. Once it
        has exited it holds them as ``seconds``."""
        return _Timed(self, key, span(name, **ids))

    def __iter__(self):
        return iter(self._keys)

    def __contains__(self, key) -> bool:
        return key in self._keys

    def __getitem__(self, key):
        with self._reg_lock:
            shards = list(self._shards)
        return sum(d[key] for d in shards)
