"""The engine — event loop, ingest path, dispatch, flush, retries.

Reference: src/flb_engine.c (flb_engine_start event loop),
src/flb_engine_dispatch.c (chunk → task → per-route flush),
src/flb_task.c (task refcounting/retries), src/flb_input_chunk.c
(ingest + synchronous filter chain at append, :3078).

Architecture (TPU-first, not a port): the engine is a host-side asyncio
loop running in its own thread (the reference runs its engine in a pthread
spawned by flb_start, src/flb_lib.c). Inputs append records; the filter
chain runs synchronously at ingest exactly like the reference; chunks
accumulate per (input, tag); a flush timer drains ready chunks into tasks
and one async flush per (task × route) — the coroutine-per-flush model of
include/fluent-bit/flb_output.h:730 mapped onto asyncio. Device (TPU)
work happens inside filters via the ops layer; the engine itself never
blocks on the device.
"""

from __future__ import annotations

import asyncio
import itertools
import logging
import sys
import threading
import time
from typing import Any, Dict, List, Optional

from .. import failpoints as _fp
from ..codec.chunk import Chunk, EVENT_TYPE_LOGS, EVENT_TYPE_METRICS, EVENT_TYPE_TRACES
from ..codec.events import LogEvent, decode_events, reencode_event
from . import copywitness as _cw
from .config import ServiceConfig
from .lockorder import make_lock
from .metrics import MetricsRegistry
from .plugin import (
    FLUSH_CHUNK,
    FilterInstance,
    FilterResult,
    FlushResult,
    InputInstance,
    OutputInstance,
    registry as default_registry,
)
from .scheduler import backoff_full_jitter
from .spans import span, spanned, unwatch_gc, watch_gc

log = logging.getLogger("flb.engine")

# _dispatch_chunk outcomes: PARKED must stay falsy (callers gate the
# park-and-break path on `not rc`)
PARKED = 0      # task map full — chunk goes back to the backlog
DISPATCHED = 1  # task spawned, a task-map slot was consumed
ABSORBED = 2    # handled without a slot (guard-shed / no live routes)

_task_ids = itertools.count(1)


class Task:
    """One flushable chunk + its routes + retry state
    (reference struct flb_task, include/fluent-bit/flb_task.h:82-98)."""

    __slots__ = ("id", "chunk", "routes", "retries", "users", "engine",
                 "processed")

    def __init__(self, chunk: Chunk, routes: List[OutputInstance]):
        self.id = next(_task_ids)
        self.chunk = chunk
        self.routes = routes
        self.retries: Dict[str, int] = {}  # output name → attempts
        self.users = 0
        # output name → processed payload (output-side processors run
        # once per route; retries reuse the cached bytes)
        self.processed: Dict[str, bytes] = {}


#: the interpreter's GIL switch interval from the first engine start on
#: (CPython's default is 5 ms). The engine's threads hand work to each
#: other across waits that release the GIL — in_forward's absorb worker
#: and a lane's launch thread come back from the device, a ctypes call
#: or a lock while the event loop decodes the next frame — and a thread
#: that wants the GIL back gets it only when the holder blocks or the
#: interval runs out. At 5 ms in_forward's two stages cost each other
#: 13 ms a 4,096-line frame and the overlap gained nothing. 1 ms keeps
#: the absorb within 4 ms of running alone; 0.5 ms is no faster there
#: and costs a chain that is Python on both threads (the sketch cell)
#: 4.6 % in hand-overs where 1 ms costs it 2.1 % (PERF.md section 6,
#: PR 30). A C call that holds the GIL (the codec's one ``unpack_from``
#: a frame) is not cut short by any interval.
GIL_SWITCH_INTERVAL_S = 0.001


class _RawTail:
    """Continuation returned by ``_ingest_raw`` when a filter declines
    mid-chain AFTER an earlier stateful filter's side effects are out.
    The caller finishes the remaining filters per-record via
    ``_finish_raw_tail`` — outside the raw-path lock scope, because the
    tail re-enters the decode path's ``self._ingest_lock`` and taking
    that while still holding ``ins.ingest_lock`` would invert the
    canonical lock order (fbtpu-locksmith)."""

    __slots__ = ("tag", "data", "remaining", "n", "n_records", "deltas",
                 "in_bytes")

    def __init__(self, tag, data, remaining, n, n_records, deltas,
                 in_bytes):
        self.tag = tag
        self.data = data
        self.remaining = remaining  # the declining filter onward
        self.n = n
        self.n_records = n_records
        self.deltas = deltas
        self.in_bytes = in_bytes


class Engine:
    """The pipeline runtime for one configuration context."""

    def __init__(self, service: Optional[ServiceConfig] = None, registry=None):
        self.service = service or ServiceConfig()
        self.registry = registry or default_registry
        self.inputs: List[InputInstance] = []
        self.filters: List[FilterInstance] = []
        self.outputs: List[OutputInstance] = []
        self.customs: List = []
        self.metrics = MetricsRegistry()
        self.storage = None  # set by core.storage when storage_path configured
        self.parsers: Dict[str, Any] = {}  # named parsers (flb_parser registry)
        self.ml_parsers: Dict[str, Any] = {}  # multiline parsers (flb_ml)
        self.sp = None  # stream processor (flb_sp), created on first task
        self.traces: Dict[str, dict] = {}  # chunk-trace "tap" contexts
        self._ingest_src = None  # input currently appending (under lock)

        self._backlog: List[Chunk] = []  # recovered chunks to re-dispatch
        self.loop: Optional[asyncio.AbstractEventLoop] = None
        self._thread: Optional[threading.Thread] = None
        self._gc_hook = None  # core/spans.py::watch_gc, while running
        self._started = threading.Event()
        self._stopping = False
        self._stop_event = threading.Event()  # wakes threaded collectors
        self._ingest_lock = make_lock("Engine._ingest_lock",
                                      reentrant=True)
        self._pending_flushes: set = set()
        # scheduler-owned retries (flb_engine_dispatch_retry,
        # src/flb_engine_dispatch.c:36-99): a retry is a loop timer +
        # this record, NOT a sleeping coroutine — key (chunk id, output)
        self._pending_retries: Dict[tuple, tuple] = {}
        # priority bucket queue (flb_bucket_queue, 8 priorities): ready
        # engine callbacks drain lowest-priority-number first, so retry
        # fires (scheduler, top) outrun fresh flush spawns (flush, 2)
        from .bucket_queue import BucketQueue

        self._event_queue = BucketQueue()
        self._event_queue_lock = make_lock("Engine._event_queue_lock")
        # task id map, default 2048 slots (flb_task_map, flb_task.c:542
        # + FLB_CONFIG_DEFAULT_TASK_MAP_SIZE): dispatch pauses when full
        self._task_map: Dict[int, Task] = {}
        self._task_map_warned = 0.0
        self._notification_subs: List = []
        self.started_at: float = 0.0
        self.reload_count = 0
        # configuration generation (fbtpu-qos): bumped by every
        # ReloadTxn.commit in the same ingest-lock critical section
        # that swaps the instance lists, so generation / reload_count /
        # list contents always read consistently
        self.generation = 0
        # outputs removed by hot reload: their in-flight tasks hold
        # direct references and finish normally; stop() reaps their
        # worker pools and runs their exit callbacks
        self._retired_outputs: List[OutputInstance] = []
        # canonical names freed by hot-reload removals (and trace-tap
        # teardown), per instance kind: numbering must never hand a
        # fresh instance a dead one's name — a guard-shed chunk's
        # persisted route_names or a dashboard's metric series would
        # silently re-bind to the unrelated newcomer
        self._retired_names: Dict[str, set] = {}
        # serializes whole hot-reload transactions (core/qos.py
        # ReloadTxn.commit): two concurrent commits would each write
        # back instance lists derived from their own pre-build
        # snapshot, silently dropping the other's changes
        self._reload_lock = make_lock("Engine._reload_lock")
        self.admin_server = None
        self.reload_callback = None  # wired by the CLI for /api/v2/reload

        self._init_metrics()
        # fbtpu-guard: flush deadlines, per-output breakers, watchdog +
        # load shedding (core/guard.py). Touches flush paths only —
        # the per-record ingest hot path has no guard code, and the
        # periodic checks ride flush_all's existing timer.
        from .guard import Guard

        self.guard = Guard(self)
        # fbtpu-qos: tenant admission, weighted-fair dispatch, hot
        # reload (core/qos.py). Ingest pays one tenant lookup + counter
        # per append; dispatch order comes from the fair queue.
        from .qos import Qos

        self.qos = Qos(self)

    # ------------------------------------------------------------------
    # metrics (names mirror the reference's fluentbit_* families)
    # ------------------------------------------------------------------

    def _init_metrics(self) -> None:
        m = self.metrics
        self.m_in_records = m.counter("fluentbit", "input", "records_total",
                                      "Input records", ("name",))
        self.m_in_bytes = m.counter("fluentbit", "input", "bytes_total",
                                    "Input bytes", ("name",))
        self.m_filter_add = m.counter("fluentbit", "filter", "add_records_total",
                                      "Records added by filter", ("name",))
        self.m_filter_drop = m.counter("fluentbit", "filter", "drop_records_total",
                                       "Records dropped by filter", ("name",))
        self.m_filter_emit = m.counter("fluentbit", "filter", "emit_records_total",
                                       "Records re-emitted by filter", ("name",))
        # batched fast-path declines (north-star addition): the
        # exactness contract says a decline is invisible in OUTPUT —
        # this counter makes it visible in OPS, so a config change that
        # silently demotes a hot chain to per-record shows up on a dash
        self.m_filter_batch_decline = m.counter(
            "fluentbit", "filter", "batch_declines_total",
            "Batched fast-path declines to the per-record path",
            ("name",))
        self.m_out_proc_records = m.counter("fluentbit", "output", "proc_records_total",
                                            "Records delivered", ("name",))
        self.m_out_proc_bytes = m.counter("fluentbit", "output", "proc_bytes_total",
                                          "Bytes delivered", ("name",))
        self.m_out_errors = m.counter("fluentbit", "output", "errors_total",
                                      "Flush errors", ("name",))
        self.m_out_retries = m.counter("fluentbit", "output", "retries_total",
                                       "Flush retries", ("name",))
        self.m_out_retries_failed = m.counter("fluentbit", "output", "retries_failed_total",
                                              "Retries exhausted", ("name",))
        self.m_out_dropped = m.counter("fluentbit", "output", "dropped_records_total",
                                       "Records dropped at output", ("name",))
        self.m_uptime = m.gauge("fluentbit", "", "uptime", "Uptime seconds")
        # end-to-end latency histogram (reference src/flb_engine.c:400-405)
        self.m_latency = m.histogram("fluentbit", "output", "latency_seconds",
                                     "chunk create → delivered latency", ("name",))
        # memrb ring-buffer eviction (src/flb_input_chunk.c:2936-2966)
        self.m_memrb_dropped_chunks = m.counter(
            "fluentbit", "input", "memrb_dropped_chunks_total",
            "Chunks evicted by memrb ring buffer", ("name",))
        self.m_memrb_dropped_bytes = m.counter(
            "fluentbit", "input", "memrb_dropped_bytes_total",
            "Bytes evicted by memrb ring buffer", ("name",))
        # fault-injection observability: every armed failpoint that
        # actually fires shows up here, so a soak run (or a forgotten
        # armed site in staging) is visible on the same dashboards as
        # the errors it provokes
        self.m_failpoint_triggered = m.counter(
            "fluentbit", "", "failpoint_triggered_total",
            "Faults triggered by the failpoint plane", ("name",))
        # fbtpu-armor device fault domain (ops/fault.py): per-lane
        # failover counters, fed by the fault listener bridge — a mesh
        # lane silently degrading to the CPU fallback is a metric, not
        # a mystery CPU-speed bench number
        self.m_device_fallback = m.counter(
            "fluentbit", "device", "fallback_segments_total",
            "Segments completed on the bit-exact CPU fallback after a "
            "device launch failed, timed out, or was short-circuited",
            ("lane",))
        self.m_device_timeouts = m.counter(
            "fluentbit", "device", "launch_timeouts_total",
            "Device launches soft-killed past the lane deadline",
            ("lane",))
        self.m_device_failures = m.counter(
            "fluentbit", "device", "launch_failures_total",
            "Device launches that raised (XlaRuntimeError, injected "
            "faults, resource exhaustion)", ("lane",))
        self.m_device_lost = m.counter(
            "fluentbit", "device", "device_lost_total",
            "Device-loss events (mesh shrinks to the survivors)",
            ("lane",))
        self.m_device_breaker = m.gauge(
            "fluentbit", "device", "breaker_state",
            "Per-lane device breaker state (0 closed, 1 half-open, "
            "2 open)", ("lane",))
        self.m_device_mesh = m.gauge(
            "fluentbit", "device", "mesh_devices",
            "Devices in the lane's current mesh (shrinks on loss, "
            "regrows on breaker re-close)", ("lane",))
        self.m_device_lane_seconds = m.gauge(
            "fluentbit", "device", "lane_seconds",
            "Seconds of a lane's launches since start, by phase: spawn "
            "(begin to worker running), run (launch closure on the "
            "worker), blocked (finish waiting on the worker), wake "
            "(the worker done to the waiting thread running again)",
            ("lane", "phase"))
        self.m_device_slow_launches = m.counter(
            "fluentbit", "device", "launches_over_1s_total",
            "Device launches whose closure ran for more than a second "
            "on the lane's worker (a stalled launch, or a first one "
            "that compiled)", ("lane",))
        self.m_device_reattach = m.counter(
            "fluentbit", "device", "reattach_total",
            "Late/re-attach generations (the mesh lane swapped in "
            "live after earlier refusals)")
        # fbtpu-shrink (DEVICE_PLANE.md "shrink"): compile-path DFA
        # reduction outcomes
        self.m_shrink_states = m.counter(
            "fluentbit", "grep_shrink", "states_eliminated_total",
            "DFA states eliminated by the compile-path minimizer "
            "(Hopcroft + dead-state pruning), summed over compiled "
            "rules", ("name",))
        self.m_shrink_classes = m.counter(
            "fluentbit", "grep_shrink", "classes_eliminated_total",
            "Byte classes eliminated by the post-minimization class "
            "remerge, summed over compiled rules", ("name",))

    # ------------------------------------------------------------------
    # configuration
    # ------------------------------------------------------------------

    def _number_instance(self, ins, peers) -> None:
        # count-of-peers matches the reference's append-only numbering,
        # but a hot reload can REMOVE lib.0 while lib.1 survives — a
        # later add would count one peer and collide on lib.1. Bump
        # past taken names (never reuse a retired name: a fresh
        # instance must not inherit a dead one's metric series)
        n = sum(1 for p in peers if p.plugin.name == ins.plugin.name)
        taken = {p.name for p in peers} \
            | self._retired_names.get(type(ins).__name__, set())
        while f"{ins.plugin.name}.{n}" in taken:
            n += 1
        ins.name = f"{ins.plugin.name}.{n}"
        pool = getattr(ins, "pool", None)
        if pool is not None:
            pool.in_name = ins.name

    def _make_instance(self, create, name: str, props, peers):
        """create + number + set props — shared by the config-time
        builders and the hot-reload build phase (core/qos.py) so the
        construction sequence cannot drift between them."""
        ins = create(name)
        self._number_instance(ins, peers)
        # props is a dict (builder API) or a properties ITEM LIST
        # (hot-reload *_items staging: repeated keys + declared order)
        items = props.items() if hasattr(props, "items") else props
        for k, v in items:
            ins.set(k, v)
        return ins

    def _init_instance(self, ins) -> None:
        """configure + plugin.init + mark initialized — THE live-init
        sequence. start(), hidden_input and hot-reload builds all go
        through here: a future post-init step added in one place
        cannot silently skip the others."""
        ins.configure()
        ins.plugin.init(ins, self)
        ins._initialized = True

    def input(self, name: str, **props) -> InputInstance:
        ins = self._make_instance(self.registry.create_input, name,
                                  props, self.inputs)
        # COW swap: collectors iterate engine.inputs lock-free, so the
        # builder publishes a fresh list instead of mutating the alias
        with self._ingest_lock:
            self.inputs = self.inputs + [ins]
        return ins

    def filter(self, name: str, **props) -> FilterInstance:
        ins = self._make_instance(self.registry.create_filter, name,
                                  props, self.filters)
        # hidden flux-SQL filters stand in for the stream processor,
        # which runs POST-filter at ingest — user filters registered
        # later (config files apply [STREAM_TASK] before [FILTER])
        # must still run BEFORE them or flux would aggregate records
        # the chain was about to drop/rewrite
        pos = len(self.filters)
        while pos > 0 and getattr(self.filters[pos - 1],
                                  "_flux_sql_hidden", False):
            pos -= 1
        # COW swap (see input()): ingest walks engine.filters lock-free
        with self._ingest_lock:
            self.filters = self.filters[:pos] + [ins] + self.filters[pos:]
        return ins

    def output(self, name: str, **props) -> OutputInstance:
        ins = self._make_instance(self.registry.create_output, name,
                                  props, self.outputs)
        # COW swap (see input()): the router reads engine.outputs
        # lock-free while dispatching
        with self._ingest_lock:
            self.outputs = self.outputs + [ins]
        return ins

    def custom(self, name: str, **props):
        """Custom plugin instance (flb_custom_create); initialized
        before the pipeline at start()."""
        ins = self.registry.create_custom(name)
        self._number_instance(ins, self.customs)
        for k, v in props.items():
            ins.set(k, v)
        self.customs.append(ins)
        return ins

    def parser(self, name: str, **props):
        """Create + register a named parser (flb_parser_create)."""
        from ..parsers import create_parser

        p = create_parser(name, **props)
        self.parsers[p.name] = p
        return p

    def ml_parser(self, name: str, rules=None, flush_ms: int = 2000,
                  key_content: str = "log"):
        """Create + register a named multiline parser
        ([MULTILINE_PARSER] section / flb_ml_parser_create)."""
        from ..multiline import MLParser, MLRule

        # from_states may be comma-separated ("start_state,cont" —
        # flb_ml_rule_create splits on comma)
        mlr = [
            MLRule([s.strip() for s in str(r[0]).split(",")], r[1], r[2])
            if not isinstance(r, MLRule) else r
            for r in (rules or [])
        ]
        p = MLParser(name, mlr, flush_ms=flush_ms, key_content=key_content)
        self.ml_parsers[name] = p
        return p

    def sp_task(self, sql: str, allow_flux: bool = True):
        """Register a stream-processor query (flb_sp_create task;
        [STREAM_TASK] Exec). The SP runs synchronously post-filter at
        ingest (src/flb_input_chunk.c:3155) and its window timer rides a
        collector on the SP emitter.

        Sketch-eligible queries transparently resolve against the flux
        plane (fbtpu-flux): a hidden ``flux`` filter maintains the
        aggregation state inside the (batched) filter pass, the task
        reads windows from it, and the raw ingest fast path stays on
        for the query's tag. ``allow_flux=False`` pins the exact
        per-event evaluation (the differential harness's twin), as does
        ``WITH (flux='off')`` per query or FBTPU_FLUX_SQL=off globally.
        """
        import os as _os

        from ..stream_processor import StreamProcessor

        if self.sp is None:
            self.sp = StreamProcessor(self)
        task = self.sp.create_task(sql)
        if allow_flux and _os.environ.get(
                "FBTPU_FLUX_SQL", "on").lower() not in ("0", "off"):
            from ..flux.query import attach_flux

            try:
                attach_flux(self, task)
            except Exception:
                log.exception(
                    "flux attach failed; query %r stays on the exact "
                    "evaluation path", sql)
        # window timer: piggyback a collector on the SP emitter input
        if self.sp._emitter is None:
            ins = self.hidden_input(
                "emitter", alias="emitter_for_stream_processor"
            )
            self.sp._emitter = ins.plugin
            self.sp.emitter_instance = ins
            sp = self.sp

            def _tick(_engine):
                with self._ingest_lock:
                    sp.tick()

            ins.plugin.collect_interval = 0.5
            ins.plugin.collect = _tick
            # tasks may be registered AFTER engine start: _main's
            # startup pass has already run, so schedule the collector
            # ourselves
            self.ensure_collector(ins)
        return task

    def enable_trace(self, input_name: str, output_tag: str = "trace") -> bool:
        """Chunk trace "tap" (src/flb_chunk_trace.c:184-203): stamp each
        append's journey — input + per-filter before/after with timing —
        and re-emit the stamps through a hidden emitter under
        ``output_tag`` so they flow the normal pipeline. Enabled per
        input (CLI -Z / HTTP api/v1/trace equivalent)."""
        target = None
        for ins in self.inputs:
            if input_name in (ins.name, ins.display_name):
                target = ins
                break
        if target is None:
            return False
        if target.name in self.traces:  # canonical key: dedup aliases
            return True
        emitter = self.hidden_input(
            "emitter", owner=target, alias=f"trace_emitter_{target.name}"
        )
        # trace installs race the reap timer / reload commits mutating
        # the same dict from other threads
        with self._ingest_lock:
            self.traces[target.name] = {
                "input": target,
                "output_tag": output_tag,
                "emitter": emitter.plugin,
                "emitter_instance": emitter,
                "count": 0,
            }
        return True

    def disable_trace(self, input_name: str) -> bool:
        key = input_name
        if key not in self.traces:
            for name, ctx in self.traces.items():
                if ctx["input"].display_name == input_name:
                    key = name
                    break
        with self._ingest_lock:
            ctx = self.traces.pop(key, None)
            if ctx is None:
                return False
            # drop the hidden emitter too — repeated enable/disable
            # cycles must not accumulate dead inputs (COW swap:
            # concurrent iterators keep their snapshot)
            self.inputs = [i for i in self.inputs
                           if i is not ctx["emitter_instance"]]
            emitter_ins = ctx["emitter_instance"]
            self._retired_names.setdefault(
                type(emitter_ins).__name__, set()).add(emitter_ins.name)
        return True

    def _trace_ctx(self, ins) -> Optional[dict]:
        if not self.traces:
            return None
        for key in (ins.name, ins.display_name):
            ctx = self.traces.get(key)
            if ctx is not None and ctx["input"] is ins:
                return ctx
        return None

    def _trace_emit(self, ctx: dict, body: dict) -> None:
        from ..codec.events import encode_event, now_event_time

        try:
            ctx["emitter"].add_record(
                ctx["output_tag"], encode_event(body, now_event_time()), 1
            )
        except Exception:
            log.exception("chunk trace emit failed")

    def ensure_collector(self, ins: InputInstance) -> None:
        """Schedule a collector for an input created after start() —
        the SAME dispatch as _main's startup pass: threaded interval
        collectors get their own OS thread (a blocking collect() must
        not stall the flush loop), loop collectors an asyncio task,
        and push servers (server_task_needed) their listener task —
        otherwise a hot-reload-added tcp/http input would never start
        listening."""
        if not self.running or self.loop is None:
            return
        plugin = ins.plugin
        if plugin.collect_interval is not None and ins.threaded:
            if getattr(ins, "collector_thread", None) is None:
                ins.collector_thread = threading.Thread(
                    target=self._collector_thread, args=(ins,),
                    daemon=True,
                    name=f"flb-in-{ins.display_name}",
                )
                ins.collector_thread.start()
            return

        def _create():
            if ins.collector_task is not None:
                return
            if plugin.collect_interval is not None:
                ins.collector_task = asyncio.ensure_future(
                    self._collector(ins))
            elif getattr(plugin, "server_task_needed", False):
                ins.collector_task = asyncio.ensure_future(
                    plugin.start_server(self))

        try:
            self.loop.call_soon_threadsafe(_create)
        except RuntimeError:
            pass

    def hidden_input(self, name: str, owner=None,
                     **props) -> InputInstance:
        """Create + immediately initialize an internal input instance —
        the hidden ``emitter`` pattern used by rewrite_tag /
        log_to_metrics / chunk traces (reference
        plugins/filter_rewrite_tag/rewrite_tag.c:245-260). Safe to call
        from a plugin's init while the engine is starting.

        ``owner`` ties the hidden input's lifecycle to the instance
        whose init created it: when a hot reload removes/replaces that
        owner, the emitter is unlinked with it (core/qos.py ReloadTxn)
        instead of leaking one orphaned input per reload."""
        ins = self._make_instance(self.registry.create_input, name,
                                  props, self.inputs)
        ins._hidden_owner = owner
        # internal replay is never re-metered (core/qos.py admit):
        # these bytes passed tenant admission at their ORIGINAL ingest
        # point, and the re-emit callers (rewrite_tag / multiline /
        # trace taps) are fire-and-forget — a DEFER here would silently
        # drop already-admitted data while counting it "deferred"
        ins.qos_exempt = True
        # COW list swap: hidden inputs appear at RUNTIME (sp emitters,
        # trace taps, rewrite_tag emitters during a hot reload's build
        # phase) while other threads iterate snapshot references
        with self._ingest_lock:
            self.inputs = self.inputs + [ins]
        self._init_instance(ins)
        return ins

    # ------------------------------------------------------------------
    # lifecycle
    # ------------------------------------------------------------------

    def start(self) -> None:
        """Spawn the engine thread (flb_start → flb_engine_start).
        Shortens the interpreter's GIL switch interval to
        ``GIL_SWITCH_INTERVAL_S`` where it is longer."""
        if self._thread is not None:
            raise RuntimeError("engine already started")
        # process-wide and left in place at stop(): another engine of
        # this process may still be running
        if sys.getswitchinterval() > GIL_SWITCH_INTERVAL_S:
            sys.setswitchinterval(GIL_SWITCH_INTERVAL_S)
        # storage + backlog recovery (flb_storage_create at
        # src/flb_engine.c:979; sb_segregate_chunks at :1129)
        if self.service.storage_path and self.storage is None:
            from .storage import Storage

            self.storage = Storage(self.service.storage_path,
                                   checksum=self.service.storage_checksum)
        if self.storage is not None:
            with self._ingest_lock:  # uniform discipline (fbtpu-lint)
                self._backlog = self.storage.scan_backlog()
        # customs first (flb_custom_init_all, src/flb_engine.c:973):
        # they may create pipeline instances programmatically
        for ins in self.customs:
            if getattr(ins, "_initialized", False):
                continue
            self._init_instance(ins)
        for ins in self.inputs + self.filters + self.outputs:
            if getattr(ins, "_initialized", False):
                continue  # hidden inputs are initialized at creation
            self._init_instance(ins)
        # fbtpu-qos: register every tenant contract EAGERLY, in config
        # order ("last declaration wins") — lazy first-append
        # registration would let input A flood unmetered before
        # sibling input B (carrying the shared tenant's rate) ever
        # ingests
        for ins in self.inputs:
            self.qos.tenant_for_input(ins)
        # output worker thread pools (flb_output_thread_pool_create,
        # src/flb_output_thread.c:472): flush callbacks leave the
        # engine loop when `workers` is set
        for out in self.outputs:
            self._ensure_worker_pool(out)
        self.started_at = time.time()
        self.guard.heartbeat = time.time()
        # failpoint trigger → metric bridge (unarmed plane: the listener
        # list is only walked when a fault actually fires)
        _fp.add_listener(self._on_failpoint_trigger)
        # device fault-domain → metric bridge (fbtpu-armor): healthy
        # lanes emit nothing, so the hot path pays zero here
        from ..ops import fault as _fault

        _fault.add_listener(self._on_device_event)
        # a cyclic-GC pass under a profiler session is a span on the
        # thread it ran on (core/spans.py); released in stop()
        self._gc_hook = watch_gc()
        if self.service.profiler_port:
            threading.Thread(target=self._serve_profiler, daemon=True,
                             name="flb-profiler").start()
        self._stopping = False
        self._stop_event.clear()
        self._thread = threading.Thread(target=self._run, name="flb-engine", daemon=True)
        self._thread.start()
        if not self._started.wait(timeout=10):
            raise RuntimeError("engine failed to start")

    def _serve_profiler(self) -> None:
        """``[SERVICE] profiler_port``: once the device is attached,
        open jax's profiler server, so that an ``xprof``/TensorBoard
        capture shows the ``fbtpu:`` spans (core/spans.py) over the
        device's operations on one timeline. One server per process;
        it waits for the attach as long as the sketch and flux launch
        paths do, and a failure here never stops the engine."""
        from ..ops import device

        if not device.wait(max(60.0, device.default_wait())):
            log.warning("profiler_port: device attach did not complete; "
                        "no profiler server")
            return
        try:
            import jax

            jax.profiler.start_server(self.service.profiler_port)
        except Exception:
            log.exception("profiler_port %d: profiler server failed to "
                          "start", self.service.profiler_port)

    def _ensure_worker_pool(self, out: OutputInstance) -> None:
        """Build the output's worker pool when configured (start() and
        hot-reload-added outputs share this path)."""
        from .output_thread import OutputWorkerPool

        if out.workers <= 0 or out.worker_pool is not None \
                or out.plugin.synchronous:
            return
        pool = OutputWorkerPool(
            out.display_name, out.workers, out.plugin,
            start_timeout=self.service.guard_worker_start_timeout)
        if pool.failed:
            # a worker that never starts must not leave submit()
            # targeting a dead loop: fail the output over to
            # inline flushes on the engine loop
            log.error(
                "output %s: worker pool startup failed — "
                "failing over to inline flush", out.display_name)
            self.guard.m_worker_start_fail.inc(
                1, (out.display_name,))
            pool.stop()
        else:
            out.worker_pool = pool

    def reload_txn(self):
        """Open a hot-reload transaction (fbtpu-qos, core/qos.py):
        stage add/remove/replace of inputs, filters, outputs and
        parsers, then ``commit()`` swaps the configuration atomically
        behind a generation bump — without dropping in-flight chunks.
        Embedders wire ``self.reload_callback`` to a function that
        builds and commits one of these for POST /api/v2/reload."""
        from .qos import ReloadTxn

        return ReloadTxn(self)

    def _run(self) -> None:
        self.loop = asyncio.new_event_loop()
        asyncio.set_event_loop(self.loop)
        try:
            self.loop.run_until_complete(self._main())
        finally:
            self.loop.close()

    async def _main(self) -> None:
        # start collectors (flb_input_collectors_start, src/flb_engine.c:1090)
        for ins in self.inputs:
            plugin = ins.plugin
            if plugin.collect_interval is not None:
                if ins.threaded:
                    # FLB_INPUT_THREADED equivalent
                    # (src/flb_input_thread.c:225): collection runs on
                    # its own OS thread; append stays thread-safe via
                    # the engine's ingest locking
                    ins.collector_thread = threading.Thread(
                        target=self._collector_thread, args=(ins,),
                        daemon=True,
                        name=f"flb-in-{ins.display_name}",
                    )
                    ins.collector_thread.start()
                else:
                    ins.collector_task = asyncio.ensure_future(
                        self._collector(ins))
            elif getattr(plugin, "server_task_needed", False):
                ins.collector_task = asyncio.ensure_future(plugin.start_server(self))
        # admin HTTP server (flb_hs_create/start, src/flb_engine.c:1074)
        admin_task = None
        if self.service.http_server:
            from .http_server import AdminServer

            self.admin_server = AdminServer(
                self, self.service.http_listen, self.service.http_port
            )
            admin_task = asyncio.ensure_future(self.admin_server.serve())
        self._started.set()
        flush_interval = max(0.02, self.service.flush)
        try:
            while not self._stopping:
                await asyncio.sleep(flush_interval)
                self.flush_all()
            # stop threaded collectors FIRST: anything they append must
            # land before the final flush below, or it would sit in the
            # pool and be lost at shutdown
            self._stop_event.set()
            for ins in self.inputs:
                t = getattr(ins, "collector_thread", None)
                if t is not None and t.is_alive():
                    await asyncio.get_event_loop().run_in_executor(
                        None, t.join, self.service.grace + 2.0)
            # graceful drain (grace period, src/flb_engine.c:1137-1160):
            # let plugins flush held state (pending multiline groups)
            # BEFORE the final chunk drain so nothing is lost at stop
            for ins in self.inputs + self.filters + self.outputs:
                drain = getattr(ins.plugin, "drain", None)
                if drain is not None:
                    try:
                        drain(self)
                    except Exception:
                        log.exception("%s drain failed", ins.display_name)
                # attached processors may hold state too (tail sampler's
                # undecided traces): give them the same drain window
                for proc in getattr(ins, "processors", None) or []:
                    pdrain = getattr(proc.plugin, "drain", None)
                    if pdrain is not None:
                        try:
                            pdrain(self)
                        except Exception:
                            log.exception("%s processor drain failed",
                                          proc.name)
            if self.sp is not None:  # flush open SQL windows
                with self._ingest_lock:
                    try:
                        self.sp.drain()
                    except Exception:
                        log.exception("stream processor drain failed")
            # shed chunks re-enter the backlog so the shutdown drain
            # (and its quarantine accounting) sees them
            self.guard.readmit_all()
            self.flush_all()
            await asyncio.sleep(0.05)  # let queued _create callbacks run
            deadline = time.time() + self.service.grace
            while self._pending_flushes and time.time() < deadline:
                await asyncio.sleep(0.02)
            # cancel stragglers (in-flight flush attempts)
            for fut in list(self._pending_flushes):
                fut.cancel()
            if self._pending_flushes:
                await asyncio.gather(*self._pending_flushes, return_exceptions=True)
            # pending scheduler retries: cancel their timers and
            # quarantine undelivered memory chunks (same semantics as a
            # cancelled in-flight flush)
            for key, (task, out, handle) in list(
                    self._pending_retries.items()):
                handle.cancel()
                self._drop_retry(task, out)
            self._pending_retries.clear()
        finally:
            # an abnormal loop exit (exception above) must still stop
            # collector threads — they check _stopping/_stop_event
            self._stopping = True
            self._stop_event.set()
            pending = []
            for ins in self.inputs:
                if ins.collector_task is not None:
                    ins.collector_task.cancel()
                    pending.append(ins.collector_task)
                t = getattr(ins, "collector_thread", None)
                if t is not None and t.is_alive():
                    t.join(timeout=2.0)
            if admin_task is not None:
                admin_task.cancel()
                pending.append(admin_task)
            if pending:  # let cancellations run their cleanup (finally:)
                await asyncio.gather(*pending, return_exceptions=True)
            self._started.clear()

    def _collector_delay(self, ins: InputInstance,
                         interval: float) -> float:
        """Collector pacing: a DEFER-paused input sleeps for the qos
        bucket's predicted refill time (Qos.defer_hint on the dropped
        append's size) instead of spin-polling every interval while the
        pause flag stays set. Capped at 30s so a starved tenant still
        re-checks (resume_paused may clear the pause for other reasons
        — config reload, quota raise); never below the configured
        interval."""
        if not getattr(ins, "paused_by_qos", False):
            return interval
        try:
            cost = int(getattr(ins, "_qos_defer_cost", 0)) or 1
            hint = float(self.qos.defer_hint(ins, cost))
        except Exception:
            return interval
        return max(interval, min(hint, 30.0))

    async def _collector(self, ins: InputInstance) -> None:
        """Interval collector (flb_input_set_collector_time)."""
        interval = ins.plugin.collect_interval or 1.0
        # hot reload removes inputs mid-run: the flag stops collection
        # even when the cancel races a collect in flight
        while not ins.removed:
            try:
                if not ins.paused:
                    ins.plugin.collect(self)
            except Exception:
                log.exception("input %s collect failed", ins.display_name)
            await asyncio.sleep(self._collector_delay(ins, interval))

    def _collector_thread(self, ins: InputInstance) -> None:
        """Threaded-input collector loop (reference
        input_thread_instance_create, src/flb_input_thread.c:225): the
        plugin's collect — file reads, socket drains, line splitting,
        encoding — runs off the engine loop so slow inputs never stall
        flushes, and independent inputs collect in parallel."""
        interval = ins.plugin.collect_interval or 1.0
        while not self._stopping and not ins.removed:
            try:
                if not ins.paused:
                    ins.plugin.collect(self)
            except Exception:
                log.exception("input %s collect failed", ins.display_name)
            if self._stop_event.wait(  # instant stop wakeup
                    self._collector_delay(ins, interval)):
                break
        if ins.removed:
            # hot reload removed this input: this thread owns the
            # plugin's I/O, so exiting HERE guarantees no collect() is
            # in flight when files/sockets close (ReloadTxn skips the
            # inline exit while this thread is alive or this flag is
            # set — flag BEFORE exit so the reload's liveness check
            # can never observe dead-thread-and-unset-flag after we
            # exited). Engine stop leaves removed=False and keeps the
            # stop()-path exit.
            ins._exited_by_collector = True
            try:
                ins.plugin.exit()
            except Exception:
                log.exception("removed input %s exit failed",
                              ins.display_name)

    def request_stop(self) -> None:
        """Ask the engine loop to shut down gracefully (the in-pipeline
        stop used by out_exit / filter_expect's exit action / in_exec's
        exit_after_oneshot). The loop drains and exits; call stop() to
        join the thread."""
        self._stopping = True

    def stop(self) -> None:
        """Graceful stop with drain (flb_stop)."""
        if self._thread is None:
            return
        self._stopping = True
        # barrier: an in-flight hot-reload commit (HTTP thread) may be
        # about to retire outputs — wait for it to finish so its
        # retired list is visible to the reap below; commits arriving
        # AFTER this point see _stopping under the same lock and
        # refuse (core/qos.py ReloadTxn.commit), so none can slip in
        # behind the reap and leak un-exited pools
        with self._reload_lock:
            pass
        self._thread.join(timeout=self.service.grace + 10)
        if self._thread.is_alive():
            # a silently-swallowed join timeout leaves a wedged engine
            # undiagnosable: say so, and dump every thread's stack
            self._dump_stuck_shutdown()
        self._thread = None
        # hot-reload-retired outputs kept their pools alive for
        # in-flight flushes; the drain above has settled them. Swap
        # under the lock: a reload commit on another thread extends
        # this list under _ingest_lock, and an unlocked swap racing it
        # would strand its outputs on a list nobody reaps
        with self._ingest_lock:
            retired, self._retired_outputs = self._retired_outputs, []
        for out in self.outputs + retired:
            if out.worker_pool is not None:
                out.worker_pool.stop()
                out.worker_pool = None
        for ins in self.inputs + self.filters + self.outputs \
                + retired + self.customs:
            try:
                ins.plugin.exit()
            except Exception:
                log.exception("%s exit failed", ins.display_name)
        try:
            if self.storage is not None:
                self.storage.close()
        finally:
            # always release the module-global listeners: a teardown
            # error must not pin this engine (and its metrics) forever
            _fp.remove_listener(self._on_failpoint_trigger)
            if self._gc_hook is not None:
                unwatch_gc(self._gc_hook)
                self._gc_hook = None
            try:
                from ..ops import fault as _fault

                _fault.remove_listener(self._on_device_event)
            except Exception:
                log.exception("device fault listener release failed")

    def _dump_stuck_shutdown(self) -> None:
        """The engine thread outlived grace+10s at stop(): log it and
        dump all thread stacks via faulthandler so a wedged shutdown
        (a flush stuck in C code, a deadlocked lock) is diagnosable
        from the crash report instead of a silent hang."""
        import faulthandler
        import sys

        log.warning(
            "engine thread did not exit within %.1fs at stop() — "
            "shutdown is stuck; dumping all thread stacks to stderr",
            self.service.grace + 10)
        try:
            faulthandler.dump_traceback(file=sys.stderr, all_threads=True)
        except Exception:
            log.exception("thread stack dump failed")

    def _on_failpoint_trigger(self, name: str, _action: str) -> None:
        self.m_failpoint_triggered.inc(1, (name,))

    def _on_device_event(self, lane: str, event: str, value) -> None:
        """fbtpu-armor listener bridge → fluentbit_device_* metrics
        (ops/fault.py event vocabulary)."""
        if event == "fallback" or event == "short_circuit":
            self.m_device_fallback.inc(1, (lane,))
        elif event == "timeout":
            self.m_device_timeouts.inc(1, (lane,))
        elif event == "failure":
            self.m_device_failures.inc(1, (lane,))
        elif event == "slow_launch":
            self.m_device_slow_launches.inc(1, (lane,))
        elif event == "device_lost":
            self.m_device_lost.inc(1, (lane,))
        elif event == "breaker":
            code = {"closed": 0, "half-open": 1, "open": 2}.get(value, 0)
            self.m_device_breaker.set(code, (lane,))
        elif event == "mesh_devices":
            self.m_device_mesh.set(float(value), (lane,))
        elif event == "reattach":
            self.m_device_reattach.inc(1)

    @property
    def running(self) -> bool:
        return self._thread is not None and self._thread.is_alive()

    # ------------------------------------------------------------------
    # ingest path (reference: flb_input_log_append → input_chunk_append_raw)
    # ------------------------------------------------------------------

    def _raw_chain(self, ins: InputInstance, tag: str) -> tuple:
        """``(matching, cond_routing, raw_ok)`` for an append of ``ins``
        under ``tag``: the filters its route reaches, whether an output
        routes it per record, and whether the chain can run straight
        off the chunk's bytes. Reads only, under no lock."""
        matching = [f for f in self.filters if f.route.matches(tag)]
        # flux-backed tasks don't need decoded events — their hidden
        # flux filter (in `matching`) absorbs on the raw chain, so they
        # must not force the decode path (that is the whole point)
        sp_active = (
            self.sp is not None
            and self.sp.tasks
            and ins is not self.sp.emitter_instance
            and any(t.matches(tag) and t.flux is None
                    for t in self.sp.tasks)
        )
        cond_routing = any(
            o.route_condition is not None and o.route.matches(tag)
            for o in self.outputs
        )
        raw_ok = (
            not ins.processors
            and not sp_active
            and not cond_routing  # per-record splits need decoded events
            and self._trace_ctx(ins) is None
            and all(f.plugin.can_process_batch() for f in matching)
        )
        return matching, cond_routing, raw_ok

    def input_log_prelaunch(self, ins: InputInstance, tag: Optional[str]):
        """The way to begin the device launch of an append that has not
        had its turn yet: where ``input_log_append(ins, tag, data, n)``
        would run the raw chain and its FIRST filter offers the begin
        half of its launch (``FilterPlugin.begin_batch``), →
        ``begin(data, n_records)``, which stages ``data``, begins that
        launch and returns its handle (or None: the filter declined),
        to be given back as ``input_log_append(..., begun=handle)``;
        else None, and there is nothing to begin.

        Pure of side effects, the lookup and the call: no lock of the
        engine's, no pool, no quota bucket, no metric, no emitter —
        staging reads the bytes, the launch is compute, and the verdict
        is committed by the append alone, so ``begin`` may run on any
        thread. The caller owns the handle: whatever the append comes
        to, it calls ``handle.drop()`` afterwards (a no-op once the
        filter has finished it)."""
        tag = tag or ins.tag or ins.plugin.name
        matching, _cond, raw_ok = self._raw_chain(ins, tag)
        if not raw_ok or not matching:
            return None
        first = matching[0]
        begin_batch = getattr(first.plugin, "begin_batch", None)
        if begin_batch is None:
            return None

        def begin(data: bytes, n_records: Optional[int]):
            try:
                return begin_batch(data, n_records)
            except Exception:
                log.exception("filter %s could not begin its launch",
                              first.display_name)
                return None

        return begin

    @spanned("engine.append")
    def input_log_append(self, ins: InputInstance, tag: Optional[str],
                         data: bytes, n_records: Optional[int] = None,
                         begun=None) -> int:
        """Append encoded log events; runs processors then the filter chain
        synchronously (src/flb_input_chunk.c:3078), then writes the chunk.
        ``begun``: this append's launch from :meth:`input_log_prelaunch`;
        it rides on the raw chain's chunk to the first filter and no
        further (an append that never gets there leaves it to the
        caller).

        Returns number of records written (post-filter), or -1 when the
        append was rejected by backpressure (reference
        flb_input_chunk_append_raw returns -1 on paused/overlimit).
        Thread-safe: the whole ingest path (processors + filters + append)
        runs under the ingest lock, serializing stateful filters exactly
        like the reference's single engine thread does.
        """
        tag = tag or ins.tag or ins.plugin.name

        # backpressure FIRST (mem_buf_limit, src/flb_input.c:157,740-746;
        # storage.pause_on_chunks_overlimit, :169) — pool counters are
        # snapshotted under the input's lock (parallel raw-path appends
        # mutate them concurrently); the pause flip itself is atomic in
        # set_paused. Runs before tenant admission so a rejected append
        # does NOT charge the tenant's token bucket: the caller retries
        # the same bytes, and charging every retry would drain quota on
        # data that was never ingested
        with ins.ingest_lock:
            over = ins.storage_type != "memrb" and ((
                ins.mem_buf_limit
                and ins.pool.pending_bytes >= ins.mem_buf_limit
            ) or (
                getattr(ins, "pause_on_chunks_overlimit", False)
                and ins.pool.pending_chunks
                >= self.service.storage_max_chunks_up
            ))
        if over:
            ins.set_paused(True)
            return -1

        # fbtpu-qos tenant admission (core/qos.py): every ingest entry
        # point meters the append against its tenant's token bucket
        # BEFORE any decode/filter work — over quota, DEFER (1) is the
        # reference's backpressure verdict (-1, caller retries) and
        # SHED (2) drops the append with per-tenant accounting
        verdict = self.qos.admit(ins, len(data))
        if verdict:
            if verdict == 1:
                # DEFER uses the SAME pause contract as mem_buf_limit:
                # collector/server inputs ignore -1 and have already
                # consumed their source, so without the pause every
                # over-quota read would be silently dropped while
                # counted "deferred". Paused collectors stop consuming;
                # housekeeping resumes once the bucket can admit this
                # append's size again (resuming on a single token
                # would churn: consume → defer-drop → re-pause)
                ins._qos_defer_cost = len(data)
                ins.paused_by_qos = True
                ins.set_paused(True)
                return -1
            return 0

        # memrb storage: a ring buffer — over the limit, the OLDEST
        # buffered chunks are evicted with drop metrics instead of
        # pausing the input (src/flb_input_chunk.c:2936-2966)
        if ins.storage_type == "memrb":
            limit = ins.mem_buf_limit or 10 * 1024 * 1024
            # read + evict atomically under the input lock; sized on
            # the incoming raw bytes, matching the reference's
            # pre-filter check (src/flb_input_chunk.c:2936, which runs
            # before flb_filter_do at :3078)
            with ins.ingest_lock:
                need = ins.pool.pending_bytes + len(data) - limit
                evicted = ins.pool.evict_oldest(need) if need > 0 else []
            for c in evicted:
                self.m_memrb_dropped_chunks.inc(
                    1, (ins.display_name,))
                self.m_memrb_dropped_bytes.inc(
                    c.size, (ins.display_name,))

        # ---- raw fast path (no decode-per-append) ----
        # When nothing on the chain needs decoded events — no
        # processors, no stream-processor task, and every matching
        # filter can operate on raw chunk bytes (grep's native
        # staging) — records are counted by the native msgpack
        # scanner and appended as raw spans. When additionally every
        # matching filter is stateless (thread_safe_raw), the chain runs
        # under the INPUT's lock only, so independent inputs ingest in
        # parallel (the global lock stops serializing independent
        # tags; reference threaded inputs + per-input chunk
        # maps, src/flb_input_thread.c:225).
        matching, cond_routing, raw_ok = self._raw_chain(ins, tag)
        if raw_ok:
            # stateful chains are pinned to the global lock even when
            # every filter is thread_safe_raw: a stateful hook's side
            # effects (emitter re-emits) re-enter input_log_append,
            # which takes self._ingest_lock — under ins.ingest_lock
            # that re-entry would invert the canonical
            # Engine._ingest_lock -> InputInstance.ingest_lock order
            # (fbtpu-locksmith lock-order-cycle)
            parallel = all(
                f.plugin.thread_safe_raw and not f.plugin.stateful_batch
                for f in matching
            )
            # two lexical branches, not a lock alias: the locksmith
            # order-graph walk resolves `with self._X:` scopes, not
            # conditionally-bound aliases
            if parallel:
                with ins.ingest_lock:
                    got = self._ingest_raw(ins, tag, data, matching,
                                           n_records, begun)
            else:
                with self._ingest_lock:
                    got = self._ingest_raw(ins, tag, data, matching,
                                           n_records, begun)
            if isinstance(got, _RawTail):
                # a mid-chain decline after committed side effects:
                # finish per-record OUTSIDE the raw-path lock scope —
                # the tail takes self._ingest_lock itself, and taking
                # it while still holding ins.ingest_lock would be the
                # inversion the order graph forbids
                got = self._finish_raw_tail(ins, got)
            if got is not None:
                return got

        with self._ingest_lock:
            # expose the appending input to filters that must recognise
            # their own emitter's records (filter_multiline's and
            # filter_rewrite_tag's i_ins == ctx->ins_emitter checks in
            # the reference). Saved/restored because emitters re-enter
            # input_log_append synchronously mid-chain — without the
            # restore the OUTER chain's remaining filters would see the
            # nested append's source
            prev_src = self._ingest_src
            self._ingest_src = ins
            try:
                return self._log_append_decoded(ins, tag, data,
                                                n_records, cond_routing)
            finally:
                self._ingest_src = prev_src

    def _log_append_decoded(self, ins, tag, data, n_records, cond_routing):
        """The decode branch of input_log_append (runs under the global
        ingest lock, with _ingest_src already pointing at ``ins``)."""
        if ins.removed:
            # hot reload unlinked this input (see _ingest_raw): refuse
            # so the caller never acks into the orphaned pool
            self.qos.refund(ins, len(data))
            return 0
        events = decode_events(data)
        if n_records is None:
            n_records = len(events)
        self.m_in_records.inc(n_records, (ins.display_name,))
        self.m_in_bytes.inc(len(data), (ins.display_name,))

        # input-side processors (flb_processor_run, src/flb_input_log.c:1562)
        events = self._run_log_processors(ins.processors, events, tag)
        if not events:
            return 0

        # chunk trace: input stamp (flb_chunk_trace_do_input,
        # src/flb_input_chunk.c:3049)
        trace_ctx = self._trace_ctx(ins)
        if trace_ctx is not None:
            trace_ctx["count"] += 1
            trace_ctx["trace_id"] = trace_id = \
                f"{ins.name}.{trace_ctx['count']}"
            self._trace_emit(trace_ctx, {
                "type": "input", "trace_id": trace_id,
                "input_instance": ins.display_name, "tag": tag,
                "records": n_records,
            })

        # filter chain — synchronous, pre-storage
        events = self._run_filters(events, tag, trace_ctx)
        if not events:
            return 0

        # stream processor on the filtered records (flb_sp_do,
        # src/flb_input_chunk.c:3155); never on its OWN emitter's
        # records — a task whose TAG pattern matches its output tag
        # must not feed back into itself
        if (
            self.sp is not None
            and self.sp.tasks
            and ins is not self.sp.emitter_instance
        ):
            try:
                self.sp.do(events, tag)
            except Exception:
                log.exception("stream processor failed")

        if cond_routing:
            # split_and_append_route_payloads
            # (src/flb_input_log.c:1495): group records by the set
            # of outputs whose condition admits them; each group
            # lands in its own chunk carrying that route bitmask
            groups: Dict[int, bytearray] = {}
            counts: Dict[int, int] = {}
            ends: Dict[int, list] = {}  # record END offsets per group
            # tag is constant for the append: resolve the matching
            # candidates once, per-record work is condition eval only
            candidates = [
                (1 << i, o.route_condition)
                for i, o in enumerate(self.outputs)
                if o.route.matches(tag)
            ]
            for ev in events:
                mask = 0
                for bit, cond in candidates:
                    if cond is None or cond.eval(ev.body):
                        mask |= bit
                if mask == 0:
                    # no output admits this record (every matching
                    # route's condition failed): nothing to deliver
                    # — parity with dispatch finding zero routes
                    continue
                raw = ev.raw if ev.raw is not None \
                    else reencode_event(ev)
                buf = groups.setdefault(mask, bytearray())
                buf.extend(raw)
                ends.setdefault(mask, []).append(len(buf))
                counts[mask] = counts.get(mask, 0) + 1
            with ins.ingest_lock:
                for mask, buf in groups.items():
                    # ONE materialization per group: the pool append
                    # adopts the same bytes object write_through
                    # persists (this branch used to call bytes(buf)
                    # twice — memscope host-redundant-copy)
                    payload = bytes(buf)
                    if _cw.witness_enabled():
                        _cw.count("engine.cond.materialize",
                                  len(payload))
                    chunk = ins.pool.append(
                        tag, payload, counts[mask],
                        routes_mask=mask)
                    if chunk.route_names is None:
                        # persisted form: NAMES, not bit positions
                        # — conditional routing must survive a
                        # restart with reordered outputs
                        chunk.route_names = tuple(
                            o.display_name
                            for i, o in enumerate(self.outputs)
                            if (mask >> i) & 1
                        )
                    self._persist(ins, chunk, payload,
                                  offsets=ends[mask])
            return len(events)

        out = bytearray()
        rec_ends = []  # per-event END offsets: the sidecar gets them free
        for ev in events:
            out += ev.raw if ev.raw is not None else reencode_event(ev)
            rec_ends.append(len(out))
        # ONE materialization: pool append + write-through share the
        # same bytes object (this used to be two full bytes(out) copies
        # of every decoded append — memscope host-redundant-copy)
        payload = bytes(out)
        if _cw.witness_enabled():
            _cw.count("engine.decoded.materialize", len(payload))
        with ins.ingest_lock:
            chunk = ins.pool.append(tag, payload, len(events))
            self._persist(ins, chunk, payload, offsets=rec_ends)
        return len(events)

    def input_event_append(self, ins: InputInstance, tag: Optional[str],
                           data: bytes, event_type: str, n_records: int = 1) -> int:
        """Non-log telemetry append (metrics/traces/profiles): no filter
        chain (reference typed appends, src/flb_input_metric.c etc.)."""
        tag = tag or ins.tag or ins.plugin.name
        in_bytes = len(data)  # pre-processor size: what admit charged
        # same tenant admission contract as input_log_append
        verdict = self.qos.admit(ins, in_bytes)
        if verdict:
            if verdict == 1:
                # DEFER pauses (see input_log_append): fire-and-forget
                # typed appenders must stop consuming until refill
                ins._qos_defer_cost = in_bytes
                ins.paused_by_qos = True
                ins.set_paused(True)
                return -1
            return 0
        with self._ingest_lock:
            # input-side metrics/traces processors (flb_processor_run on
            # the typed append path)
            if ins.processors and event_type == EVENT_TYPE_METRICS:
                data = self._run_metrics_processors(ins.processors, data, tag)
            elif ins.processors and event_type == EVENT_TYPE_TRACES:
                data, n_records = self._run_traces_processors(
                    ins.processors, data, tag, n_records)
                if not data:
                    # all spans buffered (tail sampling) or dropped —
                    # consumed, so counted as ingested
                    self.m_in_records.inc(n_records, (ins.display_name,))
                    self.m_in_bytes.inc(in_bytes, (ins.display_name,))
                    return n_records
            with ins.ingest_lock:
                if ins.removed:
                    # hot reload unlinked this input: its pool was
                    # drained and will never be visited again — refuse
                    # (un-acked) instead of appending into the orphan
                    self.qos.refund(ins, in_bytes)
                    return 0
                # counted only once the append actually lands (a
                # removed-input refusal retried by the caller must not
                # double-count)
                self.m_in_records.inc(n_records, (ins.display_name,))
                self.m_in_bytes.inc(in_bytes, (ins.display_name,))
                chunk = ins.pool.append(tag, data, n_records, event_type)
                self._persist(ins, chunk, data)
        return n_records

    def _ingest_raw(self, ins, tag: str, data: bytes, matching,
                    n_records: Optional[int], begun=None):
        """Append without Python decode. Returns the appended record
        count, None (caller falls back to the decode path: native
        unavailable / a pure-prefix filter decline), or a ``_RawTail``
        continuation (decline AFTER committed side effects — the caller
        runs it via ``_finish_raw_tail`` once the raw-path lock is
        released)."""
        from ..codec import events as _events

        from .chunk_batch import RawChunk

        if ins.removed:
            # hot reload unlinked this input while we waited on the
            # ingest lock: its pool is drained and orphaned — refuse
            # (0 ingested, so the caller never acks). ReloadTxn sets
            # the flag under BOTH locks, so whichever lock this path
            # holds serializes against the swap.
            self.qos.refund(ins, len(data))
            return 0
        in_bytes = len(data)
        # n may stay None until the FIRST raw filter discovers it (the
        # fused grep walk returns the record count as a third element),
        # skipping the counting pre-pass on the hot path entirely
        n = n_records
        # one chunk view travels the whole chain: the record count one
        # filter discovers is reused as the next one's n_hint
        chunk = RawChunk(data, tag, n, src=ins, engine=self, begun=begun)
        deltas = []  # metric updates deferred until the chain commits:
        committed = False  # True once a stateful hook's effects are out
        for fi, f in enumerate(matching):
            prev = data     # a later decline re-runs the decode path,
            got = None      # which must not double-count earlier drops
            plugin = f.plugin
            try:
                if plugin.can_process_batch():
                    if chunk.data is not data:
                        chunk.replace(data, n)
                    else:
                        chunk.n = n
                    if plugin.stateful_batch:
                        # marked BEFORE the call: a hook raising after
                        # partial emits must not trigger a full decode
                        # re-run (the tail continuation re-runs only
                        # THIS filter onward — strictly fewer doubled
                        # effects than restarting the chain; a clean
                        # decline costs nothing extra since the tail
                        # is bit-exact with the decode path)
                        committed = True
                    with span("filter." + plugin.name):
                        got = plugin.process_batch(chunk)
            except Exception:
                log.exception("filter %s raw path failed", f.display_name)
                got = None
            chunk.begun = None  # the first filter's, or nobody's
            if got is None:
                self.m_filter_batch_decline.inc(1, (f.display_name,))
                if not committed:
                    return None  # pure prefix: decode path re-runs it
                # an upstream stateful filter already emitted records /
                # bumped metrics — re-running the whole chain on the
                # decode path would double those side effects. Hand the
                # caller a continuation: the REMAINING filters finish
                # per-record (same code the decode path runs:
                # bit-exact) via _finish_raw_tail, AFTER the raw-path
                # lock is released — the tail takes self._ingest_lock
                # itself, and nesting that under ins.ingest_lock would
                # invert the canonical order (fbtpu-locksmith)
                return _RawTail(tag, data, matching[fi:], n, n_records,
                                deltas, in_bytes)
            if len(got) == 3:
                n2, data, n_in = got
                if n is None:
                    n = n_in
            else:
                n2, data = got
                if n is None:  # filter didn't count: count its input
                    n = _events.fast_count_records(prev)
                    if n is None:
                        if not committed:
                            return None
                        # committed effects forbid a decode re-run and
                        # the input count is unrecoverable: skip this
                        # filter's drop/add delta (its output count n2
                        # is still exact)
                        log.warning(
                            "filter %s output uncountable after a "
                            "committed batch stage; its filter metrics "
                            "delta is skipped", f.display_name)
                        n = n2
            deltas.append((f.display_name, n, n2))
            n = n2
            if n == 0:
                break
        if n is None:  # no filter matched: count natively
            n = _events.fast_count_records(data)
            if n is None:
                return None
        return self._finish_raw_append(ins, tag, data, n, n_records,
                                       deltas, in_bytes)

    def _persist(self, ins, chunk, data, offsets=None) -> None:
        """Write-through behind the tenant storage quota
        (``Qos.admit_storage``): over ``tenant.storage_limit`` the
        append's persistence is SHED — the chunk stays memory-buffered
        and delivery proceeds, only crash durability for the shed bytes
        is given up (``fluentbit_storage_quota_shed_bytes_total``)."""
        if self.storage is None or ins.storage_type != "filesystem":
            return
        from .qos import SHED

        if self.qos.admit_storage(ins, chunk, len(data)) == SHED:
            return
        self.storage.write_through(chunk, data, offsets=offsets)

    def _finish_raw_append(self, ins, tag: str, data, n, n_records,
                           deltas, in_bytes: int) -> int:
        """The raw path's commit epilogue: deferred filter metric
        deltas, ingest accounting, pool append. Shared by the straight
        -through chain and the decline-after-commit tail continuation."""
        if n_records is None:
            n_records = deltas[0][1] if deltas else n
        for name, before, after in deltas:
            if after < before:
                self.m_filter_drop.inc(before - after, (name,))
            elif after > before:
                self.m_filter_add.inc(after - before, (name,))
        self.m_in_records.inc(n_records, (ins.display_name,))
        self.m_in_bytes.inc(in_bytes, (ins.display_name,))
        if n == 0:
            return 0
        with ins.ingest_lock:  # no-op re-entry on the parallel path
            chunk = ins.pool.append(tag, data, n)
            self._persist(ins, chunk, data)
        return n

    def _finish_raw_tail(self, ins, cont: "_RawTail") -> int:
        """Run a _RawTail continuation: decode-path finish of the
        remaining filters, then the shared commit epilogue. MUST be
        called with no raw-path lock held (see _RawTail)."""
        tail = self._raw_tail_decoded(cont.data, cont.tag,
                                      cont.remaining, ins)
        n, data, n_records = cont.n, cont.data, cont.n_records
        if tail is not None:
            n2, data, n_in = tail
            if n_records is None and not cont.deltas:
                # the first matching filter declined before any count
                # was discovered: the tail's decode IS the append's
                # input count (m_in_records accounting)
                n_records = n_in
            # the tail's per-filter drop/add metrics were counted
            # inside _run_filters — no deltas entry here
            n = n2
        # tail None → undecodable mid-chain output (a filter contract
        # violation): append the current bytes as-is rather than losing
        # the chunk
        if tail is None and n is None:
            from ..codec import events as _events
            n = _events.fast_count_records(data)
            if n is None:
                return None  # decode-path fallback (pre-split parity)
        return self._finish_raw_append(ins, cont.tag, data, n,
                                       n_records, cont.deltas,
                                       cont.in_bytes)

    def _raw_tail_decoded(self, data, tag: str, remaining, ins):
        """Finish a raw chain per-record after a mid-chain decline once
        an earlier stateful filter's side effects (emitter re-emits,
        metric bumps) are already visible — re-running the whole chain
        on the decode path would double them. Runs exactly the decode
        path's filter code on the remaining filters only, with
        ``_ingest_src`` pointing at the appending input so own-emitter
        re-entry guards (rewrite_tag, multiline) fire exactly as they
        do on the decode path. Returns (n_out, data_out, n_in) or None
        when the current bytes do not decode (a filter contract
        violation: the append then lands as-is rather than losing the
        chunk)."""
        try:
            events = decode_events(bytes(data))
        except Exception:
            log.exception("raw-chain tail decode failed; remaining "
                          "filters skipped for this append")
            return None
        n_in = len(events)
        # runs via _finish_raw_tail with NO raw-path lock held (a
        # stateful chain's raw pass released self._ingest_lock before
        # the continuation fired); the save/restore mirrors
        # input_log_append's
        with self._ingest_lock:
            prev_src = self._ingest_src
            self._ingest_src = ins
            try:
                events = self._run_filters(events, tag, None,
                                           filters=remaining)
            finally:
                self._ingest_src = prev_src
        out = bytearray()
        for ev in events:
            out += ev.raw if ev.raw is not None else reencode_event(ev)
        return (len(events), bytes(out), n_in)

    def _run_log_processors(self, procs, events, tag: str):
        """Processor pipeline with per-unit conditions
        (flb_processor.h:69-90: a unit may carry a condition; events
        that fail it pass through the unit untouched)."""
        for proc in procs:
            if not events:
                break
            cond = getattr(proc, "condition", None)
            if cond is None:
                events = proc.plugin.process_logs(events, tag, self)
                continue
            out = []
            for ev in events:
                if cond.eval(ev.body):
                    out.extend(proc.plugin.process_logs([ev], tag, self))
                else:
                    out.append(ev)
            events = out
        return events

    def _run_payload_processors(self, procs, data: bytes, tag: str,
                                method: str) -> Optional[bytes]:
        """Shared unpack → per-plugin pipeline → repack shape for the
        typed (metrics/traces) processor paths. Returns the re-encoded
        payloads, b"" when a stage consumed everything, or None on
        pipeline failure (caller keeps the original bytes)."""
        from ..codec.msgpack import Unpacker, packb

        try:
            payloads = list(Unpacker(data))
            for proc in procs:
                payloads = getattr(proc.plugin, method)(payloads, tag, self)
                if not payloads:
                    return b""
            return b"".join(packb(p) for p in payloads)
        except Exception:
            log.exception("%s processor pipeline failed", method)
            return None

    def _run_metrics_processors(self, procs, data: bytes, tag: str) -> bytes:
        """Run a metrics processor pipeline over encoded payloads."""
        out = self._run_payload_processors(procs, data, tag,
                                           "process_metrics")
        return data if out is None else out

    def _run_traces_processors(self, procs, data: bytes, tag: str,
                               n_records: int):
        """Run a traces processor pipeline over encoded typed payloads
        (flb_processor_run on the trace append path,
        src/flb_input_trace.c). Returns (data, n_spans); b"" data means
        every span was consumed (dropped, or buffered by a tail sampler
        that re-injects later via its emitter)."""
        from ..codec.msgpack import Unpacker
        from ..codec.telemetry import count_spans

        out = self._run_payload_processors(procs, data, tag,
                                           "process_traces")
        if out is None:
            return data, n_records
        if not out:
            return b"", 0
        return out, sum(count_spans(p) for p in Unpacker(out))

    def _run_filters(self, events: List[LogEvent], tag: str,
                     trace_ctx: Optional[dict] = None,
                     filters: Optional[List[FilterInstance]] = None
                     ) -> List[LogEvent]:
        """flb_filter_do equivalent (src/flb_filter.c:119-330), with the
        chunk-trace per-filter stamps (flb_chunk_trace_filter hooks,
        src/flb_filter.c:248,312) when a tap is active. ``filters``
        restricts the pass to a sub-chain (the raw path's decoded-tail
        continuation)."""
        for f in (self.filters if filters is None else filters):
            if not events:
                break
            if not f.route.matches(tag):
                continue
            before = len(events)
            t0 = time.perf_counter_ns() if trace_ctx is not None else 0
            try:
                with span("filter." + f.plugin.name):
                    result, new_events = f.plugin.filter(events, tag,
                                                         self)
            except Exception:
                log.exception("filter %s failed", f.display_name)
                continue
            if trace_ctx is not None:
                after = (len(new_events) if new_events is not None else 0) \
                    if result == FilterResult.MODIFIED else before
                self._trace_emit(trace_ctx, {
                    "type": "filter",
                    "trace_id": trace_ctx.get("trace_id", ""),
                    "filter_instance": f.display_name,
                    "records_in": before,
                    "records_out": after,
                    "elapsed_ns": time.perf_counter_ns() - t0,
                })
            if result == FilterResult.MODIFIED:
                events = new_events if new_events is not None else []
                # modified events lose raw identity unless the filter kept it
                after = len(events)
                if after > before:
                    self.m_filter_add.inc(after - before, (f.display_name,))
                elif after < before:
                    self.m_filter_drop.inc(before - after, (f.display_name,))
        return events

    # ------------------------------------------------------------------
    # dispatch + flush (reference: flb_engine_flush → flb_engine_dispatch)
    # ------------------------------------------------------------------

    @spanned("engine.flush")
    def flush_all(self) -> None:
        """Drain ready chunks into tasks and start per-route flushes."""
        if self.started_at:
            self.m_uptime.set(time.time() - self.started_at)
        # guard watchdog rides this (the housekeeping timer): heartbeat,
        # flush-deadline scan, occupancy gauges, shed/readmit — never a
        # per-record cost (core/guard.py); qos queue gauges and the
        # device lanes' seconds ride the same timer (the registry has
        # no collect-time callback)
        self.guard.housekeeping()
        self.qos.update_gauges()
        self._update_lane_gauges()
        self.qos.resume_paused(self.inputs)
        self._reap_retired_outputs()
        with self._ingest_lock:
            chunks: List[tuple] = []
            if self._backlog:  # recovered chunks re-dispatch first
                chunks.extend((None, c) for c in self._backlog)
                self._backlog = []
            for ins in self.inputs:
                with ins.ingest_lock:  # parallel raw ingest appends
                    drained = ins.pool.drain()
                for chunk in drained:
                    if (
                        self.storage is not None
                        and ins.storage_type == "filesystem"
                    ):
                        self.storage.finalize(chunk)
                    chunks.append((ins, chunk))
                # resume paused inputs once the buffer drains (pool
                # counters read under the input's lock; flip is atomic)
                # — but NOT quota pauses: the pool draining says
                # nothing about the token bucket, and resuming early
                # would let the collector consume reads the very next
                # DEFER drops (Qos.resume_paused owns that resume)
                if ins.paused and not getattr(ins, "paused_by_qos",
                                              False):
                    with ins.ingest_lock:
                        drained_ok = (
                            not ins.mem_buf_limit
                            or ins.pool.pending_bytes < ins.mem_buf_limit
                        ) and (
                            not getattr(ins, "pause_on_chunks_overlimit",
                                        False)
                            or ins.pool.pending_chunks
                            < self.service.storage_max_chunks_up
                        )
                    if drained_ok:
                        ins.set_paused(False)
        if _fp.ACTIVE and chunks:
            # between finalize and task spawn: a crash here leaves every
            # drained chunk finalized-but-undelivered on disk — the
            # strictest recovery case (all bytes + CRCs present, zero
            # delivery acks)
            try:
                _fp.fire("engine.flush_dispatch")
            except _fp.FailpointError:
                # injected non-crash dispatch failure: this cycle is
                # aborted, but the chunks were already drained from
                # their pools — park them for the next cycle instead of
                # letting the error kill the engine loop (panic keeps
                # its bug semantics and propagates)
                log.warning("flush dispatch failed (injected); %d "
                            "chunk(s) re-queued", len(chunks))
                with self._ingest_lock:
                    self._backlog.extend(c for _i, c in chunks)
                return
        # fbtpu-qos weighted-fair dispatch (core/qos.py): ready chunks
        # drain through per-tenant bucket queues — strict priority
        # across classes, deficit-weighted round robin across tenants
        # within a class — instead of input configuration order. When
        # dispatch capacity is scarce (task map near full, or a
        # qos.cycle_budget set), the scarce slots are allocated by
        # weight, so one flooding tenant saturates only its own share.
        qos = self.qos
        for ins, chunk in chunks:
            qos.enqueue(ins, chunk)
        budget = self.service.qos_cycle_budget
        spent = 0
        while True:
            chunk = qos.pop_ready()
            if chunk is None:
                break
            rc = self._dispatch_chunk(chunk)
            if not rc:
                # task map full: park this chunk and everything still
                # queued on the backlog for the next cycle (drain pops
                # in scheduler order, so fairness order is preserved)
                leftovers = [chunk] + qos.drain_pending()
                with self._ingest_lock:
                    self._backlog.extend(leftovers)
                break
            if rc != DISPATCHED:
                # absorbed without a task slot (guard-shed / no live
                # routes): neither a "dispatch" for the metrics/lag
                # histogram nor a charge against the cycle budget —
                # a burst of shed chunks must not exhaust the budget
                # healthy chunks were going to use
                continue
            qos.note_dispatched(chunk)
            spent += chunk.size or 1
            if budget and spent >= budget:
                # per-cycle dispatch budget exhausted: the remainder
                # waits its fair turn next cycle
                leftovers = qos.drain_pending()
                if leftovers:
                    with self._ingest_lock:
                        self._backlog.extend(leftovers)
                break

    def _update_lane_gauges(self) -> None:
        """``fluentbit_device_lane_seconds{lane,phase}``: where each
        device lane's launches spent their time, summed since start —
        spawn (begin → worker running), run (the launch closure on the
        worker), blocked (finish waiting on the worker), wake (the
        worker done → the waiting thread running again)."""
        from ..ops import fault as _fault

        for lane, st in _fault.snapshot().items():
            for phase in ("spawn", "run", "blocked", "wake"):
                self.m_device_lane_seconds.set(st[phase + "_s"],
                                               (lane, phase))

    def _reap_retired_outputs(self) -> None:
        """Free hot-reload-removed outputs once their in-flight
        flushes settle (rides the housekeeping timer). A retired
        output no task routes to will never be flushed again — the
        reload cleared it from every route — so its worker-pool
        threads and plugin state can go NOW: a long-running daemon
        doing periodic reloads must not accumulate one idle pool per
        removal until engine.stop()."""
        if not self._retired_outputs:
            return
        with self._ingest_lock:
            busy = {id(o) for task in self._task_map.values()
                    for o in task.routes}
            ready = [o for o in self._retired_outputs
                     if id(o) not in busy]
            if not ready:
                return
            gone = {id(o) for o in ready}
            self._retired_outputs = [o for o in self._retired_outputs
                                     if id(o) not in gone]
        for out in ready:
            if out.worker_pool is not None:
                out.worker_pool.stop()
                out.worker_pool = None
            try:
                out.plugin.exit()
            except Exception:
                log.exception("retired output %s exit failed",
                              out.display_name)

    def _dispatch_chunk(self, chunk) -> int:
        """Resolve routes and spawn one task for a ready chunk (the
        per-chunk tail of the reference's flb_engine_dispatch).
        Returns PARKED (falsy) only when the task map is full — the
        caller then parks the chunk (and the rest of the fair queue)
        for the next cycle; DISPATCHED when a task slot was consumed;
        ABSORBED when the chunk was handled without a slot (guard-shed
        spill or no live routes), which must count against neither the
        qos dispatch metrics nor the cycle budget."""
        if chunk.route_names is not None:
            # resolve by output NAME whenever names exist (stamped at
            # conditional-split ingest, on shed, and on disk recovery):
            # bit positions index a SPECIFIC outputs list, and a hot
            # reload can swap that list while this chunk sits in
            # flush_all's in-flight window — after the pool/backlog
            # mask-clearing pass can no longer reach it. Names survive
            # any reorder; the mask is only a fast path for chunks
            # that never got names
            routes = [
                o for o in self.outputs
                if o.display_name in chunk.route_names
                and chunk.event_type in o.plugin.event_types
            ]
        elif chunk.routes_mask:
            # conditionally-split chunk: the ingest-time bitmask IS
            # the route set (tag matching already folded in)
            routes = [
                o for i, o in enumerate(self.outputs)
                if (chunk.routes_mask >> i) & 1
                and chunk.event_type in o.plugin.event_types
            ]
        else:
            routes = [
                o for o in self.outputs
                if o.route.matches(chunk.tag)
                and chunk.event_type in o.plugin.event_types
            ]
        if not routes:
            if self.storage is not None:
                self.storage.delete(chunk)
                self.qos.release_storage(chunk)
            return ABSORBED
        # load shedding (fbtpu-guard): above the occupancy watermark,
        # chunks spill to filesystem storage in priority order — the
        # lowest class first — and chunks whose EVERY route is behind
        # an open breaker spill regardless of class
        if self.guard.maybe_shed(chunk, routes):
            return ABSORBED
        # bounded task id map (flb_task_map_get_task_id,
        # src/flb_task.c:542): when every slot is in use the chunk
        # stays parked and is re-dispatched next flush cycle — the
        # reference's "task_id exhausted" stance. The map is mutated
        # here (engine loop or flush_now's caller thread) and in
        # _task_unref (loop callbacks, sync-fallback flush on any
        # thread) — both hold the ingest lock.
        task = None
        with self._ingest_lock:
            if len(self._task_map) >= self.service.task_map_size:
                now = time.time()
                if now - self._task_map_warned > 5.0:
                    self._task_map_warned = now
                    log.warning(
                        "task map full (%d tasks in flight) — chunk "
                        "dispatch paused until slots free",
                        len(self._task_map))
            else:
                task = Task(chunk, routes)
                # fully referenced BEFORE the first spawn: a route
                # completing synchronously must not see users hit 0
                # (and free the slot / delete the chunk) while its
                # siblings are still being spawned
                task.users = len(routes)
                self._task_map[task.id] = task
        if task is None:
            return PARKED
        for out in routes:
            self._spawn_flush(task, out)
        return DISPATCHED

    def _task_unref(self, task: Task) -> bool:
        """flb_task_users_dec: the id-map slot frees when the last
        route finishes (flb_task_destroy). Returns True when this was
        the last reference (callers gate storage cleanup on it instead
        of re-reading task.users unlocked)."""
        with self._ingest_lock:
            task.users -= 1
            done = task.users == 0
            if done:
                self._task_map.pop(task.id, None)
        return done

    def _enqueue_event(self, priority: int, fn) -> None:
        """Queue a ready callback through the 8-priority bucket queue
        (flb_engine_handle_event demux order): drains run lowest
        priority number first on the engine loop."""
        with self._event_queue_lock:
            self._event_queue.add(priority, fn)
        self.loop.call_soon_threadsafe(self._drain_event_queue)

    def _drain_event_queue(self) -> None:
        while True:
            with self._event_queue_lock:
                if not self._event_queue:
                    return
                fn = self._event_queue.pop()
            try:
                fn()
            except Exception:
                log.exception("engine event callback failed")

    def _spawn_flush(self, task: Task, out: OutputInstance,
                     priority: Optional[int] = None) -> None:
        from .bucket_queue import PRIORITY_FLUSH

        if self.loop is not None and self.running:
            # per-output circuit breaker (fbtpu-guard): while open,
            # dispatch short-circuits to an immediately scheduled retry
            # — no coroutine, no connection, no flush-semaphore slot.
            # Deliberately NOT counted against retry_limit: the breaker
            # is suppressing attempts, not failing them, and must never
            # turn a sick-but-recoverable route into dropped chunks.
            delay = self.guard.short_circuit_delay(out)
            if delay is not None:
                self.guard.m_short_circuit.inc(1, (out.display_name,))
                self._schedule_retry(task, out, delay)
                return
        coro = self._flush_one(task, out)
        if self.loop is None or not self.running:
            # synchronous fallback (engine not started: unit tests)
            asyncio.run(coro)
            return
        def _create():
            fut = asyncio.ensure_future(coro)
            self.guard.track(task, out, fut)
            self._pending_flushes.add(fut)
            fut.add_done_callback(self._pending_flushes.discard)
        try:
            self._enqueue_event(
                PRIORITY_FLUSH if priority is None else priority, _create)
        except RuntimeError:
            # loop shut down mid-stop: account the chunk as dropped
            coro.close()
            self.m_out_errors.inc(1, (out.display_name,))
            self.m_out_dropped.inc(task.chunk.records, (out.display_name,))
            self._task_unref(task)

    async def _flush_one(self, task: Task, out: OutputInstance) -> None:
        """One (task × output) flush ATTEMPT
        (flb_output_flush_create/output_pre_cb_flush). A RETRY result
        does not sleep here: it registers a scheduler timer that
        re-spawns a fresh attempt (flb_engine_dispatch_retry,
        src/flb_engine_dispatch.c:36-99), so a chunk backing off for
        minutes holds no coroutine and no concurrency slot. Concurrency
        honors the reference's dispatch flags
        (src/flb_engine_dispatch.c:193-207 + flb_output_thread.c):
        FLB_OUTPUT_SYNCHRONOUS / no_multiplex serialize to one in-flight
        flush per output; ``workers N`` bounds concurrency to N."""
        try:
            await self._flush_body(task, out)
        except asyncio.CancelledError:
            if self.guard.consume_timeout(task, out):
                # guard soft-kill (flush deadline expired), NOT a
                # shutdown cancel: the slot's attempt is reclaimed and
                # the chunk re-enters the retry scheduler as a normal
                # RETRY (it counts against retry_limit, so a
                # permanently hung route still drains to the DLQ)
                delay = self._handle_flush_result(task, out,
                                                  FlushResult.RETRY)
                if delay is not None:
                    self._schedule_retry(task, out, delay)
                return
            # engine stopping with this route undelivered (parked on the
            # semaphore, mid-flush, or in backoff): a memory chunk would
            # be silently lost — quarantine when storage is on.
            # Filesystem chunks are on disk and recover as backlog.
            if self.storage is not None and \
                    not self.storage.is_tracked(task.chunk):
                try:
                    if _fp.ACTIVE:
                        _fp.fire("engine.shutdown_quarantine")
                    self.storage.quarantine(task.chunk)
                except Exception:
                    log.exception("shutdown quarantine failed")
            raise

    def _flush_payload(self, task: Task, out: OutputInstance) -> bytes:
        """The bytes this output delivers for the chunk — output-side
        processors (flb_processor_run at flush-create,
        include/fluent-bit/flb_output.h:794) run ONCE per (chunk,
        output); retries reuse the cached result so non-idempotent
        processors never repeat side effects."""
        chunk = task.chunk
        cached = task.processed.get(out.name)
        if cached is not None:
            return cached
        data = chunk.get_bytes()
        if out.processors and chunk.event_type == EVENT_TYPE_LOGS:
            events = self._run_log_processors(
                out.processors, decode_events(data), chunk.tag
            )
            data = b"".join(
                ev.raw if ev.raw is not None else reencode_event(ev)
                for ev in events
            )
        elif out.processors and chunk.event_type == EVENT_TYPE_METRICS:
            data = self._run_metrics_processors(out.processors, data,
                                                chunk.tag)
        elif out.processors and chunk.event_type == EVENT_TYPE_TRACES:
            data, _ = self._run_traces_processors(out.processors, data,
                                                  chunk.tag, chunk.records)
        if out.processors:
            task.processed[out.name] = data
        return data

    async def _flush_body(self, task: Task, out: OutputInstance) -> None:
        chunk = task.chunk
        data = self._flush_payload(task, out)

        async def attempt() -> Optional[float]:
            sem = out.flush_semaphore
            if sem is not None:
                await sem.acquire()
            # fbtpu-qos tenant.flush_concurrency: cap the tenant's
            # concurrent flushes ACROSS outputs, acquired after the
            # output slot (uniform order, no cross-wait cycle). Held
            # by reference: a reload that swaps the tenant's semaphore
            # never strands this release.
            tsem = self.qos.flush_slot(chunk)
            if tsem is not None:
                await tsem.acquire()
            # the deadline clock starts HERE, once the attempt actually
            # executes: time parked in the flush-semaphore queue behind
            # a saturated-but-healthy output must not count (the slot
            # HOLDER's deadline runs, so a hung holder still frees the
            # queue), and the guard-tracked record is exposed to the
            # flush via the cooperative-cancel contextvar
            rec = self.guard.flight(task, out)
            if rec is not None:
                from . import guard as _guard

                rec.started = time.time()
                rec.begun = True
                _guard.CANCEL_EVENT.set(rec.cancel_event)
            # expose the chunk to the plugin the same way the cancel
            # event is exposed: outputs that relay pipeline metadata
            # (out_forward's tenant/priority wire stamps) read it here
            FLUSH_CHUNK.set(chunk)
            try:
                # test formatter hook (src/flb_engine_dispatch.c:101-137)
                if out.test_formatter is not None:
                    try:
                        out.test_formatter(data, chunk.tag)
                        result = FlushResult.OK
                    except Exception:
                        log.exception("test formatter failed")
                        result = FlushResult.ERROR
                else:
                    try:
                        if _fp.ACTIVE:
                            # hung/failing-destination faults: an ASYNC
                            # site, so delay()/hang() suspends only this
                            # flush (cancellable by the guard deadline),
                            # never the engine loop. The instance-scoped
                            # name lets one output hang while siblings
                            # flow (FAULTS.md).
                            await _fp.fire_async("output.flush")
                            await _fp.fire_async(
                                "output.flush." + out.display_name)
                        if out.worker_pool is not None:
                            # run the plugin's flush on a worker thread
                            # loop (flb_output_thread.c round-robin);
                            # result/retry handling stays here
                            if rec is not None:
                                rec.worker = True
                            result = await out.worker_pool.submit(
                                self._worker_flush(out.plugin, data,
                                                   chunk.tag, rec,
                                                   chunk))
                        else:
                            with span("output.flush",
                                      out=out.display_name):
                                result = await out.plugin.flush(
                                    data, chunk.tag, self)
                    except asyncio.CancelledError:
                        raise
                    except Exception:
                        log.exception("output %s flush raised",
                                      out.display_name)
                        result = FlushResult.ERROR
            finally:
                if tsem is not None:
                    tsem.release()
                if sem is not None:
                    sem.release()
            return self._handle_flush_result(task, out, result)

        delay = await attempt()
        if delay is None:
            return
        if self.loop is not None and self.running:
            self._schedule_retry(task, out, delay)
            return
        # synchronous fallback (engine not started: unit tests/lib mode
        # without a loop): retry inside this coroutine like the
        # pre-scheduler design — asyncio.run() can't be nested
        while delay is not None:
            await asyncio.sleep(delay)
            delay = await attempt()

    async def _worker_flush(self, plugin, data: bytes, tag: str, rec,
                            chunk=None):
        """Worker-pool submission wrapper: re-exposes the guard's
        cooperative cancel flag AND the flush-chunk contextvar on the
        worker loop (contextvars do not cross
        ``run_coroutine_threadsafe``) and marks completion, so the
        watchdog can tell a soft-kill that landed late from a worker
        thread wedged in sync code (the leaked-thread counter)."""
        if rec is not None:
            from . import guard as _guard

            _guard.CANCEL_EVENT.set(rec.cancel_event)
        FLUSH_CHUNK.set(chunk)
        try:
            with span("output.flush", out=plugin.name):
                return await plugin.flush(data, tag, self)
        finally:
            if rec is not None:
                rec.worker_done = True

    def _schedule_retry(self, task: Task, out: OutputInstance,
                        delay: float) -> None:
        """Timer-driven retry re-dispatch: the backoff lives in the
        event loop's timer wheel (flb_sched_request_create →
        flb_engine_dispatch_retry), not in a parked coroutine. At stop,
        pending retry records are quarantined like any undelivered
        route."""
        key = (task.chunk.id, out.name)
        if _fp.ACTIVE:
            try:
                # retry infrastructure failure: the chunk's retry cannot
                # be scheduled — account it like a shutdown-dropped
                # retry (quarantine + drop metrics), never silently leak
                # the task-map slot
                _fp.fire("engine.retry_schedule")
            except _fp.FailpointError:
                log.warning("retry scheduling failed (injected); "
                            "dropping retry for %s", out.display_name)
                self._drop_retry(task, out)
                return

        def _fire():
            from .bucket_queue import PRIORITY_TOP

            self._pending_retries.pop(key, None)
            # fire even while stopping: a retry coming due inside the
            # grace window gets its attempt (the reference services
            # retries until grace expires); if it RETRYs again,
            # _register drops it, and the stop-sequence cleanup handles
            # whatever is still pending when grace runs out.
            # Scheduler events outrank flush spawns
            # (FLB_ENGINE_PRIORITY_CB_SCHED = top)
            self._spawn_flush(task, out, priority=PRIORITY_TOP)

        def _register():
            if self._stopping:
                self._drop_retry(task, out)
                return
            handle = self.loop.call_later(delay, _fire)
            self._pending_retries[key] = (task, out, handle)

        try:
            self.loop.call_soon_threadsafe(_register)
        except RuntimeError:
            self._drop_retry(task, out)

    def _drop_retry(self, task: Task, out: OutputInstance) -> None:
        """Account a retry dropped at shutdown: quarantine the chunk
        unless its bytes are already on disk, and count the drop like
        every other drop path."""
        self.m_out_errors.inc(1, (out.display_name,))
        self.m_out_dropped.inc(task.chunk.records, (out.display_name,))
        if self.storage is not None and \
                not self.storage.is_tracked(task.chunk):
            try:
                if _fp.ACTIVE:
                    _fp.fire("engine.shutdown_quarantine")
                self.storage.quarantine(task.chunk)
            except Exception:
                log.exception("retry quarantine failed")
        self._task_unref(task)

    def _handle_flush_result(self, task: Task, out: OutputInstance,
                             result: FlushResult) -> Optional[float]:
        """handle_output_event equivalent (src/flb_engine.c:302-540).
        Returns the backoff delay when the flush must be retried, else None."""
        name = out.display_name
        chunk = task.chunk
        if result == FlushResult.OK:
            self.guard.on_result(out, ok=True)  # breaker: close/hold
            self.m_out_proc_records.inc(chunk.records, (name,))
            self.m_out_proc_bytes.inc(chunk.size, (name,))
            self.m_latency.observe(time.time() - chunk.created, (name,))
            if self._task_unref(task) and self.storage is not None:
                self.storage.delete(chunk)  # every route delivered
                self.qos.release_storage(chunk)
            return None
        if result == FlushResult.RETRY:
            attempts = task.retries.get(out.name, 0) + 1
            task.retries[out.name] = attempts
            limit = out.retry_limit if out.retry_limit is not None else self.service.retry_limit
            if limit == -1 or attempts <= limit:
                self.guard.on_result(out, ok=False)
                self.m_out_retries.inc(1, (name,))
                return backoff_full_jitter(
                    self.service.scheduler_base, self.service.scheduler_cap, attempts
                )
            self.m_out_retries_failed.inc(1, (name,))
        # ERROR or retries exhausted → drop (+ DLQ quarantine when storage on)
        self.guard.on_result(out, ok=False)  # breaker: count the failure
        self.m_out_errors.inc(1, (name,))
        self.m_out_dropped.inc(chunk.records, (name,))
        if self.storage is not None:
            try:
                self.storage.quarantine(chunk)
            except Exception:
                log.exception("DLQ quarantine failed")
        if self._task_unref(task) and self.storage is not None:
            self.storage.delete(chunk)  # dlq copy (if any) is separate
            self.qos.release_storage(chunk)
        return None

    # ------------------------------------------------------------------
    # notifications (src/flb_notification.c)
    # ------------------------------------------------------------------

    def notify(self, event: dict) -> None:
        for cb in self._notification_subs:
            try:
                cb(event)
            except Exception:
                log.exception("notification callback failed")

    def subscribe(self, cb) -> None:
        self._notification_subs.append(cb)

    # convenience for tests / lib mode
    def flush_now(self) -> None:
        """Force a flush cycle and wait for pending flushes to settle."""
        self.flush_all()
        if self.loop is None or not self.running:
            return
        # call_soon_threadsafe callbacks run FIFO: once this sentinel fires,
        # every _create queued by flush_all has populated _pending_flushes.
        settled = threading.Event()
        try:
            self.loop.call_soon_threadsafe(settled.set)
        except RuntimeError:
            return
        settled.wait(timeout=2)
        deadline = time.time() + 5
        # retried chunks park as scheduler timers, not coroutines —
        # settle on both so callers still observe final delivery
        while (self._pending_flushes or self._pending_retries) \
                and time.time() < deadline:
            time.sleep(0.01)
