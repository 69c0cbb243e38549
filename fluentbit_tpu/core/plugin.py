"""Plugin model — vtables, instances, registry.

Reference: the C plugin vtables flb_input_plugin / flb_filter_plugin /
flb_output_plugin (include/fluent-bit/flb_input.h, flb_filter.h,
flb_output.h) with cb_init/cb_collect/cb_filter/cb_flush/cb_exit, and the
per-instance property machinery in src/flb_input.c / flb_output.c /
flb_filter.c. Plugins here are Python classes registered by name; the
registry replaces the cmake plugin gating (cmake/plugins_options.cmake).
"""

from __future__ import annotations

import contextvars
import enum
import logging
import threading
from typing import Any, Callable, Dict, List, Optional, Type

from .config import ConfigMapEntry, Properties, apply_config_map
from .lockorder import make_lock
from .router import Route
from ..codec.chunk import Chunk, ChunkPool, EVENT_TYPE_LOGS

log = logging.getLogger("flb")

# The chunk whose payload the CURRENT flush attempt is delivering,
# exposed to output plugins the same way the guard's cooperative-cancel
# event is (core/guard.py CANCEL_EVENT): set by the engine around
# plugin.flush, re-set on worker loops (contextvars do not cross
# run_coroutine_threadsafe). Outputs that relay pipeline metadata —
# out_forward propagating the chunk's tenant/priority stamps across the
# fan-in hop — read it instead of growing the flush() signature that
# every registered output implements.
FLUSH_CHUNK: "contextvars.ContextVar[Optional[Chunk]]" = \
    contextvars.ContextVar("flb_flush_chunk", default=None)


class FlushResult(enum.Enum):
    """Output flush verdicts (reference FLB_OK/FLB_RETRY/FLB_ERROR,
    include/fluent-bit/flb_output.h FLB_OUTPUT_RETURN)."""

    OK = 1
    RETRY = 2
    ERROR = 3


class FilterResult(enum.Enum):
    """Filter verdicts (FLB_FILTER_NOTOUCH / FLB_FILTER_MODIFIED)."""

    NOTOUCH = 1
    MODIFIED = 2


class Plugin:
    """Common plugin base."""

    name: str = ""
    description: str = ""
    config_map: List[ConfigMapEntry] = []
    # event types the plugin handles (logs/metrics/traces); logs by default
    event_types = (EVENT_TYPE_LOGS,)

    def __init__(self) -> None:
        self.instance: Optional["Instance"] = None

    # lifecycle
    def init(self, instance: "Instance", engine) -> None:  # cb_init
        pass

    def exit(self) -> None:  # cb_exit
        pass


class InputPlugin(Plugin):
    """Input vtable. Collect models supported:
    - interval collectors: declare ``collect_interval`` (seconds) and
      implement ``collect(engine)`` — flb_input_set_collector_time
    - server inputs: implement ``start_server(engine)`` returning an
      awaitable/task — the in_http/in_forward style
    - library inputs: expose ``push`` for direct injection (in_lib)
    """

    default_tag: Optional[str] = None
    collect_interval: Optional[float] = None
    threaded_capable: bool = False

    def collect(self, engine) -> None:
        pass

    async def start_server(self, engine) -> None:
        pass

    def pause(self) -> None:  # cb_pause (backpressure)
        pass

    def resume(self) -> None:  # cb_resume
        pass


class FilterPlugin(Plugin):
    """Filter vtable: ``filter(events, tag) -> (FilterResult, events')``.

    The reference cb_filter gets the whole chunk msgpack buffer
    (src/flb_filter.c:202-210); here filters get the decoded event list for
    the chunk-sized append and return a replacement list (or the same list
    with NOTOUCH). Byte-level identity for untouched records is preserved
    because events carry their raw spans (event.raw) and the chunk writer
    re-uses them verbatim.

    Batched fast path: a filter may additionally advertise
    ``can_process_batch()`` and implement ``process_batch(chunk)`` over a
    :class:`~fluentbit_tpu.core.chunk_batch.RawChunk` — the engine then
    routes whole appends through it on the raw ingest path (no Python
    decode); it is the one raw hook the engine knows. The hook returns
    ``(n_records_out, data_out)`` or ``(n_out, data_out, n_in)`` (when
    the batch pass discovered the input record count), or None to
    decline — the engine then falls back to the bit-exact per-record
    path, so exotic option combinations cost nothing but the fallback.

    A filter whose ``process_batch`` waits for a device launch may also
    define ``begin_batch(data, n_records)`` (this class does not, and
    the engine looks for the name): the side-effect-free first
    half of that call — stage ``data`` and begin the launch, commit
    nothing, touch no counter — returning a handle with ``drop()`` (or
    None). An input that holds an append's bytes before the append's
    turn begins the FIRST matching filter's launch through
    ``Engine.input_log_prelaunch``; ``process_batch`` then finds the
    handle on its chunk (``RawChunk.take_begun()``) and must check that
    it was made for these bytes and this configuration before it
    finishes it in place of staging again. A handle is finished or
    dropped, never left.
    """

    #: True when the raw/batched path is pure (immutable config, no
    #: cross-record state): the engine may then run the chain for
    #: multiple inputs in parallel under per-input locks only
    thread_safe_raw: bool = False

    #: True when ``process_batch`` commits side effects (emitter
    #: re-emits, metric bumps) before it can decline: a decline after
    #: such a hook finishes per record from that filter onward instead
    #: of re-running the whole chain (core/chunk_batch.py)
    stateful_batch: bool = False

    def filter(self, events: list, tag: str, engine) -> tuple:
        return (FilterResult.NOTOUCH, events)

    def can_process_batch(self) -> bool:
        """True when ``process_batch`` can serve this instance's
        configuration (checked per append; cheap)."""
        return False

    def process_batch(self, chunk) -> Optional[tuple]:
        """Whole-chunk batched execution; None declines to per-record."""
        return None


class OutputPlugin(Plugin):
    """Output vtable: async ``flush(chunk_bytes, tag) -> FlushResult``."""

    synchronous: bool = False  # FLB_OUTPUT_SYNCHRONOUS
    no_multiplex: bool = False  # FLB_OUTPUT_NO_MULTIPLEX

    async def flush(self, data: bytes, tag: str, engine) -> FlushResult:
        return FlushResult.OK


class CustomPlugin(Plugin):
    """Custom vtable (reference src/flb_custom.c, flb_custom_init_all at
    src/flb_engine.c:973): initialized BEFORE the pipeline plugins; a
    custom may create input/filter/output instances programmatically
    (the calyptia control-plane pattern)."""


class ProcessorPlugin(Plugin):
    """Processor vtable — per-instance pipelines with stages/conditions
    (reference src/flb_processor.c). Runs on decoded events at input ingest
    or output flush."""

    def process_logs(self, events: list, tag: str, engine) -> list:
        return events

    def process_metrics(self, contexts: list, tag: str, engine) -> list:
        return contexts

    def process_traces(self, spans: list, tag: str, engine) -> list:
        return spans


class Instance:
    """A configured plugin instance (flb_input_instance etc.)."""

    def __init__(self, plugin: Plugin, kind: str):
        self.plugin = plugin
        self.kind = kind  # input|filter|output|processor|custom
        # provisional name; the engine re-numbers per context
        # (reference: instance names are in_emitter.0 style, per flb_config)
        self.name = f"{plugin.name}.0"
        self.alias: Optional[str] = None
        self.properties = Properties()
        self.route = Route(match="*")
        plugin.instance = self

    def set(self, key: str, value: Any) -> None:
        self.properties.set(key, value)

    def prop(self, key: str, default: Any = None) -> Any:
        return self.properties.get(key, default)

    def configure(self) -> None:
        """Apply config_map + core keys."""
        apply_config_map(self.plugin.config_map, self.properties, self.plugin)
        self.alias = self.properties.get("alias")
        match = self.properties.get("match")
        match_regex = self.properties.get("match_regex")
        if match or match_regex:
            self.route = Route(match=match, match_regex=match_regex)

    @property
    def display_name(self) -> str:
        return self.alias or self.name


class InputInstance(Instance):
    def __init__(self, plugin: InputPlugin):
        super().__init__(plugin, "input")
        self.pool = ChunkPool(self.name)
        self.tag: Optional[str] = None
        self.mem_buf_limit: int = 0  # 0 = unlimited
        self.paused = False
        self.storage_type = "memory"
        self.processors: List = []  # input-side processor pipeline
        self.collector_task = None
        self.threaded = False  # run the collector on its own OS thread
        self.collector_thread = None
        self.removed = False  # set by hot reload: collectors stop
        self.paused_by_qos = False  # quota DEFER pause (engine resume)
        # fbtpu-qos tenant membership (core/qos.py): resolved lazily
        # and cached as _qos_tenant on first admission
        self.tenant_name: Optional[str] = None
        self.tenant_params: dict = {}
        # serializes this input's pool: every append/drain of this
        # input's chunks holds it, so raw-path ingest can run WITHOUT
        # the engine-global lock when the filter chain allows (reference:
        # per-input chunk maps, src/flb_input_log.c:1524). RLock — the
        # global-lock paths nest it around their pool touches.
        self.ingest_lock = make_lock("InputInstance.ingest_lock",
                                     reentrant=True)

    def set_paused(self, paused: bool) -> bool:
        """Atomically flip the backpressure flag and fire the plugin's
        cb_pause/cb_resume (src/flb_input.c:740-788). Ingest threads and
        the engine loop both reach the check-then-act; without the lock
        two appends crossing the limit double-fire pause() (fbtpu-lint
        guarded-by: `paused`). Collectors still READ the flag lock-free
        — transient staleness there only delays a collect tick."""
        with self.ingest_lock:
            if self.paused == paused:
                return False
            self.paused = paused
            cb = self.plugin.pause if paused else self.plugin.resume
            try:
                cb()
            except Exception:
                log.exception("%s %s callback failed", self.display_name,
                              "pause" if paused else "resume")
        return True

    def configure(self) -> None:
        super().configure()
        # default tag = per-instance name (dummy.0, dummy.1, ...) so two
        # instances of the same plugin never merge streams (reference:
        # instance tag defaults to the instance name)
        self.tag = self.properties.get("tag") or self.plugin.default_tag or self.name
        from .config import parse_bool, parse_size
        mbl = self.properties.get("mem_buf_limit")
        self.mem_buf_limit = parse_size(mbl) if mbl else 0
        self.storage_type = self.properties.get("storage.type", "memory")
        # storage.pause_on_chunks_overlimit (src/flb_input.c:169):
        # filesystem-backed inputs pause at storage.max_chunks_up
        self.pause_on_chunks_overlimit = parse_bool(
            self.properties.get("storage.pause_on_chunks_overlimit", False)
        )
        # threaded collector (reference FLB_INPUT_THREADED /
        # `threaded on`, src/flb_input_thread.c:225): collection work
        # runs on a dedicated OS thread; the append path stays
        # thread-safe via the engine's ingest locking
        self.threaded = parse_bool(self.properties.get("threaded", False))
        # fbtpu-qos tenant declaration (QOS.md): `tenant <name>` joins
        # the input to a tenant; tenant.* keys declare that tenant's
        # contract (last declaration wins, so one input can carry the
        # contract for a tenant several inputs share)
        self.tenant_name = self.properties.get("tenant")
        params: dict = {}
        w = self.properties.get("tenant.weight")
        if w is not None:
            params["weight"] = float(w)
        pr = self.properties.get("tenant.priority")
        if pr is not None:
            params["priority"] = int(pr)
        rate = self.properties.get("tenant.rate")
        if rate is not None:
            params["rate"] = float(parse_size(rate))  # bytes/second
        burst = self.properties.get("tenant.burst")
        if burst is not None:
            params["burst"] = float(parse_size(burst))
        sl = self.properties.get("tenant.storage_limit")
        if sl is not None:
            # cap on the tenant's LIVE filesystem footprint (bytes of
            # stream chunk files); over it, write-through is shed and
            # the chunk stays memory-only (Qos.admit_storage)
            params["storage_limit"] = int(parse_size(sl))
        fc = self.properties.get("tenant.flush_concurrency")
        if fc is not None:
            # cap on the tenant's concurrent flush attempts across all
            # outputs (QOS.md); enforced next to the per-output worker
            # semaphore in engine._flush_body
            fc = int(fc)
            if fc < 1:
                raise ValueError(
                    f"tenant.flush_concurrency must be >= 1, got {fc}")
            params["flush_concurrency"] = fc
        ovf = self.properties.get("tenant.overflow")
        if ovf is not None:
            ovf = str(ovf).lower()
            if ovf not in ("defer", "shed"):
                raise ValueError(
                    f"tenant.overflow must be defer|shed, got {ovf!r}")
            params["overflow"] = ovf
        self.tenant_params = params


class FilterInstance(Instance):
    def __init__(self, plugin: FilterPlugin):
        super().__init__(plugin, "filter")


class OutputInstance(Instance):
    def __init__(self, plugin: OutputPlugin):
        super().__init__(plugin, "output")
        self.retry_limit: Optional[int] = None  # None → service default
        # fbtpu-guard per-output flush deadline (None → service
        # guard.flush_timeout → 2×grace; core/guard.py)
        self.flush_timeout: Optional[float] = None
        self.workers: int = 0
        self.processors: List = []
        # flush-concurrency bound, built at configure():
        # synchronous/no_multiplex → 1; workers N → N; else unbounded
        self.flush_semaphore = None
        # test hooks (reference: flb_output_set_test / test_formatter mode,
        # src/flb_engine_dispatch.c:101-137)
        self.test_formatter: Optional[Callable] = None
        self.http2 = False  # prior-knowledge h2c delivery
        self.proxy = None   # (host, port) of an http:// forward proxy
        self.worker_pool = None  # OutputWorkerPool when workers > 0
        # ingest-time conditional route (flb_router_condition.c):
        # records failing the condition never enter this output's chunks
        self.route_condition = None

    def configure(self) -> None:
        super().configure()
        from .config import parse_bool

        conds = self.properties.get_all("route_condition")
        if conds:
            from .conditions import Condition, Rule

            rules = []
            for c in conds:
                parts = c.split(None, 2) if isinstance(c, str) else list(c)
                if len(parts) < 2:
                    raise ValueError(
                        f"route_condition needs 'field op [value]': {c!r}")
                field, op = parts[0], parts[1]
                value: object = parts[2] if len(parts) > 2 else None
                # numeric coercion ONLY for ordering ops — eq/neq on a
                # numeric-looking STRING field must stay expressible
                if isinstance(value, str) and op.lower() in (
                        "gt", "lt", "gte", "lte"):
                    try:
                        value = int(value)
                    except ValueError:
                        try:
                            value = float(value)
                        except ValueError:
                            pass
                rules.append(Rule(field, op, value))
            self.route_condition = Condition(rules, "and")

        # fail fast on a bad value (config_map-typed options do the
        # same); an invalid bool must not surface per-flush
        self.http2 = parse_bool(self.properties.get("http2", False))
        pxy = self.properties.get("proxy")
        if pxy:
            # reference proxy_parse (flb_http_client.c:744): http:// only
            # (https proxies are an explicit FIXME there too)
            from urllib.parse import urlsplit
            if "://" not in pxy:
                pxy = "http://" + pxy
            parts = urlsplit(pxy)
            if parts.scheme != "http":
                raise ValueError(
                    f"proxy: only http:// proxies are supported, got {pxy!r}")
            self.proxy = (parts.hostname, parts.port or 80)
            if parts.username:
                import base64 as _b64
                cred = f"{parts.username}:{parts.password or ''}"
                self.proxy_auth = "Basic " + _b64.b64encode(
                    cred.encode()).decode()
            else:
                self.proxy_auth = None
        ft = self.properties.get("flush_timeout")
        if ft is not None:
            from .config import parse_time
            self.flush_timeout = parse_time(ft)
        rl = self.properties.get("retry_limit")
        if rl is not None:
            if str(rl).lower() in ("no_limits", "false", "no_retries_forever", "unlimited"):
                self.retry_limit = -1
            else:
                self.retry_limit = int(rl)
        w = self.properties.get("workers")
        if w is not None:
            self.workers = int(w)
        import asyncio as _asyncio
        from .config import parse_bool as _pb

        if self.plugin.synchronous or self.plugin.no_multiplex or \
                _pb(self.properties.get("no_multiplex", False)):
            self.flush_semaphore = _asyncio.Semaphore(1)
        elif self.workers > 0:
            self.flush_semaphore = _asyncio.Semaphore(self.workers)


class Registry:
    """Plugin name → class registry for all plugin kinds."""

    def __init__(self) -> None:
        self.inputs: Dict[str, Type[InputPlugin]] = {}
        self.filters: Dict[str, Type[FilterPlugin]] = {}
        self.outputs: Dict[str, Type[OutputPlugin]] = {}
        self.processors: Dict[str, Type[ProcessorPlugin]] = {}
        self.customs: Dict[str, Type[CustomPlugin]] = {}

    def register(self, cls: Type[Plugin]) -> Type[Plugin]:
        if issubclass(cls, InputPlugin):
            self.inputs[cls.name] = cls
        elif issubclass(cls, FilterPlugin):
            self.filters[cls.name] = cls
        elif issubclass(cls, OutputPlugin):
            self.outputs[cls.name] = cls
        elif issubclass(cls, ProcessorPlugin):
            self.processors[cls.name] = cls
        elif issubclass(cls, CustomPlugin):
            self.customs[cls.name] = cls
        else:
            raise TypeError(f"unknown plugin kind {cls!r}")
        return cls

    def create_input(self, name: str) -> InputInstance:
        return InputInstance(self._get(self.inputs, name, "input")())

    def create_filter(self, name: str) -> FilterInstance:
        return FilterInstance(self._get(self.filters, name, "filter")())

    def create_output(self, name: str) -> OutputInstance:
        return OutputInstance(self._get(self.outputs, name, "output")())

    def create_processor(self, name: str):
        inst = Instance(self._get(self.processors, name, "processor")(), "processor")
        return inst

    def create_custom(self, name: str):
        return Instance(self._get(self.customs, name, "custom")(),
                        "custom")

    @staticmethod
    def _get(table: dict, name: str, kind: str):
        cls = table.get(name)
        if cls is None:
            raise ValueError(f"unknown {kind} plugin {name!r} (have: {sorted(table)})")
        return cls


#: Global default registry; plugins self-register at import via
#: ``@registry.register``.
registry = Registry()
