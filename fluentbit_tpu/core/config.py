"""Typed plugin configuration — config_map equivalent.

Reference: include/fluent-bit/flb_config_map.h:33-51 defines a declarative
per-plugin option schema (FLB_CONFIG_MAP_STR/INT/BOOL/SIZE/TIME/DOUBLE/
CLIST/SLIST...) that is auto-validated and written into plugin context
structs. Here a plugin declares ``config_map`` as a list of ConfigMapEntry;
``apply_config_map`` validates + coerces user properties onto the instance.

Also the service-level config (flush interval, grace, scheduler base/cap —
reference src/flb_config.c:190-193,369-370).
"""

from __future__ import annotations

import re
from dataclasses import dataclass, field
from typing import Any, Dict, List, Optional

# ---------------------------------------------------------------------------
# Value coercion (reference: flb_utils.c flb_utils_size_to_bytes,
# flb_utils_time_to_seconds, flb_utils_bool)
# ---------------------------------------------------------------------------

_SIZE_RE = re.compile(r"^\s*(\d+(?:\.\d+)?)\s*([kKmMgG]?)b?\s*$")
_TIME_RE = re.compile(r"^\s*(\d+(?:\.\d+)?)\s*(ms|s|m|h|d)?\s*$")

_SIZE_MULT = {"": 1, "k": 1024, "m": 1024 ** 2, "g": 1024 ** 3}
_TIME_MULT = {None: 1.0, "ms": 1e-3, "s": 1.0, "m": 60.0, "h": 3600.0, "d": 86400.0}

TRUE_WORDS = {"true", "on", "yes", "1", "enabled"}
FALSE_WORDS = {"false", "off", "no", "0", "disabled"}


def parse_bool(v: Any) -> bool:
    if isinstance(v, bool):
        return v
    s = str(v).strip().lower()
    if s in TRUE_WORDS:
        return True
    if s in FALSE_WORDS:
        return False
    raise ValueError(f"invalid boolean value: {v!r}")


def parse_size(v: Any) -> int:
    """'10M' → bytes (flb_utils_size_to_bytes)."""
    if isinstance(v, (int, float)):
        return int(v)
    m = _SIZE_RE.match(str(v))
    if not m:
        raise ValueError(f"invalid size value: {v!r}")
    return int(float(m.group(1)) * _SIZE_MULT[m.group(2).lower()])


def parse_time(v: Any) -> float:
    """'5s' / '100ms' → seconds."""
    if isinstance(v, (int, float)):
        return float(v)
    m = _TIME_RE.match(str(v))
    if not m:
        raise ValueError(f"invalid time value: {v!r}")
    return float(m.group(1)) * _TIME_MULT[m.group(2)]


def split_clist(v: Any, sep: str = ",") -> List[str]:
    if isinstance(v, (list, tuple)):
        return [str(x) for x in v]
    return [part.strip() for part in str(v).split(sep) if part.strip()]


def split_slist(v: Any, max_split: int = -1) -> List[str]:
    """Space-separated list (config_map SLIST): respects max_split so the
    trailing element may contain spaces (used e.g. by grep's 'Regex key
    pattern with spaces')."""
    if isinstance(v, (list, tuple)):
        return [str(x) for x in v]
    return str(v).split(None, max_split) if max_split >= 0 else str(v).split()


_COERCERS = {
    "raw": lambda v: v,  # pass-through (python-object properties, e.g. out_lib callback)
    "str": lambda v: str(v),
    "int": lambda v: int(str(v), 0),
    "double": lambda v: float(v),
    "bool": parse_bool,
    "size": parse_size,
    "time": parse_time,
    "clist": split_clist,
    "slist": split_slist,
}


@dataclass
class ConfigMapEntry:
    """One declarative plugin option."""

    name: str
    type: str = "str"  # str|int|double|bool|size|time|clist|slist
    default: Any = None
    multiple: bool = False  # option may appear multiple times (e.g. grep rules)
    slist_max_split: int = -1
    desc: str = ""

    def coerce(self, value: Any) -> Any:
        if self.type == "slist" and self.slist_max_split >= 0:
            return split_slist(value, self.slist_max_split)
        fn = _COERCERS.get(self.type)
        if fn is None:
            raise ValueError(f"unknown config_map type {self.type!r}")
        return fn(value)


class Properties:
    """Case-insensitive property bag with multi-value support.

    Reference config keys are case-insensitive (flb_config_prop_get uses
    strcasecmp); values set multiple times accumulate (grep Regex rules).
    """

    def __init__(self) -> None:
        self._items: List[tuple] = []  # (lower_key, original_key, value)

    def set(self, key: str, value: Any) -> None:
        self._items.append((key.lower(), key, value))

    def get(self, key: str, default: Any = None) -> Any:
        k = key.lower()
        for lk, _, v in reversed(self._items):
            if lk == k:
                return v
        return default

    def get_all(self, key: str) -> List[Any]:
        k = key.lower()
        return [v for lk, _, v in self._items if lk == k]

    def items(self):
        return [(orig, v) for _, orig, v in self._items]

    def __contains__(self, key: str) -> bool:
        k = key.lower()
        return any(lk == k for lk, _, _ in self._items)

    def update(self, d: Dict[str, Any]) -> None:
        for k, v in d.items():
            self.set(k, v)


def apply_config_map(config_map: List[ConfigMapEntry], props: Properties,
                     target: Any) -> None:
    """Validate + coerce properties onto ``target`` attributes.

    Unknown properties raise (the reference fails startup on unknown keys).
    Attribute name is the option name lowercased with '.' and '-' → '_'.
    """
    by_name = {e.name.lower(): e for e in config_map}
    seen_multi: Dict[str, list] = {}
    for key, value in props.items():
        lk = key.lower()
        entry = by_name.get(lk)
        if entry is None:
            # allow shared/core keys handled by the engine itself
            if lk in CORE_INSTANCE_KEYS:
                continue
            if getattr(target, "allow_unknown_properties", False):
                # dynamic (.so) plugins declare no config_map: every
                # property passes through to the native side verbatim
                continue
            raise ValueError(f"unknown property {key!r}")
        coerced = entry.coerce(value)
        attr = _attr_name(entry.name)
        if entry.multiple:
            seen_multi.setdefault(attr, []).append(coerced)
        else:
            setattr(target, attr, coerced)
    for attr, values in seen_multi.items():
        setattr(target, attr, values)
    # defaults
    for e in config_map:
        attr = _attr_name(e.name)
        if not hasattr(target, attr) or getattr(target, attr) is None:
            if e.multiple:
                if not hasattr(target, attr) or getattr(target, attr) is None:
                    setattr(target, attr, [])
            elif e.default is not None:
                setattr(target, attr, e.coerce(e.default))
            elif not hasattr(target, attr):
                setattr(target, attr, None)


def _attr_name(name: str) -> str:
    return name.lower().replace(".", "_").replace("-", "_")


# Instance-level keys consumed by the engine, valid for every plugin
# (reference: flb_input.c/flb_output.c/flb_filter.c common properties).
CORE_INSTANCE_KEYS = {
    "tag", "match", "match_regex", "alias", "log_level",
    "mem_buf_limit", "storage.type", "storage.pause_on_chunks_overlimit",
    "threaded", "workers", "retry_limit", "no_multiplex", "host", "port", "tls",
    "tls.verify", "tls.ca_file", "tls.crt_file", "tls.key_file", "tls.vhost",
    "http2",  # HTTP-based outputs: prior-knowledge h2c delivery
    "proxy",  # HTTP-based outputs: http:// forward proxy
    "route_condition",  # ingest-time conditional routing (outputs)
    "flush_timeout",  # fbtpu-guard per-output flush deadline (outputs)
    # fbtpu-qos tenant membership + contract (inputs; core/qos.py)
    "tenant", "tenant.weight", "tenant.priority", "tenant.rate",
    "tenant.burst", "tenant.overflow", "tenant.storage_limit",
    "tenant.flush_concurrency",
    "net.keepalive", "net.keepalive_idle_timeout",
    "net.keepalive_max_recycle", "net.max_worker_connections",
}


@dataclass
class ServiceConfig:
    """[SERVICE] section (reference src/flb_config.c + flb_config.h)."""

    flush: float = 1.0           # flush timer interval seconds
    grace: float = 5.0           # shutdown grace period
    daemon: bool = False
    log_level: str = "info"
    http_server: bool = False
    http_listen: str = "0.0.0.0"
    http_port: int = 2020
    hot_reload: bool = False
    # SIGHUP applies the config-file diff through a ReloadTxn
    # generation swap (core/reload_diff.py) instead of a full
    # stop/start; unsupported edits fall back to the restart path
    hot_reload_diff: bool = False
    scheduler_base: float = 5.0      # retry backoff base (flb_scheduler.h:29)
    scheduler_cap: float = 2000.0    # retry backoff cap  (flb_scheduler.h:30)
    retry_limit: int = 1             # default per-output retries
    task_map_size: int = 2048        # FLB_CONFIG_DEFAULT_TASK_MAP_SIZE
    storage_path: Optional[str] = None
    storage_sync: str = "normal"
    storage_checksum: bool = False
    storage_backlog_mem_limit: int = 5 * 1024 * 1024
    storage_max_chunks_up: int = 128  # pause threshold (flb_storage)
    # fbtpu-guard (core/guard.py — no reference equivalent): flush
    # deadlines, per-output circuit breakers, watchdog + load shedding
    guard_enable: bool = True
    guard_flush_timeout: float = 0.0     # 0 = off → soft-kill at 2×grace
    guard_breaker_failures: int = 5      # consecutive failures to open
    guard_breaker_error_rate: float = 0.5  # windowed failure fraction
    guard_breaker_window: int = 20       # outcomes in the rate window
    guard_breaker_cooldown: float = 5.0  # open → half-open delay
    guard_breaker_probes: int = 1        # half-open successes to close
    guard_shed_watermark: float = 0.8    # task-map occupancy fraction
    guard_stall_after: float = 30.0      # heartbeat age → "stalled"
    guard_leak_grace: float = 5.0        # soft-kill → leaked-thread count
    guard_worker_start_timeout: float = 10.0  # worker pool startup bound
    # fbtpu-qos (core/qos.py — no reference equivalent). qos_enable
    # gates ADMISSION QUOTAS only (QOS.md): fair dispatch runs
    # regardless (bit-compatible FIFO with a single default tenant)
    # and shed-by-priority keys off tenants spanning >1 class
    qos_enable: bool = True
    qos_quantum: int = 2 * 1024 * 1024   # DWRR bytes/round per weight
    qos_weight_floor: float = 0.05       # zero-weight starvation floor
    qos_default_weight: float = 1.0      # tenants that declare none
    qos_default_priority: int = 4        # 0 = highest of 8 classes
    qos_cycle_budget: int = 0            # bytes dispatched per flush
    #                                      cycle (0 = unlimited)
    qos_shed_hysteresis: float = 0.75    # readmit below thr × this
    # TPU execution options (new — no reference equivalent)
    tpu_enable: bool = True
    tpu_batch_records: int = 8192
    tpu_max_record_len: int = 512
    # jax.profiler.start_server on this port once the device is
    # attached (0 = off): captures show the fbtpu: spans (core/spans.py)
    profiler_port: int = 0

    extra: Dict[str, Any] = field(default_factory=dict)

    _KEYMAP = {
        "flush": ("flush", parse_time),
        "grace": ("grace", parse_time),
        "daemon": ("daemon", parse_bool),
        "log_level": ("log_level", str),
        "http_server": ("http_server", parse_bool),
        "http_listen": ("http_listen", str),
        "http_port": ("http_port", int),
        "hot_reload": ("hot_reload", parse_bool),
        "hot_reload_diff": ("hot_reload_diff", parse_bool),
        "scheduler.base": ("scheduler_base", parse_time),
        "scheduler.cap": ("scheduler_cap", parse_time),
        "retry_limit": ("retry_limit", int),
        "task_map_size": ("task_map_size", int),
        "storage.path": ("storage_path", str),
        "storage.sync": ("storage_sync", str),
        "storage.checksum": ("storage_checksum", parse_bool),
        "storage.backlog.mem_limit": ("storage_backlog_mem_limit", parse_size),
        "storage.max_chunks_up": ("storage_max_chunks_up", int),
        "guard.enable": ("guard_enable", parse_bool),
        "guard.flush_timeout": ("guard_flush_timeout", parse_time),
        "guard.breaker_failures": ("guard_breaker_failures", int),
        "guard.breaker_error_rate": ("guard_breaker_error_rate", float),
        "guard.breaker_window": ("guard_breaker_window", int),
        "guard.breaker_cooldown": ("guard_breaker_cooldown", parse_time),
        "guard.breaker_probes": ("guard_breaker_probes", int),
        "guard.shed_watermark": ("guard_shed_watermark", float),
        "guard.stall_after": ("guard_stall_after", parse_time),
        "guard.leak_grace": ("guard_leak_grace", parse_time),
        "guard.worker_start_timeout":
            ("guard_worker_start_timeout", parse_time),
        "qos.enable": ("qos_enable", parse_bool),
        "qos.quantum": ("qos_quantum", parse_size),
        "qos.weight_floor": ("qos_weight_floor", float),
        "qos.default_weight": ("qos_default_weight", float),
        "qos.default_priority": ("qos_default_priority", int),
        "qos.cycle_budget": ("qos_cycle_budget", parse_size),
        "qos.shed_hysteresis": ("qos_shed_hysteresis", float),
        "tpu.enable": ("tpu_enable", parse_bool),
        "tpu.batch_records": ("tpu_batch_records", int),
        "tpu.max_record_len": ("tpu_max_record_len", int),
        "profiler_port": ("profiler_port", int),
    }

    def set(self, key: str, value: Any) -> None:
        lk = key.lower()
        mapped = self._KEYMAP.get(lk)
        if mapped is None:
            self.extra[lk] = value
            return
        attr, fn = mapped
        setattr(self, attr, fn(value))
