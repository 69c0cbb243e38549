"""The guarded-by registry — the declarative core of the lock rule.

Each entry names, for ONE module, the attributes (``kind="attr"``:
``obj.<name>`` accesses) or module globals (``kind="global"``: bare
names under a module-level lock) whose access must lexically sit inside
``with <lock>:``. The checker is intentionally name-based — matching the
lock *object* would need points-to analysis; matching the lock *name*
catches the real bug class (a new call path touching guarded state
off-lock) at zero false-positive cost in a codebase where lock names are
unique per module.

``writes_only=True`` entries allow lock-free reads: these are the
documented benign-staleness probes (``device.ready()``, the codec
loader's double-checked fast path, the ``paused`` backpressure flag read
by collectors) where a stale read is part of the design and only the
check-then-act WRITE must serialize.

Accesses inside ``__init__``/``__new__`` (attr kind) and at module top
level (global kind) are exempt: construction precedes sharing.

Adding state to a guarded structure? Extend the entry (or add one) in
the same PR — the lint gate then enforces the discipline on every
future caller.
"""

from __future__ import annotations

import os

from dataclasses import dataclass
from typing import Dict, Tuple

__all__ = ["GuardEntry", "GUARDS", "LAUNCH_ENTRIES", "BUDGET_PARAMS",
           "budget_path", "lock_baseline_path", "copy_budget_path",
           "fusion_plan_path"]

# -- fbtpu-xray (analysis/launchgraph.py) declarative plumbing ---------

#: Chain entry points the launch-graph walker roots at: the batched
#: plugin fast path and the flux absorb commit.
LAUNCH_ENTRIES: Tuple[str, ...] = ("process_batch", "absorb_batch")

#: Canonical evaluation point for the symbolic transfer-byte algebra —
#: the committed analysis/launch_budget.json is evaluated here (2
#: double-buffered staging slots, the default FBTPU_SEGMENT_RECORDS,
#: the grep max_len default, the simulated 8-device mesh, one flux
#: group, HLL p=12 registers, the CMS 4×16384 table — the FluxSpec
#: defaults).
BUDGET_PARAMS: Dict[str, int] = {
    "R": 2, "seg": 4096, "L": 512, "n_dev": 8, "G": 1,
    "M_hll": 1 << 12, "M_cms": 4 * 16384,
}


def budget_path() -> str:
    """Path of the committed launch/transfer budget baseline."""
    return os.path.join(os.path.dirname(os.path.abspath(__file__)),
                        "launch_budget.json")


def lock_baseline_path() -> str:
    """Path of the committed fbtpu-locksmith findings baseline."""
    return os.path.join(os.path.dirname(os.path.abspath(__file__)),
                        "lock_baseline.json")


def copy_budget_path() -> str:
    """Path of the committed fbtpu-memscope copy budget baseline."""
    return os.path.join(os.path.dirname(os.path.abspath(__file__)),
                        "copy_budget.json")


def fusion_plan_path() -> str:
    """Path of the committed fbtpu-fuseplan fusion plan baseline."""
    return os.path.join(os.path.dirname(os.path.abspath(__file__)),
                        "fusion_plan.json")


@dataclass(frozen=True)
class GuardEntry:
    #: module path suffix the entry applies to (posix separators)
    module: str
    #: lock name: the terminal attribute (``self._lock`` → ``_lock``) or
    #: the bare global holding the lock
    lock: str
    #: guarded attribute / global names
    attrs: Tuple[str, ...]
    #: True → lock-free reads are a documented part of the design
    writes_only: bool = False
    #: "attr" = obj.<name> accesses; "global" = module-level bare names
    kind: str = "attr"
    #: why the entry exists (shown in findings)
    note: str = ""


GUARDS: Tuple[GuardEntry, ...] = (
    # -- engine: the asyncio-loop / collector-thread / caller boundary --
    GuardEntry(
        "fluentbit_tpu/core/engine.py", "_ingest_lock",
        ("_ingest_src", "_backlog", "_task_map"),
        note="ingest path state: appends run on collector threads and "
             "library callers while flush_all runs on the engine loop "
             "(and flush_now on any thread)",
    ),
    GuardEntry(
        "fluentbit_tpu/core/engine.py", "_event_queue_lock",
        ("_event_queue",),
        note="priority bucket queue: enqueued from any thread, drained "
             "on the engine loop",
    ),
    GuardEntry(
        "fluentbit_tpu/core/engine.py", "ingest_lock", ("pool",),
        note="per-input chunk pool: parallel raw-path ingest appends "
             "race flush_all's drain without the input's lock",
    ),
    GuardEntry(
        "fluentbit_tpu/core/engine.py", "ingest_lock", ("paused",),
        writes_only=True,
        note="backpressure flag: collectors read it lock-free (benign "
             "staleness) but the check-then-act pause/resume flip must "
             "not double-fire plugin callbacks",
    ),
    GuardEntry(
        "fluentbit_tpu/core/plugin.py", "ingest_lock", ("paused",),
        writes_only=True,
        note="same flag, defining module (InputInstance.set_paused)",
    ),
    GuardEntry(
        "fluentbit_tpu/core/engine.py", "_ingest_lock",
        ("traces", "_retired_names", "_retired_outputs"),
        writes_only=True,
        note="hot-reload/trace bookkeeping (fbtpu-locksmith): trace "
             "installs, retired-name tombstones and the retired-output "
             "reap list are mutated by reload commits, trace admin "
             "calls, the reap timer and stop, racing each other; "
             "reads are lock-free probes by design",
    ),
    # -- fbtpu-guard: flights/breakers/shed touched from the engine
    #    loop, flush_now callers, and sync-fallback flushes --
    GuardEntry(
        "fluentbit_tpu/core/guard.py", "_lock",
        ("_flights", "_abandoned", "_shed"),
        note="guard plane state: the watchdog (engine loop or a "
             "flush_now caller thread) races flush done-callbacks and "
             "result recording",
    ),
    GuardEntry(
        "fluentbit_tpu/core/guard.py", "_lock",
        ("_breakers", "_unhealthy"), writes_only=True,
        note="breaker map + not-closed count: the dispatch hot path's "
             "health probe (maybe_shed's early-out) reads lock-free "
             "by design (benign staleness of one flush cycle); "
             "mutation serializes",
    ),
    GuardEntry(
        "fluentbit_tpu/core/guard.py", "_ingest_lock",
        ("_task_map", "_backlog"),
        note="engine ingest-path state read/written by the guard "
             "(occupancy, shed readmission): same discipline as "
             "core/engine.py's own entry",
    ),
    # -- fbtpu-qos: tenant registry + fair dispatch queue --
    GuardEntry(
        "fluentbit_tpu/core/qos.py", "_lock",
        ("_tenants", "_queue"),
        note="qos plane state: ingest threads resolve tenants while "
             "the engine loop / flush_now callers pop the fair queue "
             "and reload transactions re-declare contracts",
    ),
    GuardEntry(
        "fluentbit_tpu/core/qos.py", "_ingest_lock",
        ("_backlog", "_task_map"),
        note="engine ingest-path state written by the reload "
             "generation swap (removed-input drain, list swap): same "
             "discipline as core/engine.py's own entry",
    ),
    GuardEntry(
        "fluentbit_tpu/core/qos.py", "ingest_lock", ("pool",),
        note="per-input chunk pools drained by the reload swap race "
             "parallel raw-path appends without the input's lock",
    ),
    GuardEntry(
        "fluentbit_tpu/core/qos.py", "_ingest_lock",
        ("traces", "_retired_names", "_retired_outputs"),
        writes_only=True,
        note="the reload transaction mutates the same engine "
             "hot-reload bookkeeping from the committing thread "
             "(same discipline as core/engine.py's own entry)",
    ),
    GuardEntry(
        "fluentbit_tpu/core/qos.py", "_lock", ("_graded",),
        writes_only=True,
        note="priority-grading flag: the dispatch hot path reads it "
             "lock-free (benign staleness of one flush cycle); "
             "recomputation serializes with tenant changes",
    ),
    # -- metrics: counters incremented from every thread family --
    GuardEntry(
        "fluentbit_tpu/core/metrics.py", "_lock",
        ("_values", "_counts", "_sums", "_metrics"),
        note="cmetrics state: ingest threads, the engine loop, output "
             "workers and the admin server all touch the same registry",
    ),
    # -- shared sqlite handle registry --
    GuardEntry(
        "fluentbit_tpu/core/sqldb.py", "_lock", ("_open_dbs",),
        kind="global",
        note="shared-handle registry: open_db/close run from any "
             "plugin thread; every access serializes on the module "
             "lock (fbtpu-locksmith registry gap)",
    ),
    # -- lock-order witness recorder (fbtpu-locksmith ground truth) --
    GuardEntry(
        "fluentbit_tpu/core/lockorder.py", "_edges_guard", ("_edges",),
        kind="global",
        note="witness edge set: every acquiring thread records into "
             "it; snapshot/reset serialize on the guard",
    ),
    # -- host-copy witness recorder (fbtpu-memscope ground truth) --
    GuardEntry(
        "fluentbit_tpu/core/copywitness.py", "_counts_guard",
        ("_counts",), kind="global",
        note="copy-witness accumulator: every ingest/replay thread "
             "records into it; snapshot/reset serialize on the guard",
    ),
    GuardEntry(
        "fluentbit_tpu/core/copywitness.py", "_counts_guard",
        ("_enabled",), writes_only=True, kind="global",
        note="witness enable flag: the ingest hot path reads it "
             "lock-free by design (one falsy load when disabled); the "
             "refresh() flip serializes",
    ),
    # -- native loaders: double-checked module singletons --
    GuardEntry(
        "fluentbit_tpu/codec/_native_codec.py", "_lock",
        ("_mod", "_tried"), writes_only=True, kind="global",
        note="codec loader: lock-free settled-state fast path is "
             "documented; the build/load transition must serialize",
    ),
    GuardEntry(
        "fluentbit_tpu/native/__init__.py", "_lock",
        ("_lib", "_tried"), writes_only=True, kind="global",
        note="data-plane loader: same double-checked pattern",
    ),
    # -- device attach controller --
    GuardEntry(
        "fluentbit_tpu/ops/device.py", "_lock",
        ("_state", "_error", "_attach_seconds", "_platform", "_thread",
         "_attempts", "_retry_history", "_next_retry_at", "_generation"),
        writes_only=True, kind="global",
        note="attach state machine (retry-world, fbtpu-armor): "
             "ready()/failed()/generation()/status() are lock-free "
             "probes by design; transitions and retry bookkeeping "
             "serialize",
    ),
    # -- fbtpu-armor device fault domain --
    GuardEntry(
        "fluentbit_tpu/ops/fault.py", "_lock",
        ("_stats", "_lost", "_ok_since_shrink", "_mesh", "_mesh_key"),
        writes_only=True,
        note="device-lane failover state: stats()/current_mesh() "
             "fast-path reads are benign-staleness probes; mutation "
             "(launch outcomes, shrink/regrow) serializes",
    ),
    GuardEntry(
        "fluentbit_tpu/ops/fault.py", "_registry_lock",
        ("_lanes",), kind="global",
        note="process-global lane registry: created from plugin init "
             "on any thread, read by health/bench snapshots",
    ),
    GuardEntry(
        "fluentbit_tpu/ops/fault.py", "_listener_lock",
        ("_listeners",), kind="global",
        note="fault event listener list: engines register/release on "
             "start/stop while lanes notify from worker threads",
    ),
    # -- fbtpu-relay: forward fan-in dedup ledger + partition spool --
    GuardEntry(
        "fluentbit_tpu/core/relay.py", "_lock",
        ("_seen", "dedup_hits"),
        note="dedup ledger map + hit counter: the server's event loop "
             "absorbs while health snapshots and the soak audit read; "
             "seen/record/GC must serialize (a torn check-then-record "
             "IS a double-absorb)",
    ),
    GuardEntry(
        "fluentbit_tpu/core/relay.py", "_lock", ("_seq",),
        note="spool sequence counter: concurrent degrades must never "
             "mint the same file name (replay order is the name order)",
    ),
    # -- analyzer caches (fbtpu-locksmith lockset scope) --
    GuardEntry(
        "fluentbit_tpu/analysis/speccheck.py", "_cache_lock",
        ("_programs_cache",), writes_only=True, kind="global",
        note="shipped-programs cache: double-checked build — the "
             "lock-free settled fast path is documented, the "
             "build/store transition must serialize (speccheck runs "
             "from tests and the CLI concurrently)",
    ),
)
