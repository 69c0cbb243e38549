"""filter_flux — the stateful batched analytics processor.

The flux plane's ingest hook: one configured instance maintains one
:class:`~fluentbit_tpu.flux.state.FluxState` (per-tenant sketches +
window aggregates) and rides the PR-2 ``process_batch`` fast path —
per tagged append, the needed columns are extracted straight from chunk
bytes by the native stagers (``stage_field`` / ``stage_field_f64`` /
``stage_field_i64``) and absorbed in ONE batched commit; records pass
through untouched.  A GROUP BY field is typed row by row — a string or
an integer (FLUX.md "Typed group keys"); a chunk holding any other kind
of key declines before the commit to the per-record twin.  The
per-record ``filter()`` twin runs the identical math on decoded events,
so a decline anywhere on the raw chain stays bit-exact.

Batch-exactness contract (machine-checked, ``analysis.batch``): every
decline (``return None``) is dominated by ZERO committed effects — all
staging happens first, the single ``absorb_batch`` commit last — and
the class declares ``stateful_batch = True`` so a downstream decline
takes the decoded-tail continuation instead of replaying the absorb.

Two creation modes:

- **configured** (``[FILTER] Name flux``): spec comes from properties
  (group_by/distinct_field/aggregate_field/topk_field/window...),
  window rows optionally re-enter the pipeline through a hidden
  emitter under ``tag``, snapshots persist to ``snapshot_path``;
- **SQL-backed** (``flux.query.attach_flux``): a sketch-eligible
  stream-processor query pre-builds the state and installs a hidden
  instance of this filter on the query's tag route; emission then
  belongs to the SPTask and records appended by the SP's own emitter
  are skipped (the ``flb_sp_do`` self-feed guard).
"""

from __future__ import annotations

import logging
from typing import List, Optional

import numpy as np

from ..codec.events import encode_event, now_event_time
from ..core.config import ConfigMapEntry
from ..core.plugin import FilterPlugin, FilterResult, registry
from ..core.spans import ShardedTimings
from .exporter import FluxExporter
from .state import (KEY_NULL, KEY_OBJ, KEY_STR, TIMING_KEYS, FluxSpec,
                    FluxState, KeyCol, WindowSpec, render_key_part)

log = logging.getLogger("flb.flux")


@registry.register
class FluxFilter(FilterPlugin):
    name = "flux"
    description = "device-resident streaming analytics (sketches + windows)"
    # the batched hook commits sketch/window state: a downstream decline
    # must take the decoded-tail continuation, never a chain restart
    stateful_batch = True
    config_map = [
        ConfigMapEntry("group_by", "str", multiple=True,
                       desc="tenant/group label fields (strings or "
                            "integers, row by row)"),
        ConfigMapEntry("distinct_field", "str", multiple=True,
                       desc="HLL cardinality columns"),
        ConfigMapEntry("aggregate_field", "str", multiple=True,
                       desc="numeric count/sum/min/max/avg columns"),
        ConfigMapEntry("topk_field", "str",
                       desc="count-min hot-key column"),
        ConfigMapEntry("topk", "int", default=10),
        ConfigMapEntry("window", "str",
                       desc="'tumbling N' | 'hopping N M' | 'none'"),
        ConfigMapEntry("window_time", "str", default="processing",
                       desc="processing|event (event: tumbling only, "
                            "per-record path)"),
        ConfigMapEntry("tag", "str",
                       desc="emit closed-window rows under this tag"),
        ConfigMapEntry("emitter_name", "str"),
        ConfigMapEntry("emitter_mem_buf_limit", "str", default="10M"),
        ConfigMapEntry("sketch_precision", "int", default=12),
        ConfigMapEntry("sketch_depth", "int", default=4),
        ConfigMapEntry("sketch_width", "int", default=16384),
        ConfigMapEntry("max_field_len", "int", default=256),
        ConfigMapEntry("mesh", "bool", default=False,
                       desc="shard sketch updates across the device "
                            "mesh (simulated-mesh lane in tier-1)"),
        ConfigMapEntry("snapshot_path", "str"),
        ConfigMapEntry("snapshot_interval_sec", "int", default=0),
        ConfigMapEntry("export_interval_sec", "str", default="1"),
        ConfigMapEntry("tick_interval_sec", "str", default="0.5"),
    ]

    #: SQL mode: state pre-built by flux.query.attach_flux before init
    _preset_state: Optional[FluxState] = None
    _sql_mode: bool = False

    def init(self, instance, engine) -> None:
        self._engine = engine
        self._emitter = None
        self._emitter_ins = None
        self._last_snapshot = 0.0
        if self._preset_state is not None:
            self.state = self._preset_state
        else:
            window = WindowSpec.parse(self.window)
            self.state = FluxState(FluxSpec(
                name=instance.display_name,
                group_by=self.group_by or (),
                distinct=self.distinct_field or (),
                numeric=self.aggregate_field or (),
                topk_field=self.topk_field,
                topk=self.topk,
                window=window,
                hll_p=self.sketch_precision,
                cms_depth=self.sketch_depth,
                cms_width=self.sketch_width,
                max_len=self.max_field_len,
                event_time=(self.window_time or "").lower() == "event",
                mesh=self.mesh,
            ))
            if self.snapshot_path:
                self.state.load(self.snapshot_path)
        # absorbs and window closes, accounted (state.TIMING_KEYS; the
        # lane's stats hold the launch's own seconds)
        self.raw_timings = ShardedTimings(TIMING_KEYS)
        self.state.timings = self.raw_timings
        metrics = engine.metrics if engine is not None else None
        if metrics is None:
            from ..core.metrics import MetricsRegistry

            metrics = MetricsRegistry()
        self.exporter = FluxExporter(
            metrics, self.state,
            min_interval=float(self.export_interval_sec or 0)
            if self._preset_state is None else 1.0,
        )
        from .. import native as _native

        # probe the flux entry points ONCE: a stale prebuilt .so may
        # lack fbtpu_stage_field_f64, and discovering that per chunk
        # would stage every string column natively only to decline and
        # re-decode — permanent double work. Straight to the decoded
        # path instead.
        self._batch_ok = (
            _native.available() and not self.state.spec.event_time
            and (not (self.state.spec.numeric
                      or self.state.spec.group_by)
                 or _native.has_flux_stagers())
        )
        if self._preset_state is None and engine is not None \
                and (self.tag or self.state.spec.window.kind is not None
                     or self.state.spec.event_time
                     or (self.snapshot_path
                         and (self.snapshot_interval_sec or 0) > 0)):
            # the tick collector drives window close, gauge refresh AND
            # interval snapshots — an unwindowed state with
            # snapshot_interval_sec configured still needs the timer,
            # or the only persist would be exit() (and a crash is the
            # one scenario snapshots exist for)
            ename = self.emitter_name or \
                f"emitter_for_{instance.display_name}"
            ins = engine.hidden_input(
                "emitter", owner=instance, alias=ename,
                mem_buf_limit=self.emitter_mem_buf_limit,
            )
            self._emitter = ins.plugin
            self._emitter_ins = ins
            ins.plugin.collect_interval = float(
                self.tick_interval_sec or 0.5)
            ins.plugin.collect = self._on_tick

    # ------------------------------------------------------------- ticks

    def _on_tick(self, engine) -> None:
        """Window timer (rides the hidden emitter's collector, like the
        SP window tick): close expired windows, emit rows, refresh
        gauges, persist the snapshot.  The snapshot dict is built under
        the ingest lock (read-only copy) but pickled/fsynced OUTSIDE
        it — disk latency must not stall ingestion."""
        lock = getattr(engine, "_ingest_lock", None) \
            if engine is not None else None
        if lock is None:
            snap = self._tick_locked()
        else:
            with lock:
                snap = self._tick_locked()
        if snap is not None:
            import time as _time

            try:
                self.state.write_snapshot(snap, self.snapshot_path)
                self._last_snapshot = _time.time()
            except OSError:
                log.warning("flux snapshot persist failed; state stays "
                            "in memory", exc_info=True)

    def _tick_locked(self):
        """→ snapshot dict to write after the lock is released, or
        None."""
        def hand_on(groups) -> int:
            if self.tag and self._emitter is not None:
                return self._emit_rows(groups, "window")
            return 0

        closed = self.state.close_window(None, hand_on)
        self.exporter.refresh(force=closed)
        if not self.snapshot_path:
            return None
        import time as _time

        due = (self.snapshot_interval_sec or 0) > 0 and \
            _time.time() - self._last_snapshot >= self.snapshot_interval_sec
        if not closed and not due:
            return None
        return self.state.snapshot()

    def _emit_rows(self, closed, what: str) -> int:
        """The closed groups as rows under ``tag``, all of one emission
        under one record time → the number of rows."""
        rows = self._render_rows(closed)
        ts = now_event_time()
        buf = bytearray()
        for r in rows:
            buf += encode_event(r, ts)
        try:
            self._emitter.add_record(self.tag, bytes(buf), len(rows))
        except Exception:
            log.exception("flux %s emit failed; rows dropped "
                          "(state already rolled over)", what)
        return len(rows)

    def _render_rows(self, closed) -> List[dict]:
        spec = self.state.spec
        rows: List[dict] = []
        for key, g in closed:
            row: dict = {"flux": spec.name}
            for fname, part in zip(spec.group_by, key):
                row[fname] = render_key_part(part)
            row["count"] = g.count
            for f in spec.numeric:
                st = g.cols[f]
                row[f + "_sum"] = st.sum if st.has else 0.0
                row[f + "_min"] = st.min_value()
                row[f + "_max"] = st.max_value()
                row[f + "_avg"] = (st.sum / g.count) if g.count else 0.0
            for f in spec.distinct:
                row[f + "_distinct"] = int(round(g.hlls[f].estimate()))
            if spec.topk_field:
                row["topk"] = [
                    {"value": v.decode("utf-8", "replace"),
                     "estimate": est}
                    for est, v in self.state.topk(key)
                ]
            rows.append(row)
        return rows

    # ---------------------------------------------------- batched path

    def _skip_sources(self) -> list:
        out = []
        if self._sql_mode and self._engine is not None \
                and self._engine.sp is not None \
                and self._engine.sp.emitter_instance is not None:
            out.append(self._engine.sp.emitter_instance)
        if self._emitter_ins is not None:
            out.append(self._emitter_ins)
        return out

    def can_process_batch(self) -> bool:
        return self._batch_ok

    def process_batch(self, chunk):
        from .. import native

        data = chunk.as_bytes()
        skip = self._skip_sources()
        if chunk.src is not None and any(chunk.src is s for s in skip):
            n = chunk.n
            if n is None:
                n = native.count_records(data)
                if n is None:
                    return None
            return (n, data, n)
        spec = self.state.spec
        sfields = spec.string_fields
        strcols = {}
        n = chunk.n
        if not sfields and not spec.numeric:
            n = native.count_records(data) if n is None else n
            if n is None:
                return None
        if sfields and n is None:
            n = native.count_records(data)
            if n is None:
                return None
        for f in sfields:
            # stage straight into caller-owned column buffers: no
            # arena round-trip, so multi-column specs keep every
            # column live without the copy-out of all but the last
            b = np.empty((n, spec.max_len), dtype=np.uint8)
            ln = np.full((n,), -1, dtype=np.int32)
            n2 = native.stage_field_into(data, f.encode("utf-8"),
                                         b, ln, n_hint=n)
            if n2 is None or n2 != n:
                return None
            strcols[f] = (b, ln)
        keycols = {}
        for f in spec.group_by:
            kc = self._stage_key(native, data, f, n, *strcols[f])
            if kc is None:
                return None
            keycols[f] = kc
        numcols = {}
        for f in spec.numeric:
            got = native.stage_field_f64(data, f.encode("utf-8"),
                                         n_hint=n)
            if got is None:
                return None
            vals, kinds, n2 = got
            if n is not None and n2 != n:
                return None
            n = n2
            numcols[f] = (vals, kinds)
        # ---- the single commit: nothing below declines ----
        self.state.absorb_batch(n, strcols, numcols, keycols)
        try:
            # a raise past the commit would be an implicit decline and
            # the decoded-tail rerun would absorb the chunk AGAIN —
            # the same batch-commit-replay class the analyzer polices
            self.exporter.refresh(force=False)
        except Exception:
            log.exception("flux metrics refresh failed; export deferred")
        return (n, data, n)

    @staticmethod
    def _stage_key(native, data, field: str, n: int, b, ln):
        """One GROUP BY field of a staged chunk as a :class:`KeyCol`,
        or None: the chunk declines. The string stager has run (``b``,
        ``ln``); a column it filled is a column of strings. Rows it
        left are missing, integers, or something else: the typed
        stager tells them apart, int64 holds every integer it accepts,
        and a row of any other kind — a float, a bool, a uint64 past
        2^63-1, a nested value — declines, for the per-record twin
        keys those as the exact path does (FLUX.md "Typed group
        keys"). An oversize string (``ln`` -2) is missing, as ever."""
        if not (ln == -1).any():
            return KeyCol.of_strings(b, ln)
        got = native.stage_field_i64(data, field.encode("utf-8"),
                                     n_hint=n)
        if got is None or got[2] != n:
            return None
        ints, kinds, _n = got
        if (kinds == KEY_OBJ).any():
            return None
        is_str = ln >= 0
        # (a string the string stager left is an oversize one: missing)
        kinds[kinds == KEY_STR] = KEY_NULL
        kinds[is_str] = KEY_STR
        if not is_str.any():
            return KeyCol(kinds, ints)
        return KeyCol(kinds, ints, b, ln)

    # ------------------------------------------------- per-record twin

    def filter(self, events: list, tag: str, engine) -> tuple:
        src = getattr(engine, "_ingest_src", None) \
            if engine is not None else None
        if src is not None and any(src is s for s in
                                   self._skip_sources()):
            return (FilterResult.NOTOUCH, events)
        self.state.absorb_events(events)
        try:
            self.exporter.refresh(force=False)
        except Exception:
            log.exception("flux metrics refresh failed; export deferred")
        return (FilterResult.NOTOUCH, events)

    def exit(self) -> None:
        # drain semantics belong to the owner: SQL mode drains through
        # SPTask.drain; configured mode emits what the open window holds
        if self._preset_state is None and self.tag \
                and self._emitter is not None:
            closed = self.state.drain()
            if closed:
                self._emit_rows(closed, "drain")
        if self.snapshot_path:
            try:
                self.state.persist(self.snapshot_path)
            except OSError:
                log.warning("flux exit snapshot failed", exc_info=True)
