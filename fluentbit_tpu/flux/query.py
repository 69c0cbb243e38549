"""Sketch-eligibility for stream-processor SQL — compile a query onto
flux state.

A query is **sketch-eligible** when its aggregation can be maintained
incrementally by the flux plane at ingest rate (FLUX.md has the full
rule table):

- ``CREATE STREAM ... AS SELECT`` over ``TAG:'pattern'`` (snapshots and
  stream-to-stream sources stay on the exact path),
- a ``WINDOW TUMBLING/HOPPING`` clause with aggregates,
- no ``WHERE`` (predicate pushdown to the DFA plane is future work),
- aggregate functions within {COUNT, COUNT(DISTINCT k), SUM, MIN, MAX,
  AVG} — ``TIMESERIES_FORECAST`` needs the raw series,
- not opted out per query via ``WITH (flux='off')``.

Eligible queries get a :class:`FluxBinding`: a hidden ``flux`` filter
instance on the query's tag route updates device-resident state inside
the filter pass (batched, no Python decode), and the SPTask becomes a
reader — its window tick renders rows straight from flux state in the
exact shape ``SPTask._rows_of`` would have produced.  Exact aggregates
(COUNT/SUM/MIN/MAX/AVG) are bit-identical to the Python evaluation
path; COUNT(DISTINCT) returns the HLL estimate within the documented
error bound.  Ineligible queries are untouched — the existing exact
path IS the fallback.
"""

from __future__ import annotations

import logging
import os
from typing import List, Optional

from .state import FluxSpec, FluxState, WindowSpec, render_key_part

log = logging.getLogger("flb.flux")

__all__ = ["FluxBinding", "eligible", "attach_flux"]

#: aggregate functions the flux plane can maintain incrementally
_FLUX_FUNCS = {"count", "count_distinct", "sum", "min", "max", "avg"}


def eligible(query) -> bool:
    """Pure shape check (no side effects) — see module docstring."""
    if query.kind != "stream" or query.source_type != "tag":
        return False
    if query.where is not None or query.window is None:
        return False
    if not query.has_aggregates:
        return False
    if str(query.props.get("flux", "")).lower() in ("off", "false", "0"):
        return False
    for k in query.keys:
        if k.func is None:
            continue
        if k.func not in _FLUX_FUNCS:
            return False
        if k.func in ("sum", "min", "max", "avg", "count_distinct"):
            if k.name is None:
                return False
            if "." in k.name:
                # dotted names resolve through NESTED maps on the exact
                # path (_get_key splits on '.'); the flux stagers only
                # see literal top-level keys — silently-wrong results,
                # so nested accessors stay on the exact path
                # (ROADMAP item 3 follow-up)
                return False
    if any("." in g for g in query.group_by):
        return False
    return True


def _build_spec(query, mesh: bool) -> FluxSpec:
    distinct: List[str] = []
    numeric: List[str] = []
    for k in query.keys:
        if k.func == "count_distinct" and k.name not in distinct:
            distinct.append(k.name)
        elif k.func in ("sum", "min", "max", "avg") \
                and k.name not in numeric:
            numeric.append(k.name)
    kind, size, advance = query.window
    p = int(query.props.get("flux_precision", 12) or 12)
    return FluxSpec(
        name=query.stream_name or "sp",
        group_by=query.group_by,
        distinct=distinct,
        numeric=numeric,
        window=WindowSpec(kind, size, advance),
        hll_p=p,
        max_len=int(query.props.get("flux_max_len", 256) or 256),
        mesh=mesh,
    )


class FluxBinding:
    """One flux-backed SPTask's read side: renders window rows from
    flux state in the exact ``SPTask._rows_of`` shape."""

    def __init__(self, query, state: FluxState):
        self.query = query
        self.state = state

    def _rows(self, closed) -> List[dict]:
        q = self.query
        rows: List[dict] = []
        for key, g in closed:
            row: dict = {}
            for gname, part in zip(q.group_by, key):
                row[gname] = render_key_part(part)
            for k in q.keys:
                if k.func:
                    row[k.out_name] = self._agg_result(g, k)
                elif k.name is not None:
                    row.setdefault(k.out_name, None)
            rows.append(row)
        return rows

    @staticmethod
    def _agg_result(g, k):
        if k.func == "count":
            return g.count
        if k.func == "count_distinct":
            return int(round(g.hlls[k.name].estimate()))
        st = g.cols[k.name]
        if k.func == "sum":
            return st.sum if st.has else 0.0
        if k.func == "avg":
            return ((st.sum if st.has else 0.0) / g.count
                    if g.count else 0.0)
        if k.func == "min":
            return st.min_value()
        if k.func == "max":
            return st.max_value()
        return None

    def close(self, now: float, emit) -> bool:
        """The task's window tick: where a window closes, its rows to
        ``emit(rows)``, the whole close accounted
        (``FluxState.close_window``). → whether one closed."""
        def hand_on(closed) -> int:
            rows = self._rows(closed)
            emit(rows)
            return len(rows)

        return self.state.close_window(now, hand_on)

    def drain(self, emit) -> None:
        """Shutdown: what the ring and the open pane hold, to
        ``emit(rows)``."""
        rows = self._rows(self.state.drain())
        if rows:
            emit(rows)
            if self.state.timings is not None:
                self.state.timings.add("emitted_rows", len(rows))


def sql_mesh_enabled() -> bool:
    """SQL-backed states shard across the mesh when the lane is opted
    in (FBTPU_FLUX_MESH=1; the per-shape jit compiles are not free on
    the 8-virtual-device CPU mesh, so it is explicit)."""
    return os.environ.get("FBTPU_FLUX_MESH", "") in ("1", "on", "true")


def attach_flux(engine, task) -> bool:
    """Bind a sketch-eligible SPTask to flux state: build the state,
    install the hidden flux filter on the query's tag route, and flip
    the task into reader mode.  False = not eligible (exact path)."""
    query = task.query
    if not eligible(query):
        return False
    state = FluxState(_build_spec(query, mesh=sql_mesh_enabled()))
    # align the window clock with the task's (differential harnesses
    # fake both through the same callable)
    state._now = task._now
    state._window_start = task._window_start
    ins = engine.registry.create_filter("flux")
    engine._number_instance(ins, engine.filters)
    ins.set("match", query.source)
    ins.set("alias", f"flux_sql_{query.stream_name or 'sp'}")
    ins.plugin._preset_state = state
    ins.plugin._sql_mode = True
    # keeps the hidden filter pinned to the chain TAIL (the SP's
    # post-filter position) even when user filters register later —
    # Engine.filter() inserts new filters before flagged instances
    ins._flux_sql_hidden = True
    ins.configure()
    ins.plugin.init(ins, engine)
    ins._initialized = True
    # COW swap: ingest iterates engine.filters lock-free — publish a
    # fresh list instead of mutating the shared alias
    with engine._ingest_lock:
        engine.filters = engine.filters + [ins]
    task.flux = FluxBinding(query, state)
    log.info("stream task %s resolved against flux state (%s)",
             query.stream_name or query.source,
             "mesh" if state.spec.mesh else "single")
    return True
