"""fluentbit_flux_* metrics exporter — flux state → core.metrics.

Publishes the live flux plane into a :class:`MetricsRegistry` (the
engine's, normally — surfaces through /api/v1/metrics/prometheus and
the metrics pipeline like every other ``fluentbit_*`` family):

- ``fluentbit_flux_records_total{name}``        absorbed records
- ``fluentbit_flux_batches_total{name}``        absorbed chunks/appends
- ``fluentbit_flux_late_records_total{name}``   event-time late drops
- ``fluentbit_flux_window_emits_total{name}``   closed-window emissions
- ``fluentbit_flux_window_closes_total{name}``  window boundaries passed
  (a close whose window holds no group emits nothing and still counts)
- ``fluentbit_flux_groups{name}``               open-pane group count
- ``fluentbit_flux_cardinality{name,group,field}``  HLL estimates
- ``fluentbit_flux_topk_estimate{name,group,value}`` CMS hot keys

Gauge families are refreshed wholesale (``clear()`` + set) so groups
that age out of the window do not linger in the exposition — the same
stale-series rule filter_log_to_metrics' frequency mode follows.
"""

from __future__ import annotations

from typing import Optional

from ..core.metrics import MetricsRegistry
from .state import FluxState, render_key_part

__all__ = ["FluxExporter"]


def _group_label(key: tuple) -> str:
    """Unambiguous label for a (possibly multi-field) group key:
    distinct keys must render distinct labels or two groups' series
    silently overwrite each other on refresh — so '/' inside a part is
    escaped and a missing (None) part renders differently from an
    empty string. An integer part renders as its digits (the one pair
    that shares a label is an integer and its own decimal string)."""
    if not key:
        return ""
    return "/".join(
        "\\N" if part is None
        else str(render_key_part(part))
        .replace("\\", "\\\\").replace("/", "\\/")
        for part in key
    )


class FluxExporter:
    """One state's exporter; ``refresh()`` is cheap enough to run per
    window close and is additionally rate-limited for per-absorb calls
    (``min_interval`` seconds, 0 = always)."""

    def __init__(self, metrics: MetricsRegistry, state: FluxState,
                 min_interval: float = 0.0, now=None):
        import time as _time

        self.state = state
        self.name = state.spec.name
        self.min_interval = float(min_interval)
        self._now = now or _time.time
        self._last = 0.0
        m = metrics
        self.m_records = m.counter(
            "fluentbit", "flux", "records_total",
            "Records absorbed by the flux plane", ("name",))
        self.m_batches = m.counter(
            "fluentbit", "flux", "batches_total",
            "Chunks absorbed by the flux plane", ("name",))
        self.m_late = m.counter(
            "fluentbit", "flux", "late_records_total",
            "Event-time records behind the watermark", ("name",))
        self.m_emits = m.counter(
            "fluentbit", "flux", "window_emits_total",
            "Closed-window emissions", ("name",))
        self.m_closes = m.counter(
            "fluentbit", "flux", "window_closes_total",
            "Window boundaries passed", ("name",))
        self.m_groups = m.gauge(
            "fluentbit", "flux", "groups",
            "Open-pane group count", ("name",))
        self.m_cardinality = m.gauge(
            "fluentbit", "flux", "cardinality",
            "HLL distinct-value estimates", ("name", "group", "field"))
        self.m_topk = m.gauge(
            "fluentbit", "flux", "topk_estimate",
            "Count-min hot-key estimates", ("name", "group", "value"))
        # counters export deltas; these remember what was already added
        self._c_records = 0
        self._c_batches = 0
        self._c_late = 0
        self._c_emits = 0
        self._c_closes = 0

    def refresh(self, force: bool = True) -> bool:
        """Publish the current state; ``force=False`` applies the
        rate limit (the per-absorb call site)."""
        now = self._now()
        if not force and self.min_interval > 0 \
                and now - self._last < self.min_interval:
            return False
        self._last = now
        st = self.state
        self._bump(self.m_records, "_c_records", st.records_total)
        self._bump(self.m_batches, "_c_batches", st.batches_total)
        self._bump(self.m_late, "_c_late", st.late_records_total)
        self._bump(self.m_emits, "_c_emits", st.window_emits_total)
        self._bump(self.m_closes, "_c_closes", st.window_closes_total)
        groups = st.live_groups()
        self.m_groups.set(float(len(groups)), (self.name,))
        # wholesale refresh of THIS state's series only: stale groups
        # must drop out of exposition, sibling exporters' series must
        # not (the families are shared engine-registry metrics)
        self.m_cardinality.remove_matching("name", self.name)
        self.m_topk.remove_matching("name", self.name)
        # (a count-only state has no series a group: no walk of what
        # may be thousands of groups under the ingest lock)
        for key, g in groups if st.spec.distinct else ():
            label = _group_label(key)
            for field, hll in g.hlls.items():
                self.m_cardinality.set(
                    hll.estimate(), (self.name, label, field))
        if st.cms is not None:
            # exposition covers LIVE groups only (same rule as the
            # cardinality family): refresh runs under the engine ingest
            # lock, and walking every state-lifetime candidate group
            # (up to _MAX_CANDIDATE_GROUPS × ~80 CMS point queries)
            # would stall ingestion — historical groups stay queryable
            # through FluxState.topk, they just leave the exposition
            # when they leave the window
            for key, _g in groups:
                label = _group_label(key)
                for est, value in st.topk(key):
                    self.m_topk.set(
                        float(est),
                        (self.name, label,
                         value.decode("utf-8", "replace")))
        return True

    def _bump(self, counter, attr: str, total: int) -> None:
        prev: int = getattr(self, attr)
        if total > prev:
            counter.inc(total - prev, (self.name,))
            setattr(self, attr, total)
