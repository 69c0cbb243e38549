"""filter_log_to_metrics — derive metrics (and sketches) from log records.

Reference: plugins/filter_log_to_metrics/log_to_metrics.c. Modes
counter/gauge/histogram (:566-612 bucket setup) with grep-style
pre-filter rules in LEGACY first-rule-decides semantics
(grep_filter_data, :345-372), labels from ``label_field`` record
accessors + static ``add_label`` pairs, optional ``kubernetes_mode``
auto-labels (namespace_name/pod_name/container_name/docker_id/pod_id,
:45-49), required ``tag`` (:726), namespace default "log_metric"
(log_to_metrics.h:54). Metrics are emitted as METRICS-type events
through a hidden emitter input (flb_input_metrics_append, :633) so they
flow the metrics pipeline to any metrics-capable output.

North-star additions (BASELINE.md config 4 — no reference equivalent):
``metric_mode cardinality`` maintains a device HyperLogLog over
``value_field`` and emits the cardinality estimate as a gauge;
``metric_mode frequency`` maintains a device count-min sketch and emits
per-value estimated counts for the hottest observed values. Sketch
updates run as fused jit kernels (hash + scatter) over staged batches
(fluentbit_tpu.ops.sketch); on a device mesh the sketch merge is
pmax/psum over ICI.
"""

from __future__ import annotations

import logging
from typing import Any, Dict, List, Optional

import numpy as np

from ..codec.chunk import EVENT_TYPE_METRICS
from ..codec.msgpack import packb
from ..core.config import ConfigMapEntry
from ..core.metrics import MetricsRegistry
from ..core.plugin import FilterPlugin, FilterResult, registry
from ..core.record_accessor import RecordAccessor
from ..core.spans import ShardedTimings, span
from .filter_grep import legacy_keep, parse_grep_rules

log = logging.getLogger("flb")

K8S_LABELS = ("namespace_name", "pod_name", "container_name",
              "docker_id", "pod_id")


def _stringify(v) -> str:
    if isinstance(v, bool):
        return "true" if v else "false"
    if isinstance(v, float) and v.is_integer():
        return str(int(v))
    return str(v)


def top_k(ests: np.ndarray, values: list, k: int) -> list:
    """``sorted(zip(ests, values), reverse=True)[:k]`` — the k highest
    (estimate, value) pairs, ties broken by the value's bytes, highest
    first — with Python ints, sorting only the pairs that can be among
    them: those whose estimate reaches the k-th highest."""
    n = len(values)
    if 0 < k < n:
        kth = ests[np.argpartition(ests, n - k)[n - k]]
        rows = np.flatnonzero(ests >= kth).tolist()
    else:
        rows = range(n)
    return sorted(((int(ests[i]), values[i]) for i in rows),
                  reverse=True)[:k]


@registry.register
class LogToMetricsFilter(FilterPlugin):
    name = "log_to_metrics"
    description = "generate metrics from log records"
    # process_batch bumps counters and emits snapshots: once it has
    # run, the engine must not restart the raw chain from scratch
    # (decoded-tail continuation instead — engine._ingest_raw)
    stateful_batch = True
    config_map = [
        ConfigMapEntry("regex", "slist", multiple=True, slist_max_split=1),
        ConfigMapEntry("exclude", "slist", multiple=True, slist_max_split=1),
        ConfigMapEntry("metric_mode", "str", default="counter"),
        ConfigMapEntry("value_field", "str"),
        ConfigMapEntry("metric_name", "str"),
        ConfigMapEntry("metric_namespace", "str", default="log_metric"),
        ConfigMapEntry("metric_subsystem", "str", default=""),
        ConfigMapEntry("metric_description", "str"),
        ConfigMapEntry("kubernetes_mode", "bool", default=False),
        ConfigMapEntry("add_label", "slist", multiple=True, slist_max_split=1),
        ConfigMapEntry("label_field", "str", multiple=True),
        ConfigMapEntry("bucket", "str", multiple=True),
        ConfigMapEntry("tag", "str"),
        ConfigMapEntry("emitter_name", "str"),
        ConfigMapEntry("emitter_mem_buf_limit", "str", default="10M"),
        ConfigMapEntry("discard_logs", "bool", default=False),
        ConfigMapEntry("flush_interval_sec", "int", default=0),
        ConfigMapEntry("flush_interval_nsec", "int", default=0),
        # sketch modes (north-star additions)
        ConfigMapEntry("sketch_precision", "int", default=14,
                       desc="HLL precision p (2^p registers)"),
        ConfigMapEntry("sketch_depth", "int", default=4),
        ConfigMapEntry("sketch_width", "int", default=16384),
        ConfigMapEntry("frequency_top_k", "int", default=10),
        ConfigMapEntry("tpu_max_record_len", "int", default=256),
    ]

    MODES = ("counter", "gauge", "histogram", "cardinality", "frequency")

    def init(self, instance, engine) -> None:
        if not self.metric_name:
            raise ValueError("log_to_metrics: metric_name is not set")
        if not self.metric_description:
            raise ValueError("log_to_metrics: metric_description is not set")
        if not self.tag:
            raise ValueError("log_to_metrics: Metric tag is not set")
        self.mode = (self.metric_mode or "counter").lower()
        if self.mode not in self.MODES:
            raise ValueError(f"log_to_metrics: unknown mode {self.metric_mode!r}")
        if self.mode in ("gauge", "histogram", "cardinality", "frequency") \
                and not self.value_field:
            raise ValueError(f"log_to_metrics: {self.mode} requires value_field")

        # grep-style pre-filter, property order preserved — shares
        # filter_grep's rule machinery (grep_filter_data is the same
        # legacy logic)
        self.rules = parse_grep_rules(instance.properties)

        # labels: [k8s...] + label_field RAs + add_label statics
        self.label_keys: List[str] = []
        self._label_ras: List[RecordAccessor] = []
        self._k8s_ra = RecordAccessor("$kubernetes") if self.kubernetes_mode else None
        if self.kubernetes_mode:
            self.label_keys.extend(K8S_LABELS)
        for lf in self.label_field or []:
            name = lf[1:] if lf.startswith("$") else lf
            self.label_keys.append(name.replace("['", "_").replace("']", "")
                                   .replace(".", "_"))
            self._label_ras.append(
                RecordAccessor(lf if lf.startswith("$") else "$" + lf)
            )
        self._static_labels: List[str] = []
        for pair in self.add_label or []:
            parts = pair if isinstance(pair, list) else pair.split(None, 1)
            if len(parts) != 2:
                raise ValueError(f"log_to_metrics: invalid add_label {pair!r}")
            self.label_keys.append(parts[0])
            self._static_labels.append(parts[1])

        self.value_ra = RecordAccessor(
            self.value_field if str(self.value_field or "").startswith("$")
            else "$" + (self.value_field or "value")
        ) if self.value_field else None

        # where an append's time goes, in seconds (engine thread only):
        # picking records and their values (per record, in Python),
        # staging the batch, the sketch/metric update, reading the
        # sketch back (a device→host copy and the candidate sort). Each
        # key is a counter because a per-layer metric of the benchmark
        # reads it over the whole window; what has no metric (the
        # snapshot's emit) is a span only. The frequency instance adds
        # its query_s to cms_query_s too (the count-min's part, apart
        # from the HLL's), with the candidates it estimated and how
        # many of them were hashed one at a time (query_scalar_values)
        self.raw_timings = ShardedTimings(
            ("select_s", "stage_s", "update_s", "query_s", "cms_query_s",
             "query_values", "query_scalar_values"))

        # the cmt context emitted through the pipeline
        self.cmt = MetricsRegistry()
        ns, sub = self.metric_namespace, self.metric_subsystem or ""
        keys = tuple(self.label_keys)
        if self.mode == "counter":
            self.metric = self.cmt.counter(ns, sub, self.metric_name,
                                           self.metric_description, keys)
        elif self.mode == "gauge":
            self.metric = self.cmt.gauge(ns, sub, self.metric_name,
                                         self.metric_description, keys)
        elif self.mode == "histogram":
            buckets = [float(b) for b in (self.bucket or [])] or None
            from ..core.metrics import DEFAULT_BUCKETS

            self.metric = self.cmt.histogram(
                ns, sub, self.metric_name, self.metric_description, keys,
                tuple(buckets) if buckets else DEFAULT_BUCKETS,
            )
        elif self.mode == "cardinality":
            self.metric = self.cmt.gauge(ns, sub, self.metric_name,
                                         self.metric_description, keys)
            from ..ops.sketch import HyperLogLog

            self.hll = HyperLogLog(p=self.sketch_precision)
        else:  # frequency
            self.metric = self.cmt.gauge(
                ns, sub, self.metric_name, self.metric_description,
                keys + ("value",),
            )
            from ..ops.sketch import CountMin

            self.cms = CountMin(depth=self.sketch_depth,
                                width=self.sketch_width)
            self._freq_candidates: Dict[bytes, None] = {}

        # batched raw path (process_batch): counter mode whose labels
        # are all static vectorizes as one native DFA pass over chunk
        # bytes + a single batched inc — no Python decode. The ≥1 keep
        # rule requirement makes non-map bodies consistently excluded
        # on both paths (they can never match, and the first Regex rule
        # then decides False — same verdict the dict-body check gives).
        self._batch_tables = None
        if (
            self.mode == "counter"
            and not self._label_ras
            and not self.kubernetes_mode
            and self.rules
            and any(not r.is_exclude for r in self.rules)
            and all(r.dfa is not None and not r.ra.parts
                    for r in self.rules)
        ):
            from .. import native as _native

            if _native.available():
                try:
                    self._batch_tables = _native.GrepTables(
                        [(r.ra.head.encode("utf-8"), r.dfa)
                         for r in self.rules]
                    )
                except Exception:
                    log.warning(
                        "log_to_metrics native table build failed; "
                        "batched fast path disabled", exc_info=True)
                    self._batch_tables = None
        self._report_shrink(engine)

        self.emitter = None
        self._dirty = False
        self._interval = 0.0
        if engine is not None:
            name = self.emitter_name or f"emitter_for_{instance.display_name}"
            ins = engine.hidden_input(
                "emitter", owner=instance, alias=name,
                mem_buf_limit=self.emitter_mem_buf_limit,
            )
            self.emitter = ins.plugin
            interval = self.flush_interval_sec + self.flush_interval_nsec / 1e9
            self._interval = interval
            if interval > 0:
                # timer-driven emission (the reference's flush timer):
                # piggyback an interval collector on the hidden emitter
                # so throttled updates are flushed even when no further
                # records arrive
                ins.plugin.collect_interval = interval
                ins.plugin.collect = (
                    lambda _engine: self._emit_snapshot() if self._dirty
                    else None
                )

    def _report_shrink(self, engine) -> None:
        """fluentbit_grep_shrink_* compile-outcome counters for the
        selector-rule DFAs — compiled through the same reducer as
        filter_grep's (FlbRegex → compile_dfa), so their savings land
        in the same dashboard family, labelled by plugin
        (DEVICE_PLANE.md "shrink"); table bytes are accounted in the
        fbtpu-xray budget report (ANALYSIS.md "fbtpu-xray")."""
        if engine is None or getattr(engine, "m_shrink_states", None) \
                is None:
            return
        label = (self.name,)
        elim_s = elim_c = 0
        for r in self.rules:
            st = getattr(r.dfa, "shrink", None) if r.dfa is not None \
                else None
            if st is not None:
                elim_s += st.states_eliminated
                elim_c += st.classes_eliminated
        if elim_s:
            engine.m_shrink_states.inc(elim_s, label)
        if elim_c:
            engine.m_shrink_classes.inc(elim_c, label)

    # -- per-record helpers --

    def _selected(self, body: dict) -> bool:
        """LEGACY grep logic: first rule decides (grep_filter_data)."""
        return legacy_keep(self.rules, body)

    def _labels(self, body: dict) -> tuple:
        out: List[str] = []
        if self._k8s_ra is not None:
            k8s = self._k8s_ra.get(body) or {}
            for key in K8S_LABELS:
                v = k8s.get(key) if isinstance(k8s, dict) else None
                out.append(_stringify(v) if v is not None else "")
        for ra in self._label_ras:
            v = ra.get(body)
            out.append(_stringify(v) if v is not None else "")
        out.extend(self._static_labels)
        return tuple(out)

    def _value(self, body: dict) -> Optional[float]:
        v = self.value_ra.get(body) if self.value_ra else None
        if isinstance(v, bool) or v is None:
            return None
        try:
            return float(v)
        except (TypeError, ValueError):
            return None

    def _value_bytes(self, body: dict) -> Optional[bytes]:
        v = self.value_ra.get(body) if self.value_ra else None
        if v is None:
            return None
        return _stringify(v).encode("utf-8") if not isinstance(v, str) \
            else v.encode("utf-8")

    # -- batched raw-chunk execution (engine process_batch hook) --

    def can_process_batch(self) -> bool:
        return self._batch_tables is not None

    def process_batch(self, chunk):
        from .. import native
        from .filter_grep import legacy_keep_mask

        tm = self.raw_timings
        data = chunk.as_bytes()
        with tm.timed("select_s", "l2m.select"):
            got = native.grep_match(data, self._batch_tables,
                                    n_hint=chunk.n)
            if got is None:
                return None
            mask, _offsets, n = got
            count = int(legacy_keep_mask(self.rules, mask).sum()) \
                if n else 0
        if count:
            # one batched inc == n per-record incs on the same (static)
            # label set; the snapshot emits once per append, exactly
            # like the per-record path
            self.metric.inc(count, tuple(self._static_labels))
            self._dirty = True
            if self.emitter is not None and self._interval <= 0:
                try:
                    self._emit_snapshot()
                except Exception:
                    # the inc above is already committed: a raise here
                    # would decline the batch and the decoded-tail
                    # rerun would inc AGAIN for the same records —
                    # degrade to a deferred snapshot (_dirty stays set)
                    # to keep counter effects exactly-once
                    # (fbtpu-lint batch-commit-replay)
                    log.exception(
                        "log_to_metrics snapshot emit failed; "
                        "snapshot deferred")
        if self.discard_logs:
            return (0, b"", n)
        return (n, data, n)

    # -- the filter --

    def filter(self, events: list, tag: str, engine) -> tuple:
        tm = self.raw_timings
        with tm.timed("select_s", "l2m.select"):
            selected = [
                ev for ev in events
                if isinstance(ev.body, dict) and self._selected(ev.body)
            ]
        if self.mode == "cardinality":
            self._update_hll(selected)
        elif self.mode == "frequency":
            self._update_cms(selected)
        else:
            with tm.timed("update_s", "l2m.update"):
                self._update_metric(selected)

        if selected:
            self._dirty = True
            # interval 0 (default): emit on every append; with an
            # interval configured, the emitter collector timer emits
            if self.emitter is not None and self._interval <= 0:
                self._emit_snapshot()
        if self.discard_logs:
            return (FilterResult.MODIFIED, [])
        return (FilterResult.NOTOUCH, events)

    def _update_metric(self, selected: list) -> None:
        if self.mode == "counter":
            for ev in selected:
                self.metric.inc(1, self._labels(ev.body))
        elif self.mode == "gauge":
            for ev in selected:
                v = self._value(ev.body)
                if v is not None:
                    self.metric.set(v, self._labels(ev.body))
        else:  # histogram
            for ev in selected:
                v = self._value(ev.body)
                if v is not None:
                    self.metric.observe(v, self._labels(ev.body))

    def _emit_snapshot(self) -> None:
        with span("l2m.emit"):
            payload = packb(self.cmt.to_msgpack_obj())
            self.emitter.add_event(
                self.tag, payload, EVENT_TYPE_METRICS,
                n_records=len(list(self.cmt.metrics())),
            )
            self._dirty = False

    # -- sketch modes --

    def _staged(self, values: List[Optional[bytes]]):
        from ..ops.batch import assemble, bucket_size

        return assemble(values, self.tpu_max_record_len,
                        bucket_size(len(values),
                                    max_len=self.tpu_max_record_len))

    def _values(self, selected: list) -> List[bytes]:
        with self.raw_timings.timed("select_s", "l2m.select"):
            vals = [self._value_bytes(ev.body) for ev in selected]
            return [v for v in vals if v is not None]

    def _update_hll(self, selected: list) -> None:
        tm = self.raw_timings
        vals = self._values(selected)
        if not vals:
            return
        with tm.timed("stage_s", "l2m.stage"):
            b = self._staged(vals)
        with tm.timed("update_s", "l2m.update"):
            self.hll.update(b.batch, b.lengths)
            for i in b.overflow:  # oversized values resolve on CPU
                self.hll.add_cpu(vals[i])
        with tm.timed("query_s", "l2m.query", mode="hll"):
            labels = self._labels(selected[0].body) \
                if self.label_keys else ()
            # the estimate reads the registers back: device→host copy
            self.metric.set(self.hll.estimate(), labels)

    def _update_cms(self, selected: list) -> None:
        tm = self.raw_timings
        vals = self._values(selected)
        if not vals:
            return
        with tm.timed("stage_s", "l2m.stage"):
            b = self._staged(vals)
        with tm.timed("update_s", "l2m.update"):
            self.cms.update(b.batch, b.lengths)
            for i in b.overflow:  # oversized values resolve on CPU
                self.cms.add_cpu(vals[i])
        for v in vals:
            # delete-and-reinsert refreshes recency (dict preserves
            # insertion order; plain reassignment would not move the key)
            self._freq_candidates.pop(v, None)
            self._freq_candidates[v] = None
        # bound candidate memory: keep most recently seen 4096 values
        if len(self._freq_candidates) > 4096:
            drop = len(self._freq_candidates) - 4096
            for k in list(self._freq_candidates)[:drop]:
                del self._freq_candidates[k]
        with tm.timed("query_s", "l2m.query", mode="cms",
                      values=len(self._freq_candidates)) as q:
            cands = list(self._freq_candidates)
            base = self._labels(selected[0].body) \
                if self.label_keys else ()
            # one device→host table copy for the whole candidate set
            ests, n_scalar = self.cms.query_array(cands)
            top = top_k(ests, cands, self.frequency_top_k)
            # the gauge reports the CURRENT top-k only: stale series
            # from values that dropped out must not linger in the
            # exposition
            self.metric.clear()
            for est, v in top:
                self.metric.set(
                    est, base + (v.decode("utf-8", "replace"),)
                )
        tm.add("cms_query_s", q.seconds)
        tm.add("query_values", len(cands))
        tm.add("query_scalar_values", n_scalar)
