"""filter_rewrite_tag — re-tag records by regex rule and re-emit.

Reference: plugins/filter_rewrite_tag/rewrite_tag.c. Rules are
``Rule <$key> <regex> <new_tag_template> <keep>``; the FIRST matching
rule wins (process_record, :356-385); the new tag is composed by the
record-accessor template with access to regex captures ($0..$9), $TAG,
$TAG[n] and record fields (:393); the record is re-emitted under the new
tag through a per-instance hidden ``emitter`` input (:407, created with
alias ``emitter_for_<name>``, :245-260) and re-enters the full pipeline;
the original is kept or dropped per the rule's keep flag (:375).

Device path: when every rule regex compiles to a DFA and the append is
large, the rules run vectorized on device (fluentbit_tpu.ops.grep);
capture extraction + tag composition run on the CPU only for the first
matching rule of each matched record.

Batched fast path (``process_batch``): on the engine's raw ingest path
the verdict — the first matching rule of each record — comes straight
off chunk bytes, no Python decode at all: from the device once a
non-CPU backend is attached (``filter_grep.staged_match``: each
distinct key staged once, launched through the ``grep`` DeviceLane, the
first-match reduction on the device, overflow rows and the lane's
fallback decided by the host rule walk), from the native one-pass DFA
while the device attaches and on a CPU backend. Records whose winning rule
has a tag-static template (no ``$0..$9`` captures, no record fields)
group into per-tag span gathers (native compact) and re-emit in one
emitter append per tag; only records whose template needs captures or
record fields decode individually. The own-emitter re-entry guard uses
the chunk's source input, so re-emitted records pass through untouched
at chunk granularity.
"""

from __future__ import annotations

import logging
import time
from typing import List, Optional

import numpy as np

from ..codec.events import reencode_event
from ..core.config import ConfigMapEntry, parse_bool
from ..core.plugin import FilterPlugin, FilterResult, registry
from ..core.record_accessor import RecordAccessor, Template
from ..core.spans import ShardedTimings, span
from ..regex import FlbRegex
from .filter_grep import (decoded_match, first_of_mask, plane_index,
                          staged_match)


log = logging.getLogger("flb")


def _to_text(v) -> Optional[str]:
    """String values only — flb_ra_key_regex_match returns no-match for
    non-STR msgpack types (src/flb_ra_key.c:418)."""
    if isinstance(v, str):
        return v
    return None


#: ``raw_timings`` keys of the batched path. ``extract_s``,
#: ``kernel_s``, ``h2d_bytes``, ``d2h_bytes``, ``scan_elements``,
#: ``device_records``, ``overflow_rows``, ``split_launches`` and
#: ``long_rows`` are the staged launch's (``filter_grep.staged_match``: the device
#: lane only); ``records`` counts every record ``process_batch``
#: served; ``emit_s`` is the time from the verdict to the last emitter
#: append (grouping, ``native.compact``, ``add_record`` and the
#: pipeline re-entry under it), ``emits`` the emitter appends and
#: ``emit_backpressure`` those the emitter refused (originals kept)
_TIMING_KEYS = ("extract_s", "kernel_s", "h2d_bytes", "d2h_bytes",
                "scan_elements", "device_records", "overflow_rows",
                "split_launches", "long_rows",
                "records", "emit_s", "emits", "emit_backpressure")


class RewriteRule:
    __slots__ = ("ra", "regex", "template", "keep")

    def __init__(self, key: str, pattern: str, new_tag: str, keep):
        self.ra = RecordAccessor(key)
        self.regex = FlbRegex(pattern)
        self.template = Template(new_tag)
        self.keep = parse_bool(keep)


@registry.register
class RewriteTagFilter(FilterPlugin):
    name = "rewrite_tag"
    description = "re-tag records by regex and re-emit through the pipeline"
    # process_batch re-emits through the hidden emitter: once it has
    # run, the engine must not restart the raw chain from scratch
    # (decoded-tail continuation instead — engine._ingest_raw)
    stateful_batch = True
    config_map = [
        ConfigMapEntry("rule", "slist", multiple=True, slist_max_split=3,
                       desc="<$key> <regex> <new_tag> <keep>"),
        ConfigMapEntry("emitter_name", "str"),
        ConfigMapEntry("emitter_storage.type", "str", default="memory"),
        ConfigMapEntry("emitter_mem_buf_limit", "str", default="10M"),
        ConfigMapEntry("tpu.enable", "bool", default=True),
        ConfigMapEntry("tpu_batch_records", "int", default=64),
        ConfigMapEntry("tpu_max_record_len", "int", default=512),
    ]

    def init(self, instance, engine) -> None:
        if not self.rule:
            raise ValueError("rewrite_tag requires at least one Rule")
        self.rules: List[RewriteRule] = []
        for parts in self.rule:
            if len(parts) != 4:
                raise ValueError(f"rewrite_tag: invalid rule {parts!r}")
            self.rules.append(RewriteRule(*parts))
        self._engine = engine
        self.emitter = None
        if engine is not None:
            name = self.emitter_name or f"emitter_for_{instance.display_name}"
            ins = engine.hidden_input(
                "emitter",
                owner=instance,
                alias=name,
                mem_buf_limit=self.emitter_mem_buf_limit,
                **{"storage.type": self.emitter_storage_type},
            )
            self.emitter = ins.plugin
        self._program = None
        if (
            self.tpu_enable
            and all(r.regex.dfa is not None for r in self.rules)
        ):
            try:
                from ..ops import device
                from ..ops.grep import program_for

                self._program = program_for(
                    tuple(r.regex.pattern for r in self.rules),
                    self.tpu_max_record_len,
                    plane_of=plane_index(self.rules)[1])
                device.wait()  # bounded; CPU path serves until attached
                self._program.try_ready()
            except Exception:
                log.debug("rewrite_tag device program unavailable; "
                          "host path serves", exc_info=True)
                self._program = None
        # batched raw path: native per-rule DFA matrix off chunk bytes
        # (simple top-level keys only); rules with tag-static templates
        # render once per chunk, the rest decode per matched record
        self._batch_tables = None
        self.raw_timings = ShardedTimings(_TIMING_KEYS)
        self._batch_static = [r.template.static_for_tag
                              for r in self.rules]
        if self.emitter is not None and all(
            r.regex.dfa is not None and not r.ra.parts
            for r in self.rules
        ):
            from .. import native as _native

            if _native.available():
                try:
                    self._batch_tables = _native.GrepTables(
                        [(r.ra.head.encode("utf-8"), r.regex.dfa)
                         for r in self.rules]
                    )
                except Exception:
                    log.warning(
                        "rewrite_tag native table build failed; "
                        "batched fast path disabled", exc_info=True)
                    self._batch_tables = None
        self._report_shrink(engine)

    def _report_shrink(self, engine) -> None:
        """fluentbit_grep_shrink_* compile-outcome counters for the
        rewrite_tag match matrices — the rule DFAs compile through the
        same reducer as filter_grep's (FlbRegex → compile_dfa), so
        their savings land in the same dashboard family, labelled by
        plugin (DEVICE_PLANE.md "shrink"); the table-bytes side is accounted in
        the fbtpu-xray budget report (ANALYSIS.md "fbtpu-xray")."""
        if engine is None or getattr(engine, "m_shrink_states", None) \
                is None:
            return
        label = (self.name,)
        elim_s = elim_c = 0
        for r in self.rules:
            dfa = r.regex.dfa
            st = getattr(dfa, "shrink", None) if dfa is not None \
                else None
            if st is not None:
                elim_s += st.states_eliminated
                elim_c += st.classes_eliminated
        if elim_s:
            engine.m_shrink_states.inc(elim_s, label)
        if elim_c:
            engine.m_shrink_classes.inc(elim_c, label)

    # -- matching --

    def _lane(self):
        """The DFA plane's fault domain: every launch of this plugin
        goes through the process-global "grep" DeviceLane."""
        from ..ops import fault

        return fault.lane("grep")

    def _device_serves(self) -> bool:
        """The platform gate (same as filter_grep's): a non-CPU backend
        is attached and the program is on it. On a jax CPU backend the
        staging + launch costs far more than the host scan it
        replaces."""
        from ..ops import device

        return (self._program is not None
                and device.platform() not in (None, "cpu")
                and self._program.try_ready())

    def _first_match_cpu(self, body):
        """Per-record rule scan, break on first match (process_record)."""
        if not isinstance(body, dict):
            return None, None
        for rule in self.rules:
            v = _to_text(rule.ra.get(body))
            if v is None:
                continue
            caps = rule.regex.search_captures(v)
            if caps is not None:
                return rule, caps
        return None, None

    def _render_tag(self, ev, rule, captures, tag: str):
        """→ rendered new tag, or None when the record cannot be
        re-emitted (failed translation / no emitter) — the caller then
        keeps the original, mirroring the reference's no-match return on
        translation failure."""
        if self.emitter is None:
            return None
        new_tag = rule.template.render(record=ev.body, tag=tag,
                                       captures=captures)
        return new_tag or None

    # -- batched raw-chunk execution (engine process_batch hook) --

    def can_process_batch(self) -> bool:
        return self._batch_tables is not None

    def _staged(self, data, n_records, **halves):
        """The first-match verdict from the shared staged launch, or
        one of its two halves."""
        return staged_match(
            self.rules, self._program, self._lane(), self.raw_timings,
            data, n_records, max_len=self.tpu_max_record_len,
            min_records=self.tpu_batch_records, first_match=True,
            **halves)

    def begin_batch(self, data: bytes, n_records):
        """``process_batch``'s launch begun ahead of the chunk's turn
        (``FilterPlugin.begin_batch``): no emit, no counter."""
        if not self._device_serves():
            return None
        return self._staged(data, n_records, begin=True)

    def process_batch(self, chunk):
        from .. import native
        from ..codec.events import decode_events, fast_count_records

        # own-emitter re-entry passes through untouched at chunk
        # granularity (the i_ins == ctx->ins_emitter recursion guard)
        if chunk.src is not None and chunk.src is self.emitter.instance:
            n = chunk.n
            if n is None:
                n = fast_count_records(chunk.as_bytes())
                if n is None:
                    return None
            return (n, chunk.data, n)
        tag = chunk.tag
        data = chunk.as_bytes()
        tm = self.raw_timings
        # first matching rule per record (process_record's break), or -1
        with span("rewrite.stage"):
            got = None
            if self._device_serves():
                got = self._staged(data, chunk.n,
                                   begun=chunk.take_begun())
            if got is None:
                # the host twin: while the device attaches, on a CPU
                # backend, and for what the staged launch declines
                got = native.grep_match(data, self._batch_tables,
                                        n_hint=chunk.n)
                if got is None:
                    return None
                got = (first_of_mask(got[0]), got[1], got[2])
        first, offsets, n = got
        tm.add("records", n)
        if n == 0:
            return (0, data, 0)
        if not (first >= 0).any():
            return (n, data, n)
        t0 = time.perf_counter()
        try:
            return self._emit_batch(chunk, data, first,
                                    offsets[: n + 1], n)
        finally:
            tm.add("emit_s", time.perf_counter() - t0)

    def _emit_batch(self, chunk, data, first, offsets, n):
        """Group the matched records by rendered tag in first-seen
        order, gather each group's spans, re-emit it in one emitter
        append, drop what ``keep false`` says — → ``process_batch``'s
        result."""
        from .. import native
        from ..codec.events import decode_events

        tag = chunk.tag
        tm = self.raw_timings
        keep = np.ones(n, dtype=bool)
        # new_tag → {"mask": members, "drop": non-keep members,
        #            "first": first contributing record index}
        # — groups re-emit in first-seen order, matching the per-record
        # path's pending-dict insertion order
        groups: dict = {}

        def group(new_tag, b):
            ent = groups.get(new_tag)
            if ent is None:
                ent = groups[new_tag] = {
                    "mask": np.zeros(n, dtype=bool),
                    "drop": np.zeros(n, dtype=bool),
                    "first": b,
                }
            ent["first"] = min(ent["first"], b)
            return ent

        need_record: list = []
        for r, rule in enumerate(self.rules):
            idx = np.nonzero(first == r)[0]
            if len(idx) == 0:
                continue
            if not self._batch_static[r]:
                need_record.extend(int(b) for b in idx)
                continue
            new_tag = rule.template.render(tag=tag)
            if not new_tag:
                continue  # untranslatable tag: keep the original
            ent = group(new_tag, int(idx[0]))
            ent["mask"][idx] = True
            if not rule.keep:
                ent["drop"][idx] = True
        # records whose winning rule needs captures or record fields:
        # decode just those spans and run the per-record rule walk
        for b in need_record:
            rec = bytes(data[offsets[b]: offsets[b + 1]])
            try:
                ev = decode_events(rec)[0]
            except (ValueError, IndexError):
                return None
            rule = captures = None
            # the verdict names the first rule whose DFA accepts: the
            # walk starts there (a capture the regex then refuses
            # moves on, as the per-record path does)
            for rl in self.rules[int(first[b]):]:
                v = _to_text(rl.ra.get(ev.body)) \
                    if isinstance(ev.body, dict) else None
                if v is None:
                    continue
                captures = rl.regex.search_captures(v)
                if captures is not None:
                    rule = rl
                    break
            if rule is None:
                continue
            new_tag = self._render_tag(ev, rule, captures, tag)
            if new_tag is None:
                continue
            ent = group(new_tag, b)
            ent["mask"][b] = True
            if not rule.keep:
                ent["drop"][b] = True
        emitted = 0
        for new_tag, ent in sorted(groups.items(),
                                   key=lambda kv: kv[1]["first"]):
            m = ent["mask"]
            count = int(m.sum())
            with span("rewrite.emit", tag=new_tag, rows=count):
                payload = native.compact(data, offsets, m)
                if payload is None:
                    payload = b"".join(
                        data[offsets[i]: offsets[i + 1]]
                        for i in np.nonzero(m)[0]
                    )
                try:
                    rc = self.emitter.add_record(new_tag, payload, count)
                except Exception:
                    # earlier groups are already committed: letting
                    # this raise would decline the batch and the
                    # decoded-tail rerun would re-emit them a second
                    # time — degrade a failed group to the backpressure
                    # outcome instead (originals kept; fbtpu-lint
                    # batch-commit-replay)
                    log.exception("rewrite_tag emitter append failed "
                                  "for tag %r; originals kept", new_tag)
                    rc = -1
            tm.add("emits", 1)
            if rc < 0:
                # backpressure: keep the originals (reference keeps the
                # record when in_emitter refuses it) — drop flags for
                # this group are simply never applied
                tm.add("emit_backpressure", 1)
                continue
            emitted += count
            keep &= ~ent["drop"]
        if emitted and chunk.engine is not None:
            chunk.engine.m_filter_emit.inc(
                emitted, (self.instance.display_name,))
        n_keep = int(keep.sum())
        if n_keep == n:
            return (n, data, n)
        if n_keep == 0:
            return (0, b"", n)
        out = native.compact(data, offsets, keep)
        if out is None:
            out = b"".join(
                data[offsets[i]: offsets[i + 1]]
                for i in np.nonzero(keep)[0]
            )
        return (n_keep, out, n)

    def filter(self, events: list, tag: str, engine) -> tuple:
        # records re-entering from our OWN emitter are never re-matched
        # (the i_ins == ctx->ins_emitter check, rewrite_tag.c): without
        # it a rule whose rewritten record still matches — e.g. the new
        # tag also satisfies `match *` — recurses until the stack dies
        if (
            engine is not None
            and self.emitter is not None
            and getattr(engine, "_ingest_src", None)
            is self.emitter.instance
        ):
            return (FilterResult.NOTOUCH, events)
        # platform gate FIRST (same as filter_grep): on a CPU jax
        # backend the batch assemble + kernel launch per chunk costs
        # far more than the host regex scan it replaces
        use_device = (len(events) >= self.tpu_batch_records
                      and self._device_serves())
        if use_device:
            mask = decoded_match(self.rules, self._program, self._lane(),
                                 events, self.tpu_max_record_len)
        keep = [True] * len(events)
        # emits BATCH per rendered tag: one emitter append per (tag)
        # group instead of one full pipeline re-entry per record
        # (in_emitter_add_record per record measured ~80µs — the append
        # overhead, not the matching, dominated)
        pending: dict = {}  # new_tag → [(index, raw)]
        for b, ev in enumerate(events):
            if use_device:
                rule = captures = None
                for r in np.nonzero(mask[:, b])[0]:
                    captures = self.rules[r].regex.search_captures(
                        _to_text(self.rules[r].ra.get(ev.body)))
                    if captures is not None:
                        rule = self.rules[r]
                        break
            else:
                rule, captures = self._first_match_cpu(ev.body)
            if rule is None:
                continue
            new_tag = self._render_tag(ev, rule, captures, tag)
            if new_tag is None:
                continue
            raw = ev.raw if ev.raw is not None else reencode_event(ev)
            pending.setdefault(new_tag, []).append((b, raw))
            if not rule.keep:
                keep[b] = False
        emitted = 0
        for new_tag, items in pending.items():
            data = b"".join(raw for _, raw in items)
            if self.emitter.add_record(new_tag, data, len(items)) < 0:
                # backpressure: keep the originals (reference keeps the
                # record when in_emitter refuses it)
                for b, _ in items:
                    keep[b] = True
            else:
                emitted += len(items)
        if emitted and engine is not None:
            engine.m_filter_emit.inc(emitted,
                                     (self.instance.display_name,))
        if all(keep):
            return (FilterResult.NOTOUCH, events)
        kept = [ev for b, ev in enumerate(events) if keep[b]]
        return (FilterResult.MODIFIED, kept)
