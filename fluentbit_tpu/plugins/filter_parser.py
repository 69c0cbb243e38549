"""filter_parser — apply a named parser to a record field.

Reference: plugins/filter_parser/filter_parser.c. For each record, look
up ``key_name`` (or a record-accessor path when it starts with ``$``,
:122-126), run the configured parsers in order on its string value
(:268-303); on first success the parsed map replaces the body,
``reserve_data`` appends the other original fields (:237),
``preserve_key`` keeps the parsed source key (:238-240); a parsed
non-zero time overrides the record timestamp; on failure the record
passes through untouched. With an RA path, the reference keeps ALL
original fields under reserve_data (the matched kv is not identified in
that branch) — mirrored here.

Divergence note: the reference appends reserved originals after the
parsed fields in the msgpack map, allowing duplicate keys (first wins on
record-accessor lookups). Python dicts cannot hold duplicates, so on key
collision the parsed value wins — the same value a reference RA lookup
would return.

Device path: with a single DFA-expressible regex parser and a large
append, the match decision runs vectorized on device
(fluentbit_tpu.ops.grep) and capture extraction runs only for matching
records (match-then-extract two-pass).

Batched fast path (``process_batch``): on the engine's raw ingest path
whole chunks bypass per-record Python entirely —

- json parser (plain key, defaults): the fbtpu_codec C extension
  transcodes each record's JSON field straight to msgpack
  (``parser_json_batch``), byte-exact with json.loads → pack_event;
- regex parser: the native one-pass DFA (fluentbit_tpu.native) computes
  the match mask off chunk bytes and capture extraction runs only for
  matching records.

Exotic options (reserve_data, preserve_key, time_format, record-
accessor keys, multiple parsers, types) decline to the per-record path
— identical output either way, just slower.
"""

from __future__ import annotations

import logging
from typing import List, Optional

from .. import failpoints as _fp
from ..codec.events import LogEvent
from ..core.config import ConfigMapEntry
from ..core.plugin import FilterPlugin, FilterResult, registry
from ..core.record_accessor import RecordAccessor


log = logging.getLogger("flb")


def _to_str(v) -> Optional[str]:
    if isinstance(v, str):
        return v
    if isinstance(v, bytes):
        return v.decode("utf-8", "replace")
    return None  # msgpackobj2char: only string/bin values are parseable


@registry.register
class ParserFilter(FilterPlugin):
    name = "parser"
    description = "parse a field with a named parser"
    # the batched path is pure (parsers immutable after init, no
    # cross-record state): chains of these may ingest in parallel
    # under per-input locks
    thread_safe_raw = True
    config_map = [
        ConfigMapEntry("key_name", "str", desc="field to parse"),
        ConfigMapEntry("parser", "str", multiple=True,
                       desc="parser name (may repeat; tried in order)"),
        ConfigMapEntry("reserve_data", "bool", default=False,
                       desc="keep the other original fields"),
        ConfigMapEntry("preserve_key", "bool", default=False,
                       desc="keep the parsed source key"),
        ConfigMapEntry("tpu.enable", "bool", default=True,
                       desc="device match prefilter when the parser allows"),
        ConfigMapEntry("tpu_batch_records", "int", default=64),
        ConfigMapEntry("tpu_max_record_len", "int", default=512),
    ]

    def init(self, instance, engine) -> None:
        if not self.key_name:
            raise ValueError("parser filter requires Key_Name")
        if not self.parser:
            raise ValueError("parser filter requires at least one Parser")
        self.parsers = []
        for pname in self.parser:
            p = (engine.parsers if engine is not None else {}).get(pname)
            if p is None:
                raise ValueError(f"parser filter: unknown parser {pname!r}")
            self.parsers.append(p)
        self.ra: Optional[RecordAccessor] = None
        if self.key_name.startswith("$"):
            self.ra = RecordAccessor(self.key_name)
        # device prefilter: single regex parser with a compiled DFA
        self._prefilter = None
        if (
            self.tpu_enable
            and len(self.parsers) == 1
            and self.parsers[0].fmt == "regex"
            and self.parsers[0].regex.dfa is not None
        ):
            try:
                from ..ops import device
                from ..ops.grep import program_for

                self._prefilter = program_for(
                    (self.parsers[0].regex.pattern,), self.tpu_max_record_len
                )
                device.wait()  # bounded; CPU path serves until attached
                self._prefilter.try_ready()
            except Exception:
                log.debug("parser device prefilter unavailable; "
                          "host path serves", exc_info=True)
                self._prefilter = None

        # batched raw-path mode (process_batch): "json" = whole-chunk C
        # transcode, "regex" = native DFA mask + captures for matches
        # only. Option combinations outside these shapes keep the
        # per-record path (bit-exact, just slower).
        self._batch_mode = None
        self._batch_key = None
        self._batch_tables = None
        p0 = self.parsers[0]
        if self.ra is None and len(self.parsers) == 1 and self.key_name:
            key = self.key_name.encode("utf-8")
            if (
                p0.fmt == "json"
                and p0.time_format is None
                and not self.reserve_data
                and not self.preserve_key
            ):
                from ..codec import _native_codec

                mod = _native_codec.load()
                if mod is not None and hasattr(mod, "parser_json_batch"):
                    self._batch_mode = "json"
                    self._batch_key = key
            elif p0.fmt == "regex" and p0.regex.dfa is not None:
                from .. import native as _native

                if _native.available():
                    try:
                        self._batch_tables = _native.GrepTables(
                            [(key, p0.regex.dfa)])
                        self._batch_mode = "regex"
                        self._batch_key = key
                    except Exception:
                        log.warning(
                            "parser native table build failed; batched "
                            "regex fast path disabled", exc_info=True)
                        self._batch_tables = None

    # -- per-record semantics --

    def _get_value(self, body: dict) -> Optional[str]:
        if self.ra is not None:
            return _to_str(self.ra.get(body))
        v = body.get(self.key_name) if isinstance(body, dict) else None
        return _to_str(v)

    def _apply(self, ev: LogEvent, value: str) -> Optional[LogEvent]:
        """Try the parsers in order; build the replacement event."""
        for p in self.parsers:
            got = p.do(value)
            if got is None:
                continue
            fields, ts = got
            body = dict(fields)
            if self.reserve_data:
                for k, v in ev.body.items():
                    if (
                        self.ra is None
                        and k == self.key_name
                        and not self.preserve_key
                    ):
                        continue
                    body.setdefault(k, v)
            elif self.preserve_key and self.ra is None:
                body.setdefault(self.key_name, ev.body.get(self.key_name))
            new_ts = ev.timestamp if (ts is None or ts == 0) else ts
            return LogEvent(
                timestamp=new_ts, body=body, metadata=ev.metadata, raw=None
            )
        return None

    def _device_match_mask(self, values: List[Optional[str]]):
        """Vectorized match prefilter; None → row handled on CPU."""
        import numpy as np

        from ..ops.batch import assemble, bucket_size

        vals = [
            v.encode("utf-8") if isinstance(v, str) else None for v in values
        ]
        staged = assemble(
            vals, self.tpu_max_record_len,
            bucket_size(len(vals), max_len=self.tpu_max_record_len))
        batch = np.stack([staged.batch])
        lengths = np.stack([staged.lengths])
        mask = np.array(self._prefilter.match(batch, lengths)[0, : len(vals)])
        rx = self.parsers[0].regex
        for i in staged.overflow:
            mask[i] = rx.match(vals[i])
        return mask

    # -- batched raw-chunk execution (engine process_batch hook) --

    def can_process_batch(self) -> bool:
        return self._batch_mode is not None

    def process_batch(self, chunk):
        if self._batch_mode == "json":
            return self._process_batch_json(chunk)
        return self._process_batch_regex(chunk)

    def _process_batch_json(self, chunk):
        """Whole-chunk JSON→msgpack transcode in C — byte-exact with
        json.loads → dict → pack_event per record (differentially
        fuzzed; tests/test_batch_filters.py). FallbackError means some
        record is outside the fast set (legacy framing, bin values,
        bigints, invalid UTF-8): decline and let the per-record path
        produce the identical-or-defined behavior."""
        from ..codec import _native_codec

        if _fp.ACTIVE:
            try:
                _fp.fire("codec.fallback")
            except _fp.FailpointError:
                # forced decline: the per-record path takes over — the
                # contract says output stays bit-exact and the decline
                # shows in fluentbit_filter_batch_declines_total
                return None
        mod = _native_codec.load()
        if mod is None:
            return None
        data = chunk.as_bytes()
        try:
            out, n, parsed = mod.parser_json_batch(data, self._batch_key)
        except mod.FallbackError:
            return None
        if parsed == 0:
            return (n, data, n)  # nothing parseable: zero-copy
        return (n, out, n)

    def _process_batch_regex(self, chunk):
        """Native one-pass DFA mask over chunk bytes; the regex (with
        captures) runs only for records the mask admits — mask-false
        records skip the Python regex entirely (the DFA is the
        bit-exact twin of the fallback engine, same contract as
        filter_grep's raw path)."""
        from .. import native
        from ..codec.events import decode_events, reencode_event

        data = chunk.as_bytes()
        got = native.grep_match(data, self._batch_tables, n_hint=chunk.n)
        if got is None:
            return None
        mask, _offsets, n = got
        row = mask[0]
        try:
            events = decode_events(data)
        except ValueError:
            return None
        if len(events) != n:
            return None  # native/codec walk disagreement: decline
        out = bytearray()
        modified = False
        for i, ev in enumerate(events):
            v = None
            body = ev.body
            if isinstance(body, dict):
                raw_v = body.get(self.key_name)
                if isinstance(raw_v, bytes):
                    # bytes values never stage into the native mask —
                    # they decode (errors="replace") and always parse
                    v = raw_v.decode("utf-8", "replace")
                elif isinstance(raw_v, str) and row[i]:
                    v = raw_v
            new_ev = self._apply(ev, v) if v is not None else None
            if new_ev is None:
                out += ev.raw if ev.raw is not None \
                    else reencode_event(ev)
            else:
                out += reencode_event(new_ev)
                modified = True
        if not modified:
            return (n, data, n)
        return (n, bytes(out), n)

    def filter(self, events: list, tag: str, engine) -> tuple:
        values = [
            self._get_value(ev.body) if isinstance(ev.body, dict) else None
            for ev in events
        ]
        from ..ops import device

        mask = None
        # platform gate first (as in filter_grep/rewrite_tag): the
        # prefilter kernel only pays for itself on a real accelerator
        if (self._prefilter is not None
                and len(events) >= self.tpu_batch_records
                and device.platform() not in (None, "cpu")
                and self._prefilter.try_ready()):
            mask = self._device_match_mask(values)
        out: List[LogEvent] = []
        modified = False
        for i, ev in enumerate(events):
            v = values[i]
            if v is None or (mask is not None and not mask[i]):
                out.append(ev)
                continue
            new_ev = self._apply(ev, v)
            if new_ev is None:
                out.append(ev)
            else:
                out.append(new_ev)
                modified = True
        if not modified:
            return (FilterResult.NOTOUCH, events)
        return (FilterResult.MODIFIED, out)
