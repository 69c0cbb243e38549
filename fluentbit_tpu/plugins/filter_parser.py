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

Batched path (``process_batch``, the engine's raw hook), for one parser
on a plain top-level key — byte-equal to the per-record chain:

- **json** (no ``Time_Format``, no ``Reserve_Data`` / ``Preserve_Key``):
  the fbtpu_codec C extension transcodes each record's JSON field
  straight to msgpack (``parser_json_batch``), whole chunk, no device
  program; anything it cannot serve declines to the per-record path.
- **regex, on the chip**: once a non-CPU backend is attached and the
  regex lies inside the class ``regex.spans`` is exact in, the chunk
  goes through ``filter_grep.staged_match(..., spans=True)`` — the key
  staged once a segment, the span program (``ops.grep.SpanProgram``)
  launched through the ``grep`` DeviceLane on one device, the named
  groups' offsets copied back — and records are built from the spans
  without ``re``, in C with the GIL released (fbtpu_codec
  ``parser_spans_build``, one call a chunk): fields cut from the staged
  value with ``Parser.do_fields``' semantics (``Skip_Empty_Values``, zero
  fields = failure, ``Types`` integer, the ``Time_Key`` lookup over
  ``%d %m %b %Y %H %M %S %z %T`` and literals, and its drop) and
  ``Reserve_Data`` / ``Preserve_Key`` as the per-record path has them; an
  unmatched row's bytes pass through untouched, a chunk with no match
  returns its buffer. A row C cannot prove — an integer capture of
  another shape than ``[+-]digits``, a time that does not parse, a record
  of another framing or body — is a leftover, built per row in Python
  (``_span_event``, ``do_fields`` itself) and spliced in at its place. A
  parser outside that description (a ``float``/``bool``/``hex`` type, a
  Time_Format directive outside the set or with no year) or an extension
  without the function takes the Python build whole; ``decision()`` says
  which. Rows the program cannot decide go to the host per row and are
  counted (``host_rows``): a value longer than ``tpu_max_record_len``,
  a missing key or a ``bin`` value, and a value with a byte past ASCII
  (Python ``re`` reads characters where the automaton reads bytes, so
  byte and character spans could differ). Outside the class (a
  nullable loop body, a named group under a repetition, a possessive
  quantifier, ``\\Z``, a back-reference …) the program is not built;
  the reason is logged and shows in ``decision()``.
- **regex, on the host** (while the device attaches, on a CPU backend,
  under ``tpu_batch_records`` records, outside the class): the native
  one-pass DFA computes the match mask off chunk bytes and Python
  ``re`` extracts the captures of matching records. ``Reserve_Data``,
  ``Preserve_Key``, ``Types`` and ``Time_Format`` are served here too.

Record-accessor keys and several parsers keep the per-record path
(``filter``), where a single DFA-expressible regex parser on a large
append still takes its match mask from the device
(``_device_match_mask``) and extracts captures for matching records
only.
"""

from __future__ import annotations

import logging
from typing import List, Optional

import numpy as np

from .. import failpoints as _fp
from ..codec.events import LogEvent, decode_events, reencode_event
from ..codec.msgpack import EventTime
from ..core.config import ConfigMapEntry
from ..core.plugin import FilterPlugin, FilterResult, registry
from ..core.record_accessor import RecordAccessor
from ..core.spans import ShardedTimings, span


log = logging.getLogger("flb")

#: ``raw_timings`` keys of the batched regex path on the chip. The first
#: seven are the staged launch's (``filter_grep.staged_match``);
#: ``build_s`` is the time from the spans to the chunk's new bytes,
#: ``parsed`` the records it replaced, ``host_rows`` the rows decided on
#: the host per row (overflow rows, a missing key or a ``bin`` value, a
#: byte past ASCII), ``native_rows`` the records of ``parsed`` that C
#: built (``parser_spans_build``)
_TIMING_KEYS = ("extract_s", "kernel_s", "h2d_bytes", "d2h_bytes",
                "scan_elements", "device_records", "overflow_rows",
                "build_s", "parsed", "host_rows", "native_rows")

#: ``[[EventTime, {}], {`` — the head of an event whose time is the
#: Forward protocol's ext and whose metadata is empty, before the body
#: map's own header
_EVENT_HEAD = b"\x92\x92\xd7\x00"

#: the Time_Format directives ``parser_spans_build`` reads, as its ops:
#: flb_strptime's day, month, month name, year, hour, minute, second,
#: zone, and %T = %H:%M:%S (``L`` + a byte is a literal)
_NATIVE_DIRECTIVES = {"d": b"d", "m": b"m", "b": b"b", "B": b"b",
                      "h": b"b", "Y": b"Y", "H": b"H", "M": b"M",
                      "S": b"S", "z": b"z", "T": b"HL:ML:S"}


def _time_ops(fmt: str):
    """``Time_Format`` → the ops ``parser_spans_build`` walks (``W`` a
    run of white space, ``L`` + a literal byte, a directive's letter),
    or a str: why C does not serve it."""
    if not any(x in fmt for x in ("%Y", "%y", "%s", "%D", "%x", "%C")):
        return "Time_Format has no year (time_lookup prepends this one)"
    ops = bytearray()
    f = 0
    while f < len(fmt):
        c = fmt[f]
        f += 1
        if c.isspace():
            ops += b"W"
        elif c != "%":
            if not c.isascii():
                return f"Time_Format literal {c!r} is not ASCII"
            ops += b"L" + c.encode()
        elif fmt[f:f + 1] in _NATIVE_DIRECTIVES:
            ops += _NATIVE_DIRECTIVES[fmt[f]]
            f += 1
        else:
            return (f"Time_Format directive %{fmt[f:f + 1]} is outside "
                    f"the C build's set")
    return bytes(ops)


class _KeyRule:
    """What ``staged_match`` reads of a rule: the key and the regex."""

    __slots__ = ("ra", "regex")

    def __init__(self, key: str, regex):
        self.ra = RecordAccessor(key)
        self.regex = regex


def _row_value(res, i: int) -> bytes:
    """Row ``i``'s staged bytes, from the plane that holds it."""
    ln = int(res.lengths[i])
    for plane in res.planes:
        if i < len(plane):
            return plane[i, :ln].tobytes()
        i -= len(plane)
    raise IndexError("row past the staged planes")


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
        # transcode, "regex" = spans from the device where the regex is
        # inside the span program's class, else the native DFA mask +
        # captures for matches only. Record-accessor keys and several
        # parsers keep the per-record path (bit-exact, just slower).
        self._batch_mode = None
        self._batch_key = None
        self._batch_tables = None
        self._spans = None
        self._span_rules = None  # the one rule, as staged_match's list
        self._span_decline: Optional[str] = None
        self._native_desc = None  # parser_spans_build's description
        self._native_decline: Optional[str] = None
        self.raw_timings = ShardedTimings(_TIMING_KEYS)
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
                if self._batch_mode == "regex" and self.tpu_enable:
                    self._init_spans(p0)

    def _init_spans(self, p0) -> None:
        """Build the span program where the batched regex path will
        launch it; a pattern outside its class declines with the
        reason, and the host path serves as before."""
        from ..regex import UnsupportedRegex
        from ..regex.spans import SpanDecline

        try:
            from ..codec.msgpack import packb
            from ..ops import device
            from ..ops.grep import span_program_for

            p0.regex._py()  # the host twin has to compile too
            self._spans = span_program_for(p0.regex.pattern,
                                           self.tpu_max_record_len)
            self._span_rules = [_KeyRule(self.key_name, p0.regex)]
            # the body {key: <the value>} and nothing else, from its
            # map header on
            self._single_pair = b"\x81" + packb(self.key_name)
            self._native_desc, self._native_decline = \
                self._native_build_desc(p0)
            device.wait()  # bounded; the host path serves until attached
            self._spans.try_ready()
        except (SpanDecline, UnsupportedRegex) as e:
            self._span_decline = str(e)
            log.info("parser %s: no span program, the host path serves: "
                     "%s", p0.name, e)
        except Exception:
            self._spans = None
            self._span_decline = "the span program could not be built"
            log.debug("parser span program unavailable; host path "
                      "serves", exc_info=True)

    def _native_build_desc(self, p0):
        """The parser as ``parser_spans_build`` reads it → (description,
        None), or (None, why the Python build serves)."""
        from ..codec import _native_codec
        from ..parsers import TYPE_CASTERS

        mod = _native_codec.load()
        if mod is None or not hasattr(mod, "parser_spans_build"):
            return None, "the codec extension has no parser_spans_build"
        names = self._spans.names
        as_int, as_str = TYPE_CASTERS["integer"], TYPE_CASTERS["string"]
        for k, caster in p0.types.items():
            if caster is not as_int and caster is not as_str:
                return None, f"Types {k}: C builds integer and string only"
        ops, time_group = b"", -1
        if p0.time_format and p0.time_key in names:
            if p0.time_key in p0.types:
                return None, f"Types casts the Time_Key {p0.time_key}"
            ops = _time_ops(p0.time_format)
            if isinstance(ops, str):
                return None, ops
            time_group = names.index(p0.time_key)
        return (tuple(nm.encode("utf-8") for nm in names),
                bytes(p0.types.get(nm) is as_int for nm in names),
                time_group, p0.time_keep, ops, p0.time_offset,
                p0.skip_empty_values, self.reserve_data or self.preserve_key,
                self.preserve_key, self._single_pair,
                names.index(self.key_name) if self.key_name in names
                else -1), None

    def decision(self) -> dict:
        """What the batched path will do, and why not more."""
        return {
            "batch_mode": self._batch_mode,
            "spans": None if self._spans is None
            else self._spans.decision(),
            "span_decline": self._span_decline,
            "build": "native" if self._native_desc is not None
            else "python",
            "build_decline": self._native_decline,
        }

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
            if got is not None:
                return self._replace(ev.timestamp, ev.metadata, ev.body,
                                     *got)
        return None

    def _replace(self, timestamp, metadata, orig: dict, fields: dict,
                 ts) -> LogEvent:
        """The event a successful parse leaves: the parsed fields, the
        originals ``Reserve_Data`` / ``Preserve_Key`` keep, the parsed
        time where there is one."""
        body = dict(fields)
        if self.reserve_data:
            for k, v in orig.items():
                if (
                    self.ra is None
                    and k == self.key_name
                    and not self.preserve_key
                ):
                    continue
                body.setdefault(k, v)
        elif self.preserve_key and self.ra is None:
            body.setdefault(self.key_name, orig.get(self.key_name))
        new_ts = timestamp if (ts is None or ts == 0) else ts
        return LogEvent(
            timestamp=new_ts, body=body, metadata=metadata, raw=None
        )

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

    def _span_serves(self) -> bool:
        """The platform gate (filter_grep's and rewrite_tag's): the
        span program is built, a non-CPU backend is attached and the
        program is on it."""
        from ..ops import device

        return (self._spans is not None
                and device.platform() not in (None, "cpu")
                and self._spans.try_ready())

    def _lane(self):
        """The DFA plane's fault domain: the span launch goes through
        the process-global "grep" DeviceLane, as every launch of
        ``staged_match`` does."""
        from ..ops import fault

        return fault.lane("grep")

    def _staged(self, data, n_records, **halves):
        """The span verdict from the shared staged launch, or one of
        its two halves."""
        from .filter_grep import staged_match

        return staged_match(
            self._span_rules, self._spans, self._lane(),
            self.raw_timings, data, n_records,
            max_len=self.tpu_max_record_len,
            min_records=self.tpu_batch_records, spans=True, **halves)

    def begin_batch(self, data: bytes, n_records):
        """The regex mode's span launch begun ahead of the chunk's
        turn (``FilterPlugin.begin_batch``)."""
        if not self._span_serves():  # (no span program but in regex mode)
            return None
        return self._staged(data, n_records, begin=True)

    def _process_batch_regex(self, chunk):
        """Spans from the device where it serves, records built from
        them; else the host path below. → ``(n, bytes, n)``."""
        data = chunk.as_bytes()
        if self._span_serves():
            with span("parser.stage"):
                got = self._staged(data, chunk.n,
                                   begun=chunk.take_begun())
            if got is not None:
                return self._build_from_spans(data, *got)
        return self._process_batch_host(chunk, data)

    def _build_from_spans(self, data, res, offsets, n):
        """The chunk's new bytes from the spans: one C call where the
        parser's description allows it, its leftovers spliced in from
        the Python build; else the Python build row by row."""
        tm = self.raw_timings
        with tm.timed("build_s", "parser.build", rows=n,
                      parsed=int(res.ok.sum())):
            try:
                got = None
                if self._native_desc is not None:
                    got = self._build_native(data, res, offsets)
                if got is None:
                    got = self._build_python(data, res, offsets, n)
            except (ValueError, IndexError):
                return None  # a record that does not decode: decline
            host_rows, native_rows, parsed, out = got
            tm.add("host_rows", host_rows)
            tm.add("native_rows", native_rows)
            tm.add("parsed", parsed)
            if not parsed:
                return (n, data, n)  # nothing parsed: zero-copy
            return (n, out, n)

    def _build_native(self, data, res, offsets):
        """``parser_spans_build`` over the chunk, then each leftover row
        from ``_span_event`` at its place → (host_rows, native_rows,
        parsed, bytes); None where C hands the chunk back."""
        from ..codec import _native_codec

        mod = _native_codec.load()
        try:
            out, left, native_rows, host_rows = mod.parser_spans_build(
                data, np.ascontiguousarray(offsets, dtype=np.int64),
                [np.ascontiguousarray(p) for p in res.planes],
                np.ascontiguousarray(res.lengths, dtype=np.int32),
                np.ascontiguousarray(res.ok, dtype=bool),
                np.ascontiguousarray(res.spans, dtype=np.int32),
                *self._native_desc)
        except mod.FallbackError:
            return None
        parsed = native_rows
        if left:
            pieces, at, mv = [], 0, memoryview(out)
            for i, pos, host in left:
                rec = data[offsets[i]: offsets[i + 1]]
                new_ev = self._span_event(
                    rec, None if host else _row_value(res, i),
                    res.spans[i].tolist())
                pieces += (mv[at:pos],
                           rec if new_ev is None else reencode_event(new_ev))
                parsed += new_ev is not None
                at = pos
            pieces.append(mv[at:])
            out = b"".join(pieces)
        return host_rows, native_rows, parsed, out

    def _build_python(self, data, res, offsets, n):
        """The build row by row: ``_span_event`` for every matched or
        host row, the others' bytes passing through in runs →
        (host_rows, 0, parsed, bytes)."""
        host = res.lengths < 0
        rows: list = []  # each row's staged bytes
        at = 0
        for plane in res.planes:
            cnt, L = plane.shape
            ln = res.lengths[at: at + cnt]
            high = (plane >= 0x80) & (
                np.arange(L, dtype=np.int32)[None, :] < ln[:, None])
            host[at: at + cnt] |= high.any(axis=1)
            buf = plane.tobytes()
            rows.extend(buf[i * L: i * L + max(int(ln[i]), 0)]
                        for i in range(cnt))
            at += cnt
        parts: list = []
        kept_from = 0  # records [kept_from, i) pass through as one run
        parsed = 0
        todo = np.nonzero(res.ok | host)[0]
        for i, sp in zip(todo.tolist(), res.spans[todo].tolist()):
            rec = data[offsets[i]: offsets[i + 1]]
            new_ev = self._span_event(rec, None if host[i] else rows[i], sp)
            if new_ev is None:
                continue
            if kept_from < i:
                parts.append(data[offsets[kept_from]: offsets[i]])
            parts.append(reencode_event(new_ev))
            kept_from = i + 1
            parsed += 1
        if parts and kept_from < n:
            parts.append(data[offsets[kept_from]: offsets[n]])
        return int(host.sum()), 0, parsed, b"".join(parts)

    def _span_event(self, rec, value, sp):
        """One row's new event, or None where its bytes pass through: a
        device row's fields are cut from its staged ``value`` and go
        through ``Parser.do_fields``; a host row (``value`` None: no
        staged value, or a byte past ASCII) is decoded and parsed on
        the host."""
        if value is None:
            ev = decode_events(rec)[0]
            v = self._get_value(ev.body)
            return self._apply(ev, v) if v is not None else None
        got = self.parsers[0].do_fields({
            name: value[s:e].decode("ascii")
            for name, (s, e) in zip(self._spans.names, sp) if s >= 0})
        if got is None:
            return None
        need_orig = self.reserve_data or self.preserve_key
        if rec.startswith(_EVENT_HEAD) and rec[12] == 0x80 and (
                not need_orig or rec.startswith(self._single_pair, 13)):
            # [[EventTime, {}], body], and where the originals count
            # the body is {key: value}: nothing to decode
            return self._replace(
                EventTime.from_bytes(rec[4:12]), {},
                {self.key_name: value.decode("ascii")}
                if need_orig else {}, *got)
        ev = decode_events(rec)[0]
        return self._replace(ev.timestamp, ev.metadata, ev.body, *got)

    def _process_batch_host(self, chunk, data):
        """Native one-pass DFA mask over chunk bytes; the regex (with
        captures) runs only for records the mask admits — mask-false
        records skip the Python regex entirely (the DFA is the
        bit-exact twin of the fallback engine, same contract as
        filter_grep's raw path)."""
        from .. import native

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
