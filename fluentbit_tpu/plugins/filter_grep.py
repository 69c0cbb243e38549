"""filter_grep — keep/exclude records by regex on a record-accessor field.

Reference: plugins/filter_grep/grep.c. Rules are ``Regex <field> <pattern>``
(keep) and ``Exclude <field> <pattern>`` pairs. Three evaluation modes
(logical_op): legacy (first rule decides: Regex-miss ⇒ EXCLUDE,
Exclude-hit ⇒ EXCLUDE, Regex-hit ⇒ KEEP, fallthrough ⇒ KEEP,
grep.c:167-194), AND, OR (grep.c:250-284 — note the verdict uses the type
of the *last examined* rule, matching the reference exactly).

Execution: when every rule pattern compiles to a DFA (and ``tpu.enable``
is on, jax present), matching runs vectorized on device via
fluentbit_tpu.ops.grep — each distinct field the rules read is staged
once into the planes ``[K, B, L]``, the fused DFA kernel gathers every
rule's plane from them and produces the per-rule match matrix, and the
legacy/AND/OR verdict is applied as vector ops on the mask. Records whose
field overflows ``tpu_max_record_len`` (or batches smaller than
``tpu_batch_records``) resolve on the CPU path with identical semantics.
Surviving records are re-emitted byte-identical (raw span reuse).

The raw ingest path enters through the one batched hook,
``process_batch(chunk)``: it picks its engine from what it observes —
the fused native walk or the native matcher while the device attaches
and on a CPU backend, ``staged_match`` through the lane (on the mesh
when one is engaged) once an accelerator is up — and every engine but
the fused walk ends in one verdict → compaction tail.
"""

from __future__ import annotations

import functools
import logging
import threading
from typing import List, NamedTuple, Optional, Tuple

import numpy as np

from ..core.config import ConfigMapEntry
from ..core.plugin import FilterPlugin, FilterResult, registry
from ..core.record_accessor import RecordAccessor
from ..core.spans import ShardedTimings, bind, span
from ..regex import FlbRegex

log = logging.getLogger("flb")

LEGACY, AND, OR = "legacy", "AND", "OR"

_LEN_BUCKETS = (64, 128, 256, 512, 1024, 2048, 4096)

#: rows of a frame's long group (``staged_match``): ``bucket_size``'s
#: smallest rung, one shape whether a frame has 1 long row or 256
_LONG_ROWS = 256
#: a frame is staged in two groups only where the long group is at most
#: this share of its padded rows (``Bp >= 4,096``). The device alone,
#: ``chip_smoke.py --rules-sweep`` on a v5e (PERF.md, PR 39), two groups
#: (``Bp`` rows at L=256 + 256 at L=512, one module a child) over the
#: whole frame at L=512: at 4,096 rows 0.48 (R=50), 0.57 (R=20), 0.61
#: (R=8), 0.64-0.76 (R=1), the same for 1, 100 or 256 long rows; at
#: 1,024 rows 0.76-0.86 — a step's cost does not fall as fast as its
#: rows (a 256-row scan costs 0.25-0.3 of a 4,096-row one at R <= 8) —
#: which saves 0.2 ms (R=1) to 1.7 ms (R=8) of device time a frame there
#: where a second group costs the host ~3 ms (three more arrays to stage
#: and put, a two-scan program to enqueue: spans of a traced run): the
#: rule asks for the rows at which the device's saving is the larger
_LONG_SHARE = 16


class _RawDecline(Exception):
    """Internal: a staging stage inside the pipelined raw path cannot
    serve this chunk — unwind and decline to the decode path."""


#: ``raw_timings`` keys. ``records`` counts every record the raw path
#: served (native matcher or device); ``device_records``,
#: ``overflow_rows``, ``h2d_bytes``, ``d2h_bytes`` and ``scan_elements``
#: count the DEVICE lane only — records whose segment was launched
#: through the grep DeviceLane, the overflow rows among them (longer
#: than ``tpu_max_record_len``, resolved on the CPU after the launch),
#: the staged bytes handed to the launch (the distinct [K, Bp, L] planes
#: + their lengths), the verdict bytes copied back (``mask[R, Bp]``: a
#: byte a rule and row) and the gathered elements the launched program
#: steps through (``GrepProgram.scan_elements``). The last three say
#: how the launches staged for a mesh were laid out, counted where a
#: launch is dispatched: ``mesh_launches`` went out sharded, over
#: ``mesh_devices`` devices in all (so the ratio is devices a launch),
#: ``unsharded_launches`` found the lane's mesh gone and went out on
#: one device. ``split_launches`` counts the segments launched in two
#: groups (the few long rows apart, the rest at the width they need)
#: and ``long_rows`` the rows sent in the long group; the three before
#: them count both groups
_TIMING_KEYS = ("extract_s", "kernel_s", "compact_s", "records",
                "device_records", "overflow_rows", "h2d_bytes",
                "d2h_bytes", "scan_elements", "mesh_launches",
                "mesh_devices", "unsharded_launches", "split_launches",
                "long_rows")


def _len_bucket(n: int, cap: int) -> int:
    """Round a max value length up to a small bucket set (jit-stable
    shapes) without exceeding the configured cap."""
    for b in _LEN_BUCKETS:
        if n <= b:
            return min(b, cap)
    return cap


def _to_text(v) -> Optional[str]:
    """Only string values are regex-matchable — the reference's
    flb_ra_key_regex_match returns no-match for every non-STR msgpack
    type (src/flb_ra_key.c:418)."""
    if isinstance(v, str):
        return v
    return None


class Rule:
    __slots__ = ("is_exclude", "ra", "pattern", "regex")

    def __init__(self, is_exclude: bool, field: str, pattern: str):
        self.is_exclude = is_exclude
        self.ra = RecordAccessor(field)
        self.pattern = pattern
        # Ruby-semantics engine; .dfa is the device-executable table when
        # the pattern is DFA-expressible (fluentbit_tpu.ops.grep uses it)
        self.regex = FlbRegex(pattern)

    @property
    def dfa(self):
        return self.regex.dfa

    def match(self, body: dict) -> bool:
        val = _to_text(self.ra.get(body))
        if val is None:
            return False
        return self.regex.match(val)


def legacy_keep(rules, body: dict) -> bool:
    """First-rule-decides verdict (grep.c:167-194): Exclude-hit ⇒ drop,
    Regex-miss ⇒ drop, Regex-hit ⇒ keep, fallthrough ⇒ keep. Shared by
    filter_grep's legacy mode and filter_log_to_metrics' pre-filter
    (log_to_metrics.c grep_filter_data uses the identical logic)."""
    for rule in rules:
        if rule.match(body):
            return rule.is_exclude is False
        if not rule.is_exclude:
            return False
    return True


def legacy_keep_mask(rules, mask: np.ndarray) -> np.ndarray:
    """Vectorized ``legacy_keep`` over a per-rule match matrix
    ``mask[R, B]`` → ``keep[B]`` (grep.c:167-194 first-rule-decides as
    vector ops). Shared by filter_grep's device/native verdicts and
    filter_log_to_metrics' batched pre-filter."""
    B = mask.shape[1]
    keep = np.ones(B, dtype=bool)
    undecided = np.ones(B, dtype=bool)
    for r, rule in enumerate(rules):
        m = mask[r]
        if rule.is_exclude:
            keep &= ~(undecided & m)  # Exclude-hit → drop
            undecided &= ~m
        else:
            # a Regex rule decides every still-undecided record
            keep = np.where(undecided, m, keep)
            break
    return keep


def parse_grep_rules(properties) -> List[Rule]:
    """Build the ordered rule list from regex/exclude properties
    (property order matters for legacy semantics)."""
    rules: List[Rule] = []
    for key, value in properties.items():
        lk = key.lower()
        if lk in ("regex", "exclude"):
            parts = value.split(None, 1) if isinstance(value, str) else list(value)
            if len(parts) != 2:
                raise ValueError(f"grep: invalid rule {value!r}")
            rules.append(Rule(lk == "exclude", parts[0], parts[1]))
    return rules


def plane_index(rules) -> Tuple[list, Tuple[int, ...]]:
    """The distinct record fields a rule list reads, in first-seen
    order (as the first rule's accessor), and each rule's place among
    them — the rule→plane index a ``GrepProgram`` is built with: a
    field is staged once, however many rules read it."""
    accessors, plane_of, seen = [], [], {}
    for rule in rules:
        key = (rule.ra.head, tuple(rule.ra.parts))
        if key not in seen:
            seen[key] = len(accessors)
            accessors.append(rule.ra)
        plane_of.append(seen[key])
    return accessors, tuple(plane_of)


def rule_matches(rule, body) -> bool:
    """One rule against one decoded record on the host (string values
    only, flb_ra_key.c:418) — what decides an overflow row."""
    val = _to_text(rule.ra.get(body)) if isinstance(body, dict) else None
    return val is not None and bool(rule.regex.match(val))


def first_of_mask(mask: np.ndarray) -> np.ndarray:
    """Host twin of ``ops.grep.first_match_of``: ``mask[R, B]`` → the
    first accepting rule a record, or -1."""
    return np.where(mask.any(axis=0), mask.argmax(axis=0),
                    -1).astype(np.int32)


def host_mask(rules, plane_of, planes: np.ndarray, lengths: np.ndarray,
              cnt: int) -> np.ndarray:
    """Bit-exact host twin of the kernel verdict over a staged segment
    — the DeviceLane fallback. Rows with length < 0 (missing -1,
    overflow -2) stay False, exactly like the kernel; the caller's
    overflow decode then fixes -2 rows the same way it does after a
    device launch."""
    mask = np.zeros((len(rules), cnt), dtype=bool)
    for r, rule in enumerate(rules):
        ln = lengths[plane_of[r]]
        row = planes[plane_of[r]]
        rx = rule.regex
        for i in range(cnt):
            li = int(ln[i])
            if li >= 0:
                mask[r, i] = rx.match(bytes(row[i, :li]).decode(
                    "utf-8", "surrogateescape"))
    return mask


class SpanVerdict(NamedTuple):
    """``staged_match(..., spans=True)``'s verdict: ``ok[n]`` bool,
    ``spans[n, G, 2]`` (start, end) in bytes of the staged value (-1
    where a group took no part or the row does not match), the staged
    ``lengths[n]`` (-1 missing or not a string, -2 longer than
    ``max_len``: rows the caller decides on the host) and ``planes``,
    each segment's staged ``[cnt, L]`` u8 rows in order — the bytes the
    spans cut."""

    ok: np.ndarray
    spans: np.ndarray
    lengths: np.ndarray
    planes: list


def host_spans(regex, n_groups: int, plane: np.ndarray,
               lengths: np.ndarray, cnt: int):
    """Bit-exact host twin of the span program over one staged plane —
    the DeviceLane fallback: ``FlbRegex.parse_spans``'s offsets (in
    bytes) for every row with a length, nothing for the others."""
    ok = np.zeros(cnt, dtype=bool)
    spans = np.full((cnt, n_groups, 2), -1, dtype=np.int32)
    for i in range(cnt):
        li = int(lengths[i])
        if li < 0:
            continue
        text = bytes(plane[i, :li]).decode("utf-8", "surrogateescape")
        got = regex.parse_spans(text)
        if got is None:
            continue
        if not text.isascii():
            got = [(-1, -1) if s < 0 else tuple(
                len(text[:x].encode("utf-8", "surrogateescape"))
                for x in (s, e)) for s, e in got]
        ok[i] = True
        spans[i] = got
    return ok, spans


class LongGroup(NamedTuple):
    """A segment's few long rows, staged apart (``staged_match``): the
    layout of the segment's own planes again, ``_LONG_ROWS`` rows at the
    width the longest needs — ``planes[K, 256, L]`` u8 and
    ``lengths[K, 256]`` i32 (every key of the row, the short ones too,
    with its true length; a pad row -1) — and ``rows[256]`` i32, each
    one's row in the segment (a pad row's lies past the mask and is
    dropped); ``n`` rows are real. The first three are what
    ``GrepProgram.dispatch`` takes as ``long``."""

    planes: np.ndarray
    lengths: np.ndarray
    rows: np.ndarray
    n: int

    @classmethod
    def of(cls, staged, rows: np.ndarray, L: int, Bp: int) -> "LongGroup":
        """Rows ``rows`` of ``staged`` — a ``(values u8[cnt, >=L],
        lengths i32[cnt])`` a key — at width ``L``, for a main group of
        ``Bp`` rows (where a pad row's index points)."""
        n, K = len(rows), len(staged)
        group = cls(np.zeros((K, _LONG_ROWS, L), dtype=np.uint8),
                    np.full((K, _LONG_ROWS), -1, dtype=np.int32),
                    np.full((_LONG_ROWS,), Bp, dtype=np.int32), n)
        for k, (values, lengths) in enumerate(staged):
            group.planes[k, :n] = values[rows, :L]
            group.lengths[k, :n] = lengths[rows]
        group.rows[:n] = rows
        return group


def long_split(longest: np.ndarray, n_rows: int, max_len: int):
    """The widths a segment stages at, and the split rule, from what the
    stage sees alone: ``longest[cnt]`` (a row's longest staged value
    over the keys; negative where none was staged), ``n_rows`` (the rows
    it pads for) and the cap ``max_len``. → ``(L, split)``: ``L`` the
    bucket of the longest value — the width the whole segment needs —
    and ``split`` either None or ``(L_lo, rows, Bp)``: the bucket of the
    value that only ``_LONG_ROWS`` rows exceed, i.e. the smallest one
    that all but at most ``_LONG_ROWS`` rows fit, those rows' indices,
    and the padded rows of the main group. None where that bucket is
    ``L`` itself, or where the long group would be more than one
    ``_LONG_SHARE``-th of the padded rows (the sweep beside
    ``_LONG_SHARE``).

    Both values come from ONE selection pass where a plain maximum was
    taken before: numpy drops the GIL in every pass over more than a few
    hundred elements, and with four or five busy threads beside it a
    frame pays for each pass by waiting to take it back (six passes more
    read as 0.7 ms a frame of staging on the chip's host where they cost
    0.07 alone; PERF.md, PR 39)."""
    from ..ops.batch import bucket_size

    cnt = len(longest)
    if cnt <= _LONG_ROWS \
            or bucket_size(n_rows) < _LONG_SHARE * _LONG_ROWS:
        # (no width pads a segment to more rows than bucket_size's rung)
        top = int(longest.max()) if cnt else 0
        return _len_bucket(max(top, 1), max_len), None
    kth = cnt - 1 - _LONG_ROWS  # in order: the longest of the rest
    part = np.partition(longest, (kth, cnt - 1))
    L = _len_bucket(max(int(part[-1]), 1), max_len)
    lo = _len_bucket(max(int(part[kth]), 1), max_len)
    if lo == L:
        return L, None
    Bp = bucket_size(n_rows, max_len=lo)
    if Bp < _LONG_SHARE * _LONG_ROWS:
        return L, None
    return L, (lo, np.flatnonzero(longest > lo), Bp)


def decoded_match(rules, program, lane, events: list,
                  max_len: int) -> np.ndarray:
    """The decoded path's launch: stage each distinct field of
    ``events`` once (``ops.batch.assemble``), run the fused DFA kernel
    through ``lane`` (bit-exact host fallback), resolve overflow rows
    on the CPU. Returns mask[R, B] bool."""
    from ..ops.batch import assemble, bucket_size

    B = len(events)
    # rules addressing the same field share one extraction, one staged
    # plane and one host→device copy (the staging loop is the hot-path
    # bottleneck)
    accessors, plane_of = plane_index(rules)
    Bp = bucket_size(B, max_len=max_len)
    values: list = []
    staged: list = []
    for ra in accessors:
        vals: List[Optional[bytes]] = []
        for ev in events:
            v = _to_text(ra.get(ev.body)) \
                if isinstance(ev.body, dict) else None
            vals.append(v.encode("utf-8") if v is not None else None)
        values.append(vals)
        staged.append(assemble(vals, max_len, Bp))
    batch = np.stack([b.batch for b in staged])
    lengths = np.stack([b.lengths for b in staged])
    mask = lane.run(
        lambda: np.asarray(program.match(batch, lengths)),
        lambda: host_mask(rules, plane_of, batch, lengths,
                          batch.shape[1]),
    )
    mask = np.array(mask[:, :B])
    for r, rule in enumerate(rules):
        k = plane_of[r]
        for i in staged[k].overflow:
            mask[r, i] = rule.regex.match(values[k][i])
    return mask


class Begun:
    """A chunk's staged launch, begun and not yet finished —
    ``staged_match(..., begin=True)``'s handle. It holds the flight,
    the staged planes and lengths (the fallback's and the overflow
    rows' input) and what it was made for: the very ``data`` object,
    the ``rules`` list, the ``program`` and the kind of verdict. Handed
    back as ``staged_match(..., begun=handle)`` it is finished there if
    it still answers that call (:meth:`finish_for`), dropped if not.
    It is ended once, by whoever comes first; whoever holds a handle it
    cannot use drops it — the lane counts every flight finished."""

    __slots__ = ("data", "made_for", "_lane", "_flight", "_finish",
                 "_spent", "_lock")

    def __init__(self, data, made_for: tuple, lane, flight, finish):
        self.data = data
        self.made_for = made_for
        self._lane = lane
        self._flight = flight
        self._finish = finish
        self._spent = False
        self._lock = threading.Lock()  # a leaf: held over one swap

    def _spend(self):
        """``(flight, finish)`` for the one caller that gets to end the
        launch, None for every other; the staged planes and the chunk's
        bytes go with them, not with a handle that is kept about."""
        with self._lock:
            if self._spent:
                return None
            self._spent = True
        mine = self._flight, self._finish
        self.data = self._flight = self._finish = None
        return mine

    def finish_for(self, tm, data, *made_for):
        """The verdict (``staged_match``'s result) if this launch
        answers ``staged_match`` over ``data`` for ``made_for`` — the
        same objects, not equal ones: a reloaded filter's rules are
        another list — and has not been ended; else it is dropped and
        ``NotImplemented`` says so."""
        if data is self.data \
                and all(a is b for a, b in zip(made_for, self.made_for)):
            mine = self._spend()
            if mine is not None:
                return mine[1](tm)
        self.drop()
        return NotImplemented

    def drop(self) -> None:
        """End a launch nobody will use: the flight is finished (its
        verdict, the device's or the fallback's, is thrown away) and
        nothing more is counted in any ``raw_timings`` (on the mesh its
        layout was, when it was dispatched). A no-op on a handle
        that was finished or dropped before."""
        mine = self._spend()
        if mine is not None:
            self._lane.finish(mine[0])


def staged_match(rules, program, lane, tm, data, n_records, *,
                 max_len: int, min_records: int, mesh=None,
                 first_match: bool = False, spans: bool = False,
                 begin: bool = False, begun: Optional[Begun] = None):
    """Device matching straight off chunk bytes, with double-buffered
    staging — the one staged launch ``filter_grep``,
    ``filter_rewrite_tag`` and ``filter_parser`` share.

    The chunk's records split into fixed-size segments; each DISTINCT
    key the rules read is staged once a segment
    (native.stage_field_into over the segment's byte span) into the
    planes ``[K, Bp, L]`` the program gathers its rules' inputs from;
    staging of segment N+1 runs while segment N's kernel is in flight
    (jax async dispatch — core.chunk_batch.double_buffered), and each
    verdict is forced one segment behind. Every launch goes through
    ``lane`` (``begin``/``finish``: breaker, deadline, the bit-exact
    host fallback); rows longer than ``max_len`` (-2) are decided on
    the host after the launch.

    **A segment's few long rows go as a group of their own.** The scan
    takes ``⌈L/k⌉ + 1`` dependent steps over every row it is given, so
    one 400-byte line among 4,096 of 200 cost the whole segment twice
    the steps. Where all but at most ``_LONG_ROWS`` (256) rows fit a
    narrower bucket and the segment pads to at least ``_LONG_SHARE``
    (16) times as many rows (:func:`long_split`: what the stage sees,
    nothing else), the planes are staged at that bucket, the long rows
    again as a :class:`LongGroup` at the bucket the longest needs, and
    the ONE launch scans both (``GrepProgram.dispatch``'s ``long``): on
    the device the main group holds the long rows as rows without a
    value and their verdicts are written over theirs, so the verdict,
    its shape, the copy-out and everything after it are what they are
    for a segment staged whole; the fallback is ``host_mask`` over both
    groups, joined the same way. ``split_launches`` and ``long_rows``
    count how often and how many; ``h2d_bytes`` and ``scan_elements``
    count both groups; ``grep.stage`` and ``grep.dispatch`` carry ``L``
    and, of such a segment, ``L_long`` and ``long``. A span program
    (``spans``) takes its plane whole, and so does the mesh.

    With ``mesh`` set, each segment launches through the explicitly
    partitioned pjit matcher instead: the batch axis is padded to the
    mesh size and sharded across devices at ONE jit-stable width, and
    the staged lengths are donated to the kernel where they can alias
    its output. The mesh's verdict is ``i32[R, Bp]``, 4 B a rule and
    row, so that the ``i32[K, Bp]`` lengths can be donated into it —
    which aliases only at K = R (a plane a rule); a rule list on fewer
    planes than rules (50 rules on one key) copies out four times what
    one device would and donates nothing. Such a launch is counted
    where it is dispatched: ``mesh_launches`` and ``mesh_devices`` (the
    lane's mesh then; their ratio is devices a launch), or
    ``unsharded_launches`` where the lane's mesh is gone (fewer than
    two devices survive) and the staged planes go out on one device.

    With ``spans`` the one rule's ``program`` is an
    ``ops.grep.SpanProgram`` and the verdict a :class:`SpanVerdict`:
    the named groups' offsets a row, from the device or from
    :func:`host_spans`; rows without a staged value (-1, -2) are left
    for the caller, which builds records and decides them on the host
    per row. One device only (no ``mesh``).

    **In two halves.** With ``begin`` a chunk of ONE segment is staged
    and its launch begun (``lane.begin``), and a :class:`Begun` comes
    back instead of a verdict — of ``tm`` only the mesh's three layout
    counts are touched (below), nothing is committed, so any
    long-lived thread may do it ahead of the chunk's turn
    (``in_forward`` does, for a connection's next frame, while the
    frame before it is collected and acked: ``begin_batch``). With
    ``begun`` — that handle, given back by the chunk's own absorb — the
    call goes straight to ``lane.finish`` and the rest below, if the
    handle still serves (same ``data`` object, ``rules``, ``program``
    and kind of verdict); else the handle is dropped and the call
    starts over. Without either, both halves run back to back here, and
    a chunk of several segments keeps ``double_buffered`` inside it
    (``begin`` declines such a chunk: None).

    ``tm`` (the plugin's ``raw_timings``) is written by the finishing
    half alone, once a chunk whichever half ran where — but for
    ``mesh_launches``, ``mesh_devices`` and ``unsharded_launches``,
    which the dispatching half adds, as the lane counts its launches:
    a launch that is dropped unused was laid out all the same. It takes
    ``extract_s``, ``kernel_s`` (the finishing call's wall less the
    extraction done inside it: what the chunk waited for its launch
    **in series** — the whole launch where both halves ran here, what
    was left of it where it was begun ahead), ``h2d_bytes`` (the planes and
    their lengths), ``d2h_bytes`` (what the forced launch copies out:
    ``mask[R, Bp]`` a byte each — four on the mesh, whose verdict is
    i32 — the ``[Bp]`` i32 first-match vector, or with ``spans`` the
    verdicts and offsets), ``scan_elements`` (the gathered elements the
    launched program steps through, from the staged shape),
    ``device_records`` and ``overflow_rows``.
    Returns ``(verdict, offsets[n+1], n)`` — ``mask[R, n]`` bool, with
    ``first_match`` the ``[n]`` i32 first-match vector, with ``spans``
    the :class:`SpanVerdict` — or None to decline (fewer than
    ``min_records`` records, or bytes the staging walk cannot serve);
    nothing is counted on a decline."""
    import os as _os
    import time as _time

    from .. import native
    from ..core.chunk_batch import double_buffered, segment_bounds
    from ..ops.batch import bucket_size

    made_for = (rules, program, first_match, spans)
    if begun is not None:
        got = begun.finish_for(tm, data, *made_for)
        if got is not NotImplemented:
            return got
    if not isinstance(data, bytes):
        data = bytes(data)
    # default matches a bucket_size rung exactly: a full segment
    # stages with ZERO pad rows (8192 would round up to the 16384
    # bucket and double every segment's staging + kernel work)
    seg = int(_os.environ.get("FBTPU_SEGMENT_RECORDS", "4096"))
    n = n_records
    offsets = None
    if n is None or n > seg:
        # segmentation (or an unknown count) needs the boundary
        # table up front; single-segment chunks with a known count
        # skip this walk and take the offsets the first
        # stage_field call discovers anyway
        offsets = native.scan_offsets(data)
        if offsets is None:
            return None
        n = len(offsets) - 1
    if n < min_records:
        return None  # small batches: decline BEFORE staging/kernel
    accessors, plane_of = plane_index(rules)
    keys = [ra.head.encode("utf-8") for ra in accessors]
    K = len(keys)
    bounds = segment_bounds(n, seg)
    multi = len(bounds) > 1
    if begin and multi:
        return None  # the segments overlap each other: double_buffered
    extract_s = [0.0]
    # h2d bytes, gathered elements, segments launched in two groups,
    # rows in the long groups: counted at the finish
    sent = [0, 0, 0, 0]
    lens_parts: list = []
    cnts: list = []
    plane_parts: list = []  # spans: the staged rows the offsets cut
    offs_box = [offsets]  # filled by staging when not pre-scanned

    n_dev = mesh.devices.size if mesh is not None else 1

    def stages():
        def stage_key(part, key, wide, wlen, cnt):
            # single-segment chunks take the boundary table straight
            # from the staging walk (it computes one anyway, the same
            # whichever key discovers it) — never re-scan
            want_offs = offs_box[0] is None
            offs = np.empty(cnt + 1, dtype=np.int64) \
                if want_offs else None
            count = native.stage_field_into(
                part, key, wide, wlen, n_hint=cnt, offsets_out=offs)
            if count is None or count != cnt:
                raise _RawDecline
            if want_offs:
                offs_box[0] = offs

        def stage(s, e):
            t0 = _time.perf_counter()
            cnt = e - s
            part = data if offs_box[0] is None \
                else data[offs_box[0][s]: offs_box[0][e]]
            if mesh is not None:
                # mesh staging: ONE jit-stable width (the sharded
                # program wants one compiled shape, not per-chunk
                # L buckets) and extraction lands straight in the
                # [K, Bp, L] transfer matrix — no arena copy, the
                # native pool splits the walk across cores
                Bp = bucket_size(seg if multi else cnt,
                                 max_len=max_len, multiple_of=n_dev)
                batch = np.empty((K, Bp, max_len), dtype=np.uint8)
                lengths = np.full((K, Bp), -1, dtype=np.int32)
                for k in range(K):
                    stage_key(part, keys[k], batch[k], lengths[k], cnt)
                extract_s[0] += _time.perf_counter() - t0
                return batch, lengths, cnt, lengths[:, :cnt], None
            staged = []
            for k in range(K):
                # stage straight into a caller-owned [cnt, max_len]
                # matrix: no arena round-trip, ONE copy per key (the
                # L-bucketed slice into the segment planes below)
                wide = np.empty((cnt, max_len), dtype=np.uint8)
                wlen = np.full((cnt,), -1, dtype=np.int32)
                stage_key(part, keys[k], wide, wlen, cnt)
                staged.append((wide, wlen))
            longest = staged[0][1] if K == 1 else np.maximum.reduce(
                [wlen for _wide, wlen in staged])
            # scan-length bucketing: the DFA scan is sequential in
            # L, so clamp to the longest staged value (rounded to a
            # small bucket set for jit shape stability) — and where a
            # few long rows alone ask for that width, they go as a
            # narrow group of their own (LongGroup) and the rest at the
            # width it needs. Segment-uniform batch shape: one compile
            # covers every full segment of the chunk stream. The span
            # program takes one plane whole (its verdict carries the
            # staged rows)
            n_rows = seg if multi else cnt
            L, split = long_split(longest, n_rows, max_len)
            if spans:
                split = None
            if split is None:
                width, Bp = L, bucket_size(n_rows, max_len=L)
            else:
                width, long_idx, Bp = split
            batch = np.zeros((K, Bp, width), dtype=np.uint8)
            lengths = np.full((K, Bp), -1, dtype=np.int32)
            for k, (b, ln) in enumerate(staged):
                batch[k, :cnt] = b[:cnt, :width]
                lengths[k, :cnt] = ln[:cnt]
            # the true lengths: what finish() reads (overflow rows)
            host_lens, long = lengths[:, :cnt], None
            if split is not None:
                long = LongGroup.of(staged, long_idx, L, Bp)
                # on the device the main group holds them as rows
                # without a value: they scan as empty, and the long
                # group's verdict is written over theirs
                lengths = lengths.copy()
                lengths[:, long_idx] = -1
            extract_s[0] += _time.perf_counter() - t0
            return batch, lengths, cnt, host_lens, long

        for si, (s, e) in enumerate(bounds):
            with span("grep.stage", seg=si) as sp:
                item = stage(s, e)
                sp.set_metadata(**widths(item[0], item[4]))
            yield item + (si,)

    def widths(b, long) -> dict:
        ids = {"L": b.shape[2]}
        if long is not None:
            ids.update(L_long=long.planes.shape[2], long=long.n)
        return ids

    def launch_ids(b, long=None) -> dict:
        return {"rules": len(rules), "planes": K, **widths(b, long)}

    def forced(b, ln, long=None):
        # enqueue + argument copy-in, then the wait for the
        # execution and the copy-out
        with span("grep.dispatch", **launch_ids(b, long)):
            out = program.dispatch(b, ln) if spans else \
                program.dispatch(b, ln, first_match=first_match,
                                 long=long and long[:3])
        with span("grep.force"):
            if spans:
                return tuple(np.asarray(o) for o in out)
            return np.asarray(out)

    def dispatch(item):
        batch, lengths, cnt, host_lens, long, si = item
        lens_parts.append(host_lens)
        cnts.append(cnt)
        if spans:
            plane_parts.append(batch[0, :cnt])
        sent[0] += batch.nbytes + lengths.nbytes
        sent[1] += program.scan_elements(batch.shape[1], batch.shape[2])
        if long is not None:
            sent[0] += sum(a.nbytes for a in long[:3])
            sent[1] += program.scan_elements(*long.planes.shape[1:])
            sent[2] += 1
            sent[3] += long.n
        if mesh is not None:
            # sharded launch through the device fault domain: the
            # launch closure re-stages (fresh device_put + donation)
            # on EVERY attempt — after a failed launch the donated
            # lengths buffer is consumed (deleted aval), so a retry
            # or fallback must read the host arrays, never the
            # device buffers. The counts-free variant skips the
            # per-segment psum the filter verdict never reads.
            # Forcing inside the launch keeps the deadline armed
            # over the whole execution AND preserves the staging
            # overlap (the worker forces while the caller stages
            # the next segment).
            # The lane's mesh is read here, on the dispatching thread
            # (a lane worker lives for one launch and may not add to
            # ``tm``), and the layout counted with it.
            m = lane.current_mesh()
            if m is None:
                tm.add("unsharded_launches", 1)
            else:
                tm.add("mesh_launches", 1)
                tm.add("mesh_devices", int(m.devices.size))

            def launch(b=batch, ln=lengths):
                if m is None:
                    # mesh shrunk below 2 devices: serve unsharded
                    return forced(b, ln)
                with span("grep.dispatch", **launch_ids(b)):
                    out, _, _b2, _bp = program.dispatch_mesh(
                        m, b, ln, with_counts=False,
                        first_match=first_match)
                with span("grep.force"):
                    return np.asarray(out)  # the mask as i32, as copied
        else:
            def launch(b=batch, ln=lengths, lg=long):
                return forced(b, ln, lg)

        def fallback(b=batch, ln=lengths, c=cnt, lg=long):
            if spans:
                return host_spans(rules[0].regex, len(program.names),
                                  b[0], ln[0], c)
            mask = host_mask(rules, plane_of, b, ln, c)
            if lg is not None:  # both groups, joined as on the device
                mask[:, lg.rows[:lg.n]] = host_mask(
                    rules, plane_of, lg.planes, lg.lengths, lg.n)
            return first_of_mask(mask) if first_match else mask

        with bind(seg=si):
            return lane.begin(launch, fallback)

    def collect(pending):
        # nothing is committed until here: the segment's verdict is
        # the device result OR the bit-exact host fallback, exactly
        # one of the two (fbtpu-armor)
        return lane.finish(pending)

    def finish(tm, pending=None):
        """From the wait for the launch on: the verdicts collected,
        the counters, the overflow rows. ``pending``: the one segment's
        flight where ``begin`` dispatched it already."""
        t_all = _time.perf_counter()
        staged_before = extract_s[0]
        try:
            verdicts = [collect(pending)] if pending is not None else \
                double_buffered(stages(), dispatch, collect)
        except _RawDecline:
            return None
        wall = _time.perf_counter() - t_all
        tm.add("extract_s", extract_s[0])
        tm.add("kernel_s",
               max(wall - (extract_s[0] - staged_before), 0.0))
        tm.add("h2d_bytes", sent[0])
        tm.add("scan_elements", sent[1])
        if not spans:
            tm.add("split_launches", sent[2])
            tm.add("long_rows", sent[3])
        offsets = offs_box[0]
        lengths = np.concatenate(lens_parts, axis=1)
        overflow_rows = np.unique(np.nonzero(lengths == -2)[1])
        tm.add("device_records", n)
        tm.add("overflow_rows", len(overflow_rows))
        copied = (part for v in verdicts for part in v) if spans else verdicts
        tm.add("d2h_bytes", sum(part.nbytes for part in copied))
        if spans:
            # the caller builds the records, and decides on the host those
            # that staged no value
            return SpanVerdict(
                np.concatenate([ok[:c] for (ok, _), c in zip(verdicts, cnts)]),
                np.concatenate([sp[:c] for (_, sp), c in zip(verdicts, cnts)]),
                lengths[0], plane_parts), offsets, n
        verdict = np.concatenate(
            [np.asarray(v)[..., :c] for v, c in zip(verdicts, cnts)], axis=-1)
        if not first_match:
            verdict = verdict.astype(bool, copy=False)  # the mesh's is i32
        # overflow rows (-2): decode just those records on the CPU
        if len(overflow_rows):
            from ..codec.events import decode_events

            with span("grep.overflow", rows=len(overflow_rows)):
                for b_idx in overflow_rows:
                    rec = bytes(data[offsets[b_idx]: offsets[b_idx + 1]])
                    body = decode_events(rec)[0].body
                    if first_match:
                        # the per-record rule walk, break on first match
                        verdict[b_idx] = next(
                            (r for r, rule in enumerate(rules)
                             if rule_matches(rule, body)), -1)
                        continue
                    for r, rule in enumerate(rules):
                        if lengths[plane_of[r], b_idx] == -2:
                            verdict[r, b_idx] = rule_matches(rule, body)
        return verdict, offsets, n

    if not begin:
        return finish(tm)
    try:
        (item,) = stages()
        flight = dispatch(item)
    except _RawDecline:
        return None
    return Begun(data, made_for, lane, flight,
                 functools.partial(finish, pending=flight))


@registry.register
class GrepFilter(FilterPlugin):
    name = "grep"
    description = "keep/exclude records matching regex patterns"
    # the raw path is pure (rules are immutable after init; timing
    # counters take their own lock), so the engine may run it for
    # multiple inputs in parallel under per-input locks
    thread_safe_raw = True
    config_map = [
        ConfigMapEntry("regex", "slist", multiple=True, slist_max_split=1,
                       desc="keep rule: <field> <pattern>"),
        ConfigMapEntry("exclude", "slist", multiple=True, slist_max_split=1,
                       desc="exclude rule: <field> <pattern>"),
        ConfigMapEntry("logical_op", "str", default="legacy"),
        ConfigMapEntry("tpu.enable", "bool", default=True,
                       desc="vectorized device matching when rules allow"),
        ConfigMapEntry("tpu_batch_records", "int", default=32,
                       desc="min records per append to use the device path"),
        ConfigMapEntry("tpu_max_record_len", "int", default=512,
                       desc="field byte length staged on device; longer "
                            "values resolve on the CPU fallback"),
    ]

    def init(self, instance, engine) -> None:
        self.rules = parse_grep_rules(instance.properties)
        op = (self.logical_op or "legacy").lower()
        if op == "and":
            self.op = AND
        elif op == "or":
            self.op = OR
        else:
            self.op = LEGACY
        if self.op != LEGACY and self.rules:
            kinds = {r.is_exclude for r in self.rules}
            if len(kinds) > 1:
                raise ValueError("grep: AND/OR mode cannot mix Regex and Exclude rules")
        # probe (and first-build) the native scanner here, NOT on the
        # hot append path under the ingest lock — the one-time g++
        # compile must never stall ingest
        from .. import native as _native

        _native.available()
        # device program: all rules DFA-expressible + jax importable.
        # program_for is numpy-only (cheap); the backend transfer waits
        # on the attach controller so a slow/hung platform init never
        # blocks plugin init or ingest — records run the bit-exact CPU
        # path until the device is up (an earlier round's CLI was
        # un-killable for minutes inside eager jax init).
        self._program = None
        self._native_tables = None
        self._native_filter = None
        self._mesh = None
        self._mesh_resolved = False
        self._mesh_on = False
        self._mesh_gen = None
        self.raw_timings = ShardedTimings(_TIMING_KEYS)
        # per-worker copies of the read-only native tables (multi-input
        # scaling: no cross-thread sharing of the hot arrays)
        self._tls_tables = threading.local()
        if self.tpu_enable and self.rules and all(r.dfa is not None for r in self.rules):
            try:
                from ..ops import device
                from ..ops.grep import program_for

                self._program = program_for(
                    tuple(r.pattern for r in self.rules),
                    self.tpu_max_record_len,
                    plane_of=plane_index(self.rules)[1])
                device.wait()  # bounded (FBTPU_ATTACH_WAIT_S, default 2s)
                self._program.try_ready()
            except Exception:
                log.debug("grep device program unavailable; host path "
                          "serves", exc_info=True)
                self._program = None
            # host-side twin: one-pass C++ field-extract + DFA over
            # chunk bytes (simple top-level keys only). Serves the raw
            # path while the device attaches and whenever the attached
            # backend is the jax CPU fallback.
            if self._program is not None and all(
                not r.ra.parts for r in self.rules
            ):
                try:
                    self._native_tables = _native.GrepTables(
                        [(r.ra.head.encode("utf-8"), r.dfa)
                         for r in self.rules]
                    )
                except Exception:
                    log.warning("grep native table build failed; raw "
                                "staging path disabled", exc_info=True)
                    self._native_tables = None
                # fused single-pass variant (extract + accel DFA +
                # verdict + compaction in one native call)
                try:
                    self._native_filter = _native.GrepFilterTables(
                        [(r.ra.head.encode("utf-8"), r.dfa, r.is_exclude)
                         for r in self.rules],
                        op=self.op,
                    )
                except Exception:
                    log.warning("grep fused filter table build failed; "
                                "fused raw path disabled", exc_info=True)
                    self._native_filter = None
        self._report_shrink(instance, engine)

    def _report_shrink(self, instance, engine) -> None:
        """fluentbit_grep_shrink_* compile-outcome counters."""
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

    # -- verdicts (bit-exact vs grep.c) --

    def keep_record(self, body: dict) -> bool:
        if not self.rules:
            return True
        if self.op == LEGACY:
            return legacy_keep(self.rules, body)
        # AND/OR: compute 'found' with short-circuit, verdict by last rule's type
        found = False
        rule = self.rules[0]
        for rule in self.rules:
            found = rule.match(body)
            if self.op == OR and found:
                break
            if self.op == AND and not found:
                break
        if not rule.is_exclude:
            return found
        return not found

    # -- vectorized verdicts over the device match matrix --

    def keep_mask(self, mask: np.ndarray) -> np.ndarray:
        """mask[R, B] per-rule match matrix → keep[B], same semantics as
        keep_record (grep.c verdict logic applied as vector ops)."""
        if self.op == LEGACY:
            return legacy_keep_mask(self.rules, mask)
        found = mask.any(axis=0) if self.op == OR else mask.all(axis=0)
        # AND/OR rules are all the same kind (enforced in init)
        return ~found if self.rules[0].is_exclude else found

    def _lane(self):
        """This plugin's device fault domain (fbtpu-armor): every jit/
        pjit/shard_map launch goes through the process-global "grep"
        DeviceLane — breaker, launch deadline, bit-exact CPU fallback,
        mesh shrink/regrow (FAULTS.md "fbtpu-armor")."""
        ln = getattr(self, "_lane_obj", None)
        if ln is None:
            from ..ops import fault

            ln = self._lane_obj = fault.lane("grep")
        return ln

    def _match_matrix_device(self, events: list) -> np.ndarray:
        """mask[R, B]: rule r's regex matches record b's field value."""
        return decoded_match(self.rules, self._program, self._lane(),
                             events, self.tpu_max_record_len)

    def filter(self, events: list, tag: str, engine) -> tuple:
        if (
            self._program is not None
            and len(events) >= self.tpu_batch_records
            and self.rules
            and self._program.try_ready()
        ):
            keep = self.keep_mask(self._match_matrix_device(events))
            kept = [ev for ev, k in zip(events, keep) if k]
        else:
            kept = [ev for ev in events if self.keep_record(ev.body)]
        if len(kept) == len(events):
            return (FilterResult.NOTOUCH, events)
        return (FilterResult.MODIFIED, kept)

    # -- raw chunk-bytes path (no Python decode) --

    def _grep_mesh(self):
        """The device mesh the raw path shards across, or None.

        Resolved from ``FBTPU_MESH``: ``off``/``0`` never builds one;
        ``on``/``1``/``force`` builds it from whatever devices exist
        (the simulated-mesh lane — 8 virtual CPU devices under
        ``--xla_force_host_platform_device_count``); ``auto`` (default)
        engages only when a real accelerator with ≥2 devices attached —
        on a CPU backend the native fused matcher beats a partitioned
        lax.scan by orders of magnitude, so auto must never shadow it.

        The resolution only PINS once the attach controller reaches a
        terminal state (ready/failed-exhausted) — a chunk arriving
        mid-attach (or mid-RETRY, fbtpu-armor) must not permanently
        disable the mesh lane for the plugin's lifetime — and it pins
        per attach GENERATION: an attach that succeeds later (a retry
        attempt landing after chunks already flowed on CPU, or an
        ops-driven ``device.reattach_async``) re-opens the resolution
        and the mesh lane swaps in live. Once engaged, the mesh object
        itself comes from the "grep" DeviceLane, which shrinks it on
        device loss and regrows it when the breaker re-closes."""
        import os as _os

        from ..ops import device

        gen = device.generation()
        if self._mesh_resolved and gen > 0 \
                and getattr(self, "_mesh_gen", None) != gen:
            # new attach generation: the old verdict (pinned-off after
            # a failed attach, or a mesh over the previous backend) is
            # stale — re-resolve against the live device
            self._mesh_resolved = False
        if self._mesh_resolved:
            if getattr(self, "_mesh_on", False):
                self._mesh = self._lane().current_mesh()
            return self._mesh
        mode = _os.environ.get("FBTPU_MESH", "auto").lower()
        if self._program is None or mode in ("0", "off"):
            self._mesh_resolved = True
            self._mesh_gen = gen
            return None
        try:
            if mode in ("1", "on", "force"):
                if device.wait():
                    self._mesh = self._lane().current_mesh()
                    self._mesh_resolved = True
                elif device.failed():
                    log.warning("FBTPU_MESH=%s but device attach "
                                "exhausted its retries (%s); unsharded "
                                "path pinned until a re-attach "
                                "generation", mode,
                                device.status().get("error"))
                    self._mesh_resolved = True
                # else: still attaching/retrying — re-probe next chunk
            elif device.ready():
                if device.platform() != "cpu" \
                        and device.device_count() > 1:
                    self._mesh = self._lane().current_mesh()
                self._mesh_resolved = True
            elif device.failed():
                self._mesh_resolved = True
            else:
                device.attach_async()  # auto mid-attach: probe again
        except Exception:
            log.warning("grep mesh build failed; unsharded device "
                        "path serves", exc_info=True)
            self._mesh = None
            self._mesh_resolved = True
        if self._mesh_resolved:
            self._mesh_gen = gen
            self._mesh_on = self._mesh is not None
        return self._mesh

    def can_process_batch(self) -> bool:
        """True when matching can run straight off chunk bytes: native
        scanner present, every rule addresses a simple top-level key,
        and an engine is available — the one-pass C++ DFA (always, once
        tables are packed) or the device kernel (once attached)."""
        from .. import native

        return (
            self._program is not None
            and bool(self.rules)
            and all(not r.ra.parts for r in self.rules)
            and native.available()
            and (self._native_tables is not None
                 or self._program.try_ready())
        )

    def process_batch(self, chunk):
        """Raw chunk-bytes matching → verdict → raw-span compaction.
        Returns (n_out, new_data) — (n_out, new_data, n_in) where the
        fused walk counted the input — or None to decline (the engine
        then falls back to the decode path). Byte-identical surviving
        records — the grep contract (grep.c:286-392).

        Engine selection, from the platform and the attach state: the
        jax kernel runs when a non-CPU device is attached (the point of
        the build); the one-pass C++ DFA twin serves while the device
        is attaching and whenever jax would run on its own CPU backend
        (a table-driven C loop beats the sequential lax.scan there by
        orders of magnitude)."""
        import time as _time

        from .. import native

        if not native.available():
            return None
        data, n_records = chunk.as_bytes(), chunk.n
        tm = self.raw_timings
        mesh, use_native = self._raw_engine()
        if use_native and self._native_filter is not None:
            # fused path: extraction + prepass DFA + verdict + compaction
            # in ONE native pass; all-kept chunks return the input
            # buffer untouched (zero copies). The walk discovers the
            # record count, so the triple return lets the engine skip
            # its counting pre-pass entirely.
            t0 = _time.perf_counter()
            got = native.grep_filter(
                data, self._local_tables("_native_filter"),
                n_hint=n_records)
            if got is None:
                return None
            n, n_keep, out = got
            tm.add("kernel_s", _time.perf_counter() - t0)
            tm.add("records", n)
            return (n_keep, out, n)
        if use_native:
            t0 = _time.perf_counter()
            got = native.grep_match(
                data, self._local_tables("_native_tables"),
                n_hint=n_records
            )
            if got is None:
                return None
            tm.add("kernel_s", _time.perf_counter() - t0)
        else:
            # (declines under tpu_batch_records: decode is cheaper there)
            got = self._jax_match_raw(data, n_records, mesh=mesh,
                                      begun=chunk.take_begun())
            if got is None:
                return None
        mask, offsets, n = got
        tm.add("records", n)
        with span("grep.verdict", rules=len(self.rules)):
            keep = self.keep_mask(mask)
        n_keep = int(keep.sum())
        if n_keep == n:
            return (n, data)
        if n_keep == 0:
            return (0, b"")
        with tm.timed("compact_s", "grep.compact"):
            compacted = native.compact(data, offsets[: n + 1], keep)
        if compacted is not None:
            return (n_keep, compacted)
        parts = [
            data[offsets[i]: offsets[i + 1]]
            for i in np.nonzero(keep)[0]
        ]
        return (n_keep, b"".join(parts))

    def _raw_engine(self) -> tuple:
        """``(mesh, use_native)``: what serves a raw chunk now."""
        from ..ops import device

        # mesh first: when the partitioned pjit plane is engaged
        # (FBTPU_MESH — real multi-chip attach, or forced for the
        # simulated lane) it IS the device path, native serves staging
        mesh = self._grep_mesh()
        # platform check FIRST: on a CPU-backend host try_ready() would
        # needlessly materialize the jax program that will never run
        use_native = self._native_tables is not None and mesh is None and (
            device.platform() == "cpu" or not self._program.try_ready()
        )
        return mesh, use_native

    def begin_batch(self, data: bytes, n_records):
        """``process_batch``'s launch, begun ahead of the chunk's turn
        (``FilterPlugin.begin_batch``): where the staged device launch
        would serve ``data``, stage it and begin it → the
        :class:`Begun` that ``process_batch`` finds on its chunk; None
        where another engine serves or the launch declines."""
        from .. import native

        if not native.available():
            return None
        mesh, use_native = self._raw_engine()
        if use_native:
            return None
        return self._jax_match_raw(data, n_records, mesh=mesh, begin=True)

    def _local_tables(self, attr: str):
        """This thread's private copy of a packed native table set (the
        multi-input scaling fix: concurrent ingest workers each walk
        their own arrays instead of hammering one shared set)."""
        tls = self._tls_tables
        t = getattr(tls, attr, None)
        if t is None:
            t = getattr(self, attr).thread_copy()
            setattr(tls, attr, t)
        return t

    def _jax_match_raw(self, data, n_records, mesh=None, **halves):
        """Device-kernel raw matching (``staged_match``): returns
        (mask[R, n], offsets[n+1], n) or None to decline; with
        ``begin`` the begun launch, with ``begun`` its finish."""
        return staged_match(
            self.rules, self._program, self._lane(), self.raw_timings,
            data, n_records, max_len=self.tpu_max_record_len,
            min_records=self.tpu_batch_records, mesh=mesh, **halves)
