"""The plain reference of ``parser-apache2``: what filter_parser with a
regex parser means, written with the standard library alone (``re``,
``struct``, ``datetime``) from the configuration's own files — the
regex, ``Time_Key``, ``Time_Format`` and ``Types`` of the parsers file,
``Key_Name`` and ``Reserve_Data`` of the pipeline file — and sharing no
code with the program. This is the first configuration whose output is
not its input: ``expected_output`` computes, for one acked frame, the
bytes the main sink must hold —

- for a line the regex matches (``re.search``, Ruby's line anchors, so
  ``MULTILINE``): ``[[time, {}], fields]`` with the named groups in
  group order, an empty capture skipped, the ``Types`` cast applied
  (``integer``: a msgpack int where the text is one), the ``Time_Key``
  field parsed by ``Time_Format`` and dropped, the other fields of the
  record kept behind the parsed ones under ``Reserve_Data`` (the key
  itself not); the event's time is the parsed one, which the program's
  host chain writes as a msgpack float64 (whole seconds here), or the
  frame's where the field does not parse;
- for every other line its body unchanged under the frame's time.

``checks`` then holds three things to one another and to the program's
counters: this reference over every distinct line against the
construction (a kernel line never parses, every other line does), the
program's per-record host chain (``tpu.enable off``) against these
bytes on every 16th line and every long one, and the device's part —
every record's segment through the lane, the rows decided on the host
the long lines and no other, the span program built and on the device.
"""

import datetime
import os
import re
import struct

import wire
from wire import LONG

HERE = os.path.dirname(os.path.abspath(__file__))
CONFIGS = os.path.join(os.path.dirname(HERE), "configs")
HOST_CHAIN_EVERY = 16


def sections_of(path: str) -> list:
    """``[(section, {key: value})]`` of a classic-format file, keys
    lower-cased, in file order."""
    out = []
    with open(path) as f:
        for raw in f:
            line = raw.strip()
            if not line or line.startswith("#"):
                continue
            if line.startswith("["):
                out.append((line.strip("[]").lower(), {}))
                continue
            key, _, value = line.partition(" ")
            out[-1][1][key.lower()] = value.strip()
    return out


class Deployment:
    """The filter and its parser, read from the configuration's files."""

    def __init__(self, config: str = "parser-apache2"):
        pipeline = sections_of(os.path.join(CONFIGS, config + ".conf"))
        service = next(p for s, p in pipeline if s == "service")
        flt = next(p for s, p in pipeline
                   if s == "filter" and p.get("name") == "parser")
        parsers = sections_of(os.path.join(CONFIGS,
                                           service["parsers_file"]))
        parser = next(p for s, p in parsers
                      if s == "parser" and p["name"] == flt["parser"])
        self.key = flt["key_name"]
        self.reserve = flt.get("reserve_data", "off").lower() in (
            "on", "true", "yes", "1")
        # Onigmo's (?<name>...) is Python's (?P<name>...); ^ and $ are
        # line anchors in Ruby syntax
        self.regex = re.compile(
            re.sub(r"\(\?<([A-Za-z_])", r"(?P<\1", parser["regex"]),
            re.MULTILINE)
        self.groups = sorted(self.regex.groupindex,
                             key=self.regex.groupindex.get)
        self.time_key = parser.get("time_key", "time")
        self.time_format = parser.get("time_format")
        self.integers = {
            k for k, _, t in (e.partition(":")
                              for e in parser.get("types", "").split())
            if t == "integer"}
        self._times = {}

    def parsed_time(self, text: str):
        """Seconds since the epoch, or None where the text is not in
        ``Time_Format``."""
        if text not in self._times:
            try:
                self._times[text] = datetime.datetime.strptime(
                    text, self.time_format).timestamp()
            except ValueError:
                self._times[text] = None
        return self._times[text]

    def parse(self, record: dict):
        """→ ``(fields, seconds or None)`` for a record whose key
        parses, else None."""
        value = record.get(self.key)
        m = self.regex.search(value) if isinstance(value, str) else None
        if m is None:
            return None
        fields = {g: m.group(g) for g in self.groups
                  if m.group(g) not in (None, "")}
        if not fields:
            return None
        for k in self.integers & set(fields):
            if re.fullmatch(r"[+-]?\d+", fields[k]):
                fields[k] = int(fields[k])
            elif re.fullmatch(r"[+-]?(\d+\.\d*|\.\d+)", fields[k]):
                fields[k] = int(float(fields[k]))  # the cast truncates
        seconds = None
        if self.time_format and isinstance(fields.get(self.time_key), str):
            seconds = self.parsed_time(fields.pop(self.time_key))
        if self.reserve:
            for k, v in record.items():
                if k != self.key:
                    fields.setdefault(k, v)
        return fields, seconds


def pack_int(n: int) -> bytes:
    """msgpack's smallest integer that holds ``n``."""
    if n >= 0:
        if n <= 0xFFFFFFFF:
            return wire.pack_uint(n)
        return struct.pack(">BQ", 0xCF, n)
    if n >= -32:
        return struct.pack("b", n)
    for code, fmt, low in ((0xD0, ">Bb", -1 << 7), (0xD1, ">Bh", -1 << 15),
                           (0xD2, ">Bi", -1 << 31)):
        if n >= low:
            return struct.pack(fmt, code, n)
    return struct.pack(">Bq", 0xD3, n)


def pack_fields(fields: dict) -> bytes:
    """``{str: str | int}`` with fewer than 16 keys, in order."""
    if len(fields) >= 16:
        raise ValueError("pack_fields takes fewer than 16 keys")
    return bytes((0x80 | len(fields),)) + b"".join(
        wire.pack_str(k) + (pack_int(v) if isinstance(v, int)
                            else wire.pack_str(v))
        for k, v in fields.items())


def parsed_event(fields: dict, seconds) -> tuple:
    """A parsed record as the output sees it, in two parts: the bytes
    that do not depend on the frame, and whether the frame's time goes
    in front of them (the parsed time did not take)."""
    body = b"\x80" + pack_fields(fields)
    if seconds is None:
        return True, body
    return False, b"\x92\x92\xcb" + struct.pack(">d", seconds) + body


_deployment = None
_lines = {}


def line_parts(bodies: list) -> list:
    """For every distinct line of the corpus: ``(frame time in front?,
    bytes)`` — a parsed record, or the body unchanged behind ``{}``."""
    global _deployment
    if _deployment is None:
        _deployment = Deployment()
    key = id(bodies)
    if key not in _lines:
        parts = []
        for b in bodies:
            got = _deployment.parse(wire.unpack_str_map(b))
            parts.append((True, b"\x80" + b) if got is None
                         else parsed_event(*got))
        _lines.clear()
        _lines[key] = parts
    return _lines[key]


def expected_output(frame: dict, bodies: list, labels: bytes, wire) -> bytes:
    """The bytes the main sink must hold for one acked frame."""
    parts = line_parts(bodies)
    head = b"\x92\x92" + wire.event_time(frame["wall_ns"])
    lo = frame["slot"] * frame["lines"]
    return b"".join((head + data) if timed else data
                    for timed, data in parts[lo:lo + frame["lines"]])


def checks(run: dict) -> dict:
    cell, labels, c = run["cell"], run["labels"], run["counters"]
    bodies = run["bodies"]
    parts = line_parts(bodies)
    records = [wire.unpack_str_map(b) for b in bodies]
    parsed = [_deployment.parse(r) is not None for r in records]
    # the construction: a kernel line matches nothing, every other line
    # is an access line (every line the grep chain keeps among them)
    access = [not r["log"].startswith("kernel: ") for r in records]
    kept_parse = all(p for p, lb in zip(parsed, labels)
                     if lb & (wire.KEEP | LONG))

    # the per-record host chain costs ~50 us a line: every 16th line and
    # every line outside the short length bucket, not all 262,144
    from fluentbit_tpu.codec.events import decode_events, reencode_event

    sample = [i for i, b in enumerate(bodies)
              if i % HOST_CHAIN_EVERY == 0 or len(b) > 200]
    wall_ns = 1_700_000_000_123_456_789
    head = b"\x92\x92" + wire.event_time(wall_ns)
    host = run["reference_pipeline"]([("tpu.enable", "off")])
    host.ctx.start()  # plugin init happens at start
    try:
        chain = [p for p in host.filters if p.name == "parser"]
        no_program = all(p._spans is None and p._prefilter is None
                         for p in chain)
        differs = 0
        for at in range(0, len(sample), 4096):
            idx = sample[at:at + 4096]
            events = decode_events(wire.output_events(
                wall_ns, [bodies[i] for i in idx]))
            for p in chain:
                events = p.filter(events, cell.config["tag"],
                                  host.engine)[1]
            for i, ev in zip(idx, events):
                got = ev.raw if ev.raw is not None else reencode_event(ev)
                timed, data = parts[i]
                differs += got != ((head + data) if timed else data)
    finally:
        host.ctx.stop()

    long_sent = sum(n for n, lb in zip(run["line_counts"], labels)
                    if lb & LONG)
    access_sent = sum(n for n, a in zip(run["line_counts"], access) if a)
    out = {
        "plain_reference_parses_access_lines_and_no_other":
            parsed == access and kept_parse,
        "host_chain_equal_plain_reference_bytes": differs == 0 < len(sample),
        "host_chain_built_no_device_program": no_program,
        "filter_parsed_some_not_all": 0 < sum(parsed) < len(parsed),
    }
    pre = "filter.parser."
    served = [p for p in run["pipe"].filters if p.name == "parser"]
    device = {
        "device_records_equal_records_in":
            c.get(pre + "device_records") == c["engine.records_in"],
        "overflow_rows_equal_long_lines_sent":
            c.get(pre + "overflow_rows") == long_sent,
        "host_rows_are_the_overflow_rows_and_no_other":
            c.get(pre + "host_rows") == long_sent,
        "parsed_equal_access_lines_sent":
            c.get(pre + "parsed") == access_sent,
        "span_program_built_and_on_the_device": bool(served) and all(
            getattr(p, "_spans", None) is not None
            and p._spans.kernel_resolved == "spans" for p in served),
    }
    skipped = []
    if run["rehearse"]:
        skipped = sorted(device)
    else:
        out.update(device)
    return {"checks": out, "skipped": skipped,
            "info": {"distinct_lines": len(records),
                     "parsed": sum(parsed), "long_lines_sent": long_sent,
                     "access_lines_sent": access_sent,
                     "groups": _deployment.groups,
                     "host_chain_lines": len(sample),
                     "host_chain_lines_that_differ": differs}}
