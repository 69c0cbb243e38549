"""The few msgpack shapes the Forward protocol needs, stdlib only.

A copy of the ``str`` / ``dict`` / ``list`` / ``EventTime`` branches of
``fluentbit_tpu/codec/msgpack.py::_pack`` (smallest header that fits), so
that the generator process imports nothing of the program, and so that
the reference can say byte for byte what a surviving record looks like
at the output: ``[[EventTime, {}], body]`` (log event format V2).
"""

import struct

#: bits of a line's construction label in the corpus file, which crosses
#: from the generator to the aggregator like the frames do
KEEP = 1   # the filter chain keeps the line
LONG = 2   # longer than the filter's max record length: an overflow row

_ACK_PREFIX = b"\x81\xa3ack"


def pack_str(s) -> bytes:
    b = s.encode("utf-8") if isinstance(s, str) else bytes(s)
    n = len(b)
    if n < 32:
        return bytes((0xA0 | n,)) + b
    if n <= 0xFF:
        return struct.pack(">BB", 0xD9, n) + b
    if n <= 0xFFFF:
        return struct.pack(">BH", 0xDA, n) + b
    return struct.pack(">BI", 0xDB, n) + b


def pack_str_map(d: dict) -> bytes:
    """``{str: str}`` with fewer than 16 keys, in insertion order."""
    if len(d) >= 16:
        raise ValueError("pack_str_map takes fewer than 16 keys")
    return bytes((0x80 | len(d),)) + b"".join(
        pack_str(k) + pack_str(v) for k, v in d.items())


def array_header(n: int) -> bytes:
    if n < 16:
        return bytes((0x90 | n,))
    if n <= 0xFFFF:
        return struct.pack(">BH", 0xDC, n)
    return struct.pack(">BI", 0xDD, n)


def event_time(wall_ns: int) -> bytes:
    """Fluentd EventTime (fixext8, type 0): seconds, nanoseconds."""
    sec, nsec = divmod(wall_ns, 1_000_000_000)
    return b"\xd7\x00" + struct.pack(">II", sec & 0xFFFFFFFF, nsec)


def forward_frame(tag: bytes, wall_ns: int, bodies: list,
                  chunk_id: str) -> bytes:
    """Forward mode: ``[tag, [[time, record], ...], {"chunk": id}]``.
    ``tag`` is already packed; every entry carries the frame's time."""
    entry = b"\x92" + event_time(wall_ns)
    return b"".join((b"\x93", tag, array_header(len(bodies)), entry,
                     entry.join(bodies),
                     pack_str_map({"chunk": chunk_id})))


def bin_header(n: int) -> bytes:
    if n <= 0xFF:
        return struct.pack(">BB", 0xC4, n)
    if n <= 0xFFFF:
        return struct.pack(">BH", 0xC5, n)
    return struct.pack(">BI", 0xC6, n)


def pack_uint(n: int) -> bytes:
    if n < 0x80:
        return bytes((n,))
    if n <= 0xFF:
        return struct.pack(">BB", 0xCC, n)
    if n <= 0xFFFF:
        return struct.pack(">BH", 0xCD, n)
    return struct.pack(">BI", 0xCE, n)


def packed_forward_frame(tag: bytes, wall_ns: int, bodies: list,
                         chunk_id: str) -> bytes:
    """PackedForward, as upstream's ``out_forward`` sends it between two
    Fluent Bits: ``[tag, bin(entry ‖ body ‖ entry ‖ body …), {"size":
    n, "chunk": id}]`` — the same entries as :func:`forward_frame`'s,
    concatenated in one ``bin`` and not in an array."""
    entry = b"\x92" + event_time(wall_ns)
    blob = (entry + entry.join(bodies)) if bodies else b""
    return b"".join((b"\x93", tag, bin_header(len(blob)), blob,
                     b"\x82", pack_str("size"), pack_uint(len(bodies)),
                     pack_str("chunk"), pack_str(chunk_id)))


#: how a traffic file's ``mode`` packs a frame; ``forward`` where it has none
FRAMERS = {"forward": forward_frame, "packed": packed_forward_frame}


def ack_message(chunk_id: str) -> bytes:
    """What in_forward answers: ``{"ack": chunk_id}``."""
    return _ACK_PREFIX + pack_str(chunk_id)


def output_events(wall_ns: int, bodies: list) -> bytes:
    """The same records as the output sees them: V2 log events
    ``[[time, {}], body]``, concatenated."""
    if not bodies:
        return b""
    head = b"\x92\x92" + event_time(wall_ns) + b"\x80"
    return head + head.join(bodies)


def unpack_str_map(b: bytes) -> dict:
    """The inverse of :func:`pack_str_map`, for the reference's use."""
    def read_str(pos):
        t = b[pos]
        if 0xA0 <= t <= 0xBF:
            n, pos = t & 0x1F, pos + 1
        elif t == 0xD9:
            n, pos = b[pos + 1], pos + 2
        elif t == 0xDA:
            n, pos = struct.unpack_from(">H", b, pos + 1)[0], pos + 3
        elif t == 0xDB:
            n, pos = struct.unpack_from(">I", b, pos + 1)[0], pos + 5
        else:
            raise ValueError(f"not a str at {pos}: {t:#x}")
        return b[pos:pos + n].decode("utf-8"), pos + n

    if not 0x80 <= b[0] <= 0x8F:
        raise ValueError("not a fixmap")
    out, pos = {}, 1
    for _ in range(b[0] & 0x0F):
        k, pos = read_str(pos)
        out[k], pos = read_str(pos)
    return out
