"""NEXmark's event stream for the ``nexmark-q5`` configuration, stdlib only.

The generator of Apache Beam's ``nexmark`` suite as the configuration's
``assumed`` list describes it (written down from memory of that source:
there is no network here), flat records as a log shipper would send
them:

- of every 50 events 1 is a person, 3 are auctions, 46 are bids
  (event ``i``: ``i % 50`` 0 / 1-3 / 4-49); ``dateTime`` is
  ``base_ms + i // 10`` (10,000 events a second); ids count from 1,000;
- a bid's ``auction``: one time in ``hot_auction_ratio`` one of the
  auctions still in flight (the last ``in_flight_auctions``, and up to
  ten ahead), else the hot one — the last auction id rounded down to a
  hundred; its ``bidder`` likewise over ``active_people`` with
  ``hot_bidders_ratio``; its ``price`` ``round(10**(6u) * 100)``;
- an auction's ``seller`` the same with ``hot_sellers_ratio``, five
  categories from 10, ``reserve`` above ``initialBid``;
- ``extra`` pads a record to its kind's mean size (the rule of the
  source: nothing where the record is over it already, else the
  remainder give or take a fifth). A bid's keyed msgpack body is at
  its 100 bytes, nearly, before any padding: its ``extra`` is a letter
  or none, and ``url`` is a short path for that reason.

Integers are msgpack integers (smallest header), which
``generator.py``'s ``wire.pack_str_map`` cannot write: :func:`make`
hands it each record as a :class:`Packed`, whose one ``str`` value
carries the rest of the map already packed (PERF.md section 7 asks a
``benchmark`` PR for a maker's own ``pack``). :func:`events` gives the
same records as plain dicts, :func:`unpack` reads a body back.
"""

import functools
import random
import struct

from wire import KEEP, pack_str

PROPORTION = 50          # 1 person, 3 auctions, 46 bids
FIRST_ID = 1000
HOT_RATIO = 100          # the hot auction / seller / bidder: id // 100 * 100
ID_LEAD = 10
CHANNELS = ("Google", "Facebook", "Baidu", "Apple")
FIRST_NAMES = ("Peter", "Paul", "Luke", "John", "Saul", "Vicky", "Kate",
               "Julie", "Sarah", "Deiter", "Walter")
LAST_NAMES = ("Shultz", "Abrams", "Spencer", "White", "Bartels", "Walton",
              "Smith", "Jones", "Noris")
CITIES = ("Phoenix", "Los Angeles", "San Francisco", "Boise", "Portland",
          "Bend", "Redmond", "Seattle", "Kent", "Cheyenne")
STATES = ("AZ", "CA", "ID", "OR", "WA", "WY")
_TO_LETTERS = bytes(97 + b % 26 for b in range(256))   # any byte → a-z


def pack_int(v: int) -> bytes:
    """A msgpack integer, smallest header that fits."""
    if v >= 0:
        if v < 0x80:
            return bytes((v,))
        if v <= 0xFF:
            return struct.pack(">BB", 0xCC, v)
        if v <= 0xFFFF:
            return struct.pack(">BH", 0xCD, v)
        if v <= 0xFFFFFFFF:
            return struct.pack(">BI", 0xCE, v)
        return struct.pack(">BQ", 0xCF, v)
    if v >= -32:
        return struct.pack(">b", v)
    if v >= -0x80:
        return struct.pack(">Bb", 0xD0, v)
    if v >= -0x8000:
        return struct.pack(">Bh", 0xD1, v)
    if v >= -0x80000000:
        return struct.pack(">Bi", 0xD2, v)
    return struct.pack(">Bq", 0xD3, v)


_pack_key = functools.cache(pack_str)     # a dozen keys, packed once


def pack_pairs(record: dict) -> bytes:
    """The key/value pairs of a flat ``{str: str | int}`` record."""
    return b"".join(
        _pack_key(k) + (pack_int(v) if isinstance(v, int) else pack_str(v))
        for k, v in record.items())


def pack(record: dict) -> bytes:
    """A flat ``{str: str | int}`` record of fewer than 16 keys as the
    msgpack map the generator sends."""
    return bytes((0x80 | len(record),)) + pack_pairs(record)


def unpack(body: bytes) -> dict:
    """The inverse of :func:`pack`: a fixmap of ``str`` keys with
    ``str`` or integer values."""
    def one(pos):
        t = body[pos]
        if t < 0x80:
            return t, pos + 1
        if t >= 0xE0:
            return t - 0x100, pos + 1
        if 0xA0 <= t <= 0xBF:
            n, pos = t & 0x1F, pos + 1
        elif t in (0xD9, 0xDA, 0xDB):
            w = {0xD9: 1, 0xDA: 2, 0xDB: 4}[t]
            n = int.from_bytes(body[pos + 1:pos + 1 + w], "big")
            pos += 1 + w
        elif 0xCC <= t <= 0xCF or 0xD0 <= t <= 0xD3:
            w = 1 << ((t - 0xCC) & 3)
            return int.from_bytes(body[pos + 1:pos + 1 + w], "big",
                                  signed=t >= 0xD0), pos + 1 + w
        else:
            raise ValueError(f"neither str nor int at {pos}: {t:#x}")
        return body[pos:pos + n].decode("utf-8"), pos + n

    if not 0x80 <= body[0] <= 0x8F:
        raise ValueError("not a fixmap")
    out, pos = {}, 1
    for _ in range(body[0] & 0x0F):
        k, pos = one(pos)
        out[k], pos = one(pos)
    if pos != len(body):
        raise ValueError("bytes after the map")
    return out


class _Carrier(bytes):
    """Bytes that give the length of their first part only: the length
    ``wire.pack_str`` writes in the ``str`` header it puts before them."""

    def __new__(cls, own: bytes, rest: bytes):
        self = super().__new__(cls, own + rest)
        self.own = len(own)
        return self

    def __len__(self):
        return self.own


class _Value(str):
    """A ``str`` value whose encoding carries the rest of its record."""

    def encode(self, *_args, **_kw):
        return self.carrier


class Packed:
    """One record as ``wire.pack_str_map`` takes it: the map's size, and
    one pair — the first key, and its ``str`` value followed by every
    other pair of the record, already packed. What leaves
    ``pack_str_map`` is :func:`pack` of the record, byte for byte."""

    __slots__ = ("n", "pair")

    def __init__(self, record: dict, pairs: bytes):
        """``pairs``: :func:`pack_pairs` of the record."""
        key, first = next(iter(record.items()))
        if not isinstance(first, str):
            raise TypeError("a record's first value must be a str")
        value = _Value(first)
        value.carrier = _Carrier(
            first.encode("utf-8"),
            pairs[len(_pack_key(key)) + len(pack_str(first)):])
        self.n, self.pair = len(record), (key, value)

    def __len__(self):
        return self.n

    def items(self):
        return (self.pair,)


class _Stream:
    """The generator's state: a seeded ``random.Random`` and the event
    number everything else follows from."""

    def __init__(self, seed: int, params: dict):
        self.rng = random.Random(seed)
        self.base_ms = int(params.get("base_time_ms", 1_436_918_400_000))
        self.hot_auction = int(params.get("hot_auction_ratio", 2))
        self.hot_bidders = int(params.get("hot_bidders_ratio", 4))
        self.hot_sellers = int(params.get("hot_sellers_ratio", 4))
        self.in_flight = int(params.get("in_flight_auctions", 100))
        self.active_people = int(params.get("active_people", 1000))
        self.mean = {"person": int(params.get("person_bytes", 200)),
                     "auction": int(params.get("auction_bytes", 500)),
                     "bid": int(params.get("bid_bytes", 100))}

    def text(self, n: int) -> str:
        return self.rng.randbytes(n).translate(_TO_LETTERS).decode()

    def price(self) -> int:
        return round(10 ** (self.rng.random() * 6) * 100)

    @staticmethod
    def last_person(i: int) -> int:
        """Base-0 id of the last person made at or before event ``i``."""
        return i // PROPORTION

    @staticmethod
    def last_auction(i: int) -> int:
        epoch, offset = divmod(i, PROPORTION)
        offset = 0 if offset < 1 else min(offset - 1, 2)
        return epoch * 3 + offset

    def some_person(self, i: int) -> int:
        people = self.last_person(i) + 1
        active = min(people, self.active_people)
        return people - active + self.rng.randrange(active + ID_LEAD)

    def some_auction(self, i: int) -> int:
        last = self.last_auction(i)
        first = max(last - self.in_flight, 0)
        return first + self.rng.randrange(last - first + 1 + ID_LEAD)

    def padded(self, kind: str, record: dict) -> tuple:
        """``extra`` by the source's rule: the room left to the kind's
        mean, give or take a fifth; nothing where there is none.
        → (the record, its packed pairs)."""
        pairs = pack_pairs(record) + _pack_key("extra")
        extra = ""
        room = self.mean[kind] - (1 + len(pairs) + 1)
        if room > 0:
            delta = round(room * 0.2)
            size = room - delta + (self.rng.randrange(2 * delta)
                                   if delta else 0)
            # a str of 32 bytes and more takes a longer header
            extra = self.text(size - (1 if size >= 32 else 0)
                              - (1 if size >= 257 else 0))
        record["extra"] = extra
        return record, pairs + pack_str(extra)

    def event(self, i: int) -> tuple:
        """Event ``i`` → (the record, its packed pairs)."""
        rng, rem = self.rng, i % PROPORTION
        now = self.base_ms + i // 10
        if rem < 1:
            return self.padded("person", {
                "event_type": "person", "dateTime": now,
                "id": self.last_person(i) + FIRST_ID,
                "name": f"{rng.choice(FIRST_NAMES)} "
                        f"{rng.choice(LAST_NAMES)}",
                "emailAddress": f"{self.text(7)}@{self.text(5)}.com",
                "creditCard": " ".join("%04d" % rng.randrange(10000)
                                       for _ in range(4)),
                "city": rng.choice(CITIES), "state": rng.choice(STATES)})
        if rem < 4:
            if rng.randrange(self.hot_sellers) > 0:
                seller = self.last_person(i) // HOT_RATIO * HOT_RATIO
            else:
                seller = self.some_person(i)
            first_bid = self.price()
            return self.padded("auction", {
                "event_type": "auction", "dateTime": now,
                "id": self.last_auction(i) + FIRST_ID,
                "itemName": self.text(20), "description": self.text(100),
                "initialBid": first_bid,
                "reserve": first_bid + self.price(),
                "expires": now + 1 + rng.randrange(20_000),
                "seller": seller + FIRST_ID,
                "category": 10 + rng.randrange(5)})
        if rng.randrange(self.hot_auction) > 0:
            auction = self.last_auction(i) // HOT_RATIO * HOT_RATIO
        else:
            auction = self.some_auction(i)
        if rng.randrange(self.hot_bidders) > 0:
            bidder = self.last_person(i) // HOT_RATIO * HOT_RATIO + 1
        else:
            bidder = self.some_person(i)
        if rng.randrange(10) > 0:
            c = rng.randrange(len(CHANNELS))
            channel, url = CHANNELS[c], f"/hot/{c}"
        else:
            c = rng.randrange(10_000)
            channel, url = f"channel-{c}", f"/c/{c}"
        return self.padded("bid", {
            "event_type": "bid", "dateTime": now,
            "auction": auction + FIRST_ID, "bidder": bidder + FIRST_ID,
            "price": self.price(), "channel": channel, "url": url})


def events(n: int, seed: int, params: dict) -> list:
    """The first ``n`` events of the seeded stream, as plain dicts."""
    stream = _Stream(seed, params)
    return [stream.event(i)[0] for i in range(n)]


def make(n: int, seed: int, params: dict):
    stream = _Stream(seed, params)
    made = [stream.event(i) for i in range(n)]
    labels = bytes(KEEP if r["event_type"] == "bid" else 0
                   for r, _pairs in made)
    return [Packed(r, pairs) for r, pairs in made], labels
