#!/usr/bin/env python3
"""The load generator: its own process, stdlib only.

Started by ``run.py`` before that process touches JAX; imports neither
``jax`` nor ``fluentbit_tpu``. It makes the configuration's corpus from
the seed, writes it to files the aggregator's process reads after the
window, and replays it over TCP loopback under the traffic file's kind
(``traffic_kinds/<kind>.py``), packed as its ``mode`` says: ``forward``
(the default) ``[tag, [[time, record], ...], {"chunk": id}]``, or
``packed``, the same entries as one PackedForward ``bin``. Every frame
is logged: when it was due, created, sent and acked
(``time.monotonic_ns``, one clock for both processes on the one
machine), and the wall-clock creation time its records carry as their
Forward ``time``.

Commands arrive as JSON lines on stdin (``connect``, ``go``); events
leave as JSON lines on stdout (``corpus``, ``warm``, ``done``).
"""

import argparse
import array
import json
import os
import socket
import sys
import threading
import time

HERE = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, HERE)

import wire  # noqa: E402
from lookup import load_json, load_py  # noqa: E402

FRAME_COLUMNS = ("idx", "phase", "slot", "lines", "due_ns", "created_ns",
                 "wall_ns", "sent_ns", "ack_ns")
WARM_ROUNDS = 2     # each warm-up shape twice: compile or load, then run
WARM_ACK_S = 600.0  # a cold first frame waits for its compiles
DRAIN_GRACE_S = 15.0


def say(**kw) -> None:
    print(json.dumps(kw), flush=True)


def command(expect: str) -> dict:
    line = sys.stdin.readline()
    if not line:
        raise SystemExit("generator: the aggregator closed the pipe")
    msg = json.loads(line)
    if msg.get("cmd") != expect:
        raise SystemExit(f"generator: expected {expect!r}, got {msg!r}")
    return msg


class Link:
    """One Forward connection: frames out, acks back, every frame logged.
    Acks return in the order the frames went (one connection, served in
    order); anything else breaks the link and fails the run."""

    def __init__(self, sock, tag: str, bodies: list, frame_lines: int,
                 nonce: int, mode: str = "forward"):
        self.sock = sock
        self.frame = wire.FRAMERS[mode]
        self.tag = wire.pack_str(tag)
        self.bodies = bodies
        self.frame_lines = frame_lines
        self.slots = len(bodies) // frame_lines
        self.nonce = nonce
        self.rows = []          # one list per frame, FRAME_COLUMNS order
        self.n_acked = 0
        self.broken = None
        self.acked = threading.Semaphore(0)
        self._last_wall = 0
        self._reader = threading.Thread(target=self._read_acks, daemon=True)
        self._reader.start()

    def chunk_id(self, idx: int) -> str:
        return "%08x%08x" % (self.nonce & 0xFFFFFFFF, idx)

    def send(self, slot: int, due_ns: int, phase: str) -> None:
        idx = len(self.rows)
        lo = (slot % self.slots) * self.frame_lines
        created = time.monotonic_ns()
        wall = max(time.time_ns(), self._last_wall + 1)
        self._last_wall = wall
        frame = self.frame(self.tag, wall,
                           self.bodies[lo:lo + self.frame_lines],
                           self.chunk_id(idx))
        row = [idx, phase, slot % self.slots, self.frame_lines, due_ns,
               created, wall, 0, 0]
        self.rows.append(row)
        self.sock.sendall(frame)
        row[7] = time.monotonic_ns()

    def _read_acks(self) -> None:
        want = len(wire.ack_message(self.chunk_id(0)))
        try:
            while True:
                buf = b""
                while len(buf) < want:
                    part = self.sock.recv(want - len(buf))
                    if not part:
                        return
                    buf += part
                now = time.monotonic_ns()
                if buf != wire.ack_message(self.chunk_id(self.n_acked)):
                    self.broken = f"ack {self.n_acked} out of order: {buf!r}"
                    return
                self.rows[self.n_acked][8] = now
                self.n_acked += 1
                self.acked.release()
        except OSError as e:
            self.broken = f"ack reader: {e!r}"
        finally:
            self.acked.release()  # never leave a sender waiting

    def wait_ack(self, timeout_s: float) -> bool:
        """One more ack than before, or False on timeout / broken link."""
        return self.acked.acquire(timeout=timeout_s) and not self.broken

    def drain(self, grace_s: float) -> None:
        deadline = time.monotonic() + grace_s
        while self.n_acked < len(self.rows) and not self.broken \
                and time.monotonic() < deadline:
            time.sleep(0.005)


def write_corpus(work: str, bodies: list, labels: bytes) -> int:
    offsets = array.array("Q", [0])
    total = 0
    for b in bodies:
        total += len(b)
        offsets.append(total)
    with open(os.path.join(work, "corpus.bodies"), "wb") as f:
        f.write(b"".join(bodies))
    with open(os.path.join(work, "corpus.offsets"), "wb") as f:
        offsets.tofile(f)
    with open(os.path.join(work, "corpus.labels"), "wb") as f:
        f.write(labels)
    return total


def length_bucket(bodies, labels, lo: int, n: int, buckets: list) -> int:
    """The staging shape of the frame ``bodies[lo:lo + n]``: the length
    bucket its longest line falls in, overflow rows aside — a frame with
    one long line stages whole at the wider shape."""
    longest = max((len(bodies[i]) for i in range(lo, lo + n)
                   if not labels[i] & wire.LONG), default=0)
    return next((b for b in buckets if longest <= b), buckets[-1])


def warm_slots(bodies, labels, frame_lines: int, buckets: list) -> list:
    """One frame slot for each staging shape the cell's traffic makes:
    the first slot whose frame falls in each of the configuration's
    length buckets."""
    if not buckets:
        return [0]
    found = {}
    for slot in range(len(bodies) // frame_lines):
        found.setdefault(length_bucket(bodies, labels, slot * frame_lines,
                                       frame_lines, buckets), slot)
        if len(found) == len(buckets):
            break
    return [found[b] for b in sorted(found)]


def main(argv=None) -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--config", required=True)
    ap.add_argument("--traffic", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--work", required=True)
    args = ap.parse_args(argv)
    config, traffic = load_json(args.config), load_json(args.traffic)
    corpus, frame_lines = config["corpus"], int(traffic["frame_lines"])
    if corpus["lines"] % frame_lines:
        raise SystemExit("generator: corpus lines must be a multiple of "
                         "frame_lines")

    t0 = time.monotonic()
    maker = load_py("corpora", corpus["maker"])
    records, labels = maker.make(int(corpus["lines"]), args.seed,
                                 corpus.get("params", {}))
    bodies = [wire.pack_str_map(r) for r in records]
    del records
    n_bytes = write_corpus(args.work, bodies, labels)
    say(event="corpus", seconds=time.monotonic() - t0, lines=len(bodies),
        bytes=n_bytes, slots=len(bodies) // frame_lines)

    port = int(command("connect")["port"])
    sock = socket.create_connection(("127.0.0.1", port), timeout=30)
    sock.settimeout(None)
    sock.setsockopt(socket.IPPROTO_TCP, socket.TCP_NODELAY, 1)
    link = Link(sock, config["tag"], bodies, frame_lines, args.seed,
                traffic.get("mode", "forward"))
    kind = load_py("traffic_kinds", traffic["kind"])
    try:
        warm = warm_slots(bodies, labels, frame_lines,
                          config.get("length_buckets", []))
        for slot in warm * WARM_ROUNDS:
            link.send(slot, time.monotonic_ns(), "warm")
            if not link.wait_ack(WARM_ACK_S):
                raise SystemExit(f"generator: warm-up frame not acked "
                                 f"({link.broken})")
        say(event="warm", frames=len(link.rows), slots=warm)

        seconds = float(command("go")["seconds"])
        start = time.monotonic_ns()
        kind.run(link, traffic, start, seconds)
        link.drain(DRAIN_GRACE_S)
    finally:
        with open(os.path.join(args.work, "frames.csv"), "w") as f:
            f.write(",".join(FRAME_COLUMNS) + "\n")
            for row in link.rows:
                f.write(",".join(map(str, row)) + "\n")
        sock.close()
    say(event="done", start_ns=start, seconds=seconds,
        frames=len(link.rows), acked=link.n_acked, broken=link.broken)
    return 0


if __name__ == "__main__":
    sys.exit(main())
