"""Access-log lines for the grep configuration, stdlib only.

The record shape of ``chip_smoke.grep_corpus`` (itself
``bench.make_corpus``'s): a quarter kernel lines that match nothing,
apache2 access lines otherwise, one agent in three ``curl/8.5.0`` (the
``Exclude`` rule), every 20,000th line with a ~300-byte agent (the 512
length bucket) and every 50,000th with a ~700-byte one (longer than
``tpu_max_record_len``: an overflow row). Unlike the original, the mix
is exact and only its order comes from the seed, so every seed gives
the filter the same work in another order.

``make(n, seed, params)`` → ``(records, labels)``: ``records[i]`` is the
``{str: str}`` record, ``labels[i]`` its construction label — bit 0: the
chain keeps it, bit 1: longer than ``tpu_max_record_len``.
"""

import random

from wire import KEEP, LONG
METHODS = ("GET", "POST", "PUT", "DELETE", "HEAD")
AGENTS = ("Mozilla/5.0 (X11; Linux x86_64)", "curl/8.5.0",
          "kube-probe/1.29")
CODES = (200, 301, 404, 500)


def make(n: int, seed: int, params: dict):
    rng = random.Random(seed)
    # one line in four is a kernel line, the others take the three
    # agents in turn: exact shares, shuffled once
    kind = [i % (len(AGENTS) + 1) for i in range(n)]
    rng.shuffle(kind)
    every_mid = int(params.get("bucket512_every", 20000))
    every_long = int(params.get("overflow_every", 50000))
    records, labels = [], bytearray(n)
    for i in range(n):
        long_line = i % every_long == every_long - 1
        mid_line = i % every_mid == every_mid - 1
        if kind[i] == len(AGENTS) and not (long_line or mid_line):
            line = f"kernel: oom-killer invoked pid={rng.randrange(1 << 16)}"
            label = 0
        else:
            ag = AGENTS[kind[i] % len(AGENTS)]
            if long_line:
                ag = "Mozilla/5.0 " + "x" * 700
            elif mid_line:
                ag = "Mozilla/5.0 " + "y" * 300
            line = (
                f"10.{rng.randrange(256)}.{rng.randrange(256)}."
                f"{rng.randrange(256)} "
                f"- {'frank' if rng.random() < 0.5 else '-'} "
                f"[10/Oct/2000:13:55:{i % 60:02d} -0700] "
                f'"{rng.choice(METHODS)} /path/{rng.randrange(10000)} '
                f'HTTP/1.1" {rng.choice(CODES)} {rng.randrange(1 << 20)} '
                f'"http://referer.example/{i // 16384}" "{ag}"')
            label = (0 if "curl/8.5" in ag else KEEP) | \
                (LONG if long_line else 0)
        records.append({"log": line})
        labels[i] = label
    return records, bytes(labels)
