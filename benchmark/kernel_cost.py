"""What a kernel's work needs, from its shapes alone, and the chip's
peaks. Kept with the benchmark so that no PR that claims a gain can
change the yardstick."""

import json
import os

HERE = os.path.dirname(os.path.abspath(__file__))


def peaks(device_kind: str) -> dict:
    with open(os.path.join(HERE, "peaks.json")) as f:
        table = json.load(f)["by_device_kind"]
    if device_kind not in table:
        raise KeyError(f"no peaks for device kind {device_kind!r}: add it "
                       f"to benchmark/peaks.json with its source")
    return table[device_kind]


def grep_match_bytes(rules: list, plane_bytes: float, rows: float) -> float:
    """Bytes one grep launch has to move through HBM at least once: the
    staged ``[R, B, L]`` u8 planes with their ``[R, B]`` i32 lengths
    (``plane_bytes``, as the plugin counts host-to-device bytes), each
    rule's stride-k transition table (``S * C**k`` i32) and byte-class
    map (257 i32), and the ``[R, B]`` one-byte verdicts. The match is a
    chain of dependent gathers, so this is the bytes bound only."""
    tables = sum(4 * (r["s"] * r["c"] ** r["k"] + 257) for r in rules)
    return plane_bytes + tables + len(rules) * rows
