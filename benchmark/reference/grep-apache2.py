"""The plain reference of ``grep-apache2``: what filter_grep in legacy
mode means, written with Python's ``re`` from the rules of the pipeline
file, over every distinct line of the corpus. Three verdicts per line
must agree: the construction label the corpus maker gave it, this
reference, and (on every 16th line and every long one) the program's
per-record host chain (``tpu.enable off``).
``run.py`` then holds the output to the labels, byte for byte, in order.
Beside it, the program's own counters must say that the device did the
matching: every record's segment went through the device lane
(``device_records`` = records in; it counts the overflow rows among
them), the overflow rows it decided on the host = long lines sent, and
every child of the filter's program resolved to a device kernel —
whichever: the deployment needs the chip to match, not one kernel to.
"""

import re

import wire
from wire import KEEP, LONG

HOST_CHAIN_EVERY = 16
DEVICE_KERNELS = ("scan", "assoc")


def children_on_device_kernels(programs: list) -> bool:
    """Every child of every program (a program without children is its
    own) resolved to one of the device kernels. ``kernel_resolved`` is
    ``None`` on a child that never materialised on the backend: what it
    decided, the host decided."""
    children = [ch for p in programs for ch in (p._children or [p])]
    return bool(children) and all(
        ch.kernel_resolved in DEVICE_KERNELS for ch in children)


def rules_of(pipeline_path: str) -> list:
    """``[(exclude?, field, compiled pattern)]`` in file order."""
    rules, in_grep = [], False
    with open(pipeline_path) as f:
        for raw in f:
            line = raw.strip()
            if line.startswith("["):
                in_grep = False
                continue
            parts = line.split(None, 1)
            if len(parts) < 2 or line.startswith("#"):
                continue
            key, val = parts[0].lower(), parts[1].strip()
            if key == "name":
                in_grep = val.lower() == "grep"
            elif in_grep and key in ("regex", "exclude"):
                field, pattern = val.split(None, 1)
                # Onigmo's (?<name>...) is Python's (?P<name>...)
                pattern = re.sub(r"\(\?<([A-Za-z_])", r"(?P<\1", pattern)
                rules.append((key == "exclude", field, re.compile(pattern)))
    return rules


def keep(rules: list, record: dict) -> bool:
    """Legacy mode: the first rule that decides, decides — an Exclude
    that matches drops, a Regex that does not match drops."""
    for exclude, field, pattern in rules:
        value = record.get(field)
        hit = value is not None and pattern.search(value) is not None
        if hit == exclude:
            return False
    return True


def checks(run: dict) -> dict:
    cell, labels, c = run["cell"], run["labels"], run["counters"]
    records = [wire.unpack_str_map(b) for b in run["bodies"]]
    rules = rules_of(cell.pipeline_path)
    plain = bytes(keep(rules, r) for r in records)
    want = bytes(lb & KEEP for lb in labels)

    # the per-record host chain costs ~50 us a line: every 16th line and
    # every line outside the short length bucket, not all 262,144
    sample = [i for i, b in enumerate(run["bodies"])
              if i % HOST_CHAIN_EVERY == 0 or len(b) > 200]
    host = run["reference_pipeline"]([("tpu.enable", "off")])
    host.ctx.start()  # plugin init happens at start
    try:
        chain = [p for p in host.filters if p.name == "grep"]
        no_program = all(p._program is None for p in chain)
        host_verdict = bytes(all(p.keep_record(records[i]) for p in chain)
                             for i in sample)
    finally:
        host.ctx.stop()

    long_sent = sum(n for n, lb in zip(run["line_counts"], labels)
                    if lb & LONG)
    out = {
        "plain_reference_equal_construction_labels": plain == want,
        "host_chain_equal_construction_labels":
            host_verdict == bytes(want[i] for i in sample),
        "host_chain_built_no_device_program": no_program,
        "filter_kept_some_not_all": 0 < sum(want) < len(want),
    }
    device = {
        "device_records_equal_records_in":
            c.get("filter.grep.device_records") == c["engine.records_in"],
        "overflow_rows_equal_long_lines_sent":
            c.get("filter.grep.overflow_rows") == long_sent,
        "every_child_on_a_device_kernel_none_on_the_host":
            children_on_device_kernels([
                p._program for p in run["pipe"].filters
                if p.name == "grep" and p._program is not None]),
    }
    skipped = []
    if run["rehearse"]:
        skipped = sorted(device)
    else:
        out.update(device)
    return {"checks": out, "skipped": skipped,
            "info": {"distinct_lines": len(records), "kept": sum(want),
                     "long_lines_sent": long_sent, "rules": len(rules),
                     "host_chain_lines": len(sample)}}
