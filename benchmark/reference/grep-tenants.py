"""The plain reference of ``grep-tenants``: what one filter_grep with 50
``Exclude`` lines on ``log`` means in legacy mode, written with
Python's ``re`` from the rules of the pipeline file, in file order, over
every distinct record of the corpus (``reference/grep-apache2.py``'s
scheme, in a copy of its own). Three verdicts per record must agree: the
construction label the corpus maker gave it (it knows which template
matches which rule and never runs one), this reference, and — on every
64th record, every eighth of those whose ``log`` is 257-512 bytes and
every longer one (the overflow rows) — the program's per-record host
chain (``tpu.enable off``): it walks 50 automata a record at a
microsecond a byte, and the whole run has to end inside the time a run
is given, so the sample is a twentieth of the corpus's bytes (every 16th
record and every one over 256 bytes took 55 s on the chip's host).
``run.py`` then holds the main sink to the kept bodies, byte for byte,
in order.

Beside it, the program's own counters must say that the device did the
matching: every record's segment went through the device lane
(``device_records`` = records in, the overflow rows among them), the
overflow rows it decided on the host = long lines sent, every child of
the filter's program resolved to a device kernel, and the program holds
the whole list — 50 rules on the one staged plane, in the file's order.
"""

import re

import wire
from wire import KEEP, LONG

HOST_CHAIN_EVERY = 64      # every 64th record,
HOST_CHAIN_MID_EVERY = 8   # every eighth whose ``log`` is 257-512 B,
MID, LONG_OVER = 256, 512  # and every one longer than that
DEVICE_KERNELS = ("scan", "assoc")
RULES, KEY = 50, "log"


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


def programs_of(filters: list) -> list:
    return [p._program for p in filters
            if p.name == "grep" and p._program is not None]


def children_on_device_kernels(programs: list) -> bool:
    """Every child of every program (a program without children is its
    own) resolved to a device kernel; ``kernel_resolved`` is ``None`` on
    one that never materialised: what it decided, the host decided."""
    children = [ch for p in programs for ch in (p._children or [p])]
    return bool(children) and all(
        ch.kernel_resolved in DEVICE_KERNELS for ch in children)


def checks(run: dict) -> dict:
    cell, labels, c = run["cell"], run["labels"], run["counters"]
    records = [wire.unpack_str_map(b) for b in run["bodies"]]
    rules = rules_of(cell.pipeline_path)
    plain = bytes(keep(rules, r) for r in records)
    want = bytes(lb & KEEP for lb in labels)

    # the per-record host chain walks 50 automata a record in Python
    mid = [i for i, r in enumerate(records)
           if MID < len(r["log"]) <= LONG_OVER][::HOST_CHAIN_MID_EVERY]
    sample = sorted({*range(0, len(records), HOST_CHAIN_EVERY), *mid,
                     *(i for i, r in enumerate(records)
                       if len(r["log"]) > LONG_OVER)})
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
    programs = programs_of(run["pipe"].filters)
    decisions = [p.decision() for p in programs]
    out = {
        "plain_reference_equal_construction_labels": plain == want,
        "host_chain_equal_construction_labels":
            host_verdict == bytes(want[i] for i in sample),
        "host_chain_built_no_device_program": no_program,
        "filter_kept_some_not_all": 0 < sum(want) < len(want),
        "pipeline_file_holds_50_exclude_rules_on_log":
            len(rules) == RULES and all(ex and f == KEY
                                        for ex, f, _p in rules),
        "program_holds_50_rules_on_one_plane_in_file_order":
            len(programs) == 1 and programs[0].n_planes == 1
            and [r["pattern"] for r in decisions[0]["rules"]]
            == [p.pattern for _ex, _f, p in rules],
    }
    device = {
        "device_records_equal_records_in":
            c.get("filter.grep.device_records") == c["engine.records_in"],
        "overflow_rows_equal_long_lines_sent":
            c.get("filter.grep.overflow_rows") == long_sent,
        "every_child_on_a_device_kernel_none_on_the_host":
            children_on_device_kernels(programs),
    }
    skipped = []
    if run["rehearse"]:
        skipped = sorted(device)
    else:
        out.update(device)
    return {"checks": out, "skipped": skipped,
            "info": {"distinct_lines": len(records), "kept": sum(want),
                     "long_lines_sent": long_sent, "rules": len(rules),
                     "host_chain_lines": len(sample),
                     "k_by_rule": sorted(
                         {r["k"] for d in decisions for r in d["rules"]}),
                     "k_groups": [d["k_groups"] for d in decisions]}}
