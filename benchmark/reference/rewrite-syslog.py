"""The plain reference of ``rewrite-syslog``: what ``rewrite_tag`` means,
written with Python's ``re`` from the ``Rule`` lines of the pipeline
file in file order — ``re.search`` on ``log``, the first rule that
matches names the record's new tag, ``keep false`` drops the original —
over every distinct line of the corpus.

(a) Three labellings must agree on every line: the construction label
the corpus maker gave it, this reference, and (on every 16th line and
every long one) the program's per-record host chain (``tpu.enable off``,
``filter()`` on decoded events, read back by tag from ``lib`` outputs of
its own). ``run.py`` then holds the main sink to the survivors, byte for
byte, in order; what it does not look at is held here:

(b) the side sink (one ``lib`` output per new tag; the callback drops the
tag, so a part's tag is read off its records): every part decodes to
records that all carry one expected tag, and the parts of one tag in
arrival order are exactly that tag's records of the acked frames, frame
by frame in frame order (every frame has a time of its own, later than
the frame before), nothing missing, nothing extra;
(c) each side output's ``m_out_proc_records`` equals the reference's
count for its tag, and ``m_filter_emit`` their sum;
(d) the program's own counters say that the device did the matching:
``device_records`` = records in (so ``native.grep_match`` served no
frame), overflow rows = long lines sent, no emitter back-pressure, every
child of the program on a device kernel (whichever: the deployment needs
the chip to match, not one kernel to), and timing keys that add up.
"""

import re
import time

import wire
from lookup import load_py
from wire import KEEP, LONG

HOST_CHAIN_EVERY = 16
SIDE_WAIT_S = 15.0
EVENT_HEAD = 13     # [[EventTime, {}], body]: 92 92 d7 00 <8> 80
#: the same check as the grep deployment's: both launch a ``GrepProgram``
children_on_device_kernels = load_py(
    "reference", "grep-apache2").children_on_device_kernels


def rules_of(pipeline_path: str) -> list:
    """``[(field, compiled pattern, new tag, keep?)]`` in file order."""
    rules, mine = [], False
    with open(pipeline_path) as f:
        for raw in f:
            line = raw.strip()
            if line.startswith("["):
                mine = False
                continue
            parts = line.split(None, 1)
            if len(parts) < 2 or line.startswith("#"):
                continue
            key, val = parts[0].lower(), parts[1].strip()
            if key == "name":
                mine = val.lower() == "rewrite_tag"
            elif mine and key == "rule":
                field, pattern, tag, keep = val.split()
                rules.append((field.lstrip("$"), re.compile(pattern), tag,
                              keep.lower() in ("true", "on", "yes", "1")))
    return rules


def first_match(rules: list, record: dict) -> int:
    """Index of the first rule whose pattern is found in its field, or
    -1 (``process_record`` breaks at the first matching rule)."""
    for r, (field, pattern, _tag, _keep) in enumerate(rules):
        value = record.get(field)
        if value is not None and pattern.search(value) is not None:
            return r
    return -1


def str_end(b: bytes, pos: int) -> int:
    """Where the msgpack str at ``pos`` ends."""
    t = b[pos]
    if 0xA0 <= t <= 0xBF:
        return pos + 1 + (t & 0x1F)
    if t == 0xD9:
        return pos + 2 + b[pos + 1]
    if t == 0xDA:
        return pos + 3 + int.from_bytes(b[pos + 1:pos + 3], "big")
    if t == 0xDB:
        return pos + 5 + int.from_bytes(b[pos + 1:pos + 5], "big")
    raise ValueError(f"not a str at {pos}: {t:#x}")


def events_of(part: bytes) -> list:
    """``[(time bytes, body bytes)]`` of concatenated V2 log events
    whose bodies are ``{str: str}`` maps."""
    out, pos = [], 0
    while pos < len(part):
        if part[pos:pos + 4] != b"\x92\x92\xd7\x00" \
                or part[pos + 12] != 0x80:
            raise ValueError(f"not a V2 log event at {pos}")
        stamp, start = part[pos + 4:pos + 12], pos + EVENT_HEAD
        if not 0x80 <= part[start] <= 0x8F:
            raise ValueError(f"not a fixmap body at {start}")
        end = start + 1
        for _ in range(2 * (part[start] & 0x0F)):
            end = str_end(part, end)
        out.append((stamp, part[start:end]))
        pos = end
    return out


def side_sink_verdicts(parts: list, bodies: list, want: list,
                       frame_lines: int, line_counts: list,
                       n_rules: int) -> dict:
    """(b): read each part's tag off its records, then hold each tag's
    stream to the corpus, frame by frame."""
    index = {b: i for i, b in enumerate(bodies)}
    one_tag = known = True
    streams = [[] for _ in range(n_rules)]  # per tag: (stamp, line)
    for part in parts:
        try:
            events = events_of(part)
        except (ValueError, IndexError):
            return {"side_parts_decode": False}
        lines = [index.get(body) for _stamp, body in events]
        if None in lines:
            known = False
            continue
        tags = {want[i] for i in lines}
        if len(tags) != 1 or -1 in tags:
            one_tag = False
            continue
        streams[tags.pop()] += [(s, i) for (s, _b), i in zip(events, lines)]

    # what a frame of a slot leaves under a tag, in the frame's order
    slots = len(bodies) // frame_lines
    per_slot = [[[] for _ in range(n_rules)] for _ in range(slots)]
    for i, r in enumerate(want):
        if r >= 0:
            per_slot[i // frame_lines][r].append(i)

    in_order = complete = True
    for r, stream in enumerate(streams):
        seen = [0] * slots
        pos, last = 0, b""
        while pos < len(stream):
            stamp, first = stream[pos]
            frame = per_slot[first // frame_lines][r]
            got = [i for s, i in stream[pos:pos + len(frame)] if s == stamp]
            if got != frame or stamp <= last:
                in_order = False
                break
            seen[first // frame_lines] += 1
            pos, last = pos + len(frame), stamp
        for slot in range(slots):
            acked = line_counts[slot * frame_lines]
            if seen[slot] != (acked if per_slot[slot][r] else 0):
                complete = False
    return {"side_parts_decode": True,
            "side_records_are_corpus_lines": known,
            "side_part_carries_one_expected_tag": one_tag,
            "side_tag_streams_in_frame_order": in_order,
            "side_nothing_missing_nothing_extra": in_order and complete}


def host_chain(run: dict, sample: list, tags: list):
    """The program's per-record chain over the sampled lines: the
    ``filter()`` of a pipeline built with ``tpu.enable off``, what it
    re-emits read back by tag from ``lib`` outputs of this function's
    own. → (rule index per sampled line, no device program built)."""
    from fluentbit_tpu.codec.events import decode_events

    host = run["reference_pipeline"]([("tpu.enable", "off")])
    got = {}
    for tag in tags:
        host.ctx.output("lib", match=tag, callback=(
            lambda data, _t, tag=tag: got.setdefault(tag, []).append(
                bytes(data))))
    host.ctx.start()  # plugin init happens at start
    try:
        plugin = next(p for p in host.filters if p.name == "rewrite_tag")
        no_program = plugin._program is None
        events = decode_events(wire.output_events(
            time.time_ns(), [run["bodies"][i] for i in sample]))
        _result, kept = plugin.filter(events, run["cell"].config["tag"],
                                      host.engine)
        n_emitted = len(sample) - len(kept)
        host.ctx.flush_now()
        deadline = time.monotonic() + SIDE_WAIT_S
        while time.monotonic() < deadline and n_emitted != sum(
                len(events_of(p)) for ps in list(got.values())
                for p in list(ps)):
            time.sleep(0.02)
    finally:
        host.ctx.stop()
    where = {run["bodies"][i]: j for j, i in enumerate(sample)}
    verdict = [None] * len(sample)
    for ev in kept:
        verdict[where[wire.pack_str_map(ev.body)]] = -1
    for r, tag in enumerate(tags):
        for part in got.get(tag, []):
            for _stamp, body in events_of(part):
                verdict[where[body]] = r
    return verdict, no_program


def checks(run: dict) -> dict:
    cell, labels, c = run["cell"], run["labels"], run["counters"]
    bodies, counts = run["bodies"], run["line_counts"]
    pipe = run["pipe"]
    maker = load_py("corpora", cell.config["corpus"]["maker"])
    records = [wire.unpack_str_map(b) for b in bodies]
    rules = rules_of(cell.pipeline_path)
    tags = [tag for _f, _p, tag, _k in rules]
    frame_lines = int(cell.traffic["frame_lines"])

    # (a) the three labellings
    plain = [first_match(rules, r) for r in records]
    built = [maker.winner(lb) for lb in labels]
    sample = [i for i, lb in enumerate(labels)
              if i % HOST_CHAIN_EVERY == 0 or lb & LONG
              or len(bodies[i]) > 300]
    host, no_program = host_chain(run, sample, tags)
    out = {
        "pipeline_rules_equal_side_matches_all_drop_the_original":
            tags == cell.config["side_matches"]
            and not any(keep for _f, _p, _t, keep in rules),
        "corpus_lines_distinct": len(set(bodies)) == len(bodies),
        "plain_reference_equal_construction_labels": plain == built,
        "keep_bit_set_where_no_rule_wins": all(
            bool(lb & KEEP) == (w < 0) for lb, w in zip(labels, built)),
        "host_chain_equal_plain_reference":
            host == [plain[i] for i in sample],
        "host_chain_built_no_device_program": no_program,
        "every_rule_wins_somewhere_and_some_lines_stay":
            set(plain) == set(range(-1, len(rules))),
    }

    # (b) the side sink, once it holds what the acked frames left there
    per_tag = [0] * len(rules)
    side_bytes = 0
    for i, r in enumerate(plain):
        if r >= 0:
            per_tag[r] += counts[i]
            side_bytes += counts[i] * (EVENT_HEAD + len(bodies[i]))
    deadline = time.monotonic() + SIDE_WAIT_S
    while pipe.side.n_bytes() < side_bytes \
            and time.monotonic() < deadline:
        time.sleep(0.02)
    out["side_sink_holds_expected_bytes"] = \
        pipe.side.n_bytes() == side_bytes > 0
    out.update(side_sink_verdicts(list(pipe.side.parts), bodies, plain,
                                  frame_lines, counts, len(rules)))

    # (c) the engine's counters, output by output
    engine = pipe.engine
    by_output = [engine.m_out_proc_records.get((o.display_name,)) or 0
                 for o in engine.outputs[1:1 + len(rules)]]
    rewrite = next(f for f in engine.filters
                   if f.plugin.name == "rewrite_tag")
    out["side_outputs_records_equal_reference"] = by_output == per_tag
    out["filter_emit_equal_reference_sum"] = \
        (engine.m_filter_emit.get((rewrite.display_name,)) or 0) \
        == sum(per_tag)

    # (d) the device did the matching
    long_sent = sum(n for n, lb in zip(counts, labels) if lb & LONG)
    pre = "filter.rewrite_tag."
    out["batch_path_served_every_record"] = \
        c.get(pre + "records") == c["engine.records_in"]
    out["no_emitter_backpressure"] = \
        c.get(pre + "emit_backpressure") == 0 < c.get(pre + "emits", 0)
    programs = [p._program for p in pipe.filters
                if p.name == "rewrite_tag" and p._program is not None]
    device = {
        "device_records_equal_records_in":
            c.get(pre + "device_records") == c["engine.records_in"],
        "overflow_rows_equal_long_lines_sent":
            c.get(pre + "overflow_rows") == long_sent,
        "every_child_on_a_device_kernel_none_on_the_host":
            children_on_device_kernels(programs),
        "one_staged_plane_for_the_eight_rules":
            [getattr(p, "n_planes", None) for p in programs] == [1],
        "stage_launch_emit_seconds_inside_the_run":
            0 < c.get(pre + "extract_s", 0) and 0 < c.get(pre + "kernel_s", 0)
            and c.get(pre + "extract_s", 0) + c.get(pre + "kernel_s", 0)
            + c.get(pre + "emit_s", 0) <= c["clock.seconds"],
    }
    skipped = []
    if run["rehearse"]:
        skipped = sorted(device)
    else:
        out.update(device)
    return {"checks": out, "skipped": skipped,
            "info": {"distinct_lines": len(records),
                     "lines_by_rule": [plain.count(r)
                                       for r in range(len(rules))],
                     "lines_no_rule": plain.count(-1),
                     "records_by_tag": dict(zip(tags, per_tag)),
                     "records_by_output": by_output,
                     "side_bytes": side_bytes,
                     "side_parts": len(pipe.side.parts),
                     "long_lines_sent": long_sent, "rules": len(rules),
                     "host_chain_lines": len(sample)}}
