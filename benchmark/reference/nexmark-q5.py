"""The plain reference of ``nexmark-q5``: NEXmark's Query 5 as arithmetic
over the corpus — ``count(*)`` by ``auction`` over the bids between two
window closes — in stdlib Python, sharing no code with the program's
``flux/`` or ``stream_processor/``.

(a) Three labellings agree on every record of the corpus: the label the
maker gave it, ``event_type == "bid"`` read from the body, and (on every
16th record) the program's per-record host chain (``tpu.enable off``).
``run.py`` then holds the main sink to the kept bodies, byte for byte, in
order.

(b) The side sink decodes to rows ``{"auction": int, "num": int}`` and
nothing else. The rows of one close carry one record time and later
closes later times, which splits them into emissions; within an emission
no auction comes twice.

(c) Every emission is exact. Windows close under the ingest lock, between
appends, so a pane is a run of whole acked frames (warm-up frames
included: they were absorbed; a pane may be empty). The frames in the
order they were absorbed are read off the main sink: every frame has a
Forward time of its own, later than the frame before, and its first kept
body names its slot of the corpus. With ``panes`` panes a window (2):
emission k is panes k-1 and k (the first: pane 1 alone), and the drain at
stop is the last closed panes of the ring with the open one
(``FluxState.drain``, as ``SPTask.drain``). The emissions' totals fix the
cuts one after the other — every frame holds bids, so a total names one
cut, and it must fall on a frame boundary — and then every auction's
``num`` in every emission equals the count over exactly those frames'
bids, limit 0; the last cut is the last acked frame: nothing missing,
nothing twice. The closes come once an ``advance_s``: from the first
frame to one advance past the last there are span / 5 of them, give or
take one (a tick comes up to half a second after its boundary and waits
for the append under way, so the close of the window's last boundary
may follow the last frame) and one more at most while the run waits for
its output before it stops.

(d) The program's counters say that the device did the work: every
record's ``event_type`` went through the grep lane, every absorb's counts
came from the fused device program (fused absorbs = batches, host absorbs
0) on the device's platform, flux absorbed exactly the bids acked, and
every emitted row is in the side sink. ``run.py`` holds both lanes to
``ok == launches > 0`` (``device_lanes``). Device-only checks are listed
as skipped under ``--rehearse``.
"""

import time
from collections import Counter

from wire import KEEP

HOST_CHAIN_EVERY = 16
SIDE_WAIT_S = 15.0
EVENT_HEAD = 13     # [[EventTime, {}], body]: 92 92 d7 00 <8> 80


def read_value(b: bytes, pos: int):
    """One msgpack ``str``, integer or nil at ``pos`` → (value, end)."""
    t = b[pos]
    if t < 0x80:
        return t, pos + 1
    if t >= 0xE0:
        return t - 0x100, pos + 1
    if t == 0xC0:
        return None, pos + 1
    if 0xA0 <= t <= 0xBF:
        n, pos = t & 0x1F, pos + 1
        return b[pos:pos + n].decode("utf-8"), pos + n
    if 0xD9 <= t <= 0xDB:
        w = 1 << (t - 0xD9)
        n = int.from_bytes(b[pos + 1:pos + 1 + w], "big")
        pos += 1 + w
        return b[pos:pos + n].decode("utf-8"), pos + n
    if 0xCC <= t <= 0xD3:
        w = 1 << ((t - 0xCC) & 3)
        return int.from_bytes(b[pos + 1:pos + 1 + w], "big",
                              signed=t >= 0xD0), pos + 1 + w
    raise ValueError(f"neither str, integer nor nil at {pos}: {t:#x}")


def read_map(b: bytes, pos: int = 0):
    """A fixmap of such values at ``pos`` → (dict, end)."""
    if not 0x80 <= b[pos] <= 0x8F:
        raise ValueError(f"not a fixmap at {pos}")
    out, n, pos = {}, b[pos] & 0x0F, pos + 1
    for _ in range(n):
        k, pos = read_value(b, pos)
        out[k], pos = read_value(b, pos)
    return out, pos


def emissions_of(parts: list) -> list:
    """The side sink as emissions, in arrival order: ``[(record time,
    [row, ...])]``, a new emission wherever the record time changes.
    Raises ValueError on anything that is not a V2 log event around a
    flat map."""
    out = []
    for part in parts:
        pos = 0
        while pos < len(part):
            if part[pos:pos + 4] != b"\x92\x92\xd7\x00" \
                    or part[pos + 12] != 0x80:
                raise ValueError(f"not a V2 log event at {pos}")
            stamp = part[pos + 4:pos + 12]
            row, pos = read_map(part, pos + EVENT_HEAD)
            if not out or out[-1][0] != stamp:
                out.append((stamp, []))
            out[-1][1].append(row)
    return out


def absorbed_frames(parts: list, kept_of_slot: list) -> list:
    """The frames in the order the chain took them, read off the main
    sink (which ``run.py`` holds to the expected bytes): ``[(slot,
    record time)]``. A frame's first kept body names its slot; its size
    is the slot's; its last record carries the frame's time too."""
    sizes = [sum(map(len, kept)) + EVENT_HEAD * len(kept)
             for kept in kept_of_slot]
    frames, carry, guess = [], b"", 0
    slots = len(kept_of_slot)
    for part in parts:
        data = carry + part if carry else part
        pos = 0
        while pos < len(data):
            stamp = data[pos + 4:pos + 12]
            slot = next((s for s in ((guess + d) % slots
                                     for d in range(slots))
                         if data.startswith(kept_of_slot[s][0],
                                            pos + EVENT_HEAD)), None)
            if slot is None:
                raise ValueError(f"no slot's first bid at {pos}")
            end = pos + sizes[slot]
            if end > len(data):
                break       # the frame goes on in the next part
            last = kept_of_slot[slot][-1]
            if data[end - len(last) - EVENT_HEAD + 4:
                    end - len(last) - 1] != stamp \
                    or not data.endswith(last, pos, end):
                raise ValueError(f"not a whole frame of slot {slot} "
                                 f"at {pos}")
            frames.append((slot, stamp))
            pos, guess = end, slot + 1
        carry = data[pos:]
    if carry:
        raise ValueError("bytes after the last whole frame")
    return frames


def seconds_of(stamp: bytes) -> float:
    return int.from_bytes(stamp[:4], "big") \
        + int.from_bytes(stamp[4:], "big") / 1e9


def exact_emissions(emissions: list, frames: list, bids_of_slot: list,
                    n_panes: int) -> dict:
    """(c): fix the cuts from the totals, then hold every row to the
    count over exactly the frames of its window. → verdicts and numbers.
    ``emissions`` are the closes in order with the drain last."""
    cum, at = [0], {0: 0}     # bids in the first j frames → j
    for slot, _stamp in frames:
        cum.append(cum[-1] + sum(bids_of_slot[slot].values()))
        at[cum[-1]] = len(cum) - 1
    cuts = [0] * (n_panes + 1)    # ..., c_{k-1}: where the panes end
    on_boundary = rows_exact = True
    wrong_rows = 0
    for k, (_stamp, rows) in enumerate(emissions):
        total = sum(r["num"] for r in rows)
        drain = k == len(emissions) - 1
        # a close: the last n_panes panes, the one it closes among them;
        # the drain: the n_panes closed panes of the ring and the open one
        first = cuts[-(n_panes + 1)] if drain else cuts[-n_panes]
        end = at.get(cum[first] + total)
        if end is None or end < cuts[-1]:
            on_boundary = False
            break
        want = Counter()
        for slot, _s in frames[first:end]:
            want.update(bids_of_slot[slot])
        got = {r["auction"]: r["num"] for r in rows}
        if got != want:
            rows_exact = False
            wrong_rows += sum(1 for a in set(got) | set(want)
                              if got.get(a) != want.get(a))
        cuts.append(end)
    return {
        "checks": {
            "every_emission_total_falls_on_a_frame_boundary": on_boundary,
            "every_row_equal_reference_count": on_boundary and rows_exact,
            "panes_sum_to_the_bids_acked_nothing_missing_nothing_twice":
                on_boundary and cuts[-1] == len(frames),
        },
        "numbers": {"rows_differing_from_reference": wrong_rows,
                    "frames_past_the_last_cut": len(frames) - cuts[-1],
                    "cuts": cuts[n_panes + 1:]},
    }


def host_chain(run: dict, records: list, sample: list):
    """The program's per-record chain (``tpu.enable off``) over the
    sampled records → (verdicts, no device program built)."""
    host = run["reference_pipeline"]([("tpu.enable", "off")])
    host.ctx.start()  # plugin init happens at start
    try:
        chain = [p for p in host.filters if p.name == "grep"]
        no_program = all(p._program is None for p in chain)
        verdict = bytes(all(p.keep_record(records[i]) for p in chain)
                        for i in sample)
    finally:
        host.ctx.stop()
    return verdict, no_program


def checks(run: dict) -> dict:
    cell, labels, c = run["cell"], run["labels"], run["counters"]
    bodies, counts, pipe = run["bodies"], run["line_counts"], run["pipe"]
    frame_lines = int(cell.traffic["frame_lines"])
    window = cell.config["window"]
    n_panes = round(window["size_s"] / window["advance_s"])
    records = [read_map(b)[0] for b in bodies]

    # (a) the three labellings
    want = bytes(lb & KEEP for lb in labels)
    plain = bytes(r.get("event_type") == "bid" for r in records)
    sample = list(range(0, len(records), HOST_CHAIN_EVERY))
    host, no_program = host_chain(run, records, sample)
    out = {
        "plain_reference_equal_construction_labels": plain == want,
        "host_chain_equal_construction_labels":
            host == bytes(want[i] for i in sample),
        "host_chain_built_no_device_program": no_program,
        "filter_kept_some_not_all": 0 < sum(want) < len(want),
        "every_bid_names_an_integer_auction": all(
            type(r.get("auction")) is int
            for r, w in zip(records, want) if w),
    }

    # the corpus by slot: the kept bodies and the bids by auction
    slots = len(bodies) // frame_lines
    kept_of_slot, bids_of_slot = [], []
    for s in range(slots):
        rows = range(s * frame_lines, (s + 1) * frame_lines)
        kept_of_slot.append([bodies[i] for i in rows if want[i]])
        bids_of_slot.append(Counter(records[i]["auction"]
                                    for i in rows if want[i]))
    bids_acked = sum(n for n, w in zip(counts, want) if w)
    frames_acked = sum(counts[s * frame_lines] for s in range(slots))

    # (b) the side sink, once it holds every row the closes emitted
    deadline = time.monotonic() + SIDE_WAIT_S
    emitted = int(c.get("filter.flux.emitted_rows", 0))
    while time.monotonic() < deadline and emitted > sum(
            p.count(b"\x92\x92\xd7\x00") for p in list(pipe.side.parts)):
        time.sleep(0.05)
    info = {"bids_acked": bids_acked, "frames_acked": frames_acked}
    try:
        emissions = emissions_of(list(pipe.side.parts))
        frames = absorbed_frames(pipe.sink.parts, kept_of_slot)
    except (ValueError, IndexError) as e:
        out["sinks_decode"] = False
        info["decode_error"] = str(e)
        emissions = frames = None
    if emissions is not None:
        all_rows = [r for _s, rows in emissions for r in rows]
        stamps = [s for s, _r in emissions]
        out.update({
            "sinks_decode": True,
            "side_rows_are_auction_and_num_integers": bool(all_rows)
            and all(list(r) == ["auction", "num"]
                    and type(r["auction"]) is int
                    and type(r["num"]) is int for r in all_rows),
            "later_closes_carry_later_times":
                all(a < b for a, b in zip(stamps, stamps[1:])),
            "no_auction_twice_in_an_emission": all(
                len({r.get("auction") for r in rows}) == len(rows)
                for _s, rows in emissions),
            "main_sink_frames_equal_frames_acked":
                len(frames) == frames_acked
                and all(a[1] < b[1] for a, b in zip(frames, frames[1:])),
        })
        # (c) every emission against the count over its frames
        typed = out["side_rows_are_auction_and_num_integers"]
        exact = exact_emissions(emissions if typed else [], frames,
                                bids_of_slot, n_panes)
        out.update(exact["checks"])
        info.update(exact["numbers"])
        advance = window["advance_s"]
        t0 = seconds_of(frames[0][1]) if frames else 0.0
        span = seconds_of(frames[-1][1]) - t0 if frames else 0.0
        in_span = sum(1 for s in stamps[:-1]
                      if t0 <= seconds_of(s) <= t0 + span + advance)
        out["closes_come_once_an_advance"] = \
            -1 <= in_span - int(span // advance) <= 2
        tops = [max(r["num"] for r in rows) for _s, rows in emissions] \
            if typed else None
        info.update({
            "emissions": len(emissions), "closes_in_span": in_span,
            "span_s": span,
            "groups_a_close": [len(rows) for _s, rows in emissions],
            "hot_item_bids": tops,
            "hot_items": typed and [
                sorted(r["auction"] for r in rows if r["num"] == top)
                for (_s, rows), top in zip(emissions, tops)],
        })

    # (d) what the program's own counters say
    flux = next(p for p in pipe.filters if p.name == "flux")
    pre = "filter.flux."
    out.update({
        "task_bound_to_flux_no_opt_out": all(
            t.flux is not None and "flux" not in t.query.props
            for t in pipe.engine.sp.tasks) and bool(pipe.engine.sp.tasks),
        "flux_absorbed_exactly_the_bids_acked":
            flux.state.records_total == bids_acked > 0,
        "emitted_rows_all_in_the_side_sink": emissions is not None
        and emitted == sum(len(rows) for _s, rows in emissions) > 0,
    })
    device = {
        "device_records_equal_records_in":
            c.get("filter.grep.device_records") == c["engine.records_in"],
        "every_absorb_on_the_fused_device_program":
            c.get(pre + "fused_absorbs") == c.get(pre + "batches_total")
            and c.get(pre + "host_absorbs") == 0 < c.get(
                pre + "fused_absorbs", 0),
        "counts_computed_on_the_device_platform":
            getattr(flux.state, "counts_platform", None)
            == run["device"]["platform"],
    }
    skipped = []
    if run["rehearse"]:
        skipped = sorted(device)
    else:
        out.update(device)
    info.update({"distinct_records": len(records), "kept": sum(want),
                 "host_chain_records": len(sample),
                 "rows_emitted": emitted,
                 "closes": c.get(pre + "closes")})
    return {"checks": out, "skipped": skipped, "info": info}
