#!/usr/bin/env python3
"""One run of one benchmark cell.

    python benchmark/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

The deployment every cell measures: this process is the **aggregator**
and the only one that touches the chip — the pipeline of the cell's
configuration, built through ``fluentbit_tpu.create()`` and the config
loader (``forward`` input → filter chain → ``lib`` output, ``Flush 1``).
A **generator** process (``generator.py``, stdlib only, started before
this process imports JAX) makes the seeded corpus and replays it over
TCP loopback as Forward frames under the cell's traffic file.

Everything that belongs to one cell, configuration, traffic mix,
traffic kind, per-layer metric or reference is a file of its own, found
by the name in ``BENCHMARK.json`` (see ``README.md``). The last line of
stdout is the result object the driver reads; the lines before it carry
what a reader needs besides.

``--rehearse`` (never passed by the driver) lets the run go on without a
TPU and says ``platform: cpu`` truthfully: there the device-only checks
are listed as skipped, and no number it prints is a device number.
"""

import time

T_PROCESS = time.monotonic()  # set-up is counted from here

import argparse  # noqa: E402
import array  # noqa: E402
import hashlib  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import shutil  # noqa: E402
import signal  # noqa: E402
import subprocess  # noqa: E402
import sys  # noqa: E402
import tempfile  # noqa: E402

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(HERE)
sys.path.insert(0, HERE)

import stats  # noqa: E402
from generator import length_bucket  # noqa: E402
from lookup import load_json, load_py  # noqa: E402
import wire  # noqa: E402
from spans import Recorder  # noqa: E402

ATTACH_TIMEOUT_S = 600.0
WATCHDOG_S = 1150          # a cold first run may take 1200 s, no run more
TRACE_SECONDS = 3.0        # the profiler runs over the window's last part
LANE_CLEAN = ("failures", "timeouts", "fallback_segments",
              "short_circuits", "abandoned")


def note(**kw) -> None:
    """An earlier line of stdout: for the reader, not for the driver."""
    print(json.dumps(kw, default=str), flush=True)


def die(message: str, code: int = 2):
    print(f"benchmark/run.py: {message}", file=sys.stderr, flush=True)
    raise SystemExit(code)


# ------------------------------------------------------------ the lookup

class Cell:
    """One entry of ``BENCHMARK.json``'s ``workloads`` with everything it
    names, each found by file."""

    def __init__(self, name: str):
        self.bench = load_json(os.path.join(ROOT, "BENCHMARK.json"))
        entry = next((w for w in self.bench["workloads"]
                      if w["name"] == name), None)
        if entry is None:
            die(f"no workload {name!r} in BENCHMARK.json")
        self.name, self.chips = name, int(entry["chips"])
        cfg = next(c for c in self.bench["configs"]
                   if c["name"] == entry["config"])
        self.config_path = os.path.join(ROOT, cfg["file"])
        self.config = load_json(self.config_path)
        self.pipeline_path = os.path.join(
            os.path.dirname(self.config_path), self.config["pipeline"])
        self.traffic_path = os.path.join(
            HERE, "traffic", entry["traffic"] + ".json")
        self.traffic = load_json(self.traffic_path)

    def metrics(self, group: str) -> list:
        return [m for m in self.bench[group]
                if "workloads" not in m or self.name in m["workloads"]]


# --------------------------------------------------------- the generator

class Generator:
    """The generator process and its line protocol."""

    def __init__(self, cell: Cell, seed: int, work: str):
        self.proc = subprocess.Popen(
            [sys.executable, os.path.join(HERE, "generator.py"),
             "--config", cell.config_path, "--traffic", cell.traffic_path,
             "--seed", str(seed), "--work", work],
            stdin=subprocess.PIPE, stdout=subprocess.PIPE, text=True,
            bufsize=1)

    def tell(self, **cmd) -> None:
        self.proc.stdin.write(json.dumps(cmd) + "\n")
        self.proc.stdin.flush()

    def event(self, expect: str) -> dict:
        line = self.proc.stdout.readline()
        if not line:
            raise RuntimeError(f"the generator ended (rc "
                               f"{self.proc.wait()}) before {expect!r}")
        msg = json.loads(line)
        if msg.get("event") != expect:
            raise RuntimeError(f"expected {expect!r}, got {msg!r}")
        return msg

    def close(self) -> None:
        """Stop it and wait until it has ended."""
        if self.proc.poll() is None:
            self.proc.kill()
        self.proc.wait()
        self.proc.stdin.close()
        self.proc.stdout.close()


# ------------------------------------------------------------ the device

def attach(cell: Cell, rehearse: bool) -> dict:
    """Touch the device first, through the program's attach controller,
    and fail on anything but the chips the cell asks for — before any
    pipeline exists. Sets no JAX_PLATFORMS, no XLA_FLAGS, no cache."""
    from fluentbit_tpu.ops import device

    if not device.wait(ATTACH_TIMEOUT_S):
        die(f"device attach did not complete: {device.status()}", 3)
    import jax

    devs = jax.devices()
    info = {"platform": devs[0].platform, "kind": devs[0].device_kind,
            "count": len(devs)}
    if not rehearse:
        if info["platform"] != "tpu":
            die(f"jax found no accelerator: platform "
                f"{info['platform']!r} (--rehearse runs without one)", 3)
        if info["count"] < cell.chips:
            die(f"the cell needs {cell.chips} chip(s), jax reports "
                f"{info['count']}", 3)
    info["attach_s"] = device.status().get("attach_seconds")
    return info


class CompileLog:
    """XLA compiles, their seconds, and persistent-cache hits, from
    jax's own monitoring events (a copy of ``chip_smoke.CompileLog``)."""

    def __init__(self):
        import jax

        self.compiles = self.cache_hits = self.cache_misses = 0
        self.seconds = 0.0
        jax.monitoring.register_event_listener(self._event)
        jax.monitoring.register_event_duration_secs_listener(
            self._duration)

    def _event(self, name, **_kw):
        if name == "/jax/compilation_cache/cache_hits":
            self.cache_hits += 1
        elif name == "/jax/compilation_cache/cache_misses":
            self.cache_misses += 1

    def _duration(self, name, secs, **_kw):
        if name == "/jax/core/compile/backend_compile_duration":
            self.compiles += 1
            self.seconds += secs

    def snapshot(self) -> dict:
        return {"compiles": self.compiles, "compile_s": self.seconds,
                "cache_hits": self.cache_hits,
                "cache_misses": self.cache_misses}


# ---------------------------------------------------------- the pipeline

class Sink:
    """``lib`` output callback: keeps the chunk and notes its arrival.
    Decoding, hashing and latency arithmetic happen after the window."""

    def __init__(self):
        self.parts = []
        self.arrivals = []

    def __call__(self, data, _tag):
        self.arrivals.append(time.monotonic_ns())
        self.parts.append(bytes(data))

    def n_bytes(self) -> int:
        return sum(len(p) for p in self.parts)


class Pipeline:
    """The cell's pipeline file through the normal entry, ``lib``
    outputs added for the records and for whatever the chain emits
    beside them."""

    def __init__(self, cell: Cell, extra_filter_props=()):
        import fluentbit_tpu as flb
        from fluentbit_tpu.config_format import (ConfigFile, Section,
                                                 apply_to_context,
                                                 load_config_file)

        cf = load_config_file(cell.pipeline_path)
        sections = []
        for sec in cf.sections:
            if sec.name == "filter" and extra_filter_props:
                sec = Section("filter", list(sec.properties)
                              + list(extra_filter_props))
            sections.append(sec)
        self.ctx = flb.create()
        apply_to_context(self.ctx, ConfigFile(sections, cf.env),
                         os.path.dirname(cell.pipeline_path))
        self.sink, self.side = Sink(), Sink()
        self.ctx.output("lib", match=cell.config["record_match"],
                        callback=self.sink)
        for match in cell.config["side_matches"]:
            self.ctx.output("lib", match=match, callback=self.side)
        self.engine = self.ctx.engine
        self.forward = next(i for i in self.engine.inputs
                            if i.plugin.name == "forward")
        self.filters = [f.plugin for f in self.engine.filters]
        self.out_name = self.engine.outputs[0].display_name

    def port(self, timeout_s: float = 30.0) -> int:
        deadline = time.monotonic() + timeout_s
        while time.monotonic() < deadline:
            if self.forward.plugin.bound_port:
                return self.forward.plugin.bound_port
            time.sleep(0.005)
        raise RuntimeError("in_forward did not bind a port")

    def counters(self) -> dict:
        """The program's own counters, flat: what the readers and the
        checks take their numbers from."""
        from fluentbit_tpu.ops import fault

        e, fw = self.engine, self.forward.plugin
        out = {
            "clock.seconds": time.monotonic(),
            "engine.records_in": e.m_in_records.get(
                (self.forward.display_name,)),
            "engine.records_out": e.m_out_proc_records.get(
                (self.out_name,)),
            "engine.raw_declines": sum(
                v for _l, v in e.m_filter_batch_decline.samples()),
            "forward.absorbed": fw.n_absorbed,
            "forward.withheld_acks": fw.n_withheld_acks,
            "forward.deferred_acks": fw.n_deferred_acks,
            "forward.dedup_hits": fw._ledger.dedup_hits
            if fw._ledger is not None else 0,
        }
        for plugin in self.filters:
            timings = getattr(plugin, "raw_timings", None)
            if timings is not None:
                for key in timings:
                    name = f"filter.{plugin.name}.{key}"
                    out[name] = out.get(name, 0) + timings[key]
            state = getattr(plugin, "state", None)
            if hasattr(state, "records_total"):
                out[f"filter.{plugin.name}.records_total"] = \
                    state.records_total
                out[f"filter.{plugin.name}.batches_total"] = \
                    state.batches_total
        for lane, st in fault.snapshot().items():
            for key, v in st.items():
                if isinstance(v, (int, float)) and not isinstance(v, bool):
                    out[f"lane.{lane}.{key}"] = v
        return out

    def install_spans(self, rec: Recorder) -> None:
        """Spans around the calls into each layer, from outside."""
        from fluentbit_tpu.plugins import net_forward

        class TimedUnpacker(net_forward.Unpacker):
            feed = rec.wrap(net_forward.Unpacker.feed, "decode")
            __next__ = rec.wrap(net_forward.Unpacker.__next__, "decode")

        net_forward.Unpacker = TimedUnpacker
        net_forward._entries_to_events = rec.wrap(
            net_forward._entries_to_events, "decode")
        rec.wrap_attr(self.engine, "input_log_append", "append")
        rec.wrap_attr(self.engine, "flush_all", "flush")
        for plugin in self.filters:
            for attr in ("filter", "process_batch"):
                rec.wrap_attr(plugin, attr, f"filter:{plugin.name}")


def delta(after: dict, before: dict) -> dict:
    return {k: v - before.get(k, 0) for k, v in after.items()}


# ------------------------------------------------------------- the trace

class Profiler:
    """``jax.profiler`` over the last ``TRACE_SECONDS`` of the window,
    with the program's counters read at both ends so that device time
    can be set against the launches made in the same interval."""

    def __init__(self, pipe: Pipeline, rec: Recorder, work: str):
        self.pipe, self.rec = pipe, rec
        self.dir = os.path.join(work, "trace")
        self.before = self.after = None

    def start(self) -> None:
        import jax

        options = jax.profiler.ProfileOptions()
        options.python_tracer_level = 0  # it would slow the host
        jax.profiler.start_trace(self.dir, profiler_options=options)
        self.rec.annotate = jax.profiler.TraceAnnotation
        self.before = self.pipe.counters()

    def stop(self) -> None:
        import jax

        self.after = self.pipe.counters()
        self.rec.annotate = None
        jax.profiler.stop_trace()

    def reduce(self):
        import trace_reduce

        got = trace_reduce.reduce_trace(self.dir)
        if got is not None:
            got["counters"] = delta(self.after, self.before)
            got["window_s"] = got["span_s"]  # first to last traced event
        return got


# ------------------------------------------------------ after the window

def read_corpus(work: str):
    with open(os.path.join(work, "corpus.bodies"), "rb") as f:
        blob = f.read()
    offsets = array.array("Q")
    with open(os.path.join(work, "corpus.offsets"), "rb") as f:
        offsets.frombytes(f.read())
    with open(os.path.join(work, "corpus.labels"), "rb") as f:
        labels = f.read()
    bodies = [blob[offsets[i]:offsets[i + 1]] for i in range(len(labels))]
    return bodies, labels


def read_frames(work: str) -> list:
    with open(os.path.join(work, "frames.csv")) as f:
        cols = f.readline().strip().split(",")
        rows = []
        for line in f:
            vals = line.strip().split(",")
            row = {c: (v if c == "phase" else int(v))
                   for c, v in zip(cols, vals)}
            rows.append(row)
    return rows


def kept_unchanged():
    """The rule of a chain that passes records through or drops them:
    the frame's lines whose construction label says the chain keeps
    them, unchanged, as V2 events carrying the frame's time."""
    kept_by_slot = {}

    def one_frame(frame: dict, bodies: list, labels: bytes, wire) -> bytes:
        key = (frame["slot"], frame["lines"])
        kept = kept_by_slot.get(key)
        if kept is None:
            lo = frame["slot"] * frame["lines"]
            kept = kept_by_slot[key] = [
                bodies[i] for i in range(lo, lo + frame["lines"])
                if labels[i] & wire.KEEP]
        return wire.output_events(frame["wall_ns"], kept)
    return one_frame


def expected_output(frames: list, bodies: list, labels: bytes,
                    reference=None):
    """What the main sink must hold: for every acked frame, in order,
    the bytes the configuration says the frame leaves there — its
    reference's ``expected_output(frame, bodies, labels, wire)`` where
    the module has one (a chain that transforms its records), else
    :func:`kept_unchanged`. → (sha256, bytes per frame)."""
    one_frame = getattr(reference, "expected_output", None) \
        or kept_unchanged()
    digest, sizes = hashlib.sha256(), []
    for fr in frames:
        part = one_frame(fr, bodies, labels, wire) if fr["ack_ns"] else b""
        digest.update(part)
        sizes.append(len(part))
    return digest.hexdigest(), sizes


def flush_times(sizes: list, sink: Sink) -> list:
    """Per frame, when its last surviving record reached the output: the
    output stream is the expected stream (the check beside this says
    so), so a frame's place in it follows from the sizes alone."""
    ends, total = [], 0
    for part in sink.parts:
        total += len(part)
        ends.append(total)
    out, j, pos = [], 0, 0
    for size in sizes:
        pos += size
        while j < len(ends) and ends[j] < pos:
            j += 1
        out.append(sink.arrivals[j] if size and j < len(ends) else 0)
    return out


def backlog_at_quarters(frames: list, start_ns: int, seconds: float):
    """Frames sent and not yet acked at each quarter of the window."""
    out = []
    for q in (1, 2, 3, 4):
        t = start_ns + int(q * seconds * 1e9 / 4)
        out.append(sum(1 for f in frames if f["phase"] == "window"
                       and f["created_ns"] <= t
                       and not (f["ack_ns"] and f["ack_ns"] <= t)))
    return out


def tail_of(sample: list) -> dict:
    """For the reader of the earlier lines: where the sample's tail
    lies, whichever percentile the cell reports."""
    out = {f"p{100 * q:g}": stats.percentile(sample, q)
           for q in (0.5, 0.9, 0.95, 0.975, 0.99)
           if stats.supported(len(sample), q)}
    if sample:
        out["max"] = max(sample)
    return out


def end_to_end(cell: Cell, frames: list, flushed: list, start_ns: int,
               seconds: float, setup_s: float) -> tuple:
    """The cell's end-to-end metrics, over all the work and all the time
    of the window; a tail over all the window's frames. → (metrics,
    sample counts)."""
    end_ns = start_ns + int(seconds * 1e9)
    win = [(f, t) for f, t in zip(frames, flushed)
           if f["phase"] == "window"]
    acked_lines = sum(f["lines"] for f, _t in win
                      if f["ack_ns"] and f["ack_ns"] <= end_ns)
    ack_ms = [(f["ack_ns"] - f["due_ns"]) / 1e6 for f, _t in win
              if f["ack_ns"]]
    flush_ms = [(t - f["created_ns"]) / 1e6 for f, t in win if t]
    values = {"setup_s": setup_s, "lines_per_s": acked_lines / seconds}
    for name, sample, q in (("ack_p50_ms", ack_ms, 0.5),
                            ("ack_p95_ms", ack_ms, 0.95),
                            ("ack_p99_ms", ack_ms, 0.99),
                            ("flush_p95_ms", flush_ms, 0.95)):
        if stats.supported(len(sample), q):
            values[name] = stats.percentile(sample, q)
    units = {m["name"]: m["unit"] for m in cell.metrics("end_to_end")}
    return {n: {"value": values[n], "unit": u}
            for n, u in units.items() if n in values}, \
        {"ack_samples": len(ack_ms), "flush_samples": len(flush_ms),
         "acked_lines_in_window": acked_lines,
         "ack_ms": tail_of(ack_ms), "flush_ms": tail_of(flush_ms)}


def ack_by_length_bucket(win: list, bodies: list, labels: bytes,
                         buckets: list) -> dict:
    """For the reader of the earlier lines: the window's ack times by
    the staging shape of the frame (``generator.length_bucket``)."""
    by, bucket_of = {}, {}
    for f in win:
        if not f["ack_ns"] or not buckets:
            continue
        key = (f["slot"], f["lines"])
        if key not in bucket_of:
            bucket_of[key] = length_bucket(
                bodies, labels, f["slot"] * f["lines"], f["lines"], buckets)
        by.setdefault(bucket_of[key], []).append(
            (f["ack_ns"] - f["due_ns"]) / 1e6)
    return {str(b): {"frames": len(ms), "p50": stats.percentile(ms, 0.5),
                     "p90": stats.percentile(ms, 0.9), "max": max(ms)}
            for b, ms in sorted(by.items())}


def per_layer(cell: Cell, readings: dict) -> dict:
    """Each per-layer metric through its own reader, found by the
    metric's name; a reader that finds nothing to read returns None and
    the metric is left out."""
    out = {}
    for m in cell.metrics("per_layer"):
        spec = load_json(os.path.join(HERE, "layer_metrics",
                                      m["name"] + ".json"))
        module, func = spec["reader"].split(":")
        value = getattr(load_py("readers", module), func)(
            readings, **spec.get("args", {}))
        if value is not None:
            out[m["name"]] = {"value": value, "unit": m["unit"]}
    return out


def wire_numbers(frames, all_delta, digest_out, digest_exp, sink_bytes,
                 exp_bytes) -> dict:
    """The numbers the guarantees every configuration states are held
    by, from the program's own counters and the generator's log. Every
    comparison is exact: each has to be 0."""
    acked = [f for f in frames if f["ack_ns"]]
    return {
        "frames_sent_not_acked": len(frames) - len(acked),
        "withheld_acks": all_delta["forward.withheld_acks"],
        "dedup_hits": all_delta["forward.dedup_hits"],
        "absorbed_less_acked_frames":
            all_delta["forward.absorbed"] - len(acked),
        "records_in_less_acked_lines": all_delta["engine.records_in"]
            - sum(f["lines"] for f in acked),
        "raw_path_declines": all_delta["engine.raw_declines"],
        "output_bytes_less_expected": sink_bytes - exp_bytes,
        "output_sha256_differs": int(digest_out != digest_exp),
    }


def wire_checks(frames, win_delta, all_delta, gen_done, numbers,
                exp_bytes, lanes, rehearse):
    """The named verdicts over :func:`wire_numbers`, the connection and
    the lanes the configuration names. → (checks, skipped)."""
    checks = {
        "connection_sound": gen_done["broken"] is None,
        "every_sent_frame_acked":
            numbers["frames_sent_not_acked"] == 0 < len(frames),
        "no_withheld_acks": numbers["withheld_acks"] == 0,
        "no_dedup_hits": numbers["dedup_hits"] == 0,
        "absorbed_equal_acked_frames":
            numbers["absorbed_less_acked_frames"] == 0,
        "records_in_equal_acked_lines":
            numbers["records_in_less_acked_lines"] == 0,
        "no_raw_path_declines": numbers["raw_path_declines"] == 0,
        "output_equal_expected_survivors_in_order":
            numbers["output_sha256_differs"] == 0
            and numbers["output_bytes_less_expected"] == 0 < exp_bytes,
    }
    device = {}
    for lane in lanes:
        n = all_delta.get(f"lane.{lane}.launches", 0)
        device[f"lane_{lane}_ok_equal_launches"] = \
            n > 0 and all_delta.get(f"lane.{lane}.ok") == n
        device[f"lane_{lane}_nothing_else"] = all(
            all_delta.get(f"lane.{lane}.{k}", 0) == 0 for k in LANE_CLEAN)
        device[f"lane_{lane}_launched_in_window"] = \
            win_delta.get(f"lane.{lane}.launches", 0) > 0
    if rehearse:
        return checks, sorted(device)
    checks.update(device)
    return checks, []


# ------------------------------------------------------------------ main

def run(args) -> int:
    cell = Cell(args.workload)
    if not os.path.isdir(os.path.join(ROOT, "fluentbit_tpu")):
        die("the program (fluentbit_tpu/) is not in this directory")
    work = tempfile.mkdtemp(prefix="fbtpu-bench-")
    gen = Generator(cell, args.seed, work)  # before this process has JAX
    try:
        return measure(cell, args, gen, work)
    finally:
        gen.close()
        shutil.rmtree(work, ignore_errors=True)


def measure(cell: Cell, args, gen: Generator, work: str) -> int:
    sys.path.insert(0, ROOT)
    dev = attach(cell, args.rehearse)
    t_attached = time.monotonic()
    compiles = CompileLog()
    corpus_event = gen.event("corpus")
    t_corpus = time.monotonic()

    pipe = Pipeline(cell)
    rec = Recorder()
    if args.trace:
        pipe.install_spans(rec)
    pipe.ctx.start()
    try:
        port = pipe.port()
        t_pipeline = time.monotonic()
        start_counters = pipe.counters()

        # warm-up: frames of the cell's own shapes, flushed through
        gen.tell(cmd="connect", port=port)
        warm = gen.event("warm")
        pipe.ctx.flush_now()
        deadline = time.monotonic() + 30
        while not pipe.sink.parts and time.monotonic() < deadline:
            time.sleep(0.01)
        warm_compiles = compiles.snapshot()
        before = pipe.counters()
        setup_s = time.monotonic() - T_PROCESS
        t_go = time.monotonic()

        # the window
        gen.tell(cmd="go", seconds=args.seconds)
        prof = Profiler(pipe, rec, work) if args.trace else None
        if prof is not None:
            time.sleep(max(0.0, args.seconds - TRACE_SECONDS - 0.25))
            prof.start()
        time.sleep(max(0.0, t_go + args.seconds - time.monotonic()))
        after = pipe.counters()
        if prof is not None:
            prof.stop()
        window_compiles = compiles.snapshot()["compiles"] \
            - warm_compiles["compiles"]
        gen_done = gen.event("done")

        # drain: everything acked is handed to the output
        frames = read_frames(work)
        bodies, labels = read_corpus(work)
        reference = load_py("reference", cell.config["name"])
        digest_exp, sizes = expected_output(frames, bodies, labels,
                                            reference)
        pipe.ctx.flush_now()
        deadline = time.monotonic() + 15
        while pipe.sink.n_bytes() < sum(sizes) \
                and time.monotonic() < deadline:
            time.sleep(0.02)
    finally:
        pipe.ctx.stop()
    end_counters = pipe.counters()

    # ---- arithmetic, all of it after the window
    start_ns = gen_done["start_ns"]
    digest_out = hashlib.sha256()
    for part in pipe.sink.parts:
        digest_out.update(part)
    digest_out = digest_out.hexdigest()
    flushed = flush_times(sizes, pipe.sink)
    e2e, samples = end_to_end(cell, frames, flushed, start_ns,
                              args.seconds, setup_s)
    win_delta = delta(after, before)
    all_delta = delta(end_counters, start_counters)
    numbers = wire_numbers(frames, all_delta, digest_out, digest_exp,
                           pipe.sink.n_bytes(), sum(sizes))
    checks, skipped = wire_checks(
        frames, win_delta, all_delta, gen_done, numbers, sum(sizes),
        cell.config["device_lanes"], args.rehearse)

    # the plain reference of this configuration
    per_slot = {}
    for f in frames:
        if f["ack_ns"]:
            per_slot[f["slot"]] = per_slot.get(f["slot"], 0) + 1
    frame_lines = int(cell.traffic["frame_lines"])
    line_counts = [per_slot.get(i // frame_lines, 0)
                   for i in range(len(labels))]
    t_ref = time.monotonic()
    ref = reference.checks({
        "cell": cell, "pipe": pipe, "bodies": bodies, "labels": labels,
        "line_counts": line_counts, "counters": all_delta,
        "rehearse": args.rehearse, "device": dev,
        "reference_pipeline": lambda props: Pipeline(cell, props)})
    checks.update(ref["checks"])
    skipped += ref.get("skipped", [])
    reference_s = time.monotonic() - t_ref

    win = [f for f in frames if f["phase"] == "window"]
    failed = sum(1 for f in win if not f["ack_ns"])
    if not all(checks.values()):
        failed = max(failed, 1)
    setup = {"attach_s": dev["attach_s"],
             "attach_wall_s": t_attached - T_PROCESS,
             "corpus_s": corpus_event["seconds"],
             "corpus_wait_s": t_corpus - t_attached,
             "pipeline_s": t_pipeline - t_corpus,
             "warm_s": t_go - t_pipeline,
             "compile_s": warm_compiles["compile_s"],
             "compiles": warm_compiles["compiles"],
             "cache_hits": warm_compiles["cache_hits"],
             "cache_misses": warm_compiles["cache_misses"],
             "window_compiles": window_compiles}

    import jax

    mem = [(d.memory_stats() or {}).get("peak_bytes_in_use", 0)
           for d in jax.devices()]
    device = {"platform": dev["platform"], "kind": dev["kind"],
              "count": dev["count"], "memory_peak_bytes": max(mem)}
    result = {"correct": all(checks.values()), "attempted": len(win),
              "failed": failed, "metrics": e2e, "device": device}
    trace = None
    if args.trace:
        trace = prof.reduce()
        readings = {
            "cell": cell, "device": dev, "setup": setup,
            "window": {"start_ns": start_ns, "seconds": args.seconds,
                       "end_ns": start_ns + int(args.seconds * 1e9)},
            "counters": win_delta, "spans": rec, "trace": trace,
            "frames": win, "sink": pipe.sink, "filters": pipe.filters}
        result["metrics"] = per_layer(cell, readings)
        if trace is not None:
            device["busy_s"] = trace["busy_s"]
            device["window_s"] = trace["window_s"]
            result["breakdown"] = {"device_ops": trace["device_ops"],
                                   "idle_gaps": trace["idle_gaps"]}
        elif not args.rehearse:
            checks["traced_device_operations"] = False
            result["correct"] = False
            result["failed"] = max(failed, 1)

    note(workload=cell.name, seed=args.seed, seconds=args.seconds,
         trace=args.trace, rehearse=args.rehearse,
         host_cores=os.cpu_count(), setup=setup, warm_frames=warm,
         corpus=corpus_event, reference_s=reference_s)
    note(frames={"sent": len(frames), "acked": gen_done["acked"],
                 "in_window": len(win), "broken": gen_done["broken"],
                 "backlog_at_quarters": backlog_at_quarters(
                     frames, start_ns, args.seconds)},
         samples=samples, ack_ms_by_length_bucket=ack_by_length_bucket(
             win, bodies, labels, cell.config.get("length_buckets", [])),
         output={"sha256": digest_out,
                                  "expected_sha256": digest_exp,
                                  "bytes": pipe.sink.n_bytes(),
                                  "chunks": len(pipe.sink.parts)},
         window_counters=win_delta)
    note(checks=checks, skipped_checks=skipped,
         failed_checks=sorted(k for k, v in checks.items() if not v),
         reference=ref.get("info"),
         trace=None if trace is None else {
             k: trace[k] for k in ("busy_s", "span_s", "window_s",
                                   "launches", "devices")})
    # what was compared, beside its limit (every comparison is exact):
    # last in the result's line and last on standard error, where the
    # driver's record keeps it; the named checks say which verdict a
    # non-zero ``failed_checks`` stands for
    numbers["failed_checks"] = sum(1 for v in checks.values() if not v)
    result["compared"] = {k: {"value": v, "limit": 0}
                          for k, v in numbers.items()}
    for name in sorted(k for k, v in checks.items() if not v):
        print(f"failed check: {name}", file=sys.stderr)
    for name, c in result["compared"].items():
        print(f"compared {name}: {c['value']} (limit {c['limit']})",
              file=sys.stderr)
    sys.stderr.flush()
    print(json.dumps(result), flush=True)
    return 0 if result["correct"] else 1


def watchdog(*_):
    """Raised in the main thread, so that the generator is stopped and
    the work directory removed on the way out."""
    raise TimeoutError(f"the run took more than {WATCHDOG_S} s")


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, default=1)
    ap.add_argument("--seconds", type=float, default=None)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    ap.add_argument("--rehearse", action="store_true",
                    help="run without a TPU (never passed by the driver)")
    args = ap.parse_args(argv)
    if args.seconds is None:
        args.seconds = float(load_json(
            os.path.join(ROOT, "BENCHMARK.json"))["run_seconds"])
    signal.signal(signal.SIGALRM, watchdog)
    signal.alarm(WATCHDOG_S)
    return run(args)


if __name__ == "__main__":
    try:
        rc = main()
    except SystemExit as e:
        rc = e.code if isinstance(e.code, int) else 2
        if not isinstance(e.code, int) and e.code:
            print(e.code, file=sys.stderr)
    except BaseException:  # noqa: BLE001 - every failure is an exit code
        import traceback

        traceback.print_exc()
        rc = 1
    sys.stdout.flush()
    sys.stderr.flush()
    # abandoned lane workers or attach threads must not hold the exit
    os._exit(rc)
