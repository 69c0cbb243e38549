#!/usr/bin/env python3
"""chip_smoke.py — the quickest proof that the pipeline starts on the chip.

Drives the main path once, in ONE process, through the entry points a
user calls (``fluentbit_tpu.create()`` + the config loader → ``lib``
input → the engine's flush timer → filter → ``lib`` output), at
deployment size, and holds the result to the program's own counters:

- *grep*   ``conf/baseline1-grep.conf``'s filter (apache2, S=690 → scan
           kernel) plus one small ``Exclude`` rule on the same key
           (S=10: its own per-stride child, scan too), ≥ 1,000,000 seeded
           access-log lines over ≥ 3 flush windows, a handful longer
           than ``tpu_max_record_len`` (the overflow-row contract).
- *sketch* ``conf/baseline4-metrics.yaml``'s two ``log_to_metrics``
           filters (HLL p=14 on ``user``, count-min 4×16384 on ``path``)
           and one ``filter_flux`` (COUNT(DISTINCT user) + top-k path
           per tenant), ≥ 1,000,000 seeded events, ≥ 100,000 distinct
           users, Zipf-skewed paths.

Each phase is compared, outside any timing, with the plain reference in
the same process: grep against the same corpus through the same
pipeline with ``tpu.enable off`` (per-record host chain, byte-identical
survivors in order); sketches against ``host_update`` over the same
values (registers and table equal).

``ok`` is true only if the chip did the work: platform ``tpu``, every
lane ``ok == launches > 0`` with zero failures / timeouts / fallback
segments / short circuits / abandoned workers, device records ==
ingested records, sketch state resident on a TPU device, the donating
fused absorb. No stand-in: no JAX_PLATFORMS, no XLA_FLAGS, no host
fallback that reports success.

    python chip_smoke.py            # one chip (what the driver runs)
    python chip_smoke.py --chips 4  # the 4-device mesh paths only
    python chip_smoke.py --rules-sweep CONF MAKER 1,20,50
                                    # no phase: the compiled match
                                    # programs alone for the first R
                                    # grep (or rewrite_tag) rules of
                                    # CONF, child by child, and a frame
                                    # in two groups against the whole
                                    # (rules_sweep); with
                                    # --child-budgets 1024,48,32,16 the
                                    # children's probe alone at each
                                    # table budget (MiB)

Every line of stdout is one JSON object; the LAST line is
``{"ok": true, "device": {"platform": "tpu", "kind": "...", "count": N}}``
(``"ok": false`` and a non-zero exit on any failure).
"""

import argparse
import hashlib
import json
import os
import sys
import time

ROOT = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, ROOT)

SEED = 20260926
PUSH_RECORDS = 16384          # records per lib push = 4 full segments
N_RECORDS = 64 * PUSH_RECORDS  # 1,048,576 per phase (256 full segments)
SEGMENT = 4096                # filter_grep's default segment size
FLUSH_WINDOWS = 3             # the corpus spans at least this many
ATTACH_TIMEOUT_S = 600.0
TENANTS = (b"acme", b"globex", b"initech", b"umbrella")
SMALL_EXCLUDE = r"log curl/8\.5"   # S=10, k=5: the second child


class SmokeFailure(Exception):
    """A check failed; the message lands in the final ``ok: false``."""


def say(**kw) -> None:
    print(json.dumps(kw, default=str), flush=True)


def require(cond, what: str) -> None:
    if not cond:
        raise SmokeFailure(what)


# ---------------------------------------------------------------- attach

def attach(chips: int) -> dict:
    """Touch the device first, through the product's attach controller,
    and fail at once on anything but a TPU — before any corpus exists."""
    from fluentbit_tpu.ops import device

    ready = device.wait(ATTACH_TIMEOUT_S)
    st = device.status()
    say(stage="attach", ready=ready, seconds=st.get("attach_seconds"),
        platform=st.get("platform"), attempts=st.get("attempts"),
        error=st.get("error"))
    require(ready, f"device attach did not complete: {st}")
    import jax

    devs = jax.devices()
    info = {"platform": devs[0].platform, "kind": devs[0].device_kind,
            "count": len(devs)}
    require(info["platform"] == "tpu",
            f"jax found no accelerator: platform {info['platform']!r}")
    require(info["count"] == chips,
            f"need {chips} chip(s), jax reports {info['count']}")
    return info


# ----------------------------------------------------- compile accounting

class CompileLog:
    """XLA compiles, their seconds, and persistent-cache hits, from
    jax's own monitoring events."""

    def __init__(self):
        import jax

        self.compiles = 0
        self.seconds = 0.0
        self.cache_hits = 0
        self.cache_misses = 0
        self.longest = 0.0
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
            self.longest = max(self.longest, secs)

    def snapshot(self) -> dict:
        import jax

        from fluentbit_tpu.ops import fault

        return {"xla_compiles": self.compiles,
                "compile_seconds": round(self.seconds, 3),
                "longest_compile_seconds": round(self.longest, 3),
                "persistent_cache_hits": self.cache_hits,
                "persistent_cache_misses": self.cache_misses,
                "cache_dir": jax.config.jax_compilation_cache_dir,
                "launch_deadline_s": fault.launch_deadline()}


# ------------------------------------------------------------ native planes

def native_planes() -> None:
    """Build + load both native planes and print their source hashes.
    A hash-less prebuilt artefact (``ensure_built`` would adopt the
    current source's hash for it unseen) is refused, not trusted."""
    from fluentbit_tpu import native
    from fluentbit_tpu.codec import _native_codec
    from fluentbit_tpu.native.buildlib import src_hash

    planes = (("fbtpu_native", native._SRC, native._SO),
              ("fbtpu_codec", _native_codec._SRC, _native_codec._SO))
    before = {}
    for name, _src, so in planes:
        prebuilt = os.path.exists(so)
        require(not prebuilt or os.path.exists(so + ".hash"),
                f"{so} is a prebuilt artefact without a source-hash "
                f"sidecar; remove native/build and let the smoke build it")
        before[name] = prebuilt
    loaded = {"fbtpu_native": native.available(),
              "fbtpu_codec": _native_codec.load() is not None}
    for name, src, so in planes:
        digest = src_hash(src)
        try:
            with open(so + ".hash") as f:
                built = f.read().strip()
        except OSError:
            built = None
        say(stage="native", plane=name, loaded=loaded[name],
            built_this_run=not before[name], source_sha256=digest,
            artefact_sha256_of_source=built)
        require(loaded[name], f"native plane {name} did not build/load")
        require(digest is not None and built == digest,
                f"native plane {name}: artefact hash {built} != source "
                f"hash {digest}")


# ------------------------------------------------------------------ corpora

def grep_corpus(n: int, seed: int):
    """``bench.make_corpus``'s record shape (~25% non-matching kernel
    lines, apache2 access lines otherwise), seeded, plus a handful of
    long records: every 20,000th line carries a ~300-byte agent (the
    512 length bucket) and every 50,000th a ~700-byte one (longer than
    ``tpu_max_record_len``: an overflow row). → list of JSON push
    payloads (``[[ts, {"log": line}], ...]``), PUSH_RECORDS each."""
    import numpy as np

    rng = np.random.default_rng(seed)
    methods = ("GET", "POST", "PUT", "DELETE", "HEAD")
    agents = ("Mozilla/5.0 (X11; Linux x86_64)", "curl/8.5.0",
              "kube-probe/1.29")
    codes = (200, 301, 404, 500)
    # .tolist(): a million numpy-scalar reads per column cost more than
    # the formatting they feed
    kern = (rng.random(n) < 0.25).tolist()
    ip = rng.integers(0, 256, (n, 3)).tolist()
    frank = (rng.random(n) < 0.5).tolist()
    meth = rng.integers(0, len(methods), n).tolist()
    path = rng.integers(0, 10000, n).tolist()
    code = rng.integers(0, len(codes), n).tolist()
    size = rng.integers(0, 1 << 20, n).tolist()
    agent = rng.integers(0, len(agents), n).tolist()
    pid = rng.integers(0, 1 << 16, n).tolist()
    pushes, rows, n_long = [], [], 0
    for i in range(n):
        if kern[i]:
            line = f"kernel: oom-killer invoked pid={pid[i]}"
        else:
            ag = agents[agent[i]]
            if i % 50000 == 49999:
                ag = "Mozilla/5.0 " + "x" * 700
                n_long += 1
            elif i % 20000 == 19999:
                ag = "Mozilla/5.0 " + "y" * 300
            line = (
                f"10.{ip[i][0]}.{ip[i][1]}.{ip[i][2]} "
                f"- {'frank' if frank[i] else '-'} "
                f"[10/Oct/2000:13:55:{i % 60:02d} -0700] "
                f'"{methods[meth[i]]} /path/{path[i]} HTTP/1.1" '
                f"{codes[code[i]]} {size[i]} "
                f'"http://referer.example/{i // PUSH_RECORDS}" "{ag}"')
        rows.append([1700000000 + i * 0.001, {"log": line}])
        if len(rows) == PUSH_RECORDS or i == n - 1:
            pushes.append(json.dumps(rows))
            rows = []
    return pushes, n_long


def sketch_corpus(n: int, seed: int):
    """HTTP firehose events: ``user`` uniform over 250,000 ids (≥ 100,000
    distinct in 1M draws), ``path`` Zipf-skewed over 10,000 paths,
    ``tenant`` skewed over four. → (push payloads, users, paths,
    tenant ids) with the value columns kept for the host reference."""
    import numpy as np

    rng = np.random.default_rng(seed)
    uid = rng.integers(0, 250_000, n)
    pth = rng.zipf(1.2, n) % 10_000
    ten = rng.choice(len(TENANTS), n, p=(0.6, 0.25, 0.1, 0.05))
    users = [b"user-%06d" % u for u in uid.tolist()]
    paths = [b"/api/v1/item/%d" % p for p in pth.tolist()]
    tenants = [t.decode() for t in TENANTS]
    pushes, rows = [], []
    for i, t in enumerate(ten.tolist()):
        rows.append([1700000000 + i * 0.001,
                     {"user": users[i].decode(), "path": paths[i].decode(),
                      "tenant": tenants[t]}])
        if len(rows) == PUSH_RECORDS or i == n - 1:
            pushes.append(json.dumps(rows))
            rows = []
    return pushes, users, paths, ten


def stage_values(values, width: int = 64):
    """[n, width] u8 + lengths for the host reference sketches."""
    import numpy as np

    lengths = np.fromiter((len(v) for v in values), dtype=np.int32,
                          count=len(values))
    require(int(lengths.max()) <= width, "reference staging width")
    flat = b"".join(v.ljust(width, b"\0") for v in values)
    return (np.frombuffer(flat, dtype=np.uint8).reshape(-1, width),
            lengths)


# ----------------------------------------------------------- pipeline driver

class Sink:
    """``lib`` output callback: keeps what was flushed, and when."""

    def __init__(self):
        self.parts = []
        self.times = []

    def __call__(self, data, _tag):
        self.parts.append(bytes(data))
        self.times.append(time.time())

    def windows(self, flush_s: float) -> int:
        """Distinct flush-timer ticks that delivered something."""
        n, last = 0, None
        for t in self.times:
            if last is None or t - last > flush_s / 2:
                n += 1
            last = t
        return n


def drive(ctx, in_ffd, pushes, flush_s: float, spread: bool) -> int:
    """Start the engine, push the corpus through the input, drain, stop.
    With ``spread`` the pushes are paced so the corpus spans more than
    FLUSH_WINDOWS flush windows even when the filter is fast. → records
    the engine counted in (before the filters)."""
    from fluentbit_tpu.ops import device

    require(device.ready(), "device not ready before ctx.start()")
    ctx.start()
    t0 = time.time()
    try:
        for i, payload in enumerate(pushes):
            if spread:
                due = t0 + (FLUSH_WINDOWS + 1.2) * flush_s * i / len(pushes)
                time.sleep(max(0.0, due - time.time()))
            ctx.push(in_ffd, payload)
        ctx.flush_now()
    finally:
        ctx.stop()
    lib = next(i for i in ctx.engine.inputs if i.plugin.name == "lib")
    return int(ctx.engine.m_in_records.get((lib.display_name,)))


def total(metric) -> int:
    """Sum of one engine metric across its label sets."""
    return int(sum(v for _labels, v in metric.samples()))


def expected_launches(n_records: int) -> int:
    """One lane launch per (at most SEGMENT-record) segment of a push."""
    full, rest = divmod(n_records, PUSH_RECORDS)
    return full * -(-PUSH_RECORDS // SEGMENT) + -(-rest // SEGMENT)


def lane_report(name: str, expect_launches=None) -> dict:
    """One lane's counters, held to 'the chip did all of it'."""
    from fluentbit_tpu.ops import fault

    st = fault.snapshot().get(name)
    require(st is not None, f"lane {name!r} was never used")
    keys = ("launches", "ok", "failures", "timeouts", "fallback_segments",
            "short_circuits", "abandoned")
    say(stage="lane", lane=name, **{k: st[k] for k in keys},
        breaker=st["breaker"], mesh_devices=st["mesh_devices"])
    require(st["launches"] > 0, f"lane {name}: no launches")
    require(st["ok"] == st["launches"],
            f"lane {name}: ok {st['ok']} != launches {st['launches']}")
    for k in keys[2:]:
        require(st[k] == 0, f"lane {name}: {k} = {st[k]}")
    if expect_launches is not None:
        require(st["launches"] == expect_launches,
                f"lane {name}: {st['launches']} launches, expected "
                f"{expect_launches}")
    return st


# ------------------------------------------------------------------- grep

def grep_context(sink, device_on: bool):
    """``conf/baseline1-grep.conf`` through the config loader: its
    SERVICE and FILTER sections as written (plus the small Exclude
    rule, first, so legacy mode consults it before the Regex decides),
    its dummy input and stdout output replaced by ``lib`` ones."""
    import fluentbit_tpu as flb
    from fluentbit_tpu.config_format import (ConfigFile, Section,
                                             apply_to_context,
                                             load_config_file)

    cf = load_config_file(os.path.join(ROOT, "conf", "baseline1-grep.conf"))
    sections, tag = [], "bench.apache"
    for sec in cf.sections:
        if sec.name == "service":
            sections.append(sec)
        elif sec.name == "input":
            tag = sec.get("tag", tag)
        elif sec.name == "filter":
            props = []
            for k, v in sec.properties:
                if k.lower() == "regex":
                    props.append(("Exclude", SMALL_EXCLUDE))
                props.append((k, v))
            if not device_on:
                props.append(("tpu.enable", "off"))
            sections.append(Section("filter", props))
    ctx = flb.create()
    apply_to_context(ctx, ConfigFile(sections, cf.env),
                     os.path.join(ROOT, "conf"))
    in_ffd = ctx.input("lib", tag=tag)
    ctx.output("lib", match="*", callback=sink)
    return ctx, in_ffd


def grep_phase(dev: dict, n_records: int, mesh_sample: bool) -> None:
    from fluentbit_tpu.ops import fault

    t0 = time.time()
    pushes, n_long = grep_corpus(n_records, SEED)
    say(stage="grep:corpus", records=n_records, pushes=len(pushes),
        longer_than_max_record_len=n_long,
        json_bytes=sum(len(p) for p in pushes),
        seconds=round(time.time() - t0, 1))

    # -- the device run
    fault.reset()
    sink = Sink()
    ctx, in_ffd = grep_context(sink, device_on=True)
    flush_s = float(ctx.service.flush)
    t0 = time.time()
    ingested = drive(ctx, in_ffd, pushes, flush_s, spread=True)
    wall = time.time() - t0
    out_dev = b"".join(sink.parts)
    plugin = ctx.engine.filters[0].plugin
    prog = plugin._program
    require(prog is not None, "grep built no device program")
    children = prog._children or [prog]
    decisions = [c.decision() for c in children]
    for d in decisions:
        say(stage="grep:decision", max_states=d["max_states"], k=d["k"],
            kernel_resolved=d["kernel_resolved"],
            rules=[{"pattern": r["pattern"][:40], "s": r["s"], "c": r["c"],
                    "k": r["k"], "class_runs": r["class_runs"]}
                   for r in d["rules"]])
    tm = plugin.raw_timings
    launches = expected_launches(n_records)
    declines = total(ctx.engine.m_filter_batch_decline)
    counts = {
        "records_ingested": ingested,
        "records_through_device": int(tm["device_records"]),
        "overflow_rows": int(tm["overflow_rows"]),
        "raw_path_declines": declines,
        "records_out": total(ctx.engine.m_out_proc_records),
        "h2d_bytes": int(tm["h2d_bytes"]),
        "h2d_bytes_per_segment": int(tm["h2d_bytes"]) // max(launches, 1),
        "flush_windows": sink.windows(flush_s),
        "wall_seconds": round(wall, 1),
        "extract_s": round(tm["extract_s"], 2),
        "launch_wait_s": round(tm["kernel_s"], 2),
        "compact_s": round(tm["compact_s"], 2),
    }
    say(stage="grep:counts", **counts)
    st = lane_report("grep", expect_launches=launches)
    if dev["count"] > 1:
        require(st["mesh_devices"] == dev["count"],
                f"grep lane mesh has {st['mesh_devices']} devices")
    require(ingested == n_records,
            f"ingested {ingested} of {n_records} pushed")
    require(declines == 0, f"{declines} raw-path declines")
    require(counts["records_through_device"] == ingested,
            "records through the device != records ingested")
    require(counts["overflow_rows"] == n_long,
            f"overflow rows {counts['overflow_rows']} != {n_long} long "
            f"records in the corpus")
    require(counts["flush_windows"] >= FLUSH_WINDOWS,
            f"corpus spanned {counts['flush_windows']} flush windows")
    kernels = sorted((d["kernel_resolved"], d["max_states"] <= 64)
                     for d in decisions)
    require(kernels == [("scan", False), ("scan", True)],
            f"expected two scan-resolved children, one on each side of "
            f"S=64, got {kernels}")
    if mesh_sample:
        grep_mesh_vs_one_program(plugin, pushes[0])
        st = lane_report("grep")
    else:
        grep_launch_probe(prog)

    # -- the plain reference: same corpus, same pipeline, tpu.enable off
    t0 = time.time()
    ref_sink = Sink()
    ctx, in_ffd = grep_context(ref_sink, device_on=False)
    ref_in = drive(ctx, in_ffd, pushes, flush_s, spread=False)
    require(ctx.engine.filters[0].plugin._program is None,
            "reference pipeline built a device program")
    out_ref = b"".join(ref_sink.parts)
    same = out_dev == out_ref
    say(stage="grep:compare", reference="tpu.enable off (per-record)",
        reference_seconds=round(time.time() - t0, 1),
        reference_ingested=ref_in, bytes_device=len(out_dev),
        bytes_reference=len(out_ref),
        sha256_device=hashlib.sha256(out_dev).hexdigest(),
        sha256_reference=hashlib.sha256(out_ref).hexdigest(),
        records_kept=counts["records_out"], byte_identical=same)
    require(fault.snapshot()["grep"]["launches"] == st["launches"],
            "the reference run launched on the device")
    require(same and len(out_ref) > 0,
            "surviving records differ from the tpu.enable-off reference")
    require(0 < counts["records_out"] < n_records,
            "the filter kept everything or nothing")


def median_ms(fn) -> float:
    """The median wall time of ``fn()`` (which ends in a forced
    result), ms: 7 calls, or 3 once they have taken 2 s (assoc at
    S=690)."""
    times = []
    while len(times) < 7 and (len(times) < 3 or sum(times) < 2e3):
        t0 = time.perf_counter()
        fn()
        times.append((time.perf_counter() - t0) * 1e3)
    return round(sorted(times)[len(times) // 2], 3)


def grep_launch_probe(prog, batches=(SEGMENT,), kernels=("scan", "assoc"),
                      stage=None) -> None:
    """Where one segment's launch spends its time, per child, kernel,
    batch and length bucket: staged planes to the device, the compiled
    kernel alone, and the whole forced launch — medians of 7 (of 3
    where a call takes seconds) on the host's clock, ending in a forced
    result. Every child is timed on each of ``kernels`` (its own
    program, and a twin built with the other ``kernel=``), all held to
    the same verdicts, so each smoke repeats the probe
    ``_resolve_kernel``'s rule rests on (PERF.md, PR 33);
    ``kernels=("scan",)`` builds no twin (at R=50 the assoc twin would
    gather S times 200,000 elements a step: PERF.md, PR 34).
    ``stage(K, B, L)`` → ``(planes u8[K, B, L], lengths i32[K, B])``
    gives the probe its rows (printable random bytes without it). A
    smoke observation, not a benchmark: one run, no warm-up policy, no
    spread."""
    import jax
    import numpy as np

    from fluentbit_tpu.ops.grep import GrepProgram

    rng = np.random.default_rng(SEED)

    def random_rows(K, B, L):
        return (rng.integers(32, 127, (K, B, L), dtype=np.uint8),
                rng.integers(0, L + 1, (K, B), dtype=np.int32))

    for child in prog._children or [prog]:
        K = child.n_planes  # the distinct staged planes its rules read
        twins = {}
        for kern in kernels:
            twin = child
            if kern != child.kernel_resolved:
                twin = GrepProgram(child.dfas, child.max_len, kernel=kern,
                                   plane_of=child.plane_of)
                twin.n_planes = K
                twin._ensure_materialized()
            twins[kern] = twin
        for B in batches:
            for L in (256, 512):
                batch, lengths = (stage or random_rows)(K, B, L)
                dev = [jax.device_put(batch), jax.device_put(lengths)]
                h2d_ms = median_ms(lambda: [
                    jax.device_put(a).block_until_ready()
                    for a in (batch, lengths)])
                masks = {}
                for kern, twin in twins.items():
                    t0 = time.perf_counter()
                    masks[kern] = np.asarray(twin._jit(*dev))
                    first_ms = round((time.perf_counter() - t0) * 1e3, 1)
                    say(stage="grep:launch_probe", kernel=kern,
                        resolved=child.kernel_resolved,
                        max_states=child.max_states, k=child.k,
                        rules=len(child.dfas), shape=[K, B, L],
                        h2d_ms=h2d_ms, first_call_ms=first_ms,
                        kernel_ms=median_ms(
                            lambda: twin._jit(*dev).block_until_ready()),
                        forced_launch_ms=median_ms(
                            lambda: np.asarray(
                                twin.dispatch(batch, lengths))),
                        note="smoke observation, one run, not a benchmark")
                first = next(iter(masks.values()))
                require(all(np.array_equal(first, m)
                            for m in masks.values()),
                        f"{' and '.join(masks)} verdicts differ at "
                        f"{[K, B, L]} (S={child.max_states}, k={child.k})")


def rules_sweep(conf: str, maker: str, sizes, budgets_mib=()) -> None:
    """The compiled match programs alone along a rule axis: for each
    ``R`` of ``sizes`` the first ``R`` rules of pipeline file ``conf``'s
    grep filter — or of its ``rewrite_tag`` filter, where it has no
    grep — as the filter builds them (``program_for`` with the filter's
    ``plane_of``: scan children — one stride and at most 16 MiB of
    tables a child — under ``FBTPU_MESH_RULE_SHARD_R``),
    probed child by child on the scan kernel (``grep_launch_probe``)
    over 256, 1,024 and ``SEGMENT`` records of the benchmark's corpus
    maker ``maker`` staged at L=256 and L=512 (a longer value is an
    overflow row), the whole program's verdicts held to Python's ``re``
    on the same rows, and a frame staged in two groups against the
    frame staged whole (``two_group_probe``). How a launch's device
    time grows with the list, the rows and the width, without the
    pipeline around it. Each child's module alone over a frame staged
    as a catch-up cell stages it says what a gathered element costs
    from that child's tables (``child_probe``); with ``budgets_mib``
    that probe alone, for the same rules laid out under each child
    table budget in turn (``GrepProgram(child_budget=)``; the numbers
    beside ``ops.grep._CHILD_TABLE_BUDGET``)."""
    import importlib.util
    import re

    import numpy as np

    from fluentbit_tpu.config_format import load_config_file
    from fluentbit_tpu.core.plugin import Properties
    from fluentbit_tpu.ops.grep import GrepProgram, program_for
    from fluentbit_tpu.plugins.filter_grep import (parse_grep_rules,
                                                   plane_index)
    from fluentbit_tpu.plugins.filter_rewrite_tag import RewriteRule

    filters = {sec.get("name", "").lower(): sec
               for sec in load_config_file(conf).sections
               if sec.name == "filter"}
    section = filters.get("grep") or filters["rewrite_tag"]
    if section is filters.get("grep"):
        props = Properties()
        for k, v in section.properties:
            props.set(k, v)
        rules = parse_grep_rules(props)
    else:
        rules = [RewriteRule(*v.split(None, 3))
                 for k, v in section.properties if k.lower() == "rule"]
    max_len = int(section.get("tpu_max_record_len", 512))
    accessors, plane_of = plane_index(rules)
    require(len(accessors) == 1 and not accessors[0].parts,
            "the sweep stages one plane: rules on one top-level key")

    sys.path.insert(0, os.path.dirname(os.path.dirname(maker)))
    spec = importlib.util.spec_from_file_location("sweep_corpus", maker)
    corpus = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(corpus)
    records, _labels = corpus.make(SEGMENT, SEED, {})
    values = [r[accessors[0].head].encode() for r in records]

    def stage(K, B, L):
        planes = np.zeros((K, B, L), dtype=np.uint8)
        lengths = np.full((K, B), -2, dtype=np.int32)
        for j, v in enumerate(values[:B]):
            if len(v) <= L:
                planes[:, j, :len(v)] = np.frombuffer(v, dtype=np.uint8)
                lengths[:, j] = len(v)
        return planes, lengths

    for R in sizes:
        patterns = tuple(r.regex.pattern for r in rules[:R])
        prog = program_for(patterns, max_len, plane_of=plane_of[:R])
        for mib in budgets_mib:
            laid = GrepProgram(prog.dfas, max_len, plane_of=plane_of[:R],
                               child_budget=mib << 20)
            require(laid.try_ready(), f"{R} rules at {mib} MiB a child "
                    "did not attach")
            child_probe(laid, patterns, values, budget_mib=mib)
        if budgets_mib:
            continue
        require(prog.try_ready(), f"the {R}-rule program did not attach")
        say(stage="rules_sweep:program", rules=R,
            children=prog.decision()["children"],
            elements_256=prog.scan_elements(SEGMENT, 256),
            elements_512=prog.scan_elements(SEGMENT, 512))
        child_probe(prog, patterns, values)
        grep_launch_probe(prog, batches=(256, 1024, SEGMENT),
                          kernels=("scan",), stage=stage)
        two_group_probe(prog, patterns, values)
        for L in (256, 512):
            planes, lengths = stage(1, SEGMENT, L)
            got = np.asarray(prog.match(planes, lengths))
            want = np.array([[len(v) <= L and re.search(p, v.decode())
                              is not None for v in values]
                             for p in patterns])
            require(np.array_equal(got, want),
                    f"{R} rules at L={L}: the program and re differ on "
                    f"{int((got != want).sum())} verdicts")
            say(stage="rules_sweep:verdicts", rules=R, L=L, equal=True,
                matches=int(got.sum()),
                rows_matched=int(got.any(axis=0).sum()))


def two_group_frame(values, B: int, n_long: int):
    """``B`` rows of ``values`` of at most 256 B with ``n_long`` rows of
    257-500 B (corpus values joined) spread among them, as
    ``filter_grep.staged_match`` would send them: whole at L=512, and
    the main group at L=256 with the long rows as rows without a value
    beside those as a 256-row ``LongGroup`` at L=512. → ``(rows, at,
    (planes, lengths) whole, (planes, lengths) main, (planes, lengths,
    row indices) long)``, the arrays on the device."""
    import jax
    import numpy as np

    from fluentbit_tpu.plugins.filter_grep import LongGroup

    short = [v for v in values if len(v) <= 256]
    joined = b" ".join(values)
    put = jax.device_put
    rows = [short[i % len(short)] for i in range(B)]
    at = np.linspace(0, B - 1, n_long).astype(np.int32)
    for j, i in enumerate(at):
        cut = 257 + (j * 61) % 244
        rows[i] = joined[j * 97:j * 97 + cut]
    whole = np.zeros((1, B, 512), dtype=np.uint8)
    lengths = np.zeros((1, B), dtype=np.int32)
    for i, v in enumerate(rows):
        whole[0, i, :len(v)] = np.frombuffer(v, dtype=np.uint8)
        lengths[0, i] = len(v)
    group = LongGroup.of([(whole[0], lengths[0])], at, 512, B)
    main_len = lengths.copy()
    main_len[0, at] = -1
    return (rows, at, (put(whole), put(lengths)),
            (put(np.ascontiguousarray(whole[:, :, :256])), put(main_len)),
            tuple(put(a) for a in group[:3]))


def child_probe(prog, patterns, values, budget_mib=None) -> None:
    """What a gathered element costs from each child's tables: every
    child's module alone (``_jit_long``, forced; median of 7 on the
    host's clock) over one frame staged as a catch-up cell stages it —
    ``SEGMENT`` rows at L=256 and its 100 lines of 257-500 B as a
    256-row group at L=512 — with the child's name, stride, rules and
    laid-out table bytes (``decision()["children"]``), and then the
    whole launch, children back to back and the merge, its verdicts
    held to ``re``."""
    import re

    import numpy as np

    rows, _at, _whole, d_main, d_long = two_group_frame(
        values, SEGMENT, 100)
    B_long = d_long[0].shape[1]
    total = 0.0
    for child, said in zip(prog._children or [prog],
                           prog.decision()["children"]):
        elements = (child.scan_elements(SEGMENT, 256)
                    + child.scan_elements(B_long, 512))
        child._jit_long(*d_main, *d_long).block_until_ready()  # compiles
        ms = median_ms(lambda: child._jit_long(
            *d_main, *d_long).block_until_ready())
        total += ms
        say(stage="rules_sweep:child", budget_mib=budget_mib,
            list_rules=len(patterns), **said, elements=elements,
            launch_ms=ms, ns_per_element=round(1e6 * ms / elements, 2))
    got = np.asarray(prog._enqueue(*d_main, d_long))
    want = np.array([[re.search(p, v.decode()) is not None for v in rows]
                     for p in patterns])
    require(np.array_equal(got, want),
            f"{len(patterns)} rules at {budget_mib} MiB a child: the "
            f"program and re differ on {int((got != want).sum())} verdicts")
    elements = (prog.scan_elements(SEGMENT, 256)
                + prog.scan_elements(B_long, 512))
    ms = median_ms(lambda: prog._enqueue(
        *d_main, d_long).block_until_ready())
    say(stage="rules_sweep:launch", budget_mib=budget_mib,
        list_rules=len(patterns), children=len(prog._children or [prog]),
        table_bytes=prog.table_bytes, elements=elements, equal=True,
        children_alone_ms=round(total, 3), launch_ms=ms,
        ns_per_element=round(1e6 * ms / elements, 2),
        note="smoke observation, one run, not a benchmark")


def two_group_probe(prog, patterns, values, batches=(1024, SEGMENT),
                    longs=(1, 100, 256)) -> None:
    """A frame staged in two groups against the frame staged whole, on
    the program alone (``GrepProgram._enqueue`` over planes that are on
    the device, every child and the merge, forced): ``B`` rows of
    ``values`` of at most 256 B with ``n_long`` rows of 257-500 B (corpus
    values joined) among them, as ``filter_grep.staged_match`` would
    send them — whole at L=512; the main group at L=256 with the long
    rows as rows without a value, and they as a 256-row group at L=512
    with their row indices, in one module a child — and beside the two
    the main group alone and the long group alone. Medians of 7 on the
    host's clock; the two verdicts held to each other and to ``re``.
    The numbers beside ``filter_grep._LONG_SHARE``."""
    import re

    import numpy as np

    def forced_ms(*args):
        return median_ms(lambda: prog._enqueue(*args).block_until_ready())

    for B in batches:
        for n_long in longs:
            rows, at, d_whole, d_main, d_long = two_group_frame(
                values, B, n_long)
            got_whole = np.asarray(prog._enqueue(*d_whole))
            got_two = np.asarray(prog._enqueue(*d_main, d_long))
            want = np.array([[re.search(p, v.decode()) is not None
                              for v in rows] for p in patterns])
            require(np.array_equal(got_two, got_whole)
                    and np.array_equal(got_two, want),
                    f"two groups, whole and re differ at B={B}, "
                    f"{n_long} long rows")
            whole_ms = forced_ms(*d_whole)
            two_ms = forced_ms(*d_main, d_long)
            say(stage="rules_sweep:two_groups", rules=len(patterns),
                rows=B, long_rows=n_long, equal=True,
                long_matches=int(got_two[:, at].sum()),
                elements_whole=prog.scan_elements(B, 512),
                elements_two=prog.scan_elements(B, 256)
                + prog.scan_elements(*d_long[0].shape[1:]),
                whole_512_ms=whole_ms, two_groups_ms=two_ms,
                main_256_alone_ms=forced_ms(*d_main),
                long_group_alone_ms=forced_ms(*d_long[:2]),
                two_over_whole=round(two_ms / whole_ms, 3),
                note="smoke observation, one run, not a benchmark")


def grep_mesh_vs_one_program(plugin, payload: str) -> None:
    """--chips 4: the mesh launch against the one-program launch and
    the host twin, on one push of the corpus, through the plugin's own
    staging; plus where every operand of the mesh program lives."""
    import numpy as np

    from fluentbit_tpu.codec.events import encode_event
    from fluentbit_tpu.codec.msgpack import EventTime

    rows = json.loads(payload)
    data = b"".join(encode_event(body, EventTime.from_float(ts))
                    for ts, body in rows)
    mesh = plugin._grep_mesh()
    require(mesh is not None, "grep mesh did not engage")
    m_mesh, _, n = plugin._jax_match_raw(data, len(rows), mesh=mesh)
    m_one, _, n1 = plugin._jax_match_raw(data, len(rows), mesh=None)
    host = np.zeros_like(m_one)
    for r, rule in enumerate(plugin.rules):
        host[r] = [rule.match(body) for _ts, body in rows]
    say(stage="grep:mesh_vs_one_program", records=n,
        mesh_equals_one_program=bool(np.array_equal(m_mesh, m_one)),
        mesh_equals_host_twin=bool(np.array_equal(m_mesh, host)),
        matches_per_rule=m_mesh.sum(axis=1).tolist())
    require(n == n1 == len(rows), "sample push miscounted")
    require(np.array_equal(m_mesh, m_one), "mesh mask != one-program mask")
    require(np.array_equal(m_mesh, host), "mesh mask != host twin mask")
    prog = plugin._program
    for ci, child in enumerate(prog._children or [prog]):
        say(stage="grep:sharding", child=ci, operand="tables@materialize",
            sharding={k: str(v.sharding) for k, v in child._tbl.items()})
        for h in child._mesh_cache.values():
            say(stage="grep:sharding", child=ci, variant=h.variant,
                devices=h.n_devices, donated_args=list(h.donate_idx),
                tables={k: str(v.sharding) for k, v in h.tables.items()},
                batch=str(h.sh_b), lengths=str(h.sh_l))
            text = h.fn.lower(
                h.tables,
                np.zeros((len(child.dfas), SEGMENT, plugin.tpu_max_record_len),
                         np.uint8),
                np.full((len(child.dfas), SEGMENT), -1, np.int32)
            ).compile().as_text()
            say(stage="grep:collectives", child=ci,
                all_reduce="all-reduce" in text,
                all_gather="all-gather" in text)


# ----------------------------------------------------------------- sketch

def sketch_context(log_sink, metric_sink, with_log_to_metrics: bool,
                   mesh: bool):
    """``conf/baseline4-metrics.yaml``'s two log_to_metrics filters as
    written, one filter_flux behind them, ``lib`` in and out."""
    import fluentbit_tpu as flb
    from fluentbit_tpu.config_format import (ConfigFile, Section,
                                             apply_to_context,
                                             load_config_file)

    cf = load_config_file(
        os.path.join(ROOT, "conf", "baseline4-metrics.yaml"))
    sections = [s for s in cf.sections if s.name == "service"]
    if with_log_to_metrics:
        sections += [s for s in cf.sections if s.name == "filter"]
    sections.append(Section("filter", [
        ("name", "flux"), ("match", "firehose"),
        ("group_by", "tenant"), ("distinct_field", "user"),
        ("topk_field", "path"), ("sketch_precision", "14"),
        ("mesh", "on" if mesh else "off"), ("export_interval_sec", "1"),
    ]))
    ctx = flb.create()
    apply_to_context(ctx, ConfigFile(sections, cf.env),
                     os.path.join(ROOT, "conf"))
    in_ffd = ctx.input("lib", tag="firehose")
    ctx.output("lib", match="firehose", callback=log_sink)
    ctx.output("lib", match="metrics", callback=metric_sink)
    return ctx, in_ffd


def on_device(arr, dev: dict) -> bool:
    """A jax array resident on the platform the run is held to."""
    devices = getattr(arr, "devices", None)
    return devices is not None and all(
        d.platform == dev["platform"] for d in devices())


def sketch_phase(dev: dict, n_records: int, with_log_to_metrics: bool,
                 mesh: bool) -> None:
    import numpy as np

    from fluentbit_tpu.flux import kernels
    from fluentbit_tpu.ops import fault
    from fluentbit_tpu.ops.sketch import CountMin, HyperLogLog

    t0 = time.time()
    pushes, users, paths, ten = sketch_corpus(n_records, SEED + 1)
    say(stage="sketch:corpus", records=n_records, pushes=len(pushes),
        distinct_users=len(set(users)), distinct_paths=len(set(paths)),
        seconds=round(time.time() - t0, 1))
    require(len(set(users)) >= min(100_000, n_records // 8),
            "corpus has too few distinct users")

    fault.reset()
    kernels._fused_cache.clear()
    log_sink, metric_sink = Sink(), Sink()
    ctx, in_ffd = sketch_context(log_sink, metric_sink,
                                 with_log_to_metrics, mesh)
    flush_s = float(ctx.service.flush)
    t0 = time.time()
    ingested = drive(ctx, in_ffd, pushes, flush_s, spread=True)
    wall = time.time() - t0
    plugins = [f.plugin for f in ctx.engine.filters]
    flux = plugins[-1].state
    say(stage="sketch:counts", records_ingested=ingested,
        records_out=int(ctx.engine.m_out_proc_records.get(
            (ctx.engine.outputs[0].display_name,))),
        metric_snapshots=len(metric_sink.parts),
        flush_windows=log_sink.windows(flush_s),
        flux_records=flux.records_total, flux_batches=flux.batches_total,
        wall_seconds=round(wall, 1))
    require(ingested == n_records,
            f"ingested {ingested} of {n_records} pushed")
    require(flux.records_total == n_records, "flux absorbed fewer records")
    require(log_sink.windows(flush_s) >= FLUSH_WINDOWS,
            "sketch corpus spanned too few flush windows")
    st = lane_report("flux", expect_launches=len(pushes))
    if mesh:
        require(st["mesh_devices"] == dev["count"],
                f"flux lane mesh has {st['mesh_devices']} devices")
    fused = [{"mesh": k[0] is not None, "n_pad": k[1], "fields": k[2],
              "hll_p": k[3], "cms": k[4], "donate": k[5]}
             for k in kernels._fused_cache]
    say(stage="sketch:fused_absorb", programs=fused)
    require(fused and all(f["donate"] for f in fused),
            "the fused absorb that ran is not the donating one")
    require(all(f["mesh"] == mesh for f in fused),
            "fused absorb ran on the wrong side of the mesh choice")

    # -- the plain reference: host_update over the same values
    t0 = time.time()
    ub, ul = stage_values(users)
    pb, pl = stage_values(paths)
    verdicts = {}
    if with_log_to_metrics:
        hll_p, cms_p = plugins[0], plugins[1]
        ref_hll = HyperLogLog(p=hll_p.hll.p)
        ref_hll.host_update(ub, ul)
        ref_cms = CountMin(cms_p.cms.depth, cms_p.cms.width)
        ref_cms.host_update(pb, pl)
        say(stage="sketch:state", owner="log_to_metrics",
            hll_registers=str(getattr(hll_p.hll.registers, "sharding",
                                      type(hll_p.hll.registers))),
            cms_table=str(getattr(cms_p.cms.table, "sharding",
                                  type(cms_p.cms.table))),
            hll_shape=list(np.shape(hll_p.hll.registers)),
            cms_shape=list(np.shape(cms_p.cms.table)),
            estimate=round(hll_p.hll.estimate()),
            exact_distinct=len(set(users)))
        require(on_device(hll_p.hll.registers, dev),
                "log_to_metrics HLL registers are not on the device")
        require(on_device(cms_p.cms.table, dev),
                "log_to_metrics count-min table is not on the device")
        verdicts["log_to_metrics_hll_equal"] = bool(np.array_equal(
            np.asarray(hll_p.hll.registers), ref_hll.registers))
        verdicts["log_to_metrics_cms_equal"] = bool(np.array_equal(
            np.asarray(cms_p.cms.table), ref_cms.table))
    groups = dict(flux.live_groups())
    ref_table = CountMin(flux.cms.depth, flux.cms.width)
    comp = [TENANTS[t] + b"\x1f" + p for t, p in zip(ten.tolist(), paths)]
    ref_table.host_update(*stage_values(comp))
    verdicts["flux_cms_equal"] = bool(np.array_equal(
        np.asarray(flux.cms.table), ref_table.table))
    require(on_device(flux.cms.table, dev),
            "flux count-min table is not on the device")
    for t, name in enumerate(TENANTS):
        g = groups.get((name,))
        require(g is not None, f"flux has no group for tenant {name!r}")
        rows = np.nonzero(ten == t)[0]
        ref = HyperLogLog(p=flux.spec.hll_p)
        ref.host_update(ub[rows], ul[rows])
        regs = g.hlls["user"].registers
        require(on_device(regs, dev),
                f"flux HLL registers of {name!r} are not on the device")
        verdicts[f"flux_hll_equal[{name.decode()}]"] = bool(
            np.array_equal(np.asarray(regs), ref.registers))
        verdicts[f"flux_count_equal[{name.decode()}]"] = \
            g.count == int(rows.size)
    say(stage="sketch:state", owner="flux",
        cms_table=str(flux.cms.table.sharding),
        hll_registers=str(
            groups[(TENANTS[0],)].hlls["user"].registers.sharding),
        groups=len(groups))
    if mesh:
        verdicts["flux_mesh_equals_one_program"] = \
            flux_mesh_vs_one_program(flux, ub, ul, pb, pl, ten)
    say(stage="sketch:compare", reference="host_update over the same "
        "values", reference_seconds=round(time.time() - t0, 1), **verdicts)
    bad = [k for k, v in verdicts.items() if not v]
    require(not bad, f"sketch state differs from the reference: {bad}")


def flux_mesh_vs_one_program(flux, ub, ul, pb, pl, ten) -> bool:
    """--chips 4: the mesh fused absorb (psum/pmax merge) against the
    one-program fused absorb on the same staged batch, output for
    output; prints each output's sharding."""
    import numpy as np

    from fluentbit_tpu.flux import kernels

    B = PUSH_RECORDS
    seg = ten[:B].astype(np.int32)
    valid = np.ones((B,), np.int32)
    m = 1 << flux.spec.hll_p
    regs = [[np.zeros((m,), np.int32) for _ in TENANTS]]
    table = np.zeros((flux.cms.depth, flux.cms.width), flux.cms._dtype)
    args = (seg, valid, [(ub[:B], ul[:B])], regs, pb[:B], pl[:B], table)
    kw = dict(hll_p=flux.spec.hll_p, cms=flux.cms, n_seg=len(TENANTS))
    mesh = flux._flux_lane().current_mesh(axis="flux")
    got_m = kernels.sharded_fused_absorb(mesh, *args, **kw)
    got_1 = kernels.fused_absorb(*args, **kw)
    same = (np.array_equal(got_m[0], got_1[0])
            and np.array_equal(got_m[1][0], got_1[1][0])
            and np.array_equal(got_m[2], got_1[2]))
    say(stage="sketch:mesh_vs_one_program", equal=bool(same),
        counts=np.asarray(got_m[0]).tolist(),
        mesh_shardings={"counts": str(got_m[0].sharding),
                        "registers": str(got_m[1][0].sharding),
                        "table": str(got_m[2].sharding)},
        one_program_shardings={"registers": str(got_1[1][0].sharding),
                               "table": str(got_1[2].sharding)})
    return bool(same)


# -------------------------------------------------------------------- main

def run(chips: int, n_records: int = N_RECORDS) -> dict:
    dev = attach(chips)
    compiles = CompileLog()
    native_planes()
    four = chips > 1
    grep_phase(dev, n_records, mesh_sample=four)
    say(stage="compiles", after="grep", **compiles.snapshot())
    # four chips: only what exists across chips — the grep mesh above
    # and the flux merge; the log_to_metrics sketches are one-chip state
    sketch_phase(dev, n_records, with_log_to_metrics=not four, mesh=four)
    say(stage="compiles", after="sketch", **compiles.snapshot())
    import jax

    stats = [d.memory_stats() or {} for d in jax.devices()]
    say(stage="device_memory",
        peak_bytes_in_use=[s.get("peak_bytes_in_use") for s in stats],
        bytes_limit=[s.get("bytes_limit") for s in stats])
    return dev


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1,
                    help="1 (default): both phases on one chip; 4: only "
                         "the grep mesh and the flux merge across 4")
    ap.add_argument("--rules-sweep", nargs=3, default=None,
                    metavar=("CONF", "MAKER", "SIZES"),
                    help="instead of the phases: the compiled match "
                         "programs alone for the first R grep (or "
                         "rewrite_tag) rules of pipeline file CONF, R in "
                         "SIZES (1,20,50), over records of the "
                         "benchmark's corpus maker MAKER, and a frame in "
                         "two groups against the whole (rules_sweep)")
    ap.add_argument("--child-budgets", default="", metavar="MIB",
                    help="with --rules-sweep: only the children's probe, "
                         "the rules laid out under each of these child "
                         "table budgets (MiB, 1024,48,32,16) in turn")
    args = ap.parse_args(argv)
    dev = None
    try:
        if args.rules_sweep:
            conf, maker, sizes = args.rules_sweep
            dev = attach(1)
            rules_sweep(os.path.abspath(conf), os.path.abspath(maker),
                        [int(n) for n in sizes.split(",")],
                        [int(n) for n in args.child_budgets.split(",")
                         if n])
        else:
            dev = run(args.chips)
    except BaseException as e:  # noqa: BLE001 - every failure is a verdict
        import traceback

        traceback.print_exc()
        if dev is None:
            try:
                import jax

                d = jax.devices()
                dev = {"platform": d[0].platform, "kind": d[0].device_kind,
                       "count": len(d)}
            except BaseException:  # noqa: BLE001
                dev = None
        say(ok=False, error=f"{type(e).__name__}: {e}", device=dev)
        return 1
    say(ok=True, device=dev)
    return 0


if __name__ == "__main__":
    rc = main()
    sys.stdout.flush()
    # abandoned lane workers or attach threads must not hold the exit
    os._exit(rc)
