"""rewrite_tag launched from the staged plane (BASELINE config 3), and
the program input it shares with filter_grep: the distinct staged planes
``[K, B, L]`` with a static rule→plane index, and the first-match
reduction on the device.

On a CPU backend ``process_batch`` takes the native twin, so the
platform gate is forced open the way
``test_rewrite_tag.py::test_device_path_equivalence_config3`` does it;
the routing is then held to the per-record host chain (``tpu.enable
off``) and to the benchmark's plain reference (Python ``re`` over the
rules of the pipeline file) on a seeded 8,192-line cut of the
benchmark's own corpus.
"""

import os
import socket
import sys
import time

import numpy as np
import pytest

jax = pytest.importorskip("jax")

import fluentbit_tpu as flb  # noqa: E402
from fluentbit_tpu import failpoints  # noqa: E402
from fluentbit_tpu.codec.events import decode_events, encode_event  # noqa: E402
from fluentbit_tpu.codec.msgpack import Unpacker, packb  # noqa: E402
from fluentbit_tpu.core.engine import Engine  # noqa: E402
from fluentbit_tpu.ops import device, fault  # noqa: E402
from fluentbit_tpu.ops.grep import GrepProgram, first_match_of  # noqa: E402
from fluentbit_tpu.regex.dfa import compile_dfa  # noqa: E402

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
BENCH = os.path.join(REPO, "benchmark")
PIPELINE = os.path.join(BENCH, "configs", "rewrite-syslog.conf")
N_LINES, FRAME = 8192, 4096
TAG = "bench.syslog"
#: a cut that holds everything the whole corpus does: 512-bucket lines
#: and overflow rows in both frames
CORPUS_PARAMS = {"bucket512_every": 1000, "overflow_every": 2500}


def bench_module(folder: str, stem: str):
    if BENCH not in sys.path:
        sys.path.insert(0, BENCH)
    from lookup import load_py

    return load_py(folder, stem)


def wait_for(cond, timeout=60.0, interval=0.01):
    deadline = time.time() + timeout
    while time.time() < deadline:
        v = cond()
        if v:
            return v
        time.sleep(interval)
    raise TimeoutError("condition not met")


@pytest.fixture(scope="module")
def gate_open():
    """``device.platform()`` says "tpu" for this module: the selection
    points take the device path on the CPU backend."""
    assert device.wait(120)
    saved = device.platform
    device.platform = lambda: "tpu"
    yield
    device.platform = saved


@pytest.fixture(scope="module")
def corpus():
    records, labels = bench_module("corpora", "syslog_lines").make(
        N_LINES, 20260927, CORPUS_PARAMS)
    ref = bench_module("reference", "rewrite-syslog")
    rules = ref.rules_of(PIPELINE)
    want = [ref.first_match(rules, r) for r in records]
    return {"records": records, "labels": labels, "rules": rules,
            "tags": [t for _f, _p, t, _k in rules], "want": want}


def rule_lines() -> list:
    """The eight ``Rule`` values of the benchmark's pipeline file."""
    with open(PIPELINE) as f:
        return [ln.split(None, 1)[1].strip() for ln in f
                if ln.strip().lower().startswith("rule ")]


def route_through_forward(records, extra=()) -> dict:
    """forward → engine raw chain → rewrite_tag → emitter → one ``lib``
    output a tag: two Forward frames over a socket, each acked.
    → ``{tag: [parts]}``, the plugin's timings and the lane's stats."""
    ctx = flb.create(flush="50ms", grace="1")
    ctx.input("forward", listen="127.0.0.1", port="0")
    props = {"match": TAG, "tpu_max_record_len": "512"}
    props.update(dict(extra))
    f = ctx.filter("rewrite_tag", **props)
    for rule in rule_lines():
        ctx.set(f, rule=rule)
    got = {}
    ctx.output("lib", match="*", callback=lambda d, t: got.setdefault(
        t, []).append(bytes(d)))
    engine = ctx.engine
    before = fault.lane("grep").stats()
    ctx.start()
    try:
        port = wait_for(lambda: engine.inputs[0].plugin.bound_port)
        with socket.create_connection(("127.0.0.1", port)) as s:
            s.settimeout(120)
            for fi in range(0, len(records), FRAME):
                chunk = "frame-%04d" % fi
                entries = [[1700000000 + fi + i, r] for i, r in
                           enumerate(records[fi:fi + FRAME])]
                s.sendall(packb([TAG, entries, {"chunk": chunk}]))
                u, acked = Unpacker(), False
                while not acked:
                    u.feed(s.recv(4096))
                    for msg in u:
                        assert msg == {"ack": chunk}
                        acked = True
        ctx.flush_now()
        wait_for(lambda: sum(len(decode_events(p)) for ps in
                             list(got.values()) for p in list(ps))
                 == len(records))
    finally:
        ctx.stop()
    plugin = engine.filters[0].plugin
    after = fault.lane("grep").stats()
    return {"got": got,
            "timings": {k: plugin.raw_timings[k]
                        for k in plugin.raw_timings},
            "program": plugin._program,
            "declines": sum(v for _l, v in
                            engine.m_filter_batch_decline.samples()),
            "lane": {k: after[k] - before[k]
                     for k in ("launches", "ok", "fallback_segments")}}


@pytest.fixture(scope="module")
def routed(gate_open, corpus):
    fault.reset()
    failpoints.reset()
    dev = route_through_forward(corpus["records"])
    host = route_through_forward(corpus["records"],
                                 extra={"tpu.enable": "off"})
    return {"device": dev, "host": host}


def bodies_by_tag(got: dict) -> dict:
    return {t: [ev.body for p in parts for ev in decode_events(p)]
            for t, parts in got.items()}


# ---------------------------------------- the raw path, three ways


def test_device_verdict_served_every_record(routed):
    dev = routed["device"]
    tm = dev["timings"]
    assert tm["device_records"] == tm["records"] == N_LINES
    assert dev["lane"] == {"launches": N_LINES // FRAME,
                           "ok": N_LINES // FRAME, "fallback_segments": 0}
    assert dev["declines"] == 0
    assert routed["host"]["timings"]["device_records"] == 0
    assert routed["host"]["program"] is None


def test_one_plane_staged_for_the_eight_rules(routed, corpus):
    """Eight rules on ``log`` cost one plane of host→device bytes a
    line (L + 4), not eight — and the few 512-bucket lines of a frame
    do not set its width: they go as a 256-row group of their own at
    L=512 (with their row indices), the frame at L=256."""
    prog, tm = routed["device"]["program"], routed["device"]["timings"]
    assert prog.n_planes == 1 and prog.plane_of == (0,) * 8
    assert sorted(c.k for c in prog._children) == [4, 5, 6]
    frames = N_LINES // FRAME
    long_lines = sum(256 < len(r["log"]) <= 512 for r in corpus["records"])
    assert 0 < long_lines <= 256
    assert tm["split_launches"] == frames
    assert tm["long_rows"] == long_lines
    assert tm["h2d_bytes"] == N_LINES * (256 + 4) \
        + frames * 256 * (512 + 4 + 4)
    assert tm["scan_elements"] == frames * (
        prog.scan_elements(FRAME, 256) + prog.scan_elements(256, 512))


def test_overflow_rows_counted(routed, corpus):
    long_lines = sum(1 for lb in corpus["labels"] if lb & 2)
    assert long_lines == N_LINES // CORPUS_PARAMS["overflow_every"] > 0
    assert routed["device"]["timings"]["overflow_rows"] == long_lines


@pytest.mark.parametrize("side", ["device", "host"])
def test_tags_equal_the_plain_reference(routed, corpus, side):
    got = bodies_by_tag(routed[side]["got"])
    want = {}
    for rec, r in zip(corpus["records"], corpus["want"]):
        want.setdefault(TAG if r < 0 else corpus["tags"][r],
                        []).append(rec)
    assert set(got) == set(want) == set(corpus["tags"]) | {TAG}
    for tag in want:
        # per tag: exactly its records, in frame order
        assert got[tag] == want[tag], tag


def test_bytes_equal_the_host_chain(routed):
    """Device verdict vs ``tpu.enable off``: the same bytes under every
    tag, the survivors under the original tag included."""
    dev, host = routed["device"]["got"], routed["host"]["got"]
    assert set(dev) == set(host)
    for tag in host:
        assert b"".join(dev[tag]) == b"".join(host[tag]), tag


def test_every_record_leaves_under_exactly_one_tag(routed, corpus):
    got = bodies_by_tag(routed["device"]["got"])
    assert sum(len(v) for v in got.values()) == N_LINES
    keep = [rec for rec, lb in zip(corpus["records"], corpus["labels"])
            if lb & 1]
    assert got[TAG] == keep
    emits = routed["device"]["timings"]
    assert emits["emits"] == 8 * (N_LINES // FRAME)
    assert emits["emit_backpressure"] == 0 and emits["emit_s"] > 0


def test_construction_labels_equal_the_plain_reference(corpus):
    maker = bench_module("corpora", "syslog_lines")
    assert [maker.winner(lb) for lb in corpus["labels"]] == corpus["want"]
    counts = [corpus["want"].count(r) for r in range(-1, 8)]
    sixteenth = N_LINES // 16
    assert counts == [5 * sixteenth, 2 * sixteenth, 2 * sixteenth,
                      sixteenth, 2 * sixteenth, sixteenth, sixteenth,
                      sixteenth, sixteenth]


# ------------------------- first match, overflow, the lane's fallback


def raw_engine(extra=()):
    e = Engine()
    f = e.filter("rewrite_tag")
    for rule in rule_lines():
        f.set("rule", rule)
    f.set("match", "t")
    for k, v in dict(extra).items():
        f.set(k, v)
    ins = e.input("dummy")
    for x in e.inputs + e.filters:
        x.configure()
        x.plugin.init(x, e)
    return e, ins


def routing_of(e, ins, lines) -> dict:
    """One raw append → ``{tag: [log values]}`` from the pools."""
    chunk = b"".join(encode_event({"log": ln}, float(i))
                     for i, ln in enumerate(lines))
    e.input_log_append(ins, "t", chunk)
    out = {}
    for inst in e.inputs:
        for ch in inst.pool.drain():
            out.setdefault(ch.tag, []).extend(
                ev.body["log"] for ev in decode_events(bytes(ch.buf)))
    return out


MULTI = [
    ("kernel: Out of memory: OOM killer", "sys.kernel"),       # 2 and 8
    ("nginx[7]: ERROR upstream refused", "app.error"),         # 4 and 6
    ("app WARN: nginx reload after OOM", "app.warn"),          # 5, 6, 8
    ("sshd[1]: ERROR cron[22] exited", "sec.ssh"),             # 1, 4, 7
    ("cron[5]: OOM in job", "sys.cron"),                       # 7 and 8
    ("systemd[1]: kernel: WARN", "sys.kernel"),                # 2, 3, 5
    ("nothing of the kind", "t"),
]


@pytest.mark.parametrize("line,tag", MULTI)
def test_first_match_wins_on_lines_that_match_several(gate_open, line,
                                                      tag):
    e, ins = raw_engine()
    filler = ["plain filler %d" % i for i in range(70)]
    got = routing_of(e, ins, filler + [line])
    assert e.filters[0].plugin.raw_timings["device_records"] == 71
    assert line in got[tag]
    assert sum(len(v) for v in got.values()) == 71


def mixed_lines() -> list:
    lines = []
    for i in range(96):
        text, _tag = MULTI[i % len(MULTI)]
        # every eleventh line overflows tpu_max_record_len
        lines.append(text + (" pad=" + "z" * 600 if i % 11 == 0 else "")
                     + " #%d" % i)
    return lines


def test_overflow_rows_route_like_the_host_chain(gate_open):
    lines = mixed_lines()
    e, ins = raw_engine()
    got = routing_of(e, ins, lines)
    tm = e.filters[0].plugin.raw_timings
    assert tm["overflow_rows"] == len(range(0, 96, 11))
    assert tm["device_records"] == 96
    e2, ins2 = raw_engine({"tpu.enable": "off"})
    assert got == routing_of(e2, ins2, lines)


def test_injected_launch_failure_falls_back_bit_exact(gate_open):
    lines = mixed_lines()
    e2, ins2 = raw_engine({"tpu.enable": "off"})
    want = routing_of(e2, ins2, lines)
    fault.reset()
    failpoints.reset()
    e, ins = raw_engine()
    failpoints.enable("device.dispatch", "1*return(injected)")
    try:
        got = routing_of(e, ins, lines)
    finally:
        failpoints.reset()
    st = fault.lane("grep").stats()
    fault.reset()
    assert st["launches"] == 1 and st["fallback_segments"] == 1
    assert got == want


# ---------------------- the program input: planes and a static index


PATTERNS = {
    # one child: the same stride for every rule
    "one_child": ["GET", "POST", "HEAD", "WARN"],
    # three children (k=6, 5, 4): config 3's eight rules
    "three_children": ["sshd", "kernel:", r"systemd\[1\]", "ERROR",
                       "WARN", "nginx", r"cron\[\d+\]", ".*OOM.*"],
}
PLANE_OF = {
    "one_child": {"one_key": (0, 0, 0, 0), "mixed": (0, 1, 0, 1),
                  "reversed": (1, 0, 1, 0)},
    "three_children": {"one_key": (0,) * 8,
                       "mixed": (0, 1, 2, 0, 1, 2, 0, 1),
                       "reversed": (2, 2, 1, 1, 0, 0, 2, 1)},
}


def staged_planes(K: int, B: int = 96, L: int = 64, seed: int = 3):
    rng = np.random.default_rng(seed)
    words = [b"sshd[3]: ok", b"kernel: OOM", b"GET /a 503", b"POST 200",
             b"nginx ERROR", b"cron[41]: x", b"systemd[1]: WARN",
             b"HEAD /", b"plain"]
    planes = np.zeros((K, B, L), dtype=np.uint8)
    lengths = np.full((K, B), -1, dtype=np.int32)
    for k in range(K):
        for b in range(B):
            if rng.random() < 0.08:
                continue  # a missing value
            v = b" ".join(words[int(i)] for i in rng.integers(
                0, len(words), size=int(rng.integers(1, 4))))[:L]
            planes[k, b, :len(v)] = np.frombuffer(v, dtype=np.uint8)
            lengths[k, b] = len(v)
    return planes, lengths


@pytest.mark.parametrize("layout", ["one_key", "mixed", "reversed"])
@pytest.mark.parametrize("split", ["one_child", "three_children"])
def test_planes_and_index_equal_one_plane_a_rule(split, layout):
    """``[K, B, L]`` + index gives bit-equal masks to the old
    ``[R, B, L]`` call (each rule handed a copy of its plane)."""
    dfas = [compile_dfa(p) for p in PATTERNS[split]]
    plane_of = PLANE_OF[split][layout]
    planes, lengths = staged_planes(max(plane_of) + 1)
    shared = GrepProgram(dfas, 64, plane_of=plane_of)
    copied = GrepProgram(dfas, 64)
    assert (shared._children is not None) == (split == "three_children")
    idx = list(plane_of)
    want = copied.match(planes[idx], lengths[idx])
    got = shared.match(planes, lengths)
    assert got.dtype == np.bool_ and got.shape == want.shape
    assert np.array_equal(got, want) and want.any() and not want.all()


@pytest.mark.mesh
@pytest.mark.parametrize("split", ["one_child", "three_children"])
def test_planes_and_index_on_the_mesh(split):
    if len(jax.devices()) < 2:
        pytest.skip("need a multi-device mesh")
    from fluentbit_tpu.ops.mesh import build_mesh

    dfas = [compile_dfa(p) for p in PATTERNS[split]]
    plane_of = PLANE_OF[split]["mixed"]
    planes, lengths = staged_planes(max(plane_of) + 1, B=90)
    shared = GrepProgram(dfas, 64, plane_of=plane_of)
    want = shared.match(planes, lengths)
    mesh = build_mesh(4)
    mask, counts, _bp = shared.match_mesh(mesh, planes, lengths)
    assert np.array_equal(mask, want)
    assert np.array_equal(counts, want.sum(axis=1))
    first, _c, B, _bp = shared.dispatch_mesh(
        mesh, planes, lengths, with_counts=False, first_match=True)
    assert np.array_equal(np.asarray(first)[:B],
                          shared.match(planes, lengths, first_match=True))


@pytest.mark.parametrize("split", ["one_child", "three_children"])
def test_first_match_vector_is_argmax_of_the_mask(split):
    """The first-match vector equals ``argmax`` of the mask in the
    caller's rule order (children merged back first), -1 where no rule
    accepts."""
    dfas = [compile_dfa(p) for p in PATTERNS[split]]
    plane_of = PLANE_OF[split]["mixed"]
    planes, lengths = staged_planes(max(plane_of) + 1, seed=11)
    prog = GrepProgram(dfas, 64, plane_of=plane_of)
    mask = prog.match(planes, lengths)
    first = prog.match(planes, lengths, first_match=True)
    assert first.dtype == np.int32 and first.shape == (mask.shape[1],)
    want = np.where(mask.any(axis=0), mask.argmax(axis=0), -1)
    assert np.array_equal(first, want)
    assert (first == -1).any() and len(set(first.tolist())) > 2
    assert np.array_equal(
        np.asarray(first_match_of(mask.astype(np.int32))), want)


def test_plane_index_is_checked():
    dfas = [compile_dfa("a"), compile_dfa("b")]
    with pytest.raises(ValueError):
        GrepProgram(dfas, 64, plane_of=(0,))
    with pytest.raises(ValueError):
        GrepProgram(dfas, 64, plane_of=(0, -1))
    assert GrepProgram(dfas, 64).plane_of == (0, 1)


def test_grep_filter_stages_one_plane_for_two_rules_on_one_key():
    """filter_grep on the same program input: K=1 for its two rules on
    ``log``, K=2 when the rules read two keys."""
    from fluentbit_tpu.plugins.filter_grep import plane_index

    def planes_of(*rules):
        e = Engine()
        f = e.filter("grep")
        for kind, value in rules:
            f.set(kind, value)
        for x in e.filters:
            x.configure()
            x.plugin.init(x, e)
        p = e.filters[0].plugin
        return p._program.n_planes, p._program.plane_of, \
            plane_index(p.rules)[1]

    assert planes_of(("exclude", r"log curl/8\.5"),
                     ("regex", "log GET")) == (1, (0, 0), (0, 0))
    assert planes_of(("exclude", r"agent curl/8\.5"),
                     ("regex", "log GET"),
                     ("regex", "$agent Mozilla")) \
        == (2, (0, 1, 0), (0, 1, 0))
