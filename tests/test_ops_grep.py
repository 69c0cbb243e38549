"""Device DFA kernel tests — bit-exactness vs the CPU matcher.

Runs on the virtual 8-device CPU backend (conftest). The contract under
test is the north star's: device keep/exclude decisions must be
bit-exact vs the CPU chain."""

import random

import numpy as np
import pytest

from fluentbit_tpu.ops.batch import assemble, bucket_size
from fluentbit_tpu.ops.grep import (GrepProgram, choose_k, class_runs,
                                    compose_table, program_for)
from fluentbit_tpu.regex.dfa import DFA, compile_dfa

APACHE2 = (
    r'^(?<host>[^ ]*) [^ ]* (?<user>[^ ]*) \[(?<time>[^\]]*)\] '
    r'"(?<method>\S+)(?: +(?<path>[^ ]*) +\S*)?" '
    r'(?<code>[^ ]*) (?<size>[^ ]*)'
    r'(?: "(?<referer>[^\"]*)" "(?<agent>.*)")?$'
)


def make_lines(n, rng):
    lines = []
    for i in range(n):
        kind = rng.randrange(4)
        if kind == 0:
            lines.append(
                f'10.0.{rng.randrange(256)}.{rng.randrange(256)} - user{i} '
                f'[10/Oct/2024:13:55:36 -0700] "GET /p{i} HTTP/1.1" '
                f'{rng.choice([200, 404, 500])} {rng.randrange(10000)}'.encode()
            )
        elif kind == 1:
            lines.append(b"random junk line " + str(i).encode())
        elif kind == 2:
            lines.append(b"")
        else:
            lines.append(
                f'host{i} - u [t] "POST /x Z" 201 7 "r" "agent {i}"'.encode()
            )
    return lines


def test_compose_table_equivalence():
    dfa = compile_dfa(r"ab+c")
    t2 = compose_table(dfa.trans, 2)
    S, C = dfa.trans.shape
    for s in (0, 1, dfa.start):
        for c1 in range(C):
            for c2 in range(C):
                assert t2[s, c1 * C + c2] == dfa.trans[dfa.trans[s, c1], c2]


def test_choose_k_budget():
    assert choose_k(10, 4) >= 2
    assert choose_k(100000, 200) == 1


@pytest.mark.parametrize("pattern", ["abc", r"^\d+ GET", APACHE2, r"a*b|c$"])
def test_kernel_vs_cpu(pattern):
    rng = random.Random(7)
    dfa = compile_dfa(pattern)
    lines = make_lines(64, rng)
    b = assemble(lines, max_len=256)
    prog = GrepProgram([dfa], max_len=256)
    got = prog.match(b.batch[None], b.lengths[None])[0]
    expect = np.array([dfa.match_bytes(ln) for ln in lines])
    assert (got == expect).all(), pattern


def test_kernel_multi_rule_different_shapes():
    rng = random.Random(9)
    patterns = ["GET", r"^\d", APACHE2]
    dfas = [compile_dfa(p) for p in patterns]
    lines = make_lines(32, rng)
    b = assemble(lines, max_len=128)
    # rule 1 uses a different field: vary the batch per rule
    other = [ln[::-1] for ln in lines]
    b2 = assemble(other, max_len=128)
    batch = np.stack([b.batch, b2.batch, b.batch])
    lengths = np.stack([b.lengths, b2.lengths, b.lengths])
    prog = GrepProgram(dfas, max_len=128)
    got = prog.match(batch, lengths)
    assert (got[0] == np.array([dfas[0].match_bytes(ln) for ln in lines])).all()
    assert (got[1] == np.array([dfas[1].match_bytes(ln) for ln in other])).all()
    assert (got[2] == np.array([dfas[2].match_bytes(ln) for ln in lines])).all()


def test_invalid_rows_never_match():
    dfa = compile_dfa(r"x*")  # matches everything incl. empty
    b = assemble([b"abc", None, b"x" * 999], max_len=16)
    assert b.overflow == [2]
    prog = GrepProgram([dfa], max_len=16)
    got = prog.match(b.batch[None], b.lengths[None])[0]
    assert got[0]  # valid row matches
    assert not got[1]  # missing field
    assert not got[2]  # overflow → resolved on CPU by caller


def test_padded_batch_rows_inert():
    dfa = compile_dfa("a")
    b = assemble([b"a", b"b"], max_len=8, pad_batch_to=bucket_size(2))
    assert b.batch.shape[0] == 256
    prog = GrepProgram([dfa], max_len=8)
    got = prog.match(b.batch[None], b.lengths[None])[0]
    assert got[0] and not got[1]
    assert not got[2:].any()


def test_apache2_bulk_bit_exact():
    rng = random.Random(1234)
    dfa = compile_dfa(APACHE2)
    lines = make_lines(512, rng)
    b = assemble(lines, max_len=512)
    prog = program_for([APACHE2], max_len=512)
    got = prog.match(b.batch[None], b.lengths[None])[0]
    expect = dfa.match_batch_np(
        b.batch, np.where(b.lengths < 0, 0, b.lengths)
    ) & (b.lengths >= 0)
    assert (got == expect).all()
    scalar = np.array([dfa.match_bytes(ln) for ln in lines])
    assert (got == scalar).all()


def test_assoc_kernel_bit_exact_vs_scan():
    """The parallel-in-time (function-composition) kernel must be
    bit-identical to the sequential scan kernel on every input class:
    matches, misses, empty, padding-only, overflow rows."""
    rng = random.Random(4242)
    patterns = ["GET", r"^\d+$", APACHE2]
    dfas = [compile_dfa(p) for p in patterns]
    lines = make_lines(97, rng) + [b"", b"x" * 999, None]
    b = assemble(lines, max_len=192)
    batch = np.stack([b.batch] * 3)
    lengths = np.stack([b.lengths] * 3)
    scan_prog = GrepProgram(dfas, max_len=192, kernel="scan")
    for seg in (2, 8, 32, 1024):  # incl. seg > Lk (single segment)
        assoc_prog = GrepProgram(dfas, max_len=192, kernel="assoc",
                                 segment=seg)
        got_scan = scan_prog.match(batch, lengths)
        got_assoc = assoc_prog.match(batch, lengths)
        assert (got_scan == got_assoc).all(), f"segment={seg}"
    # and vs the ground-truth CPU matcher on the valid rows
    expect = np.array([dfas[0].match_bytes(ln)
                       if isinstance(ln, bytes) and len(ln) <= 192
                       else False for ln in lines])
    assert (got_assoc[0] == expect).all()


def _conf_patterns(path):
    """The rule regexes of a pipeline file's filter section, in file
    order: filter_grep's ``Regex``/``Exclude <key> <regex>`` and
    rewrite_tag's ``Rule <key> <regex> <tag> <keep>``."""
    from fluentbit_tpu.config_format import load_config_file

    out = []
    for section in load_config_file(path).sections:
        if section.name != "filter":
            continue
        for key, value in section.properties:
            if key.lower() in ("regex", "exclude"):
                out.append(value.split(None, 1)[1])
            elif key.lower() == "rule":
                out.append(value.split()[1])
    return out


#: the pipeline files of the benchmark's two verdict configurations and
#: the corpus module each one's cell sends
KERNEL_RULE_CONFIGS = {
    "grep-apache2": ("benchmark/configs/grep-apache2.conf", "grep_lines",
                     [(690, 3), (10, 5)]),
    "rewrite-syslog": ("conf/baseline3-rewrite.conf", "syslog_lines",
                       [(12, 4), (9, 5), (7, 6)]),
}


@pytest.mark.parametrize("config", sorted(KERNEL_RULE_CONFIGS))
def test_every_bench_child_resolves_to_scan_and_assoc_agrees(config):
    """The kernel rule as the chip measured it (PERF.md, PR 33): every
    child of the grep and rewrite configurations resolves to scan, on
    an accelerator as on the CPU, whatever its state count; the assoc
    kernel, reachable by the constructor argument alone, gives the same
    ``[R, B]`` verdicts on 256 lines of the cell's own corpus (512-
    bucket lines and overflow rows among them)."""
    import os
    import sys

    from fluentbit_tpu.ops import device

    repo = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    conf, corpus, shapes = KERNEL_RULE_CONFIGS[config]
    dfas = [compile_dfa(p) for p in _conf_patterns(os.path.join(repo, conf))]
    plane_of = (0,) * len(dfas)  # every rule reads ``log``
    children = GrepProgram(dfas, 512, plane_of=plane_of)._children
    assert [(c.max_states, c.k) for c in children] == shapes
    was = device._platform
    try:
        for plat in ("tpu", "cpu"):
            device._platform = plat
            assert [c._resolve_kernel() for c in children] \
                == ["scan"] * len(children), plat
    finally:
        device._platform = was

    bench = os.path.join(repo, "benchmark")
    if bench not in sys.path:
        sys.path.insert(0, bench)
    from lookup import load_py

    records, _labels = load_py("corpora", corpus).make(
        256, 20261002, {"bucket512_every": 40, "overflow_every": 100})
    lines = [r["log"].encode() for r in records]
    b = assemble(lines, max_len=512)
    assert (b.lengths > 256).any() and (b.lengths < 0).any()
    got = {}
    for kern in ("scan", "assoc"):
        prog = GrepProgram(dfas, 512, kernel=kern, plane_of=plane_of)
        got[kern] = prog.match(b.batch[None], b.lengths[None])
        assert {c.kernel_resolved for c in prog._children} == {kern}
    assert got["scan"].shape == (len(dfas), 256)
    assert (got["scan"] == got["assoc"]).all()
    assert got["scan"].any(axis=1).all()  # every rule has its lines
    want = np.array([[len(ln) <= 512 and d.match_bytes(ln) for ln in lines]
                     for d in dfas])
    assert (got["scan"] == want).all()


def test_assoc_kernel_sharded_matches_single_device():
    import jax
    from jax.sharding import Mesh

    rng = random.Random(77)
    dfas = [compile_dfa("GET"), compile_dfa(APACHE2)]
    lines = make_lines(41, rng)
    b = assemble(lines, max_len=128)
    batch = np.stack([b.batch] * 2)
    lengths = np.stack([b.lengths] * 2)
    prog = GrepProgram(dfas, max_len=128, kernel="assoc", segment=8)
    devs = jax.devices()
    mesh = Mesh(np.asarray(devs[:8]), ("batch",))
    mask, counts, _ = prog.match_mesh(mesh, batch, lengths)
    single = prog.match(batch, lengths)
    assert (mask == single).all()
    assert (counts == single.sum(axis=1)).all()


# ---------------------------------------------------------------------
# byte classing without a gather (class_runs → _byte_classes)
# ---------------------------------------------------------------------

#: the ten DFAs of the benchmark's configurations: grep-apache2's two
#: rules, rewrite-syslog's eight (sketch-firehose builds none)
BENCH_PATTERNS = [r"curl/8\.5", APACHE2, "sshd", "kernel:",
                  r"systemd\[1\]", "ERROR", "WARN", "nginx",
                  r"cron\[\d+\]", ".*OOM.*"]


def _made_up_dfa(class_map256):
    """A hand-built table over a made-up byte→class map (every
    transition DEAD: only the classing is under test). EOL shares
    class 0 — a uint8 map holds 256 ids."""
    cm = np.concatenate([np.asarray(class_map256), [0]]).astype(np.uint8)
    n_classes = int(cm.max()) + 1
    return DFA(trans=np.zeros((3, n_classes), np.int32), class_map=cm,
               start=2, n_states=3, n_classes=n_classes, pattern="made-up")


_B = np.arange(256)
MADE_UP_MAPS = {
    "one-class": [np.zeros(256, int)],
    "change-at-byte-1": [(_B >= 1).astype(int)],
    "change-at-byte-255": [(_B >= 255).astype(int)],
    "every-byte-its-own-class": [_B],
    # two rules of unequal breakpoint counts in one program, same (S, C)
    # so they share a stride: the shorter rule's runs are padding
    "unequal-runs-padded": [(_B // 8) % 2, (_B >= 128).astype(int)],
    "classes-fall-and-rise": [np.abs(_B // 32 - 3)],
}


def _check_classing(dfas):
    """The compare-add classing alone: for all 256 byte values, every
    rule's class is ``class_map[byte]``, and the breakpoint tables are
    the map's runs padded to the program's widest rule."""
    import jax.numpy as jnp

    prog = GrepProgram(dfas, max_len=256)
    assert prog._children is None
    t = prog._np
    R = len(dfas)
    widest = 1
    for r, d in enumerate(dfas):
        base, start, delta = class_runs(d.class_map)
        n = int(np.count_nonzero(np.diff(d.class_map[:256].astype(int))))
        assert start.size == n == prog.decision()["rules"][r]["class_runs"]
        assert base == d.class_map[0]
        assert (t["run_start"][r, :n] == start).all()
        assert (t["run_delta"][r, :n] == delta).all()
        assert (t["run_start"][r, n:] == 256).all()  # no uint8 reaches it
        assert (t["run_delta"][r, n:] == 0).all()
        widest = max(widest, n)
    assert t["run_start"].shape == t["run_delta"].shape == (R, widest)
    batch = np.broadcast_to(_B.astype(np.uint8), (R, 1, 256))
    got = np.asarray(GrepProgram._byte_classes(
        {k: jnp.asarray(v) for k, v in t.items()}, jnp.asarray(batch)))
    assert got.dtype == np.int32 and got.shape == (R, 1, 256)
    for r, d in enumerate(dfas):
        assert (got[r, 0] == d.class_map[:256]).all()


@pytest.mark.parametrize("pattern", BENCH_PATTERNS)
def test_byte_classes_equal_class_map_bench_rules(pattern):
    _check_classing([compile_dfa(pattern)])


@pytest.mark.parametrize("name", sorted(MADE_UP_MAPS))
def test_byte_classes_equal_class_map_made_up(name):
    _check_classing([_made_up_dfa(m) for m in MADE_UP_MAPS[name]])


#: one pattern for each stride choose_k gives the benchmark's rules
K_PATTERNS = {3: APACHE2, 4: r"systemd\[1\]", 5: r"curl/8\.5", 6: "sshd"}
_HIT = {3: b'h - u [t] "GET /p Z" 200 7', 4: b"systemd[1]", 5: b"curl/8.5",
        6: b"sshd"}


def _lines_of_lengths(k, L, rng):
    """Lines of length 0, 1, k-1, k, odd, L and longer than L (an
    overflow row: length -1), each as a miss and — where the rule's
    shortest hit fits — as a hit at the start, at the end and at an odd
    offset (across a super-symbol boundary)."""
    hit = _HIT[k]
    out = [b"x" * (L + 5), None]
    for n in sorted({0, 1, k - 1, k, k + 1, 2 * k - 1, 31, 77, L - 1, L}):
        out.append(bytes(rng.choice(b"abc xyz[]/.0123") for _ in range(n)))
        room = n - len(hit)
        for lead in {0, room, room // 2 | 1} if room >= 0 else ():
            if lead <= room:
                out.append(b"q" * lead + hit + b"q" * (room - lead))
    return out


@pytest.mark.parametrize("kernel", ["scan", "assoc"])
@pytest.mark.parametrize("k", sorted(K_PATTERNS))
def test_program_verdicts_vs_native_and_re(k, kernel):
    """Whole-program verdicts through the compare-add prepass, both
    kernels, strides 3-6, the boundary lengths — against the host twin
    (native.grep_match over the same records) and Python ``re``."""
    import re

    from fluentbit_tpu import native
    from fluentbit_tpu.codec.events import encode_event
    from fluentbit_tpu.regex import to_python_regex

    L = 96
    pattern = K_PATTERNS[k]
    dfa = compile_dfa(pattern)
    prog = GrepProgram([dfa], max_len=L, kernel=kernel, segment=4)
    assert prog.k == k
    lines = _lines_of_lengths(k, L, random.Random(k))
    b = assemble(lines, max_len=L)
    got = prog.match(b.batch[None], b.lengths[None])[0]
    assert prog.kernel_resolved == kernel
    valid = [i for i, ln in enumerate(lines)
             if ln is not None and len(ln) <= L]
    assert not got[[i for i in range(len(lines)) if i not in valid]].any()
    rx = re.compile(to_python_regex(pattern), re.MULTILINE)
    want_re = np.array([rx.search(lines[i].decode()) is not None
                        for i in valid])
    assert (got[valid] == want_re).all()
    assert want_re.any() and not want_re.all()
    if native.available():
        chunk = b"".join(encode_event({"log": lines[i].decode()}, float(i))
                         for i in valid)
        mask, _, n = native.grep_match(
            chunk, native.GrepTables([(b"log", dfa)]))
        assert n == len(valid)
        assert (got[valid] == mask[0]).all()


@pytest.mark.parametrize("kernel", ["scan", "assoc"])
@pytest.mark.parametrize("k", sorted(K_PATTERNS))
def test_no_gather_in_symbols_scope(k, kernel):
    """The lowered program classifies without a gather: no ``gather``
    under the ``grep.symbols`` named scope (the scan and assoc scopes
    keep theirs — the transition tables)."""
    import re

    import jax

    prog = GrepProgram([compile_dfa(K_PATTERNS[k])], max_len=64,
                       kernel=kernel)
    prog._ensure_materialized()
    planes = np.zeros((1, 8, 64), np.uint8)
    lengths = np.zeros((1, 8), np.int32)
    hlo = prog._jit.lower(planes, lengths).as_text(debug_info=True)
    assert "/grep.symbols/" in hlo  # the scope names its operations
    assert not re.search(r'grep\.symbols/[^"]*gather', hlo)
    assert "stablehlo.gather" in hlo  # the transition tables' own
    # and the prepass lowered alone, whatever its operations are named
    alone = jax.jit(lambda p, n: prog._super_symbols(prog._tbl, p, n))
    assert "gather" not in alone.lower(planes, lengths).as_text()
