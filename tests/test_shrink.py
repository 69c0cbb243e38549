"""fbtpu-shrink property tests — the compile-path reduction contract.

Two layers of contract:

- **Bit-exact minimization**: for randomized regexes, the minimized DFA
  (Hopcroft + dead-state pruning + byte-class remerge) accepts exactly
  the same byte strings as the unminimized subset-construction machine
  — including non-ASCII bytes, the empty string, and max_len
  boundaries — and the output is MINIMAL (no two distinct states
  equivalent; the Moore fixpoint is the independent oracle).
- **The unlock is observable**: GrepProgram exposes the S/C/k/kernel
  decision, the apache2 parser DFA demonstrably shrinks, and the
  ``fluentbit_grep_shrink_*`` counters move.
"""

import random

import numpy as np

from fluentbit_tpu.ops.grep import GrepProgram, choose_k, program_for
from fluentbit_tpu.regex.dfa import _moore_minimize, compile_dfa
from fluentbit_tpu.regex.parser import UnsupportedRegex

APACHE2 = (
    r'^(?<host>[^ ]*) [^ ]* (?<user>[^ ]*) \[(?<time>[^\]]*)\] '
    r'"(?<method>\S+)(?: +(?<path>[^ ]*) +\S*)?" '
    r'(?<code>[^ ]*) (?<size>[^ ]*)'
    r'(?: "(?<referer>[^\"]*)" "(?<agent>.*)")?$'
)


def _random_pattern(rng: random.Random) -> str:
    """A small DFA-expressible grammar: literals, classes, counted
    reps, alternation, anchors."""
    atoms = ["a", "b", "x", "0", " ", r"\d", r"\w", "[a-f]", "[^ ]",
             "[0-9a-f]", "."]

    def piece():
        a = rng.choice(atoms)
        r = rng.random()
        if r < 0.2:
            return a + "*"
        if r < 0.3:
            return a + "+"
        if r < 0.4:
            return a + "?"
        if r < 0.5:
            return a + "{%d,%d}" % (rng.randrange(1, 3),
                                    rng.randrange(3, 6))
        return a

    body = "".join(piece() for _ in range(rng.randrange(1, 6)))
    if rng.random() < 0.3:
        body = body + "|" + "".join(piece()
                                    for _ in range(rng.randrange(1, 4)))
    if rng.random() < 0.25:
        body = "^" + body
    if rng.random() < 0.25:
        body = body + "$"
    return body


def _random_inputs(rng: random.Random):
    """Adversarial byte strings: empty, non-ASCII, long runs, near-miss
    structured lines."""
    out = [b"", b"\x00", b"\xff\xfe bytes \x80", b"a" * 64,
           b"ab 01 xf", b"0123456789abcdef"]
    for _ in range(40):
        n = rng.randrange(0, 24)
        out.append(bytes(rng.randrange(256) for _ in range(n)))
    for _ in range(20):
        out.append(bytes(rng.choice(b"abx0 \n") for _ in range(
            rng.randrange(0, 16))))
    return out


def test_minimized_equals_unminimized_randomized():
    rng = random.Random(20260804)
    checked = 0
    for _ in range(60):
        pat = _random_pattern(rng)
        try:
            d_min = compile_dfa(pat)
            d_raw = compile_dfa(pat, minimize=False)
        except UnsupportedRegex:
            continue
        checked += 1
        assert d_min.n_states <= d_raw.n_states, pat
        assert d_min.n_classes <= d_raw.n_classes, pat
        for s in _random_inputs(rng):
            assert d_min.match_bytes(s) == d_raw.match_bytes(s), \
                (pat, s)
    assert checked >= 30  # the grammar must actually exercise the pass


def test_minimized_batch_matcher_bit_exact_incl_boundaries():
    """match_batch_np over padded [B, L] batches — rows at exactly
    L bytes (the max_len boundary) and invalid (-1/-2) rows."""
    rng = random.Random(7)
    for pat in (APACHE2, r"ab+c", r"^\d+ GET", r"[^ ]* [^ ]*$"):
        d_min = compile_dfa(pat)
        d_raw = compile_dfa(pat, minimize=False)
        L = 32
        rows = [bytes(rng.choice(b"ab c0GET\n\xc3") for _ in range(n))
                for n in (0, 1, L // 2, L, L)]  # incl. exactly-L rows
        B = len(rows)
        batch = np.zeros((B, L), dtype=np.uint8)
        lengths = np.zeros(B, dtype=np.int32)
        for i, r in enumerate(rows):
            batch[i, :len(r)] = np.frombuffer(r, dtype=np.uint8)
            lengths[i] = len(r)
        lengths[-1] = -2  # overflow-marked row must never match
        got_min = d_min.match_batch_np(batch, lengths)
        got_raw = d_raw.match_batch_np(batch, lengths)
        assert (got_min == got_raw).all(), pat
        assert not got_min[-1]


def test_hopcroft_output_is_minimal_and_agrees_with_moore():
    """No two distinct states of the minimized table are equivalent:
    the Moore fixpoint (independent implementation) over the Hopcroft
    output must not merge anything, and both minimizers must land on
    the same state count from the raw machine."""
    rng = random.Random(11)
    pats = [APACHE2, "ERROR", r"a[0-9]{8}z", r"[^ ]+ [^ ]+"]
    pats += [p for p in (_random_pattern(rng) for _ in range(20))]
    checked = 0
    for pat in pats:
        try:
            d_min = compile_dfa(pat)
            d_raw = compile_dfa(pat, minimize=False)
        except UnsupportedRegex:
            continue
        checked += 1
        refined, _ = _moore_minimize(d_min.trans, d_min.start)
        assert refined.shape[0] == d_min.n_states, pat
        moore_t, _ = _moore_minimize(d_raw.trans, d_raw.start)
        assert moore_t.shape[0] == d_min.n_states, pat
    assert checked >= 10


def test_class_remerge_no_identical_columns():
    for pat in (APACHE2, "GET|POST", r"x[0-9a-f]{4}"):
        d = compile_dfa(pat)
        used = np.unique(d.class_map)
        assert used.max() < d.n_classes
        cols = {d.trans[:, c].tobytes() for c in used}
        assert len(cols) == len(used), pat  # no two classes identical
        # every table column is referenced (dead BOS column dropped)
        assert len(used) == d.n_classes, pat


def test_apache2_shrink_and_unlock():
    """The acceptance shape: apache2 demonstrably shrinks (S and C),
    and the minimized machine composes at least as deep a stride as
    the raw one (k=3, the benchmark's scan child)."""
    d = compile_dfa(APACHE2)
    st = d.shrink
    assert st is not None and st.minimized
    assert st.s_raw > d.n_states          # Hopcroft merged states
    assert st.c_raw > d.n_classes         # class remerge shrank C
    assert choose_k(d.n_states, d.n_classes) == 3
    assert choose_k(st.s_raw, st.c_raw) <= 3


def _grep_engine(buf, **props):
    from fluentbit_tpu.core.engine import Engine

    e = Engine()
    f = e.filter("grep")
    f.set("regex", f"log {APACHE2}")
    f.set("tpu_batch_records", "1")
    for k, v in props.items():
        f.set(k, v)
    ins = e.input("dummy")
    for x in e.inputs + e.filters:
        x.configure()
        x.plugin.init(x, e)
    e.input_log_append(ins, "b", buf)
    out = b"".join(bytes(c.buf) for c in ins.pool.drain())
    return e, ins, out


def _mixed_chunk(n=2048, match_frac=0.4, seed=5):
    from fluentbit_tpu.codec.events import encode_event

    rng = random.Random(seed)
    buf = bytearray()
    for i in range(n):
        if rng.random() < match_frac:
            line = (f"10.0.0.{i % 256} - frank "
                    f"[10/Oct/2000:13:55:36 -0700] "
                    f'"GET /p{i} HTTP/1.1" 200 77 "http://r" "curl"')
        else:
            line = f"kernel: oom pid={i} seq={rng.randrange(1 << 20)}"
        buf += encode_event({"log": line}, float(i))
    return bytes(buf)


def test_shrink_metrics_wired_through_engine():
    buf = _mixed_chunk(n=2048, match_frac=0.1)
    e, _, _ = _grep_engine(buf)
    label = ("grep",)
    d = compile_dfa(APACHE2)
    assert e.m_shrink_states.get(label) == d.shrink.states_eliminated > 0
    assert e.m_shrink_classes.get(label) == d.shrink.classes_eliminated > 0


def test_grep_program_exposes_decision():
    prog = program_for((APACHE2,), 512)
    dec = prog.decision()
    r = dec["rules"][0]
    assert r["s_raw"] > r["s"] and r["c_raw"] > r["c"]
    assert r["minimized"] and dec["k"] == r["k"]
    assert dec["k_groups"] == [dec["k"]]
    assert dec["assoc_eligible"] == (dec["max_states"] <= 64)
    # materialization resolves the kernel (scan on the CPU backend)
    assert prog.try_ready()
    assert prog.decision()["kernel_resolved"] == "scan"


def test_per_dfa_k_groups_split_and_bit_exact():
    """Heterogeneous-k rule sets split into per-k child programs
    (literal k=6 no longer pinned to apache2's k=3) and stay
    bit-exact; the decision surface records the group layout."""
    from fluentbit_tpu.ops.batch import assemble

    dfas = [compile_dfa("ERROR"), compile_dfa(APACHE2)]
    prog = GrepProgram(dfas, 256)
    assert prog._children is not None
    dec = prog.decision()
    assert len(dec["k_groups"]) == 2
    assert max(dec["k_groups"]) > min(dec["k_groups"])
    rng = random.Random(13)
    lines = [b"ERROR boom", b"nothing",
             b'10.0.0.1 - u [t] "GET /a HTTP/1.1" 200 5 "r" "a"',
             b""] + _random_inputs(rng)[:20]
    b = assemble(lines, max_len=256)
    batch = np.stack([b.batch] * 2)
    lengths = np.stack([b.lengths] * 2)
    got = prog.match(batch, lengths)
    for r, d in enumerate(dfas):
        exp = np.array([d.match_bytes(ln) for ln in lines])
        assert (got[r] == exp).all()
