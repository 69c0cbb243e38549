"""flux's host absorb over the bytes a batch uses.

``FluxState._group_rows`` keys its one ``np.unique`` on the columns and
bytes that can tell a batch's rows apart, and ``_topk_composites``
writes every group's composites into one plane only as far as the
batch's longest value reaches. The full-width forms they replaced are
kept here as the oracles: the same segment ids and key tuples, the same
composite bytes and lengths, the same candidates, on keys and values
built to break a narrower key (embedded NULs, arena garbage past each
length, keys that differ only past byte 8, mixed kinds, prefixes and
composites past the width).
"""

import numpy as np
import pytest

pytest.importorskip("jax")

import fluentbit_tpu  # noqa: F401,E402  (registers plugins)
from fluentbit_tpu.codec.events import (decode_events,  # noqa: E402
                                        encode_event)
from fluentbit_tpu.core.spans import ShardedTimings  # noqa: E402
from fluentbit_tpu.flux.state import (KEY_INT, KEY_STR,  # noqa: E402
                                      TIMING_KEYS, FluxSpec, FluxState,
                                      KeyCol)
from fluentbit_tpu.ops.batch import assemble, bucket_size  # noqa: E402

from test_flux import _state_fingerprint, build_engine  # noqa: E402


# ------------------------------------------------- the full-width oracles

def full_width_group_rows(keycols, group_by):
    """``_group_rows`` as it was: a kind byte a field, the whole string
    plane with its pad zeroed, one ``np.unique`` over the void rows."""
    n = keycols[group_by[0]].kind.shape[0]
    mats = []
    for f in group_by:
        kc = keycols[f]
        mats.append(kc.kind.reshape(-1, 1))
        if kc.ints is not None:
            mats.append(np.ascontiguousarray(
                kc.ints, dtype="<i8").view(np.uint8).reshape(-1, 8))
        if kc.sbatch is not None:
            b, L = kc.sbatch, kc.sbatch.shape[1]
            ln2 = np.where(kc.kind == KEY_STR, kc.slen,
                           np.int32(-1)).astype(np.int32)
            bz = np.ascontiguousarray(b, dtype=np.uint8).copy()
            bz[np.arange(L)[None, :] >= np.clip(ln2, 0, None)[:, None]] = 0
            mats.append(bz)
            mats.append(ln2.astype("<i4").view(np.uint8).reshape(-1, 4))
    keyed = np.ascontiguousarray(np.concatenate(mats, axis=1))
    void = keyed.view(f"V{keyed.shape[1]}").reshape(-1)
    _, first_idx, inv = np.unique(void, return_index=True,
                                  return_inverse=True)
    order = np.argsort(first_idx, kind="stable")
    remap = np.empty(order.size, dtype=np.int64)
    remap[order] = np.arange(order.size)
    seg = remap[np.asarray(inv).reshape(-1)]
    rows = first_idx[order]
    keys = list(zip(*(keycols[f].values(rows) for f in group_by)))
    assert seg.shape == (n,)
    return seg, keys


def full_width_composites(st, keys, seg, batch, lengths):
    """The composites as they were built: a ``[n_g, W]`` block a group
    (its valid rows, the prefix, the value over the whole width, the
    pad zeroed), each nominated, concatenated, padded to the bucket."""
    W = st.spec.max_len
    parts = []
    for gid, key in enumerate(keys):
        rows = np.flatnonzero(seg == gid)
        gb, gl = batch[rows], lengths[rows]
        prefix = st._group_prefix(key)
        valid = np.nonzero(gl >= 0)[0]
        plen = len(prefix)
        if valid.size == 0 or plen > W:
            continue
        comp = np.zeros((valid.size, W), dtype=np.uint8)
        comp_len = np.full((valid.size,), -1, dtype=np.int32)
        if plen:
            comp[:, :plen] = np.frombuffer(prefix, dtype=np.uint8)
        vl = gl[valid]
        fits = plen + vl <= W
        cols = min(W - plen, gb.shape[1])
        comp[:, plen:plen + cols] = gb[valid, :cols]
        comp_len[fits] = (plen + vl[fits]).astype(np.int32)
        comp[np.arange(W)[None, :] >= np.clip(comp_len, 0, None)[:, None]] = 0
        st._topk_nominate(key, comp, comp_len, plen)
        parts.append((comp, comp_len))
    if not parts:
        return None, None
    comp = np.concatenate([c for c, _ in parts])
    comp_len = np.concatenate([cl for _, cl in parts])
    Bc = bucket_size(comp.shape[0], max_len=W)
    comp = np.concatenate([comp, np.zeros((Bc - comp.shape[0], W),
                                          dtype=np.uint8)])
    comp_len = np.concatenate([comp_len, np.full((Bc - comp_len.shape[0],),
                                                 -1, dtype=np.int32)])
    return comp, comp_len


# ---------------------------------------------------------- the inputs

def garble(plane, lengths, rng):
    """A staged plane as the native stager leaves it: arena garbage past
    each row's length (the whole row where it holds no string)."""
    out = rng.integers(0, 256, size=plane.shape, dtype=np.uint8)
    for i, ln in enumerate(lengths.tolist()):
        if ln > 0:
            out[i, :ln] = plane[i, :ln]
    return out


def str_col(values, max_len, rng):
    """A staged string column: bytes, None (missing) or oversize."""
    b = assemble(values, max_len)
    ln = b.lengths.copy()
    return garble(b.batch, ln, rng), ln


NUL_KEYS = [b"ab", b"ab\x00", b"a\x00b", b"ab\x00\x00", b"", b"\x00",
            b"\x00\x00", b"a", b"ab"]
LONG_KEYS = [b"tenant-00" + bytes([c]) for c in b"abcab"] + \
    [b"12345678", b"12345678\x00", b"123456789", b"1234567",
     b"x" * 40, b"x" * 39 + b"y", b"x" * 40]


def string_case(name, rng):
    """→ (keycols, group_by, max_len) for one string-keyed case."""
    if name == "embedded_nul":
        vals, L = NUL_KEYS * 3, 16
    elif name == "past_byte_8":
        vals, L = LONG_KEYS * 2, 64
    elif name == "empty_and_oversize":
        # oversize strings stage as -2: missing, one group with nulls
        vals, L = [b"", b"ok", b"o" * 17, None, b"", b"o" * 16,
                   b"p" * 30, None], 16
    elif name == "one_kind_one_key":
        vals, L = [b"same"] * 9, 256
    elif name == "all_missing":
        vals, L = [None] * 7, 32
    elif name == "same_length":
        vals, L = [b"acme", b"abcd", b"acme", b"\x00\x00\x00\x00"], 32
    elif name == "sketch_tenants":
        vals, L = [b"acme", b"globex", b"initech", b"umbrella"] * 3, 256
    else:
        raise AssertionError(name)
    b, ln = str_col(vals, L, rng)
    return {"k": KeyCol.of_strings(b, ln)}, ("k",), L


def typed_case(name, rng):
    """→ (keycols, group_by, max_len) built by the per-record twin's
    typer (``_key_column``), then staged garbage put past each length."""
    L = 32
    if name == "mixed_int_str_null":
        vals = [1, "1", None, "a", 1, "", 0, None, "a\x00", 2 ** 40,
                "a", -1]
    elif name == "int64_extremes":
        vals = [-(1 << 63), (1 << 63) - 1, -1, 0, 1, -(1 << 63), 255,
                256, -256, (1 << 63) - 1, 1 << 32, -(1 << 32)]
    elif name == "objects":
        vals = [1, 1.0, True, 2.5, "2.5", None, [1], {"a": 1}, [1],
                1 << 70, b"raw", "raw", 7]
    else:
        raise AssertionError(name)
    st = FluxState(FluxSpec("t", group_by=("k",), max_len=L))
    kc = st._key_column([{"k": v} if v is not None else {} for v in vals],
                        "k")
    if kc.sbatch is not None:
        kc.sbatch = garble(kc.sbatch, kc.slen, rng)
    return {"k": kc}, ("k",), L


def multi_case(rng):
    """Two string fields and one integer field: 3-field keys."""
    n = 64
    a = [[b"x", b"x\x00", b"", None][i % 4] for i in range(n)]
    bvals = [[b"tenant-0001", b"tenant-0002", b"t"][(i // 3) % 3]
             for i in range(n)]
    ints = np.asarray([(i // 5) % 3 - 1 for i in range(n)], dtype=np.int64)
    kind = np.full((n,), KEY_INT, dtype=np.uint8)
    kind[::7] = 0
    ints[::7] = 0
    keycols = {"a": KeyCol.of_strings(*str_col(a, 16, rng)),
               "b": KeyCol.of_strings(*str_col(bvals, 16, rng)),
               "c": KeyCol(kind, ints)}
    return keycols, ("a", "b", "c"), 16


CASES = ["embedded_nul", "past_byte_8", "empty_and_oversize",
         "one_kind_one_key", "all_missing", "same_length",
         "sketch_tenants", "mixed_int_str_null",
         "int64_extremes", "objects", "multi_field"]


def make_case(name, seed):
    rng = np.random.default_rng(seed)
    if name == "multi_field":
        return multi_case(rng)
    if name in ("mixed_int_str_null", "int64_extremes", "objects"):
        return typed_case(name, rng)
    return string_case(name, rng)


@pytest.mark.parametrize("seed", [0, 1])
@pytest.mark.parametrize("case", CASES)
def test_group_rows_equal_the_full_width_key(case, seed):
    keycols, gb, L = make_case(case, seed)
    st = FluxState(FluxSpec("t", group_by=gb, max_len=L))
    st.timings = tm = ShardedTimings(TIMING_KEYS)
    n = keycols[gb[0]].kind.shape[0]
    seg, keys = st._group_rows(n, keycols)
    want_seg, want_keys = full_width_group_rows(keycols, gb)
    assert np.array_equal(seg, want_seg)
    assert keys == want_keys
    assert [type(p) for k in keys for p in k] == \
        [type(p) for k in want_keys for p in k]
    assert tm["grouped"] == 1
    assert 8 <= tm["group_key_bytes"] <= 1 + 8 + L + 4 + 2 * (1 + L + 4)


@pytest.mark.parametrize("case,width", [
    ("one_kind_one_key", 8),        # 4 bytes, one length: one u64
    ("all_missing", 8),             # nothing to key: one u64 of zeros
    ("same_length", 8),             # 4 bytes, NULs among them: one u64
    ("sketch_tenants", 12),         # the longest tenant (8) + the length
    ("past_byte_8", 44),            # the longest key (40) + the length
    ("int64_extremes", 8),          # one kind: the integer alone
    # kinds differ, the integer, the longest string ("a\x00"), the length
    ("mixed_int_str_null", 1 + 8 + 2 + 4),
])
def test_group_key_is_the_bytes_the_batch_uses(case, width):
    keycols, gb, L = make_case(case, 0)
    st = FluxState(FluxSpec("t", group_by=gb, max_len=L))
    st.timings = tm = ShardedTimings(TIMING_KEYS)
    st._group_rows(keycols[gb[0]].kind.shape[0], keycols)
    assert tm["group_key_bytes"] == width


def topk_case(name):
    """→ (group values, top-k values, max_len) for one composite case."""
    if name == "nul_and_garbage":
        L = 32
        groups = [b"a", b"b\x00", b"a", b"", None] * 6
        vals = [b"v\x00", b"", b"w" * 5, None, b"\x00\x00x"] * 6
    elif name == "composite_past_width":
        # prefix "a\x1f" is 2 bytes: values of 15-16 bytes overflow 16
        L = 16
        groups = [b"a", b"bb", b"a", b"bb"] * 4
        vals = [b"q" * 14, b"q" * 14, b"q" * 15, b"r" * 16,
                b"s" * 13, None, b"t", b""] * 2
    elif name == "prefix_past_width":
        L = 8
        groups = [b"G" * 8, b"ok", b"G" * 8, b"ok"]
        vals = [b"v1", b"v2", b"v3", b"v4"]
    elif name == "group_without_values":
        L = 32
        groups = [b"a", b"b", b"a", b"b", b"c"]
        vals = [b"x", None, b"y", None, b"z" * 40]
    elif name == "wide_values":
        L = 256
        groups = [b"acme", b"globex"] * 40
        vals = [bytes([65 + i % 26]) * (1 + (i * 37) % 250)
                for i in range(80)]
    elif name == "nothing_valid":
        L = 16
        groups = [b"a", b"b"] * 3
        vals = [None] * 6
    else:
        raise AssertionError(name)
    return groups, vals, L


TOPK_CASES = ["nul_and_garbage", "composite_past_width",
              "prefix_past_width", "group_without_values", "wide_values",
              "nothing_valid"]


@pytest.mark.parametrize("case", TOPK_CASES)
def test_composites_equal_the_per_group_blocks(case):
    rng = np.random.default_rng(7)
    groups, vals, L = topk_case(case)
    keycols = {"g": KeyCol.of_strings(*str_col(groups, L, rng))}
    tb, tl = str_col(vals, L, rng)
    spec = FluxSpec("t", group_by=("g",), topk_field="v", topk=4,
                    max_len=L)
    new, old = FluxState(spec), FluxState(spec)
    n = len(vals)
    seg, keys = new._group_rows(n, keycols)
    order = np.argsort(seg, kind="stable")
    bounds = np.searchsorted(seg[order], np.arange(len(keys) + 1))
    vw = max(int(tl.max()), 0)
    comp, comp_len = new._topk_composites(
        keys, tb, tl, vw, lambda gid: order[bounds[gid]:bounds[gid + 1]])
    want, want_len = full_width_composites(old, keys, seg, tb, tl)
    if want is None:
        assert comp is None and comp_len is None
    else:
        assert comp.shape == want.shape and comp.dtype == want.dtype
        assert np.array_equal(comp, want)
        assert np.array_equal(comp_len, want_len)
    assert [(k, list(c)) for k, c in new._candidates.items()] == \
        [(k, list(c)) for k, c in old._candidates.items()]


def test_single_group_composites_equal_the_full_width_block():
    """No GROUP BY: one group, every row its own, the empty prefix."""
    rng = np.random.default_rng(3)
    vals = [b"/api/v1/item/%d" % i for i in range(40)] + [None, b""]
    tb, tl = str_col(vals, 64, rng)
    spec = FluxSpec("t", topk_field="v", max_len=64)
    new, old = FluxState(spec), FluxState(spec)
    n = len(vals)
    vw = int(tl.max())
    comp, comp_len = new._topk_composites([()], tb, tl, vw,
                                          lambda gid: np.arange(n))
    want, want_len = full_width_composites(old, [()], np.zeros(n, int),
                                           tb, tl)
    assert np.array_equal(comp, want) and np.array_equal(comp_len, want_len)
    assert list(new._candidates[()]) == list(old._candidates[()])


# ------------------------------------------------------------ end to end

def edge_bodies(n=600, seed=11):
    """Sketch-shaped records (tenant, user, path) with the edges in:
    NULs, empty and long tenants, integer and missing tenants, paths
    long enough to overflow a composite, missing paths."""
    rng = np.random.default_rng(seed)
    tenants = ["acme", "globex", "acme\x00", "", "a" * 60, "initech-east-1",
               "initech-east-2", None, 7, -3]
    out = []
    for i in range(n):
        t = tenants[int(rng.integers(len(tenants)))]
        body = {"user": "user-%06d" % rng.integers(5000),
                "path": "/api/v1/item/%d" % rng.zipf(1.3)}
        if i % 17 == 0:
            body["path"] = "/p" + "x" * int(rng.integers(180, 260))
        if i % 23 == 0:
            del body["path"]
        if t is not None:
            body["tenant"] = t
        out.append(body)
    return out


SKETCH_PROPS = {"group_by": "tenant", "distinct_field": "user",
                "topk_field": "path", "export_interval_sec": "0"}


def test_sketch_chain_batched_equals_per_record_twin():
    """The staged chunk (native planes, garbage past each length) and
    the decoded records give the same groups, registers, count-min
    table and candidates."""
    bodies = edge_bodies()
    raw = b"".join(encode_event(b, 1.0 + i) for i, b in enumerate(bodies))
    e1, ins1, p1 = build_engine(SKETCH_PROPS)
    e1.input_log_append(ins1, "t", raw)
    assert sum(v for _, v in e1.m_filter_batch_decline.samples()) == 0
    e2, ins2, p2 = build_engine(SKETCH_PROPS)
    p2._batch_ok = False
    e2.input_log_append(ins2, "t", raw)
    assert _state_fingerprint(p1.state) == _state_fingerprint(p2.state)
    assert p1.state._candidates == p2.state._candidates
    tm = p1.raw_timings
    assert tm["grouped"] == 1 and tm["topk_s"] > 0
    # the kind, the integer, the longest tenant ("a" * 60) and the
    # length: not the 256-byte plane
    assert tm["group_key_bytes"] == 1 + 8 + 60 + 4


@pytest.mark.mesh
def test_sketch_state_on_the_mesh_equals_single_device():
    """The fused device program gets the same composite plane: the
    mesh state is bit-identical to the host twin's."""
    import jax

    if len(jax.devices()) < 8:
        pytest.skip("need the simulated 8-device mesh")
    bodies = edge_bodies(400, seed=5)
    spec = dict(group_by=("tenant",), distinct=("user",),
                topk_field="path")
    plain = FluxState(FluxSpec("t", **spec))
    meshy = FluxState(FluxSpec("t", mesh=True, **spec))
    assert meshy._mesh is not None
    events = decode_events(b"".join(encode_event(b, 1.0 + i)
                                    for i, b in enumerate(bodies)))
    for part in (events[:150], events[150:]):
        plain.absorb_events(part)
        meshy.absorb_events(part)
    assert _state_fingerprint(plain) == _state_fingerprint(meshy)
    assert plain._candidates == meshy._candidates
