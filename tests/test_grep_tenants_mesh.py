"""The grep-tenants-x4 configuration at a small size on the CPU: the
50-rule program of ``benchmark/configs/grep-tenants.conf`` (nine scan
children of at most 16 MiB of tables) sharded by rows over four of conftest's eight
virtual devices, as ``BASELINE.json`` config 5 lays it over its chips —
(a) a chip's share of the verdict, shard by shard, against the
one-device kernel and the benchmark's plain reference; (b) the served
path (``process_batch`` under ``FBTPU_MESH=1``) against the host chain's
bytes, with the counters that say how its launches were laid out; (c)
which mesh variant each child takes, and what would flip one; (d) a
launch staged for the mesh whose lane has no mesh left.

The program, the corpus and the helpers are ``tests/test_grep_tenants.py``'s
(its fixtures are imported: one 50-rule program a process). One mesh
handle a child a session: every case here shards over the same four
devices, and the served path is given the same mesh by its lane.
"""

import numpy as np
import pytest

jax = pytest.importorskip("jax")

from fluentbit_tpu.codec.events import decode_events, encode_event
from fluentbit_tpu.core.chunk_batch import RawChunk
from fluentbit_tpu.ops import fault
from fluentbit_tpu.ops.grep import GrepProgram
from fluentbit_tpu.ops.mesh import (TABLE_BUDGET, build_mesh,
                                    pad_to_devices, replicated_table_bytes)
from fluentbit_tpu.plugins.filter_grep import staged_match

from test_grep_tenants import (CHILDREN, CONF, NO_BUDGET,  # noqa: F401
                               corpus, make_filter, maker, pick, program,
                               reference, staged)

pytestmark = pytest.mark.mesh

CHIPS = 4


@pytest.fixture(scope="module")
def mesh4():
    if len(jax.devices()) < CHIPS:
        pytest.skip(f"need {CHIPS} devices, have {len(jax.devices())}")
    return build_mesh(CHIPS)


@pytest.fixture(autouse=True)
def own_lanes():
    """Lanes of this case's own, before and after: a first launch
    compiles (on the CPU for seconds, so ``launches_over_1s`` counts it)
    and the lanes are the process's."""
    fault.reset()
    yield
    fault.reset()


@pytest.fixture
def lane4(mesh4, monkeypatch):
    """The served path's lane, its mesh the module's four devices (the
    tier-1 lane would take all eight: a second set of handles, 144 MB of
    tables a device). ``FBTPU_MESH=1`` engages it on the CPU backend."""
    monkeypatch.setenv("FBTPU_MESH", "1")
    lane = fault.lane("grep")
    monkeypatch.setattr(lane, "current_mesh", lambda axis="batch": mesh4)
    return lane


# ------------------------------------------------ (a) a chip's share

@pytest.mark.parametrize("n_rows", [13, 64, 257])
def test_row_shards_equal_one_device_and_the_reference(
        n_rows, corpus, program, reference, mesh4):
    """The verdict ``[50, Bp]`` lies on the mesh in four row shards;
    cut out shard by shard, put together again and the padding rows
    (13 and 257 are no multiples of four) left off, it is the
    one-device verdict rule by rule, and the rows it keeps are the
    rows the plain reference keeps."""
    records = corpus[0]
    rows = pick(records, n_rows)          # every line over 256 B first
    planes, lengths = staged(records, rows, 512)
    mask_dev, counts, B, Bp = program.dispatch_mesh(
        mesh4, planes, lengths, with_counts=False)
    assert counts is None and B == n_rows
    assert Bp == pad_to_devices(n_rows, CHIPS) and (Bp > B) == bool(B % 4)
    shards = sorted(mask_dev.addressable_shards,
                    key=lambda s: s.index[1].start or 0)
    assert len(shards) == CHIPS
    assert len({s.device for s in shards}) == CHIPS
    parts = [np.asarray(s.data) for s in shards]
    assert all(p.shape == (50, Bp // CHIPS) and p.dtype == np.int32
               for p in parts)
    whole = np.concatenate(parts, axis=1)
    assert not whole[:, B:].any()         # padding rows: no rule's match
    got = whole[:, :B].astype(bool)
    one = program.match(planes, lengths)
    assert one.shape == (50, n_rows)
    for r in range(50):
        assert (got[r] == one[r]).all(), r
    assert got.any(axis=1).sum() >= 5     # several tenants' rules fire
    # legacy mode over 50 Excludes: a row is kept iff no rule matches;
    # an overflow row (-2) is never a match on the device
    rules = reference.rules_of(CONF)
    want = np.array([reference.keep(rules, records[i])
                     or lengths[0, j] == -2 for j, i in enumerate(rows)])
    assert (~got.any(axis=0) == want).all()
    assert 0 < want.sum() < n_rows


# ------------------------------------------------ (c) the variants

def test_every_child_takes_batch_on_four_devices(program, mesh4):
    """Each child of a split parent decides its own variant
    (``dispatch_mesh``); the parent's ``mesh_variant`` is its first
    child's answer only. All nine land on ``batch``: three of them hold
    a rule count that divides by four (4 and 12 at k=3, 4 at k=4), and
    what keeps those off ``rules`` is the child budget — 16 MiB of
    tables as ``replicated_table_bytes`` weighs them, so four replicas
    do not cross ``TABLE_BUDGET`` (64 MiB)."""
    children = program._children
    assert [(c.k, len(c.dfas)) for c in children] == CHILDREN[50]
    assert [c.mesh_variant(mesh4) for c in children] == ["batch"] * 9
    assert program.mesh_variant(mesh4) == "batch"
    divides = [c for c in children if len(c.dfas) % CHIPS == 0]
    assert [(c.k, len(c.dfas)) for c in divides] == [(3, 4), (3, 12), (4, 4)]
    for c in children:
        tables = c._tbl if c._np is None else c._np
        assert c.table_bytes == replicated_table_bytes(tables)
        assert c.table_bytes * CHIPS <= TABLE_BUDGET
    # once a child has a handle for the mesh, decision() says what it took
    program.dispatch_mesh(mesh4, *staged([{"log": "x"}], [0], 512),
                          with_counts=False)
    took = program.decision()["mesh_children"]
    assert [(t["k"], t["rules"]) for t in took] == CHILDREN[50]
    assert {(t["variant"], t["devices"]) for t in took} == {("batch", 4)}


@pytest.mark.parametrize("n_rules,variant", [(36, "rules"), (37, "batch")])
def test_what_would_flip_a_k3_child(n_rules, variant, program, mesh4):
    """What flips a child to ``rules``: a rule count that divides the
    mesh, with tables that cross ``TABLE_BUDGET`` replicated (or R ≥
    ``FBTPU_MESH_RULE_SHARD_R``). With no child budget to speak of, 36
    of the 38 k=3 rules in one child would shard the rule axis and have
    the planes expanded to ``[36, B, 512]`` on the host every launch
    (ROADMAP M5/D14); 37 do not divide. Under the module's budget the
    same 36 rules are children of at most 16 MiB and none can flip."""
    k3 = [d for d, k in zip(program.dfas, program.k_by_rule) if k == 3]
    assert len(k3) == 38
    cut = GrepProgram(k3[:n_rules], 512, plane_of=(0,) * n_rules,
                      child_budget=NO_BUDGET)
    assert cut._children is None and cut.k == 3
    assert cut.table_bytes == replicated_table_bytes(cut._np)
    assert cut.table_bytes * CHIPS > TABLE_BUDGET
    assert cut.mesh_variant(mesh4) == variant
    laid = GrepProgram(k3[:n_rules], 512, plane_of=(0,) * n_rules)
    assert len(laid._children) == 5
    assert all(c.table_bytes * CHIPS <= TABLE_BUDGET
               and c.mesh_variant(mesh4) == "batch"
               for c in laid._children)


# --------------------------------------------- (b) the served path

def chunk_of(records, rows) -> bytes:
    return b"".join(encode_event(records[i], float(i)) for i in rows)


def test_process_batch_on_the_mesh_equals_the_host_chain(corpus, lane4):
    """``FBTPU_MESH=1``: the filter's raw path stages at the one mesh
    width (L=512, rows padded to the mesh) and launches the nine
    children sharded; what it re-emits is byte for byte what the host
    chain (``tpu.enable off``) keeps, mid-length and overflow rows
    among them, and the counters say how the launch was laid out."""
    records, labels = corpus
    rows = pick(records, 101)             # 49 of 257-500 B, two over 512
    data = chunk_of(records, rows)
    dev = make_filter()
    assert dev.can_process_batch()
    before = lane4.stats()
    n_keep, out = dev.process_batch(RawChunk(data, "kube.tenants", 101))
    assert dev._mesh is lane4.current_mesh()      # the lane engaged
    host = make_filter([("tpu.enable", "off")])
    assert host._program is None
    _res, kept = host.filter(list(decode_events(data)), "kube.tenants", None)
    assert bytes(out) == b"".join(e.raw for e in kept)
    assert n_keep == len(kept) == sum(labels[i] & 1 for i in rows)
    assert 0 < n_keep < 101

    tm, after = dev.raw_timings, lane4.stats()
    assert after["ok"] - before["ok"] == 1 \
        == after["launches"] - before["launches"]
    assert after["fallback_segments"] == before["fallback_segments"]
    assert tm["mesh_launches"] == 1 and tm["unsharded_launches"] == 0
    assert tm["mesh_devices"] / tm["mesh_launches"] \
        == lane4.current_mesh().devices.size == CHIPS
    Bp = 256                              # bucket_size's rung, 4 | 256
    assert tm["records"] == tm["device_records"] == 101
    assert tm["overflow_rows"] == 2
    assert tm["h2d_bytes"] == Bp * (512 + 4)
    assert tm["d2h_bytes"] == 4 * 50 * Bp     # the mesh's verdict is i32
    assert tm["scan_elements"] == dev._program.scan_elements(Bp, 512)
    assert tm["split_launches"] == tm["long_rows"] == 0   # one chip's
    took = dev._program.decision()["mesh_children"]
    assert len(took) == 9 and all(
        t["variant"] == "batch" and t["devices"] == CHIPS for t in took)


def test_a_begun_launch_counts_its_layout_where_it_is_dispatched(
        corpus, lane4):
    """The launch begun ahead of the chunk's turn (``begin_batch``)
    counts its layout at once, as the lane counts the launch; the
    finishing half adds everything else and the layout not again."""
    records = corpus[0]
    rows = pick(records, 64)
    data = chunk_of(records, rows)
    dev = make_filter()
    begun = dev.begin_batch(data, 64)
    tm = dev.raw_timings
    assert begun is not None
    assert (tm["mesh_launches"], tm["mesh_devices"]) == (1, CHIPS)
    assert tm["device_records"] == 0 == tm["h2d_bytes"]
    chunk = RawChunk(data, "kube.tenants", 64)
    chunk.begun = begun
    n_keep, _out = dev.process_batch(chunk)
    assert 0 < n_keep < 64
    assert (tm["mesh_launches"], tm["mesh_devices"]) == (1, CHIPS)
    assert tm["device_records"] == 64 and tm["unsharded_launches"] == 0


# ----------------------------------- (d) the lane's mesh is gone

def test_no_mesh_left_is_served_unsharded_and_counted(
        corpus, mesh4, monkeypatch):
    """Staged for the mesh, but the lane has fewer than two devices
    left (``current_mesh()`` is None): the planes go out on one device,
    ``unsharded_launches`` counts it, and the verdict is the sharded
    launch's."""
    monkeypatch.setenv("FBTPU_MESH", "off")
    records = corpus[0]
    rows = pick(records, 64)
    data = chunk_of(records, rows)
    dev = make_filter()
    lane = fault.lane("grep")
    args = dict(max_len=512, min_records=1, mesh=mesh4)

    monkeypatch.setattr(lane, "current_mesh", lambda axis="batch": mesh4)
    tm = dev.raw_timings
    sharded, offs, n = staged_match(dev.rules, dev._program, lane, tm,
                                    data, 64, **args)
    assert (tm["mesh_launches"], tm["unsharded_launches"]) == (1, 0)

    monkeypatch.setattr(lane, "current_mesh", lambda axis="batch": None)
    before = lane.stats()
    alone, offs2, n2 = staged_match(dev.rules, dev._program, lane, tm,
                                    data, 64, **args)
    after = lane.stats()
    assert after["ok"] - before["ok"] == 1     # on the device, not the host
    assert after["fallback_segments"] == before["fallback_segments"]
    assert (tm["mesh_launches"], tm["mesh_devices"],
            tm["unsharded_launches"]) == (1, CHIPS, 1)
    assert n == n2 == 64 and (offs == offs2).all()
    assert alone.shape == sharded.shape == (50, 64)
    assert alone.dtype == sharded.dtype == bool
    assert (alone == sharded).all() and sharded.any()
    Bp = 256                              # bucket_size's least rung
    assert tm["d2h_bytes"] == 4 * 50 * Bp + 50 * Bp   # i32, then a byte


def test_the_new_keys_are_greps_alone():
    """``rewrite_tag`` and the parser launch through ``staged_match``
    too, but pass no mesh: their ``raw_timings`` have no such keys and
    no such count is ever added to them."""
    from fluentbit_tpu.plugins import (filter_grep, filter_parser,
                                       filter_rewrite_tag)

    new = {"mesh_launches", "mesh_devices", "unsharded_launches"}
    assert new <= set(filter_grep._TIMING_KEYS)
    assert not new & set(filter_rewrite_tag._TIMING_KEYS)
    assert not new & set(filter_parser._TIMING_KEYS)


@pytest.mark.parametrize("key", ["mesh_launches", "mesh_devices",
                                 "unsharded_launches"])
def test_every_layout_key_feeds_a_declared_metric_and_a_check(
        key, counters_of_declared_metrics):
    """An always-on counter that nothing reads is only a cost: each of
    the three is a term of a declared data-only metric of the benchmark
    (``lane.tenants_x4_devices_per_launch``, ``..._unsharded_per_launch``)
    and is read in a named check of the configuration's reference."""
    import os

    from test_grep_tenants import BENCH

    counter = f"filter.grep.{key}"
    assert counter in counters_of_declared_metrics
    with open(os.path.join(BENCH, "reference", "grep-tenants-x4.py")) as f:
        assert f'counters.get("{counter}")' in f.read()
