"""filter_log_to_metrics: counter/gauge/histogram parity with the
reference (plugins/filter_log_to_metrics/log_to_metrics.c) plus the
north-star HLL/count-min sketch modes (BASELINE config 4), and the
device-sketch accuracy/merge tests.
"""

import json
import types

import numpy as np
import pytest

import fluentbit_tpu as flb
from fluentbit_tpu.codec.msgpack import Unpacker
from fluentbit_tpu.core.metrics import payload_to_prometheus
from fluentbit_tpu.ops.batch import assemble
from fluentbit_tpu.ops.sketch import CountMin, HyperLogLog


def run_l2m(records, flt_props, out_name="lib"):
    ctx = flb.create(flush="50ms", grace="1")
    in_ffd = ctx.input("lib", tag="logs")
    props = {"match": "logs", "metric_name": "m", "metric_description": "d",
             "tag": "metrics"}
    props.update(flt_props)
    listed = {k: v for k, v in props.items() if isinstance(v, list)}
    for k in listed:
        props.pop(k)
    f = ctx.filter("log_to_metrics", **props)
    for k, vs in listed.items():
        for v in vs:
            ctx.set(f, **{k: v})
    payloads = []
    logs = []
    ctx.output("lib", match="metrics",
               callback=lambda d, t: payloads.append(d))
    ctx.output("lib", match="logs", callback=lambda d, t: logs.append(d))
    ctx.start()
    try:
        for r in records:
            ctx.push(in_ffd, json.dumps(r))
        ctx.flush_now()
    finally:
        ctx.stop()
    metrics = {}
    for data in payloads:  # snapshots are cumulative; keep the last
        for obj in Unpacker(data):
            metrics = obj
    return metrics, logs


def find_metric(payload, name):
    for m in payload.get("metrics", []):
        if m["name"] == name:
            return m
    return None


def test_counter_with_labels_and_prefilter():
    records = (
        [{"log": "error A", "svc": "api"}] * 3
        + [{"log": "error B", "svc": "web"}] * 2
        + [{"log": "ok", "svc": "api"}] * 5
    )
    payload, logs = run_l2m(records, {
        "regex": "log error",
        "label_field": "svc",
    })
    m = find_metric(payload, "log_metric_m")
    assert m is not None and m["type"] == "counter"
    vals = {tuple(s["labels"]): s["value"] for s in m["values"]}
    assert vals == {("api",): 3, ("web",): 2}
    # logs pass through untouched (discard_logs off)
    assert logs


def test_gauge_and_histogram_value_field():
    records = [{"d": 0.2}, {"d": 1.7}, {"d": 0.009}, {"x": 1}]
    payload, _ = run_l2m(records, {
        "metric_mode": "gauge", "value_field": "d",
    })
    m = find_metric(payload, "log_metric_m")
    assert m["values"][0]["value"] == pytest.approx(0.009)  # last set wins

    payload2, _ = run_l2m(records, {
        "metric_mode": "histogram", "value_field": "d",
        "bucket": ["0.01", "0.5", "2.0"],
    })
    m2 = find_metric(payload2, "log_metric_m")
    h = m2["hist"][0]
    assert h["counts"] == [1, 1, 1, 0]  # .009 | .2 | 1.7 | +inf
    assert h["sum"] == pytest.approx(1.909)


def test_kubernetes_mode_labels():
    records = [{
        "log": "x",
        "kubernetes": {"namespace_name": "prod", "pod_name": "p1",
                       "container_name": "c", "docker_id": "d",
                       "pod_id": "u"},
    }]
    payload, _ = run_l2m(records, {"kubernetes_mode": "true"})
    m = find_metric(payload, "log_metric_m")
    assert m["labels"] == ["namespace_name", "pod_name", "container_name",
                           "docker_id", "pod_id"]
    assert m["values"][0]["labels"] == ["prod", "p1", "c", "d", "u"]


def test_discard_logs():
    _, logs = run_l2m([{"log": "a"}], {"discard_logs": "on"})
    assert logs == []


def test_cardinality_mode_hll():
    records = [{"user": f"u{i % 40}"} for i in range(400)]
    payload, _ = run_l2m(records, {
        "metric_mode": "cardinality", "value_field": "user",
    })
    m = find_metric(payload, "log_metric_m")
    est = m["values"][0]["value"]
    assert abs(est - 40) / 40 < 0.05


def test_frequency_mode_cms():
    records = [{"code": "200"}] * 50 + [{"code": "404"}] * 9 + [{"code": "500"}] * 3
    payload, _ = run_l2m(records, {
        "metric_mode": "frequency", "value_field": "code",
        "frequency_top_k": "2",
    })
    m = find_metric(payload, "log_metric_m")
    vals = {tuple(s["labels"]): s["value"] for s in m["values"]}
    assert vals == {("200",): 50, ("404",): 9}  # top-2, exact at this size


def test_prometheus_exporter_output_renders():
    ctx = flb.create(flush="50ms", grace="1")
    in_ffd = ctx.input("lib", tag="logs")
    ctx.filter("log_to_metrics", match="logs", metric_name="hits",
               metric_description="hits", tag="metrics")
    exp = ctx.output("prometheus_exporter", match="metrics")
    exp_plugin = ctx.engine.outputs[-1].plugin
    ctx.start()
    try:
        for _ in range(4):
            ctx.push(in_ffd, json.dumps({"log": "x"}))
        ctx.flush_now()
    finally:
        ctx.stop()
    text = exp_plugin.render()
    assert "# TYPE log_metric_hits counter" in text
    assert "log_metric_hits 4" in text


def test_payload_prometheus_histogram_text():
    payload = {
        "meta": {},
        "metrics": [{
            "name": "ns_h", "type": "histogram", "desc": "h",
            "labels": ["svc"], "buckets": [1.0, 5.0],
            "values": [], "hist": [
                {"labels": ["a"], "counts": [2, 1, 1], "sum": 9.5},
            ],
        }],
    }
    text = payload_to_prometheus(payload)
    assert 'ns_h_bucket{svc="a",le="1"} 2' in text
    assert 'ns_h_bucket{svc="a",le="5"} 3' in text
    assert 'ns_h_bucket{svc="a",le="+Inf"} 4' in text
    assert 'ns_h_count{svc="a"} 4' in text


# ---------------------------------------------------------------- sketches

def test_hll_accuracy_10k():
    hll = HyperLogLog(p=14)
    vals = [f"user-{i}".encode() for i in range(10000)] * 2
    for i in range(0, len(vals), 4096):
        b = assemble(vals[i : i + 4096], 64)
        hll.update(b.batch, b.lengths)
    est = hll.estimate()
    assert abs(est - 10000) / 10000 < 0.03


def test_hll_small_range_linear_counting():
    hll = HyperLogLog(p=12)
    b = assemble([f"v{i}".encode() for i in range(100)], 16)
    hll.update(b.batch, b.lengths)
    assert abs(hll.estimate() - 100) < 5


def test_cms_never_underestimates():
    cms = CountMin(depth=4, width=4096)
    stream = []
    freq = {}
    for i in range(300):
        k = f"k{i}".encode()
        n = (i % 7) + 1
        freq[k] = n
        stream += [k] * n
    b = assemble(stream, 16)
    cms.update(b.batch, b.lengths)
    for k, n in freq.items():
        assert cms.query(k) >= n


def test_sketches_sharded_equal_single_device(request):
    jax = pytest.importorskip("jax")
    from jax.sharding import Mesh

    from fluentbit_tpu.ops.sketch import sharded_cms_update, sharded_hll_update

    devs = jax.devices()
    if len(devs) < 8:
        pytest.skip("need 8 devices")
    mesh = Mesh(np.asarray(devs[:8]), ("batch",))
    vals = [f"x{i}".encode() for i in range(1000)]
    b = assemble(vals, 32)

    h1, h2 = HyperLogLog(p=12), HyperLogLog(p=12)
    sharded_hll_update(h1, mesh, b.batch, b.lengths)
    h2.update(b.batch, b.lengths)
    assert np.array_equal(np.asarray(h1.registers), np.asarray(h2.registers))

    c1, c2 = CountMin(4, 2048), CountMin(4, 2048)
    sharded_cms_update(c1, mesh, b.batch, b.lengths)
    c2.update(b.batch, b.lengths)
    assert np.array_equal(np.asarray(c1.table), np.asarray(c2.table))


def test_flush_interval_timer_emits_pending():
    """With flush_interval configured, updates arriving inside the
    throttle window are emitted by the timer even when no further
    records arrive."""
    import time

    ctx = flb.create(flush="50ms", grace="1")
    in_ffd = ctx.input("lib", tag="logs")
    ctx.filter("log_to_metrics", match="logs", metric_name="n",
               metric_description="d", tag="metrics",
               flush_interval_nsec=str(int(0.15e9)))
    payloads = []
    ctx.output("lib", match="metrics", callback=lambda d, t: payloads.append(d))
    ctx.start()
    try:
        for _ in range(3):
            ctx.push(in_ffd, json.dumps({"log": "x"}))
        time.sleep(0.6)  # no filter() calls during this window
    finally:
        ctx.stop()
    last = {}
    for data in payloads:
        for obj in Unpacker(data):
            last = obj
    m = find_metric(last, "log_metric_n")
    assert m is not None and m["values"][0]["value"] == 3


class _Emitter:
    """The hidden emitter input's stand-in: what log_to_metrics adds."""

    def __init__(self):
        self.events = []

    def add_event(self, tag, data, event_type, n_records=1):
        self.events.append((tag, event_type, data))
        return n_records


class _Engine:
    def __init__(self):
        self.emitter = _Emitter()

    def hidden_input(self, name, owner=None, **props):
        return types.SimpleNamespace(plugin=self.emitter)


class _TopKBefore:
    """The frequency snapshot as it was built before the read-back was
    vectorised: the same candidate set (recency by delete-and-reinsert,
    the 4,096 most recent kept), each candidate's row-min by
    ``_cols_cpu`` over one table copy, every (estimate, value) pair
    sorted."""

    def __init__(self, k):
        from fluentbit_tpu.core.metrics import MetricsRegistry

        self.k = k
        self.cands = {}
        self.reg = MetricsRegistry()
        self.gauge = self.reg.gauge("log_metric", "", "m", "d", ("value",))
        self.kth_ties = 0
        self.estimated = 0

    def snapshot(self, cms, vals):
        from fluentbit_tpu.codec.msgpack import packb

        for v in vals:
            self.cands.pop(v, None)
            self.cands[v] = None
        for v in list(self.cands)[:max(0, len(self.cands) - 4096)]:
            del self.cands[v]
        self.estimated += len(self.cands)
        table = np.asarray(cms.table)
        ests = [min(int(table[r, c]) for r, c in enumerate(cms._cols_cpu(v)))
                for v in self.cands]
        ranked = sorted(zip(ests, self.cands), reverse=True)
        if len(ranked) > self.k and ranked[self.k - 1][0] == ranked[self.k][0]:
            self.kth_ties += 1
        self.gauge.clear()
        for est, v in ranked[:self.k]:
            self.gauge.set(est, (v.decode("utf-8", "replace"),))
        return packb(self.reg.to_msgpack_obj())


def _zipf_frames(seed, frames=12):
    """Frames of paths: Zipf(1.2) over 10,000 paths, twelve paths seen
    six times a frame (tied near the tenth place), 450 paths seen once
    (5,400 in all: past the 4,096 candidates) and two of 300 bytes
    (past the staging width and the read-back's vector width)."""
    rng = np.random.default_rng(seed)
    out = []
    for f in range(frames):
        paths = [f"/api/v1/item/{min(z, 10_000)}"
                 for z in rng.zipf(1.2, 300)]
        paths += [f"/tie/{j}" for j in range(12)] * 6
        paths += [f"/u/{f}/{i}" for i in range(450)]
        paths += [f"/long/{j}/" + "x" * 290 for j in range(2)]
        rng.shuffle(paths)
        out.append(paths)
    return out


@pytest.mark.parametrize("geometry", [(4, 16384), (2, 256)])
def test_frequency_snapshots_byte_identical_to_full_sort(geometry,
                                                         monkeypatch):
    """Every metrics snapshot the frequency mode emits is the one the
    per-value read-back and the full sort gave, byte for byte: through
    the candidate cap's evictions and with the tenth place tied."""
    from fluentbit_tpu.codec.chunk import EVENT_TYPE_METRICS
    from fluentbit_tpu.codec.events import LogEvent
    from fluentbit_tpu.core import metrics
    from fluentbit_tpu.core.plugin import registry

    monkeypatch.setattr(metrics, "time",
                        types.SimpleNamespace(time=lambda: 1.7e9))
    depth, width = geometry
    ins = registry.create_filter("log_to_metrics")
    for key, v in {"metric_mode": "frequency", "value_field": "path",
                   "metric_name": "m", "metric_description": "d",
                   "tag": "metrics", "sketch_depth": str(depth),
                   "sketch_width": str(width)}.items():
        ins.set(key, v)
    ins.configure()
    engine = _Engine()
    ins.plugin.init(ins, engine)
    flt = ins.plugin
    before = _TopKBefore(flt.frequency_top_k)

    expect, seen = [], set()
    for paths in _zipf_frames(sum(geometry)):
        events = [LogEvent(1.0, {"path": p}) for p in paths]
        flt.filter(events, "logs", None)
        vals = [p.encode() for p in paths]
        seen.update(vals)
        expect.append(("metrics", EVENT_TYPE_METRICS,
                       before.snapshot(flt.cms, vals)))

    assert engine.emitter.events == expect
    assert len(seen) > 4096
    assert list(flt._freq_candidates) == list(before.cands)
    assert before.kth_ties > 0
    tm = flt.raw_timings
    assert tm["query_values"] == before.estimated
    assert tm["query_scalar_values"] == 2 * len(expect)
    assert 0 < tm["cms_query_s"] <= tm["query_s"]
