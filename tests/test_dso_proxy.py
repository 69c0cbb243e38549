"""Go-proxy-style foreign-runtime plugin ABI: FLBPluginRegister
definition handshake, api callback-table property reads, msgpack
flush/collect round trips (reference src/flb_plugin_proxy.c:347-433,
src/proxy/go/go.{c,h}). Demo objects are built live with gcc against
the exact struct layout cgo-built fluent-bit-go plugins use."""

import os
import subprocess
import time

import pytest

import fluentbit_tpu as flb
from fluentbit_tpu.codec.events import decode_events
from fluentbit_tpu.core.dso import load_dso_plugin, load_proxy_plugin
from fluentbit_tpu.core.plugin import registry

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def _build(tmp_path, src_name):
    src = os.path.join(REPO, "native", "demo_plugins", src_name)
    out = str(tmp_path / (src_name.replace(".c", "") + ".so"))
    subprocess.run(["gcc", "-shared", "-fPIC", "-O2", "-o", out, src],
                   check=True, capture_output=True)
    return out


@pytest.fixture(scope="module")
def proxy_so(tmp_path_factory):
    d = tmp_path_factory.mktemp("proxy")
    return {"out": _build(d, "proxy_counter.c"),
            "in": _build(d, "proxy_ticker.c")}


def test_register_handshake_names_plugin(proxy_so):
    cls = load_proxy_plugin(proxy_so["out"])
    # the PLUGIN names itself through the def struct — not the file
    assert cls.name == "gocounter"
    assert "demo output" in cls.description
    assert registry.create_output("gocounter") is not None


def test_output_reads_config_through_api_table(proxy_so, tmp_path):
    load_dso_plugin(proxy_so["out"])  # idempotent re-register
    sink = tmp_path / "sink.bin"
    ctx = flb.create(flush="50ms", grace="2")
    in_ffd = ctx.input("lib", tag="gotag")
    ctx.output("gocounter", match="*", path=str(sink))
    ctx.start()
    try:
        ctx.push(in_ffd, '{"hello": "proxy"}')
        ctx.flush_now()
        deadline = time.time() + 5
        while time.time() < deadline and not sink.exists():
            time.sleep(0.05)
    finally:
        ctx.stop()
    blob = sink.read_bytes()
    assert b"tag=gotag size=" in blob
    # the flush body is the raw msgpack chunk
    start = blob.index(b"\n") + 1
    payload = blob[start: blob.index(b"\nEXIT")]
    evs = decode_events(payload[: payload.rfind(b"\n") + 1]
                        if payload.endswith(b"\n") else payload)
    assert evs[0].body == {"hello": "proxy"}
    assert blob.endswith(b"EXIT\n")  # FLBPluginExit ran at stop


def test_output_init_failure_without_config(proxy_so):
    load_dso_plugin(proxy_so["out"])
    ins = registry.create_output("gocounter")
    ins.configure()
    with pytest.raises(RuntimeError, match="FLBPluginInit"):
        ins.plugin.init(ins, None)  # no 'path' property → FLB_ERROR


def test_input_collect_and_cleanup(proxy_so):
    import ctypes

    cls = load_proxy_plugin(proxy_so["in"])
    assert cls.name == "goticker"
    ctx = flb.create(flush="50ms", grace="2")
    ctx.input("goticker", tag="gi")
    got = []
    ctx.output("lib", match="gi", callback=lambda d, t: got.append(d))
    # fast ticks for the test
    ctx.engine.inputs[0].plugin.collect_interval = 0.1
    ctx.start()
    try:
        deadline = time.time() + 8
        while time.time() < deadline and not got:
            time.sleep(0.05)
    finally:
        ctx.stop()
    assert got, "proxy input produced no records"
    evs = decode_events(got[0])
    assert evs[0].body["msg"] == "tick"
    assert evs[0].body["n"] == 0
    # every malloc'd buffer went back through the cleanup callback
    dso = ctypes.CDLL(proxy_so["in"])
    assert dso.demo_cleanups() == dso.demo_ticks()
    assert dso.demo_ticks() >= 1


def test_api_table_matches_flb_api_header_layout(proxy_so, tmp_path,
                                                 monkeypatch):
    """round-5 advisor (high): struct flb_api's custom_* entries sit at the
    END (flb_api.h 'preserve ABI' comment). The demo output reads a
    property through custom_get_property (last pointer block) and calls
    output_log_check (slot 6) — a host table in flb_api.c assignment
    order hands back the wrong slots and this fails loudly."""
    monkeypatch.setenv("FBTPU_DSO_API_PROBE", "1")
    load_dso_plugin(proxy_so["out"])
    sink = tmp_path / "abi_sink.bin"
    ctx = flb.create(flush="50ms", grace="2")
    in_ffd = ctx.input("lib", tag="abi")
    ctx.output("gocounter", match="*", path=str(sink), banner="hdr-order")
    ctx.start()
    try:
        ctx.push(in_ffd, '{"k": 1}')
        ctx.flush_now()
        deadline = time.time() + 5
        while time.time() < deadline and not sink.exists():
            time.sleep(0.05)
    finally:
        ctx.stop()
    blob = sink.read_bytes()
    # banner via custom_get_property; logcheck=2 is output_log_check's
    # distinct host-side return — input_log_check (the neighbouring
    # slot in the buggy layout) returns 1, custom_log_check 3
    assert blob.startswith(b"banner=hdr-order logcheck=2\n"), blob[:80]


def test_input_api_entries_mid_table(proxy_so, monkeypatch):
    """goticker reads `start` via input_get_property (slot 1) and calls
    input_log_check (slot 5): both must hit their exact slots."""
    import ctypes

    monkeypatch.setenv("FBTPU_DSO_API_PROBE", "1")
    load_proxy_plugin(proxy_so["in"])
    from fluentbit_tpu.core.plugin import registry as reg

    ins = reg.create_input("goticker")
    ins.set("start", "41")
    ins.configure()
    ins.plugin.init(ins, None)
    dso = ctypes.CDLL(proxy_so["in"])
    assert dso.demo_ticks() == 41      # input_get_property("start")
    assert dso.demo_logcheck() == 1    # input_log_check's distinct value
    ins.plugin.exit()
