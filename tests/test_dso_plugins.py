"""Dynamic (.so) plugin loading — the flb_plugin.c role — with the
C++ demo plugins built live by g++ against native/fbtpu_plugin.h.
Reference: src/flb_plugin.c:200-326, plugins/out_zig_demo (the
native-language plugin proof)."""

import os
import subprocess
import time

import pytest

import fluentbit_tpu as flb
from fluentbit_tpu.codec.events import decode_events
from fluentbit_tpu.core.dso import load_dso_plugin, plugin_stem

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def _build(tmp_path, src_name):
    src = os.path.join(REPO, "native", "demo_plugins", src_name)
    out = str(tmp_path / (src_name.replace(".cpp", "") + ".so"))
    subprocess.run(
        ["g++", "-shared", "-fPIC", "-O2",
         "-I", os.path.join(REPO, "native"), "-o", out, src],
        check=True, capture_output=True)
    return out


@pytest.fixture(scope="module")
def demo_so(tmp_path_factory):
    d = tmp_path_factory.mktemp("dso")
    return {"out": _build(d, "out_demo.cpp"),
            "in": _build(d, "in_demo.cpp")}


def test_stem_derivation():
    assert plugin_stem("/x/out_demo.so") == "out_demo"
    assert plugin_stem("flb-in_foo.so") == "in_foo"


def test_load_rejects_bad_objects(tmp_path, demo_so):
    import shutil

    # stem without an in_/out_ prefix and no proxy register export
    weird = str(tmp_path / "weird.so")
    shutil.copy(demo_so["out"], weird)
    with pytest.raises(ValueError, match="FLBPluginRegister"):
        load_dso_plugin(weird)
    # wrong symbol name for the stem
    bad = str(tmp_path / "out_nosuch.so")
    shutil.copy(demo_so["out"], bad)
    with pytest.raises(ValueError, match="registration structure"):
        load_dso_plugin(bad)
    # missing file
    with pytest.raises(ValueError, match="cannot load"):
        load_dso_plugin(str(tmp_path / "out_absent.so"))


def test_native_output_flush(tmp_path, demo_so):
    load_dso_plugin(demo_so["out"])
    sink = tmp_path / "sink.txt"
    ctx = flb.create(flush="50ms", grace="1")
    ctx.input("dummy", tag="t", dummy='{"k": 1}', rate="20",
              samples="3")
    ctx.output("native_demo", match="*", path=str(sink))
    ctx.start()
    try:
        deadline = time.time() + 5
        while (not sink.exists() or not sink.read_text()) and \
                time.time() < deadline:
            time.sleep(0.05)
        time.sleep(0.2)
    finally:
        ctx.stop()
    lines = sink.read_text().strip().splitlines()
    assert lines and all(ln.startswith("t ") for ln in lines)
    total_bytes = sum(int(ln.split()[1]) for ln in lines)
    assert total_bytes > 0


def test_native_input_emits_records(tmp_path, demo_so):
    load_dso_plugin(demo_so["in"])
    got = []
    ctx = flb.create(flush="50ms", grace="1")
    ctx.input("native_demo", tag="nat", copies="2")
    ctx.output("lib", match="*",
               callback=lambda d, tag: got.extend(decode_events(d)))
    ctx.start()
    try:
        deadline = time.time() + 5
        while len(got) < 4 and time.time() < deadline:
            time.sleep(0.02)
    finally:
        ctx.stop()
    assert len(got) >= 4
    assert got[0].body["source"] == "native"
    ns = [ev.body["n"] for ev in got[:4]]
    assert ns == sorted(ns)  # counter increments across collects


def test_cli_dash_e_and_plugins_section(tmp_path, demo_so):
    """-e flag AND a [PLUGINS] path both register the plugin in a
    fresh process; records flow through the native output."""
    sink = tmp_path / "cli_sink.txt"
    conf = tmp_path / "p.conf"
    conf.write_text(f"""
[SERVICE]
    flush 0.05
    grace 1

[PLUGINS]
    path {demo_so['out']}

[INPUT]
    name dummy
    tag cli
    rate 20
    samples 2

[OUTPUT]
    name native_demo
    match *
    path {sink}
""")
    proc = subprocess.Popen(
        ["python", "-m", "fluentbit_tpu", "-c", str(conf)],
        cwd=REPO, stdout=subprocess.DEVNULL,
        stderr=subprocess.DEVNULL)
    try:
        deadline = time.time() + 20
        while time.time() < deadline:
            if sink.exists() and sink.read_text().strip():
                break
            time.sleep(0.1)
    finally:
        proc.terminate()
        proc.wait(timeout=15)
    assert sink.exists() and sink.read_text().startswith("cli ")


def test_yaml_plugins_key_loads_dso(tmp_path, demo_so):
    sink = tmp_path / "yaml_sink.txt"
    conf = tmp_path / "p.yaml"
    conf.write_text(f"""
service:
  flush: 0.05
  grace: 1
plugins:
  - {demo_so['out']}
pipeline:
  inputs:
    - name: dummy
      tag: y
      rate: 20
      samples: 2
  outputs:
    - name: native_demo
      match: "*"
      path: {sink}
""")
    proc = subprocess.Popen(
        ["python", "-m", "fluentbit_tpu", "-c", str(conf)],
        cwd=REPO, stdout=subprocess.DEVNULL,
        stderr=subprocess.DEVNULL)
    try:
        deadline = time.time() + 20
        while time.time() < deadline:
            if sink.exists() and sink.read_text().strip():
                break
            time.sleep(0.1)
    finally:
        proc.terminate()
        proc.wait(timeout=15)
    assert sink.exists() and sink.read_text().startswith("y ")


def test_rejected_object_never_mapped(tmp_path):
    """round-5 advisor: objects without a registration export must be
    rejected BEFORE dlopen — their constructors must never run. The
    probe reads the ELF dynsym instead of loading the object."""
    import subprocess
    import sys

    marker = tmp_path / "ctor_ran"
    src = tmp_path / "evil.c"
    src.write_text(
        '#include <stdio.h>\n'
        '__attribute__((constructor)) static void boom(void) {\n'
        f'    FILE *f = fopen("{marker}", "w");\n'
        '    if (f) { fputs("ran", f); fclose(f); }\n'
        '}\n'
        'int some_unrelated_export(void) { return 1; }\n')
    so = tmp_path / "evil.so"
    subprocess.run(["gcc", "-shared", "-fPIC", "-o", str(so), str(src)],
                   check=True, capture_output=True)
    with pytest.raises(ValueError, match="never ran"):
        load_dso_plugin(str(so))
    assert not marker.exists(), \
        "rejected object's constructor executed (it was dlopen'd)"
    # same invariant for a misnamed in-house object
    so2 = tmp_path / "out_evil.so"
    import shutil

    shutil.copy(str(so), str(so2))
    with pytest.raises(ValueError, match="registration structure"):
        load_dso_plugin(str(so2))
    assert not marker.exists()


def test_elf_probe_finds_real_exports(tmp_path, demo_so):
    from fluentbit_tpu.core.dso import elf_has_export

    assert elf_has_export(demo_so["out"], {"out_demo_plugin"}) is True
    assert elf_has_export(demo_so["out"], {"FLBPluginRegister"}) is False
    # non-ELF input → undecidable (falls back to dlopen-and-check)
    txt = tmp_path / "not_elf.so"
    txt.write_bytes(b"definitely not an object file")
    assert elf_has_export(str(txt), {"x"}) is None


def test_probe_rejects_undefined_reference(tmp_path):
    """An object that merely REFERENCES FLBPluginRegister (undefined
    import in .dynsym) must still be rejected pre-dlopen — only a
    DEFINED export passes the probe."""
    import subprocess

    marker = tmp_path / "ref_ctor_ran"
    src = tmp_path / "ref.c"
    src.write_text(
        '#include <stdio.h>\n'
        'extern int FLBPluginRegister(void *);\n'
        '__attribute__((constructor)) static void boom(void) {\n'
        f'    FILE *f = fopen("{marker}", "w");\n'
        '    if (f) { fputs("ran", f); fclose(f); }\n'
        '}\n'
        'int call_it(void *d) { return FLBPluginRegister(d); }\n')
    so = tmp_path / "ref.so"
    subprocess.run(["gcc", "-shared", "-fPIC", "-o", str(so), str(src)],
                   check=True, capture_output=True)
    from fluentbit_tpu.core.dso import elf_has_export

    assert elf_has_export(str(so), {"FLBPluginRegister"}) is False
    assert elf_has_export(str(so), {"call_it"}) is True
    with pytest.raises(ValueError, match="never ran"):
        load_dso_plugin(str(so))
    assert not marker.exists()
