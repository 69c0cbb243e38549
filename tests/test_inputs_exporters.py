"""node_exporter_metrics collectors + collectd binary protocol."""

import socket
import struct
import time

import pytest

import fluentbit_tpu as flb
from fluentbit_tpu.codec.events import decode_events
from fluentbit_tpu.codec.msgpack import Unpacker
from fluentbit_tpu.plugins.inputs_exporters import parse_collectd_packet


def test_node_exporter_collectors():
    ctx = flb.create(flush="50ms", grace="1")
    ctx.input("node_exporter_metrics", tag="node", scrape_interval="0.2")
    payloads = []
    ctx.output("lib", match="node", callback=lambda d, t: payloads.append(d))
    ctx.start()
    try:
        deadline = time.time() + 5
        while time.time() < deadline and not payloads:
            time.sleep(0.05)
    finally:
        ctx.stop()
    assert payloads
    obj = next(iter(Unpacker(payloads[0])))
    by_name = {m["name"]: m for m in obj["metrics"]}
    cpu = by_name["node_cpu_seconds_total"]
    assert cpu["type"] == "counter"
    assert cpu["labels"] == ["cpu", "mode"]
    modes = {s["labels"][1] for s in cpu["values"]}
    assert {"user", "system", "idle"} <= modes
    assert by_name["node_memory_MemTotal_bytes"]["values"][0]["value"] > 0
    assert "node_load1" in by_name
    assert by_name["node_uname_info"]["values"][0]["value"] == 1.0
    fs = by_name["node_filesystem_size_bytes"]
    assert fs["labels"] == ["device", "mountpoint", "fstype"]


def test_node_exporter_extended_collectors(tmp_path):
    """diskstats / vmstat / stat / filefd / cpufreq / hwmon / time /
    uptime / textfile against a synthetic procfs+sysfs tree
    (reference in_node_exporter_metrics/ne.c:34-49 collector set)."""
    proc = tmp_path / "proc"
    sys_ = tmp_path / "sys"
    (proc / "sys/fs").mkdir(parents=True)
    (proc / "diskstats").write_text(
        "   8  0 sda 100 0 2048 50 200 0 4096 80 0 30 1500\n"
        "   8  1 sda1 10 0 16 5 20 0 64 8 0 3 150\n")
    (proc / "vmstat").write_text(
        "nr_free_pages 100\npgpgin 555\npgpgout 666\npswpin 7\n"
        "pgfault 888\npgmajfault 99\noom_kill 2\n")
    (proc / "stat").write_text(
        "cpu  10 0 20 300 0 0 0 0\ncpu0 10 0 20 300 0 0 0 0\n"
        "intr 12345 1 2 3\nctxt 99999\nbtime 1700000000\n"
        "processes 4321\nprocs_running 3\nprocs_blocked 1\n")
    (proc / "sys/fs/file-nr").write_text("1234\t0\t809348\n")
    (proc / "uptime").write_text("5000.5 9000.0\n")
    cf = sys_ / "devices/system/cpu/cpu0/cpufreq"
    cf.mkdir(parents=True)
    (cf / "scaling_cur_freq").write_text("2200000\n")
    (cf / "scaling_min_freq").write_text("800000\n")
    (cf / "scaling_max_freq").write_text("3400000\n")
    hw = sys_ / "class/hwmon/hwmon0"
    hw.mkdir(parents=True)
    (hw / "name").write_text("coretemp\n")
    (hw / "temp1_input").write_text("45500\n")
    tfd = tmp_path / "textfile"
    tfd.mkdir()
    (tfd / "job.prom").write_text(
        "# HELP my_job_last_success Last success.\n"
        "# TYPE my_job_last_success gauge\n"
        "my_job_last_success 1700000001\n")

    from fluentbit_tpu.core.plugin import registry

    ins = registry.create_input("node_exporter_metrics")
    ins.set("path.procfs", str(proc))
    ins.set("path.sysfs", str(sys_))
    ins.set("collectors",
            "diskstats,vmstat,stat,filefd,cpufreq,hwmon,time,uptime")
    ins.set("textfile.directory", str(tfd))
    ins.configure()
    ins.plugin.init(ins, None)

    captured = {}

    class Eng:
        def input_event_append(self, instance, tag, data, etype,
                               n_records=1):
            captured["data"] = data
            captured["n"] = n_records

    ins.plugin.collect(Eng())
    obj = next(iter(Unpacker(captured["data"])))
    by_name = {m["name"]: m for m in obj["metrics"]}

    disk = by_name["node_disk_read_bytes_total"]
    vals = {tuple(s["labels"]): s["value"] for s in disk["values"]}
    assert vals[("sda",)] == 2048 * 512
    assert by_name["node_disk_io_time_seconds_total"]["values"][0][
        "value"] == pytest.approx(0.03)  # field 13 (ms doing I/O) / 1000
    assert by_name["node_vmstat_oom_kill"]["values"][0]["value"] == 2
    assert by_name["node_vmstat_pgfault"]["values"][0]["value"] == 888
    assert "node_vmstat_nr_free_pages" not in by_name  # filtered set
    assert by_name["node_context_switches_total"]["values"][0][
        "value"] == 99999
    assert by_name["node_forks_total"]["values"][0]["value"] == 4321
    assert by_name["node_procs_running"]["values"][0]["value"] == 3
    assert by_name["node_filefd_allocated"]["values"][0]["value"] == 1234
    assert by_name["node_filefd_maximum"]["values"][0]["value"] == 809348
    freq = by_name["node_cpu_scaling_frequency_hertz"]
    assert freq["values"][0]["value"] == 2200000 * 1000
    temp = by_name["node_hwmon_temp_celsius"]
    assert temp["values"][0]["labels"] == ["coretemp", "temp1"]
    assert temp["values"][0]["value"] == pytest.approx(45.5)
    assert by_name["node_uptime_seconds_total"]["values"][0][
        "value"] == pytest.approx(5000.5)
    assert by_name["node_time_seconds"]["values"][0]["value"] > 1e9
    assert by_name["my_job_last_success"]["values"][0][
        "value"] == 1700000001


def collectd_packet():
    def part_str(ptype, s):
        b = s.encode() + b"\x00"
        return struct.pack(">HH", ptype, 4 + len(b)) + b

    def part_u64(ptype, v):
        return struct.pack(">HHQ", ptype, 12, v)

    values = struct.pack(">HH", 0x0006, 4 + 2 + 2 * 9)  # 2 values
    values += struct.pack(">H", 2)
    values += bytes([1, 0])                  # gauge, counter
    values += struct.pack("<d", 36.5)        # gauge is little-endian
    values += struct.pack(">Q", 12345)       # counter is u64 BE
    return (part_str(0x0000, "web01")
            + part_u64(0x0008, int(1700000000 * (2 ** 30)))  # time_hr
            + part_str(0x0002, "cpu")
            + part_str(0x0003, "0")
            + part_str(0x0004, "cpu")
            + part_str(0x0005, "user")
            + values)


def test_parse_collectd_packet():
    records = parse_collectd_packet(collectd_packet())
    assert len(records) == 1
    r = records[0]
    assert r["host"] == "web01"
    assert r["plugin"] == "cpu" and r["plugin_instance"] == "0"
    assert r["type"] == "cpu" and r["type_instance"] == "user"
    assert r["values"] == [36.5, 12345]
    assert abs(r["time"] - 1700000000) < 1


def test_collectd_udp_pipeline():
    ctx = flb.create(flush="50ms", grace="1")
    ctx.input("collectd", tag="cd", port="0")
    ins = ctx.engine.inputs[0]
    got = []
    ctx.output("lib", match="cd", callback=lambda d, t: got.append(d))
    ctx.start()
    try:
        deadline = time.time() + 5
        while time.time() < deadline and not getattr(ins.plugin,
                                                     "bound_port", None):
            time.sleep(0.02)
        s = socket.socket(socket.AF_INET, socket.SOCK_DGRAM)
        s.sendto(collectd_packet(), ("127.0.0.1", ins.plugin.bound_port))
        s.close()
        deadline = time.time() + 5
        while time.time() < deadline and not got:
            time.sleep(0.05)
    finally:
        ctx.stop()
    ev = decode_events(got[0])[0]
    assert ev.body["host"] == "web01"
    assert ev.body["values"] == [36.5, 12345]
    assert abs(ev.ts_float - 1700000000) < 1
