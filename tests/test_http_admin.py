"""Admin HTTP server + hot reload.

Reference: src/http_server api/v1 (health/metrics/uptime/plugins/
storage) + api/v2 (reload), src/flb_reload.c.
"""

import json
import os
import signal
import socket
import subprocess
import sys
import time

import pytest

import fluentbit_tpu as flb

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def http_get(port, path, method="GET"):
    s = socket.create_connection(("127.0.0.1", port), timeout=5)
    s.sendall(f"{method} {path} HTTP/1.1\r\nHost: x\r\n"
              f"Connection: close\r\n\r\n".encode())
    data = b""
    while True:
        b = s.recv(65536)
        if not b:
            break
        data += b
    s.close()
    head, _, body = data.partition(b"\r\n\r\n")
    status = int(head.split(b" ", 2)[1])
    return status, body


@pytest.fixture
def admin_ctx():
    ctx = flb.create(flush="50ms", grace="1", http_server="on", http_port="0")
    in_ffd = ctx.input("lib", tag="t")
    ctx.output("null", match="*")
    ctx.start()
    deadline = time.time() + 5
    while time.time() < deadline:
        srv = ctx.engine.admin_server
        if srv is not None and srv.bound_port:
            break
        time.sleep(0.02)
    yield ctx, ctx.engine.admin_server.bound_port, in_ffd
    ctx.stop()


def test_health_and_banner(admin_ctx):
    ctx, port, _ = admin_ctx
    assert http_get(port, "/api/v1/health") == (200, b"ok\n")
    status, body = http_get(port, "/")
    assert status == 200
    assert json.loads(body)["fluentbit_tpu"]["edition"] == "tpu-native"


def test_metrics_endpoints(admin_ctx):
    ctx, port, in_ffd = admin_ctx
    ctx.push(in_ffd, json.dumps({"x": 1}))
    ctx.flush_now()
    status, body = http_get(port, "/api/v1/metrics/prometheus")
    assert status == 200
    assert b'fluentbit_input_records_total{name="lib.0"} 1' in body
    status, body = http_get(port, "/api/v1/metrics")
    assert status == 200
    names = [m["name"] for m in json.loads(body)["metrics"]]
    assert "fluentbit_input_records_total" in names


def test_uptime_plugins_storage(admin_ctx):
    ctx, port, _ = admin_ctx
    status, body = http_get(port, "/api/v1/uptime")
    assert status == 200 and "uptime_sec" in json.loads(body)
    status, body = http_get(port, "/api/v1/plugins")
    assert json.loads(body)["inputs"] == ["lib.0"]
    status, body = http_get(port, "/api/v1/storage")
    assert status == 200 and "storage_layer" in json.loads(body)


def test_reload_api_get_and_unwired_post(admin_ctx):
    ctx, port, _ = admin_ctx
    status, body = http_get(port, "/api/v2/reload")
    assert status == 200
    assert json.loads(body)["hot_reload_count"] == 0
    status, _ = http_get(port, "/api/v2/reload", method="POST")
    assert status == 400  # no reload_callback wired in lib mode


def test_not_found(admin_ctx):
    ctx, port, _ = admin_ctx
    assert http_get(port, "/nope")[0] == 404


def test_cli_sighup_reload(tmp_path):
    """SIGHUP reloads the config in-process; the pipeline keeps working
    and /api/v2/reload reports the count."""
    conf = tmp_path / "p.conf"
    port = _free_port()
    conf.write_text(f"""
[SERVICE]
    Flush        0.1
    Grace        1
    Hot_Reload   on
    HTTP_Server  on
    HTTP_Port    {port}

[INPUT]
    Name  dummy
    Tag   t
    Rate  20

[OUTPUT]
    Name   file
    Match  t
    Path   {tmp_path}
    File   out.txt
""")
    env = dict(os.environ, JAX_PLATFORMS="cpu", PYTHONPATH=REPO)
    p = subprocess.Popen(
        [sys.executable, "-m", "fluentbit_tpu", "-c", str(conf)],
        env=env, cwd=REPO, stdout=subprocess.PIPE, stderr=subprocess.PIPE,
    )
    try:
        _wait_http(port)
        p.send_signal(signal.SIGHUP)
        deadline = time.time() + 20
        while time.time() < deadline:
            try:
                status, body = http_get(port, "/api/v2/reload")
                if json.loads(body).get("hot_reload_count") == 1:
                    break
            except OSError:
                pass
            time.sleep(0.1)
        else:
            raise AssertionError("reload not observed")
        # pipeline still flows after reload
        out = tmp_path / "out.txt"
        n0 = out.read_text().count("\n") if out.exists() else 0
        deadline = time.time() + 10
        while time.time() < deadline:
            if out.exists() and out.read_text().count("\n") > n0:
                break
            time.sleep(0.1)
        else:
            raise AssertionError("pipeline stalled after reload")
    finally:
        p.terminate()
        p.wait(timeout=15)


def _free_port():
    s = socket.socket()
    s.bind(("127.0.0.1", 0))
    port = s.getsockname()[1]
    s.close()
    return port


def _wait_http(port, timeout=20):
    deadline = time.time() + timeout
    while time.time() < deadline:
        try:
            if http_get(port, "/api/v1/health")[0] == 200:
                return
        except OSError:
            time.sleep(0.1)
    raise TimeoutError("admin server not up")


def test_cli_sighup_ignored_without_hot_reload(tmp_path):
    """SIGHUP must not kill a pipeline when hot_reload is off."""
    env = dict(os.environ, JAX_PLATFORMS="cpu", PYTHONPATH=REPO)
    p = subprocess.Popen(
        [sys.executable, "-m", "fluentbit_tpu",
         "-i", "dummy", "-o", "null", "-f", "0.1", "-g", "1"],
        env=env, cwd=REPO, stdout=subprocess.PIPE, stderr=subprocess.PIPE,
    )
    try:
        # the handlers are in place once it says so (a fixed sleep lost
        # the race with the imports on a loaded machine: the default
        # action of SIGHUP kills a process that has none yet)
        os.set_blocking(p.stderr.fileno(), False)
        said, deadline = b"", time.time() + 60
        while b" started (pid" not in said and p.poll() is None \
                and time.time() < deadline:
            said += p.stderr.read() or b""
            time.sleep(0.05)
        assert b" started (pid" in said, said
        p.send_signal(signal.SIGHUP)
        time.sleep(1.0)
        assert p.poll() is None, "process died on SIGHUP"
    finally:
        p.terminate()
        p.wait(timeout=15)


def test_cli_reload_with_broken_config_keeps_running(tmp_path):
    conf = tmp_path / "p.conf"
    port = _free_port()
    good = f"""
[SERVICE]
    Flush        0.1
    Grace        1
    Hot_Reload   on
    HTTP_Server  on
    HTTP_Port    {port}

[INPUT]
    Name  dummy
    Tag   t

[OUTPUT]
    Name   null
    Match  *
"""
    conf.write_text(good)
    env = dict(os.environ, JAX_PLATFORMS="cpu", PYTHONPATH=REPO)
    p = subprocess.Popen(
        [sys.executable, "-m", "fluentbit_tpu", "-c", str(conf)],
        env=env, cwd=REPO, stdout=subprocess.PIPE, stderr=subprocess.PIPE,
    )
    try:
        _wait_http(port)
        conf.write_text("garbage outside any section\n")
        p.send_signal(signal.SIGHUP)
        time.sleep(2.0)
        # the old pipeline survives a broken reload
        assert p.poll() is None
        assert http_get(port, "/api/v1/health")[0] == 200
        assert json.loads(
            http_get(port, "/api/v2/reload")[1]
        )["hot_reload_count"] == 0
    finally:
        p.terminate()
        p.wait(timeout=15)
