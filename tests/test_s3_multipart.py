"""out_s3 multipart mode against a local S3 stub: create/upload-part/
complete sequencing, part boundaries at upload_chunk_size, restart
resume from fstore metadata, and drain completion (reference
plugins/out_s3/s3.c:82-123, s3_multipart.c)."""

import json
import os
import re
import socket
import threading
import time

import fluentbit_tpu as flb


class S3Stub:
    """Minimal multipart-aware S3 endpoint: answers ?uploads= with an
    UploadId, parts with an ETag header, and records everything."""

    def __init__(self):
        self.requests = []  # (method, path, body)
        self.upload_ids = 0
        self.srv = socket.socket()
        self.srv.bind(("127.0.0.1", 0))
        self.srv.listen(8)
        self.port = self.srv.getsockname()[1]
        threading.Thread(target=self._serve, daemon=True).start()

    def _serve(self):
        while True:
            try:
                c, _ = self.srv.accept()
            except OSError:
                return
            c.settimeout(3)
            try:
                data = b""
                while b"\r\n\r\n" not in data:
                    data += c.recv(65536)
                head, _, body = data.partition(b"\r\n\r\n")
                m = re.search(rb"Content-Length: (\d+)", head)
                cl = int(m.group(1)) if m else 0
                while len(body) < cl:
                    body += c.recv(65536)
                req = head.split(b"\r\n")[0].decode()
                method, path, _ = req.split(" ", 2)
                self.requests.append((method, path, body))
                if path.endswith("?uploads="):
                    self.upload_ids += 1
                    resp = (f"<InitiateMultipartUploadResult>"
                            f"<UploadId>UP{self.upload_ids}</UploadId>"
                            f"</InitiateMultipartUploadResult>").encode()
                    c.sendall(b"HTTP/1.1 200 OK\r\nContent-Length: "
                              + str(len(resp)).encode()
                              + b"\r\n\r\n" + resp)
                elif "partNumber=" in path:
                    n = re.search(r"partNumber=(\d+)", path).group(1)
                    c.sendall(b"HTTP/1.1 200 OK\r\nETag: \"etag-"
                              + n.encode()
                              + b"\"\r\nContent-Length: 0\r\n\r\n")
                else:
                    c.sendall(b"HTTP/1.1 200 OK\r\n"
                              b"Content-Length: 0\r\n\r\n")
            except OSError:
                pass
            c.close()

    def close(self):
        self.srv.close()

    def by_kind(self):
        creates = [r for r in self.requests if r[1].endswith("?uploads=")]
        parts = [r for r in self.requests if "partNumber=" in r[1]]
        completes = [r for r in self.requests
                     if "uploadId=" in r[1] and "partNumber" not in r[1]
                     and not r[1].endswith("?uploads=")]
        return creates, parts, completes


def run_pipeline(stub, store_dir, n_messages, msg_size=40, **extra):
    ctx = flb.create(flush="50ms", grace="3")
    in_ffd = ctx.input("lib", tag="app")
    ctx.output("s3", match="app", bucket="logs",
               endpoint=f"127.0.0.1:{stub.port}",
               use_put_object="off",
               store_dir=str(store_dir),
               s3_key_format="/mp/$TAG/obj", **extra)
    ctx.start()
    try:
        for i in range(n_messages):
            ctx.push(in_ffd, json.dumps({"i": i, "pad": "x" * msg_size}))
            ctx.flush_now()
        deadline = time.time() + 8
        while time.time() < deadline:
            creates, parts, completes = stub.by_kind()
            if completes:
                break
            time.sleep(0.05)
    finally:
        ctx.stop()
    return ctx


def test_multipart_create_part_complete(tmp_path, monkeypatch):
    monkeypatch.setenv("AWS_ACCESS_KEY_ID", "AK")
    monkeypatch.setenv("AWS_SECRET_ACCESS_KEY", "SK")
    stub = S3Stub()
    try:
        # ~55 bytes/record: chunk=128 → part every ~3 records;
        # total=384 → complete after ~2-3 parts
        run_pipeline(stub, tmp_path / "st", 12,
                     upload_chunk_size="128", total_file_size="384")
    finally:
        stub.close()
    creates, parts, completes = stub.by_kind()
    # reaching total_file_size completes an object; later records open
    # the next upload — every create must be matched by a complete
    assert creates and len(completes) == len(creates)
    assert creates[0][1] == "/logs/mp/app/obj?uploads="
    assert len(parts) >= 2
    # part numbers sequential from 1 WITHIN each upload
    by_upload = {}
    for p in parts:
        uid = re.search(r"uploadId=(\w+)", p[1]).group(1)
        by_upload.setdefault(uid, []).append(
            int(re.search(r"partNumber=(\d+)", p[1]).group(1)))
    for uid, nums in by_upload.items():
        assert nums == list(range(1, len(nums) + 1)), (uid, nums)
    # each complete's manifest lists exactly its upload's parts
    for _, path, body in completes:
        uid = re.search(r"uploadId=(\w+)", path).group(1)
        manifest = body.decode()
        for n in by_upload[uid]:
            assert f"<PartNumber>{n}</PartNumber>" in manifest
            assert f'"etag-{n}"' in manifest
    # every record delivered exactly once, in order, across all parts
    seen = []
    for _, _, body in parts:
        seen += [json.loads(l)["i"]
                 for l in body.decode().strip().splitlines()]
    assert seen == list(range(12))


def test_multipart_drain_completes_open_upload(tmp_path, monkeypatch):
    monkeypatch.setenv("AWS_ACCESS_KEY_ID", "AK")
    monkeypatch.setenv("AWS_SECRET_ACCESS_KEY", "SK")
    stub = S3Stub()
    ctx = flb.create(flush="50ms", grace="3")
    in_ffd = ctx.input("lib", tag="app")
    ctx.output("s3", match="app", bucket="logs",
               endpoint=f"127.0.0.1:{stub.port}",
               use_put_object="off",
               upload_chunk_size="64",
               total_file_size="100M",  # size trigger never fires
               store_dir=str(tmp_path / "st2"))
    ctx.start()
    try:
        for i in range(4):
            ctx.push(in_ffd, json.dumps({"i": i, "pad": "y" * 30}))
            ctx.flush_now()
        time.sleep(0.3)
    finally:
        ctx.stop()  # drain must upload the tail part AND complete
    stub.close()
    creates, parts, completes = stub.by_kind()
    assert len(creates) == 1
    assert parts, "no parts uploaded"
    assert len(completes) == 1
    seen = []
    for _, _, body in parts:
        seen += [json.loads(l)["i"]
                 for l in body.decode().strip().splitlines()]
    assert seen == list(range(4))


def test_multipart_restart_resumes_upload(tmp_path, monkeypatch):
    """Kill the pipeline mid-upload; a fresh instance over the same
    store_dir must resume the SAME UploadId and complete with all
    parts (s3.c get_upload/create_upload resume contract)."""
    monkeypatch.setenv("AWS_ACCESS_KEY_ID", "AK")
    monkeypatch.setenv("AWS_SECRET_ACCESS_KEY", "SK")
    stub = S3Stub()
    store = tmp_path / "st3"
    # phase 1: enough records for one part, then hard-stop (no drain
    # completion: simulate by NOT letting total_file_size trigger and
    # removing the drain via direct engine teardown)
    ctx = flb.create(flush="50ms", grace="3")
    in_ffd = ctx.input("lib", tag="app")
    ctx.output("s3", match="app", bucket="logs",
               endpoint=f"127.0.0.1:{stub.port}",
               use_put_object="off",
               upload_chunk_size="64", total_file_size="100M",
               store_dir=str(store), s3_key_format="/mp/$TAG/obj")
    ctx.start()
    try:
        for i in range(3):
            ctx.push(in_ffd, json.dumps({"i": i, "pad": "z" * 30}))
            ctx.flush_now()
        deadline = time.time() + 6
        while time.time() < deadline and not stub.by_kind()[1]:
            time.sleep(0.05)
        # simulate a crash: drop the drain hook so stop() leaves the
        # upload open with its fstore state on disk
        s3_plugin = ctx.engine.outputs[0].plugin
        s3_plugin.drain = lambda engine: None
    finally:
        ctx.stop()
    creates1, parts1, completes1 = stub.by_kind()
    assert len(creates1) == 1 and parts1 and not completes1
    # phase 2: new pipeline, same store_dir — push one more record and
    # stop; drain completes the RESUMED upload
    ctx2 = flb.create(flush="50ms", grace="3")
    in_ffd = ctx2.input("lib", tag="app")
    ctx2.output("s3", match="app", bucket="logs",
                endpoint=f"127.0.0.1:{stub.port}",
                use_put_object="off",
                upload_chunk_size="64", total_file_size="100M",
                store_dir=str(store), s3_key_format="/mp/$TAG/obj")
    ctx2.start()
    try:
        ctx2.push(in_ffd, json.dumps({"i": 99, "pad": "w" * 30}))
        ctx2.flush_now()
        time.sleep(0.3)
    finally:
        ctx2.stop()
    stub.close()
    creates, parts, completes = stub.by_kind()
    assert len(creates) == 1, "resume must NOT create a second upload"
    assert len(completes) == 1
    assert "uploadId=UP1" in completes[0][1]
    nums = [int(re.search(r"partNumber=(\d+)", p[1]).group(1))
            for p in parts]
    assert nums == list(range(1, len(parts) + 1))
    manifest = completes[0][2].decode()
    assert f"<PartNumber>{len(parts)}</PartNumber>" in manifest
    seen = []
    for _, _, body in parts:
        seen += [json.loads(l)["i"]
                 for l in body.decode().strip().splitlines()]
    assert seen == [0, 1, 2, 99]


def test_multipart_retry_redelivery_no_duplicate_staging(tmp_path,
                                                         monkeypatch):
    """round-5 advisor (medium): flush staged the chunk, the part upload
    failed (failpoint on the part-upload site), the engine redelivered
    the same chunk — staging must be idempotent: every record appears
    exactly once across the uploaded parts."""
    from fluentbit_tpu import failpoints

    monkeypatch.setenv("AWS_ACCESS_KEY_ID", "AK")
    monkeypatch.setenv("AWS_SECRET_ACCESS_KEY", "SK")
    failpoints.reset()
    failpoints.enable("s3.upload_part", "1*return(part-lost)")
    stub = S3Stub()
    ctx = flb.create(flush="50ms", grace="3")
    ctx.service_set(**{"scheduler.base": "0.05", "scheduler.cap": "0.1"})
    in_ffd = ctx.input("lib", tag="app")
    ctx.output("s3", match="app", bucket="logs",
               endpoint=f"127.0.0.1:{stub.port}",
               use_put_object="off",
               upload_chunk_size="64", total_file_size="100M",
               store_dir=str(tmp_path / "st4"),
               s3_key_format="/mp/$TAG/obj")
    ctx.start()
    try:
        # one chunk big enough to trip upload_chunk_size on its flush
        for i in range(3):
            ctx.push(in_ffd, json.dumps({"i": i, "pad": "q" * 30}))
        ctx.flush_now()
        deadline = time.time() + 8
        while time.time() < deadline and not stub.by_kind()[1]:
            time.sleep(0.05)
        time.sleep(0.3)
    finally:
        ctx.stop()
        failpoints.reset()
    stub.close()
    _creates, parts, _completes = stub.by_kind()
    assert parts, "the retried flush must eventually upload the part"
    seen = []
    for _, _, body in parts:
        seen += [json.loads(l)["i"]
                 for l in body.decode().strip().splitlines()]
    assert seen == list(range(3)), (
        f"RETRY redelivery duplicated staged records: {seen}")


def test_multipart_interleaved_chunk_then_retry_dedup(tmp_path,
                                                      monkeypatch):
    """A second chunk for the same tag flushing WHILE the first is in
    RETRY backoff must not defeat staging idempotence: the first
    chunk's redelivery still dedups (per-tag digest SET, not a single
    last-staged marker)."""
    from fluentbit_tpu import failpoints

    monkeypatch.setenv("AWS_ACCESS_KEY_ID", "AK")
    monkeypatch.setenv("AWS_SECRET_ACCESS_KEY", "SK")
    failpoints.reset()
    # first part-upload attempt fails; every later one succeeds
    failpoints.enable("s3.upload_part", "1*return(part-lost)")
    stub = S3Stub()
    ctx = flb.create(flush="40ms", grace="3")
    # slow retry: chunk B flushes (and uploads) while A is backing off
    ctx.service_set(**{"scheduler.base": "0.5", "scheduler.cap": "0.6"})
    in_ffd = ctx.input("lib", tag="app")
    ctx.output("s3", match="app", bucket="logs",
               endpoint=f"127.0.0.1:{stub.port}",
               use_put_object="off",
               upload_chunk_size="64", total_file_size="100M",
               store_dir=str(tmp_path / "st5"),
               s3_key_format="/mp/$TAG/obj")
    ctx.start()
    try:
        for i in range(3):  # chunk A: staged, part upload fails → RETRY
            ctx.push(in_ffd, json.dumps({"i": i, "pad": "a" * 30}))
        ctx.flush_now()
        time.sleep(0.15)  # A now parked in backoff
        for i in range(3, 6):  # chunk B: flushes while A backs off
            ctx.push(in_ffd, json.dumps({"i": i, "pad": "b" * 30}))
        ctx.flush_now()
        deadline = time.time() + 8
        while time.time() < deadline and len(stub.by_kind()[1]) < 1:
            time.sleep(0.05)
        time.sleep(1.2)  # let A's retry fire and settle
    finally:
        ctx.stop()
        failpoints.reset()
    stub.close()
    _creates, parts, _completes = stub.by_kind()
    seen = []
    for _, _, body in parts:
        seen += [json.loads(l)["i"]
                 for l in body.decode().strip().splitlines()]
    assert sorted(seen) == list(range(6)), (
        f"interleaved flush defeated staging idempotence: {sorted(seen)}")


def test_multipart_restart_redelivery_no_duplicate_staging(tmp_path,
                                                           monkeypatch):
    """The staged-digest map is persisted in the staging file's fstore
    meta: a filesystem-storage chunk redelivered after a hard restart
    must still dedup against the surviving staging file (in-memory
    tracking alone would resurrect the duplication across a crash)."""
    from fluentbit_tpu import failpoints

    monkeypatch.setenv("AWS_ACCESS_KEY_ID", "AK")
    monkeypatch.setenv("AWS_SECRET_ACCESS_KEY", "SK")
    failpoints.reset()
    stub = S3Stub()
    store = tmp_path / "st6"

    def make_ctx():
        c = flb.create(flush="50ms", grace="2",
                       **{"storage.path": str(tmp_path / "chunks")})
        c.service_set(**{"scheduler.base": "30", "scheduler.cap": "30"})
        ffd = c.input("lib", tag="app", **{"storage.type": "filesystem"})
        c.output("s3", match="app", bucket="logs",
                 endpoint=f"127.0.0.1:{stub.port}",
                 use_put_object="off", retry_limit="5",
                 upload_chunk_size="64", total_file_size="100M",
                 store_dir=str(store), s3_key_format="/mp/$TAG/obj")
        return c, ffd

    # phase 1: part upload fails after staging; hard-stop mid-backoff
    failpoints.enable("s3.upload_part", "return(down)")
    ctx, in_ffd = make_ctx()
    ctx.start()
    try:
        for i in range(3):
            ctx.push(in_ffd, json.dumps({"i": i, "pad": "r" * 30}))
        ctx.flush_now()  # stages + fails the part → RETRY parked 30 s
        ctx.engine.outputs[0].plugin.drain = lambda engine: None
    finally:
        ctx.engine.request_stop()
        ctx.stop()
    failpoints.reset()
    assert not stub.by_kind()[1], "phase 1 must not upload any part"

    # phase 2: restart recovers the chunk from disk and redelivers it
    ctx2, _ = make_ctx()
    ctx2.start()
    try:
        deadline = time.time() + 8
        while time.time() < deadline and not stub.by_kind()[1]:
            time.sleep(0.05)
        time.sleep(0.3)
    finally:
        ctx2.stop()
    stub.close()
    _creates, parts, _completes = stub.by_kind()
    assert parts, "restart redelivery must upload the staged part"
    seen = []
    for _, _, body in parts:
        seen += [json.loads(l)["i"]
                 for l in body.decode().strip().splitlines()]
    assert sorted(seen) == list(range(3)), (
        f"restart redelivery duplicated staged records: {sorted(seen)}")


def test_multipart_completed_object_then_retry_dedup(tmp_path,
                                                     monkeypatch):
    """A RETRY-parked chunk whose staged bytes were swept into an
    object that since COMPLETED (staging file deleted) must still
    dedup when its retry lands: the digest map lives in its own
    per-tag sidecar, not the staging file's meta."""
    from fluentbit_tpu import failpoints

    monkeypatch.setenv("AWS_ACCESS_KEY_ID", "AK")
    monkeypatch.setenv("AWS_SECRET_ACCESS_KEY", "SK")
    failpoints.reset()
    failpoints.enable("s3.upload_part", "1*return(part-lost)")
    stub = S3Stub()
    store = tmp_path / "st7"
    ctx = flb.create(flush="40ms", grace="3")
    ctx.service_set(**{"scheduler.base": "0.3", "scheduler.cap": "0.4"})
    in_ffd = ctx.input("lib", tag="app")
    # A (~165B) trips upload_chunk_size=64 and FAILS → RETRY; B's later
    # flush pushes the staged total past total_file_size=190 → final
    # part (carrying A+B) + complete + staging-file delete — all while
    # A is still parked in backoff
    ctx.output("s3", match="app", bucket="logs",
               endpoint=f"127.0.0.1:{stub.port}",
               use_put_object="off",
               upload_chunk_size="64", total_file_size="190",
               store_dir=str(store), s3_key_format="/mp/$TAG/obj")
    ctx.start()
    try:
        for i in range(3):  # chunk A
            ctx.push(in_ffd, json.dumps({"i": i, "pad": "c" * 30}))
        ctx.flush_now()
        time.sleep(0.1)
        ctx.push(in_ffd, json.dumps({"i": 3, "pad": "d" * 30}))  # chunk B
        ctx.flush_now()
        deadline = time.time() + 8
        while time.time() < deadline and not stub.by_kind()[2]:
            time.sleep(0.05)
        time.sleep(1.2)  # A's retry fires into the post-complete world
    finally:
        ctx.stop()
        failpoints.reset()
    stub.close()
    _creates, parts, completes = stub.by_kind()
    assert completes, "the object must have completed"
    seen = []
    for _, _, body in parts:
        seen += [json.loads(l)["i"]
                 for l in body.decode().strip().splitlines()]
    assert sorted(seen) == list(range(4)), (
        f"retry after object completion duplicated records: {sorted(seen)}")
    # nothing left staged: A's redelivery deduped instead of re-staging
    leftover = [f for f in os.listdir(store / "s3-s3.0") if
                not f.endswith(".meta")] if (store / "s3-s3.0").exists() \
        else []
    for name in leftover:
        assert os.path.getsize(store / "s3-s3.0" / name) == 0, (
            f"records re-staged after dedup should not exist: {name}")
