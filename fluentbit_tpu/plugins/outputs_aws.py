"""AWS outputs: s3 (fstore-staged put-object + multipart uploads),
cloudwatch_logs.

Reference: plugins/out_s3 (6452 LoC — buffered uploads staged through
fstore, s3_key_format with $TAG/time expansion, use_put_object vs
multipart) and plugins/out_cloudwatch_logs (PutLogEvents API). Both
sign with SigV4 (utils.aws) using the env/profile credential chain.

Multipart mirrors s3.c:82-123 / s3_multipart.c: staged bytes reaching
``upload_chunk_size`` become an UploadPart on an upload created with
``POST ?uploads=`` (XML UploadId); reaching ``total_file_size`` or
``upload_timeout`` completes with the part manifest. Upload state
(UploadId + part ETags) persists in the staging file's fstore metadata,
so a restart RESUMES the open multipart upload instead of orphaning it
(get_upload/create_upload state machine, s3.c:82-123). ``endpoint``
points at any S3-compatible HTTP endpoint (path-style).
"""

from __future__ import annotations

import asyncio
import time
from typing import Dict, List, Optional, Tuple

from .. import failpoints as _fp
from ..codec.events import decode_events
from ..core.config import ConfigMapEntry
from ..core.fstore import FStore
from ..core.plugin import FlushResult, OutputPlugin, registry
from ..core.upstream import close_quietly
from ..utils import aws as _aws
from .outputs_basic import format_json_lines
from .outputs_http_based import _dumps


async def _http_request(ins, host: str, port: int, method: str, path: str,
                        headers: Dict[str, str], body: bytes,
                        timeout: float = 30.0, quote_path: bool = True,
                        use_tls: Optional[bool] = None) -> Tuple[int, bytes]:
    from urllib.parse import quote

    from ..core.tls import open_connection

    # honor the instance's tls.* properties (never plaintext when
    # `tls on`). SigV4 callers keep quote_path=True: the request line
    # must carry the SAME encoding the signature was computed over
    # (identical quote + safe set); Google-style method paths
    # (…/entries:write) pass quote_path=False and pre-safe paths.
    if quote_path:
        path = quote(path, safe="/-_.~")
    if use_tls:
        import asyncio as _aio
        import ssl as _ssl

        ctx = _ssl.create_default_context()
        reader, writer = await _aio.wait_for(
            _aio.open_connection(host, port, ssl=ctx), 10.0
        )
    else:
        reader, writer = await open_connection(ins, host, port, timeout=10.0)
    try:
        lines = [f"{method} {path} HTTP/1.1", f"Host: {host}:{port}",
                 f"Content-Length: {len(body)}", "Connection: close"]
        lines += [f"{k}: {v}" for k, v in headers.items()]
        if _fp.ACTIVE:
            # FailpointError is an OSError: callers' except clauses map
            # it to RETRY exactly like a real peer reset mid-request
            _fp.fire("upstream.send")
        writer.write(("\r\n".join(lines) + "\r\n\r\n").encode() + body)
        await asyncio.wait_for(writer.drain(), timeout)
        if _fp.ACTIVE:
            # the nastiest window: the request was SENT (the server may
            # have acted on it) but the response is lost — redelivery
            # after this fault is where duplication bugs live
            _fp.fire("upstream.recv")
        data = b""
        while True:
            chunk = await asyncio.wait_for(reader.read(65536), timeout)
            if not chunk:
                break
            data += chunk
        head, _, resp_body = data.partition(b"\r\n\r\n")
        status = int(head.split(b" ", 2)[1])
        return status, head, resp_body
    finally:
        close_quietly(writer)


@registry.register
class S3Output(OutputPlugin):
    name = "s3"
    description = "Amazon S3 (fstore-staged put-object uploads)"
    config_map = [
        ConfigMapEntry("bucket", "str"),
        ConfigMapEntry("region", "str", default="us-east-1"),
        ConfigMapEntry("endpoint", "str"),
        ConfigMapEntry("s3_key_format", "str",
                       default="/fluent-bit-logs/$TAG/%Y/%m/%d/%H_%M_%S"),
        ConfigMapEntry("total_file_size", "size", default="100M"),
        ConfigMapEntry("upload_chunk_size", "size", default="5242880"),
        ConfigMapEntry("upload_timeout", "time", default="10m"),
        ConfigMapEntry("store_dir", "str", default="/tmp/fluent-bit/s3"),
        ConfigMapEntry("use_put_object", "bool", default=True),
        ConfigMapEntry("compression", "str"),
    ]

    def init(self, instance, engine) -> None:
        if not self.bucket:
            raise ValueError("s3: bucket is required")
        algo = (self.compression or "").lower()
        if algo in ("gzip", "zstd"):
            from ..utils import compression_available
            if not compression_available(algo):
                raise ValueError(f"s3: {algo} codec unavailable on "
                                 "this host")
        if not self.use_put_object:
            # s3.c:1102-1126 sizing rules (5MB AWS minimum relaxed only
            # for explicitly tiny test endpoints via upload_chunk_size)
            if self.upload_chunk_size > self.total_file_size:
                raise ValueError(
                    "s3: upload_chunk_size cannot exceed total_file_size")
        self._fstore = FStore(self.store_dir)
        self._stream = self._fstore.stream(f"s3-{instance.name}")
        # staging idempotence across RETRY redelivery (round-5 advisor): the
        # engine redelivers the SAME chunk bytes after a failed part
        # upload / complete. A per-tag sidecar in its OWN stream
        # carries {digest: staged-at} for every staged-but-unacked
        # chunk: a map, not one marker (other chunks for the tag may
        # flush while one is backing off); PERSISTED (a
        # filesystem-storage chunk redelivered after a crash/restart
        # must still dedup); and OUTSIDE the staging file's meta (a
        # completed upload deletes the staging file, but a RETRY-parked
        # chunk whose bytes rode that object must still dedup when its
        # retry lands). Entries are removed when their flush resolves,
        # and expire after the engine's worst-case retry window so a
        # chunk dropped without a final flush call can never swallow a
        # later byte-identical chunk.
        self._staged_stream = self._fstore.stream(
            f"s3-{instance.name}-staged")
        self._opened: Dict[str, float] = {}  # tag → first-append time
        # staging + part sequencing is read-modify-write around an
        # await: concurrent flushes for one tag must serialize or parts
        # collide / staged bytes vanish (the engine runs one coroutine
        # per (task x route) with no semaphore by default)
        self._tag_locks: Dict[str, "asyncio.Lock"] = {}
        self._creds = _aws.get_credentials() or _aws.Credentials("", "")

    def _endpoint(self) -> Tuple[str, int]:
        ep = self.endpoint or f"s3.{self.region}.amazonaws.com"
        ep = ep.replace("http://", "").replace("https://", "")
        host, _, port = ep.partition(":")
        from ..core.tls import client_context

        default = 443 if client_context(self.instance) is not None else 80
        return host, int(port or default)

    def _key_for(self, tag: str) -> str:
        # strftime FIRST: a '%' inside the tag must never be read as a
        # time directive
        key = time.strftime(self.s3_key_format or "/", time.gmtime())
        key = key.replace("$TAG", tag)
        return key if key.startswith("/") else "/" + key

    async def _upload(self, tag: str, payload: bytes) -> FlushResult:
        algo = (self.compression or "").lower()
        if algo in ("gzip", "zstd"):  # reference out_s3 codecs
            from ..utils import compress

            payload = compress(algo, payload)
        host, port = self._endpoint()
        path = f"/{self.bucket}{self._key_for(tag)}"
        url = f"http://{host}:{port}{path}"
        self._creds = _aws.current(self._creds) or self._creds
        headers = _aws.sigv4_headers("PUT", url, self.region, "s3",
                                     payload, self._creds)
        try:
            status, _head, _body = await _http_request(self.instance, host,
                                                       port, "PUT", path,
                                                       headers, payload)
        except (OSError, asyncio.TimeoutError, ValueError, IndexError):
            return FlushResult.RETRY
        if 200 <= status < 300:
            return FlushResult.OK
        return FlushResult.RETRY if status >= 500 else FlushResult.ERROR

    # ------------------------------------------------------ multipart

    async def _s3_call(self, method: str, key: str, query: str,
                       payload: bytes) -> Tuple[int, bytes, bytes]:
        """One signed S3 request with a query string; returns
        (status, response head, response body)."""
        from urllib.parse import quote

        host, port = self._endpoint()
        raw_path = f"/{self.bucket}{key}"
        # sign over the RAW path: sigv4_headers percent-encodes it once
        # for the canonical request, and the wire path below applies the
        # SAME single encoding — pre-quoting here would double-encode
        # the signature side only (SignatureDoesNotMatch on any key
        # with a space or non-ASCII byte)
        url = f"http://{host}:{port}{raw_path}{query}"
        self._creds = _aws.current(self._creds) or self._creds
        headers = _aws.sigv4_headers(method, url, self.region, "s3",
                                     payload, self._creds)
        wire_path = quote(raw_path, safe="/-_.~") + query
        status, head, body = await _http_request(
            self.instance, host, port, method, wire_path, headers,
            payload, quote_path=False)
        return status, head, body

    async def _mp_create(self, key: str) -> Optional[str]:
        """CreateMultipartUpload (s3_multipart.c:558: POST ?uploads=);
        returns the UploadId."""
        status, _head, body = await self._s3_call("POST", key,
                                                  "?uploads=", b"")
        if not 200 <= status < 300:
            return None
        import re as _re

        m = _re.search(rb"<UploadId>([^<]+)</UploadId>", body)
        return m.group(1).decode() if m else None

    async def _mp_upload_part(self, key: str, upload_id: str, n: int,
                              payload: bytes) -> Optional[str]:
        """UploadPart (s3_multipart.c:685: PUT ?partNumber=N&uploadId=);
        returns the part's ETag."""
        if _fp.ACTIVE:
            try:
                _fp.fire("s3.upload_part")
            except _fp.FailpointError:
                return None  # part upload failed → flush returns RETRY
        status, head, _body = await self._s3_call(
            "PUT", key, f"?partNumber={n}&uploadId={upload_id}", payload)
        if not 200 <= status < 300:
            return None
        import re as _re

        m = _re.search(rb"(?im)^etag:\s*(\S+)\s*$", head)
        if m is None:
            # no ETag → the part cannot ever appear in a valid complete
            # manifest; fail the flush (RETRY) while the staged bytes
            # are still on disk
            return None
        return m.group(1).decode().strip('"')

    async def _mp_complete(self, key: str, upload_id: str,
                           parts: List[dict]) -> bool:
        """CompleteMultipartUpload (s3_multipart.c:405: POST ?uploadId=
        with the part manifest)."""
        if _fp.ACTIVE:
            try:
                _fp.fire("s3.complete")
            except _fp.FailpointError:
                # parts uploaded, completion lost: redelivery follows —
                # the round-5 advisor's duplication window in its pure form
                return False
        xml = ["<CompleteMultipartUpload>"]
        for p in parts:
            xml.append(
                f"<Part><PartNumber>{p['n']}</PartNumber>"
                f"<ETag>\"{p['etag']}\"</ETag></Part>")
        xml.append("</CompleteMultipartUpload>")
        status, _head, body = await self._s3_call(
            "POST", key, f"?uploadId={upload_id}",
            "".join(xml).encode())
        # a 200 body may still carry <Error> (S3 completes lazily)
        return 200 <= status < 300 and b"<Error>" not in body

    def _staged_ttl(self, engine) -> Optional[float]:
        """Upper bound on how long the engine can still redeliver one
        chunk: the summed worst-case capped backoff over the retry
        budget (x2 + slack for scheduling). None with unlimited
        retries — redelivery can then come arbitrarily late, and the
        engine never drops the chunk short of shutdown."""
        svc = getattr(engine, "service", None)
        if svc is None:
            return 600.0
        limit = self.instance.retry_limit
        if limit is None:
            limit = svc.retry_limit
        if limit == -1:
            return None
        total = 0.0
        for k in range(1, max(1, int(limit)) + 1):
            total += min(svc.scheduler_cap,
                         svc.scheduler_base * (2 ** k)) + 1.0
        return total * 2 + 60.0

    def _persist_staged(self, fname: str, sf, staged):
        """Write the staged-digest map's sidecar (delete it when the
        map empties); returns the current sidecar file or None."""
        if staged:
            sf = sf or self._staged_stream.create(fname)
            sf.set_meta(staged)
            return sf
        if sf is not None:
            sf.delete()
        return None

    def _mp_state(self, f) -> dict:
        st = f.meta()
        return st if st.get("upload_id") else {}

    async def _mp_flush_part(self, f, tag: str,
                             final: bool) -> FlushResult:
        """Upload the staged bytes as the next part; on final, complete
        the upload with the accumulated manifest."""
        st = self._mp_state(f)
        if not st:
            key = self._key_for(tag)
            upload_id = await self._mp_create(key)
            if upload_id is None:
                return FlushResult.RETRY
            st = {"upload_id": upload_id, "key": key, "parts": []}
            f.set_meta(st)
        payload = f.content()
        if payload:
            algo = (self.compression or "").lower()
            if algo in ("gzip", "zstd"):
                from ..utils import compress

                payload = compress(algo, payload)
            n = len(st["parts"]) + 1
            if n > 10000:  # hard S3 limit (s3.c:1688)
                return FlushResult.ERROR
            etag = await self._mp_upload_part(st["key"], st["upload_id"],
                                              n, payload)
            if etag is None:
                return FlushResult.RETRY
            st["parts"].append({"n": n, "etag": etag})
            # staged bytes are uploaded: restart the staging file but
            # KEEP the upload state (restart resume reads it back)
            name = f.name
            f.delete()
            f = self._stream.create(name)
            f.set_meta(st)
        if final:
            if not st["parts"]:
                f.delete()
                self._opened.pop(tag, None)
                return FlushResult.OK
            if not await self._mp_complete(st["key"], st["upload_id"],
                                           st["parts"]):
                return FlushResult.RETRY
            f.delete()
            self._opened.pop(tag, None)
        return FlushResult.OK

    async def flush(self, data: bytes, tag: str, engine) -> FlushResult:
        """Stage into fstore; upload when the buffer reaches
        total_file_size or upload_timeout elapses (out_s3's buffering
        contract — delivery is deferred, OK acknowledges staging). In
        multipart mode (use_put_object off) every upload_chunk_size of
        staged bytes becomes an UploadPart immediately."""
        from urllib.parse import quote as _q

        import hashlib

        lock = self._tag_locks.setdefault(tag, asyncio.Lock())
        async with lock:
            fname = _q(tag, safe="")  # reversible: no cross-tag collisions
            f = self._stream.get(fname) or self._stream.create(fname)
            digest = hashlib.sha256(data).hexdigest()
            sf = self._staged_stream.get(fname)
            staged = dict(sf.meta()) if sf is not None else {}
            staged_orig = dict(staged)
            ttl = self._staged_ttl(engine)
            now = time.time()  # wall clock: must survive a restart
            if ttl is not None and staged:
                staged = {d: ts for d, ts in staged.items()
                          if now - ts <= ttl}  # redelivery window over
            if digest not in staged:
                f.append(format_json_lines(data).encode() + b"\n")
                staged[digest] = now
            # else: RETRY redelivery (same process or post-restart) of
            # a chunk whose bytes are already staged — or already
            # uploaded, whether the object is still open or was since
            # completed — re-appending would duplicate the records.
            # Known tradeoff: identity is CONTENT (the flush ABI
            # carries no chunk id), so a genuinely new chunk that is
            # byte-identical — same records AND same event timestamps —
            # to one still parked in RETRY dedups against it; an
            # unbounded duplication bug is traded for that corner.
            if staged != staged_orig:
                # persist BEFORE the awaited upload: a crash during the
                # network call must not leave appended bytes with an
                # unrecorded digest (restart redelivery would re-append)
                sf = self._persist_staged(fname, sf, staged)
                staged_orig = dict(staged)
            self._opened.setdefault(tag, time.monotonic())
            timed_out = (time.monotonic() - self._opened[tag]
                         >= self.upload_timeout)
            if not self.use_put_object:
                st = self._mp_state(f)
                uploaded = (len(st.get("parts", []))
                            * self.upload_chunk_size)
                final = (uploaded + f.size >= self.total_file_size
                         or timed_out)
                if final or f.size >= self.upload_chunk_size:
                    res = await self._mp_flush_part(f, tag, final)
                else:
                    res = FlushResult.OK
            else:
                due = f.size >= self.total_file_size or timed_out
                if due:
                    payload = f.content()
                    res = await self._upload(tag, payload)
                    if res == FlushResult.OK:
                        f.delete()
                        self._opened.pop(tag, None)
                else:
                    res = FlushResult.OK
            if res != FlushResult.RETRY:
                # OK (acked — no redelivery coming) or ERROR (dropped —
                # no redelivery either): a future byte-identical chunk
                # is a NEW chunk and must stage
                staged.pop(digest, None)
            if staged != staged_orig:
                sf = self._persist_staged(fname, sf, staged)
            return res

    def drain(self, engine) -> None:
        """Shutdown: upload everything still staged (completing any open
        multipart uploads). Runs on the engine loop (the _main drain
        phase); the futures join the pending set so the grace period
        waits for them."""
        if getattr(engine, "loop", None) is None:
            return
        from urllib.parse import unquote as _uq

        for f in self._stream.files():
            tag = _uq(f.name)
            lock = self._tag_locks.setdefault(tag, asyncio.Lock())
            if not self.use_put_object:
                if not f.size and not self._mp_state(f):
                    continue

                async def _final_mp(tag=tag, f=f, lock=lock):
                    async with lock:
                        await self._mp_flush_part(f, tag, final=True)

                fut = asyncio.ensure_future(_final_mp())
            else:
                if not f.size:
                    continue

                async def _final(tag=tag, f=f, lock=lock):
                    async with lock:
                        payload = f.content()
                        if payload and await self._upload(
                                tag, payload) == FlushResult.OK:
                            f.delete()

                fut = asyncio.ensure_future(_final())
            engine._pending_flushes.add(fut)
            fut.add_done_callback(engine._pending_flushes.discard)


@registry.register
class CloudwatchLogsOutput(OutputPlugin):
    name = "cloudwatch_logs"
    description = "Amazon CloudWatch Logs (PutLogEvents)"
    config_map = [
        ConfigMapEntry("log_group_name", "str"),
        ConfigMapEntry("log_stream_name", "str"),
        ConfigMapEntry("region", "str", default="us-east-1"),
        ConfigMapEntry("endpoint", "str"),
        ConfigMapEntry("log_key", "str"),
    ]

    def init(self, instance, engine) -> None:
        if not self.log_group_name or not self.log_stream_name:
            raise ValueError(
                "cloudwatch_logs: log_group_name + log_stream_name required"
            )
        self._creds = _aws.get_credentials() or _aws.Credentials("", "")

    def format(self, data: bytes, tag: str) -> bytes:
        events = []
        for ev in decode_events(data):
            if self.log_key and isinstance(ev.body, dict):
                msg = str(ev.body.get(self.log_key, ""))
            else:
                msg = _dumps(ev.body)
            events.append({"timestamp": int(ev.ts_float * 1000),
                           "message": msg})
        return _dumps({
            "logGroupName": self.log_group_name,
            "logStreamName": self.log_stream_name,
            "logEvents": events,
        }).encode()

    async def flush(self, data: bytes, tag: str, engine) -> FlushResult:
        body = self.format(data, tag)
        ep = self.endpoint or f"logs.{self.region}.amazonaws.com"
        ep = ep.replace("http://", "").replace("https://", "")
        host, _, port = ep.partition(":")
        port = int(port or 80)
        url = f"http://{host}:{port}/"
        self._creds = _aws.current(self._creds) or self._creds
        extra = {"X-Amz-Target": "Logs_20140328.PutLogEvents",
                 "Content-Type": "application/x-amz-json-1.1"}
        headers = _aws.sigv4_headers("POST", url, self.region, "logs",
                                     body, self._creds, headers=extra)
        headers.update(extra)
        try:
            status, _h, _b = await _http_request(self.instance, host, port,
                                                 "POST", "/", headers, body)
        except (OSError, asyncio.TimeoutError, ValueError, IndexError):
            return FlushResult.RETRY
        if 200 <= status < 300:
            return FlushResult.OK
        return FlushResult.RETRY if status >= 500 else FlushResult.ERROR
