"""in_kafka — native Kafka consumer.

Reference: plugins/in_kafka/in_kafka.c (librdkafka consumer; record
shape in_kafka.c:55-130: {topic, partition, offset, error, key,
payload}). This build speaks the broker protocol directly: Metadata v1
→ ListOffsets v1 (initial position) → Fetch v4 polling, decoding
magic-v2 RecordBatches. With ``group_id`` set it runs the full
consumer-group protocol the way librdkafka's cgrp state machine does:
FindCoordinator → JoinGroup (range assignor computed by the elected
leader) → SyncGroup → committed-offset resume via OffsetFetch,
scheduled Heartbeats with rebalance-triggered rejoin, and
OffsetCommit after consumption. Without a group it is a simple
consumer reading every partition of the configured topics;
``initial_offset`` picks latest/earliest.
"""

from __future__ import annotations

import asyncio
import json
import logging
import time
from typing import Dict, List, Optional, Tuple

from ..codec.events import encode_event, now_event_time
from ..core.config import ConfigMapEntry
from ..core.plugin import InputPlugin, registry
from ..utils import kafka_protocol as kp

log = logging.getLogger("flb.in_kafka")


@registry.register
class KafkaInput(InputPlugin):
    name = "kafka"
    description = "Kafka consumer (native wire protocol + groups)"
    server_task_needed = True
    config_map = [
        ConfigMapEntry("brokers", "str", default="127.0.0.1:9092"),
        ConfigMapEntry("topics", "str"),
        ConfigMapEntry("poll_ms", "int", default=500),
        ConfigMapEntry("format", "str", default="none",
                       desc="none | json (parse payloads)"),
        ConfigMapEntry("initial_offset", "str", default="latest",
                       desc="latest | earliest"),
        ConfigMapEntry("client_id", "str", default="fluentbit-tpu"),
        ConfigMapEntry("group_id", "str",
                       desc="consumer group: coordinator discovery, "
                            "join/sync with the range assignor, "
                            "heartbeats, committed offsets"),
        ConfigMapEntry("session_timeout_ms", "int", default=10000),
    ]

    def init(self, instance, engine) -> None:
        if not self.topics:
            raise ValueError("in_kafka requires 'topics'")
        self._topics = [t.strip() for t in self.topics.split(",")
                        if t.strip()]
        self._brokers: List[Tuple[str, int]] = []
        for item in (self.brokers or "").split(","):
            item = item.strip()
            if item:
                host, _, port = item.partition(":")
                self._brokers.append((host, int(port or 9092)))
        if not self._brokers:
            raise ValueError("in_kafka: no brokers configured")
        self._offsets: Dict[Tuple[str, int], int] = {}
        self._expected_parts = 0
        self._corr = 0
        self._pools: Dict[Tuple[str, int], object] = {}
        # consumer-group state (librdkafka's cgrp state machine)
        self._member_id = ""
        self._generation = -1
        self._coordinator: Optional[Tuple[str, int]] = None
        self._assignment: Dict[str, List[int]] = {}
        self._last_heartbeat = 0.0
        self._hb_ok = time.monotonic()
        self._uncommitted = False
        # partitions whose COMMITTED offset came back trimmed
        # (OFFSET_OUT_OF_RANGE): resolution bypasses OffsetFetch
        self._oor: set = set()

    def _pool(self, addr):
        from ..core.upstream import Upstream

        pool = self._pools.get(addr)
        if pool is None:
            self._pools[addr] = pool = Upstream(
                self.instance, addr[0], addr[1], connect_timeout=10.0)
        return pool

    def exit(self) -> None:
        for pool in self._pools.values():
            pool.close()
        self._pools.clear()

    async def _rpc_to(self, addr, api: int, version: int,
                      body: bytes) -> bytes:
        """Pooled request/response against ONE broker address."""
        self._corr += 1
        corr = self._corr
        pool = self._pool(addr)
        reader, writer, _reused, uses = await pool.get()
        try:
            writer.write(kp.request(api, version, corr,
                                    self.client_id or "fbtpu", body))
            await asyncio.wait_for(writer.drain(), 10.0)
            raw = await asyncio.wait_for(reader.readexactly(4), 10.0)
            n = int.from_bytes(raw, "big")
            if n < 4 or n > 64 * 1024 * 1024:
                raise kp.KafkaProtocolError("bad response length")
            payload = await asyncio.wait_for(
                reader.readexactly(n), 15.0)
            got, rest = kp.parse_response_header(payload)
            if got != corr:
                raise kp.KafkaProtocolError("correlation mismatch")
        except (OSError, asyncio.TimeoutError,
                asyncio.IncompleteReadError, kp.KafkaProtocolError):
            pool.release(reader, writer, reusable=False)
            raise
        pool.release(reader, writer, reusable=True, use_count=uses)
        return rest

    async def _rpc(self, api: int, version: int, body: bytes) -> bytes:
        """_rpc_to over the bootstrap list (first reachable wins; the
        poll loop runs twice a second — per-RPC TCP churn would defeat
        the shared keepalive layer)."""
        last: Exception = OSError("no brokers reachable")
        for addr in self._brokers:
            try:
                return await self._rpc_to(addr, api, version, body)
            except (OSError, asyncio.TimeoutError,
                    asyncio.IncompleteReadError,
                    kp.KafkaProtocolError) as e:
                last = e
                continue
        raise last

    async def _bootstrap(self) -> bool:
        try:
            rest = await self._rpc(kp.API_METADATA, 1,
                                   kp.metadata_request(self._topics))
            _nodes, tops, errors = kp.parse_metadata_response(rest)
            for t, err in errors.items():
                log.warning("in_kafka: metadata error %d for %s", err, t)
            want: Dict[str, List[int]] = {
                t: sorted(parts) for t, parts in tops.items() if parts
            }
            if not want:
                return False
            ts = -2 if (self.initial_offset or "latest").lower() \
                == "earliest" else -1
            rest = await self._rpc(kp.API_LIST_OFFSETS, 1,
                                   kp.list_offsets_request(want, ts))
            for topic, pid, err, off in \
                    kp.parse_list_offsets_response(rest):
                if err == 0 and (topic, pid) not in self._offsets:
                    self._offsets[(topic, pid)] = off
            self._expected_parts = max(
                getattr(self, "_expected_parts", 0), len(self._offsets))
            return bool(self._offsets)
        except (OSError, asyncio.TimeoutError,
                kp.KafkaProtocolError) as e:
            log.debug("in_kafka bootstrap failed: %s", e)
            return False

    # -- consumer group (librdkafka cgrp state machine) ---------------

    def _reset_group(self, forget_member: bool = False) -> None:
        self._generation = -1
        self._assignment = {}
        self._offsets = {}
        # OFFSET_OUT_OF_RANGE markers must not survive a rebalance:
        # another member may have committed a VALID offset since, and a
        # stale marker would bypass OffsetFetch on reassignment and
        # reset the partition to latest/earliest (skipping or
        # duplicating records — round-5 advisor low)
        self._oor.clear()
        # fresh session: a stale pre-outage timestamp would turn the
        # FIRST transient heartbeat failure after rejoin into another
        # full reset (rebalance churn on flaky networks)
        self._hb_ok = time.monotonic()
        if forget_member:
            self._member_id = ""

    async def _group_bootstrap(self) -> bool:
        """FindCoordinator → JoinGroup → SyncGroup (leader runs the
        range assignor) → OffsetFetch/ListOffsets for the assignment."""
        try:
            # topic → partitions via metadata (the leader needs the
            # full partition map to assign)
            rest = await self._rpc(kp.API_METADATA, 1,
                                   kp.metadata_request(self._topics))
            _nodes, tops, _errors = kp.parse_metadata_response(rest)
            partitions = {t: sorted(p) for t, p in tops.items() if p}
            if not partitions:
                return False
            rest = await self._rpc(
                kp.API_FIND_COORDINATOR, 0,
                kp.find_coordinator_request(self.group_id))
            err, _node, host, port = \
                kp.parse_find_coordinator_response(rest)
            if err:
                log.warning("in_kafka: FindCoordinator error %d", err)
                return False
            self._coordinator = (host, port)
            rest = await self._rpc_to(
                self._coordinator, kp.API_JOIN_GROUP, 0,
                kp.join_group_request(self.group_id,
                                      int(self.session_timeout_ms),
                                      self._member_id, self._topics))
            err, gen, _proto, leader, member_id, members = \
                kp.parse_join_group_response(rest)
            if err == kp.ERR_UNKNOWN_MEMBER_ID:
                self._reset_group(forget_member=True)
                return False
            if err:
                log.warning("in_kafka: JoinGroup error %d", err)
                return False
            self._member_id = member_id
            self._generation = gen
            assignments = []
            if leader == member_id:
                plan = kp.range_assign(members, partitions)
                assignments = [(mid, kp.consumer_assignment(p))
                               for mid, p in plan.items()]
            rest = await self._rpc_to(
                self._coordinator, kp.API_SYNC_GROUP, 0,
                kp.sync_group_request(self.group_id, gen, member_id,
                                      assignments))
            err, blob = kp.parse_sync_group_response(rest)
            if err:
                log.warning("in_kafka: SyncGroup error %d", err)
                self._reset_group(
                    forget_member=(err == kp.ERR_UNKNOWN_MEMBER_ID))
                return False
            self._assignment = kp.parse_consumer_assignment(blob)
            if not self._assignment:
                log.info("in_kafka: empty assignment (generation %d)",
                         gen)
            self._offsets = {}
            await self._resolve_group_offsets()
            self._expected_parts = sum(
                len(p) for p in self._assignment.values())
            self._last_heartbeat = time.monotonic()
            log.info("in_kafka: joined group %r generation %d as %s "
                     "(%d partitions)", self.group_id, gen, member_id,
                     self._expected_parts)
            return True
        except (OSError, asyncio.TimeoutError,
                kp.KafkaProtocolError) as e:
            log.debug("in_kafka group bootstrap failed: %s", e)
            return False

    async def _resolve_group_offsets(self) -> None:
        """Committed offsets first; -1 (no commit) falls back to the
        configured initial_offset via ListOffsets. Only partitions
        with NO in-memory position are touched — consumed-but-not-yet-
        committed progress on healthy partitions must never be wound
        back to the committed offset (that re-emits duplicates)."""
        if not self._assignment:
            return
        missing: Dict[str, List[int]] = {}
        for topic, pids in self._assignment.items():
            for pid in pids:
                if (topic, pid) not in self._offsets:
                    missing.setdefault(topic, []).append(pid)
        if not missing:
            return
        # partitions whose committed offset was trimmed
        # (OFFSET_OUT_OF_RANGE) bypass OffsetFetch entirely
        oor_now = {tp for tp in self._oor
                   if tp[0] in missing and tp[1] in missing[tp[0]]}
        fetchable = {t: [p for p in ps if (t, p) not in oor_now]
                     for t, ps in missing.items()}
        fetchable = {t: ps for t, ps in fetchable.items() if ps}
        uncommitted: Dict[str, List[int]] = {}
        for topic, pid in oor_now:
            uncommitted.setdefault(topic, []).append(pid)
        if fetchable:
            rest = await self._rpc_to(
                self._coordinator, kp.API_OFFSET_FETCH, 1,
                kp.offset_fetch_request(self.group_id, fetchable))
            for topic, pid, off, err in \
                    kp.parse_offset_fetch_response(rest):
                if err == 0 and off >= 0:
                    self._offsets[(topic, pid)] = off
                else:
                    uncommitted.setdefault(topic, []).append(pid)
        if uncommitted:
            ts = -2 if (self.initial_offset or "latest").lower() \
                == "earliest" else -1
            rest = await self._rpc(kp.API_LIST_OFFSETS, 1,
                                   kp.list_offsets_request(uncommitted,
                                                           ts))
            for topic, pid, err, off in \
                    kp.parse_list_offsets_response(rest):
                if err == 0:
                    self._offsets[(topic, pid)] = off
                    if (topic, pid) in self._oor:
                        self._oor.discard((topic, pid))
                        # commit the reset position promptly so a
                        # rebalance doesn't hand the trimmed offset to
                        # another member
                        self._uncommitted = True

    async def _group_heartbeat_and_commit(self) -> bool:
        """Heartbeat on schedule + commit consumed offsets; returns
        False when the group must be rejoined (rebalance)."""
        now = time.monotonic()
        interval = max(1.0, int(self.session_timeout_ms) / 3000.0)
        if self._uncommitted and self._offsets:
            try:
                rest = await self._rpc_to(
                    self._coordinator, kp.API_OFFSET_COMMIT, 2,
                    kp.offset_commit_request(
                        self.group_id, self._generation,
                        self._member_id, self._offsets))
                errs = [e for _t, _p, e in
                        kp.parse_offset_commit_response(rest) if e]
                if any(e in (kp.ERR_ILLEGAL_GENERATION,
                             kp.ERR_REBALANCE_IN_PROGRESS,
                             kp.ERR_UNKNOWN_MEMBER_ID) for e in errs):
                    return False
                if errs:
                    # transient rejection (coordinator loading, ...):
                    # keep _uncommitted so the commit retries
                    log.debug("in_kafka commit errors: %s", errs)
                else:
                    self._uncommitted = False
            except (OSError, asyncio.TimeoutError,
                    kp.KafkaProtocolError) as e:
                log.debug("in_kafka commit failed: %s", e)
        if now - self._last_heartbeat < interval:
            return True
        self._last_heartbeat = now
        try:
            rest = await self._rpc_to(
                self._coordinator, kp.API_HEARTBEAT, 0,
                kp.heartbeat_request(self.group_id, self._generation,
                                     self._member_id))
            err = kp.parse_error_response(rest)
            if err in (kp.ERR_REBALANCE_IN_PROGRESS,
                       kp.ERR_ILLEGAL_GENERATION):
                log.info("in_kafka: rebalance signalled (%d)", err)
                return False
            if err == kp.ERR_UNKNOWN_MEMBER_ID:
                self._reset_group(forget_member=True)
                return False
            self._hb_ok = now
            return True
        except (OSError, asyncio.TimeoutError,
                kp.KafkaProtocolError) as e:
            log.debug("in_kafka heartbeat failed: %s", e)
            # transient failures tolerated only within the session
            # timeout: past it the broker has already evicted this
            # member and rebalanced its partitions elsewhere —
            # continuing to fetch makes a ZOMBIE consuming duplicates
            # it can never commit. Rejoin instead.
            session = max(1.0, int(self.session_timeout_ms) / 1000.0)
            if now - self._hb_ok >= session:
                log.info("in_kafka: no successful heartbeat for %.0fs "
                         "(session timeout) — rejoining group", session)
                self._reset_group(forget_member=True)
                return False
            return True  # transient: keep fetching, retry next tick

    def _emit(self, engine, topic: str, pid: int, base: int,
              records) -> int:
        out = bytearray()
        n = 0
        fmt = (self.format or "none").lower()
        for key, value, _ts, delta in records:
            if value is None:
                payload: object = None  # tombstone (compacted topics)
            else:
                payload = value.decode("utf-8", "replace")
                if fmt == "json":
                    try:
                        payload = json.loads(value)
                    except ValueError:
                        pass  # keep the raw string (reference keeps going)
            body = {
                "topic": topic,
                "partition": pid,
                "offset": base + delta,
                "error": None,
                "key": key.decode("utf-8", "replace")
                if key is not None else None,
                "payload": payload,
            }
            out += encode_event(body, now_event_time())
            n += 1
        if n:
            engine.input_log_append(self.instance, self.instance.tag,
                                    bytes(out), n)
        return n

    async def _leave_group(self) -> None:
        """Best-effort LeaveGroup so the coordinator reassigns this
        member's partitions immediately instead of after the session
        timeout (librdkafka does the same on clean close)."""
        if not (self.group_id and self._coordinator
                and self._member_id and self._generation >= 0):
            return
        try:
            await asyncio.wait_for(
                self._rpc_to(self._coordinator, kp.API_LEAVE_GROUP, 0,
                             kp.leave_group_request(self.group_id,
                                                    self._member_id)),
                1.0)
        except Exception as e:  # noqa: BLE001 — shutdown must not stall
            log.debug("leave_group at shutdown failed: %r", e)

    async def start_server(self, engine) -> None:
        try:
            await self._run(engine)
        except asyncio.CancelledError:
            await self._leave_group()
            raise

    async def _run(self, engine) -> None:
        poll = max(0.05, float(self.poll_ms or 500) / 1000.0)
        grouped = bool(self.group_id)
        if grouped:
            # the Fetch long-poll must stay well under the heartbeat
            # interval or the coordinator evicts the member mid-fetch
            poll = min(poll, max(0.05,
                                 int(self.session_timeout_ms) / 6000.0))
            while not await self._group_bootstrap():
                await asyncio.sleep(poll)
        else:
            while not await self._bootstrap():
                await asyncio.sleep(poll)
        while True:
            try:
                if grouped:
                    ok = await self._group_heartbeat_and_commit()
                    if not ok or self._generation < 0:
                        self._reset_group()
                        while not await self._group_bootstrap():
                            await asyncio.sleep(poll)
                        continue
                parts: Dict[str, List[Tuple[int, int]]] = {}
                for (topic, pid), off in self._offsets.items():
                    parts.setdefault(topic, []).append((pid, off))
                rest = await self._rpc(
                    kp.API_FETCH, 4,
                    kp.fetch_request(parts,
                                     max_wait_ms=int(poll * 1000)))
                got_any = False
                for topic, pid, err, _hw, record_set in \
                        kp.parse_fetch_response(rest):
                    if err:
                        log.warning("in_kafka fetch error %d on %s[%d]",
                                    err, topic, pid)
                        # stale leadership / trimmed offset: drop the
                        # position so the next bootstrap re-resolves it
                        # via Metadata + ListOffsets instead of
                        # re-fetching the same failure forever
                        self._offsets.pop((topic, pid), None)
                        if err == kp.ERR_OFFSET_OUT_OF_RANGE:
                            # the COMMITTED offset itself is trimmed:
                            # grouped-mode re-resolution must skip
                            # OffsetFetch (it would hand the same bad
                            # offset back forever) and go straight to
                            # ListOffsets
                            self._oor.add((topic, pid))
                        continue
                    for base, crc_ok, records, next_off in \
                            kp.iter_record_batches(record_set):
                        if not crc_ok:
                            log.warning("in_kafka: CRC mismatch on "
                                        "%s[%d]@%d", topic, pid, base)
                            continue
                        if self._emit(engine, topic, pid, base, records):
                            got_any = True
                        # honors lastOffsetDelta (compacted batches)
                        self._offsets[(topic, pid)] = next_off
                        if grouped:
                            self._uncommitted = True
                if not got_any:
                    await asyncio.sleep(poll)
                if len(self._offsets) < self._expected_parts:
                    # partitions dropped by fetch errors re-resolve:
                    # grouped mode re-reads committed offsets, the
                    # simple consumer re-runs Metadata + ListOffsets
                    if grouped:
                        try:
                            await self._resolve_group_offsets()
                        except (OSError, asyncio.TimeoutError,
                                kp.KafkaProtocolError):
                            pass
                    else:
                        await self._bootstrap()
            except asyncio.CancelledError:
                raise
            except (OSError, asyncio.TimeoutError,
                    kp.KafkaProtocolError) as e:
                log.debug("in_kafka poll failed: %s", e)
                await asyncio.sleep(poll)
