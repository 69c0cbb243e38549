"""Container-log records of a shared cluster for the grep-tenants
configuration, stdlib only.

Fifty tenant namespaces (``tenant-00`` … ``tenant-49``), as an edge's
``filter_kubernetes`` and ``filter_nest`` (lift) leave their records: six
flat string keys, ``log``, ``stream`` and four ``kubernetes_*``. Tenant
``t`` runs an application of family ``t mod 5`` (access log, logfmt,
JSON, klog, Java) whose noise is what rule ``t`` of
``configs/grep-tenants.conf`` drops. ``WITNESS[r]`` writes a line that
rule ``r`` matches; ``NORMAL[f]`` a line of family ``f`` that no rule
matches. The label is by construction and never runs a rule: a record is
kept iff its line is a normal one (``reference/grep-tenants.py`` holds
every distinct line to that with ``re``).

The mix is exact and only its order comes from the seed. In every block
of ``shift_every`` lines the tenants' shares are Zipf (``zipf_s``) over
their ranks, and the rank order moves on by ``shift_by`` tenants a block
(the hot set moves); a tenant's ``j``-th line of a block is noise three
times in ten. Every ``mid_every``-th line carries a payload that brings
``log`` to 257-500 bytes (the frame stages at L=512), every
``long_every``-th one to 600-2,000 (longer than ``tpu_max_record_len``:
an overflow row, label ``LONG``); every other ``log`` is 40-250 bytes.

``make(n, seed, params)`` → ``(records, labels)``; ``labels[i]`` bit 0:
the chain keeps the record, bit 1: an overflow row.
"""

import random

from wire import KEEP, LONG

TENANTS = 50
FAMILIES = 5
#: a tenant's j-th line of a block is noise where NOISE[j % 10]: three in
#: ten, and 7 (coprime to 10) spreads them, so that a tenant with a
#: handful of lines has its share too
NOISE = [(j * 7) % 10 < 3 for j in range(10)]
APPS = ("web", "orders", "checkout", "operator", "billing")  # by family
HEX = "0123456789abcdef"


def zipf_counts(total: int, ranks: int, s: float) -> list:
    """``total`` lines over ``ranks`` ranks, Zipf with exponent ``s``:
    whole numbers that add up, the remainder to the first ranks."""
    w = [(r + 1) ** -s for r in range(ranks)]
    counts = [int(total * x / sum(w)) for x in w]
    for r in range(total - sum(counts)):
        counts[r % ranks] += 1
    return counts


# ----------------------------------------------------------- the lines
# Every writer returns (head, tail, pre, suf): the line is head + tail,
# or head + pre + <hex payload> + suf + tail where the line carries one,
# the payload lying where neither this rule nor any other minds it.

def _hms(i: int) -> str:
    return f"13:{(i // 60) % 60:02d}:{i % 60:02d}"


def _iso(r, i):
    return f"2026-10-02T{_hms(i)}.{r.randrange(1000):03d}Z"


def _access(r, i, *, ip=None, method=None, path=None, proto="HTTP/1.1",
            status=None, size=None, ref='"-"', agent=None, rt=None,
            pad_in_path=True):
    ip = ip or f"10.{r.randrange(256)}.{r.randrange(256)}.{r.randrange(256)}"
    method = method or r.choice(("GET", "POST", "PUT", "DELETE", "PATCH"))
    path = path or (f"/api/v1/{r.choice(('orders', 'carts', 'users', 'items'))}"
                    f"/{r.randrange(100000)}")
    status = status or r.choice((200, 201, 400, 401, 403, 404, 500, 502, 503))
    size = r.randrange(100, 100000) if size is None else size
    agent = agent or r.choice(("Mozilla/5.0 (X11; Linux x86_64)",
                               "okhttp/4.12.0", "python-requests/2.31.0",
                               "Go-http-client/2.0"))
    rt = rt or f"0.{r.randrange(10, 1000):03d}"
    head = f'{ip} - - [02/Oct/2026:{_hms(i)} +0000] "{method} {path}'
    rest = f' {proto}" {status} {size} {ref} "{agent}"'
    if pad_in_path:
        return head, f"{rest} rt={rt}", "?cursor=", ""
    return head + rest, f" rt={rt}", " rid=", ""


def _logfmt(r, i, body, tail="", level="info", ts="ts"):
    return (f"{ts}={_iso(r, i)} level={level} {body}", tail, " blob=", "")


def _json(r, i, fields, tail="}", level="info"):
    return (f'{{"ts":"{_iso(r, i)}","level":"{level}",{fields}', tail,
            ',"payload":"', '"')


def _klog(r, i, src, msg, sev="I"):
    return (f"{sev}1002 {_hms(i)}.{r.randrange(1000000):06d} "
            f"{r.randrange(1, 30):7d} {src}.go:{r.randrange(20, 900)}] {msg}",
            "", ' patch="', '"')


def _java(r, i, level, thread, logger, msg):
    return (f"2026-10-02 {_hms(i)},{r.randrange(1000):03d} {level:5s} "
            f"[{thread}] {logger} - {msg}", "", " ctx=", "")


def _exec(r):
    return f"http-nio-8080-exec-{r.randrange(1, 11)}"


NORMAL = (
    lambda r, i: _access(r, i),
    lambda r, i: _logfmt(
        r, i, f"caller={r.choice(('orders', 'stock', 'mail'))}/handler.go:"
        f"{r.randrange(20, 400)} msg=\"{r.choice(('order created', 'payment captured', 'stock reserved', 'email queued'))}\""
        f" order_id={r.randrange(100000)} user=u{r.randrange(10000)}"
        f" status={r.choice((201, 400, 404, 409, 500))}",
        f" dur=0.{r.randrange(1000):03d}s",
        level=r.choice(("info", "info", "warn", "error"))),
    lambda r, i: _json(
        r, i, f'"logger":"{r.choice(("checkout.cart", "orders.api", "billing.invoice", "auth.session"))}",'
        f'"msg":"{r.choice(("item added", "order placed", "invoice issued", "session opened"))}",'
        f'"user_id":"u-{r.randrange(10000)}","sku":"A-{r.randrange(10000)}",'
        f'"status":{r.choice((201, 400, 404, 500))},'
        f'"duration_ms":{r.randrange(100, 1000)}',
        level=r.choice(("info", "info", "warn", "error"))),
    lambda r, i: _klog(
        r, i, r.choice(("controller", "deployment_controller", "replica_set",
                        "scale")),
        f"\"{r.choice(('Reconciled', 'Scaled up', 'Updated status', 'Created pod'))}\""
        f" deployment=\"shop/{r.choice(('cart', 'orders', 'web'))}\""
        f" generation={r.randrange(1, 500)}", sev=r.choice("IIIWE")),
    lambda r, i: _java(
        r, i, r.choice(("INFO", "INFO", "WARN", "ERROR")), _exec(r),
        f"c.s.{r.choice(('orders.OrderService', 'cart.CartService', 'pay.PaymentGateway'))}",
        f"{r.choice(('Created order', 'Updated cart', 'Captured payment'))} "
        f"{r.randrange(100000)} for customer {r.randrange(10000)}"),
)

WITNESS = (
    # 0-4
    lambda r, i: _access(r, i, method="GET", path="/health-check",
                         status=200),
    lambda r, i: _logfmt(r, i, f'caller=store/cache.go:{r.randrange(20, 400)} '
                         f'msg="lookup" key=k{r.randrange(10000)}',
                         level=r.choice(("debug", "trace"))),
    lambda r, i: _json(r, i, f'"logger":"orders.api","msg":"query plan",'
                       f'"rows":{r.randrange(1000)}',
                       level=r.choice(("debug", "trace"))),
    lambda r, i: _klog(r, i, r.choice(("reflector", "round_trippers",
                                       "request")),
                       f"GET https://10.96.0.1:443/api/v1/pods 200 OK in "
                       f"{r.randrange(1, 50)} milliseconds"),
    lambda r, i: _java(r, i, "TRACE", _exec(r), "c.s.orders.OrderMapper",
                       f"==> Parameters: {r.randrange(100000)}(Long)"),
    # 5-9
    lambda r, i: _access(r, i, method="GET", path="/api/v1/ping", status=200,
                         agent=f"kube-probe/1.{r.randrange(24, 32)}"),
    lambda r, i: _logfmt(
        r, i, f"msg=\"{r.choice(('health check', 'readiness probe', 'liveness probe'))}"
        f"{r.choice(('', ' ok', ' passed'))}\" peer=10.0.{r.randrange(256)}.1"),
    lambda r, i: (f'{{"timestamp":"{_iso(r, i)}","severity":"DEBUG",'
                  f'"message":"cache warm {r.randrange(1000)}"', "}",
                  ',"payload":"', '"'),
    lambda r, i: _klog(r, i, "leaderelection",
                       "successfully renewed lease shop/controller-leader"),
    lambda r, i: ("\tat com.shop.orders.",
                  f"OrderService.create(OrderService.java:{r.randrange(20, 900)})",
                  "g", "."),
    # 10-14
    lambda r, i: _access(r, i, method="GET", path="/", status=200,
                         agent="ELB-HealthChecker/2.0"),
    lambda r, i: _logfmt(
        r, i, f'msg="request completed" method=GET path=/api/v1/items '
        f'status={r.choice((200, 204, 304))} bytes={r.randrange(10000)}',
        f" duration={r.randrange(1, 900)}ms"),
    lambda r, i: _json(
        r, i, f"\"logger\":\"{r.choice(('org.apache.kafka', 'io.netty', 'com.zaxxer.hikari'))}"
        f"{r.choice(('.clients.NetworkClient', '.util.Pool', ''))}\","
        f'"msg":"connection {r.randrange(1000)} ready"'),
    lambda r, i: _klog(r, i, "controller",
                       f"\"{r.choice(('Starting sync', 'Finished syncing', 'Starting syncing', 'Finished sync'))}\""
                       f" key=\"shop/cart-{r.randrange(100)}\""),
    lambda r, i: _java(r, i, "DEBUG", _exec(r),
                       f"o.s.{r.choice(('web', 'jdbc', 'orm'))}.core.Template",
                       f"Executing prepared statement {r.randrange(1000)}"),
    # 15-19
    lambda r, i: _access(
        r, i, method=r.choice(("GET", "HEAD")),
        path=r.choice(("/healthz", "/readyz", "/livez")),
        proto=r.choice(("HTTP/1.0", "HTTP/1.1")), status=200,
        pad_in_path=False),
    lambda r, i: _logfmt(
        r, i, f"caller={r.choice(('grpc', 'http'))}/middleware.go:"
        f"{r.randrange(20, 400)} msg=\"request "
        f"{r.choice(('started', 'finished'))}\" id={r.randrange(100000)}"),
    lambda r, i: _json(
        r, i, f"\"logger\":\"cluster.gossip\",\"msg\":\""
        f"{r.choice(('heartbeat', 'keepalive', 'ping'))}\","
        f'"peer":"10.0.{r.randrange(256)}.{r.randrange(256)}"'),
    lambda r, i: _klog(
        r, i, "httplog", f"\"HTTP\" verb=\"{r.choice(('GET', 'WATCH', 'LIST'))}\""
        f" URI=\"/api/v1/pods\" latency=\"{r.randrange(1, 900)}ms\" resp=200"),
    lambda r, i: _java(
        r, i, "INFO", "kafka-coordinator-heartbeat-thread",
        f"o.a.k.clients.{r.choice(('consumer', 'producer'))}.internals.Fetcher",
        f"[Consumer clientId=shop-{r.randrange(10)}] Resetting offset"),
    # 20-24
    lambda r, i: _access(
        r, i, method="GET", path="/metrics", status=200,
        agent=f"Prometheus/2.{r.randrange(30, 54)}.{r.randrange(4)}",
        pad_in_path=False),
    lambda r, i: _logfmt(
        r, i, f"component={r.choice(('scheduler', 'reconciler', 'gc'))} "
        f"msg=\"{r.choice(('tick', 'sync', 'sweep'))} "
        f"{r.choice(('started', 'done'))}\" n={r.randrange(1000)}"),
    lambda r, i: _json(r, i, f'"logger":"audit.read","msg":"object read",'
                       f'"key":"bucket/{r.randrange(100000)}"'),
    lambda r, i: _klog(r, i, "garbagecollector",
                       f"\"Deleting item\" uid=\"{r.randrange(1 << 30):08x}\""),
    lambda r, i: _java(
        r, i, "INFO", "HikariPool-1 housekeeper", "c.z.h.pool.HikariPool",
        f"HikariPool-1 - {r.choice(('Pool stats', 'Fill pool', 'Before cleanup', 'After cleanup'))}"
        f" (total=10, active={r.randrange(10)}, idle={r.randrange(10)})"),
    # 25-29
    lambda r, i: _access(r, i, method="OPTIONS", status=204, size=0),
    lambda r, i: _logfmt(r, i, f"msg=\"cache {r.choice(('hit', 'miss'))}\" "
                         f"key=user:{r.randrange(100000)}"),
    lambda r, i: _json(
        r, i, f"\"logger\":\"http.access\",\"msg\":\"served\",\"path\":\"/"
        f"{r.choice(('healthz', 'readyz', 'metrics'))}\",\"code\":200"),
    lambda r, i: _klog(
        r, i, "throttle", f"Throttling request took "
        f"{r.choice(('1.', '12.', ''))}{r.randrange(1, 999)}"
        f"{r.choice(('s', 'ms'))}, request: GET:https://10.96.0.1:443/apis",
        sev="W"),
    lambda r, i: (f"{r.choice(('Caused by', 'Suppressed'))}: java.lang."
                  f"{r.choice(('IllegalStateException', 'OutOfMemoryError'))}"
                  f": cart {r.randrange(100000)} is locked", "", " ctx=", ""),
    # 30-34
    lambda r, i: _access(r, i, method="GET", path="/", status=200,
                         agent="GoogleHC/1.0"),
    lambda r, i: _logfmt(r, i, 'msg="retrying" err="upstream timeout"',
                         f" attempt={r.randrange(1, 4)} max=5", level="warn"),
    lambda r, i: _json(
        r, i, f'"logger":"http.dump","request_id":"{r.randrange(1 << 32):08x}-'
        f'{r.randrange(1 << 16):04x}-{r.randrange(1 << 16):04x}-'
        f'{r.randrange(1 << 16):04x}-{r.randrange(1 << 48):012x}",'
        f"\"msg\":\"{r.choice(('request', 'response'))} body\""),
    lambda r, i: _klog(
        r, i, "event", f"\"Event occurred\" object=\"shop/cart-"
        f"{r.randrange(100)}\" kind=\"Deployment\" type=\"Normal\" "
        f"reason=\"ScalingReplicaSet\""),
    lambda r, i: _java(
        r, i, "WARN", _exec(r), "c.s.client.HttpClient",
        f"call {r.randrange(100000)} failed: java.net.SocketTimeoutException:"
        f" {r.choice(('Read', 'connect'))} timed out"),
    # 35-39
    lambda r, i: _access(r, i, method="GET",
                         status=r.choice((301, 302, 307, 308)), size=0),
    lambda r, i: (f"t={_iso(r, i)} lvl=dbug msg=\"peer connected\" "
                  f"id={r.randrange(1 << 32):08x}", "", " blob=", ""),
    lambda r, i: _json(
        r, i, f'"logger":"http.access","msg":"served","status":'
        f'{r.choice((200, 204))},"duration_ms":{r.randrange(100)}'),
    lambda r, i: _klog(
        r, i, "trace", f"Trace[{r.randrange(1 << 30)}]: \"List\" "
        f"url:/api/v1/pods (total time: {r.randrange(500, 3000)}ms)"),
    lambda r, i: (
        f"2026-10-02T{_hms(i)}.{r.randrange(1000):03d}+0000: "
        f"{r.randrange(100000)}.{r.randrange(1000):03d}: [GC ("
        f"{r.choice(('Allocation Failure', 'G1 Evacuation Pause'))}) "
        f"{r.randrange(100, 999)}M->{r.randrange(10, 99)}M(2048M), "
        f"0.0{r.randrange(100, 999)} secs]", "", " ctx=", ""),
    # 40-44
    lambda r, i: _access(
        r, i, method="GET", path=f"/static/app-{r.randrange(1000)}."
        f"{r.choice(('css', 'js', 'png', 'ico', 'svg', 'woff2'))}",
        status=r.choice((200, 304)), pad_in_path=False),
    lambda r, i: _logfmt(r, i, f'msg="lease renewed" holder=node-'
                         f'{r.randrange(100)}'),
    lambda r, i: _json(
        r, i, f"\"logger\":\"cache.l2\",\"event\":\""
        f"{r.choice(('cache_hit', 'cache_miss', 'cache_evict'))}\","
        f'"key":"sku:{r.randrange(100000)}"'),
    lambda r, i: _klog(r, i, "cacher",
                       f"Forcing pods watcher close due to unresponsiveness:"
                       f" {r.randrange(1000)} events queued"),
    lambda r, i: _java(
        r, i, "INFO", f"scheduling-{r.randrange(1, 5)}",
        "c.s.cluster.Membership",
        f"{r.choice(('Heartbeat', 'heartbeat'))} sent to "
        f"{r.randrange(2, 9)} peers"),
    # 45-49
    lambda r, i: _access(r, i, method="GET", path="/", status=200,
                         agent=f"Blackbox Exporter/0.{r.randrange(20, 26)}.0"),
    lambda r, i: _logfmt(
        r, i, f"msg=\"finished unary call\" grpc.method="
        f"{r.choice(('Check', 'Watch'))} grpc.service=grpc.health.v1.Health"
        f" grpc.code=OK grpc.time_ms={r.randrange(100)}"),
    lambda r, i: (f'{{"ts":"{_iso(r, i)}","level":"warn","logger":'
                  f'"deprecation","msg":"field v{r.randrange(1, 4)} is going '
                  f'away"', "}", ',"payload":"', '"'),
    lambda r, i: _klog(
        r, i, "watcher", "watch chan error: etcdserver: mvcc: required "
        "revision has been compacted", sev="W"),
    lambda r, i: _java(
        r, i, "INFO", "main", "o.s.b.w.e.tomcat.TomcatWebServer",
        f"Tomcat started on port(s): {r.choice((8080, 8443, 9090))} (http)"),
)


# --------------------------------------------------------------- make

def make(n: int, seed: int, params: dict):
    rng = random.Random(seed)
    tenants = int(params.get("tenants", TENANTS))
    if tenants != len(WITNESS):
        raise ValueError(f"the drop list is written for {len(WITNESS)} "
                         f"tenants")
    block = int(params.get("shift_every", 65536))
    shift = int(params.get("shift_by", 16))
    every_mid = int(params.get("mid_every", 40))
    every_long = int(params.get("long_every", 1000))
    zipf_s = float(params.get("zipf_s", 1.1))

    # who writes each line and whether it is noise: exact a block, shuffled
    plan = []
    for b in range(-(-n // block)):
        size = min(block, n - b * block)
        part = [((rank + shift * b) % tenants, NOISE[j % len(NOISE)])
                for rank, c in enumerate(zipf_counts(size, tenants, zipf_s))
                for j in range(c)]
        rng.shuffle(part)
        plan += part

    records, labels = [], bytearray(n)
    for i, (t, noise) in enumerate(plan):
        replica = rng.randrange(1 + t % 4)
        app = APPS[t % FAMILIES]
        writer = WITNESS[t] if noise else NORMAL[t % FAMILIES]
        head, tail, pre, suf = writer(rng, i)
        long_line = i % every_long == every_long - 1
        if long_line or i % every_mid == every_mid - 1:
            want = rng.randrange(600, 2001) if long_line \
                else rng.randrange(257, 501)
            fill = max(1, want - len(head) - len(tail) - len(pre) - len(suf))
            line = (head + pre + "".join(rng.choices(HEX, k=fill)) + suf
                    + tail)
        else:
            line = head + tail
        records.append({
            "log": line,
            "stream": "stderr" if not noise and i % 8 == 0 else "stdout",
            "kubernetes_namespace_name": f"tenant-{t:02d}",
            "kubernetes_pod_name":
                f"{app}-{(t * 2654435761) % (1 << 36):09x}-{replica:x}k"
                f"{t % 7}q9",
            "kubernetes_container_name": app,
            "kubernetes_host": f"ip-10-0-{t}-{replica + 10}.ec2.internal",
        })
        labels[i] = (0 if noise else KEEP) | (LONG if long_line else 0)
    return records, bytes(labels)
