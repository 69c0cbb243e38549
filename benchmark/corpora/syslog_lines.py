"""Syslog lines for the rewrite configuration, stdlib only.

RFC 3164 shape, ``Mon dd hh:mm:ss host prog[pid]: message`` (values of
about 60 to 200 bytes), in the mix a relay in front of a small fleet
sees, cut to what ``conf/baseline3-rewrite.conf``'s eight first-match
rules tell apart. In sixteenths: ``sshd`` 2; ``kernel:`` 2, half of them
naming the OOM killer (rule 2 wins over rule 8); ``systemd[1]`` 1; an
application's ``ERROR`` 1 and ``WARN`` 1; ``nginx`` 2, half of them
error-log lines that carry ``ERROR`` (rule 4 wins over rule 6);
``cron[pid]`` 1; ``OOM`` with no earlier rule's word 1; and 5 that no
rule matches (dhclient, postfix, NetworkManager, an application's
``INFO``). Every 20,000th line's message is long enough for the 512
length bucket and every 50,000th longer than ``tpu_max_record_len`` (an
overflow row), as ``grep_lines.py`` does. The mix is exact and only its
order comes from the seed, so every seed gives the filter the same work
in another order.

``make(n, seed, params)`` → ``(records, labels)``: ``records[i]`` is the
``{"log": line}`` record, ``labels[i]`` its construction label — bit 0
(``KEEP``): no rule matches, the record stays under its tag; bit 1
(``LONG``): longer than ``tpu_max_record_len``; bits 2-5: 1 + the index
of the winning rule in file order, 0 when none wins.
"""

import random

from wire import KEEP, LONG

RULE_SHIFT = 2      # labels[i] >> RULE_SHIFT == 1 + winning rule, or 0
MID_BYTES = 480     # a line of the 512 length bucket
LONG_BYTES = 880    # longer than tpu_max_record_len 512: an overflow row
MONTHS = ("Jan", "Feb", "Mar", "Apr", "May", "Jun", "Jul", "Aug", "Sep",
          "Oct", "Nov", "Dec")
USERS = ("deploy", "admin", "backup", "git", "ubuntu", "svc-build")
UNITS = ("docker.service", "ssh.service", "apt-daily.service",
         "logrotate.service", "fstrim.service", "user@1000.service")
APPS = ("orders-api", "billing", "inventory", "auth-gw", "search")
PATHS = ("/api/v1/orders", "/api/v1/cart", "/healthz", "/static/app.js",
         "/api/v1/users/me", "/login")
JOBS = ("/usr/local/bin/rotate-logs", "/usr/bin/backup --incremental",
        "/opt/jobs/report.sh daily", "/usr/sbin/tmpreaper 7d /tmp")


def winner(label: int) -> int:
    """The winning rule's index in a construction label, or -1."""
    return (label >> RULE_SHIFT) - 1


def _ip(rng) -> str:
    return "10.%d.%d.%d" % (rng.randrange(256), rng.randrange(256),
                            1 + rng.randrange(254))


def _sshd(rng):
    if rng.random() < 0.5:
        msg = ("Accepted publickey for %s from %s port %d ssh2: ED25519 "
               "SHA256:%032x" % (rng.choice(USERS), _ip(rng),
                                 1024 + rng.randrange(60000),
                                 rng.getrandbits(128)))
    else:
        msg = ("Failed password for invalid user %s from %s port %d ssh2"
               % (rng.choice(USERS), _ip(rng),
                  1024 + rng.randrange(60000)))
    return "sshd[%d]" % rng.randrange(1, 1 << 16), msg, 0


def _kernel(rng):
    msg = ("[%d.%06d] eth0: renamed from veth%07x, link becomes ready"
           % (rng.randrange(1 << 20), rng.randrange(10 ** 6),
              rng.getrandbits(28)))
    return "kernel", msg, 1


def _kernel_oom(rng):
    msg = ("[%d.%06d] Out of memory: OOM killer invoked, killed process "
           "%d (java) total-vm:%dkB, anon-rss:%dkB"
           % (rng.randrange(1 << 20), rng.randrange(10 ** 6),
              rng.randrange(1, 1 << 16), rng.randrange(1 << 24),
              rng.randrange(1 << 22)))
    return "kernel", msg, 1


def _systemd(rng):
    unit = rng.choice(UNITS)
    msg = rng.choice(("Started %s.", "Stopped %s.", "Reloading %s.",
                      "%s: Succeeded.")) % unit
    return "systemd[1]", msg + " (job %d)" % rng.randrange(1 << 20), 2


def _app(level: str, rule: int):
    def make(rng):
        msg = ("%s request_id=%016x route=%s status=%d latency_ms=%d "
               "upstream=%s" % (level, rng.getrandbits(64),
                                rng.choice(PATHS),
                                rng.choice((200, 404, 500, 503)),
                                rng.randrange(5000), _ip(rng)))
        return ("%s[%d]" % (rng.choice(APPS), rng.randrange(1, 1 << 16)),
                msg, rule)
    return make


def _nginx(rng):
    msg = ('%s - - "GET %s?id=%d HTTP/1.1" %d %d "-" "Mozilla/5.0 (X11; '
           'Linux x86_64)" rt=%d.%03d'
           % (_ip(rng), rng.choice(PATHS), rng.randrange(1 << 20),
              rng.choice((200, 301, 404)), rng.randrange(1 << 16),
              rng.randrange(3), rng.randrange(1000)))
    return "nginx[%d]" % rng.randrange(1, 1 << 16), msg, 5


def _nginx_error(rng):
    msg = ("ERROR %d#%d: *%d connect() failed (111: Connection refused) "
           "while connecting to upstream, client: %s, upstream: "
           "\"http://%s:8080%s\""
           % (rng.randrange(1, 1 << 16), rng.randrange(8),
              rng.randrange(1 << 20), _ip(rng), _ip(rng),
              rng.choice(PATHS)))
    return "nginx[%d]" % rng.randrange(1, 1 << 16), msg, 3


def _cron(rng):
    msg = "(%s) CMD (%s)" % (rng.choice(USERS), rng.choice(JOBS))
    return "cron[%d]" % rng.randrange(1, 1 << 16), msg, 6


def _oom(rng):
    msg = ("mem avail: %d of %d MiB (%d %%), OOM threshold reached, "
           "sending SIGTERM to pid %d"
           % (rng.randrange(512), 16384, rng.randrange(4),
              rng.randrange(1, 1 << 16)))
    return "earlyoom[%d]" % rng.randrange(1, 1 << 16), msg, 7


def _dhclient(rng):
    msg = ("DHCPACK of %s from %s, bound -- renewal in %d seconds."
           % (_ip(rng), _ip(rng), rng.randrange(86400)))
    return "dhclient[%d]" % rng.randrange(1, 1 << 16), msg, -1


def _postfix(rng):
    msg = ("%010X: to=<user%d@example.org>, relay=%s[%s]:25, delay=%d.%d, "
           "status=sent (250 2.0.0 Ok)"
           % (rng.getrandbits(40), rng.randrange(10000), "mx.example.org",
              _ip(rng), rng.randrange(30), rng.randrange(10)))
    return "postfix/smtp[%d]" % rng.randrange(1, 1 << 16), msg, -1


def _network_manager(rng):
    msg = ("<info>  [%d.%04d] device (eth%d): state change: ip-config -> "
           "ip-check (reason 'none', sys-iface-state: 'managed')"
           % (1700000000 + rng.randrange(1 << 24), rng.randrange(10000),
              rng.randrange(4)))
    return "NetworkManager[%d]" % rng.randrange(1, 1 << 16), msg, -1


#: the sixteen slots of the mix, in no particular order
KINDS = (_sshd, _sshd, _kernel, _kernel_oom, _systemd, _app("ERROR", 3),
         _app("WARN", 4), _nginx, _nginx_error, _cron, _oom, _dhclient,
         _postfix, _network_manager, _app("INFO", -1), _app("INFO", -1))


def make(n: int, seed: int, params: dict):
    rng = random.Random(seed)
    kind = [i % len(KINDS) for i in range(n)]
    rng.shuffle(kind)
    every_mid = int(params.get("bucket512_every", 20000))
    every_long = int(params.get("overflow_every", 50000))
    records, labels = [], bytearray(n)
    for i in range(n):
        prog, msg, rule = KINDS[kind[i]](rng)
        line = "%s %2d %02d:%02d:%02d ip-10-0-%d-%d %s: %s" % (
            MONTHS[i // 4096 % 12], 1 + i // 16384 % 28, i // 3600 % 24,
            i // 60 % 60, i % 60, rng.randrange(16), rng.randrange(256),
            prog, msg)
        long_line = i % every_long == every_long - 1
        if long_line:
            line += " trace=" + "x" * (LONG_BYTES - 7 - len(line))
        elif i % every_mid == every_mid - 1:
            line += " trace=" + "y" * (MID_BYTES - 7 - len(line))
        records.append({"log": line})
        labels[i] = (KEEP if rule < 0 else 0) \
            | (LONG if long_line else 0) | ((rule + 1) << RULE_SHIFT)
    return records, bytes(labels)
