"""The plain reference of ``sketch-firehose``: the sketches as functions
of the events, written out in numpy from their description and
independent of the program's kernels and host twins.

- hash: FNV-1a 32 over the value's bytes, finalized with murmur3 fmix32;
- HyperLogLog(p): register ``h >> (32-p)`` holds the largest rank seen,
  rank = leading zeros of ``(h << p) mod 2**32`` + 1, at most 32-p+1;
- count-min(d, w): row r adds the weight at ``((h + r*h2) mod 2**32)
  mod w`` with ``h2 = fmix32(h) | 1``.

log_to_metrics keeps one HLL over ``user`` and one count-min over
``path``; filter_flux keeps per tenant a count and an HLL over ``user``,
and one count-min over ``tenant 0x1f path``. State at the end must equal
these over exactly the acked events (warm-up frames included: they were
absorbed too).
"""

import numpy as np

import wire

M32 = 0xFFFFFFFF


def fmix32(h):
    h = h ^ (h >> 16)
    h = (h * 0x85EBCA6B) & M32
    h = h ^ (h >> 13)
    h = (h * 0xC2B2AE35) & M32
    return h ^ (h >> 16)


def hash32(values: list) -> np.ndarray:
    """One hash per value (bytes), vectorized over the byte position."""
    n = len(values)
    width = max(map(len, values))
    flat = np.frombuffer(b"".join(v.ljust(width, b"\0") for v in values),
                         dtype=np.uint8).reshape(n, width).astype(np.uint64)
    lengths = np.fromiter(map(len, values), dtype=np.int64, count=n)
    h = np.full(n, 0x811C9DC5, dtype=np.uint64)
    for pos in range(width):
        nh = ((h ^ flat[:, pos]) * 0x01000193) & M32
        h = np.where(pos < lengths, nh, h)
    return fmix32(h)


def hashes_by_line(column: list):
    """Hash each distinct value once → one hash per line."""
    distinct = sorted(set(column))
    where = {v: i for i, v in enumerate(distinct)}
    h = hash32(distinct)
    return h[np.fromiter((where[v] for v in column), dtype=np.int64,
                         count=len(column))]


def hll_registers(h, seen, p: int) -> np.ndarray:
    h = h[seen]
    idx = (h >> (32 - p)).astype(np.int64)
    rest = (h << p) & M32
    bits = np.zeros(rest.shape, dtype=np.int64)
    nz = rest > 0
    bits[nz] = np.floor(np.log2(rest[nz].astype(np.float64))) + 1
    rank = np.minimum(32 - bits + 1, 32 - p + 1)
    regs = np.zeros(1 << p, dtype=np.int64)
    np.maximum.at(regs, idx, rank)
    return regs


def cms_table(h, counts, depth: int, width: int) -> np.ndarray:
    h2 = fmix32(h) | 1
    table = np.zeros((depth, width), dtype=np.int64)
    for r in range(depth):
        cols = (((h + r * h2) & M32) % width).astype(np.int64)
        np.add.at(table[r], cols, counts)
    return table


def on_platform(arr, platform: str) -> bool:
    devices = getattr(arr, "devices", None)
    return devices is not None and all(
        d.platform == platform for d in devices())


def checks(run: dict) -> dict:
    records = [wire.unpack_str_map(b) for b in run["bodies"]]
    counts = np.asarray(run["line_counts"], dtype=np.int64)
    seen = counts > 0
    users = [r["user"].encode() for r in records]
    paths = [r["path"].encode() for r in records]
    tenants = [r["tenant"].encode() for r in records]
    user_h, path_h = hashes_by_line(users), hashes_by_line(paths)
    comp_h = hashes_by_line([t + b"\x1f" + p for t, p in zip(tenants, paths)])

    plugins = run["pipe"].filters
    l2m_hll = next(p for p in plugins if getattr(p, "hll", None) is not None
                   and p.name == "log_to_metrics"
                   and p.mode == "cardinality")
    l2m_cms = next(p for p in plugins if p.name == "log_to_metrics"
                   and p.mode == "frequency")
    flux = next(p for p in plugins if p.name == "flux").state
    total = int(counts.sum())

    def same(got, want):
        return bool(np.array_equal(np.asarray(got).astype(np.int64), want))

    out = {
        "log_to_metrics_hll_equal_reference": same(
            l2m_hll.hll.registers,
            hll_registers(user_h, seen, l2m_hll.hll.p)),
        "log_to_metrics_cms_equal_reference": same(
            l2m_cms.cms.table,
            cms_table(path_h, counts, l2m_cms.cms.depth, l2m_cms.cms.width)),
        "flux_cms_equal_reference": same(
            flux.cms.table,
            cms_table(comp_h, counts, flux.cms.depth, flux.cms.width)),
        "flux_absorbed_every_acked_record": flux.records_total == total,
    }
    groups = dict(flux.live_groups())
    tenant_arr = np.asarray(tenants)
    state = [l2m_hll.hll.registers, l2m_cms.cms.table, flux.cms.table]
    for name in sorted(set(tenants)):
        g = groups.get((name,))
        mine = tenant_arr == name
        label = name.decode()
        out[f"flux_count_equal_reference.{label}"] = \
            g is not None and g.count == int(counts[mine].sum())
        out[f"flux_hll_equal_reference.{label}"] = g is not None and same(
            g.hlls["user"].registers,
            hll_registers(user_h, seen & mine, flux.spec.hll_p))
        if g is not None:
            state.append(g.hlls["user"].registers)

    from fluentbit_tpu.flux import kernels

    device = {
        "sketch_state_resident_on_the_device": all(
            on_platform(a, run["device"]["platform"]) for a in state),
        "fused_absorb_is_the_donating_one": bool(kernels._fused_cache)
        and all(k[5] for k in kernels._fused_cache),
    }
    skipped = []
    if run["rehearse"]:
        skipped = sorted(device)
    else:
        out.update(device)
    return {"checks": out, "skipped": skipped,
            "info": {"acked_events": total,
                     "distinct_users_seen": len({u for u, s in
                                                 zip(users, seen) if s}),
                     "groups": len(groups)}}
