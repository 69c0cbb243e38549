"""Device sketches — HyperLogLog + count-min, the psum/pmax showcase.

The north-star additions over the reference's filter_log_to_metrics
(BASELINE.md config 4: "count-min/HLL cardinality" — the reference
supports only counter/gauge/histogram). Batches of field values are
hashed ON DEVICE (FNV-1a over the padded ``[B, L] uint8`` staging
layout, masked by lengths — one fused jit with the register updates),
and sketch state lives as device arrays:

- HLL: 2^p registers of max-rank; multi-device merge is ``lax.pmax``
  over the mesh axis (register-wise max IS the union of sketches).
- Count-min: ``[d, w]`` counters via Kirsch-Mitzenmacher double
  hashing; multi-device merge is ``lax.psum`` (counter sum IS the
  union).

Both merges ride ICI on a real mesh — sketches are the rare aggregate
whose distributed reduction is exact, which is why they are the chosen
showcase for the metrics-reduction contract (SURVEY §2.4).
"""

from __future__ import annotations

from typing import Optional, Tuple

import numpy as np

try:
    import jax
    import jax.numpy as jnp
    from jax import lax

    HAVE_JAX = True
except Exception:  # pragma: no cover
    HAVE_JAX = False

FNV_OFFSET = np.uint32(0x811C9DC5)
FNV_PRIME = np.uint32(0x01000193)


def _fnv1a_scan(batch, lengths):
    """FNV-1a 32-bit over valid bytes of each row: [B, L] u8 → [B] u32.

    Pad positions multiply by 1 (identity) so fixed shapes stay exact.
    """
    B, L = batch.shape
    pos = jnp.arange(L, dtype=jnp.int32)
    valid = pos[None, :] < lengths[:, None]  # [B, L]
    data = batch.astype(jnp.uint32)

    def step(h, xs):
        byte, ok = xs
        nh = (h ^ byte) * FNV_PRIME
        return jnp.where(ok, nh, h), None

    # ^ 0*lengths: ties the carry to the (possibly mesh-sharded) batch so
    # its varying-axes annotation matches the scan output under shard_map
    h0 = jnp.full((B,), FNV_OFFSET, dtype=jnp.uint32) ^ (
        lengths.astype(jnp.uint32) * 0
    )
    h, _ = lax.scan(step, h0, (data.T, valid.T))
    # FNV's high bits avalanche poorly; finalize so index bits (taken
    # from the top for HLL) are uniform
    return _mix(h)


def _mix(h):
    """murmur3 fmix32 — independent second hash for double hashing."""
    h = h ^ (h >> 16)
    h = h * np.uint32(0x85EBCA6B)
    h = h ^ (h >> 13)
    h = h * np.uint32(0xC2B2AE35)
    h = h ^ (h >> 16)
    return h


def hll_index_rank(batch, lengths, p: int):
    """Per-row HLL (register index, rank) over a staged batch — the
    hash/rank half of :meth:`HyperLogLog._update_impl`, factored out so
    the fused flux absorb program (flux/kernels.build_fused_absorb) can
    scatter into a *per-group* [Gp, m] register stack with the exact
    same math. Invalid rows (length < 0) get rank 0, which every
    scatter-max treats as a no-op."""
    h = _fnv1a_scan(batch, lengths)
    idx = (h >> np.uint32(32 - p)).astype(jnp.int32)
    rest = h << np.uint32(p)
    # clz via bit-smear + popcount (integer-exact, TPU-friendly)
    x = rest
    for s in (1, 2, 4, 8, 16):
        x = x | (x >> np.uint32(s))
    nlz = 32 - lax.population_count(x).astype(jnp.int32)
    # rank = leading zeros of the remaining (32-p) bits + 1; rest==0
    # (nlz 32) saturates at the max rank for a (32-p)-bit suffix
    rank = jnp.minimum(nlz + 1, 32 - p + 1)
    valid = lengths >= 0
    return idx, jnp.where(valid, rank, 0)


class HyperLogLog:
    """HLL over 32-bit hashes; registers jnp int32 [2^p]."""

    def __init__(self, p: int = 14):
        if not HAVE_JAX:
            raise RuntimeError("jax is unavailable")
        self.p = p
        self.m = 1 << p
        # registers start host-side (numpy) and move to the device when
        # the backend attaches — constructing a sketch must never block
        # on backend init (see ops.device); add_cpu is bit-identical to
        # the device kernel, so pre-attach updates stay exact
        self.registers = np.zeros((self.m,), dtype=np.int32)
        self._update = None

    def _device_jit(self, wait: bool = False):
        """The update jit if the backend is attached (built once),
        WITHOUT touching register state — safe from the fbtpu-armor
        watched worker threads (the only race is a benign
        double-assignment of an equivalent jit)."""
        if self._update is None:
            from . import device

            ok = device.wait(max(60.0, device.default_wait())) if wait \
                else device.ready()
            if not ok:
                if not wait:
                    device.attach_async()
                return None
            def hll_update(registers, batch, lengths):
                return self._update_impl(registers, batch, lengths)

            self._update = jax.jit(hll_update)
        return self._update

    def _ensure_device(self, wait: bool = False) -> bool:
        if self._device_jit(wait) is None:
            return False
        if isinstance(self.registers, np.ndarray):
            self.registers = jnp.asarray(self.registers)
        return True

    def _update_impl(self, registers, batch, lengths):
        idx, rank = hll_index_rank(batch, lengths, self.p)
        return registers.at[idx].max(rank)

    def device_registers(self, batch: np.ndarray, lengths: np.ndarray,
                         wait: bool = False, registers=None):
        """Compute the post-update register set on the device WITHOUT
        committing it or mutating ANY sketch state (None when the
        backend isn't attached yet). The fbtpu-armor flux lane runs
        this inside its watched launch from an explicit pre-launch
        ``registers`` snapshot and commits on the caller thread only
        after the launch resolves — a soft-killed (abandoned) launch
        computes into a discarded local and can never clobber
        registers a fallback or later batch already advanced."""
        fn = self._device_jit(wait)
        if fn is None:
            return None
        regs = self.registers if registers is None else registers
        return fn(jnp.asarray(regs), jnp.asarray(batch),
                  jnp.asarray(lengths))

    def update(self, batch: np.ndarray, lengths: np.ndarray) -> None:
        """Absorb a staged [B, L] batch (rows with length<0 ignored).
        Falls back to the bit-identical host twins while the device
        backend is still attaching — the C batch kernel when the native
        plane is loaded (fbtpu_hll_update; the flux ingest-rate path),
        else the Python per-row loop."""
        if self._ensure_device():
            self.registers = self._update(
                self.registers, jnp.asarray(batch), jnp.asarray(lengths)
            )
            return
        self.host_update(batch, lengths)

    def host_update(self, batch: np.ndarray, lengths: np.ndarray) -> None:
        """Host-pinned batch update — never touches the device backend.
        The C batch kernel (fbtpu_hll_update) when the native plane is
        loaded and the registers are still host-side, else the
        bit-identical Python per-row loop. The flux plane uses this
        directly when the attached backend IS the host CPU (the jit
        round trip loses to the C walk there)."""
        from .. import native as _native

        if isinstance(self.registers, np.ndarray) and _native.hll_update(
                self.registers, batch, lengths, self.p):
            return
        for i in range(batch.shape[0]):
            ln = int(lengths[i])
            if ln >= 0:
                self.add_cpu(batch[i, :ln].tobytes())

    def add_cpu(self, value: bytes) -> None:
        """Host-side single-value update (overflow-row fallback) — same
        hash/rank math as the device kernel."""
        h = int(_hash32_cpu(value))
        idx = h >> (32 - self.p)
        rest = (h << self.p) & 0xFFFFFFFF
        nlz = 32 - rest.bit_length()
        rank = min(nlz + 1, 32 - self.p + 1)
        if isinstance(self.registers, np.ndarray):
            self.registers[idx] = max(int(self.registers[idx]), rank)
        else:
            self.registers = self.registers.at[idx].max(rank)

    def merge_registers(self, other) -> None:
        if isinstance(self.registers, np.ndarray):
            self.registers = np.maximum(self.registers, np.asarray(other))
        else:
            self.registers = jnp.maximum(self.registers, other)

    def estimate(self) -> float:
        """Standard HLL estimator with small/large range corrections."""
        regs = np.asarray(self.registers)
        m = float(self.m)
        alpha = 0.7213 / (1.0 + 1.079 / m)
        e = alpha * m * m / np.sum(np.exp2(-regs.astype(np.float64)))
        if e <= 2.5 * m:
            v = int(np.sum(regs == 0))
            if v > 0:
                e = m * np.log(m / v)
        elif e > (1 << 32) / 30.0:
            e = -(2.0 ** 32) * np.log(1.0 - e / 2.0 ** 32)
        return float(e)


class CountMin:
    """Count-min sketch [d, w]; conservative point queries via row-min."""

    def __init__(self, depth: int = 4, width: int = 16384):
        if not HAVE_JAX:
            raise RuntimeError("jax is unavailable")
        self.depth = depth
        self.width = width
        # host-side until the backend attaches (see HyperLogLog); the
        # dtype matches what the device table will use so the CPU-pinned
        # path keeps the same overflow envelope
        self._dtype = (np.int64 if jax.config.jax_enable_x64
                       else np.int32)
        self.table = np.zeros((depth, width), dtype=self._dtype)
        self._update = None
        self._row_ids = np.arange(depth, dtype=np.uint32)

    def _device_jit(self, wait: bool = False):
        """Non-mutating jit accessor (see HyperLogLog._device_jit)."""
        if self._update is None:
            from . import device

            ok = device.wait(max(60.0, device.default_wait())) if wait \
                else device.ready()
            if not ok:
                if not wait:
                    device.attach_async()
                return None
            def cms_update(table, batch, lengths, weights):
                return self._update_impl(table, batch, lengths, weights)

            self._update = jax.jit(cms_update)
        return self._update

    def _ensure_device(self, wait: bool = False) -> bool:
        if self._device_jit(wait) is None:
            return False
        if isinstance(self.table, np.ndarray):
            self.table = jnp.asarray(self.table, dtype=self._dtype)
        return True

    def _hashes(self, batch, lengths):
        h1 = _fnv1a_scan(batch, lengths)
        h2 = _mix(h1) | np.uint32(1)  # odd → full-period double hashing
        rows = jnp.asarray(self._row_ids)[:, None]  # [d, 1]
        cols = (h1[None, :] + rows * h2[None, :]) % np.uint32(self.width)
        return cols.astype(jnp.int32)  # [d, B]

    def _update_impl(self, table, batch, lengths, weights):
        cols = self._hashes(batch, lengths)  # [d, B]
        valid = (lengths >= 0).astype(table.dtype) * weights.astype(table.dtype)
        d = self.depth

        def body(r, tb):
            return tb.at[r, cols[r]].add(valid)

        return lax.fori_loop(0, d, body, table)

    def device_table(self, batch: np.ndarray, lengths: np.ndarray,
                     weights: Optional[np.ndarray] = None,
                     wait: bool = False, table=None):
        """Compute the post-update table on the device WITHOUT
        committing or mutating any sketch state (None until attached)
        — the same snapshot-in/commit-on-finish protocol as
        :meth:`HyperLogLog.device_registers`."""
        fn = self._device_jit(wait)
        if fn is None:
            return None
        if weights is None:
            weights = np.ones((batch.shape[0],), dtype=np.int32)
        tbl = self.table if table is None else table
        return fn(
            jnp.asarray(tbl, dtype=self._dtype), jnp.asarray(batch),
            jnp.asarray(lengths), jnp.asarray(weights),
        )

    def update(self, batch: np.ndarray, lengths: np.ndarray,
               weights: Optional[np.ndarray] = None) -> None:
        B = batch.shape[0]
        unit_weights = weights is None
        if weights is None:
            weights = np.ones((B,), dtype=np.int32)
        if self._ensure_device():
            self.table = self._update(
                self.table, jnp.asarray(batch), jnp.asarray(lengths),
                jnp.asarray(weights),
            )
            return
        self.host_update(batch, lengths, weights if not unit_weights
                         else None)

    def host_update(self, batch: np.ndarray, lengths: np.ndarray,
                    weights: Optional[np.ndarray] = None) -> None:
        """Host-pinned batch update (see HyperLogLog.host_update): the C
        batch twin for the weight-1 shape, else the Python loop."""
        from .. import native as _native

        if weights is None and isinstance(self.table, np.ndarray) \
                and _native.cms_update(self.table, batch, lengths):
            return
        B = batch.shape[0]
        if weights is None:
            weights = np.ones((B,), dtype=np.int32)
        for i in range(B):
            ln = int(lengths[i])
            if ln >= 0:
                self.add_cpu(batch[i, :ln].tobytes(), int(weights[i]))

    def merge_table(self, other) -> None:
        if isinstance(self.table, np.ndarray):
            self.table = self.table + np.asarray(other)
        else:
            self.table = self.table + other

    def _cols_cpu(self, value: bytes):
        """Column per row for one value — bit-identical to the device
        kernel (uint32 wrap BEFORE the modulo)."""
        h1 = int(_hash32_cpu(value))
        h2 = int(_mix_np(np.uint32(h1))) | 1
        return [((h1 + r * h2) & 0xFFFFFFFF) % self.width
                for r in range(self.depth)]

    def add_cpu(self, value: bytes, weight: int = 1) -> None:
        """Host-side single-value update (overflow-row fallback)."""
        cols = self._cols_cpu(value)
        rows = np.arange(self.depth)
        if isinstance(self.table, np.ndarray):
            self.table[rows, np.asarray(cols)] += weight
        else:
            self.table = self.table.at[rows, np.asarray(cols)].add(weight)

    def query(self, value: bytes) -> int:
        """Point estimate for one value (row-min)."""
        table = np.asarray(self.table)
        return int(min(
            int(table[r, c]) for r, c in enumerate(self._cols_cpu(value))
        ))

    def query_many(self, values) -> list:
        """Point estimates for many values with ONE device→host table
        copy (per-value query() would sync the device each time)."""
        table = np.asarray(self.table)
        out = []
        for v in values:
            out.append(int(min(
                int(table[r, c]) for r, c in enumerate(self._cols_cpu(v))
            )))
        return out


def _hash32_cpu(value: bytes) -> np.uint32:
    """Finalized FNV-1a — bit-identical to _fnv1a_scan on the device."""
    h = int(FNV_OFFSET)
    for b in value:
        h = ((h ^ b) * int(FNV_PRIME)) & 0xFFFFFFFF
    return _mix_np(np.uint32(h))


def _mix_np(h: np.uint32) -> np.uint32:
    h = np.uint32(h)
    with np.errstate(over="ignore"):
        h ^= h >> np.uint32(16)
        h = np.uint32((int(h) * 0x85EBCA6B) & 0xFFFFFFFF)
        h ^= h >> np.uint32(13)
        h = np.uint32((int(h) * 0xC2B2AE35) & 0xFFFFFFFF)
        h ^= h >> np.uint32(16)
    return h


# -- multi-device (SPMD) sketch update: batch sharded, state merged --

def _mesh_key(mesh) -> tuple:
    """Structural cache key: equal meshes (same axes + devices) share a
    compiled step; keying by id(mesh) would miss every freshly
    constructed-but-identical Mesh and pin dead meshes forever.
    (Shared helper in ops.mesh — same key the grep/flux caches use.)"""
    from .mesh import mesh_key

    return mesh_key(mesh)


def _pad_to_mesh(mesh, batch, lengths):
    """Pad the batch axis up to the mesh size through the one shared
    helper (``ops.mesh.pad_to_devices``) — the call fbtpu-speccheck
    recognizes as discharging the B-divisibility obligation of the
    sharded in_specs below. Pad rows carry length -1 (invalid), so they
    contribute nothing to any sketch."""
    from .mesh import pad_to_devices

    n_dev = mesh.devices.size
    B = batch.shape[0]
    Bp = pad_to_devices(B, n_dev)
    if Bp != B:
        batch = np.concatenate(
            [batch, np.zeros((Bp - B, batch.shape[1]), dtype=batch.dtype)]
        )
        lengths = np.concatenate(
            [lengths, np.full((Bp - B,), -1, dtype=lengths.dtype)]
        )
    return batch, lengths


def build_sharded_hll(hll: HyperLogLog, mesh):
    """Compile the mesh HLL-update program: each device absorbs its
    batch shard into a full local register set (the ``registers`` state
    leaf rides the declarative ``flux-hll`` partition rule — an
    explicit replicate, not the implicit fallback), merged with
    lax.pmax (union of HLLs). Factored out of the dispatch wrapper so
    the fbtpu-speccheck static==dynamic crosscheck can ``lower()`` the
    exact shipped program on the simulated mesh."""
    from jax import shard_map
    from jax.sharding import PartitionSpec as P

    from .mesh import rule_spec

    axis = mesh.axis_names[0]
    regs_spec = rule_spec("flux-hll", axis, "registers")

    def step(regs, b, ln):
        local = hll._update_impl(regs, b, ln)
        return lax.pmax(local, axis_name=axis)

    step.__name__ = "hll_update_mesh"
    return jax.jit(shard_map(
        step, mesh=mesh,
        in_specs=(regs_spec, P(axis, None), P(axis)),
        out_specs=regs_spec,
    ))


def sharded_hll_registers(hll: HyperLogLog, mesh, batch: np.ndarray,
                          lengths: np.ndarray, registers=None):
    """Mesh update, WITHOUT committing or mutating any sketch state:
    runs the :func:`build_sharded_hll` program and returns the merged
    registers, computed from the explicit ``registers`` snapshot
    (default: the sketch's current set). The fbtpu-armor flux lane
    commits the result on the caller thread after the watched launch
    returns (see :meth:`HyperLogLog.device_registers`)."""
    from . import device

    if not device.wait(max(60.0, device.default_wait())):
        raise RuntimeError(
            f"device backend not attached: {device.status()}"
        )
    batch, lengths = _pad_to_mesh(mesh, batch, lengths)
    # cache the compiled step per mesh — a fresh jit(shard_map(...))
    # closure would recompile on every call
    cache = getattr(hll, "_sharded_cache", None)
    if cache is None:
        cache = hll._sharded_cache = {}
    fn = cache.get(_mesh_key(mesh))
    if fn is None:
        fn = build_sharded_hll(hll, mesh)
        cache[_mesh_key(mesh)] = fn
    regs = hll.registers if registers is None else registers
    return fn(jnp.asarray(regs), jnp.asarray(batch),
              jnp.asarray(lengths))


def sharded_hll_update(hll: HyperLogLog, mesh, batch: np.ndarray,
                       lengths: np.ndarray) -> None:
    """Compute-and-commit convenience over
    :func:`sharded_hll_registers` (bench / unguarded callers)."""
    merged = sharded_hll_registers(hll, mesh, batch, lengths)
    hll.registers = merged


def build_sharded_cms(cms: CountMin, mesh):
    """Compile the mesh count-min program: local scatter-adds over the
    batch shard, psum merge (the ``table`` state leaf rides the
    declarative ``flux-cms`` partition rule). Factored out of the
    dispatch wrapper for the fbtpu-speccheck lowering crosscheck, like
    :func:`build_sharded_hll`."""
    from jax import shard_map
    from jax.sharding import PartitionSpec as P

    from .mesh import rule_spec

    axis = mesh.axis_names[0]
    table_spec = rule_spec("flux-cms", axis, "table")

    def step(table, b, ln, w):
        # + 0*sum(w): ties the accumulator to the sharded batch so
        # the fori_loop carry's varying annotation stays consistent
        zero = jnp.zeros_like(table) + (0 * w.sum()).astype(table.dtype)
        local = cms._update_impl(zero, b, ln, w)
        return table + lax.psum(local, axis_name=axis)

    step.__name__ = "cms_update_mesh"
    return jax.jit(shard_map(
        step, mesh=mesh,
        in_specs=(table_spec, P(axis, None), P(axis), P(axis)),
        out_specs=table_spec,
    ))


def sharded_cms_table(cms: CountMin, mesh, batch: np.ndarray,
                      lengths: np.ndarray, table=None):
    """Count-min over a mesh, WITHOUT committing or mutating any
    sketch state: runs the :func:`build_sharded_cms` program and
    returns the merged table, computed from the explicit ``table``
    snapshot (snapshot-in/commit-on-finish protocol — see
    :func:`sharded_hll_registers`)."""
    from . import device

    if not device.wait(max(60.0, device.default_wait())):
        raise RuntimeError(
            f"device backend not attached: {device.status()}"
        )
    batch, lengths = _pad_to_mesh(mesh, batch, lengths)
    weights = np.ones((batch.shape[0],), dtype=np.int32)
    cache = getattr(cms, "_sharded_cache", None)
    if cache is None:
        cache = cms._sharded_cache = {}
    fn = cache.get(_mesh_key(mesh))
    if fn is None:
        fn = build_sharded_cms(cms, mesh)
        cache[_mesh_key(mesh)] = fn
    tbl = cms.table if table is None else table
    return fn(jnp.asarray(tbl, dtype=cms._dtype), jnp.asarray(batch),
              jnp.asarray(lengths), jnp.asarray(weights))


def sharded_cms_update(cms: CountMin, mesh, batch: np.ndarray,
                       lengths: np.ndarray) -> None:
    """Compute-and-commit convenience over
    :func:`sharded_cms_table`."""
    merged = sharded_cms_table(cms, mesh, batch, lengths)
    cms.table = merged
