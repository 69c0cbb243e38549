"""Device kernels for the flux plane — segment reductions + mesh merge.

The window-aggregate counterpart of ``ops/sketch.py``: per-batch group
counts run as a scatter-add kernel over the segment-id column, and the
multi-chip merge is ``lax.psum`` over the mesh axis (integer counter sum
IS the union, the same exactness argument as the count-min merge).
Counts are integers end to end, so the device/mesh result is
bit-identical to the host ``np.bincount`` twin — which is what lets the
simulated-mesh lane assert equality in tier-1 on every PR.

Float sums/mins/maxs deliberately do NOT run here: the exact Python
evaluation path accumulates IEEE doubles in record order, and the CPU
jax backend is float32 without ``jax_enable_x64`` — flux keeps those
host-side (``flux/state.py``) so sketch-eligible SQL stays bit-exact.
See FLUX.md "exactness model".
"""

from __future__ import annotations

from typing import Optional

import numpy as np

try:
    import jax
    import jax.numpy as jnp

    HAVE_JAX = True
except Exception:  # pragma: no cover - jax absent: host twins only
    HAVE_JAX = False

__all__ = ["flux_mesh", "segment_counts", "sharded_segment_counts",
           "host_segment_counts", "guarded_segment_counts",
           "build_sharded_counts", "build_fused_absorb",
           "sharded_fused_absorb", "fused_absorb"]

#: compiled-kernel caches, keyed by padded segment count (and mesh
#: structure for the sharded variant) — a fresh jit per call would
#: recompile every batch
_jit_cache: dict = {}
_shard_cache: dict = {}


def _pad_segments(n_seg: int) -> int:
    """Round the segment-table size to a power of two so jit sees a
    small set of stable shapes (same motivation as ops.batch.bucket_size).
    Host-only: n_seg is always a Python int computed BEFORE tracing (it
    becomes the jit-static output shape), never a tracer."""
    n = 8
    while n < n_seg:
        n *= 2
    return n


def flux_mesh(n_devices: Optional[int] = None):
    """A 1-D mesh over the available devices (axis ``flux``) — the
    shared constructor in ops.mesh, which also serves the grep DFA
    plane's partitioned matcher.  Under the simulated-mesh lane
    (``XLA_FLAGS=--xla_force_host_platform_device_count=8``, the tier-1
    default — tests/conftest.py) this is 8 virtual CPU devices; on real
    hardware it is the attached chips.  Returns None when jax is
    unavailable or only one device exists (the mesh path would be pure
    overhead)."""
    from ..ops.mesh import build_mesh

    return build_mesh(n_devices, axis="flux")


def host_segment_counts(seg: np.ndarray, valid: np.ndarray,
                        n_seg: int) -> np.ndarray:
    """Host twin: rows-per-segment over valid rows (int64 → int32-safe
    counts; a chunk has < 2^31 rows by construction)."""
    if n_seg <= 0:
        return np.zeros((0,), dtype=np.int32)
    return np.bincount(
        seg[valid.astype(bool)], minlength=n_seg
    ).astype(np.int32)[:n_seg]


def _counts_impl(seg, valid, n_pad):
    out = jnp.zeros((n_pad,), dtype=jnp.int32)
    return out.at[seg].add(valid.astype(jnp.int32))


def segment_counts(seg: np.ndarray, valid: np.ndarray,
                   n_seg: int) -> np.ndarray:
    """Device scatter-add group counts — bit-identical to
    :func:`host_segment_counts` (integers)."""
    if not HAVE_JAX:
        return host_segment_counts(seg, valid, n_seg)
    n_pad = _pad_segments(n_seg)
    fn = _jit_cache.get(n_pad)
    if fn is None:
        def flux_counts(s, v):
            return _counts_impl(s, v, n_pad)

        fn = _jit_cache[n_pad] = jax.jit(flux_counts)
    got = np.asarray(fn(jnp.asarray(seg.astype(np.int32)),
                        jnp.asarray(valid.astype(np.int32))))
    return got[:n_seg]


def _mesh_key(mesh) -> tuple:
    # structural key, not id(): equal meshes share a compiled step
    # (the shared helper in ops.mesh — also keys the grep/sketch caches)
    from ..ops.mesh import mesh_key

    return mesh_key(mesh)


def build_sharded_counts(mesh, n_pad: int):
    """Compile the mesh group-count program for an ``n_pad``-slot
    segment table: the ``seg``/``valid`` batch columns ride the
    declarative ``flux-counts`` partition rules (batch-axis sharded),
    each device scatter-adds its shard locally, and the merge is
    ``lax.psum`` over the mesh axis. Factored out of the dispatch
    wrapper so the fbtpu-speccheck static==dynamic crosscheck can
    ``lower()`` the exact shipped program on the simulated mesh."""
    from jax import lax, shard_map
    from jax.sharding import PartitionSpec as P

    from ..ops.mesh import rule_spec

    axis = mesh.axis_names[0]

    def step(s, v):
        local = _counts_impl(s, v, n_pad)
        return lax.psum(local, axis_name=axis)

    step.__name__ = "flux_counts_mesh"
    return jax.jit(shard_map(
        step, mesh=mesh,
        in_specs=(rule_spec("flux-counts", axis, "seg"),
                  rule_spec("flux-counts", axis, "valid")),
        out_specs=P(),
    ))


def sharded_segment_counts(mesh, seg: np.ndarray, valid: np.ndarray,
                           n_seg: int) -> np.ndarray:
    """Group counts over a mesh: the batch axis is sharded across
    devices, each device scatter-adds its shard locally, and the merge
    is ``lax.psum`` over the mesh axis — the psum-style tree reduction
    of the flux contract.  Bit-identical to the host twin (integer
    counters)."""
    if not HAVE_JAX or mesh is None:
        return host_segment_counts(seg, valid, n_seg)
    from ..ops.mesh import pad_to_devices

    n_dev = mesh.devices.size
    B = seg.shape[0]
    # pad_to_devices: the divisibility proof fbtpu-speccheck keys the
    # sharded batch axis on (pad rows are invalid → contribute zero)
    Bp = pad_to_devices(B, n_dev)
    seg32 = seg.astype(np.int32)
    valid32 = valid.astype(np.int32)
    if Bp != B:
        seg32 = np.concatenate(
            [seg32, np.zeros((Bp - B,), dtype=np.int32)])
        valid32 = np.concatenate(
            [valid32, np.zeros((Bp - B,), dtype=np.int32)])
    n_pad = _pad_segments(n_seg)
    key = (_mesh_key(mesh), n_pad)
    fn = _shard_cache.get(key)
    if fn is None:
        fn = _shard_cache[key] = build_sharded_counts(mesh, n_pad)
    got = np.asarray(fn(jnp.asarray(seg32), jnp.asarray(valid32)))
    return got[:n_seg]


# -- the fused absorb: counts + HLL stack + count-min, ONE launch ------
#
# The cashed fbtpu-fuseplan merge (ANALYSIS.md "Fusion pack"): the flux
# chain's three per-segment launches (guarded_segment_counts, the
# per-group HLL lane.run, the count-min lane.run) collapse into a
# single program. Legality is exactly what the planner proves: every
# constituent is a commutative integer scatter (add/max) from an
# explicit snapshot, no host effect or compact sits between them, and
# the producer/consumer avals are independent state leaves — so one
# program computing all three from the same staged batch is bit-exact
# vs both the unfused chain and the host twins.

#: compiled fused-absorb cache — keyed by mesh structure, segment-table
#: size, field count, HLL precision and CMS geometry (jit handles the
#: per-shape executables underneath the one wrapped callable)
_fused_cache: dict = {}


def build_fused_absorb(mesh, n_pad: int, n_fields: int, hll_p: int,
                       cms=None, donate: bool = False):
    """Compile the ONE-launch flux absorb program.

    Flat argument layout (``F = n_fields`` distinct columns)::

        seg [Bp] i32, valid [Bp] i32,
        (batch_f [Bp, L] u8, lengths_f [Bp] i32) × F,
        registers_f [n_pad, m] i32 × F,
        [table [d, w], comp [Bc, W] u8, comp_len [Bc] i32]   (cms only)

    Returns ``(counts [n_pad] i32, registers_f × F, [table])``.  On a
    mesh every batch-axis column shards per the declarative
    ``flux-fused`` partition rules; sketch state replicates and merges
    with pmax (HLL register stack) / psum (counts, count-min) — the
    same exact integer merges as the unfused programs.  ``mesh=None``
    compiles the plain single-device jit.  ``donate=True`` donates the
    register stacks (always freshly assembled inside the launch, so
    aliasing them is safe; the count-min table is NOT donated — the
    fallback path re-materializes host state from that snapshot).
    Factored out of the dispatch wrappers so the fbtpu-speccheck
    static==dynamic crosscheck can ``lower()`` the exact shipped
    program on the simulated mesh."""
    from jax import lax

    from ..ops.sketch import hll_index_rank

    axis = mesh.axis_names[0] if mesh is not None else None

    def step(seg, valid, *rest):
        with jax.named_scope("flux.counts"):
            counts = _counts_impl(seg, valid, n_pad)
            if axis is not None:
                counts = lax.psum(counts, axis_name=axis)
        outs = [counts]
        for f in range(n_fields):
            b, ln = rest[2 * f], rest[2 * f + 1]
            regs = rest[2 * n_fields + f]
            with jax.named_scope("flux.hll"):
                idx, rank = hll_index_rank(b, ln, hll_p)
                # 2-D scatter-max into the per-group register stack:
                # row = the row's segment id, column = the hash's
                # register index. Invalid rows carry rank 0 (a no-op
                # under max), so pad rows may scatter anywhere.
                local = regs.at[seg, idx].max(rank)
                outs.append(lax.pmax(local, axis_name=axis)
                            if axis is not None else local)
        if cms is not None:
            table, comp, comp_len = rest[3 * n_fields:]
            with jax.named_scope("flux.cms"):
                w = jnp.ones_like(comp_len)  # flux absorbs are weight-1
                # + 0*sum: ties the accumulator to the sharded batch so
                # the fori_loop carry's varying annotation stays
                # consistent
                zero = jnp.zeros_like(table) + (
                    0 * comp_len.sum()).astype(table.dtype)
                local = cms._update_impl(zero, comp, comp_len, w)
                outs.append(table + (lax.psum(local, axis_name=axis)
                                     if axis is not None else local))
        return tuple(outs)

    step.__name__ = "flux_absorb" if mesh is None else "flux_absorb_mesh"

    donate_idx: tuple = ()
    if donate:
        # the register stacks alias their outputs exactly (replicated
        # [n_pad, m] i32 in and out) — the one safely-donatable subset
        donate_idx = tuple(range(2 + 2 * n_fields, 2 + 3 * n_fields))
    if mesh is None:
        return jax.jit(step, donate_argnums=donate_idx)
    from jax import shard_map
    from jax.sharding import PartitionSpec as P

    from ..ops.mesh import rule_spec

    in_specs = [rule_spec("flux-fused", axis, "seg"),
                rule_spec("flux-fused", axis, "valid")]
    for _ in range(n_fields):
        in_specs.append(rule_spec("flux-fused", axis, "batch"))
        in_specs.append(rule_spec("flux-fused", axis, "lengths"))
    regs_spec = rule_spec("flux-fused", axis, "registers")
    in_specs.extend([regs_spec] * n_fields)
    out_specs = [P()] + [regs_spec] * n_fields
    if cms is not None:
        in_specs.extend([rule_spec("flux-fused", axis, "table"),
                         rule_spec("flux-fused", axis, "comp"),
                         rule_spec("flux-fused", axis, "comp_len")])
        out_specs.append(rule_spec("flux-fused", axis, "table"))
    return jax.jit(shard_map(
        step, mesh=mesh,
        in_specs=tuple(in_specs), out_specs=tuple(out_specs),
    ), donate_argnums=donate_idx)


def _pad_rows_to(arr: np.ndarray, n: int, fill) -> np.ndarray:
    """Pad the leading (batch) axis up to ``n`` rows with ``fill``."""
    if arr.shape[0] >= n:
        return arr
    pad_shape = (n - arr.shape[0],) + arr.shape[1:]
    return np.concatenate([arr, np.full(pad_shape, fill,
                                        dtype=arr.dtype)])


def _fused_call(mesh, seg, valid, fields, regs, comp, comp_len,
                table, hll_p: int, cms, n_seg: int):
    """Shared dispatch body of :func:`sharded_fused_absorb` /
    :func:`fused_absorb` — pads the batch axis to the mesh multiple
    (the divisibility proof fbtpu-speccheck keys the sharded in_specs
    on), stacks the per-group register snapshots to the padded segment
    table, and runs the cached compiled program."""
    from ..ops import device
    from ..ops.mesh import pad_to_devices

    if not device.wait(max(60.0, device.default_wait())):
        raise RuntimeError(
            f"device backend not attached: {device.status()}")
    n_dev = mesh.devices.size if mesh is not None else 1
    B = seg.shape[0]
    Bp = pad_to_devices(B, n_dev)
    args = [jnp.asarray(_pad_rows_to(seg.astype(np.int32), Bp, 0)),
            jnp.asarray(_pad_rows_to(valid.astype(np.int32), Bp, 0))]
    for b, ln in fields:
        args.append(jnp.asarray(_pad_rows_to(
            np.ascontiguousarray(b, dtype=np.uint8), Bp, 0)))
        args.append(jnp.asarray(_pad_rows_to(
            ln.astype(np.int32), Bp, -1)))
    n_pad = _pad_segments(n_seg)
    for group_regs in regs:
        # the per-group snapshot stack: ALWAYS freshly assembled here
        # (inside the watched launch), which is what makes donating it
        # safe — no caller holds a reference to the stacked buffer
        stack = jnp.stack([jnp.asarray(r) for r in group_regs])
        if n_pad > stack.shape[0]:
            stack = jnp.concatenate(
                [stack, jnp.zeros((n_pad - stack.shape[0],
                                   stack.shape[1]), stack.dtype)])
        args.append(stack)
    has_cms = cms is not None and comp is not None
    if has_cms:
        Bc = pad_to_devices(comp.shape[0], n_dev)
        args.append(jnp.asarray(table, dtype=cms._dtype))
        args.append(jnp.asarray(_pad_rows_to(
            np.ascontiguousarray(comp, dtype=np.uint8), Bc, 0)))
        args.append(jnp.asarray(_pad_rows_to(
            comp_len.astype(np.int32), Bc, -1)))
    plat = (list(mesh.devices.flat)[0].platform if mesh is not None
            else device.platform())
    donate = plat not in (None, "cpu")  # CPU never aliases: donating
    # there only buys the "donated buffers were not usable" warning
    key = (None if mesh is None else _mesh_key(mesh), n_pad,
           len(fields), hll_p,
           (cms.depth, cms.width) if has_cms else None, donate)
    fn = _fused_cache.get(key)
    if fn is None:
        fn = _fused_cache[key] = build_fused_absorb(
            mesh, n_pad, len(fields), hll_p,
            cms if has_cms else None, donate=donate)
    out = fn(*args)
    counts = out[0][:n_seg]
    regs_out = tuple(out[1:1 + len(fields)])
    table_out = out[1 + len(fields)] if has_cms else None
    return counts, regs_out, table_out


def sharded_fused_absorb(mesh, seg: np.ndarray, valid: np.ndarray,
                         fields, regs, comp=None, comp_len=None,
                         table=None, *, hll_p: int, cms=None,
                         n_seg: int):
    """Mesh dispatch of the fused absorb program, WITHOUT committing or
    mutating any sketch state: computes from the explicit per-group
    register snapshots in ``regs`` (sequence over distinct fields of
    sequences over groups) and the ``table`` snapshot, and returns
    ``(counts [:n_seg], register stacks × F, table-or-None)`` — the
    fbtpu-armor flux lane commits on the caller thread after the
    watched launch resolves (snapshot-in/commit-on-finish, see
    ops.sketch.sharded_hll_registers)."""
    return _fused_call(mesh, seg, valid, fields, regs, comp, comp_len,
                       table, hll_p, cms, n_seg)


def fused_absorb(seg: np.ndarray, valid: np.ndarray, fields, regs,
                 comp=None, comp_len=None, table=None, *, hll_p: int,
                 cms=None, n_seg: int):
    """Single-device twin of :func:`sharded_fused_absorb` (plain jit,
    no mesh) — the fused path when the lane's mesh has shrunk below
    two devices or the state was built without ``mesh``."""
    return _fused_call(None, seg, valid, fields, regs, comp, comp_len,
                       table, hll_p, cms, n_seg)


def guarded_segment_counts(lane, seg: np.ndarray, valid: np.ndarray,
                           n_seg: int, axis: str = "flux") -> np.ndarray:
    """Group counts through the fbtpu-armor flux DeviceLane: the
    sharded scatter-add/psum launch runs on the lane's watched worker
    (deadline, breaker, ``flux.device_update`` failpoint), the mesh
    comes from the lane (shrinks on device loss, regrows on breaker
    re-close), and any failure resolves to the bit-identical host twin
    — integer counters, so the result is exact either way."""
    from .. import failpoints as _fp

    def launch():
        if _fp.ACTIVE:
            _fp.fire("flux.device_update")
        mesh = lane.current_mesh(axis=axis)
        if mesh is None:  # shrunk below 2 devices: host twin serves
            return host_segment_counts(seg, valid, n_seg)
        return sharded_segment_counts(mesh, seg, valid, n_seg)

    def fallback():
        return host_segment_counts(seg, valid, n_seg)

    return lane.run(launch, fallback)
