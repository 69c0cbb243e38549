"""Mesh partitioning layer — declarative PartitionSpecs + shared mesh
helpers for every SPMD plane (grep DFA, sketches, flux kernels).

The device programs in this repo all shard the same way: one 1-D device
mesh, a batch-like axis split across chips, small lookup tables
replicated (or sharded over the rule axis when R is large). Before this
module each plane hand-wrote its specs inline; the partition decisions
now live in *rules* — ``(regex over the leaf name, PartitionSpec)``
pairs matched against a named table pytree, the ``match_partition_rules``
pattern of large-model training codebases (SNIPPETS.md [2]) — so a
reviewer can read the whole sharding layout of a program in one table,
and a new table added to a program picks up a spec by name instead of
by editing three call sites.

Also here:

- ``build_mesh`` / ``mesh_key`` / ``mesh_info`` — the one mesh
  constructor and cache-key/diagnostics helpers every plane shares
  (flux_mesh and ops.sketch used to carry private copies).
- donation helpers — compute the *aliasable* subset of staged input
  buffers (exact sharded shape+dtype match against the outputs, the
  same matching ``jax.jit`` itself performs) so donation never degrades
  into the silent "Some donated buffers were not usable" copy fallback,
  and report which aliases actually landed in the lowered HLO
  (``tf.aliasing_output``) for the tier-1 donation tests.

Everything degrades gracefully without jax: ``build_mesh`` returns
None and the callers stay on their host twins.
"""

from __future__ import annotations

import re
import warnings
from typing import Any, Dict, List, Optional, Sequence, Tuple

import numpy as np

try:
    import jax

    HAVE_JAX = True
except Exception:  # pragma: no cover - jax absent: host twins only
    HAVE_JAX = False

__all__ = [
    "named_tree_map", "match_partition_rules", "build_mesh", "mesh_key",
    "mesh_info", "pad_to_devices", "aliasable_donations",
    "donation_report", "replicated_table_bytes", "TABLE_BUDGET",
    "AXIS", "PARTITION_RULES", "partition_rules", "rule_spec",
]

# -- the declarative partition-rules registry --------------------------
#
# One table per device program, naming EVERY leaf of its table pytree
# explicitly — anchored regexes, no catch-alls. The programs consume
# these through ``partition_rules``/``rule_spec`` (which bind the axis
# placeholder to the live mesh axis), and the fbtpu-speccheck abstract
# interpreter (analysis/speccheck.py) evaluates the same tables
# symbolically at lint time: a leaf that falls through to the implicit
# replicate fallback, a rule an earlier rule shadows, or a sharded dim
# with no divisibility proof is a finding BEFORE anything traces on a
# mesh. Spec templates are plain tuples (axis token / axis name / None
# per dim) so the registry imports without jax.

#: Placeholder resolved to the program's mesh axis name at build time.
AXIS = "@axis"

PARTITION_RULES: Dict[str, Tuple[Tuple[str, Tuple[Any, ...]], ...]] = {
    # grep DFA plane, batch variant: B shards across devices, every
    # table leaf replicated (the post-shrink matrices are small
    # relative to per-device memory — mesh_variant gates the flip)
    "grep-batch": (
        (r"^(trans_flat|class_base|run_start|run_delta|C|Ck|eol_cls"
         r"|starts)$", ()),
    ),
    # grep rule-sharded variant: each device holds 1/n of the rules —
    # 2-D table leaves split on the rule axis, per-rule vectors too
    "grep-rules": (
        (r"^(trans_flat|run_start|run_delta)$", (AXIS, None)),
        (r"^(class_base|C|Ck|eol_cls|starts)$", (AXIS,)),
    ),
    # flux sketch state leaves: replicated snapshots — every device
    # absorbs its batch shard into a full local copy, merged by
    # pmax (HLL union) / psum (count-min sum) inside the program
    "flux-hll": ((r"^registers$", ()),),
    "flux-cms": ((r"^table$", ()),),
    # flux window/segment-count columns: batch-axis sharded inputs,
    # replicated counts out of the psum merge
    "flux-counts": ((r"^(seg|valid)$", (AXIS,)),),
    # ONE-launch fused flux absorb (counts + per-group HLL stack +
    # count-min in a single program — the fbtpu-fuseplan cashed merge):
    # every batch-axis column shards, all sketch state replicates (the
    # merges are pmax over the [Gp, m] register stack and psum over the
    # count-min table / segment counts, same exactness as the unfused
    # programs)
    "flux-fused": (
        (r"^(seg|valid|lengths|comp_len)$", (AXIS,)),
        (r"^(batch|comp)$", (AXIS, None)),
        (r"^(registers|table)$", ()),
    ),
}


def partition_rules(key: str, axis: str):
    """The ``(regex, PartitionSpec)`` rows of one registry table with
    the axis placeholder bound — what ``match_partition_rules`` and the
    program builders consume. Unknown keys raise: a renamed table must
    not silently build an unsharded program."""
    from jax.sharding import PartitionSpec as P

    try:
        rows = PARTITION_RULES[key]
    except KeyError:
        raise KeyError(
            f"unknown partition-rule table {key!r}; known: "
            f"{sorted(PARTITION_RULES)}") from None
    return tuple(
        (regex, P(*(axis if t == AXIS else t for t in tmpl)))
        for regex, tmpl in rows
    )


def rule_spec(key: str, axis: str, name: str):
    """The PartitionSpec a registry table assigns to the leaf ``name``
    (first-match, same semantics as ``match_partition_rules``) — the
    single-leaf convenience the flux kernel builders use."""
    for regex, spec in partition_rules(key, axis):
        if re.search(regex, name) is not None:
            return spec
    raise ValueError(
        f"partition-rule table {key!r} has no rule for leaf {name!r}")


#: bytes of tables replicated across a mesh (footprint × devices) above
#: which a program shards its RULE axis instead of the batch
TABLE_BUDGET = 64 * 1024 * 1024


def replicated_table_bytes(tables) -> int:
    """Total byte footprint of a program's table pytree (numpy dicts
    with possible None leaves, or device-array pytrees) — the number
    the batch-vs-rules partition decision weighs against
    ``TABLE_BUDGET``. Centralized here (rather than inline
    per program) so every plane sizes its replication the same way —
    the fbtpu-shrink pass changes these shapes per DFA, and the mesh
    variant choice must follow the REAL post-reduction footprint."""
    total = 0
    for v in (tables.values() if isinstance(tables, dict) else tables):
        if v is None:
            continue
        shape = getattr(v, "shape", None)
        if shape is None:
            continue
        itemsize = getattr(getattr(v, "dtype", None), "itemsize", 1)
        total += int(np.prod(shape)) * int(itemsize)
    return total


def named_tree_map(fn, tree, sep: str = "/"):
    """``tree_map`` with the leaf's /-joined key path as first argument
    (the naming layer ``match_partition_rules`` matches against)."""
    from jax.tree_util import keystr, tree_map_with_path

    def call(path, leaf):
        name = keystr(path)
        # keystr renders "['trans_flat']"; flatten to trans_flat/sub
        name = re.sub(r"\[['\"]?([^'\"\]]*)['\"]?\]", r"\1" + sep, name)
        return fn(name.rstrip(sep), leaf)

    return tree_map_with_path(call, tree)


def match_partition_rules(rules: Sequence[Tuple[str, Any]], tree,
                          *, scalars_replicate: bool = True,
                          dead_rules: str = "raise"):
    """Pytree of arrays → pytree of PartitionSpec via first-match regex
    rules over leaf names. Scalars (0-d / size-1 leaves) replicate
    unconditionally — there is nothing to split. A leaf no rule covers
    raises: an unsharded table sneaking into a partitioned program is a
    layout bug, not a default.

    A rule that fires on NO leaf across the whole pytree is equally a
    layout bug — a renamed table leaf silently reverts to whatever the
    later rules (or the unmatched-leaf error) decide while its spec
    rots in the table. ``dead_rules`` controls the response: ``"raise"``
    (default), ``"warn"``, or ``"ignore"`` (for rule tables shared by
    programs whose pytrees are legitimate subsets, e.g. an optional
    leaf). The fbtpu-speccheck lint rule ``shard-shadowed-rule`` makes
    the same check statically, before anything traces."""
    from jax.sharding import PartitionSpec as P

    used: set = set()

    def pick(name, leaf):
        shape = getattr(leaf, "shape", ())
        if scalars_replicate and (len(shape) == 0 or int(np.prod(shape)) == 1):
            return P()
        for i, (rule, spec) in enumerate(rules):
            if re.search(rule, name) is not None:
                used.add(i)
                return spec
        raise ValueError(f"no partition rule matches leaf {name!r}")

    out = named_tree_map(pick, tree)
    if dead_rules != "ignore":
        dead = [rules[i][0] for i in range(len(rules)) if i not in used]
        if dead:
            msg = (f"partition rule(s) matched no leaf: {dead!r} — "
                   f"a renamed table leaf no longer picks up its spec "
                   f"(dead_rules='ignore' if the subset is deliberate)")
            if dead_rules == "raise":
                raise ValueError(msg)
            warnings.warn(msg, stacklevel=2)
    return out


def build_mesh(n_devices: Optional[int] = None, axis: str = "batch"):
    """A 1-D mesh over the available devices. Under the simulated-mesh
    lane (``XLA_FLAGS=--xla_force_host_platform_device_count=8``, the
    tier-1 default — tests/conftest.py) these are 8 virtual CPU
    devices; on real hardware, the attached chips. Returns None when
    jax is unavailable or fewer than two devices exist (the mesh path
    would be pure overhead)."""
    if not HAVE_JAX:
        return None
    from jax.sharding import Mesh

    devs = jax.devices()
    if n_devices is not None:
        devs = devs[:n_devices]
    if len(devs) < 2:
        return None
    return Mesh(np.asarray(devs), (axis,))


def mesh_key(mesh) -> tuple:
    """Structural cache key: equal meshes share a compiled program
    (id() would recompile per Mesh object)."""
    return (tuple(mesh.axis_names),
            tuple(d.id for d in mesh.devices.flat))


def mesh_info(mesh) -> Dict[str, Any]:
    """Diagnostics block for RESULT JSON / health surfaces: shape,
    platform, and whether this is the simulated host-platform mesh."""
    import os

    if mesh is None:
        return {"devices": 1, "axis_names": [], "simulated": False,
                "platform": None}
    devs = list(mesh.devices.flat)
    plat = getattr(devs[0], "platform", None)
    flags = os.environ.get("XLA_FLAGS", "")
    simulated = (plat == "cpu"
                 and "xla_force_host_platform_device_count" in flags)
    return {
        "devices": len(devs),
        "axis_names": list(mesh.axis_names),
        "platform": plat,
        "simulated": simulated,
    }


def pad_to_devices(n: int, n_devices: int) -> int:
    """Smallest multiple of the device count ≥ n (NamedSharding requires
    the sharded dimension divisible by the mesh size)."""
    if n_devices <= 1:
        return n
    return ((n + n_devices - 1) // n_devices) * n_devices


# -- donation ----------------------------------------------------------

def _sharded_shape(shape, spec, mesh) -> tuple:
    """Per-device shard shape for an array of ``shape`` under ``spec``
    (what jax's donation matcher compares — aliasing is decided on the
    *sharded* avals)."""
    axes = {a: n for a, n in zip(mesh.axis_names,
                                 mesh.devices.shape)}
    out = list(shape)
    for i, s in enumerate(spec):
        if s is None:
            continue
        names = s if isinstance(s, tuple) else (s,)
        for nm in names:
            out[i] //= axes.get(nm, 1)
    return tuple(out)


def aliasable_donations(mesh, in_specs: Sequence[tuple],
                        out_specs: Sequence[tuple]) -> List[int]:
    """Indices of donatable inputs whose sharded (shape, dtype) exactly
    matches an output's — the subset jax can actually alias. Donating
    anything else is a silent no-op plus a compile-time warning (the
    "copy fallback" ``donation_report`` must never hide), so the mesh
    matcher donates exactly this set.

    ``in_specs``/``out_specs``: sequences of
    ``(shape, dtype, PartitionSpec, donatable: bool)`` /
    ``(shape, dtype, PartitionSpec)``.
    """
    outs: Dict[tuple, int] = {}
    for shape, dtype, spec in out_specs:
        key = (_sharded_shape(shape, spec, mesh), np.dtype(dtype))
        outs[key] = outs.get(key, 0) + 1
    donate: List[int] = []
    for i, (shape, dtype, spec, ok) in enumerate(in_specs):
        if not ok:
            continue
        key = (_sharded_shape(shape, spec, mesh), np.dtype(dtype))
        if outs.get(key, 0) > 0:
            outs[key] -= 1
            donate.append(i)
    return donate


def donation_report(lowered, donate_argnums: Sequence[int],
                    arg_names: Sequence[str]) -> Dict[str, Any]:
    """Inspect a ``jax.jit(...).lower(...)`` result for the
    input→output aliases donation promised. Returns
    ``{"declared": [...], "held": bool, "alias_count": int}`` where
    ``held`` means the lowered module carries at least one
    ``tf.aliasing_output`` annotation per declared arg — the
    compiled-module check the tier-1 donation test asserts (run-time
    proof is the donated buffer's ``is_deleted()`` flip)."""
    txt = lowered.as_text()
    n_alias = txt.count("tf.aliasing_output")
    declared = [arg_names[i] if i < len(arg_names) else str(i)
                for i in donate_argnums]
    return {
        "declared": declared,
        "alias_count": n_alias,
        "held": n_alias >= len(declared) and bool(declared),
    }
