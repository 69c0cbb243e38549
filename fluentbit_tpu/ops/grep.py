"""Device DFA execution — vectorized regex matching on TPU.

The Onigmo-replacement kernel (SURVEY §2.2: "the thing the TPU build must
re-express as a vectorized/compiled automaton kernel"). A compiled scan
DFA (fluentbit_tpu.regex.dfa) runs over a ``[B, L] uint8`` batch as a
``lax.scan`` of table gathers:

    state[b] = trans[state[b], class(byte[b, t])]        t = 0..L

- Multi-rule: R DFAs run in one kernel. The caller stages each DISTINCT
  record field once — the planes ``[K, B, L]`` — and the program's
  static rule→plane index (``plane_of``) gathers every rule's plane on
  the device, so eight rules on one key cost one plane of host→device
  bytes, not eight. All R transition tables are fused into ONE flat
  gather per scan step (``trans_flat[R, max_flat]`` + per-rule radix),
  so the step cost does not grow a kernel launch per rule.
- First match: ``dispatch(..., first_match=True)`` reduces the merged
  ``[R, B]`` mask on the device to one i32 a record — the first rule,
  in the caller's rule order, whose DFA accepts, or -1 — so a router
  (rewrite_tag) copies out B integers instead of R×B verdicts.
- k-byte super-steps: transition tables are pre-composed to ``C^k``
  columns (T2[s, c1*C+c2] = T[T[s,c1],c2]), cutting sequential scan steps
  by k at the cost of a larger (still VMEM-resident) table. k is chosen
  so the table stays under a size budget.
- Byte classing without a gather: a rule's byte→class map is a step
  function of the byte with a handful of breakpoints (4-16 for the
  benchmark's rules, 255 at worst), so the super-symbol prepass
  computes ``class(b) = class_base + Σ_n (b >= run_start[n]) *
  run_delta[n]`` in the same elementwise pass that pads and combines k
  classes — an element gather costs 8-12 ns an element on a v5e
  (PERF.md, PRs 28 and 41), a compare-add next to nothing.
- Padding positions map to the EOL symbol class, which is absorbing after
  the first step — fixed shapes stay exact, no masking in the inner loop.
- matched == (final_state == ACC): single comparison at scan end, no
  per-position accept reduction.
- Kernel selection: ``kernel="auto"`` (the default) is the sequential
  scan on every platform; the parallel-in-time assoc kernel runs only
  where the constructor's ``kernel="assoc"`` asks for it (the
  differential tests, ``chip_smoke.py``'s probe). A launch's time is
  its count of gathered elements, and assoc gathers S× scan's: on a
  v5e at ``[1, 4096, 256]`` the benchmark's S=10 rule took 21.1 ms on
  assoc and 2.3 on scan, its S=690 rule 2,707 and 3.3, and scan won at
  every shape down to 256 rows (PERF.md, PR 33); on a host CPU assoc
  measured 300× slower.
- Scan children: below the rule-shard regime (``R < 64``) the rules run
  as child programs, one stride a child and no child's tables — laid
  out ``[R_c, widest]`` — over ``_CHILD_TABLE_BUDGET`` unless it is one
  rule (``partition_children``): the scan's gather costs by the table it
  reads, 8-12 ns an element from children of up to 50 MB and 17-25 from
  one of 144 MB (PERF.md, PRs 34 and 41), and sorted by size a child
  pads little. A list whose strides agree and whose tables fit is one
  program, as it always was.

This module works on any JAX backend (tests force a CPU mesh); on TPU the
gathers vectorize across the batch dimension.
"""

from __future__ import annotations

import collections
import functools
import logging
import threading
from typing import List, Optional, Sequence, Tuple

import numpy as np

log = logging.getLogger("flb.grep")

try:
    import jax
    import jax.numpy as jnp
    from jax import lax

    HAVE_JAX = True
except Exception:  # pragma: no cover
    HAVE_JAX = False

from ..core.spans import span
from ..regex.dfa import ACC, DFA, EOL

# table budget for k-byte super-stepping (bytes); C^k columns * S rows * 4
_TABLE_BUDGET = 4 * 1024 * 1024
# budget of one scan child's tables as laid out (bytes): ``R_c`` rules
# padded to the child's widest and their class runs, the whole pytree as
# ``ops.mesh.replicated_table_bytes`` weighs it. A gathered element costs
# by the table it is gathered from (v5e; PERF.md, PR 41)
_CHILD_TABLE_BUDGET = 16 * 1024 * 1024


def choose_k(n_states: int, n_classes: int, budget: int = _TABLE_BUDGET) -> int:
    """Largest stride whose composed table fits the budget (strides up
    to 6 — small alphabets with few states compose deep)."""
    k = 1
    while k < 6:
        cols = n_classes ** (k + 1)
        if n_states * cols * 4 > budget:
            break
        k += 1
    return k


def class_runs(class_map: np.ndarray) -> Tuple[int, np.ndarray, np.ndarray]:
    """A byte→class map as a step function of the byte: ``(base,
    run_start, run_delta)`` with ``class_map[b] == base + sum(run_delta[
    run_start <= b])`` for every byte ``b`` — the class of byte 0, the
    byte values at which the class changes, and by how much."""
    cm = np.asarray(class_map[:256], dtype=np.int32)
    step = np.diff(cm)
    start = np.flatnonzero(step) + 1
    return int(cm[0]), start.astype(np.int32), step[start - 1]


def compose_table(trans: np.ndarray, k: int) -> np.ndarray:
    """Pre-compose a [S, C] table to k-byte super-steps: [S, C^k]
    (delegates to the shared composition in regex.dfa so the device and
    native tables stay bit-identical)."""
    from ..regex.dfa import compose_supersteps

    return compose_supersteps(trans, k)


def scan_steps(L: int, k: int) -> int:
    """Super-steps the scan kernel takes over a plane of ``L`` bytes at
    stride ``k``: the bytes rounded up to whole strides, and one stride
    of EOL after them (``_super_symbols``) — ``⌈L/k⌉ + 1``."""
    return -(-L // k) + 1


def laid_out_bytes(R: int, max_flat: int, n_runs: int) -> int:
    """What ``R`` rules take as ONE scan child, from shapes alone: every
    table padded to the widest (``trans_flat[R, max_flat]``), the two
    ``[R, n_runs]`` class-run arrays and the five ``[R]`` vectors, i32
    all — to the byte what ``ops.mesh.replicated_table_bytes`` reads off
    the child's built pytree."""
    return 4 * R * (max_flat + 2 * max(n_runs, 1) + 5)


def partition_children(k_by_rule: Sequence[int], flat: Sequence[int],
                       n_runs: Sequence[int],
                       budget: int) -> List[np.ndarray]:
    """The rules in scan children: stride by stride (ascending), a
    stride's rules ordered by table size (``flat[r]`` = ``S · C^k``
    entries, the widest first; file order among equals) and cut greedily
    wherever one rule more would carry the child's laid-out tables
    (``laid_out_bytes``; ``n_runs[r]`` the rule's class runs) over
    ``budget`` — sorted, a child pads little. A rule alone is always a
    child. → each child's rule indices, the children in that order,
    a child's rules in file order (so a stride that stays one child is
    laid out as it always was)."""
    groups: List[np.ndarray] = []
    for k in sorted(set(k_by_rule)):
        rules = sorted((r for r, kk in enumerate(k_by_rule) if kk == k),
                       key=lambda r: -flat[r])
        child: List[int] = []
        runs = 0
        for r in rules:
            if child and laid_out_bytes(
                    len(child) + 1, flat[child[0]],
                    max(runs, n_runs[r])) > budget:
                groups.append(np.sort(np.asarray(child, dtype=np.int64)))
                child, runs = [], 0
            child.append(r)
            runs = max(runs, n_runs[r])
        groups.append(np.sort(np.asarray(child, dtype=np.int64)))
    return groups


class GrepProgram:
    """R compiled DFAs fused into one device program.

    Produces ``match(planes_u8[K,B,L], lengths[K,B]) -> bool[R,B]``:
    ``planes`` holds each distinct staged field once and ``plane_of[r]``
    (static, default ``range(R)``: one plane a rule) names the plane
    rule ``r`` reads.
    """

    def __init__(self, dfas: Sequence[DFA], max_len: int = 512,
                 kernel: str = "auto", segment: int = 32,
                 plane_of: Optional[Sequence[int]] = None,
                 child_budget: int = _CHILD_TABLE_BUDGET):
        if not HAVE_JAX:
            raise RuntimeError("jax is unavailable")
        self.dfas = list(dfas)
        self.max_len = max_len
        if plane_of is None:
            plane_of = range(len(self.dfas))
        self.plane_of = tuple(int(p) for p in plane_of)
        if len(self.plane_of) != len(self.dfas) or (
                self.plane_of and min(self.plane_of) < 0):
            raise ValueError(f"plane_of {self.plane_of!r} does not name "
                             f"a plane for each of {len(self.dfas)} rules")
        #: planes the caller stages (children take the parent's planes
        #: whole and gather their own rules' from them)
        self.n_planes = max(self.plane_of, default=-1) + 1
        # kernel variant: "scan" = sequential lax.scan of table gathers
        # (Lk serialized steps, R·B gathered elements a step); "assoc" =
        # parallel-in-time function composition (segments scanned as
        # transition FUNCTIONS over all states, then a log2-depth tree
        # of compositions) — sequential depth m + log2(Lk/m) instead of
        # Lk, for S× the gathered elements; "auto" = scan, and why
        # (_resolve_kernel)
        self.kernel = kernel
        if self.kernel not in ("scan", "assoc", "auto"):
            raise ValueError(f"unknown grep kernel {self.kernel!r}")
        self.kernel_resolved: Optional[str] = None
        self.segment = max(2, int(segment))
        R = len(self.dfas)

        # fbtpu-shrink: per-DFA stride selection. choose_k re-resolves
        # here against the MINIMIZED (S, C) — the whole point of the
        # compile-path reduction is that these numbers shrank. The rules
        # then go into scan children (each a plain homogeneous
        # GrepProgram) by what the code can observe of them, their
        # tables: one stride a child — a literal rule's k=6 does not
        # ride at a rich parser's k=3 — and no child's laid-out tables
        # over ``child_budget`` where it holds more than one rule
        # (partition_children). A list that comes out as one child is this
        # program itself. The split is gated off the rule-shard regime
        # (large R wants ONE fused table set to shard over the rule
        # axis — ops/mesh.py).
        self.k_by_rule = [choose_k(d.n_states, d.n_classes)
                          for d in self.dfas]
        # byte classing as breakpoints (class_runs); decision() reads
        # the per-rule counts on a split parent too
        runs = [class_runs(d.class_map) for d in self.dfas]
        self._class_runs = [int(st.size) for _, st, _ in runs]
        self._children: Optional[List["GrepProgram"]] = None
        self._inv_perm: Optional[np.ndarray] = None
        #: what sets this child's module name apart from an earlier
        #: child's of the same stride (the parent sets it)
        self.name_tag = ""
        import os as _os
        min_shard_r = int(_os.environ.get("FBTPU_MESH_RULE_SHARD_R", "64"))
        groups = partition_children(
            self.k_by_rule,
            [d.n_states * d.n_classes ** k
             for d, k in zip(self.dfas, self.k_by_rule)],
            self._class_runs, child_budget) if 1 < R < min_shard_r else []
        if len(groups) > 1:
            self._children = [
                GrepProgram([self.dfas[int(i)] for i in idxs], max_len,
                            kernel=self.kernel, segment=segment,
                            plane_of=[self.plane_of[int(i)]
                                      for i in idxs],
                            child_budget=child_budget)
                for idxs in groups
            ]
            seen: collections.Counter = collections.Counter()
            for c in self._children:
                c.n_planes = self.n_planes
                # the first child of a stride keeps the name a stride's
                # one child has; the trace's readers sum by module name
                c.name_tag = f"_c{seen[c.k]}" if seen[c.k] else ""
                seen[c.k] += 1
            self._inv_perm = np.argsort(np.concatenate(groups))
            self.k = min(self.k_by_rule)
            self.max_states = max(d.n_states for d in self.dfas)
            self.table_bytes = sum(c.table_bytes for c in self._children)
            self._merge_jit = None
            self._np = None
            self._jit = None
            self._mat_lock = threading.Lock()
            self._mesh_cache = {}
            return

        # Table prep is pure numpy — cheap and safe at plugin init. The
        # jnp transfers + jit happen in _materialize(), gated on the
        # device-attach controller, so constructing a GrepProgram never
        # blocks on (possibly minutes-long) backend init.
        self.k = min(self.k_by_rule)
        tables = [compose_table(d.trans, self.k) for d in self.dfas]
        max_flat = max(t.shape[0] * t.shape[1] for t in tables)
        flat = np.zeros((R, max_flat), dtype=np.int32)
        for r, t in enumerate(tables):
            flat[r, : t.size] = t.reshape(-1)
        # the breakpoints padded to the widest rule with a start no
        # uint8 reaches
        n_runs = max(self._class_runs + [1])
        run_start = np.full((R, n_runs), 256, dtype=np.int32)
        run_delta = np.zeros((R, n_runs), dtype=np.int32)
        for r, (_, st, dl) in enumerate(runs):
            run_start[r, : st.size] = st
            run_delta[r, : dl.size] = dl
        self._np = {
            "trans_flat": flat,
            "C": np.asarray([d.n_classes for d in self.dfas],
                            dtype=np.int32),
            "Ck": np.asarray([d.n_classes ** self.k for d in self.dfas],
                             dtype=np.int32),
            "class_base": np.asarray([b for b, _, _ in runs],
                                     dtype=np.int32),
            "run_start": run_start,
            "run_delta": run_delta,
            "eol_cls": np.asarray([d.eol_class for d in self.dfas],
                                  dtype=np.int32),
            "starts": np.asarray([d.start for d in self.dfas],
                                 dtype=np.int32),
        }
        from .mesh import replicated_table_bytes

        #: the tables as laid out, bytes (what ``child_budget`` bounds
        #: and ``mesh_variant`` weighs)
        self.table_bytes = replicated_table_bytes(self._np)
        self.max_states = max(d.n_states for d in self.dfas)
        self._jit = None
        self._mat_lock = threading.Lock()
        self._mesh_cache: dict = {}

    def _resolve_kernel(self) -> str:
        """``auto`` is scan, whatever the shape and the platform. On a
        v5e the compiled kernels alone, both over each of the five
        children of the grep and rewrite configurations at B ∈ {256,
        1,024, 4,096}, L ∈ {256, 512} (PERF.md, PR 33): scan 0.8-15 ms,
        assoc 2.2-21× that at S ≤ 12 and 200-1,100× at S=690 — assoc
        gathers ``[R, B, G2, S]`` elements a step where scan gathers
        ``[R, B]``, and a gather costs the same 8-11 ns an element
        whatever idles beside it. On a host CPU it was 300× slower."""
        return "scan" if self.kernel == "auto" else self.kernel

    # -- fbtpu-shrink decision surface --

    def decision(self) -> dict:
        """The resolved compile/kernel decisions, per rule: S/C before →
        after the reduction pass (regex.dfa ShrinkStats), the chosen
        stride k, the compare-adds a byte its classing costs
        (``class_runs``), the k-group layout, and the scan/assoc
        resolution — what the benchmark's readers and references, the
        smoke and the unlock tests read. ``kernel_resolved`` is None
        until the program materializes on a backend (the resolution is
        a trace-time decision)."""
        rules = []
        for r, d in enumerate(self.dfas):
            st = d.shrink
            rules.append({
                "pattern": d.pattern,
                "s_raw": st.s_raw if st else None,
                "c_raw": st.c_raw if st else None,
                "s": d.n_states,
                "c": d.n_classes,
                "minimized": bool(st.minimized) if st else False,
                "k": self.k_by_rule[r],
                "class_runs": self._class_runs[r],
            })
        children = self._children or [self]
        resolved = {c.kernel_resolved for c in children}
        kernel_resolved = resolved.pop() if len(resolved) == 1 else "mixed"
        return {
            "rules": rules,
            "k": int(self.k),
            "k_groups": [int(c.k) for c in children],
            # the scan children in launch order, ``table_bytes`` the
            # tables as laid out (each within the child budget unless
            # the child is one rule)
            "children": [
                {"name": c.program_name(), "k": int(c.k),
                 "rules": len(c.dfas), "table_bytes": int(c.table_bytes)}
                for c in children],
            # one entry a mesh handle a child has built (none before
            # the first sharded launch): each child decides its own
            # variant (mesh_variant)
            "mesh_children": [
                {"k": int(c.k), "rules": len(c.dfas),
                 "variant": h.variant, "devices": h.n_devices}
                for c in self._children or [self]
                for h in list(c._mesh_cache.values())],
            "max_states": int(self.max_states),
            "assoc_eligible": self.max_states <= 64,
            "kernel": self.kernel,
            "kernel_resolved": kernel_resolved,
        }

    def program_name(self, suffix: str = "") -> str:
        """The jitted function's name — ``jit_<name>`` is the module
        name a profiler trace shows on ``XLA Modules``. One name a
        child, whatever it shares with a sibling (``name_tag``:
        ``…_k3``, ``…_k3_c1``, ``…_k3_c2``): the trace's readers take a
        launch as the sum over the names."""
        return (f"grep_{self.kernel_resolved or self._resolve_kernel()}"
                f"_S{self.max_states}_k{self.k}{self.name_tag}{suffix}")

    def scan_elements(self, B: int, L: int) -> int:
        """Gathered elements one launch over ``[K, B, L]`` planes steps
        through on the scan kernel: every rule of every child reads one
        table entry a row and super-step, ``Σ R_c · B · scan_steps(L,
        k_c)`` — what a launch's device time counts in (8-12 ns an
        element on a v5e from a child's tables of up to 50 MB, which
        ``_CHILD_TABLE_BUDGET`` keeps every child under; 17-25 from
        one of 144 MB; PERF.md, PRs 28, 33, 34 and 41). The same sum
        however the rules of a stride are cut into children. From
        shapes alone; the assoc kernel gathers ``S``× more and is not
        counted here."""
        return sum(len(c.dfas) * B * scan_steps(L, c.k)
                   for c in self._children or [self])

    def _merge_rule_axis(self, parts):
        """Reassemble per-child rule rows into the caller's order (one
        small jitted program, ``jit_grep_merge``)."""
        fn = self._merge_jit
        if fn is None:
            inv = self._inv_perm

            def grep_merge(*rows):
                return jnp.concatenate(rows, axis=0)[inv]

            fn = self._merge_jit = jax.jit(grep_merge)
        return fn(*parts)

    def _gather_planes(self, planes, lengths):
        """Each rule's plane and lengths from the distinct staged ones,
        on the device: ``[K, B, L]``, ``[K, B]`` → ``[R, B, L]``,
        ``[R, B]`` through the static ``plane_of`` (no-op for the
        one-plane-a-rule layout)."""
        if self.plane_of == tuple(range(self.n_planes)):
            return planes, lengths
        return (jnp.stack([planes[p] for p in self.plane_of]),
                jnp.stack([lengths[p] for p in self.plane_of]))

    def _materialize(self) -> None:
        """Transfer tables to the attached backend + build the jit.

        The tables live in ONE pytree (``self._tbl``) that the kernels
        take as an explicit first argument — the mesh matcher shards
        that same pytree by name through the partition-rules layer
        (ops.mesh.match_partition_rules), so the single-device and
        partitioned programs are the same code over the same tree."""
        with self._mat_lock:
            if self._jit is not None:
                return
            t = self._np
            self._tbl = {k: jnp.asarray(v) for k, v in t.items()}
            self.kernel_resolved = self._resolve_kernel()
            kern = (self._match_assoc_impl
                    if self.kernel_resolved == "assoc"
                    else self._match_impl)
            tbl = self._tbl

            def impl(planes, lengths):
                return kern(tbl, *self._gather_planes(planes, lengths))

            def impl_long(planes, lengths, lplanes, llengths, rows):
                # the frame in two groups (dispatch's ``long``): both
                # scans in this one module, over the one table; the
                # long rows' verdicts land in their columns (a pad
                # index lies past the mask and is dropped)
                return impl(planes, lengths).at[:, rows].set(
                    impl(lplanes, llengths), mode="drop")

            # one name for both: a launch is one module a child whichever
            # ran, which is what the trace's readers sum by
            impl.__name__ = impl_long.__name__ = self.program_name()
            self._impl = impl
            self._jit_long = jax.jit(impl_long)
            self._jit = jax.jit(impl)  # last: what try_ready() reads
            self._np = None  # tables now live on device; free host copy
            # the shrink/unlock audit line: S/C before→after, chosen
            # stride, resolved kernel
            log.info("grep program materialized: %s", self.decision())

    def try_ready(self) -> bool:
        """Non-blocking: True iff the device path is usable now. Kicks
        background attach on first call; until ready, callers run their
        bit-exact CPU fallback."""
        if self._children is not None:
            ready = [c.try_ready() for c in self._children]
            return all(ready)
        if self._jit is not None:
            return True
        from . import device

        if not device.ready():
            device.attach_async()
            return False
        self._materialize()
        return True

    # -- the kernel --

    @staticmethod
    def _byte_classes(t: dict, batch: "jnp.ndarray") -> "jnp.ndarray":
        """byte → class, per rule: ``[R, B, L]`` u8 → i32, equal to
        ``class_map[r][byte]``. The map's breakpoints as compare-adds
        (class_runs: the count is static, a padded run adds 0) — no
        gather over an ``[R, B, L]`` index, so XLA fuses it into the
        pass that pads and combines the classes."""
        byte = batch.astype(jnp.int32)
        cls = jnp.broadcast_to(t["class_base"][:, None, None], byte.shape)
        for n in range(t["run_start"].shape[1]):
            start = lax.index_in_dim(t["run_start"], n, axis=1)  # [R,1]
            delta = lax.index_in_dim(t["run_delta"], n, axis=1)
            cls = cls + jnp.where(byte >= start[:, :, None],
                                  delta[:, :, None], 0)
        return cls

    def _super_symbols(self, t: dict, batch: "jnp.ndarray",
                       lengths: "jnp.ndarray") -> "jnp.ndarray":
        """bytes → per-rule k-byte super-symbols: [R, B, Lk]. ``t`` is
        the table pytree (whole under single-device jit, this device's
        shard under the partitioned program — the kernels are uniform
        over the leading rule axis, so both read identically)."""
        R, B, L = batch.shape
        k = self.k
        cls = self._byte_classes(t, batch)  # [R,B,L] i32
        pos = jnp.arange(L, dtype=jnp.int32)
        pad = pos[None, None, :] >= lengths[:, :, None]  # [R,B,L]
        cls = jnp.where(pad, t["eol_cls"][:, None, None], cls)
        # append EOL block: guarantees >=1 EOL and rounds L to multiple of k
        Lk = scan_steps(L, k)
        eol_block = jnp.broadcast_to(
            t["eol_cls"][:, None, None], (R, B, Lk * k - L)
        )
        cls = jnp.concatenate([cls, eol_block], axis=2)
        cls = cls.reshape(R, B, Lk, k)
        # combine k classes into one super-symbol, per-rule radix C_r
        comb = cls[..., 0]
        for j in range(1, k):
            comb = comb * t["C"][:, None, None] + cls[..., j]
        return comb

    def _match_impl(self, t: dict, batch: "jnp.ndarray",
                    lengths: "jnp.ndarray"):
        R, B, L = batch.shape
        with jax.named_scope("grep.symbols"):
            comb = self._super_symbols(t, batch, lengths)
        comb_t = jnp.moveaxis(comb, 2, 0)  # [Lk, R, B]

        # + 0*lengths: ties the carry to the (possibly mesh-sharded) batch
        # so its varying-axes annotation matches the scan output under
        # shard_map; a no-op single-device
        state0 = jnp.broadcast_to(t["starts"][:, None], (R, B)) + 0 * lengths

        if R == 1 and B:
            # One rule: the scan is handed its table as the 1-D array
            # the gather reads, made once a launch out here. Left as
            # ``[1, N]`` XLA makes it 1-D inside the ``while``, every
            # step, wherever the table is an argument (the mesh program:
            # ``reduce.2 s32[N]``, 31-39 us a step on a v5e, 5.5 of the
            # 7.7 ms of an apache2 launch a chip; PERF.md, PRs 33, 38).
            # A program that closes over its tables compiles either
            # form to the same code. (Not for an empty batch: a take of
            # no indices forgets the carry's shard_map annotation.)
            flat, ck = t["trans_flat"][0], t["Ck"][0]

            def step(state, c_t):
                return jnp.take(flat, state * ck + c_t, axis=0), None
        else:
            def step(state, c_t):
                idx = state * t["Ck"][:, None] + c_t
                ns = jnp.take_along_axis(t["trans_flat"], idx, axis=1)
                return ns, None

        with jax.named_scope("grep.scan"):
            final, _ = lax.scan(step, state0, comb_t)
        return (final == ACC) & (lengths >= 0)

    def _match_assoc_impl(self, t: dict, batch: "jnp.ndarray",
                          lengths: "jnp.ndarray"):
        """Parallel-in-time DFA: the line's symbols are composed as
        transition FUNCTIONS instead of stepped as states.

        Each segment of m super-symbols is scanned once over ALL S
        states (m sequential steps on [R,B,G,S] gathers), producing a
        per-segment function table; segments then combine in a
        log2(G)-deep tree of compositions ``(f∘g)[s] = g[f[s]]``
        (take_along_axis over the state axis). Sequential depth drops
        from Lk to m + log2(G) at S× the gathered elements, and the
        elements are what a launch's time counts, on the chip as on a
        host CPU (``_resolve_kernel`` has the measurements), so ``auto``
        never resolves to it. Bit-identical to _match_impl
        (differentially tested), reachable by ``kernel="assoc"`` alone."""
        R, B, L = batch.shape
        m = self.segment
        S = self.max_states
        with jax.named_scope("grep.symbols"):
            comb = self._super_symbols(t, batch, lengths)  # [R, B, Lk]
        Lk = comb.shape[2]
        G = -(-Lk // m)
        # pad the segment grid to a power of two with all-EOL segments
        # (EOL is absorbing, so they compose as no-ops past the line)
        G2 = 1
        while G2 < G:
            G2 *= 2
        pad = G2 * m - Lk
        if pad:
            # super-symbol of k EOL classes: eol * (C^{k-1}+...+C+1)
            radix = jnp.ones_like(t["C"])
            eol_super = jnp.zeros_like(t["eol_cls"])
            for _ in range(self.k):
                eol_super = eol_super + t["eol_cls"] * radix
                radix = radix * t["C"]
            comb = jnp.concatenate(
                [comb, jnp.broadcast_to(eol_super[:, None, None],
                                        (R, B, pad))], axis=2)
        comb = comb.reshape(R, B, G2, m)

        def gather_rule(tf, idx):
            return tf[idx]

        def seg_step(F, c_j):  # c_j: [R, B, G2]
            idx = F * t["Ck"][:, None, None, None] + c_j[..., None]
            return jax.vmap(gather_rule)(t["trans_flat"], idx), None

        with jax.named_scope("grep.assoc_segments"):
            states = jnp.arange(S, dtype=jnp.int32)
            idx0 = (states[None, None, None, :]
                    * t["Ck"][:, None, None, None] + comb[..., 0:1])
            F = jax.vmap(gather_rule)(t["trans_flat"], idx0)  # [R,B,G2,S]
            if m > 1:
                comb_j = jnp.moveaxis(comb[..., 1:], 3, 0)  # [m-1,R,B,G2]
                F, _ = lax.scan(seg_step, F, comb_j)
        with jax.named_scope("grep.assoc_tree"):
            g = G2
            while g > 1:  # static tree: g halves each round
                f_half = F[:, :, 0::2]
                g_half = F[:, :, 1::2]
                F = jnp.take_along_axis(g_half, f_half, axis=3)
                g //= 2
        final_fn = F[:, :, 0, :]  # [R, B, S]: whole-line function
        start_idx = jnp.broadcast_to(t["starts"][:, None, None], (R, B, 1))
        final = jnp.take_along_axis(final_fn, start_idx, axis=2)[..., 0]
        # + 0*lengths keeps the shard_map varying-axes annotation tied
        # to the batch, mirroring _match_impl's state0 trick
        return (final + 0 * lengths == ACC) & (lengths >= 0)

    def _ensure_materialized(self) -> None:
        if self._jit is None:
            from . import device

            if not device.wait(60.0):
                raise RuntimeError(
                    f"device backend not attached: {device.status()}"
                )
            self._materialize()

    def dispatch(self, planes: np.ndarray, lengths: np.ndarray,
                 first_match: bool = False, long=None):
        """Launch the kernel WITHOUT forcing the result (jax dispatch
        is asynchronous) — the launch half of the double-buffered
        staging pipeline (core.chunk_batch.double_buffered): the caller
        stages the next segment while this one's kernel is in flight,
        then forces with np.asarray one segment behind.

        ``planes[K, B, L]`` / ``lengths[K, B]`` are the distinct staged
        fields; they cross to the device ONCE, whatever the number of
        rules or scan children that read them. → ``mask[R, B]`` bool,
        or with ``first_match`` the ``[B]`` i32 first-match vector.

        ``long``: the frame's few long rows as a narrow group of their
        own — ``(planes[K, Bl, Ll], lengths[K, Bl], rows[Bl])``, the
        same layout at a wider ``Ll``, ``rows`` each one's row among the
        ``B`` (a pad row's lies past them) — so that ``L`` is the width
        the rest needs and not the longest row's: the scan takes
        ``⌈L/k⌉ + 1`` dependent steps over every row it is given. Each
        child then runs ONE module that scans both groups over the one
        table and writes the long rows' verdicts over their columns of
        the mask (whatever the main group said of them: the caller
        stages them there as rows without a value), so what comes back
        has the shape and the meaning it has without."""
        # the copy-in and the enqueue apart: two spans inside the
        # caller's grep.dispatch, once a launch whatever the children
        with span("grep.put"):
            planes, lengths = jnp.asarray(planes), jnp.asarray(lengths)
            if long is not None:
                long = tuple(jnp.asarray(a) for a in long)
        n = len(self._children) if self._children is not None else 1
        with span("grep.call", children=n):
            mask = self._enqueue(planes, lengths, long)
            return first_match_of(mask) if first_match else mask

    def _enqueue(self, planes, lengths, long=None):
        """The jitted calls over planes that are on the device."""
        if self._children is not None:
            # child programs: every child launches (async) before the
            # merge touches any result, so the children overlap the
            # same way double-buffered segments do
            return self._merge_rule_axis(
                [c._enqueue(planes, lengths, long)
                 for c in self._children])
        self._ensure_materialized()
        if long is None:
            return self._jit(planes, lengths)
        return self._jit_long(planes, lengths, *long)

    def match(self, planes: np.ndarray, lengths: np.ndarray,
              first_match: bool = False) -> np.ndarray:
        """Run the kernel; returns bool [R, B] (numpy), or the i32 [B]
        first-match vector. Blocks up to the attach-wait deadline if
        the backend isn't up yet."""
        return np.asarray(self.dispatch(planes, lengths, first_match))

    # -- explicitly partitioned pjit program (the fbtpu-mesh plane) --

    def mesh_variant(self, mesh) -> str:
        """Which axis of the program shards across the mesh.

        ``"batch"`` (default): B splits across devices, the transition
        and class tables replicate — right whenever the tables are
        small relative to per-device memory. ``"rules"``: for large
        rule sets the replicated tables dominate (R × C^k rows), so
        the RULE axis shards instead — each
        device holds 1/n of the tables and matches the full batch
        against its own rules. Gated on the replicated-table footprint
        crossing ``ops.mesh.TABLE_BUDGET`` (64 MiB) or R ≥
        ``FBTPU_MESH_RULE_SHARD_R`` (default 64), and on R dividing the
        mesh evenly (no rule padding — a dead-rule pad row would cost a
        full batch scan).

        On a split parent this is the FIRST child's answer and no
        more: ``dispatch_mesh`` asks each child, and the children need
        not agree. Since a child of more than one rule lays out at most
        ``_CHILD_TABLE_BUDGET`` (16 MiB) of tables — the same bytes
        weighed here — four replicas never cross ``TABLE_BUDGET``:
        grep-tenants' nine children (5; 4, 5, 6, 11, 12; 4, 2; 1 rules)
        all take ``batch`` on four devices, the three that divide by
        four among them, where 36 of its k=3 rules in ONE child, 137 MB
        a device, would take ``rules``. ``decision()["mesh_children"]``
        reports what each child's handle took."""
        import os as _os

        from .mesh import TABLE_BUDGET

        if self._children is not None:
            # a split parent has no variant of its own: dispatch_mesh
            # lets every child decide for its own slice, and this is
            # only the first child's answer. The split is gated off the
            # R >= FBTPU_MESH_RULE_SHARD_R arm, not off the table arm:
            # a child whose rule count divides the mesh and whose
            # tables, replicated, cross TABLE_BUDGET would take "rules"
            # beside siblings on "batch" — which on up to four devices
            # the child budget rules out (decision()["mesh_children"]
            # says what each took)
            return self._children[0].mesh_variant(mesh)
        n_dev = mesh.devices.size
        R = len(self.dfas)
        if R < 2 or R % n_dev != 0:
            return "batch"
        min_r = int(_os.environ.get("FBTPU_MESH_RULE_SHARD_R", "64"))
        if self.table_bytes * n_dev > TABLE_BUDGET or R >= min_r:
            return "rules"
        return "batch"

    def _mesh_program(self, mesh, donate: str = "auto",
                      with_counts: bool = True):
        """Build the explicitly partitioned matcher for ``mesh`` WITHOUT
        placing anything on a device: a ``shard_map`` program under
        ``jax.jit`` with declarative PartitionSpecs from the
        partition-rules layer, and staged input buffers donated where
        (and only where) they can alias an output. Returns ``(fn,
        table shardings, batch sharding, lengths sharding, variant,
        donate_idx)`` — what :meth:`_mesh_handle` places and caches,
        and what tests/test_tpu_compile.py lowers for a described
        4-chip mesh.

        ``with_counts=False`` compiles the engine-dispatch variant
        WITHOUT the per-rule match totals: the counts are an O(R·B)
        reduction plus (batch variant) a cross-device ``psum`` — a
        sync point per segment launch — and the filter path never
        reads them. Only match_mesh's callers pay for counts."""
        from jax import shard_map
        from jax.sharding import NamedSharding
        from jax.sharding import PartitionSpec as P

        from .mesh import (aliasable_donations, match_partition_rules,
                           partition_rules)

        axis = mesh.axis_names[0]
        variant = self.mesh_variant(mesh)
        R = len(self.dfas)
        # the whole sharding layout of the program lives in the
        # declarative registry (ops.mesh.PARTITION_RULES) — every table
        # leaf named explicitly, the same tables fbtpu-speccheck
        # evaluates statically; only the staged-input/output specs are
        # per-variant here
        if variant == "rules":
            # each device matches its own rules: it is handed those
            # rules' planes only, [R, B, L] sharded on the rule axis
            # (dispatch_mesh expands the staged planes on the host)
            table_rules = partition_rules("grep-rules", axis)
            spec_b, spec_l = P(axis, None, None), P(axis, None)
            spec_mask, spec_counts = P(axis, None), P(axis)
            n_in = R
        else:
            # the staged planes [K, Bp, L] shard on the batch axis and
            # every device gathers its rules' planes from its own shard
            table_rules = partition_rules("grep-batch", axis)
            spec_b, spec_l = P(None, axis, None), P(None, axis)
            spec_mask, spec_counts = P(None, axis), P()
            n_in = self.n_planes
        tspecs = match_partition_rules(table_rules, self._tbl)

        kern = (self._match_assoc_impl
                if self.kernel_resolved == "assoc" else self._match_impl)

        def step(t, batch, lengths):
            if variant == "batch":
                batch, lengths = self._gather_planes(batch, lengths)
            mask = kern(t, batch, lengths)
            # i32 mask (not bool): exactly matches the donated lengths
            # buffer's sharded aval, so XLA aliases the verdict into
            # the staging buffer instead of allocating a new one
            if not with_counts:
                return mask.astype(jnp.int32)
            counts = jnp.sum(mask.astype(jnp.int32), axis=1)
            if variant == "batch":
                # global per-rule totals over ICI; the rules variant
                # already sees the full batch per shard
                counts = lax.psum(counts, axis_name=axis)
            return mask.astype(jnp.int32), counts

        step.__name__ = self.program_name("_mesh")
        out_specs = (spec_mask, spec_counts) if with_counts else spec_mask
        sm = shard_map(step, mesh=mesh,
                       in_specs=(tspecs, spec_b, spec_l),
                       out_specs=out_specs)
        tsh = jax.tree_util.tree_map(
            lambda s: NamedSharding(mesh, s), tspecs)
        sh_b = NamedSharding(mesh, spec_b)
        sh_l = NamedSharding(mesh, spec_l)
        out_sh = (NamedSharding(mesh, spec_mask),
                  NamedSharding(mesh, spec_counts)) if with_counts \
            else NamedSharding(mesh, spec_mask)

        # donation: arg 1 (batch) and arg 2 (lengths) are per-segment
        # staging buffers; donate exactly the subset whose sharded
        # (shape, dtype) matches an output — jax silently falls back to
        # a copy (plus a warning) for anything else, which donation_info
        # must never report as donated. Shapes vary per call, so the
        # donate set is computed from dtypes on a canonical shape:
        # lengths i32 [K, B] ↔ mask i32 [R, B] aliases when the planes
        # are one a rule (K == R); batch u8 never has an aliasable
        # output.
        Bc = mesh.devices.size * 8  # canonical shape for the aval match
        Lc = self.max_len
        donate_idx: tuple = ()
        if donate != "off":
            outs = [((R, Bc), np.int32, spec_mask)]
            if with_counts:
                outs.append(((R,), np.int32, spec_counts))
            cand = aliasable_donations(
                mesh,
                in_specs=[
                    ((n_in, Bc, Lc), np.uint8, spec_b, True),
                    ((n_in, Bc), np.int32, spec_l, True),
                ],
                out_specs=outs,
            )
            if donate == "all":
                cand = [0, 1]
            donate_idx = tuple(i + 1 for i in cand)  # tables are arg 0

        fn = jax.jit(sm, in_shardings=(tsh, sh_b, sh_l),
                     out_shardings=out_sh, donate_argnums=donate_idx)
        return fn, tsh, sh_b, sh_l, variant, donate_idx

    def _mesh_handle(self, mesh, donate: str = "auto",
                     with_counts: bool = True):
        """The :meth:`_mesh_program` of ``mesh``, with the tables
        device_put once under their shardings — built once and cached
        per mesh structure."""
        from .mesh import mesh_key

        self._ensure_materialized()
        key = (mesh_key(mesh), donate, with_counts)
        h = self._mesh_cache.get(key)
        if h is not None:
            return h
        fn, tsh, sh_b, sh_l, variant, donate_idx = self._mesh_program(
            mesh, donate, with_counts)
        tables_dev = jax.device_put(self._tbl, tsh)
        h = _MeshHandle(fn, tables_dev, sh_b, sh_l, variant,
                        int(mesh.devices.size), donate_idx, with_counts)
        self._mesh_cache[key] = h
        return h

    def dispatch_mesh(self, mesh, batch: np.ndarray, lengths: np.ndarray,
                      donate: str = "auto", with_counts: bool = True,
                      first_match: bool = False):
        """Launch the partitioned matcher over the staged planes
        ``batch[K, B, L]`` WITHOUT forcing (the mesh half of the
        double-buffered pipeline). Pads B up to the mesh size
        (batch variant; the rules variant shards R and takes B as-is),
        transfers the staged buffers with their input shardings — each
        device receives only its own shard — and returns
        ``(mask_i32 dev[R, Bp], counts dev | None, B, Bp)``
        (``with_counts=False`` skips the per-rule totals and their
        cross-device psum — the engine filter path never reads them;
        ``first_match`` returns the ``[Bp]`` i32 first-match vector in
        the mask's place).
        The staged device buffers are CONSUMED when donation is on:
        re-reading them after dispatch raises instead of silently
        aliasing the verdict bytes."""
        from .mesh import pad_to_devices

        if self._children is not None:
            # scan children: launch them all first (async), then merge
            # on the rule axis. Each child takes the host planes whole
            # and places them itself (a donated buffer cannot be shared
            # between programs). Children may pad B differently (the
            # rules variant is gated off, but keep the contract local):
            # each part is sliced back to B lazily before the concat.
            B = batch.shape[1]
            parts, count_parts, bps = [], [], []
            for c in self._children:
                m, ct, _b, bp = c.dispatch_mesh(
                    mesh, batch, lengths, donate, with_counts)
                parts.append(m)
                count_parts.append(ct)
                bps.append(bp)
            if len(set(bps)) == 1:
                # the normal case: every child padded B identically
                # (same mesh, batch variant), so the merged mask keeps
                # the padded columns and Bp describes it — the same
                # contract as the unsplit program
                Bp = bps[0]
            else:
                # children disagree (a child crossed into the rules
                # variant): normalize to the unpadded batch
                parts = [p[:, :B] for p in parts]
                Bp = B
            # each child has written its own grep.put and grep.call;
            # the merge is the parent's part of the enqueue
            with span("grep.call", children=len(self._children)):
                mask = self._merge_rule_axis(parts)
                counts = (self._merge_rule_axis(count_parts)
                          if with_counts else None)
                if first_match:
                    mask = first_match_of(mask)
            return mask, counts, B, Bp

        h = self._mesh_handle(mesh, donate, with_counts)
        B = batch.shape[1]
        # the host's share of the copy-in (padding, the rules variant's
        # gather, the contiguous copy) and the two transfers
        with span("grep.put", variant=h.variant,
                  devices=h.n_devices) as put:
            if h.variant == "batch":
                Bp = pad_to_devices(B, h.n_devices)
                batch, lengths = _pad_rows(batch, lengths, Bp)
            else:
                Bp = B
                idx = list(self.plane_of)
                batch, lengths = batch[idx], lengths[idx]
            put.set_metadata(bytes=batch.nbytes + lengths.nbytes)
            bd = jax.device_put(
                np.ascontiguousarray(batch, dtype=np.uint8), h.sh_b)
            ld = jax.device_put(
                np.ascontiguousarray(lengths, dtype=np.int32), h.sh_l)
        with span("grep.call", children=1):
            if with_counts:
                mask_i32, counts = h.fn(h.tables, bd, ld)
            else:
                mask_i32, counts = h.fn(h.tables, bd, ld), None
            if first_match:
                mask_i32 = first_match_of(mask_i32)
        return mask_i32, counts, B, Bp

    def match_mesh(self, mesh, batch: np.ndarray, lengths: np.ndarray,
                   donate: str = "auto"):
        """Run the partitioned matcher over the staged planes
        ``batch[K, B, L]`` and force: returns
        ``(mask[R, B] bool numpy, counts[R] numpy, Bp)`` — bit-exact
        with :meth:`match` and the CPU chain (tier-1 ``mesh`` tests)."""
        mask_i32, counts, B, Bp = self.dispatch_mesh(
            mesh, batch, lengths, donate)
        mask = np.asarray(mask_i32).astype(bool)[:, :B]
        return mask, np.asarray(counts), Bp

    def donation_info(self, mesh, B: int = 64,
                      donate: str = "auto") -> dict:
        """Compile-level donation status for the tier-1 donation
        tests: which staged args are declared donated, whether
        the lowered module carries the input→output aliases
        (``tf.aliasing_output``), plus the variant and per-device batch
        share for a B-row segment."""
        from .mesh import donation_report, pad_to_devices

        if self._children is not None:
            rep = self._children[0].donation_info(mesh, B, donate)
            rep["k_groups"] = [int(c.k) for c in self._children]
            return rep

        h = self._mesh_handle(mesh, donate)
        R = len(self.dfas)
        Bp = pad_to_devices(B, h.n_devices) if h.variant == "batch" else B
        n_in = self.n_planes if h.variant == "batch" else R
        batch = np.zeros((n_in, Bp, self.max_len), dtype=np.uint8)
        lengths = np.full((n_in, Bp), -1, dtype=np.int32)
        bd = jax.device_put(batch, h.sh_b)
        ld = jax.device_put(lengths, h.sh_l)
        lowered = h.fn.lower(h.tables, bd, ld)
        names = ["tables", "batch", "lengths"]
        rep = donation_report(lowered, h.donate_idx, names)
        rep.update({
            "variant": h.variant,
            "devices": h.n_devices,
            "per_device_batch_share": (
                Bp // h.n_devices if h.variant == "batch" else Bp),
            "per_device_rule_share": (
                R // h.n_devices if h.variant == "rules" else R),
        })
        return rep


class _MeshHandle:
    """One mesh's compiled partitioned matcher + resident sharded
    tables (built once per mesh structure by ``_mesh_handle``)."""

    __slots__ = ("fn", "tables", "sh_b", "sh_l", "variant",
                 "n_devices", "donate_idx", "with_counts")

    def __init__(self, fn, tables, sh_b, sh_l, variant, n_devices,
                 donate_idx, with_counts=True):
        self.fn = fn
        self.tables = tables
        self.sh_b = sh_b
        self.sh_l = sh_l
        self.variant = variant
        self.n_devices = n_devices
        self.donate_idx = donate_idx
        self.with_counts = with_counts


def _pad_rows(planes: np.ndarray, lengths: np.ndarray, Bp: int):
    """Pad the batch axis of staged planes up to ``Bp`` rows (zero
    bytes, length -1: a missing value, no rule accepts it)."""
    K, B, L = planes.shape
    if Bp == B:
        return planes, lengths
    return (np.concatenate(
                [planes, np.zeros((K, Bp - B, L), dtype=planes.dtype)],
                axis=1),
            np.concatenate(
                [lengths, np.full((K, Bp - B), -1, dtype=lengths.dtype)],
                axis=1))


class SpanProgram:
    """One parser regex's capture spans on the device — the program
    beside ``GrepProgram`` that returns more than a verdict.

    ``dispatch(planes_u8[1, B, L], lengths[1, B]) -> (ok bool[B],
    spans[B, G, 2])``: two dependent scans a launch over the tables of
    ``regex.spans`` — right to left ``r_i = rev[r_{i+1}, cls(x[i])]``,
    emitting a reverse state for every byte (``[L, B]`` i32), then left
    to right ``(u_{i+1}, tags_i) = fwd[u_i, cls(x[i]), r_{i+1}]``, the
    carry holding the ``2G`` offsets next to the walk state (a tag's
    bit writes ``i``). Stride 1: one ``[B]`` gather a byte and a pass.
    ``spans[b, g]`` is group ``g``'s (start, end) in bytes of the
    staged value, (-1, -1) for a group the walk never entered and for
    every group of a row that does not match; i16 where ``max_len``
    fits it. Byte classing is ``GrepProgram._byte_classes`` (no
    gather). One device: a parser launches unsharded, as
    ``rewrite_tag`` does."""

    n_planes = 1

    def __init__(self, tables, max_len: int = 512):
        if not HAVE_JAX:
            raise RuntimeError("jax is unavailable")
        self.tables = tables
        self.max_len = max_len
        self.names = list(tables.names)
        self.span_dtype = np.int16 if max_len < (1 << 15) else np.int32
        base, start, delta = class_runs(tables.class_map)
        NW, C, NR = tables.fwd.shape
        self._np = {
            "rev_flat": np.ascontiguousarray(tables.rev.reshape(-1)),
            "fwd_flat": np.ascontiguousarray(tables.fwd.reshape(-1)),
            "class_base": np.asarray([base], dtype=np.int32),
            "run_start": (start if start.size else
                          np.asarray([256], np.int32))[None, :],
            "run_delta": (delta if delta.size else
                          np.asarray([0], np.int32))[None, :],
        }
        self._shape = (NW, C, NR)
        self.kernel_resolved: Optional[str] = None
        self._jit = None
        self._mat_lock = threading.Lock()

    def decision(self) -> dict:
        """What was built, for the benchmark's references and the
        log."""
        NW, C, NR = self._shape
        return {"pattern": self.tables.pattern, "groups": self.names,
                "nfa_states": self.tables.nfa_states,
                "walk_states": NW - 2, "classes": C, "reverse_states": NR,
                "table_bytes": 4 * (NW * C * NR + NR * C),
                "span_dtype": np.dtype(self.span_dtype).name,
                "kernel_resolved": self.kernel_resolved}

    def program_name(self) -> str:
        NW, _C, NR = self._shape
        return f"grep_spans_W{NW - 2}_R{NR}"

    def scan_elements(self, B: int, L: int) -> int:
        """Gathered elements of one launch, as
        ``GrepProgram.scan_elements`` counts them: one a row and byte
        in the reverse pass (``L`` steps), one in the walk (``L + 1``)."""
        return B * (2 * L + 1)

    def _materialize(self) -> None:
        with self._mat_lock:
            if self._jit is not None:
                return
            tbl = {k: jnp.asarray(v) for k, v in self._np.items()}

            def impl(planes, lengths):
                return self._spans_impl(tbl, planes[0], lengths[0])

            impl.__name__ = self.program_name()
            self.kernel_resolved = "spans"
            self._jit = jax.jit(impl)
            self._np = None
            log.info("span program materialized: %s", self.decision())

    def try_ready(self) -> bool:
        if self._jit is not None:
            return True
        from . import device

        if not device.ready():
            device.attach_async()
            return False
        self._materialize()
        return True

    def _spans_impl(self, t: dict, plane: "jnp.ndarray",
                    lengths: "jnp.ndarray"):
        B, L = plane.shape
        _NW, C, NR = self._shape
        tb = self.tables
        G2 = 2 * len(self.names)
        eol = jnp.int32(tb.eol_class)
        with jax.named_scope("spans.symbols"):
            cls = GrepProgram._byte_classes(t, plane[None])[0]  # [B, L]
            pos = jnp.arange(L, dtype=jnp.int32)
            cls = jnp.where(pos[None, :] >= lengths[:, None], eol, cls)
            cls_t = cls.T  # [L, B]

        def rev_step(r, c_i):
            r = jnp.take(t["rev_flat"], r * C + c_i)
            return r, r

        # + 0*lengths: the carry's type follows the batch, as in
        # _match_impl
        r_eol = jnp.full((B,), tb.r_eol, dtype=jnp.int32) + 0 * lengths
        with jax.named_scope("spans.reverse"):
            # r_i for every byte; the set at the first EOL is the same
            # whatever follows it, so the scan starts from there
            _, rr = lax.scan(rev_step, r_eol, cls_t, reverse=True)
        # what step i reads is r_{i+1}; past the last symbol: the empty
        # set, id 0
        r_next = jnp.concatenate(
            [rr[1:], r_eol[None], jnp.zeros((1, B), jnp.int32)], axis=0)
        cls_f = jnp.concatenate(
            [cls_t, jnp.broadcast_to(eol, (1, B))], axis=0)  # [L+1, B]
        mask = jnp.int32((1 << tb.state_bits) - 1)
        bit = jnp.arange(G2, dtype=jnp.int32)[:, None]

        def fwd_step(carry, x):
            u, sp = carry
            c_i, r_i, i = x
            packed = jnp.take(t["fwd_flat"], (u * C + c_i) * NR + r_i)
            tags = packed >> tb.state_bits
            sp = jnp.where((tags[None, :] >> bit) & 1 != 0, i, sp)
            return (packed & mask, sp), None

        u0 = jnp.full((B,), tb.start, dtype=jnp.int32) + 0 * lengths
        sp0 = jnp.full((G2, B), -1, dtype=jnp.int32) + 0 * lengths[None]
        with jax.named_scope("spans.walk"):
            (u, sp), _ = lax.scan(
                fwd_step, (u0, sp0),
                (cls_f, r_next, jnp.arange(L + 1, dtype=jnp.int32)))
        ok = (u == 1) & (lengths >= 0)  # regex.spans.ACCEPTED
        sp = jnp.where(ok[None, :], sp, -1)
        return ok, sp.T.reshape(B, G2 // 2, 2).astype(self.span_dtype)

    def dispatch(self, planes: np.ndarray, lengths: np.ndarray):
        """Launch WITHOUT forcing (as ``GrepProgram.dispatch``): the one
        staged plane ``[1, B, L]`` and its lengths ``[1, B]`` → device
        ``(ok[B], spans[B, G, 2])``."""
        if self._jit is None:
            from . import device

            if not device.wait(60.0):
                raise RuntimeError(
                    f"device backend not attached: {device.status()}")
            self._materialize()
        with span("grep.put"):
            planes, lengths = jnp.asarray(planes), jnp.asarray(lengths)
        with span("grep.call", children=1):
            return self._jit(planes, lengths)

    def spans(self, planes: np.ndarray, lengths: np.ndarray):
        """Run and force: numpy ``(ok[B], spans[B, G, 2])``."""
        ok, sp = self.dispatch(planes, lengths)
        return np.asarray(ok), np.asarray(sp)


def grep_first_match(mask):
    """``mask[R, B]`` (bool or i32) → ``[B]`` i32: the first rule in row
    order that accepts the record, or -1 — the reduction of a
    first-match-wins rule list."""
    hit = mask != 0
    first = jnp.argmax(hit, axis=0).astype(jnp.int32)
    return jnp.where(hit.any(axis=0), first, jnp.int32(-1))


#: the same on the device, after the children are merged: one small
#: jitted program (``jit_grep_first_match``)
first_match_of = jax.jit(grep_first_match) if HAVE_JAX else None


@functools.lru_cache(maxsize=64)
def _cached_program(patterns: Tuple[str, ...], max_len: int,
                    plane_of: Optional[Tuple[int, ...]]) -> "GrepProgram":
    from ..regex.dfa import compile_dfa

    return GrepProgram([compile_dfa(p) for p in patterns], max_len,
                       plane_of=plane_of)


def program_for(patterns: Sequence[str], max_len: int = 512,
                plane_of: Optional[Sequence[int]] = None) -> "GrepProgram":
    """Compiled-program cache keyed by the pattern tuple and the
    rule→plane index."""
    return _cached_program(
        tuple(patterns), max_len,
        None if plane_of is None else tuple(int(p) for p in plane_of))


@functools.lru_cache(maxsize=16)
def _cached_span_program(pattern: str, max_len: int) -> "SpanProgram":
    from ..regex import parse
    from ..regex.spans import compile_spans

    return SpanProgram(compile_spans(parse(pattern)), max_len)


def span_program_for(pattern: str, max_len: int = 512) -> "SpanProgram":
    """The span program of one parser regex, cached by pattern; raises
    ``regex.spans.SpanDecline`` (or ``UnsupportedRegex``) with the
    reason where the pattern lies outside the class it is exact in."""
    return _cached_span_program(pattern, max_len)
