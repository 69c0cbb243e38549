"""fbtpu-xray: the interprocedural device launch-graph and PCIe
transfer-budget analyzer.

The measured wall is launches-per-PCIe-crossing (ROADMAP item 1): every
filter stage is its own jit/pjit launch with its own staging, and the
verdict comes home as a mask the host scatters. Nothing in the tree
could *see* or *gate* that cost — this module makes it reviewable. It
walks, from each ``FilterPlugin.process_batch`` and the flux absorb
entry, the call closure down to every
``DeviceLane.run``/``begin``/dispatch/jit/pjit/shard_map site (the
tail-call + self-method inlining of ``analysis/batch.py`` plus the
name-closure of ``devlane.py``) and emits a per-tag **device launch
graph**: launches per staged segment, host→device and device→host byte
crossings sized symbolically from the ``[R, B, L]`` staging shapes, the
static donate/alias set cross-checked against
``ops.mesh.aliasable_donations``, host scatter passes, and
replicated-table bytes.

The model the walker implements (kept honest by the tier-1 parity test
against the ``device.dispatch`` failpoint / lane launch counters on the
simulated 8-device mesh):

- one ``lane.run(launch, fallback)`` / ``lane.begin(launch, fallback)``
  is ONE watched launch; dispatch calls inside the closure defs handed
  to the lane are absorbed into it (the worker forces there — that is
  the sanctioned sync point, not a hazard);
- a bare dispatch call (``dispatch_mesh``/``sharded_*``/``device_*`` or
  ``.dispatch``/``.match`` on a ``*program*``/``*prefilter*`` chain) is
  one unguarded launch;
- ``kernels.guarded_segment_counts`` wraps its own lane launch
  (cross-module knowledge, one name);
- the callback handed to ``core.chunk_batch.double_buffered`` runs once
  per staged segment, so its launches ARE the chain's
  launches-per-segment; loops over groups count their body once and the
  sites carry ``in_loop`` (×G multiplicity is data-dependent);
- branches contribute the maximum over alternatives, and a branch that
  returns does not chain into the statements after the ``if``.

On top of the graph, five rules (suppress with
``# fbtpu-lint: allow(<rule>)`` + justification; shipped debt is
baselined in ``analysis/launch_budget.json`` under the PR-3
``(path, rule, message)`` key scheme):

- ``device-multi-launch-chain`` — an entry's chain reaches more than
  one device launch per staged segment (the fusion target is one).
- ``device-undonated-buffer`` — a staged buffer enters a pjit launch
  outside the donate set: ``donate="off"``/``False`` at a mesh dispatch
  site (error), or the structural ``[R, B, L]`` u8 batch gap — no
  aliasable u8 output exists, so the byte matrix crosses PCIe
  un-donated every segment (warning; PR-8's known gap, gated by the
  budget file until a same-aval survivor-bytes output lands).
- ``device-host-roundtrip`` — a chain that launches on device AND
  re-walks host bytes with the verdict (``native.compact`` scatter):
  the mask came home just to re-index the chunk.
- ``device-sync-in-staging-loop`` — ``np.asarray``/
  ``block_until_ready``/``device_get`` forcing a dispatch result inside
  the double-buffered dispatch callback, the stage generator, or a
  ``segment_bounds`` loop — defeats the staging overlap. Forcing inside
  the lane closure (worker-side) or the ``collect`` callback (one
  segment behind) is the sanctioned pattern and does not fire.
- ``stage-redundant-copy`` — ``.copy()`` on arrays staged by the
  arena-returning ``native.stage_field`` where the caller-buffer
  ``native.stage_field_into`` applies (stage straight into the
  transfer matrix; the mesh path already does).
"""

from __future__ import annotations

import ast
from typing import Any, Dict, Iterable, List, Optional, Set, Tuple

from . import Finding, Module, Rule
from .registry import BUDGET_PARAMS, LAUNCH_ENTRIES

__all__ = [
    "LaunchGraphRules", "build_launch_graph", "graph_to_dot",
    "budget_snapshot", "compare_budget", "donation_crosscheck",
    "table_bytes", "EXAMPLE_TABLES",
]

#: Engine-facing device planes (same boundary as devlane/qos: ops/ is
#: the kernel layer the lanes wrap, not a chain entry).
SCOPES = ("fluentbit_tpu/plugins/", "fluentbit_tpu/flux/")

#: One DeviceLane.run/begin == one watched launch (``begin`` bumps the
#: lane's ``launches`` stat; the ``device.dispatch`` failpoint fires on
#: the worker — the counters the parity test reads).
LANE_LAUNCH = frozenset({"run", "begin"})

#: Helpers that wrap their own lane launch, known by name where the
#: caller imports them from another module (flux/kernels.py;
#: filter_grep's staged launch, which filter_rewrite_tag shares). In
#: their own module the walker inlines them instead.
GUARDED_LAUNCH_FNS = frozenset({"guarded_segment_counts", "staged_match"})

#: Raw jit/pjit/shard_map dispatch terminals, by launch kind.
KIND_BY_NAME = {
    "dispatch_mesh": "grep-mesh", "match_mesh": "grep-mesh",
    "sharded_segment_counts": "flux-segment-counts",
    "guarded_segment_counts": "flux-segment-counts",
    "staged_match": "grep-mesh",
    "sharded_hll_registers": "flux-hll", "sharded_hll_update": "flux-hll",
    "device_registers": "flux-hll",
    "sharded_cms_table": "flux-cms", "sharded_cms_update": "flux-cms",
    "device_table": "flux-cms",
    "sharded_fused_absorb": "flux-fused", "fused_absorb": "flux-fused",
}
DISPATCH_NAMES = frozenset(KIND_BY_NAME) - GUARDED_LAUNCH_FNS

#: ``.dispatch(``/``.match(`` count as a launch only on a chain whose
#: names mention a compiled program (``self._program.dispatch``,
#: ``self._prefilter.match``).
PROGRAM_ATTRS = frozenset({"dispatch", "match"})
PROGRAM_RECV = ("program", "prefilter")

MESH_DISPATCH_SITES = frozenset({"dispatch_mesh", "match_mesh"})

#: Host-side force points (the sync rule's terminals).
SYNC_NAMES = frozenset({"asarray", "block_until_ready", "device_get"})

#: Host scatter: the verdict re-indexes the chunk bytes.
SCATTER_NAMES = frozenset({"compact"})

#: Arena-view stager (the redundant-copy rule's taint source) and its
#: caller-buffer replacement.
ARENA_STAGER = "stage_field"

SEGMENT_ITERS = frozenset({"segment_bounds"})
PIPELINE_FN = "double_buffered"

_SEVERITY = {
    "device-multi-launch-chain": "warning",
    "device-undonated-buffer": "warning",
    "device-host-roundtrip": "warning",
    "device-sync-in-staging-loop": "error",
    "stage-redundant-copy": "error",
}

#: Per-launch-kind transfer shapes (bytes, symbolic in the canonical
#: parameter names of ``registry.BUDGET_PARAMS``): the ``[R, B, L]``
#: staging algebra of ops/grep (mesh: mask i32 aliases the donated
#: lengths buffer — ``ops.mesh.aliasable_donations`` is the
#: cross-check; the u8 batch never has an aliasable output) and the
#: flux sketch planes (registers/tables ride along per launch until the
#: fusion PR keeps them device-resident across segments).
TRANSFER_SHAPES: Dict[str, Dict[str, List[Tuple[str, str, str, bool]]]] = {
    "grep-mesh": {
        # sized at one staged plane a rule (every rule on its own key):
        # the upper bound — rules that share a key share its plane
        "h2d": [("batch", "R*Bp*L", "uint8", False),
                ("lengths", "4*R*Bp", "int32", True)],
        "d2h": [("mask", "4*R*Bp", "int32", False)],
    },
    "grep-jit": {
        "h2d": [("batch", "R*Bp*L", "uint8", False),
                ("lengths", "4*R*Bp", "int32", False)],
        "d2h": [("mask", "R*Bp", "bool", False)],
    },
    "flux-segment-counts": {
        "h2d": [("seg", "8*B", "int64", False),
                ("ones", "4*B", "int32", False)],
        "d2h": [("counts", "4*G", "int32", False)],
    },
    "flux-hll": {
        "h2d": [("batch", "B*L", "uint8", False),
                ("lengths", "4*B", "int32", False),
                ("registers", "M_hll", "uint8", False)],
        "d2h": [("registers", "M_hll", "uint8", False)],
    },
    "flux-cms": {
        "h2d": [("batch", "B*L", "uint8", False),
                ("lengths", "4*B", "int32", False),
                ("table", "8*M_cms", "int64", False)],
        "d2h": [("table", "8*M_cms", "int64", False)],
    },
    # the ONE-launch fused flux absorb (counts + HLL stack + count-min
    # — the cashed fbtpu-fuseplan merge): everything the three unfused
    # programs staged, once, with the freshly-stacked [Gp, m] register
    # snapshot the only donated input (it aliases its output exactly;
    # the table snapshot must survive for the host-twin fallback)
    "flux-fused": {
        "h2d": [("seg", "4*Bp", "int32", False),
                ("valid", "4*Bp", "int32", False),
                ("batch", "Bp*L", "uint8", False),
                ("lengths", "4*Bp", "int32", False),
                ("registers", "Gp*M_hll", "uint8", True),
                ("comp", "Bp*L", "uint8", False),
                ("comp_len", "4*Bp", "int32", False),
                ("table", "8*M_cms", "int64", False)],
        "d2h": [("counts", "4*Gp", "int32", False),
                ("registers", "Gp*M_hll", "uint8", False),
                ("table", "8*M_cms", "int64", False)],
    },
}

#: Worked-example DFA matrices for the table-bytes accounting (the
#: rewrite_tag / log_to_metrics satellites share filter_grep's rule
#: machinery, so their native GrepTables footprint is the same
#: ``S × C`` i32 algebra — sized here post-shrink, the only honest
#: number after the PR-10 reducer).
APACHE2 = (
    r'^(?<host>[^ ]*) [^ ]* (?<user>[^ ]*) \[(?<time>[^\]]*)\] '
    r'"(?<method>\S+)(?: +(?<path>[^ ]*) +\S*)?" '
    r'(?<code>[^ ]*) (?<size>[^ ]*)'
    r'(?: "(?<referer>[^\"]*)" "(?<agent>.*)")?$'
)
EXAMPLE_TABLES = {
    "filter_grep[apache2]": (APACHE2,),
    "filter_rewrite_tag[apache2]": (APACHE2,),
    "filter_log_to_metrics[5xx]": (r"50[0-9]",),
}


def _terminal(func: ast.AST) -> Optional[str]:
    if isinstance(func, ast.Attribute):
        return func.attr
    if isinstance(func, ast.Name):
        return func.id
    return None


def _chain_names(node) -> Set[str]:
    out: Set[str] = set()
    while True:
        if isinstance(node, ast.Attribute):
            out.add(node.attr)
            node = node.value
        elif isinstance(node, ast.Call):
            node = node.func
        else:
            break
    if isinstance(node, ast.Name):
        out.add(node.id)
    return out


def _is_program_call(call: ast.Call) -> bool:
    f = call.func
    if not (isinstance(f, ast.Attribute) and f.attr in PROGRAM_ATTRS):
        return False
    chain = " ".join(_chain_names(f.value)).lower()
    return any(frag in chain for frag in PROGRAM_RECV)


def _is_lane_call(call: ast.Call) -> bool:
    f = call.func
    if not (isinstance(f, ast.Attribute) and f.attr in LANE_LAUNCH):
        return False
    chain = " ".join(_chain_names(f.value)).lower()
    return "lane" in chain


def _walk_no_nested(node: ast.AST) -> Iterable[ast.AST]:
    """ast.walk that does not descend into nested function/class defs
    or lambdas (their bodies run later, under their own context)."""
    stack = [node]
    while stack:
        n = stack.pop()
        yield n
        for child in ast.iter_child_nodes(n):
            if isinstance(child, (ast.FunctionDef, ast.AsyncFunctionDef,
                                  ast.ClassDef, ast.Lambda)):
                continue
            stack.append(child)


def _local_defs(fn: ast.AST) -> Dict[str, List[ast.FunctionDef]]:
    """Function-local nested defs by name, wherever they sit in the
    body (branch-local ``def launch(...)`` variants included — grep's
    dispatch callback defines one per mesh arm), without descending
    into the nested defs themselves."""
    out: Dict[str, List[ast.FunctionDef]] = {}
    stack = list(ast.iter_child_nodes(fn))
    while stack:
        n = stack.pop()
        if isinstance(n, (ast.FunctionDef, ast.AsyncFunctionDef)):
            out.setdefault(n.name, []).append(n)
            continue
        if isinstance(n, (ast.ClassDef, ast.Lambda)):
            continue
        stack.extend(ast.iter_child_nodes(n))
    return out


def _contains_dispatch(node: ast.AST) -> bool:
    """Any device-dispatch-ish call in the subtree (nested defs
    included — classifying a launch closure wants the full body)."""
    for sub in ast.walk(node):
        if isinstance(sub, ast.Call):
            t = _terminal(sub.func)
            if t in KIND_BY_NAME or _is_program_call(sub) \
                    or _is_lane_call(sub):
                return True
    return False


def _closure_kind(defs: List[ast.AST]) -> Tuple[str, bool]:
    """Classify a lane launch by the dispatch terminals inside its
    closure defs → (kind, lane_guarded)."""
    kinds: List[str] = []
    for d in defs:
        for sub in ast.walk(d):
            if isinstance(sub, ast.Call):
                t = _terminal(sub.func)
                if t in KIND_BY_NAME:
                    kinds.append(KIND_BY_NAME[t])
                elif _is_program_call(sub):
                    kinds.append("grep-jit")
    # mesh beats the unsharded fallback branch inside the same closure;
    # the fused absorb beats its constituent kinds (a closure that
    # dispatches the fused program IS one fused launch)
    for pref in ("flux-fused", "grep-mesh", "flux-segment-counts",
                 "flux-hll", "flux-cms", "grep-jit"):
        if pref in kinds:
            return pref, True
    return "device", True


class _Site:
    __slots__ = ("line", "col", "kind", "what", "lane", "in_loop")

    def __init__(self, line, col, kind, what, lane, in_loop):
        self.line, self.col = line, col
        self.kind, self.what = kind, what
        self.lane, self.in_loop = lane, in_loop

    def as_dict(self) -> Dict[str, Any]:
        return {"line": self.line, "kind": self.kind, "what": self.what,
                "lane": self.lane, "in_loop": self.in_loop}


class _Ctx:
    """Walk context: loop nesting, per-segment staging scope, the
    lexical scope chain of nested defs, inline depth, plus the names
    bound from segment_bounds / dispatch calls in the current function
    (the segment-loop and pending-device-value taint sets)."""

    __slots__ = ("in_loop", "per_segment", "scopes", "depth",
                 "seg_names", "pending")

    def __init__(self, in_loop=False, per_segment=False, scopes=None,
                 depth=0):
        self.in_loop = in_loop
        self.per_segment = per_segment
        self.scopes = scopes if scopes is not None else []
        self.depth = depth
        self.seg_names: Set[str] = set()
        self.pending: Set[str] = set()

    def child(self, **kw) -> "_Ctx":
        c = _Ctx(self.in_loop, self.per_segment, list(self.scopes),
                 self.depth)
        c.seg_names = set(self.seg_names)
        c.pending = set(self.pending)
        for k, v in kw.items():
            setattr(c, k, v)
        return c

    def lookup(self, name: str) -> List[ast.FunctionDef]:
        for scope in reversed(self.scopes):
            if name in scope:
                return scope[name]
        return []


class _EntryWalk:
    """One entry's closure walk: max-path launch count + site/scatter/
    sync collection. Methods of the owning class and module-level
    functions inline by name (cycle-guarded, depth-capped like
    analysis/batch.py)."""

    def __init__(self, module: Module, methods: Dict[str, ast.FunctionDef],
                 functions: Dict[str, ast.FunctionDef]):
        self.module = module
        self.methods = methods
        self.functions = functions
        self.sites: Dict[Tuple[int, int], _Site] = {}
        self.scatters: Dict[Tuple[int, int], ast.Call] = {}
        self.sync_hits: Dict[Tuple[int, int], Tuple[ast.Call, str]] = {}
        self.staged = False
        self._inlining: Set[str] = set()

    # -- entry ---------------------------------------------------------

    def run(self, fn: ast.FunctionDef) -> int:
        count, _term = self._fn_body(fn, _Ctx())
        return count

    def _fn_body(self, fn: ast.FunctionDef, ctx: _Ctx) -> Tuple[int, bool]:
        scope = _local_defs(fn)
        # names bound from segment_bounds(...): loops over them are the
        # staged segment loop (filter_grep: bounds = segment_bounds(..))
        seg_names = set()
        pending_names = set()
        for sub in _walk_no_nested(fn):
            if isinstance(sub, ast.Assign) \
                    and isinstance(sub.value, ast.Call):
                t = _terminal(sub.value.func)
                names = {tgt.id for tgt in sub.targets
                         if isinstance(tgt, ast.Name)}
                if t in SEGMENT_ITERS:
                    seg_names |= names
                if t is not None and (t in KIND_BY_NAME
                                      or _is_lane_call(sub.value)
                                      or _is_program_call(sub.value)):
                    pending_names |= names
        sub_ctx = ctx.child(scopes=ctx.scopes + [scope])
        sub_ctx.seg_names = seg_names
        sub_ctx.pending = pending_names
        return self._stmts(fn.body, sub_ctx)

    # -- statements (right-to-left suffix counting: a branch that
    #    returns does not chain into the statements after the if) ------

    def _stmts(self, stmts: List[ast.stmt], ctx: _Ctx) -> Tuple[int, bool]:
        suffix = 0
        terminated = False
        for stmt in reversed(stmts):
            if isinstance(stmt, (ast.Return, ast.Raise)):
                val = stmt.value if isinstance(stmt, ast.Return) \
                    else getattr(stmt, "exc", None)
                suffix = self._expr(val, ctx) if val is not None else 0
                terminated = True
            elif isinstance(stmt, ast.If):
                t = self._expr(stmt.test, ctx)
                b, bt = self._stmts(stmt.body, ctx)
                e, et = self._stmts(stmt.orelse, ctx)
                through_b = b if bt else b + suffix
                through_e = e if et else e + suffix
                suffix = t + max(through_b, through_e)
                # both branches returning/raising → nothing after this
                # if runs; otherwise the block's fall-through status is
                # whatever the trailing statements already decided
                terminated = terminated or (bt and et)
            elif isinstance(stmt, (ast.For, ast.AsyncFor)):
                it = self._expr(stmt.iter, ctx)
                seg_loop = self._is_segment_loop(stmt, ctx)
                body_ctx = ctx.child(
                    in_loop=True,
                    per_segment=ctx.per_segment or seg_loop)
                b, _ = self._stmts(stmt.body, body_ctx)
                o, _ = self._stmts(stmt.orelse, ctx)
                suffix += it + b + o
            elif isinstance(stmt, ast.While):
                t = self._expr(stmt.test, ctx)
                body_ctx = ctx.child(in_loop=True)
                b, _ = self._stmts(stmt.body, body_ctx)
                suffix += t + b
            elif isinstance(stmt, ast.Try):
                b, bt = self._stmts(stmt.body, ctx)
                h = 0
                for handler in stmt.handlers:
                    hc, _ = self._stmts(handler.body, ctx)
                    h = max(h, hc)
                o, _ = self._stmts(stmt.orelse, ctx)
                f, _ = self._stmts(stmt.finalbody, ctx)
                suffix += b + h + o + f
                del bt  # handlers may continue: no termination claim
            elif isinstance(stmt, (ast.With, ast.AsyncWith)):
                w = sum(self._expr(i.context_expr, ctx)
                        for i in stmt.items)
                b, bt = self._stmts(stmt.body, ctx)
                suffix += w + b
                terminated = terminated or bt
            elif isinstance(stmt, (ast.FunctionDef, ast.AsyncFunctionDef,
                                   ast.ClassDef)):
                continue  # runs later, under its own call context
            else:
                suffix += self._expr(stmt, ctx)
        return suffix, terminated

    def _is_segment_loop(self, loop: ast.For, ctx: _Ctx) -> bool:
        seg_names = ctx.seg_names
        for sub in ast.walk(loop.iter):
            if isinstance(sub, ast.Call) \
                    and _terminal(sub.func) in SEGMENT_ITERS:
                return True
            if isinstance(sub, ast.Name) and sub.id in seg_names:
                return True
        return False

    # -- expressions ---------------------------------------------------

    def _expr(self, node: Optional[ast.AST], ctx: _Ctx) -> int:
        if node is None:
            return 0
        count = 0
        for sub in _walk_no_nested(node):
            if isinstance(sub, ast.Call):
                count += self._call(sub, ctx)
        return count

    def _call(self, call: ast.Call, ctx: _Ctx) -> int:
        t = _terminal(call.func)
        # lane guard: ONE watched launch; closure defs are absorbed
        if _is_lane_call(call):
            defs = self._closure_defs(call, ctx)
            kind, _ = _closure_kind(defs)
            self._site(call, kind, f"lane.{t}", lane=True, ctx=ctx)
            return 1
        if t in GUARDED_LAUNCH_FNS and self._callee(call, ctx) is None:
            self._site(call, KIND_BY_NAME[t], t, lane=True, ctx=ctx)
            return 1
        if t in DISPATCH_NAMES:
            self._site(call, KIND_BY_NAME[t], t, lane=False, ctx=ctx)
            return 1
        if _is_program_call(call):
            self._site(call, "grep-jit", f"<program>.{t}", lane=False,
                       ctx=ctx)
            return 1
        if t in SYNC_NAMES:
            self._sync(call, t, ctx)
            return sum(self._expr(a, ctx) for a in call.args)
        if t in SCATTER_NAMES:
            self.scatters[(call.lineno, call.col_offset)] = call
            return sum(self._expr(a, ctx) for a in call.args)
        if t == PIPELINE_FN:
            return self._pipeline(call, ctx)
        # interprocedural edges: self.<m>() / same-module fn / a nested
        # def invoked by name (the stages() generator pattern)
        target = self._callee(call, ctx)
        if target is not None:
            inlined = self._inline(target, ctx)
            return inlined + sum(self._expr(a, ctx) for a in call.args)
        return 0

    def _callee(self, call: ast.Call,
                ctx: _Ctx) -> Optional[ast.FunctionDef]:
        f = call.func
        if isinstance(f, ast.Attribute) and isinstance(f.value, ast.Name) \
                and f.value.id == "self":
            return self.methods.get(f.attr)
        if isinstance(f, ast.Name):
            local = ctx.lookup(f.id)
            if local:
                return local[0]  # nested def called in place
            return self.functions.get(f.id)
        return None

    def _inline(self, fn: ast.FunctionDef, ctx: _Ctx,
                per_segment: Optional[bool] = None) -> int:
        if ctx.depth >= 6 or fn.name in self._inlining:
            return 0
        self._inlining.add(fn.name)
        try:
            sub = ctx.child(depth=ctx.depth + 1)
            if per_segment is not None:
                sub.per_segment = per_segment
            count, _ = self._fn_body(fn, sub)
            return count
        finally:
            self._inlining.discard(fn.name)

    def _pipeline(self, call: ast.Call, ctx: _Ctx) -> int:
        """double_buffered(stage_iter, dispatch, collect): the dispatch
        callback runs once per staged segment — its launches ARE the
        per-segment launches; the stage generator is staging context;
        collect is the sanctioned force point (one segment behind)."""
        self.staged = True
        count = 0
        args = list(call.args)
        # arg 0: generator — usually a call to a nested def
        if args:
            gen = args[0]
            gen_fn = None
            if isinstance(gen, ast.Call):
                gen_fn = self._callee(gen, ctx)
            elif isinstance(gen, ast.Name):
                gen_fn = next(iter(ctx.lookup(gen.id)), None)
            if gen_fn is not None:
                count += self._inline(gen_fn, ctx, per_segment=True)
        if len(args) > 1 and isinstance(args[1], ast.Name):
            for cb in ctx.lookup(args[1].id):
                count += self._inline(cb, ctx, per_segment=True)
        # arg 2 (collect): forcing there is the pattern — not walked
        # as per-segment hazard context, but launches still count
        if len(args) > 2 and isinstance(args[2], ast.Name):
            for cb in ctx.lookup(args[2].id):
                count += self._inline(cb, ctx, per_segment=False)
        return count

    def _closure_defs(self, call: ast.Call, ctx: _Ctx) -> List[ast.AST]:
        out: List[ast.AST] = []
        for arg in list(call.args) + [k.value for k in call.keywords]:
            if isinstance(arg, ast.Name):
                out.extend(ctx.lookup(arg.id))
            elif isinstance(arg, ast.Lambda):
                out.append(arg)
        return out

    def _site(self, call: ast.Call, kind: str, what: str, lane: bool,
              ctx: _Ctx) -> None:
        key = (call.lineno, call.col_offset)
        if key not in self.sites:
            self.sites[key] = _Site(call.lineno, call.col_offset, kind,
                                    what, lane, ctx.in_loop)

    def _sync(self, call: ast.Call, t: str, ctx: _Ctx) -> None:
        if not ctx.per_segment:
            return
        pending = ctx.pending
        hazard = t == "block_until_ready"
        if not hazard:
            for arg in call.args:
                for sub in ast.walk(arg):
                    if isinstance(sub, ast.Call) and (
                            _terminal(sub.func) in KIND_BY_NAME
                            or _is_program_call(sub)
                            or _is_lane_call(sub)):
                        hazard = True
                    if isinstance(sub, ast.Name) and sub.id in pending:
                        hazard = True
        if hazard:
            self.sync_hits.setdefault(
                (call.lineno, call.col_offset), (call, t))


# -- per-module scan ----------------------------------------------------

class _ModuleScan:
    """All entries of one module → chains + rule findings."""

    def __init__(self, module: Module):
        self.module = module
        self.functions: Dict[str, ast.FunctionDef] = {}
        self.classes: List[ast.ClassDef] = []
        nested: Set[ast.AST] = set()
        for node in module.tree.body:
            if isinstance(node, (ast.FunctionDef, ast.AsyncFunctionDef)):
                self.functions[node.name] = node
            elif isinstance(node, ast.ClassDef):
                self.classes.append(node)
        del nested

    def chains(self) -> List[Dict[str, Any]]:
        out: List[Dict[str, Any]] = []
        for cls in self.classes:
            methods = {
                n.name: n for n in cls.body
                if isinstance(n, (ast.FunctionDef, ast.AsyncFunctionDef))
            }
            for entry in LAUNCH_ENTRIES:
                fn = methods.get(entry)
                if fn is None:
                    continue
                walk = _EntryWalk(self.module, methods, self.functions)
                launches = walk.run(fn)
                out.append({
                    "module": self.module.path,
                    "cls": cls.name,
                    "entry": entry,
                    "line": fn.lineno,
                    "launches_per_segment": launches,
                    "sites": [s.as_dict() for s in
                              sorted(walk.sites.values(),
                                     key=lambda s: (s.line, s.col))],
                    "scatter_sites": sorted(
                        ln for ln, _ in walk.scatters),
                    "scatter_passes": len(walk.scatters),
                    "staged": walk.staged,
                    "sync_hits": [
                        (c.lineno, c.col_offset, t)
                        for (c, t) in walk.sync_hits.values()],
                })
        return out


class LaunchGraphRules(Rule):
    name = "launch-graph"  # umbrella; findings carry precise rules
    description = ("fbtpu-xray launch-graph rules: launches per staged "
                   "segment, donation gaps, verdict round-trips, "
                   "overlap-defeating syncs, redundant arena copies")

    RULE_NAMES = ("device-multi-launch-chain", "device-undonated-buffer",
                  "device-host-roundtrip", "device-sync-in-staging-loop",
                  "stage-redundant-copy")

    def check(self, module: Module) -> List[Finding]:
        if not any(s in module.path for s in SCOPES):
            return []
        out: List[Finding] = []
        scan = _ModuleScan(module)
        flagged: Set[Tuple[int, str]] = set()

        def emit(line: int, col: int, rule: str, message: str,
                 severity: Optional[str] = None) -> None:
            if (line, rule) in flagged or module.allowed(rule, line):
                return
            flagged.add((line, rule))
            out.append(Finding(module.path, line, col, rule, message,
                               severity or _SEVERITY[rule]))

        for chain in scan.chains():
            n = chain["launches_per_segment"]
            if n > 1:
                whats = ", ".join(
                    s["what"] + ("×G" if s["in_loop"] else "")
                    for s in chain["sites"])
                emit(chain["line"], 0, "device-multi-launch-chain",
                     f"`{chain['cls']}.{chain['entry']}` reaches {n} "
                     f"device launches per staged segment ({whats}): "
                     f"each pays its own staging + PCIe crossing — the "
                     f"fusion target is ONE launch per segment "
                     f"(ROADMAP item 1)")
            if n >= 1 and chain["scatter_passes"]:
                for line in chain["scatter_sites"]:
                    emit(line, 0, "device-host-roundtrip",
                         f"device verdict from "
                         f"`{chain['cls']}.{chain['entry']}` returns to "
                         f"host as a mask, then `compact` re-walks the "
                         f"chunk bytes to scatter survivors: the bytes "
                         f"cross PCIe just to be re-indexed — a fused "
                         f"program returning compacted survivor bytes "
                         f"kills this pass")
            for line, col, t in chain["sync_hits"]:
                emit(line, col, "device-sync-in-staging-loop",
                     f"`{t}` forces a dispatch result inside the "
                     f"double-buffered segment loop of "
                     f"`{chain['cls']}.{chain['entry']}`: the host "
                     f"blocks mid-pipeline and the next segment's "
                     f"staging no longer overlaps the in-flight launch "
                     f"— force inside the lane closure (worker-side) "
                     f"or the collect callback instead")
        self._undonated(module, emit)
        self._arena_copies(module, emit)
        out.sort(key=lambda f: (f.line, f.col, f.rule))
        return out

    # -- site-level rules ---------------------------------------------

    def _undonated(self, module: Module, emit) -> None:
        for node in ast.walk(module.tree):
            if not (isinstance(node, ast.Call)
                    and _terminal(node.func) in MESH_DISPATCH_SITES):
                continue
            donate_off = any(
                kw.arg == "donate" and isinstance(kw.value, ast.Constant)
                and kw.value.value in ("off", False)
                for kw in node.keywords)
            if donate_off:
                emit(node.lineno, node.col_offset,
                     "device-undonated-buffer",
                     "mesh dispatch with donation disabled: every "
                     "staged buffer (batch u8 [R,B,L] AND lengths i32 "
                     "[R,B]) crosses host→device un-aliased each "
                     "segment — use the auto donate set "
                     "(ops.mesh.aliasable_donations)", severity="error")
            else:
                emit(node.lineno, node.col_offset,
                     "device-undonated-buffer",
                     "staged u8 batch [R,B,L] enters the pjit launch "
                     "outside the donate set: no aliasable u8 output "
                     "exists (only lengths i32 aliases the mask), so "
                     "R*Bp*L bytes cross host→device un-donated every "
                     "segment — a fused same-aval survivor-bytes "
                     "output would make it donatable (ROADMAP item 1)")

    def _arena_copies(self, module: Module, emit) -> None:
        for node in ast.walk(module.tree):
            if not isinstance(node, (ast.FunctionDef,
                                     ast.AsyncFunctionDef)):
                continue
            tainted: Set[str] = set()
            stmts = sorted(
                (s for s in ast.walk(node) if isinstance(s, ast.Assign)),
                key=lambda s: s.lineno)
            for s in stmts:
                names = self._target_names(s.targets)
                if isinstance(s.value, ast.Call) \
                        and _terminal(s.value.func) == ARENA_STAGER:
                    tainted |= names
                elif isinstance(s.value, ast.Name) \
                        and s.value.id in tainted:
                    tainted |= names
                elif isinstance(s.value, ast.Tuple) and any(
                        isinstance(e, ast.Call)
                        and _terminal(e.func) == ARENA_STAGER
                        for e in s.value.elts):
                    tainted |= names
            if not tainted:
                continue
            for sub in ast.walk(node):
                if not (isinstance(sub, ast.Call)
                        and isinstance(sub.func, ast.Attribute)
                        and sub.func.attr == "copy"
                        and not sub.args):
                    continue
                base = sub.func.value
                while isinstance(base, ast.Subscript):
                    base = base.value
                if isinstance(base, ast.Name) and base.id in tainted:
                    emit(sub.lineno, sub.col_offset,
                         "stage-redundant-copy",
                         f"`.copy()` on the arena view "
                         f"`{base.id}` staged by native.stage_field: "
                         f"the per-thread arena forces a copy-out that "
                         f"native.stage_field_into avoids by staging "
                         f"straight into the caller's transfer matrix "
                         f"(the mesh path already does)")

    def _target_names(self, targets) -> Set[str]:
        names: Set[str] = set()
        for tgt in targets:
            if isinstance(tgt, ast.Name):
                names.add(tgt.id)
            elif isinstance(tgt, (ast.Tuple, ast.List)):
                for e in tgt.elts:
                    if isinstance(e, ast.Name):
                        names.add(e.id)
        return names


# -- the graph / budget API --------------------------------------------

def _package_root() -> str:
    import os

    return os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def _eval_bytes(expr: str, env: Dict[str, int]) -> int:
    return int(eval(expr, {"__builtins__": {}}, dict(env)))  # noqa: S307


def canonical_env(params: Optional[Dict[str, int]] = None
                  ) -> Dict[str, int]:
    """The canonical evaluation point for the symbolic byte algebra —
    ``registry.BUDGET_PARAMS`` plus the derived padded batch (the
    committed ``launch_budget.json`` is evaluated here, so the gate
    compares like with like)."""
    from ..ops.batch import bucket_size

    env = dict(BUDGET_PARAMS)
    if params:
        env.update(params)
    env.setdefault("B", env["seg"])
    env.setdefault("Bp", bucket_size(env["seg"], max_len=env["L"],
                                     multiple_of=env["n_dev"]))
    # the fused absorb's padded segment table (flux/kernels:
    # _pad_segments(G) — power of two, floor 8): the [Gp, m] register
    # stack and counts table ride the fused launch at this size
    gp = 8
    while gp < env["G"]:
        gp *= 2
    env.setdefault("Gp", gp)
    return env


def donation_crosscheck(n_dev: Optional[int] = None, R: int = 2,
                        L: int = 512) -> Dict[str, Any]:
    """Cross-check the static donate/alias expectation (lengths i32
    [R,B] ↔ mask i32 [R,B] aliases; batch u8 [R,B,L] never does)
    against ``ops.mesh.aliasable_donations`` on a live mesh — exactly
    the specs ``ops.grep._mesh_handle`` donates from. Returns
    ``checked=False`` (expectation only) when jax or a multi-device
    mesh is unavailable."""
    out = {"checked": False, "batch_donated": False,
           "lengths_donated": True, "variant": "batch"}
    try:
        import jax
        from jax.sharding import PartitionSpec as P

        import numpy as np

        from ..ops.mesh import aliasable_donations, build_mesh

        devs = len(jax.devices())
        if devs < 2:
            return out
        mesh = build_mesh(min(n_dev or devs, devs))
        axis = mesh.axis_names[0]
        Bc = mesh.devices.size * 8
        cand = aliasable_donations(
            mesh,
            in_specs=[((R, Bc, L), np.uint8, P(None, axis, None), True),
                      ((R, Bc), np.int32, P(None, axis), True)],
            out_specs=[((R, Bc), np.int32, P(None, axis))],
        )
        out.update(checked=True, batch_donated=0 in cand,
                   lengths_donated=1 in cand)
    except Exception:
        pass
    return out


def table_bytes(patterns, n_dev: int = 1) -> Dict[str, Any]:
    """Post-shrink DFA matrix footprint for a rule set: the ``S × C``
    i32 transition tables + class maps the native GrepTables /
    GrepProgram build from ``FlbRegex.dfa`` (always through the PR-10
    ``compile_dfa`` reducer), replicated ``n_dev`` times on a mesh.
    The carried-over rewrite_tag / log_to_metrics accounting rides on
    this: their matrices share the same compile path, so their budget
    entries are sized (and shrink-audited) here."""
    from ..regex.dfa import compile_dfa

    per_rule = []
    total = 0
    for pat in patterns:
        dfa = compile_dfa(pat)
        nbytes = dfa.n_states * dfa.n_classes * 4 + 257
        st = dfa.shrink
        per_rule.append({
            "pattern": pat[:48], "states": dfa.n_states,
            "classes": dfa.n_classes, "bytes": nbytes,
            "states_eliminated":
                0 if st is None else st.states_eliminated,
            "classes_eliminated":
                0 if st is None else st.classes_eliminated,
        })
        total += nbytes
    return {"rules": per_rule, "bytes": total,
            "replicated_bytes": total * n_dev}


def _chain_transfers(chain: Dict[str, Any],
                     env: Dict[str, int]) -> Dict[str, Any]:
    h2d: List[Dict[str, Any]] = []
    d2h: List[Dict[str, Any]] = []
    seen: Set[Tuple[str, str]] = set()
    for site in chain["sites"]:
        shapes = TRANSFER_SHAPES.get(site["kind"])
        if shapes is None:
            continue
        for direction, rows in (("h2d", shapes["h2d"]),
                                ("d2h", shapes["d2h"])):
            for name, expr, dtype, donated in rows:
                key = (site["kind"], f"{direction}:{name}")
                if key in seen:
                    continue
                seen.add(key)
                row = {"buffer": name, "bytes": expr, "dtype": dtype,
                       "donated": donated, "kind": site["kind"],
                       "bytes_canonical": _eval_bytes(expr, env),
                       "per_group": site["in_loop"]}
                (h2d if direction == "h2d" else d2h).append(row)
    undonated = sum(r["bytes_canonical"] for r in h2d
                    if not r["donated"])
    return {
        "h2d": h2d, "d2h": d2h,
        "h2d_bytes_canonical": sum(r["bytes_canonical"] for r in h2d),
        "d2h_bytes_canonical": sum(r["bytes_canonical"] for r in d2h),
        "undonated_h2d_bytes_canonical": undonated,
    }


def build_launch_graph(root: Optional[str] = None,
                       params: Optional[Dict[str, int]] = None
                       ) -> Dict[str, Any]:
    """Scan the shipped device planes and emit the per-tag launch
    graph. A tag's filter chain composes these per-plugin chains in
    config order; per chain: launches per staged segment, the launch
    sites (kind, lane guard, ×G loop multiplicity), symbolic +
    canonical transfer bytes, host scatter passes, and the example DFA
    table footprints."""
    import os

    from . import iter_py_files, Module

    pkg = root or _package_root()
    env = canonical_env(params)
    chains: Dict[str, Any] = {}
    scopes = [os.path.join(pkg, "plugins"), os.path.join(pkg, "flux")]
    for scope in scopes:
        if not os.path.isdir(scope):
            continue
        for path in iter_py_files([scope]):
            with open(path, "r", encoding="utf-8") as fh:
                source = fh.read()
            rel = os.path.relpath(path, os.path.dirname(pkg))
            module = Module(rel, source)
            if module.tree is None:
                continue
            for chain in _ModuleScan(module).chains():
                cid = f"{chain['module']}::{chain['cls']}." \
                      f"{chain['entry']}"
                chain["transfers"] = _chain_transfers(chain, env)
                chains[cid] = chain
    tables = {
        name: table_bytes(pats, n_dev=env["n_dev"])
        for name, pats in EXAMPLE_TABLES.items()
    }
    try:
        # fbtpu-speccheck: predicted per-leaf PartitionSpecs + donation
        # set of every shipped device program (kernel deps may be
        # absent on a lint-only host — the graph still builds)
        from .speccheck import shardings_snapshot

        shardings = shardings_snapshot()
    except Exception:  # pragma: no cover - jax-less host
        shardings = {}
    return {
        "version": 1,
        "params": env,
        "chains": dict(sorted(chains.items())),
        "donation": donation_crosscheck(n_dev=env["n_dev"], R=env["R"],
                                        L=env["L"]),
        "tables": tables,
        "shardings": shardings,
    }


def budget_snapshot(graph: Dict[str, Any]) -> Dict[str, Any]:
    """The regression-gated subset of the graph: launches per segment
    and un-donated host→device bytes per chain (plus scatter passes).
    The committed ``analysis/launch_budget.json`` holds this snapshot —
    the item-1 fusion PR lands by SHRINKING it, and any PR that grows a
    number here fails the gate until the budget file says so."""
    chains = {}
    for cid, chain in graph["chains"].items():
        # 0-launch chains never cross PCIe — their host compacts are
        # not roundtrips, so they carry no device budget to gate
        if chain["launches_per_segment"] == 0:
            continue
        chains[cid] = {
            "launches_per_segment": chain["launches_per_segment"],
            "undonated_h2d_bytes":
                chain["transfers"]["undonated_h2d_bytes_canonical"],
            "d2h_bytes": chain["transfers"]["d2h_bytes_canonical"],
            "scatter_passes": chain["scatter_passes"],
        }
    return {"params": {k: int(v) for k, v in graph["params"].items()},
            "chains": chains,
            "shardings": graph.get("shardings", {})}


def compare_budget(current: Dict[str, Any],
                   baseline: Dict[str, Any]
                   ) -> Tuple[List[str], List[str]]:
    """Compare a budget snapshot against the committed baseline →
    (regressions, notes). Any growth in launches-per-segment,
    un-donated bytes, or scatter passes — or a device chain the
    baseline has never seen — is a regression; improvements are notes
    (regenerate the budget file to claim them)."""
    regressions: List[str] = []
    notes: List[str] = []
    base_chains = baseline.get("chains", {})
    gate_keys = ("launches_per_segment", "undonated_h2d_bytes",
                 "scatter_passes")
    for cid, cur in current.get("chains", {}).items():
        base = base_chains.get(cid)
        if base is None:
            regressions.append(
                f"{cid}: new device chain not in launch_budget.json "
                f"({cur['launches_per_segment']} launches/segment) — "
                f"baseline it deliberately or fuse it")
            continue
        for key in gate_keys:
            b, c = int(base.get(key, 0)), int(cur.get(key, 0))
            if c > b:
                regressions.append(
                    f"{cid}: {key} grew {b} → {c} (the budget file "
                    f"gates this — a fusion PR shrinks it, nothing "
                    f"grows it silently)")
            elif c < b:
                notes.append(
                    f"{cid}: {key} improved {b} → {c}; regenerate "
                    f"launch_budget.json (--write-budget) to claim it")
    for cid in base_chains:
        if cid not in current.get("chains", {}):
            notes.append(f"{cid}: chain no longer reaches the device "
                         f"plane; regenerate launch_budget.json")
    _compare_shardings(current, baseline, regressions, notes)
    return regressions, notes


def _compare_shardings(current: Dict[str, Any], baseline: Dict[str, Any],
                       regressions: List[str], notes: List[str]) -> None:
    """fbtpu-speccheck leaf-spec regression: a table/input/output leaf
    whose predicted PartitionSpec (or a program's predicted donation
    set) differs from the committed snapshot fails the gate — a
    sharding refactor must re-baseline deliberately (--write-budget).
    A baseline written before the shardings block existed gates
    nothing (old synthetic baselines in tests stay valid); a current
    snapshot can also be empty on a kernel-less host — skip then too,
    never fail on missing machinery."""
    base_sh = baseline.get("shardings")
    cur_sh = current.get("shardings")
    if not base_sh or not cur_sh:
        return
    for pname, cur in cur_sh.items():
        base = base_sh.get(pname)
        if base is None:
            regressions.append(
                f"{pname}: new device program not in "
                f"launch_budget.json shardings — baseline its "
                f"predicted specs deliberately (--write-budget)")
            continue
        for group in ("tables", "inputs", "outputs"):
            bleaves = base.get(group, {})
            for leaf, spec in cur.get(group, {}).items():
                if leaf not in bleaves:
                    regressions.append(
                        f"{pname}: {group} leaf `{leaf}` not in the "
                        f"committed shardings snapshot — re-baseline "
                        f"(--write-budget)")
                elif bleaves[leaf] != spec:
                    regressions.append(
                        f"{pname}: {group} leaf `{leaf}` sharding "
                        f"changed {bleaves[leaf]!r} → {spec!r}: a "
                        f"layout change re-shards resident state at "
                        f"the next dispatch — re-baseline "
                        f"deliberately (--write-budget)")
            for leaf in bleaves:
                if leaf not in cur.get(group, {}):
                    notes.append(
                        f"{pname}: {group} leaf `{leaf}` left the "
                        f"program; regenerate launch_budget.json")
        if base.get("donate_predicted") is not None \
                and base["donate_predicted"] != cur.get(
                    "donate_predicted"):
            regressions.append(
                f"{pname}: predicted donation set changed "
                f"{base['donate_predicted']!r} → "
                f"{cur.get('donate_predicted')!r} — an input stopped "
                f"(or started) aliasing its output; re-baseline "
                f"deliberately (--write-budget)")
    for pname in base_sh:
        if pname not in cur_sh:
            notes.append(f"{pname}: program left the shipped set; "
                         f"regenerate launch_budget.json")


def graph_to_dot(graph: Dict[str, Any]) -> str:
    """Graphviz rendering: entry → launch sites (kind, lane guard,
    canonical bytes) → host sinks (scatter passes)."""
    lines = ["digraph launchgraph {", "  rankdir=LR;",
             '  node [shape=box, fontname="monospace"];']
    for cid, chain in graph["chains"].items():
        if not chain["sites"] and not chain["scatter_passes"]:
            continue
        ent = f'"{cid}"'
        n = chain["launches_per_segment"]
        lines.append(
            f'  {ent} [label="{chain["cls"]}.{chain["entry"]}\\n'
            f'{n} launch(es)/segment", style=bold];')
        for site in chain["sites"]:
            sid = f'"{cid}#L{site["line"]}"'
            guard = "lane" if site["lane"] else "UNGUARDED"
            mult = " ×G" if site["in_loop"] else ""
            lines.append(
                f'  {sid} [label="{site["what"]}{mult}\\n'
                f'{site["kind"]} [{guard}]"];')
            lines.append(f"  {ent} -> {sid};")
        if chain["scatter_passes"]:
            hid = f'"{cid}#scatter"'
            lines.append(
                f'  {hid} [label="host scatter ×'
                f'{chain["scatter_passes"]}\\n(compact)", '
                f'style=dashed];')
            lines.append(f"  {ent} -> {hid} [style=dashed];")
    lines.append("}")
    return "\n".join(lines)
