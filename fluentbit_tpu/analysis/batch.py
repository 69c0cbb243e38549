"""Batch-exactness dataflow rules for ``FilterPlugin.process_batch``.

The batched fast path (DEVICE_PLANE.md) carries delicate contracts the type
system cannot see: the engine treats ``return None`` / any raise from
``process_batch`` as a *decline* and re-runs the chain per-record from
the declining filter onward (``engine._ingest_raw`` + the decoded-tail
continuation). That rerun is bit-exact ONLY when the declining hook has
not yet committed side effects — a counter already incremented or a
record already re-emitted through a hidden emitter fires a second time
on the rerun. These rules encode the contract as an interprocedural
forward dataflow over every ``process_batch`` implementation and the
``self.<method>()`` calls reachable from it:

- ``batch-decline-after-commit``: an explicit decline site (``return
  None`` / bare ``return`` / ``raise FallbackError``) reachable after a
  committed side effect (metric ``inc``/``observe``, emitter
  ``add_record``/``add_event``, flux-state
  ``absorb_batch``/``absorb_events`` — the fbtpu-flux surface: an
  absorbed batch is observable in every later window emission, so a
  rerun absorbs the same records twice). The decoded-tail rerun
  replays the commit — counters double-count, emits duplicate,
  windows double-aggregate.
- ``batch-commit-replay``: an emitter append (``add_record``/
  ``add_event``) after an earlier commit with no enclosing
  ``try``/``except``. The call raising IS an implicit decline, with the
  same replay consequence; guard it and degrade like backpressure.
- ``batch-stateful-unmarked``: ``process_batch`` commits side effects
  but the class does not declare ``stateful_batch = True`` — the engine
  keys the decoded-tail continuation off that attribute, so an unmarked
  stateful hook makes a downstream decline restart the WHOLE chain and
  replay everything this hook committed.
- ``batch-no-fallback``: a class advertising ``can_process_batch`` whose
  ``process_batch`` has no reachable decline site at all — configs
  outside the fast set then have no bit-exact per-record escape.
- ``batch-unordered-emit``: a ``for`` loop feeding an emit (or building
  the output buffer) from an unordered iterable (``set``/``frozenset``
  constructors or literals, set comprehensions, ``np.unique`` — which
  sorts). Span-gather re-emits must preserve FIRST-SEEN record order to
  stay byte-exact with the per-record path's pending-dict insertion
  order.

The dataflow is a may-analysis: branches merge with OR, loop bodies run
a two-iteration fixpoint (so a commit on iteration N is visible to the
same statement on iteration N+1 — the emit-loop replay case), and
``self.<method>()`` calls inline the callee's effects. A method called
in *tail position* (``return self._impl(chunk)``) contributes its
decline sites to the caller; a statement call contributes only its
commits.
"""

from __future__ import annotations

import ast
from typing import Dict, List, Optional, Set, Tuple

from . import Finding, Module, Rule

__all__ = ["BatchExactnessRules"]

#: metric-commit terminals: observable counter/histogram updates
_METRIC_COMMITS = {"inc", "observe"}
#: emitter-append terminals: records re-entering the pipeline
_EMIT_COMMITS = {"add_record", "add_event"}
#: flux-state commit terminals (fbtpu-flux): absorbing a batch into
#: per-tenant sketch/window state is observable in every later window
#: emission and metric export — a decline after it makes the decoded
#: rerun absorb the same records twice (double-counted windows,
#: inflated sketches). Same contract as the metric commits, new surface.
_FLUX_COMMITS = {"absorb_batch", "absorb_events"}
#: unordered-iterable constructor terminals (np.unique SORTS, which is
#: just as order-destroying as a set walk)
_UNORDERED = {"set", "frozenset", "unique"}


def _terminal(func: ast.AST) -> Optional[str]:
    if isinstance(func, ast.Attribute):
        return func.attr
    if isinstance(func, ast.Name):
        return func.id
    return None


def _is_none(expr: Optional[ast.AST]) -> bool:
    return expr is None or (isinstance(expr, ast.Constant)
                            and expr.value is None)


def _self_method(call: ast.Call) -> Optional[str]:
    """``self.<name>(...)`` → name (the interprocedural edge)."""
    f = call.func
    if isinstance(f, ast.Attribute) and isinstance(f.value, ast.Name) \
            and f.value.id == "self":
        return f.attr
    return None


def _receiver_names(call: ast.Call) -> Set[str]:
    out: Set[str] = set()
    f = call.func
    if isinstance(f, ast.Attribute):
        for node in ast.walk(f.value):
            if isinstance(node, ast.Name):
                out.add(node.id)
            elif isinstance(node, ast.Attribute):
                out.add(node.attr)
    return out


def _calls_in_order(node: ast.AST) -> List[ast.Call]:
    """Call expressions in source order (good enough for left-to-right
    evaluation within one statement)."""
    calls = [n for n in ast.walk(node) if isinstance(n, ast.Call)]
    calls.sort(key=lambda c: (c.lineno, c.col_offset))
    return calls


class _State:
    """May-have-committed lattice element."""

    __slots__ = ("committed",)

    def __init__(self, committed: bool = False):
        self.committed = committed

    def copy(self) -> "_State":
        return _State(self.committed)


class _ClassScan:
    """One class's process_batch analyzed with its reachable methods."""

    def __init__(self, rule: "BatchExactnessRules", module: Module,
                 cls: ast.ClassDef):
        self.rule = rule
        self.module = module
        self.cls = cls
        self.methods: Dict[str, ast.FunctionDef] = {}
        self.stateful = False
        self.has_can = False
        for node in cls.body:
            if isinstance(node, (ast.FunctionDef, ast.AsyncFunctionDef)):
                self.methods[node.name] = node
                if node.name == "can_process_batch":
                    self.has_can = True
            elif isinstance(node, ast.Assign):
                for tgt in node.targets:
                    if isinstance(tgt, ast.Name) \
                            and tgt.id == "stateful_batch" \
                            and isinstance(node.value, ast.Constant) \
                            and node.value.value is True:
                        self.stateful = True
        self.findings: List[Finding] = []
        self.any_commit = False
        self.any_decline = False
        self._inlining: Set[Tuple[str, bool]] = set()

    # -- reporting ----------------------------------------------------

    def _emit(self, node: ast.AST, rule: str, message: str,
              severity: str = "error") -> None:
        line = getattr(node, "lineno", 1)
        if not self.module.allowed(rule, line):
            self.findings.append(Finding(
                self.module.path, line, getattr(node, "col_offset", 0),
                rule, message, severity))

    # -- the dataflow -------------------------------------------------

    def run(self) -> List[Finding]:
        fn = self.methods.get("process_batch")
        if fn is None:
            return []
        self._stmts(fn.body, _State(), guarded=False, tail=True, depth=0)
        if self.has_can and not self.any_decline:
            self._emit(fn, "batch-no-fallback",
                       f"`{self.cls.name}.process_batch` advertises "
                       f"can_process_batch but has no reachable decline "
                       f"site (`return None` / FallbackError): configs "
                       f"outside the fast set have no bit-exact "
                       f"per-record escape")
        if self.any_commit and not self.stateful:
            self._emit(fn, "batch-stateful-unmarked",
                       f"`{self.cls.name}.process_batch` commits side "
                       f"effects (counter incs / emitter appends) but "
                       f"the class does not declare `stateful_batch = "
                       f"True` — a downstream decline then restarts the "
                       f"whole raw chain and replays them")
        return self.findings

    def _decline(self, node: ast.AST, state: _State, what: str) -> None:
        self.any_decline = True
        if state.committed:
            self._emit(node, "batch-decline-after-commit",
                       f"{what} after a committed side effect: the "
                       f"engine's decoded-tail rerun re-executes this "
                       f"filter per-record and replays the commit "
                       f"(double-counted counters / duplicate emits) — "
                       f"decline BEFORE committing, or guard the "
                       f"committing call and succeed")

    def _inline(self, name: str, state: _State, guarded: bool,
                tail: bool, depth: int) -> None:
        callee = self.methods.get(name)
        if callee is None or depth >= 6:
            return
        key = (name, tail)
        if key in self._inlining:
            return
        self._inlining.add(key)
        try:
            self._stmts(callee.body, state, guarded, tail, depth + 1)
        finally:
            self._inlining.discard(key)

    def _calls(self, node: ast.AST, state: _State, guarded: bool,
               depth: int) -> None:
        """Effect pass over every call inside one statement/expression."""
        for call in _calls_in_order(node):
            t = _terminal(call.func)
            m = _self_method(call)
            if m is not None and m in self.methods:
                # statement-position inline: commits propagate, the
                # callee's returns are the CALLER's values (not declines)
                self._inline(m, state, guarded, tail=False, depth=depth)
                continue
            if t in _EMIT_COMMITS:
                if state.committed and not guarded:
                    self._emit(call, "batch-commit-replay",
                               f"emitter `.{t}()` after an earlier "
                               f"committed effect with no enclosing "
                               f"try/except: a raise here declines the "
                               f"batch and the per-record rerun replays "
                               f"the earlier commit — guard it and "
                               f"degrade like backpressure")
                state.committed = True
                self.any_commit = True
            elif t in _METRIC_COMMITS or t in _FLUX_COMMITS:
                # flux absorbs are idempotent-or-guarded by the same
                # rule metric incs are: committed state the decoded
                # rerun would replay
                state.committed = True
                self.any_commit = True
            elif t == "set" and isinstance(call.func, ast.Attribute) \
                    and "metric" in " ".join(_receiver_names(call)):
                # gauge .set() on a metric receiver commits too
                state.committed = True
                self.any_commit = True

    def _check_loop_order(self, loop: ast.For) -> None:
        unordered = None
        for sub in ast.walk(loop.iter):
            if isinstance(sub, (ast.Set, ast.SetComp)):
                unordered = "a set"
                break
            if isinstance(sub, ast.Call) \
                    and _terminal(sub.func) in _UNORDERED:
                unordered = f"`{_terminal(sub.func)}(...)`"
                break
        if unordered is None:
            return
        def _builds_output(aug: ast.AugAssign) -> bool:
            # `out += span` style concatenation onto the chunk's output
            # buffer is order-sensitive; an order-independent reduction
            # (`total += counts[tag]`) is not
            if not isinstance(aug.op, ast.Add):
                return False
            t = _terminal(aug.target)
            return t is not None and any(
                frag in t.lower() for frag in ("out", "buf", "payload"))

        feeds_emit = any(
            isinstance(n, ast.Call) and _terminal(n.func) in _EMIT_COMMITS
            for n in ast.walk(loop)
        ) or any(isinstance(n, ast.AugAssign) and _builds_output(n)
                 for n in ast.walk(loop))
        if feeds_emit:
            self._emit(loop, "batch-unordered-emit",
                       f"re-emit loop iterates {unordered}: span-gather "
                       f"re-emits must preserve first-seen record order "
                       f"to stay byte-exact with the per-record path — "
                       f"key groups by first contributing index "
                       f"(insertion-ordered dict / sorted-by-first)")

    def _stmts(self, stmts: List[ast.stmt], state: _State, guarded: bool,
               tail: bool, depth: int) -> None:
        for stmt in stmts:
            self._stmt(stmt, state, guarded, tail, depth)

    def _stmt(self, stmt: ast.stmt, state: _State, guarded: bool,
              tail: bool, depth: int) -> None:
        if isinstance(stmt, ast.Return):
            if _is_none(stmt.value):
                if tail:
                    self._decline(stmt, state, "`return None`")
                return
            call = stmt.value if isinstance(stmt.value, ast.Call) else None
            m = _self_method(call) if call is not None else None
            if m is not None and m in self.methods and tail:
                # tail call: inline ONCE, with decline semantics (the
                # callee's `return None` IS a decline of process_batch).
                # Only the call's arguments get the plain effect pass —
                # running _calls on the whole expression would inline
                # the callee a second time at statement position and
                # pollute `state` with its commits BEFORE the tail walk,
                # falsely flagging decline-before-commit callees.
                for arg in list(call.args) + [k.value for k in
                                              call.keywords]:
                    self._calls(arg, state, guarded, depth)
                self._inline(m, state, guarded, tail=True, depth=depth)
            else:
                self._calls(stmt.value, state, guarded, depth)
            return
        if isinstance(stmt, ast.Raise):
            names = {n.id for n in ast.walk(stmt) if isinstance(n, ast.Name)}
            names |= {n.attr for n in ast.walk(stmt)
                      if isinstance(n, ast.Attribute)}
            if any("FallbackError" in n for n in names):
                self._decline(stmt, state, "`raise FallbackError`")
            return
        if isinstance(stmt, ast.If):
            self._calls(stmt.test, state, guarded, depth)
            s_then, s_else = state.copy(), state.copy()
            self._stmts(stmt.body, s_then, guarded, tail, depth)
            self._stmts(stmt.orelse, s_else, guarded, tail, depth)
            state.committed = s_then.committed or s_else.committed
            return
        if isinstance(stmt, (ast.For, ast.AsyncFor)):
            if isinstance(stmt, ast.For):
                self._check_loop_order(stmt)
            self._calls(stmt.iter, state, guarded, depth)
            # two-iteration fixpoint: a commit on iteration N reaches
            # the same statement on iteration N+1
            body_state = state.copy()
            self._stmts(stmt.body, body_state, guarded, tail, depth)
            if body_state.committed:
                self._stmts(stmt.body, body_state, guarded, tail, depth)
            self._stmts(stmt.orelse, body_state, guarded, tail, depth)
            state.committed = state.committed or body_state.committed
            return
        if isinstance(stmt, ast.While):
            self._calls(stmt.test, state, guarded, depth)
            body_state = state.copy()
            self._stmts(stmt.body, body_state, guarded, tail, depth)
            if body_state.committed:
                self._stmts(stmt.body, body_state, guarded, tail, depth)
            state.committed = state.committed or body_state.committed
            return
        if isinstance(stmt, ast.Try):
            # any handler makes body raises recoverable at this level
            body_guarded = guarded or bool(stmt.handlers)
            body_state = state.copy()
            self._stmts(stmt.body, body_state, body_guarded, tail, depth)
            merged = body_state.committed
            for handler in stmt.handlers:
                h_state = body_state.copy()
                self._stmts(handler.body, h_state, guarded, tail, depth)
                merged = merged or h_state.committed
            state.committed = state.committed or merged
            self._stmts(stmt.orelse, state, guarded, tail, depth)
            self._stmts(stmt.finalbody, state, guarded, tail, depth)
            return
        if isinstance(stmt, (ast.With, ast.AsyncWith)):
            for item in stmt.items:
                self._calls(item.context_expr, state, guarded, depth)
            self._stmts(stmt.body, state, guarded, tail, depth)
            return
        if isinstance(stmt, (ast.FunctionDef, ast.AsyncFunctionDef,
                             ast.ClassDef)):
            return  # nested defs run later; their calls are not ours
        # plain statement: effect pass over its expressions
        self._calls(stmt, state, guarded, depth)


class BatchExactnessRules(Rule):
    name = "batch-exactness"  # umbrella; findings carry precise rules
    description = ("process_batch contract dataflow: decline-after-"
                   "commit, unguarded emit replay, missing fallback, "
                   "unmarked stateful hooks, order-destroying re-emits")

    RULE_NAMES = ("batch-decline-after-commit", "batch-commit-replay",
                  "batch-stateful-unmarked", "batch-no-fallback",
                  "batch-unordered-emit")

    def check(self, module: Module) -> List[Finding]:
        if "process_batch" not in module.source:
            return []
        out: List[Finding] = []
        for node in ast.walk(module.tree):
            if isinstance(node, ast.ClassDef):
                out.extend(_ClassScan(self, module, node).run())
        out.sort(key=lambda f: (f.line, f.col))
        return out
