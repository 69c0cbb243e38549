"""device-unguarded-dispatch rule.

fbtpu-armor (ops/fault.py) wraps every engine/plugin entry into the
jit/pjit/shard_map plane in a :class:`DeviceLane`: breaker, launch
deadline, bit-exact CPU fallback, mesh shrink/regrow. The whole
fault-domain contract rests on that invariant — a device dispatch added
later that calls the kernel directly would reintroduce exactly the
failure modes the lane exists to contain (a wedged launch stalling
ingest, an XlaRuntimeError dropping a segment's verdict, a consumed
donated buffer read on retry), and nothing at runtime would notice
until the first real fault.

``device-unguarded-dispatch`` makes the invariant machine-checked (the
``qos-unmetered-ingest`` pattern): in ``fluentbit_tpu/plugins/`` and
``fluentbit_tpu/flux/`` modules, every PUBLIC function from which a
*device dispatch call* is reachable (directly or through same-module
helpers) must also reach a lane-guarded launch — a ``.run(`` /
``.begin(`` / ``.finish(`` call on something whose name chain mentions
``lane``. Dispatch calls are matched by name: the GrepProgram mesh/
sharded matchers, the sketch sharded updates and device_* compute
variants, and ``.dispatch(``/``.match(`` on a ``*program*`` chain.
Reachability is the same intentionally-lexical same-module call-name
closure the qos rule uses. The kernel layer itself (``ops/``) is out of
scope — lanes are the *boundary*, not the internals.

Suppress with ``# fbtpu-lint: allow(device-unguarded-dispatch)`` plus a
justification — e.g. a bench-only diagnostic path that wants the raw
failure.
"""

from __future__ import annotations

import ast
from typing import Dict, List, Set, Tuple

from . import Finding, Module, Rule

__all__ = ["UnguardedDispatchRule"]

#: Engine-facing device planes; ops/ (the kernel layer the lanes wrap)
#: and bench/test harnesses are out of scope.
SCOPES = ("fluentbit_tpu/plugins/", "fluentbit_tpu/flux/")

#: Calls that enter the jit/pjit/shard_map plane by simple name.
DISPATCH_NAMES = frozenset({
    "dispatch_mesh", "match_mesh",
    "sharded_hll_update", "sharded_cms_update",
    "sharded_hll_registers", "sharded_cms_table",
    "sharded_segment_counts", "device_registers", "device_table",
})

#: Attr names that count as dispatch only on a ``*program*`` chain
#: (``self._program.dispatch(...)`` / ``_program.match(...)``).
PROGRAM_ATTRS = frozenset({"dispatch", "match"})

LANE_GUARDS = frozenset({"run", "begin", "finish"})


def _chain_names(node) -> Set[str]:
    out: Set[str] = set()
    while True:
        if isinstance(node, ast.Attribute):
            out.add(node.attr)
            node = node.value
        elif isinstance(node, ast.Call):
            node = node.func  # self._lane().run — walk through the call
        else:
            break
    if isinstance(node, ast.Name):
        out.add(node.id)
    return out


def _is_dispatch(call: ast.Call) -> bool:
    f = call.func
    name = f.attr if isinstance(f, ast.Attribute) else (
        f.id if isinstance(f, ast.Name) else None)
    if name in DISPATCH_NAMES:
        return True
    if isinstance(f, ast.Attribute) and f.attr in PROGRAM_ATTRS:
        return any("program" in n for n in _chain_names(f.value))
    return False


def _is_lane_guard(call: ast.Call) -> bool:
    f = call.func
    return (isinstance(f, ast.Attribute) and f.attr in LANE_GUARDS
            and any("lane" in n for n in _chain_names(f.value)))


class _FnInfo:
    __slots__ = ("node", "dispatches", "guarded", "calls")

    def __init__(self, node):
        self.node = node
        self.dispatches: List[ast.Call] = []
        self.guarded = False
        self.calls: Set[str] = set()


def _analyze(fn) -> _FnInfo:
    """One function's dispatch calls, lane guards, and called simple
    names. Nested closures (the lane launch/fallback lambdas) count
    toward the enclosing function — the guard and the dispatch live in
    the same logical launch path."""
    info = _FnInfo(fn)
    for node in ast.walk(fn):
        if not isinstance(node, ast.Call):
            continue
        if _is_lane_guard(node):
            info.guarded = True
        elif _is_dispatch(node):
            info.dispatches.append(node)
        f = node.func
        if isinstance(f, ast.Name):
            info.calls.add(f.id)
        elif isinstance(f, ast.Attribute):
            info.calls.add(f.attr)
    return info


class UnguardedDispatchRule(Rule):
    name = "device-unguarded-dispatch"
    description = ("engine/plugin path reaches a jit/pjit/shard_map "
                   "dispatch without going through the fbtpu-armor "
                   "DeviceLane — device faults would stall or drop "
                   "instead of failing over (ops/fault.py)")

    def check(self, module: Module) -> List[Finding]:
        if not any(s in module.path for s in SCOPES):
            return []
        by_name: Dict[str, List[_FnInfo]] = {}
        infos: List[_FnInfo] = []
        nested: Set[ast.AST] = set()
        for node in ast.walk(module.tree):
            if isinstance(node, (ast.FunctionDef, ast.AsyncFunctionDef)):
                info = _analyze(node)
                infos.append(info)
                by_name.setdefault(node.name, []).append(info)
                for sub in ast.walk(node):
                    if sub is not node and isinstance(
                            sub, (ast.FunctionDef, ast.AsyncFunctionDef)):
                        nested.add(sub)

        def closure(start: _FnInfo) -> Tuple[List[ast.Call], bool]:
            dispatches: List[ast.Call] = list(start.dispatches)
            guarded = start.guarded
            seen: Set[str] = {start.node.name}
            frontier = set(start.calls)
            while frontier:
                name = frontier.pop()
                if name in seen:
                    continue
                seen.add(name)
                for callee in by_name.get(name, ()):
                    dispatches.extend(callee.dispatches)
                    guarded = guarded or callee.guarded
                    frontier.update(callee.calls)
            return dispatches, guarded

        out: List[Finding] = []
        for info in infos:
            name = info.node.name
            if name.startswith("_"):
                continue  # helpers are covered via their public callers
            if info.node in nested:
                continue  # closures are reached via their container
            dispatches, guarded = closure(info)
            if not dispatches or guarded:
                continue
            f = self.finding(
                module, info.node,
                f"device path {name!r} reaches a jit/shard_map dispatch "
                f"(line "
                f"{', '.join(str(d.lineno) for d in dispatches[:3])}) "
                f"without the fbtpu-armor DeviceLane (lane.run/begin/"
                f"finish) — device faults must fail over bit-exactly, "
                f"not stall or drop (ops/fault.py)",
                extra_lines=tuple(d.lineno for d in dispatches))
            if f is not None:
                out.append(f)
        return out
