"""grep-unminimized-dfa rule.

fbtpu-shrink (DEVICE_PLANE.md "shrink") moves the whole kernel-table
economy — stride depth, gathered table size, native table cache footprint, mesh
replication size — onto one invariant: every ``DFA`` that reaches
``GrepProgram`` / ``GrepTables`` / ``GrepFilterTables`` passed through
the compile-path reduction pass (``regex.dfa.compile_dfa``: Hopcroft
minimization, dead-state pruning, byte-class remerge). A hand-built
``DFA(...)`` table, or a ``compile_dfa(..., minimize=False)`` escape
hatch wired into a production path, silently re-bloats S and C — the
kernel still produces correct verdicts, so nothing at runtime notices
that the tables grew and the stride dropped until a bench round
asks where the throughput went.

``grep-unminimized-dfa`` makes the invariant machine-checked (the
``qos-unmetered-ingest`` / ``device-unguarded-dispatch`` registry
pattern): in ``fluentbit_tpu/`` modules (outside ``regex/`` — the
definition site — and ``analysis/``), any function from whose
same-module call closure BOTH a program/tables constructor AND an
unminimized-DFA source are reachable is flagged. Sources are matched
lexically: a bare ``DFA(...)`` construction (the dataclass constructor
bypasses the minimizer by definition) and ``compile_dfa`` called with a
constant-false ``minimize=``. The closure is the same intentionally
lexical same-module call-name walk the sibling rules use; cross-module
laundering is out of scope (and the runtime ShrinkStats audit trail on
the DFA covers it in bench output).

Suppress with ``# fbtpu-lint: allow(grep-unminimized-dfa)`` plus a
justification — e.g. a differential harness that deliberately measures
the unminimized machine.
"""

from __future__ import annotations

import ast
from typing import Dict, List, Set, Tuple

from . import Finding, Module, Rule

__all__ = ["UnminimizedDfaRule"]

#: Where the invariant binds. The regex package is the definition site
#: (the minimizer itself must build raw tables) and analysis/ lints
#: itself; everything else in the package is a consumer.
SCOPE = "fluentbit_tpu/"
EXEMPT = ("fluentbit_tpu/regex/", "fluentbit_tpu/analysis/")

#: Kernel-table sinks: a DFA handed to any of these is on the hot path.
SINK_NAMES = frozenset({"GrepProgram", "GrepTables", "GrepFilterTables"})


def _call_name(call: ast.Call):
    f = call.func
    if isinstance(f, ast.Name):
        return f.id
    if isinstance(f, ast.Attribute):
        return f.attr
    return None


def _is_const_false(node) -> bool:
    return isinstance(node, ast.Constant) and not node.value


def _is_source(call: ast.Call) -> bool:
    name = _call_name(call)
    if name == "DFA":
        return True
    if name == "compile_dfa":
        return any(kw.arg == "minimize" and _is_const_false(kw.value)
                   for kw in call.keywords)
    return False


class _FnInfo:
    __slots__ = ("node", "sources", "sinks", "calls")

    def __init__(self, node):
        self.node = node
        self.sources: List[ast.Call] = []
        self.sinks: List[ast.Call] = []
        self.calls: Set[str] = set()


def _analyze(fn) -> _FnInfo:
    info = _FnInfo(fn)
    for node in ast.walk(fn):
        if not isinstance(node, ast.Call):
            continue
        if _is_source(node):
            info.sources.append(node)
        elif _call_name(node) in SINK_NAMES:
            info.sinks.append(node)
        f = node.func
        if isinstance(f, ast.Name):
            info.calls.add(f.id)
        elif isinstance(f, ast.Attribute):
            info.calls.add(f.attr)
    return info


class UnminimizedDfaRule(Rule):
    name = "grep-unminimized-dfa"
    description = ("a DFA that bypassed the fbtpu-shrink compile-path "
                   "reduction (raw DFA(...) construction or "
                   "compile_dfa(minimize=False)) reaches GrepProgram/"
                   "GrepTables — the kernel runs on an un-minimized "
                   "table, silently growing the tables and "
                   "shrinking the stride (regex/dfa.py)")

    def check(self, module: Module) -> List[Finding]:
        if SCOPE not in module.path or \
                any(e in module.path for e in EXEMPT):
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

        def closure(start: _FnInfo) -> Tuple[List[ast.Call],
                                             List[ast.Call]]:
            sources = list(start.sources)
            sinks = list(start.sinks)
            seen: Set[str] = {start.node.name}
            frontier = set(start.calls)
            while frontier:
                name = frontier.pop()
                if name in seen:
                    continue
                seen.add(name)
                for callee in by_name.get(name, ()):
                    sources.extend(callee.sources)
                    sinks.extend(callee.sinks)
                    frontier.update(callee.calls)
            return sources, sinks

        out: List[Finding] = []
        flagged: Set[int] = set()
        for info in infos:
            if info.node in nested:
                continue  # closures are reached via their container
            sources, sinks = closure(info)
            if not sources or not sinks:
                continue
            for src in sources:
                if src.lineno in flagged:
                    continue
                flagged.add(src.lineno)
                kind = ("raw DFA(...) construction"
                        if _call_name(src) == "DFA"
                        else "compile_dfa(minimize=False)")
                f = self.finding(
                    module, src,
                    f"{kind} reaches a GrepProgram/GrepTables build "
                    f"(via {info.node.name!r}) without the fbtpu-shrink "
                    f"reduction pass — the kernel table ships "
                    f"un-minimized, growing the tables and "
                    f"shrinking the stride budget (regex/dfa.py "
                    f"compile_dfa)",
                    extra_lines=(info.node.lineno,))
                if f is not None:
                    out.append(f)
        return out
