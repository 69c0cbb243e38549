"""fbtpu-lint — repo-native static analysis for the data plane.

Round 4's heap overflow taught us that the bug classes this codebase
actually ships are not caught by example-based tests: they live in the
gaps *between* correct components — a guarded attribute touched off-lock
by a new call path, an ``await`` slipped inside a ``threading`` lock, a
host sync added to a traced kernel. This package makes those invariants
machine-checked, the same way ``tests/test_asan_native.py`` made the
memory-safety invariant repeatable.

Six rule families (see ANALYSIS.md for the full contract):

- **lock discipline** (`guarded-by`, `await-in-lock`): a declarative
  guarded-by registry (`analysis.registry.GUARDS`) names, per module,
  the attributes/globals whose access must hold a named lock; the
  checker flags accesses outside a lexical ``with <lock>:`` scope, and
  flags ``await`` while a ``threading`` lock is held inside async code.
- **JAX kernel purity** (`jax-host-sync`, `jax-side-effect`,
  `jax-retrace`): functions reachable from ``jit``/``pmap``/
  ``shard_map``/``lax.scan``/``lax.fori_loop`` tracing must not host-sync
  (``block_until_ready``, ``np.asarray``, ``float()``/``int()`` on traced
  values), must not carry Python side effects, and must not branch on
  shapes/data in Python (recompile storms / tracer errors).
- **silent failures** (`swallowed-error`): ``except Exception: pass`` on
  data-path modules hides real errors; narrow the type, count it in a
  metric, or justify the swallow with an explicit suppression.
- **batch exactness** (`batch-decline-after-commit`,
  `batch-commit-replay`, `batch-stateful-unmarked`,
  `batch-no-fallback`, `batch-unordered-emit`): interprocedural
  dataflow over every ``FilterPlugin.process_batch`` verifying the
  batched fast path's contracts — declines dominated by zero committed
  side effects, guarded emits, a reachable per-record fallback, and
  first-seen emission order (analysis.batch).
- **decline-path swallows** (`decline-swallow`): broad excepts whose
  body only declines a fast path (None assignment / return None)
  without logging — silent permanent fallback (analysis.decline).
- **dtype narrowing** (`dtype-narrowing`): int64→int32 truncation in
  offset/index math — astype/array/cumsum with a narrow dtype on
  offset-flavored values (analysis.dtype).
- **flush-path deadlines** (`await-no-deadline`): raw socket/upstream
  awaits inside output flush paths with no ``asyncio.wait_for``/
  ``guard.io_deadline`` bound, and ``open_connection`` dials without a
  ``timeout=`` — the hung-peer shape the fbtpu-guard plane contains
  (analysis.deadline).
- **metered ingest** (`qos-unmetered-ingest`): any public ingest entry
  point in ``core/`` from which a chunk-pool append is reachable must
  also reach the fbtpu-qos tenant admission call (``qos.admit``) —
  an unmetered path silently bypasses every tenant quota
  (analysis.qos).
- **guarded device dispatch** (`device-unguarded-dispatch`): any
  public plugin/flux path from which a jit/pjit/shard_map dispatch is
  reachable must also go through the fbtpu-armor ``DeviceLane``
  (``lane.run``/``begin``/``finish``) — an unguarded dispatch would
  stall or drop on device faults instead of failing over bit-exactly
  (analysis.devlane).
- **minimized kernel DFAs** (`grep-unminimized-dfa`): any path from
  which a ``GrepProgram``/``GrepTables`` build is reachable must not
  also reach an unminimized-DFA source (raw ``DFA(...)`` construction,
  ``compile_dfa(minimize=False)``) — an un-reduced table silently
  grows the tables and shrinks the stride budget
  (analysis.shrink; DEVICE_PLANE.md "shrink").
- **launch graph / transfer budget** (`device-multi-launch-chain`,
  `device-undonated-buffer`, `device-host-roundtrip`,
  `device-sync-in-staging-loop`, `stage-redundant-copy`): the
  fbtpu-xray interprocedural walk from every plugin/flux chain entry
  to every device launch site — launches per staged segment, PCIe
  byte crossings, the donate set, host scatters
  (analysis.launchgraph; budget gated by analysis/launch_budget.json,
  rendered by ``--graph json|dot``).
- **host-memory pack** (`host-redundant-copy`,
  `host-decode-then-restage`, `host-mutable-view-escape`,
  `mmap-lifetime-escape`): the fbtpu-memscope copy census — a walk
  from every ingest entry counting the materialization passes and
  byte walks each record pays, cross-referenced against the
  ``core.copywitness`` instrumentation sites' declared per-record
  byte budgets, plus escape rules for mutable staging-arena views and
  views that outlive their mmap (analysis.memscope; census gated by
  analysis/copy_budget.json).
- **fusion pack** (`fusable-unfused-boundary`,
  `fusion-blocked-by-host-compact`, `cross-launch-restage`,
  `fused-effect-violation`, `fusion-plan-regression`): the
  fbtpu-fuseplan planner classifies every boundary between consecutive
  device launches of a chain as FUSABLE or BLOCKED (host compact,
  intervening host effect, speccheck aval incompatibility, donation
  break), prices the planned fused program, and gates it against
  analysis/fusion_plan.json (analysis.fuseplan; rendered by
  ``--graph fusion|fusion-dot``).
- **stale suppressions** (`stale-suppression`): an
  ``allow(<rule>)`` comment whose named rules no longer match any
  finding on the covered line — fixed code, stale waiver
  (analysis.suppress).

The native C/C++ data plane has its own gate (analysis.native_gate):
clang-tidy with the repo profile (.clang-tidy), the gcc ``-fanalyzer``
static analyzer, and a libclang-based checker for the codec's
invariants (container emission balance, bounds-guarded cursor reads,
error-path frees). ``python -m fluentbit_tpu.analysis --all`` runs
everything; C sources take the same ``fbtpu-lint: allow(...)``
suppressions in ``/* */`` or ``//`` comments.

Suppressions: a ``# fbtpu-lint: allow(<rule>[, <rule>...])`` comment on
the flagged line (or the line above) silences that rule there. Every
suppression must carry an inline justification.

Run: ``python -m fluentbit_tpu.analysis [paths...]`` (exit 1 on
findings); ``tests/test_lint.py`` gates the whole package tree.
"""

from __future__ import annotations

import ast
import os
import re
from dataclasses import dataclass, field
from typing import Iterable, List, Optional, Sequence, Tuple

__all__ = [
    "Finding", "Module", "lint_source", "lint_path", "lint_paths",
    "iter_py_files", "RULES", "rule_names",
]

_ALLOW_RE = re.compile(r"#\s*fbtpu-lint:\s*allow\(([^)]*)\)")


@dataclass(frozen=True)
class Finding:
    """One rule violation at a source location."""

    path: str
    line: int
    col: int
    rule: str
    message: str
    #: "error" fails the gate outright; "warning" fails too unless
    #: baselined (see __main__ --baseline) — the split exists so CI can
    #: diff legacy debt instead of flag-daying it
    severity: str = "error"

    def render(self) -> str:
        return (f"{self.path}:{self.line}:{self.col}: "
                f"[{self.rule}] {self.severity}: {self.message}")

    def baseline_key(self) -> tuple:
        """Line/col-insensitive identity for --baseline diffs (a pure
        reformat must not churn the baseline)."""
        return (self.path, self.rule, self.message)


class Module:
    """Parsed unit handed to every rule: AST + raw lines (for the
    suppression comments ast discards) + the posix-ish path rules match
    registry entries and data-path prefixes against."""

    def __init__(self, path: str, source: str):
        self.path = path.replace(os.sep, "/")
        self.source = source
        self.lines = source.splitlines()
        self.tree = ast.parse(source, filename=path)

    def allowed(self, rule: str, line: int, extra_lines: Sequence[int] = ()) -> bool:
        """True when an allow(<rule>) comment covers ``line`` (or the
        line above it, or any of ``extra_lines`` — multi-line constructs
        like except handlers accept the comment on their body too)."""
        for ln in {line, line - 1, *extra_lines}:
            if 1 <= ln <= len(self.lines):
                m = _ALLOW_RE.search(self.lines[ln - 1])
                if m:
                    names = {p.strip() for p in m.group(1).split(",")}
                    if rule in names or "*" in names:
                        return True
        return False


class Rule:
    """Base rule: subclasses set ``name`` and implement ``check``."""

    name = ""
    description = ""
    severity = "error"

    def check(self, module: Module) -> List[Finding]:  # pragma: no cover
        raise NotImplementedError

    def finding(self, module: Module, node: ast.AST, message: str,
                extra_lines: Sequence[int] = ()) -> Optional[Finding]:
        """Build a Finding unless a suppression comment covers it."""
        line = getattr(node, "lineno", 1)
        if module.allowed(self.name, line, extra_lines):
            return None
        return Finding(module.path, line, getattr(node, "col_offset", 0),
                       self.name, message, self.severity)


def _build_rules(guards=None) -> List[Rule]:
    from .batch import BatchExactnessRules
    from .deadline import AwaitNoDeadlineRule
    from .decline import DeclineSwallowRule
    from .devlane import UnguardedDispatchRule
    from .dtype import DtypeNarrowingRule
    from .fuseplan import FuseplanRules
    from .launchgraph import LaunchGraphRules
    from .locks import AwaitUnderLockRule, GuardedByRule
    from .locksmith import LocksmithRules
    from .memscope import MemscopeRules
    from .purity import JaxPurityRules
    from .qos import UnmeteredIngestRule
    from .shrink import UnminimizedDfaRule
    from .silent import SwallowedErrorRule
    from .speccheck import SpecCheckRules
    from .suppress import StaleSuppressionRule

    return [
        GuardedByRule(guards),
        AwaitUnderLockRule(),
        JaxPurityRules(),
        SwallowedErrorRule(),
        BatchExactnessRules(),
        DeclineSwallowRule(),
        DtypeNarrowingRule(),
        AwaitNoDeadlineRule(),
        UnmeteredIngestRule(),
        UnguardedDispatchRule(),
        UnminimizedDfaRule(),
        LaunchGraphRules(),
        SpecCheckRules(),
        LocksmithRules(guards),
        MemscopeRules(),
        FuseplanRules(),
        # last: the stale-suppression audit re-runs the packs above on
        # a suppression-disabled clone to prove a comment still earns
        # its keep
        StaleSuppressionRule(),
    ]


#: Default rule set (module-level so ``--list-rules`` and tests share it).
RULES: List[Rule] = _build_rules()


def rule_names() -> List[str]:
    names: List[str] = []
    for r in RULES:
        for n in ([r.name] if isinstance(r.name, str) else list(r.name)):
            if n not in names:
                names.append(n)
    return names


def lint_source(source: str, path: str, guards=None) -> List[Finding]:
    """Lint one source string as if it lived at ``path`` (the test
    fixture entry point — registry matching keys off the path)."""
    module = Module(path, source)
    rules = RULES if guards is None else _build_rules(guards)
    out: List[Finding] = []
    for rule in rules:
        out.extend(rule.check(module))
    out.sort(key=lambda f: (f.path, f.line, f.col, f.rule))
    return out


def lint_path(path: str, guards=None) -> List[Finding]:
    with open(path, "r", encoding="utf-8", errors="replace") as fh:
        source = fh.read()
    try:
        return lint_source(source, path, guards)
    except SyntaxError as e:
        return [Finding(path.replace(os.sep, "/"), e.lineno or 1, 0,
                        "parse", f"syntax error: {e.msg}")]


def iter_py_files(paths: Iterable[str]) -> List[str]:
    """Expand paths to .py files. A path that is neither a directory
    nor an existing .py file raises — a lint gate that silently lints
    nothing on a typo'd/moved path would stay green forever."""
    files: List[str] = []
    for p in paths:
        if os.path.isdir(p):
            for root, dirs, names in os.walk(p):
                dirs[:] = sorted(d for d in dirs
                                 if d not in ("__pycache__", ".git"))
                for n in sorted(names):
                    if n.endswith(".py"):
                        files.append(os.path.join(root, n))
        elif p.endswith(".py") and os.path.isfile(p):
            files.append(p)
        else:
            raise FileNotFoundError(
                f"fbtpu-lint: not a directory or .py file: {p!r}")
    return files


def lint_paths(paths: Iterable[str], guards=None) -> List[Finding]:
    out: List[Finding] = []
    for f in iter_py_files(paths):
        out.extend(lint_path(f, guards))
    return out
