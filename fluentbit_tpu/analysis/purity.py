"""JAX kernel purity / retrace rules.

The device kernels must stay pure and shape-stable to hold the ≥50M
lines/s line: one stray host sync serializes every dispatch behind a
device→host copy, one Python side effect fires once at trace time (or
once per retrace — silently wrong either way), and one data/shape branch
turns the compile cache into a compile storm.

Traced-function discovery is name-based and transitive:

- seeds: defs decorated with ``jit``/``pjit``/``pmap``/``vmap``/
  ``shard_map`` (incl. through ``partial``), and defs referenced in the
  arguments of ``jit``/``pjit``/``pmap``/``vmap``/``shard_map``/
  ``checkpoint``/``remat``/``lax.scan``/``fori_loop``/``while_loop``/
  ``cond`` calls — single-level aliases are followed
  (``impl = self._a if p else self._b; jax.jit(impl)`` marks both,
  including through attribute stores like ``self._impl = impl``).
- propagation: a call to a module-local def (or alias) from traced code
  marks the callee; defs nested inside traced defs are traced.

The ``pjit``/``shard_map`` coverage exists for the partitioned mesh
plane (ops/mesh.py + ops/grep.py mesh matcher): the sharded hot path
compiles ONCE per mesh and runs on every device per dispatch, so a
host callback or shape-dependent retrace that sneaks in there costs
n_devices× what it costs single-device.

Rules emitted:

- ``jax-host-sync``: ``block_until_ready``/``device_get``/``.item()``/
  ``.tolist()``/``np.asarray``/``np.array``/``np.frombuffer`` and
  1-arg ``float()``/``int()``/``bool()`` casts inside traced code;
  also host-callback escapes (``pure_callback``/``io_callback``/
  ``debug_callback``/``host_callback``) — inside a pjit/shard_map
  program each shard's step blocks on a Python round-trip.
- ``jax-side-effect``: ``print``, ``global``/``nonlocal``, and
  attribute writes on ``self`` inside traced code.
- ``jax-retrace``: ``if``/``while`` whose test touches ``.shape``/
  ``.ndim``/``.size``/``len(<param>)`` directly (per-shape recompiles),
  or references a traced parameter bare (tracer boolification —
  ``TracerBoolConversionError`` at run time).

Batched filter entry points: defs named ``process_batch`` (the engine's
whole-chunk filter hook) are additionally checked for the retrace
hazard even though they are not traced themselves — a Python branch on
an array ``.shape``/``.size``/``.ndim`` inside one re-specializes every
kernel it feeds per distinct shape, which is exactly the compile-storm
the traced rule exists for. Host syncs are legal there (it IS host
code), so only the shape-branch rule applies.

Shape-derived *locals* (``pad = G2 * m - Lk``) branching is deliberately
NOT flagged: bucketed shapes make those branches trace-stable by design
here, and chasing derivation would drown the signal in noise.
"""

from __future__ import annotations

import ast
from typing import Dict, List, Optional, Set

from . import Finding, Module, Rule

__all__ = ["JaxPurityRules"]

#: call/decorator terminals that trace their function arguments
_TRACERS = {"jit", "pjit", "pmap", "vmap", "shard_map", "checkpoint",
            "remat", "scan", "fori_loop", "while_loop", "cond",
            "named_call", "custom_jvp", "custom_vjp"}

#: decorator terminals that make the decorated def itself traced
_TRACER_DECOS = {"jit", "pjit", "pmap", "vmap", "shard_map"}

#: host-callback escapes: legal jax, but a per-dispatch Python round
#: trip — in a sharded program every device's step blocks on it
_HOST_CALLBACKS = {"pure_callback", "io_callback", "debug_callback",
                   "host_callback"}

#: batched filter entry points — shape-branch (retrace) checked even
#: though untraced (see module docstring)
_BATCH_ENTRIES = {"process_batch"}

_NP_SYNCS = {"asarray", "array", "frombuffer", "copy"}
_ATTR_SYNCS = {"block_until_ready", "item", "tolist", "device_get"}
_CAST_SYNCS = {"float", "int", "bool"}
_SHAPE_ATTRS = {"shape", "ndim", "size"}


def _terminal(func: ast.AST) -> Optional[str]:
    if isinstance(func, ast.Attribute):
        return func.attr
    if isinstance(func, ast.Name):
        return func.id
    return None


def _ref_names(expr: ast.AST) -> Set[str]:
    out: Set[str] = set()
    for node in ast.walk(expr):
        if isinstance(node, ast.Name):
            out.add(node.id)
        elif isinstance(node, ast.Attribute):
            out.add(node.attr)
    return out


class JaxPurityRules(Rule):
    name = "jax-purity"  # umbrella; findings carry their precise rule
    description = ("host syncs / side effects / retrace hazards inside "
                   "jit- or scan-traced code")

    def check(self, module: Module) -> List[Finding]:
        if "jax" not in module.source \
                and not any(e in module.source for e in _BATCH_ENTRIES):
            return []
        tree = module.tree

        defs: Dict[str, List[ast.AST]] = {}
        for node in ast.walk(tree):
            if isinstance(node, (ast.FunctionDef, ast.AsyncFunctionDef)):
                defs.setdefault(node.name, []).append(node)

        # single-level aliases: name/attr → def names its value refers to
        aliases: Dict[str, Set[str]] = {}
        for node in ast.walk(tree):
            if isinstance(node, ast.Assign):
                refs = _ref_names(node.value) & set(defs)
                if not refs:
                    continue
                for tgt in node.targets:
                    t = _terminal(tgt)
                    if t is not None:
                        aliases.setdefault(t, set()).update(refs)

        def resolve(names: Set[str]) -> Set[str]:
            out = names & set(defs)
            for n in names:
                out |= aliases.get(n, set()) & set(defs)
            return out

        traced: Set[str] = set()
        for node in ast.walk(tree):
            if isinstance(node, (ast.FunctionDef, ast.AsyncFunctionDef)):
                for dec in node.decorator_list:
                    if _ref_names(dec) & _TRACER_DECOS:
                        traced.add(node.name)
            elif isinstance(node, ast.Call):
                if _terminal(node.func) in _TRACERS:
                    arg_refs: Set[str] = set()
                    for a in list(node.args) + [k.value for k in node.keywords]:
                        arg_refs |= _ref_names(a)
                    traced |= resolve(arg_refs)

        # transitive closure over module-local calls from traced code
        changed = True
        while changed:
            changed = False
            for name in list(traced):
                for d in defs.get(name, ()):
                    for node in ast.walk(d):
                        if isinstance(node, (ast.FunctionDef,
                                             ast.AsyncFunctionDef)) \
                                and node.name not in traced:
                            traced.add(node.name)  # nested def
                            changed = True
                        elif isinstance(node, ast.Call):
                            callee = _terminal(node.func)
                            if callee is None:
                                continue
                            for t in resolve({callee}):
                                if t not in traced:
                                    traced.add(t)
                                    changed = True

        findings: List[Finding] = []
        for name in traced:
            for d in defs.get(name, ()):
                findings.extend(self._check_traced(module, d))
        # batched filter entry points: retrace (shape-branch) rule only
        # — they are host code feeding jit'd kernels, so host syncs are
        # fine but per-shape Python branches re-specialize downstream
        for name in _BATCH_ENTRIES:
            if name in traced:
                continue  # already fully checked above
            for d in defs.get(name, ()):
                findings.extend(self._check_batch_entry(module, d))
        # a def can be reached under several names; dedup by location
        seen: Set[tuple] = set()
        out = []
        for f in findings:
            key = (f.line, f.col, f.rule, f.message)
            if key not in seen:
                seen.add(key)
                out.append(f)
        out.sort(key=lambda f: (f.line, f.col))
        return out

    def _check_batch_entry(self, module: Module, fn) -> List[Finding]:
        """Retrace-only pass over a ``process_batch`` def: flag
        ``if``/``while`` tests touching array ``.shape``/``.size``/
        ``.ndim`` — each distinct shape re-specializes the kernels the
        batch feeds (bucket shapes upstream: ops.batch.bucket_size)."""
        out: List[Finding] = []
        where = f"batched entry ({fn.name})"

        def walk(node: ast.AST) -> None:
            for child in ast.iter_child_nodes(node):
                if isinstance(child, (ast.FunctionDef,
                                      ast.AsyncFunctionDef)):
                    continue
                if isinstance(child, (ast.If, ast.While)):
                    for sub in ast.walk(child.test):
                        if isinstance(sub, ast.Attribute) \
                                and sub.attr in _SHAPE_ATTRS:
                            self._emit(
                                module, child, "jax-retrace",
                                f"Python branch on `.{sub.attr}` in "
                                f"{where}: re-specializes the "
                                f"downstream kernel per distinct shape "
                                f"— bucket shapes upstream "
                                f"(ops.batch.bucket_size)", out)
                            break
                walk(child)

        walk(fn)
        return out

    # -- per-function checks ------------------------------------------

    def _emit(self, module: Module, node: ast.AST, rule: str,
              message: str, out: List[Finding]) -> None:
        line = getattr(node, "lineno", 1)
        if not module.allowed(rule, line):
            out.append(Finding(module.path, line,
                               getattr(node, "col_offset", 0),
                               rule, message))

    def _check_traced(self, module: Module, fn) -> List[Finding]:
        out: List[Finding] = []
        params = {a.arg for a in (fn.args.posonlyargs + fn.args.args
                                  + fn.args.kwonlyargs)} - {"self", "cls"}
        # dict-like params: subscripted with a string key somewhere in
        # the body (`t["trans_flat"]`) — these are pytree containers,
        # so `"key" in t` is static structure, not tracer data. A
        # param never string-subscripted stays array-like and keeps
        # the full retrace/boolification checks.
        dict_params: Set[str] = set()
        for node in ast.walk(fn):
            if isinstance(node, ast.Subscript) \
                    and isinstance(node.value, ast.Name) \
                    and node.value.id in params \
                    and isinstance(node.slice, ast.Constant) \
                    and isinstance(node.slice.value, str):
                dict_params.add(node.value.id)
        where = f"traced code ({fn.name})"

        def walk(node: ast.AST) -> None:
            for child in ast.iter_child_nodes(node):
                # nested defs are traced too but get their own pass
                # (their params differ)
                if isinstance(child, (ast.FunctionDef,
                                      ast.AsyncFunctionDef)):
                    continue
                self._check_node(module, child, params, where, out,
                                 dict_params)
                walk(child)

        walk(fn)
        return out

    def _check_node(self, module: Module, node: ast.AST, params: Set[str],
                    where: str, out: List[Finding],
                    dict_params: Optional[Set[str]] = None) -> None:
        if isinstance(node, ast.Call):
            t = _terminal(node.func)
            if t in _HOST_CALLBACKS:
                self._emit(module, node, "jax-host-sync",
                           f"`{t}(...)` in {where}: a host-callback "
                           f"escape blocks every device's step on a "
                           f"Python round-trip per dispatch — keep the "
                           f"sharded hot path callback-free (compute "
                           f"on-device or post-process the forced "
                           f"result)", out)
                return
            if isinstance(node.func, ast.Attribute):
                base = _terminal(node.func.value)
                if t in _NP_SYNCS and base in ("np", "numpy"):
                    self._emit(module, node, "jax-host-sync",
                               f"`{base}.{t}(...)` in {where} forces a "
                               f"device→host copy per dispatch; use jnp "
                               f"or move it outside the kernel", out)
                elif t in _ATTR_SYNCS:
                    self._emit(module, node, "jax-host-sync",
                               f"`.{t}()` in {where} synchronizes the "
                               f"host with the device stream", out)
            elif isinstance(node.func, ast.Name):
                if t in _CAST_SYNCS and len(node.args) == 1 \
                        and not node.keywords:
                    self._emit(module, node, "jax-host-sync",
                               f"`{t}(...)` in {where} concretizes a "
                               f"traced value (host sync or tracer "
                               f"error)", out)
                elif t == "print":
                    self._emit(module, node, "jax-side-effect",
                               f"`print` in {where} fires at trace "
                               f"time, not per call; use jax.debug."
                               f"print if intended", out)
        elif isinstance(node, (ast.Global, ast.Nonlocal)):
            kw = "global" if isinstance(node, ast.Global) else "nonlocal"
            self._emit(module, node, "jax-side-effect",
                       f"`{kw}` write in {where}: traced code must be "
                       f"pure — return the value instead", out)
        elif isinstance(node, ast.Attribute) \
                and isinstance(node.ctx, ast.Store) \
                and isinstance(node.value, ast.Name) \
                and node.value.id == "self":
            self._emit(module, node, "jax-side-effect",
                       f"attribute write `self.{node.attr} = ...` in "
                       f"{where}: runs once at trace time, silently "
                       f"stale after; thread state through the carry",
                       out)
        elif isinstance(node, (ast.If, ast.While)):
            self._check_branch(module, node, params, where, out,
                               dict_params or set())

    def _check_branch(self, module: Module, node, params: Set[str],
                      where: str, out: List[Finding],
                      dict_params: Set[str] = frozenset()) -> None:
        # pytree-structure membership is static at trace time: a kernel
        # taking its table pytree as a DICT param may branch on
        # `"<leaf>" in t` to pick a sub-kernel — that is pytree
        # STRUCTURE (fixed per jit cache entry), not tracer data, so it
        # can never boolify a tracer. The idiom is allowed; no kernel
        # in the tree uses it today. Only params the function also
        # string-subscripts qualify: `"GET" in batch` over a traced
        # ARRAY param still iterates the tracer and must keep firing.
        test = node.test
        if isinstance(test, ast.Compare) and len(test.ops) == 1 \
                and isinstance(test.ops[0], (ast.In, ast.NotIn)) \
                and isinstance(test.left, ast.Constant) \
                and isinstance(test.left.value, str) \
                and all(n.id in dict_params or n.id not in params
                        for n in ast.walk(test)
                        if isinstance(n, ast.Name)):
            return
        for sub in ast.walk(node.test):
            if isinstance(sub, ast.Attribute) and sub.attr in _SHAPE_ATTRS:
                self._emit(module, node, "jax-retrace",
                           f"Python branch on `.{sub.attr}` in {where}: "
                           f"recompiles per distinct shape — bucket "
                           f"shapes upstream or use lax.cond", out)
                return
            if isinstance(sub, ast.Call) and _terminal(sub.func) == "len" \
                    and len(sub.args) == 1 \
                    and isinstance(sub.args[0], ast.Name) \
                    and sub.args[0].id in params:
                self._emit(module, node, "jax-retrace",
                           f"Python branch on `len(...)` of a traced "
                           f"argument in {where}: recompiles per "
                           f"distinct shape", out)
                return
            if isinstance(sub, ast.Name) and sub.id in params:
                self._emit(module, node, "jax-retrace",
                           f"Python branch on traced argument "
                           f"`{sub.id}` in {where}: tracer "
                           f"boolification fails at run time — use "
                           f"jnp.where or lax.cond", out)
                return
