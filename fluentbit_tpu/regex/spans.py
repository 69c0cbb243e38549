"""Capture spans from two automata — greedy regular-expression parsing.

A verdict is one bit a row; a parser needs ``2·G`` offsets a row (the
named groups' starts and ends). They come from a second automaton
product over the same AST (``regex.parser``), after Frisch and Cardelli
(ICALP 2004) and Grathwohl, Henglein, Nielsen and Rasmussen (2013):

- a **prioritised** Thompson NFA: the ε edges out of every state in the
  order a backtracking matcher tries them (greedy ``* + ? {m,n}`` body
  before exit, lazy ones exit before body, alternation left to right,
  the search prefix *enter the pattern* before *consume one byte*), an
  ε edge that enters or leaves named group ``g`` tagged ``open_g`` /
  ``close_g``, anchors as constraint ε edges on the previous / next
  symbol (``regex.dfa``'s model);
- **pass 1, right to left**: ``R_i`` = the walk states from which accept
  can still be reached on ``x[i..n)``, determinised ahead of time over
  the byte classes: ``r_i = rev[r_{i+1}, cls(x[i])]``;
- **pass 2, left to right**: from the walk state ``u_i``, of the
  priority-ordered ε-paths that end in a byte edge on ``x[i]``, the
  first whose target lies in ``R_{i+1}`` — a table built here:
  ``(u_{i+1}, tags_i) = fwd[u_i, cls(x[i]), r_{i+1}]``; group ``g``
  starts at the ``i`` whose tags hold ``open_g`` and ends where they
  hold ``close_g``.

The walk takes, at each step, the highest-priority edge from which a
match can still be completed: the first successful path of a
backtracking engine (Onigmo; Python ``re``, this repo's stand-in).
That is **exact only inside a class**, checked here, and everything
outside it raises :class:`SpanDecline` with its reason: no repeated
body may be nullable (backtracking engines cut an empty iteration), no
named group may lie under a repetition that can run it twice, no
possessive quantifier, no ``\\Z`` (its automaton consumes the newline),
no ``$``-then-``^`` on one ε-path (``compile_dfa`` resolves the two
constraint kinds in a fixed order), no case folding, and the groups and
walk states have to fit one packed i32 a table entry.

A *walk state* is the target of a byte edge (or the start) together
with what the anchors need of the last symbol (BOS, ``\\n``, other) —
carried only where a ``^`` or ``\\A`` is ε-reachable.
"""

from __future__ import annotations

from dataclasses import dataclass
from typing import Dict, List, Optional, Tuple

import numpy as np

from .dfa import BOS_BIT, EOL, EOL_BIT, NL_BIT
from .parser import (ALL_BYTES, Alt, Anchor, Group, Lit, Node, ParsedRegex,
                     Rep, Seq)

DEAD, ACCEPTED = 0, 1  # walk ids; walk state u has id u + 2
PC_BOS, PC_NL, PC_OTHER = 0, 1, 2  # class of the last symbol consumed
MAX_REV_STATES = 32768
MAX_TABLE_ENTRIES = 1 << 24


class SpanDecline(Exception):
    """The pattern is outside the class the span program is exact in."""


class _TNFA:
    """The prioritised, tagged NFA. A state has one byte edge, or an
    ordered list of ε edges ``(kind, mask, dst, tag)`` — ``kind`` None
    or ``"prev"`` / ``"next"`` with the symbols that let it pass,
    ``tag`` a bit index (``2g`` open, ``2g+1`` close) or -1."""

    def __init__(self) -> None:
        self.byte: List[Optional[Tuple[int, int]]] = []
        self.eps: List[List[Tuple[Optional[str], int, int, int]]] = []

    def new(self) -> int:
        self.byte.append(None)
        self.eps.append([])
        return len(self.byte) - 1

    def add_eps(self, src: int, dst: int, kind: Optional[str] = None,
                mask: int = 0, tag: int = -1) -> None:
        self.eps[src].append((kind, mask, dst, tag))


def _nullable(node: Node) -> bool:
    if isinstance(node, Lit):
        return False
    if isinstance(node, Seq):
        return all(_nullable(n) for n in node.items)
    if isinstance(node, Alt):
        return any(_nullable(n) for n in node.items)
    if isinstance(node, Rep):
        return node.min == 0 or _nullable(node.node)
    if isinstance(node, Group):
        return _nullable(node.node)
    return True  # an anchor consumes nothing


def _has_named(node: Node) -> bool:
    if isinstance(node, Group):
        return bool(node.name) or _has_named(node.node)
    if isinstance(node, (Seq, Alt)):
        return any(_has_named(n) for n in node.items)
    if isinstance(node, Rep):
        return _has_named(node.node)
    return False


def _build(nfa: _TNFA, node: Node, start: int, tag_of: Dict[int, int]) -> int:
    """Thompson construction in backtracking order. ``start`` has no
    edge yet; → the fragment's end state, which has none either."""
    if isinstance(node, Lit):
        end = nfa.new()
        nfa.byte[start] = (node.mask, end)
        return end
    if isinstance(node, Seq):
        cur = start
        for item in node.items:
            cur = _build(nfa, item, cur, tag_of)
        return cur
    if isinstance(node, Group):
        g = tag_of.get(node.index)
        if g is None:
            return _build(nfa, node.node, start, tag_of)
        inner = nfa.new()
        nfa.add_eps(start, inner, tag=2 * g)
        inner_end = _build(nfa, node.node, inner, tag_of)
        end = nfa.new()
        nfa.add_eps(inner_end, end, tag=2 * g + 1)
        return end
    if isinstance(node, Alt):
        end = nfa.new()
        for item in node.items:  # left to right
            b_start = nfa.new()
            nfa.add_eps(start, b_start)
            nfa.add_eps(_build(nfa, item, b_start, tag_of), end)
        return end
    if isinstance(node, Rep):
        if node.max == 0:
            raise SpanDecline("a {0} repetition")
        if _nullable(node.node):
            raise SpanDecline("a repeated body is nullable (backtracking "
                              "engines cut an empty iteration)")
        if _has_named(node.node) and (node.max is None or node.max > 1):
            raise SpanDecline("a named group lies under a repetition "
                              "that can run it more than once")

        def choice(at: int, enter: int, leave: int) -> None:
            order = (leave, enter) if node.lazy else (enter, leave)
            for dst in order:
                nfa.add_eps(at, dst)

        cur = start
        for _ in range(node.min):
            cur = _build(nfa, node.node, cur, tag_of)
        end = nfa.new()
        if node.max is None:
            loop = nfa.new()
            nfa.add_eps(cur, loop)
            inner = nfa.new()
            choice(loop, inner, end)
            nfa.add_eps(_build(nfa, node.node, inner, tag_of), loop)
            return end
        for _ in range(node.max - node.min):  # x(x(x)?)?
            inner = nfa.new()
            choice(cur, inner, end)
            cur = _build(nfa, node.node, inner, tag_of)
        nfa.add_eps(cur, end)
        return end
    if isinstance(node, Anchor):
        end = nfa.new()
        kind, mask = {
            "bol": ("prev", BOS_BIT | NL_BIT), "bos": ("prev", BOS_BIT),
            "eol": ("next", EOL_BIT | NL_BIT), "eos": ("next", EOL_BIT),
        }.get(node.kind, (None, 0))
        if kind is None:
            raise SpanDecline(f"anchor {node.kind!r} (its automaton "
                              f"consumes a symbol)")
        nfa.add_eps(start, end, kind, mask)
        return end
    raise TypeError(f"unknown AST node {node!r}")


def _eps_reach(nfa: _TNFA, src: int) -> set:
    """States ε-reachable from ``src``, constraints ignored."""
    seen, stack = {src}, [src]
    while stack:
        for _k, _m, dst, _t in nfa.eps[stack.pop()]:
            if dst not in seen:
                seen.add(dst)
                stack.append(dst)
    return seen


@dataclass
class SpanTables:
    """The two tables of one parser regex (numpy; the device program and
    the host walker below read the same arrays).

    ``rev[NR, C]`` i32: pass 1; row 0 is the empty set (what lies past
    the last symbol), ``r_eol`` the set at the first EOL. ``fwd[NW, C,
    NR]`` i32: pass 2, ``next_id | tags << state_bits``; ids 0 / 1 are
    DEAD / ACCEPTED, absorbing. ``class_map[257]``: byte → class, index
    256 the EOL class (padding)."""

    pattern: str
    names: List[str]
    class_map: np.ndarray
    n_classes: int
    rev: np.ndarray
    r_eol: int
    fwd: np.ndarray
    start: int
    state_bits: int
    matches: np.ndarray  # [NR] bool: start ∈ R
    nfa_states: int

    @property
    def eol_class(self) -> int:
        return int(self.class_map[EOL])

    @property
    def n_groups(self) -> int:
        return len(self.names)

    def run(self, data: bytes) -> Optional[List[Tuple[int, int]]]:
        """The walk over one value on the host, as the device program
        runs it: → ``[(start, end)]`` a named group (-1, -1 for one the
        walk never entered), or None where the row does not match."""
        cm, rev, fwd = self.class_map, self.rev, self.fwd
        cls = [int(cm[b]) for b in data] + [self.eol_class]
        n = len(cls)
        r_next = [0] * (n + 1)  # r_next[i] = r_{i+1}
        r = self.r_eol
        for i in range(n - 2, -1, -1):
            r_next[i] = r
            r = int(rev[r, cls[i]])
        u, mask = self.start, (1 << self.state_bits) - 1
        spans = [-1] * (2 * self.n_groups)
        for i in range(n):
            packed = int(fwd[u, cls[i], r_next[i]])
            u, tags = packed & mask, packed >> self.state_bits
            t = 0
            while tags:
                if tags & 1:
                    spans[t] = i
                tags >>= 1
                t += 1
        if u != ACCEPTED:
            return None
        return [(spans[2 * g], spans[2 * g + 1])
                for g in range(self.n_groups)]


def compile_spans(parsed: ParsedRegex, ignorecase: bool = False
                  ) -> SpanTables:
    """The span tables of a parsed pattern, or :class:`SpanDecline` with
    the reason it lies outside the class."""
    if ignorecase:
        raise SpanDecline("case folding (Python re folds beyond ASCII)")
    if getattr(parsed, "possessive", False):
        raise SpanDecline("a possessive quantifier")
    named = sorted(parsed.group_names.items())
    names = [name for _i, name in named]
    if not names:
        raise SpanDecline("no named group to capture")
    if len(set(names)) != len(names):
        raise SpanDecline("a group name is used twice")
    tag_of = {index: g for g, (index, _n) in enumerate(named)}

    nfa = _TNFA()
    scan = nfa.new()      # the search prefix: enter the pattern first,
    p_start = nfa.new()   # else consume one byte and try again
    scan_b = nfa.new()
    nfa.add_eps(scan, p_start)
    nfa.add_eps(scan, scan_b)
    nfa.byte[scan_b] = (ALL_BYTES, scan)
    accept = nfa.new()
    nfa.add_eps(_build(nfa, parsed.root, p_start, tag_of), accept)
    n_states = len(nfa.byte)

    prev_edges = {s for s in range(n_states)
                  if any(k == "prev" for k, _m, _d, _t in nfa.eps[s])}
    reach = [_eps_reach(nfa, s) for s in range(n_states)]
    for s in range(n_states):
        for kind, _m, dst, _t in nfa.eps[s]:
            if kind == "next" and reach[dst] & prev_edges:
                raise SpanDecline("a $ before a ^ on one ε-path")
    needs_pc = [bool(reach[s] & prev_edges) for s in range(n_states)]

    # ---- byte classes: every mask in use, \n and EOL apart
    masks = {NL_BIT, EOL_BIT}
    for s in range(n_states):
        if nfa.byte[s] is not None:
            masks.add(nfa.byte[s][0])
        for kind, m, _d, _t in nfa.eps[s]:
            if kind is not None:
                masks.add(m & ((1 << 257) - 1))
    mask_list = sorted(masks)
    sig_ids: Dict[tuple, int] = {}
    class_map = np.zeros(257, dtype=np.int32)
    for sym in range(257):
        sig = tuple(bool(m >> sym & 1) for m in mask_list)
        class_map[sym] = sig_ids.setdefault(sig, len(sig_ids))
    C = len(sig_ids)
    rep = [0] * C
    for sym in range(256, -1, -1):
        rep[class_map[sym]] = sym

    # ---- walk states and their candidates, in priority order
    walk_ids: Dict[Tuple[int, int], int] = {}
    walk_list: List[Tuple[int, int]] = []

    def walk_id(state: int, pc: int) -> int:
        key = (state, pc if needs_pc[state] else PC_OTHER)
        got = walk_ids.get(key)
        if got is None:
            got = walk_ids[key] = len(walk_list)
            walk_list.append(key)
        return got

    def candidates(state: int, pc: int, c: int):
        """``[(target, tags)]`` from a walk state on class ``c``: the
        ε-paths in the order a backtracking matcher tries them, each
        state at its first visit; target -1 is accept, and nothing
        after it can be preferred."""
        sym = rep[c]
        pc_after = PC_NL if sym == 10 else PC_OTHER
        out, seen = [], set()

        def dfs(s: int, tags: int) -> bool:
            if s in seen:
                return False
            seen.add(s)
            if s == accept:
                out.append((-1, tags))
                return True
            edge = nfa.byte[s]
            if edge is not None:
                if sym != EOL and edge[0] >> sym & 1:
                    out.append((walk_id(edge[1], pc_after), tags))
                return False
            for kind, m, dst, tag in nfa.eps[s]:
                if kind == "prev" and not m & (
                        BOS_BIT if pc == PC_BOS else
                        NL_BIT if pc == PC_NL else 0):
                    continue
                if kind == "next" and not m >> sym & 1:
                    continue
                if dfs(dst, tags | (1 << tag if tag >= 0 else 0)):
                    return True
            return False

        dfs(state, 0)
        return out

    start = walk_id(scan, PC_BOS)
    cands: List[List[list]] = []
    u = 0
    while u < len(walk_list):  # walk_list grows as targets are named
        state, pc = walk_list[u]
        cands.append([candidates(state, pc, c) for c in range(C)])
        u += 1
    NW = len(walk_list)

    # ---- pass 1: the reverse automaton over sets of walk states
    to = np.zeros((C, NW, NW), dtype=bool)   # u has a candidate t on c
    acc = np.zeros((C, NW), dtype=bool)      # u reaches accept on c
    for u in range(NW):
        for c in range(C):
            for t, _tags in cands[u][c]:
                if t < 0:
                    acc[c, u] = True
                else:
                    to[c, u, t] = True
    sets: List[np.ndarray] = [np.zeros(NW, dtype=bool)]
    ids: Dict[bytes, int] = {sets[0].tobytes(): 0}
    rev_rows: List[List[int]] = []
    r = 0
    while r < len(sets):
        row = []
        for c in range(C):
            nxt = acc[c] | (to[c] & sets[r][None, :]).any(axis=1)
            key = nxt.tobytes()
            got = ids.get(key)
            if got is None:
                if len(sets) >= MAX_REV_STATES:
                    raise SpanDecline(
                        f"the reverse automaton passes "
                        f"{MAX_REV_STATES} states")
                got = ids[key] = len(sets)
                sets.append(nxt)
            row.append(got)
        rev_rows.append(row)
        r += 1
    rev = np.asarray(rev_rows, dtype=np.int32)
    NR = len(sets)
    member = np.stack(sets)  # [NR, NW]

    # ---- pass 2: the first candidate whose target can still complete
    state_bits = max((NW + 2 - 1).bit_length(), 1)
    if state_bits + 2 * len(names) > 31:
        raise SpanDecline(f"{len(names)} groups and {NW} walk states do "
                          f"not fit one packed i32")
    if (NW + 2) * C * NR > MAX_TABLE_ENTRIES:
        raise SpanDecline(f"the walk table would hold "
                          f"{(NW + 2) * C * NR} entries")
    fwd = np.zeros((NW + 2, C, NR), dtype=np.int32)
    fwd[ACCEPTED] = ACCEPTED
    for u in range(NW):
        for c in range(C):
            res = np.full(NR, DEAD, dtype=np.int32)
            for t, tags in reversed(cands[u][c]):
                if t < 0:
                    res[:] = ACCEPTED | tags << state_bits
                else:
                    res = np.where(member[:, t],
                                   np.int32((t + 2) | tags << state_bits),
                                   res)
            fwd[u + 2, c] = res
    return SpanTables(
        pattern=parsed.pattern, names=names,
        class_map=class_map.astype(np.uint8), n_classes=C, rev=rev,
        r_eol=int(rev[0, class_map[EOL]]), fwd=fwd, start=start + 2,
        state_bits=state_bits, matches=member[:, start].copy(),
        nfa_states=n_states)
