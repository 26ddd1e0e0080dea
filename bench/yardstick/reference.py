"""The plain reference: regular path query answers by a product-automaton
search, written without any code of the program.

A query is parsed (the same surface syntax the traffic sends: labels,
``{a|b}`` classes, ``(a|b)`` unions, ``.``, ``*``, ``+``, ``?``,
concatenation by juxtaposition, and a ``^-1`` suffix for inverse
traversal), built into a Thompson automaton, and freed of its empty
moves.  Answers follow the paper's Definition 1 (arXiv:1510.04347 §2.4):
for a start node ``s``, every node ``v`` with a path ``s -> v`` whose
label word the expression accepts; ``s`` itself is an answer when the
expression accepts the empty word.

The search runs for many start nodes at once: each automaton state keeps
a (nodes x starts) 0/1 matrix of visited product states, and one level
multiplies the new frontier by the sparse adjacency of each transition's
label set, until no new product state appears.
"""

from __future__ import annotations

import dataclasses

import numpy as np
import scipy.sparse as sp

ANY = None  # the wildcard's label
_PUNCT = set("()|*+?{}.,")
_INVERSE = ("^-1", "^{-1}", "⁻¹")


# -- parsing ------------------------------------------------------------------


def _tokens(src: str) -> list[tuple[str, str, bool]]:
    """(kind, text, inverse) triples; kind is "label" or the punctuation."""
    out, i, n = [], 0, len(src)
    while i < n:
        c = src[i]
        if c.isspace():
            i += 1
            continue
        if c in _PUNCT:
            out.append((c, c, False))
            i += 1
            continue
        if c == '"':
            j = src.index('"', i + 1)
            name, i = src[i + 1 : j], j + 1
        else:
            j = i
            while j < n and not src[j].isspace() and src[j] not in _PUNCT and src[j] != '"':
                j += 1
            name, i = src[i:j], j
        inverse = False
        for marker in _INVERSE:
            if name.endswith(marker):
                name, inverse = name[: -len(marker)], True
                break
        out.append(("label", name, inverse))
    return out


class _Parser:
    """expr := term ('|' term)*; term := factor+; factor := atom [*+?]*;
    atom := label | '.' | '(' expr ')' | '{' label ([,|] label)* '}'.

    Nodes are tuples: ("sym", ((label, inverse), ...)), ("cat", parts),
    ("alt", parts), ("star", x), ("plus", x), ("opt", x)."""

    def __init__(self, src: str):
        self.toks = _tokens(src)
        self.pos = 0

    def _peek(self) -> str | None:
        return self.toks[self.pos][0] if self.pos < len(self.toks) else None

    def _take(self, kind: str | None = None):
        tok = self.toks[self.pos] if self.pos < len(self.toks) else None
        if tok is None or (kind is not None and tok[0] != kind):
            raise ValueError(f"expected {kind!r} at token {self.pos}, got {tok}")
        self.pos += 1
        return tok

    def parse(self):
        node = self._expr()
        if self.pos != len(self.toks):
            raise ValueError(f"trailing tokens at {self.pos}")
        return node

    def _expr(self):
        parts = [self._term()]
        while self._peek() == "|":
            self._take()
            parts.append(self._term())
        return parts[0] if len(parts) == 1 else ("alt", tuple(parts))

    def _term(self):
        parts = []
        while self._peek() not in (None, "|", ")", "}"):
            parts.append(self._factor())
        if not parts:
            raise ValueError("empty term")
        return parts[0] if len(parts) == 1 else ("cat", tuple(parts))

    def _factor(self):
        node = self._atom()
        while self._peek() in ("*", "+", "?"):
            node = ({"*": "star", "+": "plus", "?": "opt"}[self._take()[0]], node)
        return node

    def _atom(self):
        kind = self._peek()
        if kind == "(":
            self._take()
            node = self._expr()
            self._take(")")
            return node
        if kind == "{":
            self._take()
            syms = []
            while self._peek() not in (None, "}"):
                if self._peek() in (",", "|"):
                    self._take()
                    continue
                _, name, inverse = self._take("label")
                syms.append((name, inverse))
            self._take("}")
            return ("sym", tuple(syms))
        if kind == ".":
            self._take()
            return ("sym", ((ANY, False),))
        _, name, inverse = self._take("label")
        return ("sym", ((name, inverse),))


# -- automaton ----------------------------------------------------------------


@dataclasses.dataclass(frozen=True)
class Automaton:
    """An automaton without empty moves: ``moves[(p, q)]`` is the set of
    (label, inverse) symbols that lead from state p to state q."""

    n_states: int
    start: int
    accepting: frozenset[int]
    moves: dict[tuple[int, int], frozenset[tuple[str | None, bool]]]


def compile_query(src: str) -> Automaton:
    node = _Parser(src).parse()
    empty: list[list[int]] = []
    sym: list[tuple[int, tuple, int]] = []

    def state() -> int:
        empty.append([])
        return len(empty) - 1

    def build(n) -> tuple[int, int]:
        kind = n[0]
        if kind == "sym":
            a, b = state(), state()
            for s in n[1]:
                sym.append((a, s, b))
            return a, b
        if kind == "cat":
            first, last = build(n[1][0])
            for part in n[1][1:]:
                a, b = build(part)
                empty[last].append(a)
                last = b
            return first, last
        a, b = state(), state()
        if kind == "alt":
            for part in n[1]:
                pa, pb = build(part)
                empty[a].append(pa)
                empty[pb].append(b)
            return a, b
        pa, pb = build(n[1])
        empty[a].append(pa)
        empty[pb].append(b)
        if kind in ("star", "opt"):
            empty[a].append(b)
        if kind in ("star", "plus"):
            empty[pb].append(pa)
        return a, b

    start, final = build(node)
    closure = []
    for s in range(len(empty)):
        seen, todo = {s}, [s]
        while todo:
            for t in empty[todo.pop()]:
                if t not in seen:
                    seen.add(t)
                    todo.append(t)
        closure.append(seen)
    moves: dict[tuple[int, int], set] = {}
    for p in range(len(empty)):
        for a, s, b in sym:
            if a in closure[p]:
                moves.setdefault((p, b), set()).add(s)
    # keep what the start state reaches
    keep, todo = {start}, [start]
    while todo:
        p = todo.pop()
        for (a, b) in moves:
            if a == p and b not in keep:
                keep.add(b)
                todo.append(b)
    order = {s: i for i, s in enumerate(sorted(keep))}
    return Automaton(
        n_states=len(order),
        start=order[start],
        accepting=frozenset(order[s] for s in keep if final in closure[s]),
        moves={
            (order[a], order[b]): frozenset(ss)
            for (a, b), ss in moves.items()
            if a in keep and b in keep
        },
    )


# -- evaluation ---------------------------------------------------------------


class Evaluator:
    """Answers of many queries over one graph (arrays src, lbl, dst and the
    label names), with the per-label adjacency built once."""

    def __init__(self, n_nodes: int, src, lbl, dst, labels: list[str]):
        self.n_nodes = n_nodes
        self.src = np.asarray(src, np.int64)
        self.lbl = np.asarray(lbl, np.int64)
        self.dst = np.asarray(dst, np.int64)
        self.label_id = {name: i for i, name in enumerate(labels)}
        self._cache: dict = {}

    def _edges(self, symbols) -> tuple[np.ndarray, np.ndarray]:
        """(from, to) node pairs of the edges a symbol set lets a path take."""
        fwd = {name for name, inv in symbols if not inv}
        inv = {name for name, inv in symbols if inv}
        froms, tos = [], []
        for names, reverse in ((fwd, False), (inv, True)):
            if not names:
                continue
            if ANY in names:
                mask = np.ones(len(self.lbl), bool)
            else:
                ids = [self.label_id[n] for n in names if n in self.label_id]
                mask = np.isin(self.lbl, ids)
            a, b = self.src[mask], self.dst[mask]
            froms.append(b if reverse else a)
            tos.append(a if reverse else b)
        if not froms:
            return np.zeros(0, np.int64), np.zeros(0, np.int64)
        return np.concatenate(froms), np.concatenate(tos)

    def _step_matrix(self, symbols) -> sp.csr_matrix:
        """M with M[v, u] = 1 where a symbol leads u -> v, so M @ F moves a
        (nodes x starts) frontier one edge."""
        m = self._cache.get(symbols)
        if m is None:
            u, v = self._edges(symbols)
            m = sp.csr_matrix(
                (np.ones(len(u), np.float32), (v, u)), shape=(self.n_nodes, self.n_nodes)
            )
            m.sum_duplicates()
            self._cache[symbols] = m
        return m

    def valid_starts(self, query: str) -> np.ndarray:
        """Nodes with an edge that a move out of the start state can take
        (the paper's valid starting points, Table 2's last column)."""
        a = compile_query(query)
        has = np.zeros(self.n_nodes, bool)
        for (p, _), symbols in a.moves.items():
            if p == a.start:
                u, _ = self._edges(symbols)
                has[u] = True
        return np.nonzero(has)[0].astype(np.int32)

    def answers(self, query: str, starts, chunk: int = 128) -> list[np.ndarray]:
        """Per start node, the sorted answer nodes of ``query``."""
        a = compile_query(query)
        starts = np.asarray(starts, np.int64)
        steps = [(p, q, self._step_matrix(ss)) for (p, q), ss in sorted(a.moves.items(), key=str)]
        out: list[np.ndarray] = []
        for lo in range(0, len(starts), chunk):
            cols = starts[lo : lo + chunk]
            b = len(cols)
            visited = [np.zeros((self.n_nodes, b), bool) for _ in range(a.n_states)]
            visited[a.start][cols, np.arange(b)] = True
            frontier = [v.copy() for v in visited]
            while any(f.any() for f in frontier):
                reached = [np.zeros((self.n_nodes, b), bool) for _ in range(a.n_states)]
                for p, q, m in steps:
                    if frontier[p].any():
                        reached[q] |= (m @ frontier[p].astype(np.float32)) > 0
                for q in range(a.n_states):
                    frontier[q] = reached[q] & ~visited[q]
                    visited[q] |= frontier[q]
            acc = np.zeros((self.n_nodes, b), bool)
            for q in a.accepting:
                acc |= visited[q]
            out.extend(np.nonzero(acc[:, j])[0] for j in range(b))
        return out
