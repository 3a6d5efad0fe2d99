"""The plain reference matcher that decides whether a run is correct.

A copy, owned by the benchmark, of ``repro.core.oracle`` (a host
backtracker over the whole, unpartitioned graph) with the same semantics,
reading the benchmark's own ``GraphArrays`` and the query dicts of
``bench/queries/``.  It imports nothing of the program.

Semantics, as in the original:

* injective node mapping (subgraph isomorphism, not homomorphism);
* an undirected graph edge satisfies any query direction; a directed one
  matches ``out`` along it, ``in`` against it, ``any`` either way;
* a label absent from the graph matches nothing, ``"?"`` matches all;
* a vertex without a number (NaN) fails every value predicate, ``!=``
  included; no predicate (``""``) passes;
* answers are binding rows (query slot -> vertex id), padded with -1 to
  ``q_pad``, sorted and unique; automorphic embeddings are distinct rows.

The adjacency is built with numpy, and the first slot's candidates are
the vertices whose label and value pass, instead of every vertex tried in
turn; the search is otherwise the original's.
"""
from __future__ import annotations

from typing import Dict, List, Sequence

import numpy as np

WILDCARD = -1
NO_MATCH = -3
QDIR_ANY, QDIR_OUT, QDIR_IN = 0, 1, 2
_FLIP = {QDIR_ANY: QDIR_ANY, QDIR_OUT: QDIR_IN, QDIR_IN: QDIR_OUT}


def _value_ok(op: str, value: float, want: float) -> bool:
    if op == "":
        return True
    if value != value:              # NaN fails every comparison
        return False
    return {"=": value == want, "!=": value != want, "<": value < want,
            "<=": value <= want, ">": value > want, ">=": value >= want}[op]


class Adjacency:
    """Both directions of every edge, grouped by vertex: for vertex v,
    ``nbr/lab/gdir[ptr[v]:ptr[v+1]]`` in edge order, with ``gdir`` +1
    along a directed edge, -1 against it and 0 for an undirected one."""

    def __init__(self, g):
        src = np.concatenate([g.edge_src, g.edge_dst]).astype(np.int64)
        dst = np.concatenate([g.edge_dst, g.edge_src]).astype(np.int64)
        d = np.asarray(g.edge_directed, bool).astype(np.int8)
        order = np.argsort(src, kind="stable")
        self.nbr = dst[order]
        self.lab = np.concatenate([g.edge_label, g.edge_label])[order]
        self.gdir = np.concatenate([d, -d])[order]
        self.ptr = np.concatenate(
            [[0], np.cumsum(np.bincount(src, minlength=g.n_nodes))])

    def of(self, v: int):
        lo, hi = self.ptr[v], self.ptr[v + 1]
        return zip(self.nbr[lo:hi].tolist(), self.lab[lo:hi].tolist(),
                   self.gdir[lo:hi].tolist())


def _label_id(vocab: Sequence[str], label: str) -> int:
    if label == "?":
        return WILDCARD
    try:
        return vocab.index(label)
    except ValueError:
        return NO_MATCH


def match_conjunctive(g, adj: Adjacency, q: dict, q_pad: int) -> np.ndarray:
    """All embeddings of one conjunctive pattern as sorted unique
    ``[n, q_pad]`` rows."""
    nodes, edges = q["nodes"], q["edges"]
    Q = len(nodes)
    nl = [_label_id(g.node_vocab, n.get("label", "?")) for n in nodes]
    el = [_label_id(g.edge_vocab, e.get("label", "?")) for e in edges]
    ops = [n.get("value_op", "") for n in nodes]
    vals = [float(n.get("value", 0.0)) for n in nodes]
    qadj: List[List[tuple]] = [[] for _ in range(Q)]
    for ei, e in enumerate(edges):
        qadj[e["a"]].append((e["b"], ei, True))
        qadj[e["b"]].append((e["a"], ei, False))

    def node_ok(slot: int, v: int) -> bool:
        if nl[slot] != WILDCARD and int(g.node_label[v]) != nl[slot]:
            return False
        return _value_ok(ops[slot], float(np.float32(g.node_value[v])),
                         vals[slot])

    def dir_ok(qdir: int, from_a: bool, gdir: int) -> bool:
        if not from_a:
            qdir = _FLIP[qdir]
        if qdir == QDIR_ANY or gdir == 0:
            return True
        return (qdir == QDIR_OUT and gdir == 1) or (qdir == QDIR_IN and gdir == -1)

    binding = [-1] * Q

    def consistent(slot: int, v: int) -> bool:
        if v in binding or not node_ok(slot, v):
            return False
        for other, ei, from_this in qadj[slot]:
            if binding[other] == -1:
                continue
            qdir = edges[ei].get("direction", QDIR_ANY)
            if not any(nbr == binding[other]
                       and (el[ei] == WILDCARD or lab == el[ei])
                       and dir_ok(qdir, from_this, gdir)
                       for nbr, lab, gdir in adj.of(v)):
                return False
        return True

    order, seen, i = [0], {0}, 0
    while i < len(order):
        for other, _, _ in qadj[order[i]]:
            if other not in seen:
                seen.add(other)
                order.append(other)
        i += 1

    if nl[0] == NO_MATCH:
        first: List[int] = []
    elif nl[0] == WILDCARD:
        first = list(range(g.n_nodes))
    else:
        first = np.nonzero(np.asarray(g.node_label) == nl[0])[0].tolist()

    results: List[tuple] = []

    def backtrack(oi: int) -> None:
        if oi == Q:
            results.append(tuple(binding))
            return
        slot = order[oi]
        if oi == 0:
            cands = first
        else:
            cand = set()
            for other, _, _ in qadj[slot]:
                if binding[other] != -1:
                    cand.update(n for n, _, _ in adj.of(binding[other]))
            cands = sorted(cand)
        for v in cands:
            if consistent(slot, v):
                binding[slot] = v
                backtrack(oi + 1)
                binding[slot] = -1

    backtrack(0)
    out = np.full((len(results), q_pad), -1, np.int32)
    for r, row in enumerate(sorted(set(results))):
        out[r, :Q] = row
    return np.unique(out, axis=0) if out.shape[0] else out


def match(g, query: dict, q_pad: int, adj: Adjacency = None) -> np.ndarray:
    """The answers of one query dict (``{"disjuncts": [...]}`` or a bare
    conjunctive pattern): the sorted unique union over its disjuncts."""
    adj = adj if adj is not None else Adjacency(g)
    disjuncts = query.get("disjuncts", [query])
    parts = [match_conjunctive(g, adj, q, q_pad) for q in disjuncts]
    parts = [p for p in parts if p.shape[0]]
    if not parts:
        return np.zeros((0, q_pad), np.int32)
    return np.unique(np.concatenate(parts), axis=0)


def match_all(g, queries: Dict[str, dict], q_pad: int) -> Dict[str, np.ndarray]:
    """Answers for every named query, sharing one adjacency build."""
    adj = Adjacency(g)
    return {name: match(g, q, q_pad, adj) for name, q in queries.items()}
