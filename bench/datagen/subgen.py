"""The Subgen-style synthetic graph of the paper's Sec. 7, made from a seed.

A copy, owned by the benchmark, of ``repro.data.generators.subgen_like_graph``
with the same distribution, drawn in bulk with numpy instead of one
``GraphBuilder`` call per vertex and edge:

* ``n_nodes`` background vertices, each labelled ``v<i>`` with ``i``
  uniform over ``n_vlabels``;
* ``n_embed`` planted instances of the 4-node template (vertices labelled
  ``tmpl_A`` .. ``tmpl_D``, edges ``e_ab``, ``e_bc``, ``e_bd``), numbered
  after the background vertices;
* ``n_edges`` undirected background edges between two distinct vertices
  drawn uniformly over all vertices, labelled ``e<j>`` with ``j`` uniform
  over ``n_elabels``;
* one tie edge per instance from a uniform background vertex to its
  ``tmpl_A`` vertex, so instances cross partitions.

Edges are listed in the original's order: template edges, background
edges, tie edges.  The stream of random numbers differs from the
original's, so a seed gives a different graph there and here.
"""
from __future__ import annotations

import dataclasses
from typing import List

import numpy as np

TEMPLATE_LABELS = ("tmpl_A", "tmpl_B", "tmpl_C", "tmpl_D")
TEMPLATE_EDGES = (("e_ab", 0, 1), ("e_bc", 1, 2), ("e_bd", 1, 3))


@dataclasses.dataclass
class GraphArrays:
    """A labelled graph as plain arrays: labels index the vocabularies,
    ``node_value`` is NaN where a vertex has no number, and an edge is
    undirected unless ``edge_directed``."""

    n_nodes: int
    node_label: np.ndarray       # [V] int32 into node_vocab
    node_value: np.ndarray       # [V] float32
    node_vocab: List[str]
    edge_src: np.ndarray         # [E] int32
    edge_dst: np.ndarray         # [E] int32
    edge_label: np.ndarray       # [E] int32 into edge_vocab
    edge_directed: np.ndarray    # [E] bool
    edge_vocab: List[str]

    @property
    def n_edges(self) -> int:
        return int(self.edge_src.shape[0])


def generate(params: dict, seed: int) -> GraphArrays:
    """The graph that ``params`` (the configuration's ``data`` block)
    describes, drawn from ``seed``."""
    n = int(params["n_nodes"])
    n_edges = int(params["n_edges"])
    n_vl = int(params["n_vlabels"])
    n_el = int(params["n_elabels"])
    m = int(params["n_embed"])
    rng = np.random.default_rng(seed)
    total = n + 4 * m

    node_vocab = [f"v{i}" for i in range(n_vl)] + list(TEMPLATE_LABELS)
    node_label = np.empty(total, np.int32)
    node_label[:n] = rng.integers(0, n_vl, size=n)
    node_label[n:] = np.tile(np.arange(n_vl, n_vl + 4, dtype=np.int32), m)

    edge_vocab = [f"e{j}" for j in range(n_el)] + [e for e, _, _ in TEMPLATE_EDGES]
    inst = n + 4 * np.arange(m, dtype=np.int64)
    t_src = np.stack([inst + a for _, a, _ in TEMPLATE_EDGES], 1).ravel()
    t_dst = np.stack([inst + b for _, _, b in TEMPLATE_EDGES], 1).ravel()
    t_lab = np.tile(np.arange(n_el, n_el + 3), m)

    ends = rng.integers(0, total, size=(n_edges, 2))
    loops = np.nonzero(ends[:, 0] == ends[:, 1])[0]
    while loops.size:
        ends[loops] = rng.integers(0, total, size=(loops.size, 2))
        loops = loops[ends[loops, 0] == ends[loops, 1]]
    b_lab = rng.integers(0, n_el, size=n_edges)

    tie_src = rng.integers(0, n, size=m)
    tie_lab = rng.integers(0, n_el, size=m)

    src = np.concatenate([t_src, ends[:, 0], tie_src]).astype(np.int32)
    dst = np.concatenate([t_dst, ends[:, 1], inst]).astype(np.int32)
    lab = np.concatenate([t_lab, b_lab, tie_lab]).astype(np.int32)
    return GraphArrays(
        n_nodes=total, node_label=node_label,
        node_value=np.full(total, np.nan, np.float32), node_vocab=node_vocab,
        edge_src=src, edge_dst=dst, edge_label=lab,
        edge_directed=np.zeros(src.shape[0], bool), edge_vocab=edge_vocab)
