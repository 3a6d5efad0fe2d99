"""The benchmark's reference matcher agrees with the program's oracle."""
import json

import numpy as np
import pytest

import reference
import run
from datagen import subgen
from repro.core.oracle import match_disjunctive
from repro.core.query import DisjunctiveQuery

QUERIES = json.loads((run.BENCH / "queries" / "subgen.json").read_text())
SMALL = {"generator": "subgen", "n_nodes": 1500, "n_edges": 4500,
         "n_vlabels": 12, "n_elabels": 20, "n_embed": 30}


@pytest.mark.parametrize("seed", [0, 1, 2**31 + 11])
def test_subgen_queries_match_oracle(seed):
    ga = subgen.generate(SMALL, seed)
    g = run.program_graph(ga)
    got = reference.match_all(ga, {q["name"]: q for q in QUERIES}, 8)
    for q in QUERIES:
        want = match_disjunctive(g, DisjunctiveQuery.from_json_dict(q), q_pad=8)
        assert np.array_equal(got[q["name"]], want), q["name"]
    assert got["Q4"].shape[0] == got["Q5"].shape[0] == SMALL["n_embed"]
    assert got["Q6"].shape[0] == 0


def _random_graph(rng, n=60, e=220):
    """Few labels, numbers on some vertices, directed and undirected
    edges: the cases the Subgen queries never reach."""
    lab = rng.integers(0, 3, n).astype(np.int32)
    val = np.where(rng.random(n) < 0.6, rng.integers(0, 5, n), np.nan)
    src = rng.integers(0, n, e)
    dst = (src + rng.integers(1, n, e)) % n
    return subgen.GraphArrays(
        n_nodes=n, node_label=lab, node_value=val.astype(np.float32),
        node_vocab=["a", "b", "c"], edge_src=src.astype(np.int32),
        edge_dst=dst.astype(np.int32),
        edge_label=rng.integers(0, 2, e).astype(np.int32),
        edge_directed=rng.random(e) < 0.5, edge_vocab=["x", "y"])


def _random_query(rng):
    n = int(rng.integers(2, 5))
    nodes = []
    for _ in range(n):
        node = {"label": str(rng.choice(["a", "b", "c", "?", "zz"],
                                        p=[.3, .3, .2, .15, .05]))}
        if rng.random() < 0.3:
            node["value_op"] = str(rng.choice(["=", "!=", "<", "<=", ">", ">="]))
            node["value"] = float(rng.integers(0, 5))
        nodes.append(node)
    edges = [{"a": int(rng.integers(0, i)), "b": i,
              "label": str(rng.choice(["x", "y", "?"])),
              "direction": int(rng.integers(0, 3))} for i in range(1, n)]
    if n > 2 and rng.random() < 0.4:          # close a cycle
        edges.append({"a": 0, "b": n - 1, "label": "?", "direction": 0})
    return {"name": "r", "disjuncts": [{"name": "r", "nodes": nodes, "edges": edges}]}


@pytest.mark.parametrize("seed", range(6))
def test_random_patterns_match_oracle(seed):
    rng = np.random.default_rng(seed)
    ga = _random_graph(rng)
    g = run.program_graph(ga)
    for _ in range(8):
        q = _random_query(rng)
        dq = DisjunctiveQuery.from_json_dict(q)
        assert np.array_equal(reference.match(ga, q, 8),
                              match_disjunctive(g, dq, q_pad=8)), q
