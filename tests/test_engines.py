"""OPAT / TraditionalMP / MapReduceMP vs the whole-graph oracle
(paper correctness claims, Sec. 4.2 / 7 / 8 / 9)."""
import numpy as np
import pytest


from repro.core import (ALL_HEURISTICS, EngineConfig, MAX_SN, OPATEngine, TraditionalMPEngine,
                        build_catalog, build_partitions, generate_plan, match_query,
                        partition_graph)
from repro.core.mapreduce_mp import MapReduceMPEngine
from repro.data.generators import (imdb_like_graph, imdb_queries,
                                   subgen_like_graph, subgen_queries)


def _ref(graph, query, q_pad=8):
    return match_query(graph, query, q_pad=q_pad)


@pytest.fixture(scope="module")
def setup():
    g = subgen_like_graph(n_nodes=250, n_edges=700, n_embed=10, seed=3)
    assign = partition_graph(g, 4, "kway_shem")
    pg = build_partitions(g, assign, 4)
    cat = build_catalog(g)
    queries = [dq.disjuncts[0] for dq in subgen_queries(g)]
    return g, pg, cat, queries


@pytest.mark.parametrize("heuristic", ALL_HEURISTICS)
def test_opat_matches_oracle_all_heuristics(setup, heuristic):
    g, pg, cat, queries = setup
    eng = OPATEngine(pg, EngineConfig(cap=16384))
    for q in queries:
        plan = generate_plan(q, g, cat)
        res = eng.run(plan, heuristic, seed=1)
        assert np.array_equal(np.unique(res.answers, axis=0), _ref(g, q)), \
            (q.name, heuristic)


def test_opat_load_ratio_in_range(setup):
    g, pg, cat, queries = setup
    eng = OPATEngine(pg, EngineConfig(cap=16384))
    for q in queries:
        plan = generate_plan(q, g, cat)
        res = eng.run(plan, MAX_SN)
        assert 1 <= res.stats.l_ideal <= pg.k
        if res.answers.shape[0]:
            assert 0 < res.stats.load_ratio <= 1.0


@pytest.mark.parametrize("p", [1, 2, 4, 6])
def test_traditional_mp_matches_oracle(setup, p):
    g, pg, cat, queries = setup
    eng = TraditionalMPEngine(pg, p, EngineConfig(cap=16384))
    for q in queries:
        plan = generate_plan(q, g, cat)
        res = eng.run(plan, MAX_SN, seed=1)
        assert np.array_equal(np.unique(res.answers, axis=0), _ref(g, q))
        # p processors -> each iteration uses at most p partitions
        assert all(len(it) <= p for it in res.partitions_per_iteration)


def test_traditional_mp_fewer_iterations_than_opat(setup):
    """More processors should never need MORE iterations (paper Sec. 8.2)."""
    g, pg, cat, queries = setup
    e1 = TraditionalMPEngine(pg, 1, EngineConfig(cap=16384))
    e4 = TraditionalMPEngine(pg, 4, EngineConfig(cap=16384))
    for q in queries:
        plan = generate_plan(q, g, cat)
        i1 = e1.run(plan, MAX_SN, seed=1).stats.iterations
        i4 = e4.run(plan, MAX_SN, seed=1).stats.iterations
        assert i4 <= i1


def test_mapreduce_single_device_matches_oracle(setup):
    g, pg_4, cat, queries = setup
    # one partition per device; this container has 1 device -> k=1
    pg = build_partitions(g, np.zeros(g.n_nodes, dtype=np.int32), 1)
    from repro.core.mapreduce_mp import make_part_mesh
    mesh = make_part_mesh(1)
    eng = MapReduceMPEngine(pg, mesh, EngineConfig(cap=32768))
    for q in queries:
        plan = generate_plan(q, g, cat)
        res = eng.run(plan)
        assert np.array_equal(np.unique(res.answers, axis=0), _ref(g, q))
        # one-edge-at-a-time: iterations >= max plan path length (Sec. 9)
        assert res.n_iterations >= plan.max_path_len()


def test_same_partition_needed_twice(small_graph):
    """Fig. 4c: answers that re-enter an already-processed partition."""
    # force a 2-partition split of a path that zig-zags across partitions
    from repro.core.graph import GraphBuilder
    b = GraphBuilder()
    n0 = b.add_node("S")
    n1 = b.add_node("T")
    n2 = b.add_node("U")
    n3 = b.add_node("V")
    b.add_edge(n0, n1, "e")
    b.add_edge(n1, n2, "e")
    b.add_edge(n2, n3, "e")
    g = b.build()
    assign = np.array([0, 1, 0, 1], dtype=np.int32)  # zig-zag
    pg = build_partitions(g, assign, 2)
    cat = build_catalog(g)
    from repro.core.query import Query, QueryEdge, QueryNode
    q = Query(nodes=[QueryNode("S"), QueryNode("T"), QueryNode("U"),
                     QueryNode("V")],
              edges=[QueryEdge(0, 1, "e"), QueryEdge(1, 2, "e"),
                     QueryEdge(2, 3, "e")])
    plan = generate_plan(q, g, cat, start_slot=0)
    eng = OPATEngine(pg, EngineConfig(cap=256))
    res = eng.run(plan, MAX_SN)
    assert res.answers.shape[0] == 1
    # partition 0 (and 1) must appear more than once in the load sequence
    loads = res.stats.loads
    assert max(loads.count(0), loads.count(1)) >= 2


def test_imdb_disjunctive_queries():
    g = imdb_like_graph(n_movies=120, n_people=150, seed=7)
    assign = partition_graph(g, 4, "ecosocial")
    pg = build_partitions(g, assign, 4)
    cat = build_catalog(g)
    eng = OPATEngine(pg, EngineConfig(cap=16384))
    from repro.core.oracle import match_disjunctive
    for dq in imdb_queries(g, seed=7):
        got = None
        for q in dq.disjuncts:
            plan = generate_plan(q, g, cat)
            res = eng.run(plan, MAX_SN)
            a = res.answers
            got = a if got is None else np.unique(np.concatenate([got, a]), axis=0)
        ref = match_disjunctive(g, dq, q_pad=8)
        assert got.shape[0] == ref.shape[0]
        if ref.shape[0]:
            assert np.array_equal(np.unique(got, axis=0), ref)


def test_overflow_raises(setup):
    g, pg, cat, queries = setup
    from repro.core.query import Query, QueryEdge, QueryNode
    # all-wildcard 2-path: thousands of embeddings >> cap
    q = Query(nodes=[QueryNode("?")] * 3,
              edges=[QueryEdge(0, 1, "?"), QueryEdge(1, 2, "?")])
    eng = OPATEngine(pg, EngineConfig(cap=8))   # absurdly small buffers
    plan = generate_plan(q, g, cat)
    with pytest.raises(RuntimeError):
        eng.run(plan, MAX_SN)
