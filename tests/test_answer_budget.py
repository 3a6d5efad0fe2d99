"""Answer-budget (top-K) evaluation: the paper's "all or specified number
of answers" mode, uniform across all three engines via the QueryRunner
protocol (core/runner.py).

Invariants asserted per engine:
  * exactly min(K, total) unique answer rows come back,
  * every returned row is in the exhaustive run's answer set,
  * OPAT at K=1 does strictly fewer partition loads than the full run on
    a workload whose answers span partitions (the budget's whole point).
"""
import numpy as np
import pytest

from repro.core.mapreduce_mp import make_part_mesh
from repro.core import (EngineConfig, MAX_SN, MAX_YIELD, OPATEngine, RunRequest,
                        TraditionalMPEngine, build_catalog, build_partitions, generate_plan,
                        match_query, partition_graph)
from repro.core.mapreduce_mp import MapReduceMPEngine
from repro.core.runner import QueryRunner, RunReport, truncate_answers
from repro.data.generators import subgen_like_graph, subgen_queries

BUDGETS = (0, 1, 3, 10, 10**6)


@pytest.fixture(scope="module")
def setup():
    g = subgen_like_graph(n_nodes=250, n_edges=700, n_embed=10, seed=3)
    assign = partition_graph(g, 4, "kway_shem")
    pg = build_partitions(g, assign, 4)
    cat = build_catalog(g)
    queries = [dq.disjuncts[0] for dq in subgen_queries(g)]
    return g, pg, cat, queries


@pytest.fixture(scope="module")
def engines(setup):
    g, pg, cat, queries = setup
    # MapReduceMP needs one partition per device; this container has one
    # CPU device -> a k=1 partitioning of the same graph
    pg1 = build_partitions(g, np.zeros(g.n_nodes, dtype=np.int32), 1)
    return {
        "opat": OPATEngine(pg, EngineConfig(cap=16384)),
        "traditional": TraditionalMPEngine(pg, 2, EngineConfig(cap=16384)),
        "mapreduce": MapReduceMPEngine(pg1, make_part_mesh(1),
                                       EngineConfig(cap=32768)),
    }


def test_engines_satisfy_runner_protocol(engines):
    for eng in engines.values():
        assert isinstance(eng, QueryRunner)


@pytest.mark.parametrize("engine_name", ["opat", "traditional", "mapreduce"])
def test_budget_returns_min_k_total_subset(setup, engines, engine_name):
    g, pg, cat, queries = setup
    eng = engines[engine_name]
    for q in queries:
        plan = generate_plan(q, g, cat)
        ref = match_query(g, q, q_pad=8)
        refset = {tuple(r) for r in ref}
        total = ref.shape[0]
        for k in BUDGETS:
            rep = eng.run_request(RunRequest(plan=plan, heuristic=MAX_SN,
                                             max_answers=k, seed=1))
            assert isinstance(rep, RunReport)
            got = rep.answers
            assert got.shape[0] == min(k, total), (q.name, k)
            # unique rows, each one a real answer of the exhaustive run
            assert len({tuple(r) for r in got}) == got.shape[0]
            assert all(tuple(r) in refset for r in got), (q.name, k)
            assert rep.stats.answers_requested == k
            assert rep.stats.n_answers == got.shape[0]


@pytest.mark.parametrize("engine_name", ["opat", "traditional", "mapreduce"])
def test_no_budget_matches_oracle(setup, engines, engine_name):
    g, pg, cat, queries = setup
    eng = engines[engine_name]
    for q in queries:
        plan = generate_plan(q, g, cat)
        rep = eng.run_request(RunRequest(plan=plan, heuristic=MAX_SN, seed=1))
        assert rep.stats.answers_requested is None
        assert np.array_equal(np.unique(rep.answers, axis=0),
                              match_query(g, q, q_pad=8)), q.name


def test_opat_k1_fewer_loads_than_full(setup, engines):
    """On a spanning-answer workload, stopping at the first answer must
    load strictly fewer partitions than exhausting the query."""
    g, pg, cat, queries = setup
    eng = engines["opat"]
    checked = 0
    for q in queries:
        plan = generate_plan(q, g, cat)
        if match_query(g, q, q_pad=8).shape[0] == 0:
            continue                      # no answers -> no early exit
        full = eng.run_request(RunRequest(plan=plan, heuristic=MAX_SN, seed=1))
        k1 = eng.run_request(RunRequest(plan=plan, heuristic=MAX_SN,
                                        max_answers=1, seed=1))
        assert k1.stats.n_loads < full.stats.n_loads, q.name
        checked += 1
    assert checked, "workload produced no answerable queries"


def test_max_yield_heuristic_correct_and_budgeted(setup, engines):
    """MAX-YIELD must stay exact without a budget and respect K with one,
    on both host-orchestrated engines."""
    g, pg, cat, queries = setup
    for name in ("opat", "traditional"):
        eng = engines[name]
        for q in queries:
            plan = generate_plan(q, g, cat)
            ref = match_query(g, q, q_pad=8)
            rep = eng.run_request(RunRequest(plan=plan, heuristic=MAX_YIELD,
                                             seed=1))
            assert np.array_equal(np.unique(rep.answers, axis=0), ref), \
                (name, q.name)
            k = 2
            repk = eng.run_request(RunRequest(plan=plan, heuristic=MAX_YIELD,
                                              max_answers=k, seed=1))
            assert repk.answers.shape[0] == min(k, ref.shape[0])


def test_mapreduce_budget_stops_compiled_loop_early(setup):
    """The on-device psum stop condition must cut iterations, not just
    truncate on the host: K=1 on an answer-rich query ends the compiled
    while_loop in fewer iterations than exhaustion.  A tiny expand_block
    staggers completions across iterations so the early exit is visible
    even on one device."""
    g, pg, cat, queries = setup
    pg1 = build_partitions(g, np.zeros(g.n_nodes, dtype=np.int32), 1)
    eng = MapReduceMPEngine(pg1, make_part_mesh(1),
                            EngineConfig(cap=32768, expand_block=8))
    cut = 0
    for q in queries:
        plan = generate_plan(q, g, cat)
        if match_query(g, q, q_pad=8).shape[0] == 0:
            continue
        full = eng.run(plan, seed=1)
        k1 = eng.run(plan, seed=1, max_answers=1)
        assert k1.n_iterations <= full.n_iterations
        cut += int(k1.n_iterations < full.n_iterations)
    # at least one query must genuinely exit early on-device
    assert cut >= 1


def test_run_request_validates_max_answers(setup, engines):
    with pytest.raises(ValueError):
        RunRequest(plan=None, max_answers=-1)


def test_truncate_answers_helper():
    a = np.arange(12, dtype=np.int32).reshape(4, 3)
    assert truncate_answers(a, None).shape[0] == 4
    assert truncate_answers(a, 2).shape[0] == 2
    assert truncate_answers(a, 99).shape[0] == 4


def test_budget_sweep_and_k_table_smoke(tmp_path):
    """The response-time-vs-K benchmark path (run_budget_sweep +
    table_k_budget) — not exercised by the CI benchmark smoke, which runs
    --skip-sweep, so cover it here at tiny scale."""
    import sys
    sys.path.insert(0, ".")
    from benchmarks.common import Workload, run_budget_sweep
    from benchmarks.paper_tables import table_k_budget
    from repro.data.generators import subgen_queries

    g = subgen_like_graph(n_nodes=150, n_edges=420, n_embed=8, seed=5)
    wl = Workload("Tiny", g, subgen_queries(g))
    sweep = run_budget_sweep([wl], heuristics=(MAX_SN,), ks=(1, None),
                             seed=0, cap=16384)
    assert sweep.stats
    for s in sweep.stats:
        assert s.answers_requested in (1, None)
        assert s.loads_saved_vs_full >= 0
        if s.answers_requested is None:
            assert s.loads_saved_vs_full == 0
    table = table_k_budget(sweep, str(tmp_path))
    assert "K=1" in table and "K=inf" in table and "MAX-SN" in table
    assert (tmp_path / "table_k_budget.csv").exists()
