"""Pallas kernels vs pure-jnp oracles, swept over shapes/dtypes
(interpret mode on CPU; the kernels TARGET TPU)."""
import numpy as np
import pytest

import jax
import jax.numpy as jnp

from repro.core import (EngineConfig, MAX_SN, OPATEngine, build_catalog,
                        build_partitions, generate_plan, match_query,
                        partition_graph)
from repro.core.plan import PlanArrays
from repro.kernels import ops, ref
from repro.kernels.ops import frontier_expand, frontier_expand_ref, label_histogram


def _random_plan(rng, S, Q):
    return PlanArrays(
        n_slots=Q, n_steps=S,
        start_slot=np.int32(0), start_label=np.int32(0),
        start_value_op=np.int32(0), start_value=np.float32(0),
        src_slot=rng.integers(0, Q, S).astype(np.int32),
        dst_slot=rng.integers(0, Q, S).astype(np.int32),
        edge_label=rng.integers(-1, 3, S).astype(np.int32),
        direction=rng.integers(0, 3, S).astype(np.int32),
        dst_label=rng.integers(-1, 3, S).astype(np.int32),
        dst_value_op=rng.integers(0, 7, S).astype(np.int32),
        dst_value=rng.normal(size=S).astype(np.float32),
        closes_cycle=rng.integers(0, 2, S).astype(np.int32),
    )


def _random_ell(rng, Np, W, n_labels=3):
    dst = rng.integers(-1, Np, size=(Np, W)).astype(np.int32)
    lab = rng.integers(-2, n_labels, size=(Np, W)).astype(np.int32)
    dire = rng.integers(0, 3, size=(Np, W)).astype(np.int32)
    dlab = rng.integers(-2, n_labels, size=(Np, W)).astype(np.int32)
    dval = rng.normal(size=(Np, W)).astype(np.float32)
    dval[rng.random((Np, W)) < 0.2] = np.nan
    dgid = np.where(dst >= 0, rng.integers(0, 1000, size=(Np, W)), -1).astype(np.int32)
    return dst, lab, dire, dlab, dval, dgid


@pytest.mark.parametrize("EB,W,Q,Np", [
    (4, 4, 4, 8),
    (16, 7, 6, 32),       # W not a multiple of 128 -> wrapper pads
    (32, 128, 8, 64),     # W already lane-aligned
    (8, 130, 5, 16),      # W just past one lane tile
    (1, 1, 1, 1),         # degenerate minimum
])
def test_frontier_expand_matches_ref(EB, W, Q, Np):
    rng = np.random.default_rng(EB * 1000 + W)
    S = 6
    plan = _random_plan(rng, S, Q)
    tables = _random_ell(rng, Np, W)
    rows = rng.integers(-1, 1000, size=(EB, Q)).astype(np.int32)
    step = rng.integers(0, S + 2, size=EB).astype(np.int32)
    lidx = rng.integers(0, Np, size=EB).astype(np.int32)
    m = rng.random(EB) < 0.8
    n_steps = np.int32(S - 1)

    ok_k, dg_k = frontier_expand(rows, step, lidx, m, *tables, plan, n_steps)
    ok_r, dg_r = frontier_expand_ref(rows, step, lidx, m, *tables, plan, n_steps)
    np.testing.assert_array_equal(np.asarray(ok_k), np.asarray(ok_r))
    # dst gids only meaningful where an edge exists
    mask = np.asarray(tables[0])[np.clip(lidx, 0, Np - 1)] >= 0
    np.testing.assert_array_equal(np.asarray(dg_k)[mask], np.asarray(dg_r)[mask])


@pytest.mark.parametrize("Np", [1, 5, 1024, 1025, 4096])
@pytest.mark.parametrize("label,op", [(0, 0), (1, 1), (-1, 3), (2, 6)])
def test_label_histogram_matches_ref(Np, label, op):
    rng = np.random.default_rng(abs(Np + label * 31 + op))
    node_label = rng.integers(-2, 4, Np).astype(np.int32)
    node_value = rng.normal(size=Np).astype(np.float32)
    node_value[rng.random(Np) < 0.3] = np.nan
    core = (rng.random(Np) < 0.7).astype(np.int32)
    got = label_histogram(node_label, node_value, core,
                          np.int32(label), np.int32(op), np.float32(0.1))
    want = ref.label_histogram_ref(node_label, node_value, core.astype(bool),
                                   np.int32(label), np.int32(op),
                                   np.float32(0.1))
    assert int(got) == int(want)


def test_value_pred_nan_semantics():
    vals = jnp.asarray([1.0, jnp.nan, 3.0])
    for op in range(7):
        out = np.asarray(ref.value_pred(jnp.int32(op), vals, jnp.float32(1.0)))
        if op == 0:
            assert out.all()
        else:
            assert not out[1]  # NaN fails every comparison


def test_engine_end_to_end_with_pallas(small_graph):
    """The OPAT engine produces oracle-identical answers with the Pallas
    match kernel swapped in (interpret mode)."""
    from repro.data.generators import subgen_queries
    assign = partition_graph(small_graph, 4, "fast")
    pg = build_partitions(small_graph, assign, 4)
    cat = build_catalog(small_graph)
    q = subgen_queries(small_graph)[0].disjuncts[0]
    plan = generate_plan(q, small_graph, cat)
    eng = OPATEngine(pg, EngineConfig(cap=16384, use_pallas=True))
    res = eng.run(plan, MAX_SN)
    ref_ans = match_query(small_graph, q, q_pad=8)
    assert np.array_equal(np.unique(res.answers, axis=0), ref_ans)


# ---------------------------------------------------------------------------
# fused expand + classify kernel (single-pass done/keep/out routing)
# ---------------------------------------------------------------------------

_V = 1000   # global-id space used by _random_ell's dgid column


def _random_locality(rng, Np):
    """Random partition context: g2l row (-1 = absent), owner map, core
    boundary."""
    g2l_row = np.full(_V, -1, np.int32)
    present = rng.choice(_V, size=min(Np, _V), replace=False)
    g2l_row[present] = rng.permutation(len(present)).astype(np.int32)
    owner = rng.integers(0, 4, _V).astype(np.int32)
    n_core = int(rng.integers(1, Np + 1))
    return g2l_row, owner, n_core


def _fused_both(rng, plan, tables, EB, W, Q, Np, n_steps, m=None):
    g2l_row, owner, n_core = _random_locality(rng, Np)
    dlidx, downer = ops.denorm_locality(jnp.asarray(tables[5]),
                                        jnp.asarray(g2l_row),
                                        jnp.asarray(owner))
    rows = rng.integers(-1, _V, size=(EB, Q)).astype(np.int32)
    step = rng.integers(0, plan.n_steps + 2, size=EB).astype(np.int32)
    lidx = rng.integers(0, Np, size=EB).astype(np.int32)
    if m is None:
        m = rng.random(EB) < 0.8
    got = ops.fused_frontier(rows, step, lidx, m, *tables, dlidx, downer,
                             g2l_row, owner, n_core, plan, n_steps)
    want = ops.fused_frontier_ref(rows, step, lidx, m, *tables,
                                  g2l_row, owner, n_core, plan, n_steps)
    return got, want, lidx


def _assert_fused_equal(got, want, tables, lidx, Np):
    names = ("ok", "dg", "done", "keep", "out", "dest")
    ok_k, dg_k, done_k, keep_k, out_k, dest_k = map(np.asarray, got)
    ok_r, dg_r, done_r, keep_r, out_r, dest_r = map(np.asarray, want)
    for name, a, b in zip(names, (ok_k, done_k, keep_k, out_k),
                          (ok_r, done_r, keep_r, out_r)):
        np.testing.assert_array_equal(a, b, err_msg=name)
    # dst gids only meaningful where an edge exists; dest only where the
    # row is routed out
    edge = np.asarray(tables[0])[np.clip(lidx, 0, Np - 1)] >= 0
    np.testing.assert_array_equal(dg_k[edge], dg_r[edge], err_msg="dg")
    np.testing.assert_array_equal(dest_k[out_r], dest_r[out_r],
                                  err_msg="dest")
    # the three routes partition the matches: done|keep|out == ok, disjoint
    assert not (done_r & keep_r).any() and not (done_r & out_r).any() \
        and not (keep_r & out_r).any()
    np.testing.assert_array_equal(done_r | keep_r | out_r, ok_r)


@pytest.mark.parametrize("EB,W,Q,Np", [
    (4, 4, 4, 8),
    (16, 7, 6, 32),       # W not a multiple of 128 -> wrapper pads
    (32, 128, 8, 64),     # W already lane-aligned
    (8, 130, 5, 16),      # W just past one lane tile
    (1, 1, 1, 1),         # degenerate minimum
])
def test_fused_frontier_matches_ref(EB, W, Q, Np):
    rng = np.random.default_rng(EB * 1000 + W + 7)
    plan = _random_plan(rng, 6, Q)
    tables = _random_ell(rng, Np, W)
    got, want, lidx = _fused_both(rng, plan, tables, EB, W, Q, Np,
                                  np.int32(5))
    _assert_fused_equal(got, want, tables, lidx, Np)


def test_fused_frontier_empty_frontier():
    """An all-inactive binding batch matches the oracle and routes
    nothing."""
    rng = np.random.default_rng(11)
    EB, W, Q, Np = (8, 16, 4, 8)
    plan = _random_plan(rng, 6, Q)
    tables = _random_ell(rng, Np, W)
    got, want, lidx = _fused_both(rng, plan, tables, EB, W, Q, Np,
                                  np.int32(5), m=np.zeros(EB, bool))
    _assert_fused_equal(got, want, tables, lidx, Np)
    ok, _, done, keep, out, _ = map(np.asarray, got)
    assert not ok.any() and not done.any() and not keep.any() \
        and not out.any()


def test_fused_frontier_all_filtered_labels():
    """A plan whose edge label exists nowhere in the partition matches
    the oracle and produces zero matches."""
    import dataclasses
    rng = np.random.default_rng(13)
    EB, W, Q, Np = (8, 16, 4, 8)
    plan = _random_plan(rng, 6, Q)
    plan = dataclasses.replace(plan, edge_label=np.full(6, 7, np.int32))
    tables = _random_ell(rng, Np, W, n_labels=3)   # labels in [-2, 3)
    got, want, lidx = _fused_both(rng, plan, tables, EB, W, Q, Np,
                                  np.int32(5))
    _assert_fused_equal(got, want, tables, lidx, Np)
    assert not np.asarray(got[0]).any()


# ---------------------------------------------------------------------------
# fused path swapped into every engine: oracle identity end to end
# ---------------------------------------------------------------------------

def _pallas_setup(small_graph):
    from repro.data.generators import subgen_queries
    assign = partition_graph(small_graph, 4, "kway_shem")
    pg = build_partitions(small_graph, assign, 4)
    cat = build_catalog(small_graph)
    queries = [dq.disjuncts[0] for dq in subgen_queries(small_graph)]
    return pg, cat, queries


def test_traditional_mp_end_to_end_with_pallas(small_graph):
    """TraditionalMP vmaps the fused kernel over p partitions per
    iteration; answers stay oracle-identical."""
    from repro.core import TraditionalMPEngine
    pg, cat, queries = _pallas_setup(small_graph)
    eng = TraditionalMPEngine(pg, 2, EngineConfig(cap=16384, use_pallas=True))
    for q in queries:
        plan = generate_plan(q, small_graph, cat)
        res = eng.run(plan, MAX_SN, seed=1)
        ref_ans = match_query(small_graph, q, q_pad=8)
        assert np.array_equal(np.unique(res.answers, axis=0), ref_ans), q.name


@pytest.mark.parametrize("K", [None, 3])
def test_mapreduce_end_to_end_with_pallas(small_graph, K):
    """MapReduceMP runs the fused kernel under shard_map; with a budget the
    single compiled run returns exactly min(K, total) unique answers."""
    from repro.core.mapreduce_mp import make_part_mesh
    from repro.core.mapreduce_mp import MapReduceMPEngine
    _, cat, queries = _pallas_setup(small_graph)
    pg = build_partitions(small_graph,
                          np.zeros(small_graph.n_nodes, np.int32), 1)
    mesh = make_part_mesh(1)
    eng = MapReduceMPEngine(pg, mesh, EngineConfig(cap=32768, use_pallas=True))
    for q in queries:
        plan = generate_plan(q, small_graph, cat)
        res = eng.run(plan, max_answers=K)
        ref_ans = match_query(small_graph, q, q_pad=8)
        if K is None:
            assert np.array_equal(np.unique(res.answers, axis=0), ref_ans)
        else:
            got = np.unique(res.answers, axis=0)
            assert got.shape[0] == min(K, ref_ans.shape[0]), q.name
            refset = {tuple(r) for r in ref_ans}
            assert all(tuple(r) in refset for r in got), q.name


def test_scheduler_batch_with_pallas(small_graph):
    """The scheduler's batched evaluator (query-vmapped fused kernel)
    returns oracle-identical answer sets for a shared batch."""
    from repro.core import GraphSession, match_disjunctive
    from repro.data.generators import subgen_queries
    dqueries = subgen_queries(small_graph)
    sess = GraphSession(small_graph, k=4, scheme="kway_shem", engine="opat",
                        seed=1, config=EngineConfig(cap=32768,
                                                    use_pallas=True))
    report = sess.submit_many(dqueries)
    assert report.shared
    for res, dq in zip(report.results, dqueries):
        ref_ans = match_disjunctive(small_graph, dq, q_pad=8)
        assert np.array_equal(res.answers, ref_ans), dq.name


def test_opat_pallas_k_budget_truncation(small_graph):
    """K-budget truncation through the fused path: min(K, total) unique
    true answers."""
    pg, cat, queries = _pallas_setup(small_graph)
    eng = OPATEngine(pg, EngineConfig(cap=16384, use_pallas=True))
    for q in queries:
        plan = generate_plan(q, small_graph, cat)
        ref_ans = match_query(small_graph, q, q_pad=8)
        refset = {tuple(r) for r in ref_ans}
        for K in (1, 3):
            res = eng.run(plan, MAX_SN, seed=1, max_answers=K)
            got = np.unique(res.answers, axis=0)
            assert got.shape[0] == min(K, ref_ans.shape[0]), (q.name, K)
            assert all(tuple(r) in refset for r in got), (q.name, K)


def test_mapreduce_yield_counters_surface(small_graph):
    """The compiled MapReduce program carries per-partition completed/
    spawned counters out; a budgeted run is a single compiled call (no
    geometric host re-runs), so requested==returned exactly."""
    from repro.core.mapreduce_mp import make_part_mesh
    from repro.core.mapreduce_mp import MapReduceMPEngine
    _, cat, queries = _pallas_setup(small_graph)
    pg = build_partitions(small_graph,
                          np.zeros(small_graph.n_nodes, np.int32), 1)
    eng = MapReduceMPEngine(pg, make_part_mesh(1), EngineConfig(cap=32768))
    for q in queries:
        plan = generate_plan(q, small_graph, cat)
        res = eng.run(plan)
        assert res.completed_from is not None and \
            res.completed_from.shape == (1,)
        assert res.spawned_from is not None and \
            res.spawned_from.shape == (1,)
        # every unique answer was completed at least once (duplicates may
        # push the raw counter higher)
        ref_ans = match_query(small_graph, q, q_pad=8)
        assert int(res.completed_from.sum()) >= ref_ans.shape[0]
        assert int(res.spawned_from.sum()) >= 0
