"""QueryScheduler — shared-load multi-query serving (core/scheduler.py).

Covers the ISSUE-4 satellite/acceptance list:
  * batched answers bit-identical to sequential ``submit`` for the same
    query set, for all three engines;
  * per-query ``max_answers`` budgets respected inside a shared batch;
  * retirement releases partitions from the index, with store eviction /
    release observable via ``LoadStats``;
  * shared serving of overlapping queries pays strictly fewer cold loads
    than isolated (no-sharing) serving;
  * ``QueryResult.load_stats`` deltas are round-scoped (a query's counters
    cover exactly the loads it participated in, never other queries');
  * the workload JSONL round trip (serve ``--workload`` format);
  * the shared-vs-isolated throughput sweep (slow marker).
"""
import json

import numpy as np
import pytest

from repro.core import (EngineConfig, GraphSession, MAX_SN, MAX_YIELD_SHARED,
                        batch_bucket, match_disjunctive,
                        rank_partitions_shared)
from repro.core.query import DisjunctiveQuery
from repro.data.generators import subgen_like_graph, subgen_queries


@pytest.fixture(scope="module")
def setup():
    g = subgen_like_graph(n_nodes=250, n_edges=700, n_embed=10, seed=3)
    dqueries = subgen_queries(g)
    refs = {dq.name: match_disjunctive(g, dq, q_pad=8) for dq in dqueries}
    return g, dqueries, refs


def make_session(g, engine="opat", k=4, **kw):
    return GraphSession(g, k=k, scheme="kway_shem", engine=engine, seed=1,
                        processors=2, config=EngineConfig(cap=32768), **kw)


# ---------------------------------------------------------------------------
# building blocks
# ---------------------------------------------------------------------------

def test_batch_bucket_powers_of_two():
    assert [batch_bucket(n) for n in (1, 2, 3, 4, 5, 8, 9, 16)] == \
        [1, 2, 4, 4, 8, 8, 16, 16]


def test_rank_partitions_shared_scoring():
    rng = np.random.default_rng(0)
    # pid 0: two waiters with high SNI but near-zero completion rates;
    # pid 1: one waiter with modest SNI but perfect completion rate
    waiting = {0: [(10, 0.01), (10, 0.01)], 1: [(5, 1.0)]}
    assert rank_partitions_shared(MAX_SN, waiting, rng)[0] == 0      # 20 > 5
    assert rank_partitions_shared(MAX_YIELD_SHARED, waiting, rng)[0] == 1
    assert rank_partitions_shared(MAX_SN, {}, rng) == []
    with pytest.raises(ValueError):
        rank_partitions_shared("min-sn", waiting, rng)


def test_rank_partitions_shared_aggregates_over_waiters():
    rng = np.random.default_rng(0)
    # one query alone would prefer pid 1 (bigger single SNI), but the
    # workload's summed yield makes pid 0 the shared winner
    waiting = {0: [(4, 0.5), (4, 0.5), (4, 0.5)], 1: [(5, 0.5)]}
    assert rank_partitions_shared(MAX_YIELD_SHARED, waiting, rng)[0] == 0


# ---------------------------------------------------------------------------
# parity with sequential submit (acceptance)
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("engine_name", ["opat", "traditional", "mapreduce"])
def test_submit_many_matches_sequential_submit(setup, engine_name):
    """Acceptance: batched answers bit-identical to sequential ``submit``
    for the same query set, across all three engines."""
    g, dqueries, refs = setup
    k = 1 if engine_name == "mapreduce" else 4   # 1 partition per device
    seq = make_session(g, engine_name, k=k)
    seq_res = [seq.submit(dq) for dq in dqueries]
    sh = make_session(g, engine_name, k=k)
    report = sh.submit_many(dqueries)
    # OPAT and TraditionalMP both share (OPAT: one partition advancing the
    # batch; TMP: one stacked top-p bundle carrying every waiter's plans);
    # MapReduceMP has no host loop to share and drains sequentially
    assert report.shared == (engine_name in ("opat", "traditional"))
    assert [r.name for r in report.results] == [dq.name for dq in dqueries]
    for sres, bres, dq in zip(seq_res, report.results, dqueries):
        assert np.array_equal(sres.answers, bres.answers), dq.name
        assert np.array_equal(bres.answers, refs[dq.name]), dq.name
        assert len(bres.reports) == len(dq.disjuncts)
        assert bres.latency_s >= 0.0


def test_shared_batch_budgets_respected(setup):
    """Per-query budgets retire queries independently inside one shared
    batch: every returned row is a true answer and each query returns
    min(K, total) unique rows."""
    g, dqueries, refs = setup
    sess = make_session(g)
    batch = dqueries * 3                        # 9 overlapping queries
    report = sess.submit_many(batch, max_answers=2)
    assert len(report.results) == len(batch)
    for res, dq in zip(report.results, batch):
        ref = refs[dq.name]
        refset = {tuple(r) for r in ref}
        assert all(tuple(r) in refset for r in res.answers), dq.name
        assert res.n_answers == min(2, ref.shape[0]), dq.name
        for rep in res.reports:
            assert rep.stats.answers_requested == 2


def test_submit_many_per_query_budget_list(setup):
    g, dqueries, refs = setup
    sess = make_session(g)
    budgets = [1, None, 3]
    report = sess.submit_many(dqueries, max_answers=budgets)
    for res, dq, b in zip(report.results, dqueries, budgets):
        ref = refs[dq.name]
        want = ref.shape[0] if b is None else min(b, ref.shape[0])
        assert res.n_answers == want, dq.name
    with pytest.raises(ValueError):
        sess.submit_many(dqueries, max_answers=[1])   # wrong length


def test_budget_zero_does_no_loads(setup):
    g, dqueries, _ = setup
    sess = make_session(g)
    report = sess.submit_many(dqueries, max_answers=0)
    assert report.loads == []
    for res in report.results:
        assert res.n_answers == 0 and res.n_loads == 0


# ---------------------------------------------------------------------------
# shared-load amortization (acceptance)
# ---------------------------------------------------------------------------

def test_shared_fewer_cold_loads_than_isolated(setup):
    """Acceptance: a batch of >= 8 overlapping queries pays strictly fewer
    cold partition loads shared than served in isolation (store cleared
    between queries, the no-sharing baseline), at identical answers."""
    g, dqueries, refs = setup
    batch = dqueries * 3                        # 9 overlapping queries
    iso = make_session(g)
    iso0 = iso.load_stats.copy()
    iso_answers = []
    for dq in batch:
        iso.store.clear()
        iso_answers.append(iso.submit(dq).answers)
    iso_cold = (iso.load_stats - iso0).cold_loads

    sh = make_session(g)
    report = sh.submit_many(batch)
    assert report.load_stats.cold_loads < iso_cold
    # shared workload loads are amortized: fewer load events than the sum
    # of per-query sequences
    assert report.n_loads < sum(r.n_loads for r in report.results)
    for res, ref_a in zip(report.results, iso_answers):
        assert np.array_equal(res.answers, ref_a), res.name
    # one batched evaluation really advanced many queries at once
    assert max(report.batch_sizes) >= 8


def test_round_scoped_load_stats(setup):
    """Satellite: LoadStats deltas are scoped to the scheduler round —
    the report's delta is the store's exact delta over the round, and each
    query's counters cover exactly the loads it participated in."""
    g, dqueries, _ = setup
    sess = make_session(g)
    stats0 = sess.load_stats.copy()
    report = sess.submit_many(dqueries)
    delta = sess.load_stats - stats0
    assert report.load_stats == delta
    # round totals: one store get per workload load event
    assert delta.hits + delta.misses == report.n_loads
    for res in report.results:
        # single-disjunct queries: one get per participated round
        part = res.load_stats
        assert part.hits + part.misses == res.n_loads
        assert part.cold_loads <= report.load_stats.cold_loads
    # a query participating in every round sees the round's cold loads;
    # the ROUND still counts each shared cold load once, so summing the
    # per-query views over-counts exactly the sharing factor
    assert sum(r.load_stats.cold_loads for r in report.results) \
        >= report.load_stats.cold_loads
    # interleaved single submits stay correctly scoped after a batch
    res = sess.submit(dqueries[0])
    assert res.load_stats.hits + res.load_stats.misses == res.n_loads


def test_retirement_releases_partitions(setup):
    """Satellite: budget retirement drops queries from the partition index
    and (with release_retired) releases store entries nobody pending can
    use — observable via LoadStats.released and the store contents."""
    g, dqueries, _ = setup
    sess = make_session(g, cache_parts=2)
    sched = sess.scheduler(release_retired=True)
    for dq in dqueries:
        sched.admit(dq, max_answers=1)
    assert sched.n_pending == sum(len(dq.disjuncts) for dq in dqueries)
    assert sched.partition_waiters()            # index non-empty up front
    report = sched.run()
    assert sched.n_pending == 0
    assert sched.partition_waiters() == {}      # retired queries dropped out
    stats = report.load_stats
    assert stats.released > 0                   # retirement really released
    # released entries are gone from the device cache
    assert all(not sess.store.contains(p) for p in set(report.loads))
    # and the capacity-bounded LRU evicted at session scope as usual
    assert stats.released + stats.evictions > 0


def test_streaming_admission_two_rounds(setup):
    """The scheduler is a stream: admit -> run -> admit -> run reports
    each query exactly once, and the second round reuses residency."""
    g, dqueries, refs = setup
    sess = make_session(g)
    sched = sess.scheduler()
    empty = sched.run()
    assert empty.results == [] and empty.loads == []
    sched.admit(dqueries[0])
    r1 = sched.run()
    assert [r.name for r in r1.results] == [dqueries[0].name]
    sched.admit(dqueries[1])
    r2 = sched.run()
    assert [r.name for r in r2.results] == [dqueries[1].name]
    assert np.array_equal(r1.results[0].answers, refs[dqueries[0].name])
    assert np.array_equal(r2.results[0].answers, refs[dqueries[1].name])
    # round 2 found round 1's partitions device-resident
    assert r2.load_stats.warm_loads > 0


@pytest.mark.parametrize("engine_name", ["opat", "traditional"])
def test_long_lived_scheduler_serves_past_the_load_guard(setup, engine_name):
    """A streaming scheduler pumped one round at a time, one query pending
    at a time, keeps serving after its lifetime loads pass the runaway
    guard's per-query bound (64 loads a partition): the guard weighs a
    query's own loads, not everything the scheduler ever loaded."""
    g, dqueries, refs = setup
    sess = make_session(g, engine_name, k=1)
    sched = sess.scheduler()
    bound = 64 * sess.pg.k
    served = 0
    while len(sched.loads) <= bound:
        dq = dqueries[served % len(dqueries)]
        qid = sched.admit(dq)
        results = []
        while sched.n_pending:
            results += sched.run(max_rounds=1).results
        assert [r.qid for r in results] == [qid]
        assert np.array_equal(results[0].answers, refs[dq.name])
        served += 1
    assert served > 1


def test_scheduler_refuses_rebound_session(setup):
    """GraphSession.repartition() rebinds store/layout; a scheduler built
    against the old binding must refuse loudly instead of mixing pids."""
    g, dqueries, _ = setup
    sess = make_session(g)
    sched = sess.scheduler()
    sched.admit(dqueries[0])
    sched.run()
    sess.repartition()
    with pytest.raises(RuntimeError, match="rebound"):
        sched.admit(dqueries[1])
    with pytest.raises(RuntimeError, match="rebound"):
        sched.run()
    # a fresh scheduler against the new binding works
    assert sess.submit_many([dqueries[1]]).results[0].n_answers >= 0


def test_submit_many_feeds_workload_profile_like_submit(setup):
    """Satellite: the profile absorbs batched results exactly as single
    submits do — same queries/answers served, same answer-span
    observations (the spans depend only on the answers)."""
    g, dqueries, _ = setup
    seq = make_session(g)
    for dq in dqueries:
        seq.submit(dq)
    sh = make_session(g)
    sh.submit_many(dqueries)
    p_seq, p_sh = seq.workload_profile(), sh.workload_profile()
    assert p_sh["queries_served"] == p_seq["queries_served"]
    assert p_sh["answers_served"] == p_seq["answers_served"]
    assert p_sh["answer_spans"] == p_seq["answer_spans"]
    assert p_sh["assignment"] == p_seq["assignment"]
    # per-partition load counters exist for the shared path too (they
    # count each query's participations, so totals can only be smaller)
    assert sum(p["loads"] for p in p_sh["partitions"]) > 0


# ---------------------------------------------------------------------------
# workload JSONL round trip (serve --workload format)
# ---------------------------------------------------------------------------

def test_query_jsonl_roundtrip(setup, tmp_path):
    g, dqueries, refs = setup
    path = tmp_path / "w.jsonl"
    with open(path, "w") as f:
        for dq in dqueries:
            f.write(json.dumps(dq.to_json_dict()) + "\n")
    with open(path) as f:
        loaded = [DisjunctiveQuery.from_json_dict(json.loads(l)) for l in f]
    assert [dq.name for dq in loaded] == [dq.name for dq in dqueries]
    sess = make_session(g)
    report = sess.submit_many(loaded)
    for res, dq in zip(report.results, dqueries):
        assert np.array_equal(res.answers, refs[dq.name]), dq.name
    # a bare conjunctive line is accepted as a single-disjunct query
    bare = DisjunctiveQuery.from_json_dict(
        dqueries[0].disjuncts[0].to_json_dict())
    assert len(bare.disjuncts) == 1 and bare.name == dqueries[0].name
    # a malformed line fails at parse time, not deep inside serving
    with pytest.raises(ValueError, match="no disjuncts"):
        DisjunctiveQuery.from_json_dict({"name": "bad", "disjuncts": []})


# ---------------------------------------------------------------------------
# throughput sweep (the benchmark the CI full lane smokes)
# ---------------------------------------------------------------------------

@pytest.mark.slow
def test_shared_sweep_acceptance():
    """Acceptance: on a batch of 8 overlapping skewed-workload queries the
    shared scheduler performs strictly fewer cold loads than isolated
    serving, with identical oracle-verified answers, and the table inputs
    (loads/query, q/s) are populated for both modes."""
    from benchmarks.common import run_shared_sweep
    res = run_shared_sweep(batch_sizes=(8,))
    assert res.answers_identical and res.oracle_match
    iso = res.phase(8, "isolated")
    sh = res.phase(8, "shared")
    assert sh.cold_loads < iso.cold_loads
    assert sh.loads_per_query < iso.loads_per_query
    assert iso.qps > 0 and sh.qps > 0
    assert iso.n_answers == sh.n_answers > 0


# ---------------------------------------------------------------------------
# scheduler fairness under skew (ISSUE-5 satellite)
# ---------------------------------------------------------------------------

def test_fairness_aging_bounds_starvation_rounds():
    """A no-overlap query's partition (one waiter, SNI 1) can be passed
    over forever by pure yield ranking while hot traffic keeps a big
    shared score alive; the aging term (rounds-waiting x SNI, weighted by
    fairness_gamma) guarantees it reaches rank 0 within a bounded number
    of rounds."""
    rng = np.random.default_rng(0)

    def waiting(age):
        # partition 0: three persistent hot waiters (base score 75);
        # partition 9: the lone cold waiter, aged `age` rounds
        return {0: [(50, 0.5, 0), (50, 0.5, 0), (50, 0.5, 0)],
                9: [(1, 0.5, age)]}

    # gamma = 0 (the default): starves at every age — pure yield
    for age in (0, 10, 100, 10_000):
        assert rank_partitions_shared(
            MAX_YIELD_SHARED, waiting(age), rng)[0] == 0
    # gamma > 0: served within ceil(hot_score / (gamma * sni)) rounds
    gamma = 1.0
    first = next(age for age in range(200) if rank_partitions_shared(
        MAX_YIELD_SHARED, waiting(age), rng, fairness_gamma=gamma)[0] == 9)
    assert first <= 75       # 0.5 + gamma*age > 75  <=>  age >= 75
    # the same bound applies to the max-sn shared ranking (base 150)
    first_sn = next(age for age in range(400) if rank_partitions_shared(
        MAX_SN, waiting(age), rng, fairness_gamma=gamma)[0] == 9)
    assert first_sn <= 150
    # two-tuple observations (no age recorded) still rank — age reads 0
    assert rank_partitions_shared(MAX_YIELD_SHARED,
                                  {0: [(10, 0.5)], 1: [(1, 0.5)]},
                                  rng, fairness_gamma=5.0)[0] == 0


def test_fairness_gamma_threaded_and_semantics_preserved(setup):
    """fairness_gamma reaches the shared ranking through submit_many /
    scheduler() and never changes answer sets — only the load ORDER may
    differ."""
    g, dqueries, refs = setup
    for gamma in (0.0, 2.5):
        sess = make_session(g)
        report = sess.submit_many(dqueries, fairness_gamma=gamma)
        for r in report.results:
            assert np.array_equal(r.answers, refs[r.name]), (gamma, r.name)
    sess = make_session(g)
    sched = sess.scheduler(fairness_gamma=1.5)
    assert sched.fairness_gamma == 1.5
    with pytest.raises(ValueError, match="fairness_gamma"):
        sess.scheduler(fairness_gamma=-0.1)
