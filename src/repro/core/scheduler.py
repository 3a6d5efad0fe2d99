"""QueryScheduler — shared-load multi-query OPAT with batched partition
evaluation.

The paper's cost model says response time is dominated by the number and
sequence of partition *loads*, and its heuristics (Sec. 5) optimize that
sequence per query.  A serving deployment has many queries outstanding at
once, and a single device-resident partition can advance all of them —
throughput comes from amortizing data residency across concurrent work
(Fan et al.'s partial evaluation of distributed query fragments; Vaquero
et al.'s near-real-time systems survey), not from optimizing queries in
isolation.  This module is that observation as a subsystem, one layer
between the ``GraphSession`` API and the engines:

  admission    — ``admit()`` expands a (possibly disjunctive) query into
                 per-disjunct *jobs*, each carrying its own plan,
                 ``QueryState`` (SNI/IMA/FAA bookkeeping, identical to the
                 per-query OPAT loop) and ``max_answers`` budget.
  the index    — every round the scheduler derives the partition →
                 waiting-jobs index from the jobs' SNI/IMA eligibility;
                 ``rank_partitions_shared`` (core/heuristics.py) scores
                 each candidate partition by total expected yield summed
                 over every waiting query (MAX-YIELD-SHARED: Σ SNI ×
                 smoothed completion rate), so one cold load services many
                 queries, and the store prefetches the *workload's*
                 runner-up rather than one query's.
  batched eval — the loaded partition evaluates the plans of ALL waiting
                 jobs in one compiled call: stacked ``PlanArrays`` +
                 per-job inputs through ``OPATEngine.batched_evaluator()``
                 (``vmap`` over the query axis, partition broadcast).  The
                 batch is padded up to a power-of-two bucket so the jit
                 cache keeps one trace per bucket, reused across rounds
                 and batch sizes.
  retirement   — a job retires when its budget is met or nothing is
                 eligible; a query retires when all its jobs have.  Retired
                 queries drop out of the index, so their partitions stop
                 being touched and age out of the store's LRU naturally;
                 with ``release_retired=True`` the scheduler additionally
                 ``release()``s partitions no pending job can currently
                 use (observable via ``LoadStats.released``).

Per-query bookkeeping correctness is preserved exactly: each job routes
its evaluator outputs through the same ``absorb_eval_outputs`` as the
one-query-at-a-time loop, so exhaustive answers are bit-identical to
sequential ``GraphSession.submit`` (tests/test_scheduler.py asserts this
for all three engines).  TraditionalMP shares too: each round one stacked
top-p bundle carries EVERY waiting query's inputs through the store and
the engine's double-vmapped ``shared_evaluator()`` — B plans × p
partitions in one compiled call (``_run_shared_tmp``).  MapReduceMP runs
a whole query as one compiled program with no host partition loop to
share, so the scheduler drains its jobs sequentially with unchanged
semantics.

``LoadStats`` attribution is *round-scoped*: ``ScheduleReport.load_stats``
is the store's exact delta over one ``run()`` (what the round cost), and
each ``QueryResult.load_stats`` is that query's participation view — the
sum of the per-load-event deltas for loads its plans took part in (a cold
load shared by three queries appears in each one's view but only once in
the round's).  Interleaved/batched submits therefore never bleed other
queries' store traffic into a result's counters.
"""
from __future__ import annotations

import contextlib
import dataclasses
import time
from typing import Dict, List, Optional, Set, Union

import numpy as np

from .heuristics import MAX_YIELD_SHARED, SHARED_HEURISTICS, \
    rank_partitions_shared
from .engine import host_nbytes, read_rows, traced_eval
from .metrics import RunStats, l_ideal_for_plan, residency
from .opat import OPATEngine, absorb_eval_outputs
from .plan import Plan, PlanArrays, generate_plan
from .query import DisjunctiveQuery, Query
from .runner import RunReport, RunRequest, truncate_answers
from .session import QueryResult
from .state import BindingBatch, QueryState
from .store import LoadStats
from .traditional_mp import TraditionalMPEngine


def batch_bucket(n: int) -> int:
    """Round a batch size up to the next power of two — the padded batch
    shapes the compiled call sees, so B=5..8 all reuse the B=8 trace."""
    assert n >= 1
    return 1 << (n - 1).bit_length()


@dataclasses.dataclass
class _Job:
    """One disjunct of one admitted query: a plan plus the same SNI/IMA/FAA
    bookkeeping state the per-query OPAT loop keeps."""

    qid: int
    plan: Plan
    plan_arrays: PlanArrays
    state: QueryState
    max_answers: Optional[int]
    retired: bool = False
    load_stats: LoadStats = dataclasses.field(default_factory=LoadStats)
    report: Optional[RunReport] = None   # sequential fallback: engine-built
    rounds_waiting: int = 0              # consecutive rounds passed over
                                         # (the fairness aging signal)
    urgency: float = 0.0                 # deadline pressure (SLO front end:
                                         # slack-weighted; 0 = no deadline)
    eval_iters: int = 0                  # evaluator trips of the lanes this
    rows_expanded: int = 0               # job rode, and rows they expanded


@dataclasses.dataclass
class _Admitted:
    """One admitted query: its jobs plus per-query attribution."""

    qid: int
    name: str
    jobs: List[_Job]
    max_answers: Optional[int]
    load_stats: LoadStats = dataclasses.field(default_factory=LoadStats)
    finished_at: Optional[float] = None
    # perf_counter bounds of the query's life in the scheduler — the
    # tracer's timebase, so _collect_results can emit one root "query"
    # span per retired query (admission → retirement) via add_span
    admitted_perf: float = 0.0
    finished_perf: Optional[float] = None


@dataclasses.dataclass
class ScheduleReport:
    """What one ``run()`` round produced: per-query results plus the
    workload-level load sequence and the round-scoped store delta."""

    results: List[QueryResult]   # queries finished this round, admit order
    loads: List[int]             # workload-level partition-load sequence
    batch_sizes: List[int]       # jobs advanced per load (1s when not shared)
    load_stats: LoadStats        # exact store delta over this round
    wall_s: float
    shared: bool                 # True when the shared OPAT path ran

    @property
    def n_loads(self) -> int:
        return len(self.loads)

    @property
    def loads_per_query(self) -> float:
        """Workload loads amortized over the round's queries — the shared
        path's headline metric (one load advancing 4 queries counts once
        here, once per query in each ``QueryResult``)."""
        return self.n_loads / len(self.results) if self.results else 0.0


class QueryScheduler:
    """Admits a batch/stream of queries against one ``GraphSession`` and
    serves them with workload-level load ordering.

    ``heuristic`` is a shared ranking (``SHARED_HEURISTICS``:
    ``max-yield-shared`` default, or ``max-sn`` for the plain summed-SNI
    variant); the per-query heuristic of the session still governs the
    non-OPAT sequential fallback.  ``release_retired`` proactively frees
    store entries no pending job can use when a query retires (off by
    default: a warm entry is only worth dropping under memory pressure).
    ``fairness_gamma`` weights the aging term (rounds-waiting × SNI) in
    the shared ranking — 0 (default) is pure yield; any positive value
    bounds how many rounds a no-overlap query can be passed over under a
    skewed workload (see ``rank_partitions_shared``).
    """

    def __init__(self, session, *, heuristic: str = MAX_YIELD_SHARED,
                 seed: Optional[int] = None,
                 release_retired: bool = False,
                 prefetch: Optional[bool] = None,
                 fairness_gamma: float = 0.0):
        if heuristic not in SHARED_HEURISTICS:
            raise ValueError(f"shared heuristic must be one of "
                             f"{SHARED_HEURISTICS}, got {heuristic!r}")
        if fairness_gamma < 0.0:
            raise ValueError(f"fairness_gamma must be >= 0, "
                             f"got {fairness_gamma}")
        self.session = session
        self.fairness_gamma = float(fairness_gamma)
        self.pg = session.pg
        self.store = session.store
        from ..obs.trace import NULL_TRACER
        self.tracer = getattr(session, "tracer", None) or NULL_TRACER
        from ..obs.profile import NULL_PROFILER
        self.profiler = getattr(session, "profiler", None) or NULL_PROFILER
        # generation pinning (storage/deltas.py): the scheduler takes its
        # OWN pin on the session's current view at construction — every
        # round of every run() resolves loads, SNI counts, and plans
        # against that one generation, even while mutations land and
        # compactions publish newer ones mid-run.  The pin keeps the
        # generation's files out of GC until close().  In-RAM sessions
        # have no view and nothing changes.
        self.view = getattr(session, "current_view", None)
        if self.view is not None:
            self.view.pin()
        self._graph = session.graph
        self._catalog = session.catalog
        self._closed = False
        self.heuristic = heuristic
        self.seed = session.seed if seed is None else seed
        self.release_retired = release_retired
        self.prefetch = (getattr(session.engine, "prefetch", False)
                         if prefetch is None else prefetch)
        # reported queries are pruned after each run(), so a long-lived
        # streaming scheduler holds state proportional to the PENDING set,
        # not to everything it ever served
        self._admitted: Dict[int, _Admitted] = {}
        self._next_qid = 0
        self._jobs: List[_Job] = []
        self._touched: Set[int] = set()   # pids the shared loop ever loaded
        self.loads: List[int] = []
        self.batch_sizes: List[int] = []

    # -- admission ---------------------------------------------------------

    def admit(self, query: Union[Query, DisjunctiveQuery],
              max_answers: Optional[int] = None,
              urgency: float = 0.0) -> int:
        """Add a query to the pending set; returns its qid.  ``max_answers``
        is the per-disjunct answer budget K, exactly as in ``submit``.
        ``urgency`` is the SLO front end's deadline-pressure weight: every
        partition this query waits on gains ``SNI × urgency`` in the shared
        ranking (0, the default, changes nothing — see
        ``rank_partitions_shared``); update it per round via
        ``set_urgency`` as slack shrinks."""
        self._check_binding()
        session = self.session
        cfg = session.config
        qid = self._next_qid
        self._next_qid += 1
        disjuncts = (query.disjuncts if isinstance(query, DisjunctiveQuery)
                     else [query])
        jobs: List[_Job] = []
        for q in disjuncts:
            # plans and SNI counts come from the scheduler's PINNED
            # binding, not the session's live one — one scheduler, one
            # generation, even for queries admitted after a mutation
            with self.tracer.span("query.plan", query=q.name):
                plan = generate_plan(q, self._graph, self._catalog)
            assert plan.n_slots <= cfg.q_pad and plan.n_steps <= cfg.s_pad
            counts = self.pg.start_label_counts(plan.start_label,
                                                plan.start_value_op,
                                                plan.start_value)
            st = QueryState.initial(self.pg.k, cfg.q_pad, counts,
                                    track_answer_keys=max_answers is not None)
            jobs.append(_Job(
                qid=qid, plan=plan,
                plan_arrays=PlanArrays.from_plan(plan, pad_steps=cfg.s_pad),
                state=st, max_answers=max_answers,
                urgency=float(urgency)))
        self._admitted[qid] = _Admitted(qid=qid, name=query.name, jobs=jobs,
                                        max_answers=max_answers,
                                        admitted_perf=time.perf_counter())
        self._jobs.extend(jobs)
        return qid

    def set_urgency(self, qid: int, urgency: float) -> None:
        """Refresh a pending query's deadline pressure (all its jobs); the
        SLO front end calls this each pump as deadlines approach.  Unknown
        (already-reported) qids are ignored — the query no longer ranks."""
        rec = self._admitted.get(qid)
        if rec is not None:
            for j in rec.jobs:
                j.urgency = float(urgency)

    def _check_binding(self) -> None:
        """A scheduler is bound to one session *binding*: its store, layout,
        and SNI counts all name the assignment that existed at construction.
        ``GraphSession.repartition()``/``fold()`` rebind the session (NEW
        store, new pids/paddings), which would silently mix layouts —
        refuse loudly.  Streaming mutations/compactions are fine: they
        keep the store and the scheduler keeps serving its pinned
        generation view (generation-qualified cache keys isolate it from
        newer views sharing the same store)."""
        if self.session.store is not self.store:
            raise RuntimeError(
                "the session was rebound (repartition()/fold()?) after "
                "this scheduler was created; its pending state names the "
                "old layout — create a fresh scheduler via "
                "GraphSession.scheduler()/submit_many()")
        if self._closed:
            raise RuntimeError("this scheduler was close()d — its "
                               "generation pin is gone; create a fresh one")

    def close(self) -> None:
        """Release the scheduler's generation pin (idempotent).  After the
        last pin on a superseded generation goes, the next compaction's GC
        may reclaim that generation's unreferenced files."""
        if not self._closed:
            self._closed = True
            if self.view is not None:
                self.view.release()

    @property
    def n_pending(self) -> int:
        return sum(1 for j in self._jobs if not j.retired)

    def partition_waiters(self) -> Dict[int, List[int]]:
        """The partition → waiting-qids index (observability/tests): which
        pending queries each partition would advance if loaded now."""
        return {p: sorted({j.qid for j in js})
                for p, js in self._waiters().items()}

    # -- the shared-load loop ----------------------------------------------

    def run(self, max_rounds: Optional[int] = None) -> ScheduleReport:
        """Serve every pending job to retirement and return the round's
        report.  Re-entrant: queries admitted after a ``run()`` are served
        (and reported) by the next one.  ``max_rounds`` bounds this call:
        at most that many partition-load rounds on the shared paths (whole
        queries on the sequential fallback), leaving the rest pending —
        the SLO front end pumps with ``max_rounds=1`` so admission and
        urgency updates interleave with serving; None (default) drains
        everything, exactly the pre-existing batch semantics."""
        self._check_binding()
        t0 = time.time()
        stats0 = self.store.stats.copy()
        loads0, batches0 = len(self.loads), len(self.batch_sizes)
        engine = self.session.engine
        shared = isinstance(engine, (OPATEngine, TraditionalMPEngine))
        # every load this call issues resolves against the scheduler's
        # pinned generation, whatever the session's live view is by now
        ctx = (self.store.viewing(self.view) if self.view is not None
               else contextlib.nullcontext())
        with ctx:
            if isinstance(engine, OPATEngine):
                self._run_shared(t0, max_rounds)
            elif isinstance(engine, TraditionalMPEngine):
                self._run_shared_tmp(t0, max_rounds)
            else:
                self._run_sequential(t0, max_rounds)
        report = ScheduleReport(
            results=self._collect_results(t0),
            loads=self.loads[loads0:],
            batch_sizes=self.batch_sizes[batches0:],
            load_stats=self.store.stats - stats0,
            wall_s=time.time() - t0,
            shared=shared)
        return report

    def _run_shared(self, t0: float,
                    max_rounds: Optional[int] = None) -> None:
        engine: OPATEngine = self.session.engine
        beval = engine.batched_evaluator()
        rng = np.random.default_rng(self.seed)
        # per job, as OPAT's per-query guard: ``self.loads`` spans the
        # scheduler's life and ``self._jobs`` only the pending set, so a
        # long-lived streaming scheduler must not weigh one by the other
        limit = 64 * self.pg.k
        rounds = 0
        while True:
            if max_rounds is not None and rounds >= max_rounds:
                break
            waiters, ranked = self._rank(rng)
            if not waiters:
                break
            if any(j.state.iterations >= limit
                   for js in waiters.values() for j in js):
                raise RuntimeError("a query exceeded max partition loads "
                                   f"({limit}); likely a routing bug")
            pid = int(ranked[0])
            batch = waiters[pid]
            with self.tracer.span("scheduler.round", pid=pid, round=rounds,
                                  batch=len(batch),
                                  qids=sorted({j.qid for j in batch})):
                ev0 = self.store.stats.copy()
                entry = self.store.get(pid)
                # the attributable event is the load itself (cold/warm +
                # prefetch hit); snapshot it BEFORE staging the runner-up so
                # a query retiring this round is never charged prefetch
                # traffic for a partition it takes no part in
                event = self.store.stats - ev0
                # double-buffered streaming: pin pid, then stage the
                # WORKLOAD's runner-up while pid evaluates — the shared
                # generalization of OPAT's per-query prefetch; the pin keeps
                # the overlapped H2D copy from evicting the entry the batched
                # evaluator is reading
                with self.store.pinned(pid):
                    if self.prefetch and len(ranked) > 1:
                        self.store.prefetch(int(ranked[1]))
                    self._eval_batch(beval, entry, pid, batch)
            self.loads.append(pid)
            self.batch_sizes.append(len(batch))
            # round-scoped attribution: the event lands once in each
            # participating QUERY's view, and once per participating JOB
            # (a disjunct's RunStats) — never in any bystander's
            for qid in {j.qid for j in batch}:
                rec = self._admitted[qid]
                rec.load_stats = rec.load_stats + event
            self._touched.add(pid)
            in_batch = {id(j) for j in batch}
            for j in batch:
                j.load_stats = j.load_stats + event
                j.state.loads.append(pid)
                j.state.iterations += 1
            # fairness aging: a pending job the chosen partition did NOT
            # advance has waited one more round (core/heuristics.py turns
            # rounds_waiting × SNI into a score bonus when fairness_gamma
            # is set, bounding how long a no-overlap query can starve)
            for j in self._jobs:
                if not j.retired:
                    j.rounds_waiting = 0 if id(j) in in_batch \
                        else j.rounds_waiting + 1
            rounds += 1

    def _rank(self, rng: np.random.Generator):
        """Retire finished jobs, index the waiters by partition and rank
        the candidates (one ``heuristics.rank`` span); returns the
        waiters and the ranked pids (empty when nothing is eligible)."""
        with self.tracer.span("heuristics.rank") as rsp:
            self._retire()
            waiters = self._waiters()
            rsp.set(n_eligible=len(waiters))
            if not waiters:
                return waiters, []
            # score each candidate by every waiter's (SNI, completion
            # rate); a job's rates are partition-indexed but identical
            # across candidates, so compute them once per job per round —
            # and only when the ranking reads them (as in the per-query
            # OPAT loop, which gates rates on MAX-YIELD the same way)
            rates = {}
            if self.heuristic == MAX_YIELD_SHARED:
                for js in waiters.values():
                    for j in js:
                        if id(j) not in rates:
                            rates[id(j)] = j.state.completion_rates()
            scored = {p: [(j.state.sni_count(p),
                           rates[id(j)][p] if rates else 0.0,
                           j.rounds_waiting,
                           j.urgency)
                          for j in js]
                      for p, js in waiters.items()}
            ranked = rank_partitions_shared(
                self.heuristic, scored, rng,
                fairness_gamma=self.fairness_gamma, tracer=self.tracer)
        return waiters, ranked

    def _run_shared_tmp(self, t0: float,
                        max_rounds: Optional[int] = None) -> None:
        """TraditionalMP shared batching: each round ranks partitions with
        the same workload-level heuristic, takes the TOP-P set (the
        engine's p processors), and ships ONE stacked bundle through the
        store carrying EVERY waiting query's inputs — the double-vmapped
        ``TraditionalMPEngine.shared_evaluator()`` then evaluates B plans ×
        p partitions in one compiled call.  Per-job SNI/IMA/FAA bookkeeping
        is the sequential TMP loop's, verbatim (tail-kept cap chunking, one
        chunk per iteration of the same partition), so exhaustive answers
        stay bit-identical to per-query ``submit``."""
        engine: TraditionalMPEngine = self.session.engine
        seval = engine.shared_evaluator()
        cfg = self.session.config
        k = self.pg.k
        p = engine.p
        rng = np.random.default_rng(self.seed)
        # per job, as OPAT's per-query guard: ``self.loads`` spans the
        # scheduler's life and ``self._jobs`` only the pending set, so a
        # long-lived streaming scheduler must not weigh one by the other
        limit = 64 * self.pg.k
        rounds = 0
        while True:
            if max_rounds is not None and rounds >= max_rounds:
                break
            waiters, ranked = self._rank(rng)
            if not waiters:
                break
            if any(j.state.iterations >= limit
                   for js in waiters.values() for j in js):
                raise RuntimeError("a query exceeded max partition loads "
                                   f"({limit}); likely a routing bug")
            # canonical sorted order + first-pid padding, exactly as the
            # per-query TMP loop: the stacked store key is then
            # permutation-invariant across rounds (padding lanes are
            # no-ops — idle processors — and sort in with the rest)
            chosen = sorted(int(q) for q in ranked[:p])
            lanes = sorted([(pid, True) for pid in chosen]
                           + [(chosen[0], False)] * (p - len(chosen)))
            exec_set = [t[0] for t in lanes]
            is_real = [t[1] for t in lanes]
            waiter_ids = {pid: {id(j) for j in js}
                          for pid, js in waiters.items()}
            # the round's batch: every job waiting on ANY chosen partition,
            # in stable admit order (deduped — a job waiting on two chosen
            # partitions gets ONE lane row with both its IMAs drained)
            in_round = {id(j) for pid in chosen for j in waiters[pid]}
            batch = [j for j in self._jobs
                     if not j.retired and id(j) in in_round]
            B = len(batch)
            Bpad = batch_bucket(B)
            lanes_of: List[List[int]] = []   # per job: real lanes it rode
            with self.tracer.span("eval.inputs") as isp:
                plans = [j.plan_arrays for j in batch]
                stacked = PlanArrays.stack(plans + [plans[0]] * (Bpad - B))
                n_steps = np.asarray([j.plan.n_steps for j in batch]
                                     + [1] * (Bpad - B), np.int32)
                in_rows = np.full((Bpad, p, cfg.cap, cfg.q_pad), -1, np.int32)
                in_step = np.zeros((Bpad, p, cfg.cap), np.int32)
                in_valid = np.zeros((Bpad, p, cfg.cap), bool)
                seeds = np.zeros((Bpad, p), bool)
                for b, j in enumerate(batch):
                    mine: List[int] = []
                    for i, pid in enumerate(exec_set):
                        if not is_real[i] or id(j) not in waiter_ids[pid]:
                            continue
                        mine.append(i)
                        bb = j.state.ima[pid]
                        j.state.ima[pid] = BindingBatch.empty(cfg.q_pad)
                        if bb.n > cfg.cap:
                            # tail kept for a later round of the same
                            # partition
                            j.state.ima[pid] = BindingBatch(
                                rows=bb.rows[cfg.cap:],
                                step=bb.step[cfg.cap:])
                            bb = BindingBatch(rows=bb.rows[: cfg.cap],
                                              step=bb.step[: cfg.cap])
                        if bb.n:
                            in_rows[b, i, : bb.n] = bb.rows
                            in_step[b, i, : bb.n] = bb.step
                            in_valid[b, i, : bb.n] = True
                        seeds[b, i] = bool(j.state.fresh_pending[pid])
                        j.state.fresh_pending[pid] = False
                    lanes_of.append(mine)
                plan_args = (stacked, n_steps, in_rows, in_step, in_valid,
                             seeds)
                n_rows = int(in_valid.sum())
                isp.set(rows=n_rows)
                if self.tracer.enabled:
                    isp.set(bytes_h2d=host_nbytes(plan_args))
            ev0 = self.store.stats.copy()
            with self.tracer.span("scheduler.round", pids=chosen,
                                  round=rounds, batch=B,
                                  qids=sorted({j.qid for j in batch})):
                entry = self.store.get_stacked(tuple(exec_set))
                event = self.store.stats - ev0
                res, c = traced_eval(
                    self, ("scheduler.tmp", Bpad), seval,
                    (entry.part, entry.g2l, self.store.owner) + plan_args,
                    pids=chosen, batch=B, bucket=Bpad, rows=n_rows)
            for b, j in enumerate(batch):
                for i in lanes_of[b]:
                    if bool(c.overflow[b, i]):
                        raise RuntimeError(
                            f"evaluator buffer overflow on partition "
                            f"{exec_set[i]} (query {j.plan.query.name!r} in "
                            f"a batch of {B}); raise EngineConfig.cap "
                            f"(currently {cfg.cap})")
            with self.tracer.span("eval.absorb") as asp:
                bufs = read_rows(res, c)
                for b, j in enumerate(batch):
                    for i in lanes_of[b]:
                        absorb_eval_outputs(j.state, exec_set[i], k, bufs,
                                            int(c.comp_n[b, i]),
                                            int(c.out_n[b, i]), lane=(b, i))
                        j.eval_iters += int(c.n_iters[b, i])
                        j.rows_expanded += int(c.n_expanded[b, i])
                if self.tracer.enabled:
                    asp.set(bytes_d2h=host_nbytes(bufs))
            # attribution: the stacked bundle is ONE store event; each
            # chosen pid counts one workload load, and its batch size is
            # the number of jobs its lane advanced
            self.loads.extend(chosen)
            for pid in chosen:
                self.batch_sizes.append(
                    sum(1 for b, j in enumerate(batch)
                        if any(exec_set[i] == pid for i in lanes_of[b])))
            for qid in {j.qid for j in batch}:
                rec = self._admitted[qid]
                rec.load_stats = rec.load_stats + event
            self._touched.update(chosen)
            in_batch = {id(j) for j in batch}
            for b, j in enumerate(batch):
                j.load_stats = j.load_stats + event
                j.state.loads.extend(exec_set[i] for i in lanes_of[b])
                j.state.iterations += 1
            for j in self._jobs:
                if not j.retired:
                    j.rounds_waiting = 0 if id(j) in in_batch \
                        else j.rounds_waiting + 1
            rounds += 1

    def _eval_batch(self, beval, entry, pid: int, batch: List[_Job]) -> None:
        """One compiled call advances every waiting job's plan against the
        loaded partition (chunked when an IMA exceeds the row capacity;
        later chunks are inert for jobs already drained)."""
        cfg = self.session.config
        k = self.pg.k
        B = len(batch)
        Bpad = batch_bucket(B)
        imas: List[BindingBatch] = []
        seed_flags: List[bool] = []
        for j in batch:
            imas.append(j.state.ima[pid])
            j.state.ima[pid] = BindingBatch.empty(cfg.q_pad)
            seed_flags.append(bool(j.state.fresh_pending[pid]))
            j.state.fresh_pending[pid] = False
        n_chunks = max(1, max(-(-bb.n // cfg.cap) for bb in imas))
        for ci in range(n_chunks):
            with self.tracer.span("eval.inputs") as isp:
                if ci == 0:
                    plans = [j.plan_arrays for j in batch]
                    stacked = PlanArrays.stack(plans
                                               + [plans[0]] * (Bpad - B))
                    n_steps = np.asarray([j.plan.n_steps for j in batch]
                                         + [1] * (Bpad - B), np.int32)
                in_rows = np.full((Bpad, cfg.cap, cfg.q_pad), -1, np.int32)
                in_step = np.zeros((Bpad, cfg.cap), np.int32)
                in_valid = np.zeros((Bpad, cfg.cap), bool)
                n_rows = 0
                for b, bb in enumerate(imas):
                    lo = ci * cfg.cap
                    n = min(bb.n - lo, cfg.cap)
                    if n > 0:
                        in_rows[b, :n] = bb.rows[lo:lo + n]
                        in_step[b, :n] = bb.step[lo:lo + n]
                        in_valid[b, :n] = True
                        n_rows += n
                sf = np.asarray([s and ci == 0 for s in seed_flags]
                                + [False] * (Bpad - B))
                args = (entry.part, entry.g2l, self.store.owner, stacked,
                        n_steps, in_rows, in_step, in_valid, sf)
                isp.set(rows=n_rows)
                if self.tracer.enabled:
                    isp.set(bytes_h2d=host_nbytes(args))
            res, c = traced_eval(self, ("scheduler.opat", Bpad), beval, args,
                                 pid=pid, batch=B, bucket=Bpad, rows=n_rows)
            for b, j in enumerate(batch):
                if bool(c.overflow[b]):
                    raise RuntimeError(
                        f"evaluator buffer overflow on partition {pid} "
                        f"(query {j.plan.query.name!r} in a batch of {B}); "
                        f"raise EngineConfig.cap (currently {cfg.cap})")
            with self.tracer.span("eval.absorb") as asp:
                bufs = read_rows(res, c)
                for b, j in enumerate(batch):
                    absorb_eval_outputs(j.state, pid, k, bufs,
                                        int(c.comp_n[b]), int(c.out_n[b]),
                                        lane=(b,))
                    j.eval_iters += int(c.n_iters[b])
                    j.rows_expanded += int(c.n_expanded[b])
                if self.tracer.enabled:
                    asp.set(bytes_d2h=host_nbytes(bufs))

    def _run_sequential(self, t0: float,
                        max_rounds: Optional[int] = None) -> None:
        """Engines with no host partition loop to share (MapReduceMP) run a
        whole query as one (or few) compiled program(s), so the scheduler
        drains their jobs one query at a time — answers, budgets, and
        per-call LoadStats deltas identical to sequential ``submit``.
        ``max_rounds`` bounds the number of QUERIES served this call."""
        session = self.session
        served = 0
        # the engine reads its pg attribute at call time; hold it to the
        # scheduler's pinned binding for the drain so a mutation landing
        # mid-run can't mix generations into the ranking
        engine = session.engine
        prev_pg = engine.pg
        engine.pg = self.pg
        try:
            for rec in self._admitted.values():
                if rec.finished_at is not None:
                    continue
                if max_rounds is not None and served >= max_rounds:
                    break
                served += 1
                ev0 = self.store.stats.copy()
                for j in rec.jobs:
                    jv0 = self.store.stats.copy()
                    rep = engine.run_request(RunRequest(
                        plan=j.plan, heuristic=session.heuristic,
                        max_answers=j.max_answers, seed=self.seed))
                    j.retired = True
                    j.report = rep  # engine-built report reused verbatim
                    j.load_stats = j.load_stats + (self.store.stats - jv0)
                    self.loads.extend(rep.stats.loads)
                    self.batch_sizes.extend([1] * len(rep.stats.loads))
                rec.load_stats = rec.load_stats + (self.store.stats - ev0)
                rec.finished_at = time.time()
                rec.finished_perf = time.perf_counter()
        finally:
            engine.pg = prev_pg

    # -- retirement and the waiter index -----------------------------------

    def _waiters(self) -> Dict[int, List[_Job]]:
        w: Dict[int, List[_Job]] = {}
        for j in self._jobs:
            if j.retired:
                continue
            for p in j.state.eligible():
                w.setdefault(int(p), []).append(j)
        return w

    def _retire(self) -> None:
        """Retire jobs whose budget is met or whose SNI/IMA are exhausted,
        stamp queries whose last job retired, and (optionally) release
        store entries no pending job can currently use."""
        now = time.time()
        newly: List[_Job] = []
        for j in self._jobs:
            if j.retired:
                continue
            if j.state.budget_met(j.max_answers) or not j.state.eligible():
                j.retired = True
                newly.append(j)
        for rec in self._admitted.values():
            if rec.finished_at is None and all(j.retired for j in rec.jobs):
                rec.finished_at = now
                rec.finished_perf = time.perf_counter()
        if newly and self.release_retired:
            # any partition the workload loaded that no pending job can
            # currently use is releasable — cumulative, so an early
            # retiree's partitions go as soon as the last query needing
            # them retires (prefetched-but-never-loaded entries are left
            # to the LRU)
            needed: Set[int] = set()
            for j in self._jobs:
                if not j.retired:
                    needed.update(int(p) for p in j.state.eligible())
            for pid in sorted(self._touched - needed):
                if self.store.contains(pid):
                    self.store.release(pid)

    # -- results -----------------------------------------------------------

    def _collect_results(self, t0: float) -> List[QueryResult]:
        """Build the finished queries' results (admit order) and prune
        their state — a streaming scheduler's footprint stays proportional
        to the pending set, not to its serving history."""
        gen = int(self.view.generation) if self.view is not None else None
        results: List[QueryResult] = []
        done: List[int] = []
        for rec in self._admitted.values():
            if rec.finished_at is None:
                continue
            done.append(rec.qid)
            reports: List[RunReport] = []
            answers: Optional[np.ndarray] = None
            for j in rec.jobs:
                rep = j.report
                if rep is None:          # shared path: build from job state
                    a = truncate_answers(j.state.unique_answers(),
                                         j.max_answers)
                    delta = j.load_stats
                    rep = RunReport(
                        answers=a,
                        stats=RunStats(
                            query=j.plan.query.name, scheme=self.pg.scheme,
                            heuristic=self.heuristic,
                            loads=list(j.state.loads),
                            l_ideal=l_ideal_for_plan(self.pg, j.plan),
                            n_answers=int(a.shape[0]),
                            iterations=j.state.iterations,
                            answers_requested=j.max_answers,
                            eval_iters=j.eval_iters,
                            rows_expanded=j.rows_expanded,
                            **residency(delta)),
                        engine=self.session.engine_name,
                        extra={"state": j.state})
                rep.stats.generation = gen
                reports.append(rep)
                a = rep.answers
                answers = a if answers is None else np.unique(
                    np.concatenate([answers, a]), axis=0)
            results.append(QueryResult(
                name=rec.name, answers=answers, reports=reports,
                latency_s=max(0.0, rec.finished_at - t0),
                load_stats=rec.load_stats, qid=rec.qid, generation=gen))
            if self.tracer.enabled and rec.finished_perf is not None:
                # one root span per retired query, admission → retirement
                # (externally-timed: the lifetime crosses many rounds, so
                # no single call frame could carry it)
                self.tracer.add_span(
                    "query", rec.admitted_perf, rec.finished_perf,
                    qid=rec.qid, query=rec.name, generation=gen,
                    n_answers=int(answers.shape[0]),
                    n_loads=sum(len(r.stats.loads) for r in reports))
        for qid in done:
            del self._admitted[qid]
        self._jobs = [j for j in self._jobs if not j.retired]
        return results
