"""OPAT — One Partition At a Time query evaluation (paper Sec. 5-7).

The host orchestrator mirrors the paper's PGQP loop exactly:

  1. build the initial SNI from start-label counts per partition,
  2. choose the next partition with the configured heuristic,
  3. run the jitted within-partition evaluator (= "load" the partition),
  4. route outgoing continuations into destination IMA files, append
     completed answers to the FAA, update the SNI,
  5. repeat until no partition is eligible.

Partition *loads* (including re-loads of the same partition, Fig. 4c) are
recorded for the load-ratio metrics.

Partition residency goes through a ``PartitionStore`` (core/store.py): a
load is *cold* when the store must ``device_put`` the partition and *warm*
when device buffers are reused — a re-load of an already-resident partition
(Fig. 4c) costs bookkeeping, not a transfer.  While one partition
evaluates, the engine prefetches the heuristic's runner-up so the next
pick's transfer overlaps the current evaluation (ROADMAP item #1);
``RunStats.cold_loads`` / ``warm_loads`` / ``prefetch_hits`` record the
split.
"""
from __future__ import annotations

import dataclasses
from typing import Any, Dict, List, Optional, Tuple

import jax
import numpy as np

from .engine import (EngineConfig, host_nbytes, jit_evaluator,
                     make_partition_evaluator, read_rows, traced_eval)
from .graph import PartitionedGraph
from .heuristics import MAX_YIELD, rank_partitions
from .metrics import RunStats, l_ideal_for_plan, residency
from .plan import Plan, PlanArrays
from .runner import RunReport, RunRequest, truncate_answers
from .state import BindingBatch, QueryState
from .store import PartitionStore, StoreEntry


@dataclasses.dataclass
class OPATResult:
    answers: np.ndarray          # [n, q_pad] global-vertex-id rows
    stats: RunStats
    state: QueryState


def absorb_eval_outputs(st: QueryState, pid: int, k: int,
                        bufs: Dict[str, np.ndarray], comp_n: int, out_n: int,
                        lane: Tuple[int, ...] = ()) -> None:
    """Route one evaluator lane's outputs into a query's bookkeeping state:
    completed rows append to the FAA, outgoing continuations land in their
    destination partitions' IMA files (deduped, paper Fig. 4c), and the
    partition's yield counters update.  ``bufs`` holds the host copies of
    the call's buffers (``engine.read_rows``) and ``lane`` indexes one
    lane of a vmapped call.  Shared by OPAT, TraditionalMP and the
    scheduler's batched evaluation (core/scheduler.py), so the paper's
    bookkeeping cannot diverge between the paths."""
    if comp_n:
        st.add_answers(bufs["comp_rows"][lane][:comp_n])
    st.observe_yield(pid, comp_n, out_n)
    if out_n:
        rows = bufs["out_rows"][lane][:out_n]
        step = bufs["out_step"][lane][:out_n]
        dest = bufs["out_dest"][lane][:out_n]
        for q in range(k):
            sel = dest == q
            if sel.any():
                st.ima[q] = st.ima[q].concat(
                    BindingBatch(rows=rows[sel], step=step[sel])).dedup()


class OPATEngine:
    """Reusable engine bound to one partitioned graph (one compile).

    ``store`` defaults to a private unbounded ``PartitionStore``; a
    ``GraphSession`` passes its own so residency (and its hit/miss
    accounting) is shared across queries.  ``prefetch`` stages the
    heuristic's runner-up partition while the chosen one evaluates.
    """

    def __init__(self, pg: PartitionedGraph, cfg: Optional[EngineConfig] = None,
                 store: Optional[PartitionStore] = None,
                 prefetch: bool = True,
                 tracer: Optional[Any] = None,
                 profiler: Optional[Any] = None):
        self.pg = pg
        self.cfg = cfg or EngineConfig()
        assert pg.node_pad > 0, "build_partitions(uniform_pad=True) required"
        self._eval = make_partition_evaluator(pg.node_pad, pg.ell_width,
                                              self.cfg)
        self._beval = None
        self.store = store if store is not None else PartitionStore(pg)
        self.prefetch = prefetch
        from ..obs.trace import NULL_TRACER
        self.tracer = tracer if tracer is not None else NULL_TRACER
        from ..obs.profile import NULL_PROFILER
        self.profiler = profiler if profiler is not None else NULL_PROFILER

    def batched_evaluator(self):
        """The *plan-batched* partition evaluator: ``vmap`` of the compiled
        evaluator over the query axis with the partition inputs broadcast
        — the mirror image of TraditionalMP's partition-vmapped call.  One
        loaded partition advances B pending queries' plans in a single
        compiled call: inputs gain a leading [B] axis (stacked
        ``PlanArrays``, per-query n_steps / IMA rows / seed flags) while
        ``part``/``g2l``/``owner`` stay un-batched.  The scheduler
        (core/scheduler.py) pads B up to a bucket size so the jit cache
        holds one trace per bucket, reused across rounds.  Built lazily:
        per-query serving never pays for it."""
        if self._beval is None:
            self._beval = jit_evaluator(jax.vmap(
                self._eval, in_axes=(None, None, None, 0, 0, 0, 0, 0, 0)))
        return self._beval

    def _run_partition(self, entry: StoreEntry, plan_arrays: PlanArrays,
                       n_steps: int, batch: BindingBatch, seed_fresh: bool,
                       st: QueryState) -> Tuple[int, int]:
        """Evaluate ``batch`` on the loaded partition (in ``cap``-row
        chunks); returns the evaluator's (trips, rows expanded)."""
        cfg = self.cfg
        pid = int(entry.key)
        iters = expanded = 0
        chunks: List[BindingBatch] = []
        if batch.n == 0:
            chunks.append(BindingBatch.empty(cfg.q_pad))
        else:
            for i in range(0, batch.n, cfg.cap):
                chunks.append(BindingBatch(rows=batch.rows[i : i + cfg.cap],
                                           step=batch.step[i : i + cfg.cap]))
        for ci, chunk in enumerate(chunks):
            with self.tracer.span("eval.inputs", rows=int(chunk.n)) as isp:
                in_rows = np.full((cfg.cap, cfg.q_pad), -1, dtype=np.int32)
                in_step = np.zeros(cfg.cap, dtype=np.int32)
                in_valid = np.zeros(cfg.cap, dtype=bool)
                if chunk.n:
                    in_rows[: chunk.n] = chunk.rows
                    in_step[: chunk.n] = chunk.step
                    in_valid[: chunk.n] = True
                args = (entry.part, entry.g2l, self.store.owner, plan_arrays,
                        np.int32(n_steps), in_rows, in_step, in_valid,
                        np.bool_(seed_fresh and ci == 0))
                if self.tracer.enabled:
                    isp.set(bytes_h2d=host_nbytes(args))
            res, c = traced_eval(self, ("opat", "eval"), self._eval, args,
                                 pid=pid, engine="opat", rows=int(chunk.n))
            iters += int(c.n_iters)
            expanded += int(c.n_expanded)
            if c.overflow:
                raise RuntimeError(
                    f"evaluator buffer overflow on partition {pid}; raise "
                    f"EngineConfig.cap (currently {cfg.cap})")
            with self.tracer.span("eval.absorb") as asp:
                bufs = read_rows(res, c)
                absorb_eval_outputs(st, pid, self.pg.k, bufs, int(c.comp_n),
                                    int(c.out_n))
                if self.tracer.enabled:
                    asp.set(bytes_d2h=host_nbytes(bufs))
        return iters, expanded

    def run(self, plan: Plan, heuristic: str, seed: int = 0,
            max_loads: Optional[int] = None,
            max_answers: Optional[int] = None) -> OPATResult:
        cfg = self.cfg
        assert plan.n_slots <= cfg.q_pad and plan.n_steps <= cfg.s_pad
        rng = np.random.default_rng(seed)
        plan_arrays = PlanArrays.from_plan(plan, pad_steps=cfg.s_pad)
        counts = self.pg.start_label_counts(plan.start_label,
                                            plan.start_value_op,
                                            plan.start_value)
        st = QueryState.initial(self.pg.k, cfg.q_pad, counts,
                                track_answer_keys=max_answers is not None)
        limit = max_loads if max_loads is not None else 64 * self.pg.k
        load0 = self.store.stats.copy()
        iters = expanded = 0

        while not st.budget_met(max_answers):
            with self.tracer.span("heuristics.rank") as rsp:
                eligible = st.eligible()
                rsp.set(n_eligible=len(eligible))
                if eligible:
                    sni = {p: st.sni_count(p) for p in eligible}
                    rates = (st.completion_rates() if heuristic == MAX_YIELD
                             else None)
                    ranked = rank_partitions(heuristic, eligible, sni, rng,
                                             rates, tracer=self.tracer)
            if not eligible:
                break
            if len(st.loads) >= limit:
                raise RuntimeError("OPAT exceeded max partition loads "
                                   f"({limit}); likely a routing bug")
            pid = ranked[0]
            with self.tracer.span("opat.round", pid=pid,
                                  iteration=st.iterations,
                                  pending_rows=int(st.ima[pid].n)):
                st.loads.append(pid)
                st.iterations += 1
                batch = st.ima[pid]
                st.ima[pid] = BindingBatch.empty(cfg.q_pad)
                seed_fresh = bool(st.fresh_pending[pid])
                st.fresh_pending[pid] = False
                entry = self.store.get(pid)
                # double-buffered streaming: pin pid, then stage the
                # heuristic's runner-up while pid evaluates — device_put
                # dispatch returns immediately, so the H2D copy overlaps the
                # evaluator work (ROADMAP item #1); the pin guarantees the
                # in-flight staging can evict anything BUT the partition the
                # running kernel reads (store may exceed capacity by one slot)
                with self.store.pinned(pid):
                    if self.prefetch and len(ranked) > 1:
                        self.store.prefetch(ranked[1])
                    it, nx = self._run_partition(entry, plan_arrays,
                                                 plan.n_steps, batch,
                                                 seed_fresh, st)
                iters += it
                expanded += nx

        answers = truncate_answers(st.unique_answers(), max_answers)
        delta = self.store.stats - load0
        stats = RunStats(query=plan.query.name, scheme=self.pg.scheme,
                         heuristic=heuristic,
                         loads=list(st.loads),
                         l_ideal=l_ideal_for_plan(self.pg, plan),
                         n_answers=int(answers.shape[0]),
                         iterations=st.iterations,
                         answers_requested=max_answers,
                         eval_iters=iters, rows_expanded=expanded,
                         **residency(delta))
        return OPATResult(answers=answers, stats=stats, state=st)

    def run_request(self, req: RunRequest) -> RunReport:
        """The shared ``QueryRunner`` protocol (see core/runner.py)."""
        res = self.run(req.plan, req.heuristic, seed=req.seed,
                       max_answers=req.max_answers)
        return RunReport(answers=res.answers, stats=res.stats, engine="opat",
                         extra={"state": res.state})
