"""TraditionalMP — parallel partition processing with p processors
(paper Sec. 8, Algorithm 1).

Identical bookkeeping to OPAT; the difference is the *set* of partitions
chosen per iteration (top-p under the heuristic) and their parallel
execution.  On real hardware each chosen partition maps to one device; here
the chosen partitions are evaluated with ``jax.vmap`` over stacked partition
arrays — the same compiled program OPAT uses, batched — which is exactly the
semantics of p identical processors executing PGQP independently
(Algorithm 1 lines 6-8).  IMA merging order does not matter (line 9), so the
host merge loop is order-insensitive.
"""
from __future__ import annotations

import dataclasses
from typing import List, Optional

import jax
import numpy as np

from .engine import (EngineConfig, host_nbytes, jit_evaluator,
                     make_partition_evaluator, read_rows, traced_eval)
from .graph import PartitionedGraph
from .heuristics import MAX_YIELD, choose_top_p
from .metrics import RunStats, l_ideal_for_plan, residency
from .opat import absorb_eval_outputs
from .plan import Plan, PlanArrays
from .runner import RunReport, RunRequest, truncate_answers
from .state import BindingBatch, QueryState
from .store import PartitionStore


@dataclasses.dataclass
class TraditionalMPResult:
    answers: np.ndarray
    stats: RunStats
    state: QueryState
    partitions_per_iteration: List[List[int]]


class TraditionalMPEngine:
    """``store`` defaults to a private unbounded ``PartitionStore``; its
    load unit is the *stacked* top-p bundle one iteration ships to the p
    processors, so a recurring top-p set is a warm load."""

    def __init__(self, pg: PartitionedGraph, n_processors: int,
                 cfg: Optional[EngineConfig] = None,
                 store: Optional[PartitionStore] = None,
                 tracer=None,
                 profiler=None):
        assert n_processors >= 1
        self.pg = pg
        self.p = n_processors
        self.cfg = cfg or EngineConfig()
        self._eval = make_partition_evaluator(pg.node_pad, pg.ell_width,
                                              self.cfg)
        # vmapped over (partition arrays, g2l row, inputs); plan broadcast
        self._veval = jit_evaluator(jax.vmap(
            self._eval, in_axes=(0, 0, None, None, None, 0, 0, 0, 0)))
        self._seval = None       # lazy: the queries x partitions double-vmap
        self.store = store if store is not None else PartitionStore(pg)
        from ..obs.trace import NULL_TRACER
        self.tracer = tracer if tracer is not None else NULL_TRACER
        from ..obs.profile import NULL_PROFILER
        self.profiler = profiler if profiler is not None else NULL_PROFILER

    def shared_evaluator(self):
        """The *stacked top-p, multi-query* evaluator: ``vmap`` over the
        query axis wrapped around this engine's per-query partition-vmap —
        one compiled call evaluates B stacked plans against the same p
        stacked partitions (inputs [B, p, ...]; partition arrays and the
        owner map broadcast across queries, each query keeps its own plan,
        n_steps, per-lane IMA rows, and seed flags).  This is how the
        ``QueryScheduler`` shares one top-p load across every waiting
        query (core/scheduler.py): the paper's p processors each advance
        the whole workload, not one query.  Built lazily — per-query
        serving never pays the extra trace."""
        if self._seval is None:
            self._seval = jit_evaluator(jax.vmap(
                jax.vmap(self._eval,
                         in_axes=(0, 0, None, None, None, 0, 0, 0, 0)),
                in_axes=(None, None, None, 0, 0, 0, 0, 0, 0)))
        return self._seval

    def run(self, plan: Plan, heuristic: str, seed: int = 0,
            max_iterations: Optional[int] = None,
            max_answers: Optional[int] = None) -> TraditionalMPResult:
        cfg = self.cfg
        assert plan.n_slots <= cfg.q_pad and plan.n_steps <= cfg.s_pad
        rng = np.random.default_rng(seed)
        plan_arrays = PlanArrays.from_plan(plan, pad_steps=cfg.s_pad)
        counts = self.pg.start_label_counts(plan.start_label,
                                            plan.start_value_op,
                                            plan.start_value)
        st = QueryState.initial(self.pg.k, cfg.q_pad, counts,
                                track_answer_keys=max_answers is not None)
        limit = max_iterations if max_iterations is not None else 64 * self.pg.k
        per_iter: List[List[int]] = []
        load0 = self.store.stats.copy()
        iters = expanded = 0

        # budget check after each top-p merge (and before the first load:
        # a K=0 request does no work)
        while not st.budget_met(max_answers):
            with self.tracer.span("heuristics.rank") as rsp:
                eligible = st.eligible()
                rsp.set(n_eligible=len(eligible))
                if eligible:
                    sni = {p: st.sni_count(p) for p in eligible}
                    rates = (st.completion_rates()
                             if heuristic == MAX_YIELD else None)
                    chosen = choose_top_p(heuristic, eligible, sni, self.p,
                                          rng, rates, tracer=self.tracer)
            if not eligible:
                break
            if st.iterations >= limit:
                raise RuntimeError("TraditionalMP exceeded max iterations")
            per_iter.append(list(chosen))
            st.iterations += 1
            # process the set in sorted order: which processor runs which
            # partition is arbitrary (Algorithm 1 lines 6-8), and a
            # canonical order — including the chosen[0] padding below —
            # makes the stacked store key permutation-invariant, so
            # heuristic tie-break order never forces a cold re-stage of
            # the same top-p set
            chosen = sorted(chosen)

            # pad the chosen set to exactly p so the vmapped evaluator keeps a
            # single compiled shape (padding entries are no-ops: empty input,
            # no fresh seeding) — idle processors in the paper's terms.
            exec_set = list(chosen) + [chosen[0]] * (self.p - len(chosen))
            batches: List[BindingBatch] = []
            seeds: List[bool] = []
            is_real: List[bool] = [True] * len(chosen) + [False] * (self.p - len(chosen))
            for pid in chosen:
                st.loads.append(pid)
                b = st.ima[pid]
                st.ima[pid] = BindingBatch.empty(cfg.q_pad)
                if b.n > cfg.cap:
                    # keep the tail for a later iteration of the same partition
                    st.ima[pid] = BindingBatch(rows=b.rows[cfg.cap:],
                                               step=b.step[cfg.cap:])
                    b = BindingBatch(rows=b.rows[: cfg.cap],
                                     step=b.step[: cfg.cap])
                batches.append(b)
                seeds.append(bool(st.fresh_pending[pid]))
                st.fresh_pending[pid] = False
            while len(batches) < self.p:
                batches.append(BindingBatch.empty(cfg.q_pad))
                seeds.append(False)

            # canonicalize lane order: IMA merging is order-insensitive
            # (Algorithm 1 line 9), so which vmap lane runs which partition
            # doesn't matter — sorting collapses permutations of the same
            # top-p set onto one stacked store entry (warm across
            # iterations regardless of heuristic tie-break order)
            lanes = sorted(zip(exec_set, batches, seeds, is_real),
                           key=lambda t: t[0])
            exec_set = [t[0] for t in lanes]
            batches = [t[1] for t in lanes]
            seeds = [t[2] for t in lanes]
            is_real = [t[3] for t in lanes]

            with self.tracer.span("eval.inputs",
                                  rows=sum(b.n for b in batches)) as isp:
                in_rows = np.full((self.p, cfg.cap, cfg.q_pad), -1,
                                  dtype=np.int32)
                in_step = np.zeros((self.p, cfg.cap), dtype=np.int32)
                in_valid = np.zeros((self.p, cfg.cap), dtype=bool)
                for i, b in enumerate(batches):
                    if b.n:
                        in_rows[i, : b.n] = b.rows
                        in_step[i, : b.n] = b.step
                        in_valid[i, : b.n] = True
                plan_args = (plan_arrays, np.int32(plan.n_steps), in_rows,
                             in_step, in_valid, np.asarray(seeds, dtype=bool))
                if self.tracer.enabled:
                    isp.set(bytes_h2d=host_nbytes(plan_args))

            with self.tracer.span("engine.iteration", engine="traditional",
                                  pids=list(map(int, exec_set)),
                                  iteration=st.iterations):
                entry = self.store.get_stacked(tuple(exec_set))
                res, c = traced_eval(
                    self, ("traditional", "veval"), self._veval,
                    (entry.part, entry.g2l, self.store.owner) + plan_args,
                    engine="traditional", pids=list(map(int, exec_set)),
                    rows=int(sum(b.n for b in batches)))
            iters += int(np.sum(c.n_iters))
            expanded += int(np.sum(c.n_expanded))
            if np.any(c.overflow):
                raise RuntimeError("evaluator buffer overflow; raise cap")
            with self.tracer.span("eval.absorb") as asp:
                bufs = read_rows(res, c)
                # merge IMA_i -> FAA/IMA (order-insensitive)
                for i in range(self.p):
                    if is_real[i]:
                        absorb_eval_outputs(st, exec_set[i], self.pg.k, bufs,
                                            int(c.comp_n[i]), int(c.out_n[i]),
                                            lane=(i,))
                if self.tracer.enabled:
                    asp.set(bytes_d2h=host_nbytes(bufs))

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
        return TraditionalMPResult(answers=answers, stats=stats,
                                   state=st, partitions_per_iteration=per_iter)

    def run_request(self, req: RunRequest) -> RunReport:
        """The shared ``QueryRunner`` protocol (see core/runner.py)."""
        res = self.run(req.plan, req.heuristic, seed=req.seed,
                       max_answers=req.max_answers)
        return RunReport(answers=res.answers, stats=res.stats,
                         engine="traditional",
                         extra={"state": res.state,
                                "partitions_per_iteration":
                                    res.partitions_per_iteration})
