"""MapReduceMP — map/reduce-style parallel query evaluation (paper Sec. 9),
adapted to TPU as a single SPMD ``shard_map`` program.

Mapping of the paper's roles onto JAX/TPU constructs (see DESIGN.md):

  mapper task (one per partition)   -> one device on the "part" mesh axis,
                                       holding its partition resident in HBM
  one-edge expansion per iteration  -> one dense [EB, W] tile-match step
                                       (NO within-partition closure; exactly
                                       the paper's mapper semantics)
  emit (dest partition id, value)   -> rows tagged with owner[frontier]
  shuffle on partition id           -> quota-based ragged jax.lax.all_to_all
  reducer (update SNI/IMA/FAA)      -> masked merge into device-local buffers
  jobtracker SNI merge / stop check -> jax.lax.psum of active counts inside
                                       a lax.while_loop

The whole query runs as ONE compiled program: iterations are a
``lax.while_loop`` whose condition is a global psum — there is no host
round-trip between iterations, which is the beyond-paper response-time win
(the paper's Hadoop incarnation pays a full job launch per iteration).
The same condition also carries the answer budget ("all or specified
number of answers", Sec. 1): a psum of per-mapper UNIQUE-answer counts
(dedup is done device-side; duplicates of an answer always converge on the
mapper owning its last frontier vertex, so per-mapper distinct counts add
up exactly) reaching ``max_answers`` exits the compiled program early
on-device — ``max_answers=K`` returns exactly K unique answers in one run.

Backpressure: rows whose destination quota is full simply stay in the local
buffer and are re-offered next iteration — deadlock-free because delivered
rows strictly drain and the while-loop only ends when nothing is active
anywhere.  Overflow of the *merge* buffer sets a flag the host checks.

When fewer mapper nodes than partitions are available (the paper's
m < required(i) case), ``m_limit`` gates expansion to the top-m partitions
per iteration, ranked on-device by the SN heuristics — including MAX-YIELD,
whose per-partition completed/spawned counters are carried through the
while_loop state and all_gather'd at ranking time.
"""
from __future__ import annotations

import dataclasses
from typing import Optional

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import AxisType, Mesh, NamedSharding, PartitionSpec as P

from .engine import (EngineConfig, EvalCounts, _expand_classify, host_nbytes,
                     traced_eval)
from .graph import PartitionedGraph, WILDCARD
from .heuristics import MAX_SN, MAX_YIELD, MIN_SN, RANDOM_SN
from .metrics import RunStats, l_ideal_for_plan, residency
from .plan import Plan, PlanArrays
from .runner import RunReport, RunRequest, truncate_answers
from .state import apply_value_op
from .store import PartitionStore

# "no budget" sentinel for the on-device answer-count stop test
_NO_BUDGET = np.int32(2**31 - 1)


@dataclasses.dataclass
class MapReduceMPResult:
    answers: np.ndarray
    stats: RunStats
    n_iterations: int
    # per-partition yield counters carried through the while_loop state —
    # the same completed/spawned observations the host-loop engines feed
    # into QueryState.observe_yield, surfaced for the session profile
    completed_from: np.ndarray = None   # [P] int64
    spawned_from: np.ndarray = None     # [P] int64


def make_part_mesh(k: int) -> Mesh:
    """The 1-D ``("part",)`` mesh MapReduceMP runs on: one device per
    partition."""
    return jax.make_mesh((k,), ("part",), axis_types=(AxisType.Auto,))


def _read_counts(out) -> EvalCounts:
    """The SPMD program's scalars in one ``jax.device_get``.  Its loop
    condition is a ``psum``, so every device runs the same trips.  The
    program does not count the rows it expands."""
    faa_n, overflow, iters = jax.device_get((out[1], out[2], out[3]))
    return EvalCounts(overflow=np.any(overflow), comp_n=faa_n,
                      out_n=np.zeros_like(faa_n), n_iters=np.max(iters),
                      n_expanded=None)


def _heuristic_id(h: str) -> int:
    # MAX-YIELD (id 3) ranks on SNI x completion rate; the completed/
    # spawned counters it needs are carried through the while_loop state
    # and all_gather'd at ranking time, so it runs fully on-device.
    return {MAX_SN: 0, MIN_SN: 1, RANDOM_SN: 2, MAX_YIELD: 3}[h]


class MapReduceMPEngine:
    """One partition per device along the ``part`` mesh axis (k == mesh size)."""

    def __init__(self, pg: PartitionedGraph, mesh: Mesh,
                 cfg: Optional[EngineConfig] = None,
                 quota_per_dest: Optional[int] = None,
                 m_limit: Optional[int] = None,
                 heuristic: str = MAX_SN,
                 max_outer_iters: int = 4096,
                 store: Optional[PartitionStore] = None,
                 tracer=None,
                 profiler=None):
        self.pg = pg
        self.mesh = mesh
        self.cfg = cfg or EngineConfig()
        self.P = int(np.prod([mesh.shape[a] for a in mesh.axis_names]))
        assert pg.k == self.P, (
            f"MapReduceMP requires one partition per device (k={pg.k}, "
            f"mesh={self.P}); repartition or resize the mesh")
        self.axis = mesh.axis_names[0]
        assert len(mesh.axis_names) == 1, "use a 1-D 'part' mesh"
        self.quota = quota_per_dest or max(8, self.cfg.cap // (4 * self.P))
        self.m_limit = m_limit if m_limit is not None else self.P
        self.heuristic = heuristic
        self.max_outer_iters = max_outer_iters
        self._compiled = None

        # all partitions ship at once, one per device along the mesh axis:
        # the job-start load in MapReduce terms.  The store stages the
        # stacked [P, ...] bundle sharded so device d holds partition d;
        # the first run is a cold load, later runs on the same store reuse
        # the device-resident shards (a warm load).
        self.store = store if store is not None else PartitionStore(pg)
        self._part_sharding = NamedSharding(mesh, P(mesh.axis_names[0]))
        from ..obs.trace import NULL_TRACER
        self.tracer = tracer if tracer is not None else NULL_TRACER
        from ..obs.profile import NULL_PROFILER
        self.profiler = profiler if profiler is not None else NULL_PROFILER

    # -- the SPMD program ----------------------------------------------------

    def _build(self, plan_pad_steps: int):
        cfg = self.cfg
        Q, S = cfg.q_pad, cfg.s_pad
        CAP = cfg.cap
        PP, quota = self.P, self.quota
        FAA_CAP = cfg.cap
        axis = self.axis
        hid = _heuristic_id(self.heuristic)
        m_limit = self.m_limit

        def unique_rows(faa, faa_n):
            """#distinct rows among the first faa_n FAA entries, on-device.

            Lexicographic sort via Q iterated stable argsorts (invalid rows
            sentinel-filled with INT32_MAX so they sort last), then count
            rows that differ from their predecessor.  Exact — no hashing.
            """
            N = faa.shape[0]
            valid = jnp.arange(N, dtype=jnp.int32) < faa_n
            rows = jnp.where(valid[:, None], faa, jnp.int32(2**31 - 1))
            order = jnp.arange(N, dtype=jnp.int32)
            for q in range(Q - 1, -1, -1):
                keys = jnp.take(rows[:, q], order)
                order = jnp.take(order, jnp.argsort(keys, stable=True))
            srt = jnp.take(rows, order, axis=0)
            vsrt = jnp.take(valid, order)
            first = jnp.concatenate(
                [jnp.ones(1, bool), jnp.any(srt[1:] != srt[:-1], axis=1)])
            return (vsrt & first).sum(dtype=jnp.int32)

        def frontier_info(rows, step, valid, plan, n_steps, g2l_row, n_core):
            s = jnp.clip(step, 0, S - 1)
            src_slot = plan.src_slot[s]
            fg = jnp.take_along_axis(rows, src_slot[:, None], axis=1)[:, 0]
            fg_safe = jnp.clip(fg, 0, g2l_row.shape[0] - 1)
            lidx = jnp.where(fg >= 0, jnp.take(g2l_row, fg_safe), -1)
            local = (lidx >= 0) & (lidx < n_core)
            live = valid & (step < n_steps)
            return live & local, live & ~local, lidx, fg

        def device_fn(part, g2l_row, owner, plan, n_steps, rngseed, budget):
            # per-device state; partition id == device index on `axis`
            my = jax.lax.axis_index(axis)
            n_core = part["n_core"][0]
            node_label = part["node_label"][0]
            node_value = part["node_value"][0]
            node_gid = part["node_gid"][0]
            pdict = {k: v[0] for k, v in part.items()}
            g2l_row = g2l_row[0]
            # geometry off the input shapes (static at trace time) — one
            # engine serves any padded layout; jit retraces per shape
            Np = node_label.shape[0]
            W = pdict["ell_dst"].shape[1]
            EB = min(cfg.expand_block, CAP + Np)

            if cfg.use_pallas:
                # locality tables for the fused kernel — once per query,
                # outside the while loop (cfg is a closure constant)
                from ..kernels import ops as kops
                aux = kops.denorm_locality(pdict["ell_dgid"], g2l_row, owner)
            else:
                aux = None

            # ---- iteration-0 seeding on every partition (all mappers) ----
            node_idx = jnp.arange(Np, dtype=jnp.int32)
            start_ok = ((node_idx < n_core)
                        & ((plan.start_label == WILDCARD)
                           | (node_label == plan.start_label))
                        & apply_value_op(plan.start_value_op, node_value,
                                         plan.start_value))
            col = jnp.arange(Q, dtype=jnp.int32)
            seed_rows = jnp.where(
                (col[None, :] == plan.start_slot) & start_ok[:, None],
                node_gid[:, None], jnp.int32(-1))

            WT = CAP + Np
            rows = jnp.concatenate(
                [seed_rows, jnp.full((CAP, Q), -1, jnp.int32)], axis=0)
            step = jnp.zeros(WT, jnp.int32)
            valid = jnp.concatenate([start_ok, jnp.zeros(CAP, bool)])
            # single-node queries: seeds may already be complete
            faa = jnp.full((FAA_CAP, Q), -1, jnp.int32)
            faa_n = jnp.int32(0)
            done0 = valid & (step >= n_steps)
            cnt0 = jnp.cumsum(done0.astype(jnp.int32)) - 1
            tgt0 = jnp.where(done0, cnt0, FAA_CAP)
            faa = faa.at[tgt0].set(rows, mode="drop")
            faa_n = jnp.minimum(done0.sum(dtype=jnp.int32), FAA_CAP)
            valid = valid & ~done0

            overflow = jnp.bool_(False)
            # unique-FAA count for the budget stop (seeds are distinct
            # vertices so seed answers are duplicate-free, but keep the
            # same gated computation for uniformity)
            uniq_n = jax.lax.cond(budget < _NO_BUDGET,
                                  lambda: unique_rows(faa, faa_n),
                                  lambda: faa_n)
            # per-partition yield counters (MAX-YIELD observations)
            comp_cnt = faa_n
            spawn_cnt = jnp.int32(0)

            def cond(st):
                rows, step, valid, faa, faa_n, uniq, _c, _s, ovf, it = st
                live = (valid & (step < n_steps)).sum(dtype=jnp.int32)
                total = jax.lax.psum(live, axis)
                # answer-budget stop: the jobtracker's global UNIQUE answer
                # count (psum of per-mapper distinct-FAA sizes; duplicates
                # of an answer always land on one mapper, so per-device
                # unique counts add up exactly) reaching K ends the single
                # compiled program early — no host round-trip and no
                # host-side re-run (Sec. 9 + runner.py budget semantics)
                got = jax.lax.psum(uniq, axis)
                return (total > 0) & (got < budget) & (it < self.max_outer_iters)

            def body(st):
                rows, step, valid, faa, faa_n, uniq, comp, spawn, ovf, it = st
                act, pend, lidx, fg = frontier_info(rows, step, valid, plan,
                                                    n_steps, g2l_row, n_core)

                # -- heuristic gating when m_limit < P (paper Sec. 9.2) --
                my_sni = act.sum(dtype=jnp.int32)
                all_sni = jax.lax.all_gather(my_sni, axis)       # [P]
                if m_limit < PP:
                    if hid == 0:        # MAX-SN: most start/cont. nodes first
                        key = -all_sni
                    elif hid == 1:      # MIN-SN among non-empty
                        key = jnp.where(all_sni > 0, all_sni, jnp.int32(2**30))
                    elif hid == 3:      # MAX-YIELD: SNI x completion rate
                        # the on-device mirror of heuristics.rank_partitions:
                        # Laplace-smoothed completed/(completed+spawned)
                        # from the counters carried in the loop state
                        all_comp = jax.lax.all_gather(comp, axis)    # [P]
                        all_spawn = jax.lax.all_gather(spawn, axis)  # [P]
                        rate = ((all_comp.astype(jnp.float32) + 1.0)
                                / ((all_comp + all_spawn).astype(jnp.float32)
                                   + 2.0))
                        key = -(all_sni.astype(jnp.float32) * rate)
                    else:               # RANDOM among non-empty
                        r = jax.random.permutation(
                            jax.random.fold_in(jax.random.PRNGKey(rngseed), it), PP)
                        key = jnp.where(all_sni > 0, r.astype(jnp.int32),
                                        jnp.int32(2**30))
                    rank = jnp.argsort(jnp.argsort(key))          # dense ranks
                    chosen = rank[my] < m_limit
                else:
                    chosen = jnp.bool_(True)
                act = act & chosen

                # -- map: ONE-edge expansion of up to EB active rows --
                sel = jnp.argsort(~act, stable=True)[:EB]
                m = jnp.take(act, sel)
                rows_b = jnp.take(rows, sel, axis=0)
                step_b = jnp.take(step, sel)
                lidx_b = jnp.take(lidx, sel)
                valid = valid.at[sel].set(jnp.take(valid, sel) & ~m)

                (ok, dg, ns, nr, done_t, keep_t, outm_t, _dest) = \
                    _expand_classify(rows_b, step_b, lidx_b, m, pdict,
                                     g2l_row, owner, aux, plan, n_steps,
                                     cfg.use_pallas)
                EBW = EB * W
                ok_f = ok.reshape(EBW)
                nr_f = nr.reshape(EBW, Q)
                ns_f = ns.reshape(EBW)
                done = done_t.reshape(EBW)

                cnt = jnp.cumsum(done.astype(jnp.int32)) - 1
                tgt = jnp.where(done, faa_n + cnt, FAA_CAP)
                faa = faa.at[tgt].set(nr_f, mode="drop")
                new_faa_n = faa_n + done.sum(dtype=jnp.int32)
                ovf = ovf | (new_faa_n > FAA_CAP)
                faa_n = jnp.minimum(new_faa_n, FAA_CAP)
                uniq = jax.lax.cond(budget < _NO_BUDGET,
                                    lambda f, n: unique_rows(f, n),
                                    lambda f, n: n, faa, faa_n)

                # yield observations: completions here vs continuations
                # spawned into another partition's buffers (the kernel's
                # `out` class — next frontier owned elsewhere)
                comp = comp + done.sum(dtype=jnp.int32)
                spawn = spawn + outm_t.reshape(EBW).sum(dtype=jnp.int32)

                # ALL continuing rows stay local until the shuffle below —
                # the mapper holds non-local rows back-pressured in its own
                # buffer (kernel classes keep | out)
                keep = (keep_t | outm_t).reshape(EBW)
                free = jnp.argsort(valid, stable=True)
                ovf = ovf | (keep.sum(dtype=jnp.int32)
                             > (~valid).sum(dtype=jnp.int32))
                pos = jnp.cumsum(keep.astype(jnp.int32)) - 1
                tgt2 = jnp.where(keep & (pos < WT),
                                 free[jnp.clip(pos, 0, WT - 1)], WT)
                rows = rows.at[tgt2].set(nr_f, mode="drop")
                step = step.at[tgt2].set(ns_f, mode="drop")
                valid = valid.at[tgt2].set(True, mode="drop")

                # -- shuffle: quota-based all_to_all on destination pid --
                _, pend, _, fg = frontier_info(rows, step, valid, plan,
                                               n_steps, g2l_row, n_core)
                dest = jnp.take(owner, jnp.clip(fg, 0, owner.shape[0] - 1))
                dest = jnp.where(pend, dest, PP)          # PP = "no send"
                order = jnp.argsort(dest, stable=True)    # group rows by dest
                sdest = jnp.take(dest, order)
                # rank within each destination group
                grp_start = jnp.searchsorted(sdest, jnp.arange(PP + 1,
                                                               dtype=sdest.dtype))
                rank_in_grp = jnp.arange(WT, dtype=jnp.int32) - grp_start[
                    jnp.clip(sdest, 0, PP)]
                sendable = (sdest < PP) & (rank_in_grp < quota)
                slot = jnp.where(sendable, sdest * quota + rank_in_grp,
                                 PP * quota)
                send_rows = jnp.full((PP * quota, Q), -1, jnp.int32)
                send_step = jnp.zeros(PP * quota, jnp.int32)
                send_valid = jnp.zeros(PP * quota, bool)
                src_idx = order
                send_rows = send_rows.at[slot].set(jnp.take(rows, src_idx, axis=0),
                                                   mode="drop")
                send_step = send_step.at[slot].set(jnp.take(step, src_idx),
                                                   mode="drop")
                send_valid = send_valid.at[slot].set(sendable, mode="drop")
                # invalidate sent rows locally
                sent_src = jnp.where(sendable, src_idx, WT)
                valid = valid.at[sent_src].set(False, mode="drop")

                recv_rows = jax.lax.all_to_all(
                    send_rows.reshape(PP, quota, Q), axis, 0, 0, tiled=False
                ).reshape(PP * quota, Q)
                recv_step = jax.lax.all_to_all(
                    send_step.reshape(PP, quota), axis, 0, 0, tiled=False
                ).reshape(PP * quota)
                recv_valid = jax.lax.all_to_all(
                    send_valid.reshape(PP, quota), axis, 0, 0, tiled=False
                ).reshape(PP * quota)

                # -- reduce: merge received rows into free local slots --
                free2 = jnp.argsort(valid, stable=True)
                ovf = ovf | (recv_valid.sum(dtype=jnp.int32)
                             > (~valid).sum(dtype=jnp.int32))
                pos2 = jnp.cumsum(recv_valid.astype(jnp.int32)) - 1
                tgt3 = jnp.where(recv_valid & (pos2 < WT),
                                 free2[jnp.clip(pos2, 0, WT - 1)], WT)
                rows = rows.at[tgt3].set(recv_rows, mode="drop")
                step = step.at[tgt3].set(recv_step, mode="drop")
                valid = valid.at[tgt3].set(True, mode="drop")

                return (rows, step, valid, faa, faa_n, uniq, comp, spawn,
                        ovf, it + 1)

            st = (rows, step, valid, faa, faa_n, uniq_n, comp_cnt, spawn_cnt,
                  overflow, jnp.int32(0))
            (rows, step, valid, faa, faa_n, uniq_n, comp_cnt, spawn_cnt,
             overflow, iters) = jax.lax.while_loop(cond, body, st)
            # did the loop end because the work drained (vs budget/iter cap)?
            live_end = (valid & (step < n_steps)).sum(dtype=jnp.int32)
            exhausted = jax.lax.psum(live_end, axis) == 0
            return (faa[None], faa_n[None], overflow[None], iters[None],
                    exhausted[None], comp_cnt[None], spawn_cnt[None])

        pspec = P(axis)
        in_specs = (
            {k: pspec for k in self.store.part_keys},  # parts sharded by device
            pspec,                              # g2l rows
            P(),                                # owner replicated
            P(),                                # plan replicated
            P(),                                # n_steps
            P(),                                # rng seed
            P(),                                # answer budget (replicated)
        )
        out_specs = (pspec,) * 7
        fn = jax.shard_map(device_fn, mesh=self.mesh, in_specs=in_specs,
                           out_specs=out_specs, check_vma=False)
        return jax.jit(fn)

    def run(self, plan: Plan, seed: int = 0,
            max_answers: Optional[int] = None) -> MapReduceMPResult:
        cfg = self.cfg
        assert plan.n_slots <= cfg.q_pad and plan.n_steps <= cfg.s_pad
        if self._compiled is None:
            self._compiled = self._build(cfg.s_pad)
        plan_arrays = PlanArrays.from_plan(plan, pad_steps=cfg.s_pad)
        # The device-side budget stop counts UNIQUE answers (per-mapper
        # distinct-FAA sizes; duplicates of an answer always converge on
        # one mapper), so a single compiled run suffices — no geometric
        # host re-run on duplicate-heavy workloads.
        dev_budget = (int(_NO_BUDGET) if max_answers is None
                      else int(max_answers))
        load0 = self.store.stats.copy()
        entry = self.store.get_stacked(tuple(range(self.P)),
                                       sharding=self._part_sharding)
        out, c = traced_eval(
            self, ("mapreduce", "eval"), self._compiled,
            (entry.part, entry.g2l, self.store.owner, plan_arrays,
             np.int32(plan.n_steps), np.int32(seed),
             np.int32(min(dev_budget, int(_NO_BUDGET)))),
            read=_read_counts, engine="mapreduce", n_parts=self.P, rows=0)
        if c.overflow:
            raise RuntimeError(
                "MapReduceMP buffer overflow; raise cap/quota")
        with self.tracer.span("eval.absorb") as asp:
            faa_n = c.comp_n
            faa, comp, spawn = jax.device_get((out[0], out[5], out[6]))
            rows = [faa[p, : faa_n[p]] for p in range(self.P) if faa_n[p]]
            answers = (np.unique(np.concatenate(rows), axis=0) if rows
                       else np.zeros((0, cfg.q_pad), dtype=np.int32))
            if self.tracer.enabled:
                asp.set(bytes_d2h=host_nbytes(faa, comp, spawn))
        answers = truncate_answers(answers, max_answers)
        n_iter = int(c.n_iters)
        delta = self.store.stats - load0
        stats = RunStats(query=plan.query.name, scheme=self.pg.scheme,
                         heuristic=self.heuristic,
                         loads=[], l_ideal=l_ideal_for_plan(self.pg, plan),
                         n_answers=int(answers.shape[0]),
                         iterations=n_iter,
                         answers_requested=max_answers,
                         eval_iters=n_iter,
                         rows_expanded=None,
                         **residency(delta))
        return MapReduceMPResult(
            answers=answers, stats=stats, n_iterations=n_iter,
            completed_from=comp.astype(np.int64).reshape(-1),
            spawned_from=spawn.astype(np.int64).reshape(-1))

    def run_request(self, req: RunRequest) -> RunReport:
        """The shared ``QueryRunner`` protocol (see core/runner.py).

        The engine's heuristic is fixed at construction (it is baked into
        the compiled program); a conflicting per-request heuristic is an
        error rather than a silent ignore.
        """
        if req.heuristic != self.heuristic:
            raise ValueError(
                f"MapReduceMPEngine was compiled with heuristic "
                f"{self.heuristic!r}; rebuild the engine to run "
                f"{req.heuristic!r}")
        res = self.run(req.plan, seed=req.seed, max_answers=req.max_answers)
        return RunReport(answers=res.answers, stats=res.stats,
                         engine="mapreduce",
                         extra={"n_iterations": res.n_iterations,
                                "completed_from": res.completed_from,
                                "spawned_from": res.spawned_from})
