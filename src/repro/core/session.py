"""GraphSession — the stateful serving API over one partitioned graph.

The paper's workload is *query serving*: many queries, one partitioned
graph, response time dominated by the partition-load sequence.  The seed
code had no object for that shape — every caller re-built engines and
re-shipped partitions per query.  A ``GraphSession`` is constructed once
from (graph, scheme, k, engine, EngineConfig) and then serves repeated
``submit`` calls against the same residency state:

  * it owns the ``PartitionStore`` (core/store.py), so the second query
    finds the first query's partitions device-resident — warm loads — and
    OPAT's runner-up prefetch overlaps transfers with evaluation;
  * it owns the catalog and the engine (one compile of the partition
    evaluator per session, reused across queries);
  * it accumulates a per-partition *workload profile* — loads, completed
    vs spawned rows, completion rates, and the per-answer partition-span
    matrix — that persists to JSON.  ``core/repartition.py`` consumes it:
    hot query paths show up as partitions with many loads, low completion
    rates, and heavy co-span pairs, i.e. spanning work the partitioner
    should co-locate — and ``repartition()`` (below) closes that loop in
    place, rebuilding the session against the workload-aware layout.

``submit(query, max_answers=K)`` accepts a conjunctive ``Query`` or a
``DisjunctiveQuery`` (per-disjunct plans, unioned answers; a budget K
applies per disjunct, matching ``launch/serve.py`` semantics) and returns a
``QueryResult`` carrying the merged answers, per-disjunct ``RunReport``s,
wall latency, and this call's cold/warm/prefetch ``LoadStats`` delta.

``submit_many(queries, max_answers=K)`` serves a whole batch through the
``QueryScheduler`` (core/scheduler.py): pending queries share partition
loads (workload-level MAX-YIELD-SHARED ordering, batched partition
evaluation on the OPAT path), each retires independently on its own
budget, and the workload profile absorbs every result exactly as single
submits do.

``save(path)`` / ``open(path)`` round the partitioned graph through disk
(src/repro/storage/): a saved *graph directory* reopens as an
out-of-core session whose partitions stream through the store's
disk → pinned-host → device cache tiers with identical answers.
"""
from __future__ import annotations

import contextlib
import dataclasses
import json
import time
from typing import Any, Dict, List, Optional, Sequence, Union

import numpy as np

from .catalog import Catalog, build_catalog
from .engine import EngineConfig
from .graph import Graph, PartitionedGraph, build_partitions
from .heuristics import MAX_SN
from .metrics import RunStats
from .partition import partition_graph
from .plan import generate_plan
from .query import DisjunctiveQuery, Query
from .runner import QueryRunner, RunReport, RunRequest
from .store import LoadStats, PartitionStore

ENGINES = ("opat", "traditional", "mapreduce")


@dataclasses.dataclass
class QueryResult:
    """What ``GraphSession.submit`` returns for one (possibly disjunctive)
    query: merged unique answers plus everything observability needs."""

    name: str
    answers: np.ndarray            # [n, q_pad] unique rows (union of disjuncts)
    reports: List[RunReport]       # one per disjunct, in disjunct order
    latency_s: float
    load_stats: LoadStats          # this call's store delta (cold/warm/prefetch)
    qid: Optional[int] = None      # scheduler admission id (None on submit);
                                   # the SLO front end matches results back
                                   # to requests with it
    generation: Optional[int] = None   # the graph generation this result was
                                       # pinned to (storage/deltas.py); None
                                       # for in-RAM sessions — no generations

    @property
    def n_answers(self) -> int:
        return int(self.answers.shape[0])

    @property
    def stats(self) -> List[RunStats]:
        return [r.stats for r in self.reports]

    @property
    def n_loads(self) -> int:
        return sum(s.n_loads for s in self.stats)


class GraphSession:
    """One partitioned graph, one engine compile, many queries.

    Parameters mirror the serving CLI: ``engine`` is one of ``"opat"``,
    ``"traditional"``, ``"mapreduce"``; ``cache_parts`` / ``cache_bytes``
    size the store's LRU device cache (None = unbounded); ``prefetch``
    enables OPAT's runner-up staging.  Pass ``pg`` to reuse an existing
    ``PartitionedGraph`` (then ``graph``/``k``/``scheme`` are taken from
    it); ``mesh`` is required context for MapReduceMP on >1 device
    (defaults to a 1-D mesh over all local devices).

    Out of core: ``GraphSession.open(path)`` builds a session over a
    ``save``d graph directory — partitions stay disk-resident behind a
    three-tier cache, with ``host_cache_parts`` / ``host_cache_bytes``
    sizing the pinned-host LRU and ``read_ahead`` enabling the
    background-thread disk staging of the heuristic's runner-up (both
    are ignored for in-RAM sessions, whose host tier is the whole graph).
    See docs/storage.md.
    """

    def __init__(self, graph: Optional[Graph] = None, *,
                 k: int = 4,
                 scheme: str = "kway_shem",
                 engine: str = "opat",
                 heuristic: str = MAX_SN,
                 config: Optional[EngineConfig] = None,
                 cache_parts: Optional[int] = None,
                 cache_bytes: Optional[int] = None,
                 host_cache_parts: Optional[int] = None,
                 host_cache_bytes: Optional[int] = None,
                 read_ahead: bool = True,
                 processors: int = 2,
                 prefetch: bool = True,
                 seed: int = 0,
                 pg: Optional[PartitionedGraph] = None,
                 mesh: Optional[Any] = None,
                 catalog: Optional[Catalog] = None,
                 tracer: Optional[Any] = None,
                 profiler: Optional[Any] = None):
        if engine not in ENGINES:
            raise ValueError(f"engine must be one of {ENGINES}, got {engine!r}")
        if pg is None:
            if graph is None:
                raise ValueError("need a graph (or a pre-built pg)")
            assign = partition_graph(graph, k, scheme, seed=seed)
            pg = build_partitions(graph, assign, k, scheme=scheme)
        self.graph = pg.graph
        self.engine_name = engine
        self.heuristic = heuristic
        self.seed = seed
        self.config = config or EngineConfig()
        self.catalog = catalog if catalog is not None else build_catalog(self.graph)
        # remembered so repartition() can rebuild the stack identically
        self._cache_parts = cache_parts
        self._cache_bytes = cache_bytes
        # the disk tier (out-of-core sessions, GraphSession.open): a
        # DiskCatalog the store's host LRU reads shards from, plus that
        # LRU's sizing and read-ahead switch (storage/host_cache.py)
        self._backing = getattr(pg, "backing", None)
        self._host_cache_parts = host_cache_parts
        self._host_cache_bytes = host_cache_bytes
        self._read_ahead = read_ahead
        self._processors = processors
        self._prefetch = prefetch
        self._mesh = mesh
        self.repartitions = 0
        # observability (obs/trace.py): one tracer serves the whole stack
        # threaded under this session — store, host tier, engines,
        # scheduler, front end, delta layer.  The no-op default keeps
        # untraced serving at pre-obs cost.
        from ..obs.trace import NULL_TRACER
        self.tracer = tracer if tracer is not None else NULL_TRACER
        # resource profiling (obs/profile.py): defaults ON whenever a real
        # tracer is attached — traced spans then carry memory/cost
        # attributes — and to the no-op singleton otherwise; pass an
        # explicit profiler (or NULL_PROFILER) to decouple the two
        from ..obs.profile import NULL_PROFILER, ResourceProfiler
        if profiler is not None:
            self.profiler = profiler
        elif self.tracer.enabled:
            self.profiler = ResourceProfiler(self.tracer)
        else:
            self.profiler = NULL_PROFILER
        self.store: Optional[PartitionStore] = None
        # streaming updates (storage/deltas.py): a session built by
        # ``open`` owns the directory's writer handle and keeps one pinned
        # generation view current; in-RAM sessions have neither and
        # ``mutate``/``compact``/``snapshot`` raise
        self._mdir: Optional[Any] = None
        self._view: Optional[Any] = None
        self._bind(pg)

    def _bind(self, pg: PartitionedGraph) -> None:
        """(Re)build everything that depends on the vertex assignment: the
        store (so no stale single-partition entry or stacked bundle from an
        older layout can ever be served), the engine (its compiled
        evaluator is shaped by the new padding geometry and it must point
        at the new store), and the per-partition profile counters (old pids
        name different vertex sets, so old counts are not observations of
        the new layout)."""
        if self.store is not None:
            # join in-flight read-aheads and drop every cache tier: no
            # stale host/device entry of an old layout can ever be served
            self.store.close()
        self.pg = pg
        self.scheme = pg.scheme
        self.k = pg.k
        self.store = PartitionStore(pg, capacity_parts=self._cache_parts,
                                    capacity_bytes=self._cache_bytes,
                                    backing=self._backing,
                                    host_cache_parts=self._host_cache_parts,
                                    host_cache_bytes=self._host_cache_bytes,
                                    read_ahead=self._read_ahead,
                                    tracer=self.tracer,
                                    profiler=self.profiler)
        engine = self.engine_name
        if engine == "opat":
            from .opat import OPATEngine
            self.engine: QueryRunner = OPATEngine(
                pg, self.config, store=self.store, prefetch=self._prefetch,
                tracer=self.tracer, profiler=self.profiler)
        elif engine == "traditional":
            from .traditional_mp import TraditionalMPEngine
            self.engine = TraditionalMPEngine(
                pg, self._processors, self.config, store=self.store,
                tracer=self.tracer, profiler=self.profiler)
        else:
            from .mapreduce_mp import MapReduceMPEngine, make_part_mesh
            mesh = self._mesh
            if mesh is None:
                mesh = make_part_mesh(pg.k)
            self.engine = MapReduceMPEngine(
                pg, mesh, self.config, heuristic=self.heuristic,
                store=self.store, tracer=self.tracer,
                profiler=self.profiler)

        # per-partition workload profile, accumulated across submits.
        # MapReduceMP runs as one compiled program with no host loop: it
        # now surfaces per-partition YIELD counters (carried through the
        # while_loop state), but still no per-partition LOAD sequence —
        # the profile flags that rather than passing off all-zeros as
        # load observations.
        self.observes_partition_counters = engine != "mapreduce"
        self._loads = np.zeros(self.k, dtype=np.int64)
        self._completed = np.zeros(self.k, dtype=np.int64)
        self._spawned = np.zeros(self.k, dtype=np.int64)
        # answer-span observations (host-side, engine-independent): how many
        # answer rows bound vertices in both p and q, and how often each
        # vertex was bound in a partition-spanning answer — the co-traversal
        # signals core/repartition.py reweights boundary edges with
        self._cospan = np.zeros((self.k, self.k), dtype=np.int64)
        self._vertex_span = np.zeros(self.graph.n_nodes, dtype=np.int64)
        self._span_sum = 0
        self._span_rows = 0
        self._queries_served = 0
        self._answers_served = 0
        # SLO serving accumulators (serving/frontend.py feeds these via
        # record_serving; empty for plain submit/submit_many sessions, and
        # workload_profile() only emits a "serving" block when non-empty —
        # keeping non-SLO profiles byte-identical)
        self._slo_counters: Dict[str, int] = {}
        self._slo_shed_reasons: Dict[str, int] = {}
        self._slo_latencies: Dict[str, List[float]] = {}
        self._slo_deadline: Dict[str, List[int]] = {}
        # latest per-class burn-rate snapshot (obs/profile.SloBurnMonitor
        # via record_serving): {cls: {window, misses, miss_fraction,
        # burn_rate, error_budget}}
        self._slo_burn: Dict[str, Dict[str, Any]] = {}

    # -- serving -----------------------------------------------------------

    def submit(self, query: Union[Query, DisjunctiveQuery],
               max_answers: Optional[int] = None,
               heuristic: Optional[str] = None,
               seed: Optional[int] = None) -> QueryResult:
        """Serve one query against the session's resident partitions.

        ``max_answers`` is the paper's "specified number of answers" K
        (per disjunct); ``heuristic``/``seed`` default to the session's.
        """
        disjuncts = (query.disjuncts if isinstance(query, DisjunctiveQuery)
                     else [query])
        h = heuristic if heuristic is not None else self.heuristic
        s = seed if seed is not None else self.seed
        stats0 = self.store.stats.copy()
        t0 = time.time()
        reports: List[RunReport] = []
        answers: Optional[np.ndarray] = None
        # the whole call runs against ONE pinned generation view: a
        # mutation or compaction landing mid-query never changes what this
        # query's loads resolve to (new submits pick up the latest view)
        view = self._view
        ctx = (self.store.viewing(view) if view is not None
               else contextlib.nullcontext())
        gen = int(view.generation) if view is not None else None
        with self.tracer.span("query", query=query.name, heuristic=h,
                              engine=self.engine_name,
                              generation=gen) as qsp, ctx:
            for q in disjuncts:
                with self.tracer.span("query.plan", query=q.name):
                    plan = generate_plan(q, self.graph, self.catalog)
                rep = self.engine.run_request(RunRequest(
                    plan=plan, heuristic=h, max_answers=max_answers, seed=s))
                reports.append(rep)
                a = rep.answers
                answers = a if answers is None else np.unique(
                    np.concatenate([answers, a]), axis=0)
            qsp.set(n_answers=int(answers.shape[0]),
                    n_loads=sum(len(r.stats.loads) for r in reports))
        latency = time.time() - t0
        for rep in reports:
            rep.stats.generation = gen
        self._absorb(reports, answers)
        return QueryResult(name=query.name, answers=answers, reports=reports,
                           latency_s=latency,
                           load_stats=self.store.stats - stats0,
                           generation=gen)

    def scheduler(self, heuristic: Optional[str] = None,
                  seed: Optional[int] = None,
                  release_retired: bool = False,
                  fairness_gamma: float = 0.0) -> "Any":
        """A ``QueryScheduler`` bound to this session's store, engine, and
        catalog (core/scheduler.py) — the multi-query serving loop.
        ``heuristic`` is a *shared* ranking (default MAX-YIELD-SHARED);
        ``fairness_gamma`` weights the anti-starvation aging term
        (rounds-waiting × SNI) in that ranking.  Prefer ``submit_many``
        unless you need streaming admission, since only ``submit_many``
        feeds results into the workload profile."""
        from .heuristics import MAX_YIELD_SHARED
        from .scheduler import QueryScheduler
        return QueryScheduler(
            self,
            heuristic=heuristic if heuristic is not None else MAX_YIELD_SHARED,
            seed=seed, release_retired=release_retired,
            fairness_gamma=fairness_gamma)

    def frontend(self, **kwargs) -> "Any":
        """A ``ServingFrontend`` bound to this session
        (serving/frontend.py): continuous-arrival serving with admission
        control, cost prediction, deadline scheduling, and load shedding.
        Keyword arguments pass through (``slo_classes``, ``cost_model``,
        ``shed_policy``, ``replay_speed``, ...).  With no SLO classes the
        front end delegates to ``submit_many`` byte-identically."""
        from ..serving.frontend import ServingFrontend
        return ServingFrontend(self, **kwargs)

    def record_serving(self, *, counters: Dict[str, int],
                       shed_by_reason: Dict[str, int],
                       latencies: Dict[str, List[float]],
                       deadline_met: Dict[str, List[bool]],
                       slo_burn: Optional[Dict[str, Dict[str, Any]]] = None
                       ) -> None:
        """Fold one ``ServingFrontend.serve`` run's admission/shed counters
        and per-SLO-class latencies into the session's workload profile
        (the ``"serving"`` block of ``workload_profile()``).  ``slo_burn``
        is the front end's rolling error-budget burn snapshot (kept as
        latest-wins: the window is the monitor's, not the session's)."""
        for key, n in counters.items():
            self._slo_counters[key] = self._slo_counters.get(key, 0) + int(n)
        for reason, n in shed_by_reason.items():
            self._slo_shed_reasons[reason] = \
                self._slo_shed_reasons.get(reason, 0) + int(n)
        for cls, vals in latencies.items():
            self._slo_latencies.setdefault(cls, []).extend(
                float(v) for v in vals)
        for cls, oks in deadline_met.items():
            met = self._slo_deadline.setdefault(cls, [0, 0])
            for ok in oks:
                met[0] += int(bool(ok))
                met[1] += 1
        if slo_burn:
            for cls, snap in slo_burn.items():
                self._slo_burn[cls] = dict(snap)

    def submit_many(self, queries: Sequence[Union[Query, DisjunctiveQuery]],
                    max_answers: Union[None, int,
                                       Sequence[Optional[int]]] = None,
                    heuristic: Optional[str] = None,
                    seed: Optional[int] = None,
                    release_retired: bool = False,
                    fairness_gamma: float = 0.0) -> "Any":
        """Serve a batch of queries through the shared-load scheduler and
        return its ``ScheduleReport`` (``.results`` holds one
        ``QueryResult`` per query, in input order).  ``max_answers`` is
        one per-disjunct budget K for the whole batch, or a per-query
        sequence of budgets (None entries = exhaustive).

        Semantics match a loop of ``submit`` calls — same per-query answer
        sets when exhaustive, same per-disjunct budget K, and every result
        is absorbed into the workload profile exactly as single submits
        are — but on the OPAT path the partition-load sequence is chosen
        at the *workload* level, so overlapping queries share cold loads
        and each ``QueryResult.load_stats`` reports the loads that query
        participated in (round-scoped, never other queries' traffic).
        """
        if isinstance(max_answers, (list, tuple)):
            budgets = list(max_answers)
            if len(budgets) != len(queries):
                raise ValueError(f"got {len(budgets)} budgets for "
                                 f"{len(queries)} queries")
        else:
            budgets = [max_answers] * len(queries)
        sched = self.scheduler(heuristic=heuristic, seed=seed,
                               release_retired=release_retired,
                               fairness_gamma=fairness_gamma)
        try:
            for q, b in zip(queries, budgets):
                sched.admit(q, max_answers=b)
            report = sched.run()
        finally:
            sched.close()   # drop the scheduler's generation pin
        for res in report.results:
            self._absorb(res.reports, res.answers)
        return report

    def _absorb(self, reports: List[RunReport], answers: np.ndarray) -> None:
        from .repartition import answer_span_matrix
        for rep in reports:
            for pid in rep.stats.loads:
                self._loads[pid] += 1
            st = rep.extra.get("state")
            if st is not None:     # OPAT / TraditionalMP expose QueryState
                self._completed += st.completed_from
                self._spawned += st.spawned_from
            elif rep.extra.get("completed_from") is not None:
                # MapReduceMP: yield counters carried through the device
                # while_loop and surfaced as plain [k] arrays
                self._completed += rep.extra["completed_from"]
                self._spawned += rep.extra["spawned_from"]
        pairs, span = answer_span_matrix(self.pg.owner, answers, self.k)
        self._cospan += pairs
        spanning = answers[span >= 2]
        if spanning.size:
            ids = spanning[spanning >= 0]
            np.add.at(self._vertex_span, ids, 1)
        self._span_sum += int(span.sum())
        self._span_rows += int(span.shape[0])
        self._queries_served += 1
        self._answers_served += int(answers.shape[0])

    # -- observability -----------------------------------------------------

    @property
    def load_stats(self) -> LoadStats:
        """Lifetime store counters (cold/warm/evictions/prefetch)."""
        return self.store.stats

    def workload_profile(self) -> Dict[str, Any]:
        """Per-partition load/yield/completion-rate profile of everything
        this session served, plus the answer-span (co-traversal) matrix and
        the assignment it was observed under — exactly what
        ``core/repartition.py`` consumes to produce the ``"waw"`` layout
        (WawPart, arXiv:2203.14888), and what ``launch/serve.py --json``
        embeds for CI.

        ``partition_counters_observed`` is False for MapReduceMP: yield
        counters (completed/spawned) ARE carried through the device
        while_loop and absorbed, but there is no host loop and hence no
        per-partition LOAD sequence, so the repartitioner skips its
        load-share split-pressure term; the ``answer_spans`` block is
        observed host-side from the answers and is valid for every engine.

        Sessions served through the SLO front end additionally carry a
        ``"serving"`` block: admission/degrade/shed counters, shed reasons,
        and per-SLO-class p50/p95/p99 latency + deadline attainment.  Plain
        sessions emit no such block, so their profiles stay byte-identical
        to pre-SLO builds.
        """
        pending = (self._mdir.pending_counts()
                   if self._mdir is not None else None)
        partitions = []
        for p in range(self.k):
            comp = int(self._completed[p])
            spawn = int(self._spawned[p])
            entry = {
                "pid": p,
                "loads": int(self._loads[p]),
                "completed": comp,
                "spawned": spawn,
                # Laplace-smoothed, matching heuristics.MAX_YIELD
                "completion_rate": (comp + 1.0) / (comp + spawn + 2.0),
            }
            if pending is not None:
                # per-partition pending delta volume: the hot-update
                # signal continuous repartitioning (fold) keys off
                entry["delta_count"] = int(pending[p])
            partitions.append(entry)
        profile: Dict[str, Any] = {
            "engine": self.engine_name,
            "scheme": self.scheme,
            "k": self.k,
            "heuristic": self.heuristic,
            "partition_counters_observed": self.observes_partition_counters,
            "queries_served": self._queries_served,
            "answers_served": self._answers_served,
            "partitions": partitions,
            "answer_spans": {
                "answers_observed": self._span_rows,
                "mean_span": (self._span_sum / self._span_rows
                              if self._span_rows else 0.0),
                "pair_counts": self._cospan.tolist(),
                # per-vertex: #spanning answers (span >= 2) binding it; the
                # edge-level co-traversal signal for reweight_edges
                "vertex_span_counts": self._vertex_span.tolist(),
            },
            # the [V] assignment the counters refer to, so a saved profile
            # is self-contained for repartition_assignment()
            "assignment": self.pg.assignment.astype(int).tolist(),
            # out-of-core sessions: disk_reads / read_ahead_* land here too
            # (the LoadStats dict is field-complete by construction)
            "out_of_core": self.out_of_core,
            "cache": self.store.stats.to_dict(),
        }
        if self._mdir is not None:
            profile["generation"] = int(self._view.generation)
            profile["pending_deltas"] = int(sum(pending))
            profile["compactions"] = int(self._mdir.compactions)
        if self._slo_counters or self._slo_latencies:
            def _pct(vals: List[float], q: float) -> float:
                return float(np.percentile(np.asarray(vals), q * 100.0)) \
                    if vals else 0.0
            profile["serving"] = {
                "counters": dict(sorted(self._slo_counters.items())),
                "shed_by_reason": dict(sorted(
                    self._slo_shed_reasons.items())),
                "classes": {
                    cls: {
                        "served": len(vals),
                        "p50_latency_s": _pct(vals, 0.5),
                        "p95_latency_s": _pct(vals, 0.95),
                        "p99_latency_s": _pct(vals, 0.99),
                        "deadline_met": self._slo_deadline.get(
                            cls, [0, 0])[0],
                        "deadline_total": self._slo_deadline.get(
                            cls, [0, 0])[1],
                    }
                    for cls, vals in sorted(self._slo_latencies.items())
                },
            }
        return profile

    def save_profile(self, path: str) -> None:
        """Persist ``workload_profile()`` as JSON — the self-contained
        input of ``core/repartition.py`` (and the CI serve artifact)."""
        with open(path, "w") as f:
            json.dump(self.workload_profile(), f, indent=2)

    # -- out-of-core storage (src/repro/storage/) --------------------------

    @property
    def out_of_core(self) -> bool:
        """True when partitions are disk-resident (session built by
        ``open``; a later ``repartition()`` moves back in-RAM until the
        new layout is ``save``d)."""
        return self._backing is not None

    def save(self, path: str) -> Dict[str, Any]:
        """Write this session's partitioned graph as a *graph directory*
        (storage/format.py: ``manifest.json`` + one ``part-<pid>.npz``
        shard per partition + ``graph.npz``); returns the manifest.
        Works for in-RAM and disk-opened sessions alike (the latter
        streams shards one at a time, never holding the graph's partition
        bytes in memory); the manifest is written last, so an interrupted
        save never yields an openable directory and re-saving over a live
        one leaves the old shards intact until the fresh manifest lands.
        """
        from ..storage.format import save_partitioned_graph
        return save_partitioned_graph(self.pg, path)

    @classmethod
    def open(cls, path: str, *,
             engine: str = "opat",
             heuristic: str = MAX_SN,
             config: Optional[EngineConfig] = None,
             cache_parts: Optional[int] = None,
             cache_bytes: Optional[int] = None,
             host_cache_parts: Optional[int] = None,
             host_cache_bytes: Optional[int] = None,
             read_ahead: bool = True,
             processors: int = 2,
             prefetch: bool = True,
             seed: int = 0,
             mesh: Optional[Any] = None,
             verify_checksums: bool = True,
             tracer: Optional[Any] = None,
             profiler: Optional[Any] = None) -> "GraphSession":
        """Open a ``save``d graph directory as an *out-of-core* session.

        Partition shards stay on disk; the store serves them through a
        three-tier cache — device LRU (``cache_parts``/``cache_bytes``)
        over a pinned-host LRU (``host_cache_parts``/``host_cache_bytes``,
        None = unbounded) over disk — and ``read_ahead`` pulls the
        heuristic's runner-up off disk on a background thread while the
        current partition evaluates.  Heuristic ranking and scheduler
        admission read the manifest catalog, so they never touch a shard.
        Answers are identical to a session over the in-RAM graph; only
        residency (and ``LoadStats.disk_reads`` / ``read_ahead_hits``)
        differs.

        The directory opens *mutable* (storage/deltas.py): the session
        binds a pinned generation view, ``mutate``/``add_edge``/... append
        durable delta records, and ``compact``/``fold`` publish new
        generations — in-flight queries keep their pinned view, new
        submits pick up the latest.
        """
        from ..storage.deltas import open_mutable
        mdir = open_mutable(path, verify_checksums=verify_checksums)
        view = mdir.snapshot()
        pg = view.as_partitioned_graph()
        sess = cls(pg=pg, engine=engine, heuristic=heuristic, config=config,
                   cache_parts=cache_parts, cache_bytes=cache_bytes,
                   host_cache_parts=host_cache_parts,
                   host_cache_bytes=host_cache_bytes, read_ahead=read_ahead,
                   processors=processors, prefetch=prefetch, seed=seed,
                   mesh=mesh, tracer=tracer, profiler=profiler)
        sess._mdir = mdir
        sess._view = view
        # the directory's writes (append/compact/overlay rebuild) trace
        # into the same stream as the session that owns it
        mdir.tracer = sess.tracer
        return sess

    # -- streaming updates (storage/deltas.py) -----------------------------

    @property
    def mutable(self) -> bool:
        """True when the session owns a writable graph directory."""
        return self._mdir is not None

    @property
    def current_view(self):
        """The session's pinned GenerationView (None: in-RAM session)."""
        return self._view

    @property
    def generation(self) -> Optional[int]:
        """Generation new submits run against (None: in-RAM session)."""
        return int(self._view.generation) if self._view is not None else None

    def _require_mutable(self) -> "Any":
        if self._mdir is None:
            raise RuntimeError(
                "streaming updates need a disk-backed session — build one "
                "with GraphSession.open(path) over a save()d directory")
        return self._mdir

    def snapshot(self):
        """A fresh pinned GenerationView of the latest generation + deltas
        (caller releases).  While any snapshot stays pinned, the files its
        generation needs survive every later compaction's GC."""
        return self._require_mutable().snapshot()

    def _refresh_view(self) -> None:
        """Re-pin the latest generation and rebind the pg-level state on
        top of the UNCHANGED store — generation-qualified cache keys keep
        old-view entries valid for their pins while new submits resolve
        against the new view; nothing is invalidated."""
        mdir = self._mdir
        old = self._view
        self._view = mdir.snapshot()
        if old is not None:
            old.release()
        pg = self._view.as_partitioned_graph()
        self.pg = pg
        self.graph = pg.graph
        self.catalog = build_catalog(self.graph)
        self.engine.pg = pg
        self.store.pg = pg
        self.store.backing = mdir.catalog
        self.store.host_tier.catalog = mdir.catalog
        self._backing = mdir.catalog
        if self._vertex_span.shape[0] < self.graph.n_nodes:
            self._vertex_span = np.concatenate([
                self._vertex_span,
                np.zeros(self.graph.n_nodes - self._vertex_span.shape[0],
                         dtype=np.int64)])

    def mutate(self, ops: Sequence[Dict[str, Any]]) -> List[Any]:
        """Apply a batch of update operations durably (each a dict:
        ``{"op": "edge_add"|"edge_del"|"vertex_add"|"vertex_del", ...}``,
        see ``MutableGraphDirectory.apply_op``) and advance the session's
        view once.  Returns the appended ``DeltaRecord``s."""
        mdir = self._require_mutable()
        recs = [mdir.apply_op(d) for d in ops]
        self._refresh_view()
        return recs

    def add_edge(self, u: int, v: int, label: str,
                 directed: bool = False) -> "Any":
        rec = self._require_mutable().add_edge(u, v, label, directed=directed)
        self._refresh_view()
        return rec

    def del_edge(self, u: int, v: int, label: str) -> "Any":
        rec = self._require_mutable().del_edge(u, v, label)
        self._refresh_view()
        return rec

    def add_vertex(self, label: str, value: float = float("nan"),
                   pid: Optional[int] = None) -> "Any":
        rec = self._require_mutable().add_vertex(label, value=value, pid=pid)
        self._refresh_view()
        return rec

    def del_vertex(self, gid: int) -> "Any":
        rec = self._require_mutable().del_vertex(gid)
        self._refresh_view()
        return rec

    def compact(self, pid: int) -> int:
        """Fold one partition's pending deltas into a fresh shard
        generation (manifest commit is the publish point) and advance the
        session's view; returns the published generation.  Queries pinned
        to older views keep serving them until released."""
        mdir = self._require_mutable()
        gen = mdir.compact(int(pid))
        self._refresh_view()
        return gen

    def compact_all(self) -> int:
        mdir = self._require_mutable()
        gen = mdir.compact_all()
        self._refresh_view()
        return gen

    def compact_hot(self, min_pending: int = 1) -> List[int]:
        """Compact every partition with at least ``min_pending`` pending
        delta records — the background maintenance policy the mutation
        soak (launch/serve.py --mutate-workload) runs between queries.
        Returns the pids compacted."""
        mdir = self._require_mutable()
        pending = mdir.pending_counts()
        hot = [p for p in range(self.k) if int(pending[p]) >= min_pending]
        for p in hot:
            mdir.compact(p)
        if hot:
            self._refresh_view()
        return hot

    def fold(self, repartition: bool = False, *,
             seed: Optional[int] = None,
             config: Optional[Any] = None) -> Dict[str, Any]:
        """Fold the overlay into a brand-new full layout on disk and
        rebind the session to it — the heavyweight maintenance step
        ``compact`` amortizes away, and (with ``repartition=True``) the
        continuous-repartitioning trigger: hot-update partitions observed
        by ``workload_profile()`` reshape the layout, the new generation
        is re-``save``d in the background of pinned readers, and the
        session ``open``s it live.  Returns the published manifest."""
        mdir = self._require_mutable()
        if repartition:
            from .repartition import RepartitionConfig, repartition as _repart
            cfg = config if config is not None else RepartitionConfig()
            new_pg = _repart(self.pg, self.workload_profile(),
                             seed=seed, config=cfg)
            self.repartitions += 1
        else:
            new_pg = build_partitions(
                self.graph,
                np.asarray(self._view.assignment, dtype=np.int64),
                self.k, scheme=self.scheme)
        manifest = mdir.resave(new_pg)
        old = self._view
        self._view = mdir.snapshot()
        if old is not None:
            old.release()
        self._backing = mdir.catalog
        # a full re-layout invalidates pid meanings — rebind the whole
        # stack (store, engine, profile counters), exactly as
        # ``repartition()`` does for in-RAM sessions
        self._bind(self._view.as_partitioned_graph())
        self.graph = self.pg.graph
        self.catalog = build_catalog(self.graph)
        return manifest

    # -- the WawPart loop --------------------------------------------------

    def repartition(self, profile: Optional[Any] = None, *,
                    seed: Optional[int] = None,
                    config: Optional[Any] = None) -> Dict[str, Any]:
        """Re-layout the graph from observed traffic and rebind the session.

        ``profile`` is a ``workload_profile()`` dict or a
        ``save_profile()`` JSON path; None uses everything this session has
        served so far.  The store, compiled evaluators, and engine are
        rebuilt against the new assignment — cached single-partition
        entries and stacked bundles of the old layout are all invalidated
        (their pids/paddings no longer mean the same thing) — and the
        profile counters restart from zero for the new layout.  The graph,
        catalog, engine choice, cache capacities, and k are unchanged.

        Returns a summary dict: scheme/cut before and after, k, and which
        repartition round this is (``GraphSession.repartitions``).
        """
        from .partition import partition_quality
        from .repartition import RepartitionConfig, repartition as _repart
        prof = profile if profile is not None else self.workload_profile()
        cfg = config if config is not None else RepartitionConfig()
        before = partition_quality(self.graph, self.pg.assignment, self.k)
        new_pg = _repart(self.pg, prof, seed=seed, config=cfg)
        # a disk-opened session's backing names the OLD layout's shards —
        # drop it before rebinding so the fresh store pins the new in-RAM
        # partitions instead (and _bind closes the old store, joining any
        # in-flight read-ahead and invalidating its host-cache entries).
        # The graph directory on disk is untouched until save() writes
        # the new layout back (fresh manifest last).  A mutable session
        # moves in-RAM too: its view pin is released and further mutate()
        # calls raise (use fold(repartition=True) to re-layout in place).
        if self._view is not None:
            self._view.release()
            self._view = None
            self._mdir = None
        self._backing = None
        self._bind(new_pg)
        self.repartitions += 1
        after = partition_quality(self.graph, new_pg.assignment, self.k)
        return {"round": self.repartitions, "k": self.k,
                "scheme": self.scheme,
                "cut_before": before["cut"], "cut_after": after["cut"],
                "imbalance_after": after["imbalance"]}
