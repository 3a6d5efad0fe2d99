"""Run one benchmark cell once and print its result line.

    python3 bench/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

A cell is one entry of ``workloads`` in ``BENCHMARK.json``: a
configuration (``bench/configs/<config>.json``, found through the entry of
``configs`` that names it) under a traffic mix
(``bench/traffic/<traffic>.json``).  A run:

1. checks for a TPU with as many chips as the cell asks (no CPU fallback)
   and turns on JAX's persistent compilation cache in ``.jax_cache/`` of
   the checkout;
2. opens the seed's saved graph directory (``bench/datacache.py``),
   building it first on a miss: the configuration's graph (drawn from its
   data block's seed, partitioned by the program once per checkout)
   renumbered by a permutation drawn from ``--seed`` (``bench/datagen/``)
   and saved; the seconds of that build are printed on their own line and
   are not set-up;
3. opens it with ``GraphSession.open``, stages it and warms every program
   the window will run; ``setup_s`` runs from process start to the first
   timed request, less the build;
4. drives the mix for ``--seconds``: a closed loop through
   ``GraphSession.submit``, or an open loop through
   ``GraphSession.scheduler()`` (``admit`` when due, ``run(max_rounds=1)``
   while requests are pending);
5. compares every answer set due in the window with the plain reference
   (``bench/reference.py``) over the graph regenerated from the seed.

With ``--trace 1`` the session carries the program's ``Tracer`` and the
last seconds of the window are traced with ``jax.profiler``; the line then
holds the cell's per-layer metrics, each read by
``bench/metrics/<metric>.py``, and a ``breakdown``.  With ``--trace 0`` it
holds the cell's end-to-end metrics.

``--rehearse`` runs the same steps on the CPU at the sizes of
``bench/rehearsal.json``, without the persistent cache; it exists for the
tests under ``bench/tests/`` and for trying the harness without a chip.
"""
from __future__ import annotations

import time

T_PROCESS = time.perf_counter()

import argparse  # noqa: E402
import contextlib  # noqa: E402
import dataclasses  # noqa: E402
import gc  # noqa: E402
import importlib  # noqa: E402
import importlib.util  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import shutil  # noqa: E402
import sys  # noqa: E402
import tempfile  # noqa: E402
import traceback  # noqa: E402
from collections import deque  # noqa: E402
from pathlib import Path  # noqa: E402
from typing import Any, Dict, List, Optional  # noqa: E402

import numpy as np  # noqa: E402

BENCH = Path(__file__).resolve().parent
ROOT = BENCH.parent
for _p in (BENCH, ROOT / "src"):
    if str(_p) not in sys.path:
        sys.path.insert(0, str(_p))

import datacache  # noqa: E402
import datagen as datagen_pkg  # noqa: E402
import reference  # noqa: E402
import tracereduce  # noqa: E402
import traffic  # noqa: E402

PROFILE_S = 5.0           # traced seconds at the end of a --trace 1 window
DRAIN_S = 60.0            # how long past the window's close answers may come
_COMPILE = "/jax/core/compile/backend_compile_duration"
_LOWER = "/jax/core/compile/jaxpr_to_mlir_module_duration"
_CACHE_HIT = "/jax/compilation_cache/cache_hits"
_CACHE_MISS = "/jax/compilation_cache/cache_misses"
# what a saved graph directory is built from: the program and the generator
BUILD_INPUTS = (ROOT / "src", BENCH / "datagen")


class BenchError(Exception):
    """The run cannot be made: no chip, a malformed cell, no program."""


def log(msg: str) -> None:
    print(msg, flush=True)


# -- the cell ---------------------------------------------------------------

@dataclasses.dataclass
class Cell:
    name: str
    chips: int
    config: dict
    mix: dict
    queries: Dict[str, dict]            # name -> query dict, in file order
    end_to_end: List[dict]
    per_layer: List[dict]


def _apply(base: dict, over: dict) -> dict:
    out = dict(base)
    for k, v in over.items():
        out[k] = _apply(out[k], v) if isinstance(v, dict) and k in out else v
    return out


def load_cell(name: str, rehearse: bool = False) -> Cell:
    spec_path = ROOT / "BENCHMARK.json"
    if not spec_path.exists():
        raise BenchError(f"{spec_path} is missing")
    spec = json.loads(spec_path.read_text())
    work = next((w for w in spec["workloads"] if w["name"] == name), None)
    if work is None:
        raise BenchError(f"no workload {name!r} in BENCHMARK.json")
    entry = next(c for c in spec["configs"] if c["name"] == work["config"])
    config = json.loads((ROOT / entry["file"]).read_text())
    if rehearse:
        config = _apply(config, json.loads((BENCH / "rehearsal.json").read_text()))
    mix = traffic.load_mix(work["traffic"])
    with open(BENCH / "queries" / f"{config['queries']}.json") as f:
        queries = {q["name"]: q for q in json.load(f)}
    e2e = [m for m in spec["end_to_end"]
           if name in m.get("workloads", [name])]
    reported = {m["name"] for m in e2e}
    per_layer = [m for m in spec["per_layer"]
                 if (name in m["workloads"] if "workloads" in m
                     else m["moves"] in reported)]
    return Cell(name=name, chips=int(work["chips"]), config=config, mix=mix,
                queries=queries, end_to_end=e2e, per_layer=per_layer)


# -- the device -------------------------------------------------------------

def devices(chips: int, rehearse: bool):
    import jax
    devs = jax.devices()
    d0 = devs[0]
    if not rehearse:
        if d0.platform != "tpu":
            raise BenchError(f"no TPU: JAX's default device is {d0.platform!r}")
        peaks = json.loads((BENCH / "peaks.json").read_text())
        if d0.device_kind not in peaks:
            raise BenchError(f"no peaks for device kind {d0.device_kind!r} "
                             f"in bench/peaks.json")
    if len(devs) < chips:
        raise BenchError(f"the cell needs {chips} chips, JAX finds {len(devs)}")
    return devs


def checkout_compile_cache() -> None:
    """Point JAX's persistent compilation cache at ``.jax_cache/`` in this
    checkout, whatever the environment says, before JAX starts; JAX does
    not create the directory itself."""
    path = ROOT / ".jax_cache"
    path.mkdir(exist_ok=True)
    os.environ["JAX_COMPILATION_CACHE_DIR"] = str(path)


def enable_compile_cache() -> str:
    """Turn the persistent cache on (``compile_cache.enable()``) in the
    checkout's directory, with no size limit.  Under a limit
    (``JAX_COMPILATION_CACHE_MAX_SIZE``) JAX reads an access-time file
    beside every entry before each write, and one entry without it,
    written by a process with no limit, makes every later write fail."""
    import jax
    from repro.launch import compile_cache
    path = compile_cache.enable()
    jax.config.update("jax_compilation_cache_dir", path)
    jax.config.update("jax_compilation_cache_max_size", -1)
    return path


def memory_peak(devs) -> int:
    peaks = [int((d.memory_stats() or {}).get("peak_bytes_in_use", 0))
             for d in devs]
    return max(peaks) if peaks else 0


class CompileCounter:
    """XLA compiles (or persistent-cache reads), lowerings and their
    seconds, and the persistent cache's hits and misses, from JAX's
    monitoring events."""

    def __init__(self):
        import jax.monitoring
        self.compiles = 0
        self.lowerings = 0
        self.seconds = 0.0
        self.hits = 0
        self.misses = 0
        jax.monitoring.register_event_duration_secs_listener(self._on)
        jax.monitoring.register_event_listener(self._on_event)

    def _on_event(self, event, **_):
        if event == _CACHE_HIT:
            self.hits += 1
        elif event == _CACHE_MISS:
            self.misses += 1

    def _on(self, event, secs, **_):
        if event == _COMPILE:
            self.compiles += 1
            self.seconds += secs
        elif event == _LOWER:
            self.lowerings += 1
            self.seconds += secs

    def snapshot(self):
        return self.compiles, self.lowerings, self.seconds, self.hits, self.misses


# -- the data ---------------------------------------------------------------

def datagen(config: dict):
    """The generator module ``bench/datagen/<generator>.py``."""
    return importlib.import_module(f"datagen.{config['data']['generator']}")


def base_graph(config: dict):
    """The configuration's one graph, drawn from its data block's seed."""
    data = config["data"]
    return datagen(config).generate(data, int(data["seed"]))


def seed_graph(config: dict, seed: int):
    """The run's graph: the configuration's graph renumbered by a
    permutation drawn from ``seed``; returns it and the permutation."""
    ga = base_graph(config)
    perm = datagen_pkg.permutation(ga.n_nodes, seed)
    return datagen_pkg.relabel(ga, perm), perm


def program_graph(ga):
    """The benchmark's ``GraphArrays`` as the program's ``Graph``."""
    from repro.core.graph import Graph, LabelVocab
    nv, ev = LabelVocab(), LabelVocab()
    for s in ga.node_vocab:
        nv.intern(s)
    for s in ga.edge_vocab:
        ev.intern(s)
    g = Graph(n_nodes=ga.n_nodes, node_label=ga.node_label,
              node_value=ga.node_value, edge_src=ga.edge_src,
              edge_dst=ga.edge_dst, edge_label=ga.edge_label,
              edge_directed=ga.edge_directed, node_vocab=nv, edge_vocab=ev)
    g.validate()
    return g


def partition_base(config: dict, path: Path) -> dict:
    """Partition the configuration's graph with the program, once."""
    from repro.core.partition import partition_graph
    part = config["partition"]
    t0 = time.perf_counter()
    g = program_graph(base_graph(config))
    t1 = time.perf_counter()
    assign = partition_graph(g, int(part["k"]), part["scheme"],
                             seed=int(config["data"]["seed"]))
    np.save(path / "assignment.npy", assign)
    return {"generate_s": t1 - t0, "partition_s": time.perf_counter() - t1}


def build(config: dict, seed: int, path: Path) -> dict:
    """The seed's graph directory: the configuration's partition (made
    once, ``partition_base``) carried through the seed's renumbering,
    materialised and saved by the program."""
    from repro.core.graph import build_partitions
    from repro.storage.format import save_partitioned_graph
    part = config["partition"]
    base, base_s, base_info = datacache.ensure(
        BENCH / ".cache" / "base", config, int(config["data"]["seed"]),
        datacache.tree_digest(*BUILD_INPUTS),
        lambda p: partition_base(config, p))
    if base_s is not None:
        log(f"[build] partitioned the configuration's graph in {base_s} s "
            f"(generate {base_info['generate_s']} s, partition "
            f"{base_info['partition_s']} s)")
    t0 = time.perf_counter()
    ga, perm = seed_graph(config, seed)
    assign = np.empty(ga.n_nodes, np.int32)
    assign[perm] = np.load(base / "assignment.npy")
    g = program_graph(ga)
    t1 = time.perf_counter()
    pg = build_partitions(g, assign, int(part["k"]), scheme=part["scheme"])
    t2 = time.perf_counter()
    manifest = save_partitioned_graph(pg, str(path / "graph"))
    t3 = time.perf_counter()
    return {"renumber_s": t1 - t0, "materialise_s": t2 - t1, "save_s": t3 - t2,
            "n_nodes": g.n_nodes, "n_edges": g.n_edges,
            "node_pad": pg.node_pad, "ell_width": pg.ell_width,
            "cut_edges": pg.cut_edges,
            "part_bytes": [p["nbytes"] for p in manifest["partitions"]]}


def graph_dir(cell: Cell, seed: int) -> Path:
    program = datacache.tree_digest(*BUILD_INPUTS)
    path, build_s, info = datacache.ensure(
        BENCH / ".cache" / "seeds", cell.config, seed, program,
        lambda p: build(cell.config, seed, p))
    if build_s is None:
        log(f"[build] cache hit {path.relative_to(BENCH.parent)}")
    else:
        log(f"[build] cache miss: built {path.relative_to(BENCH.parent)} in "
            f"{build_s} s (renumber {info['renumber_s']} s, materialise "
            f"{info['materialise_s']} s, save {info['save_s']} s; not set-up)")
    log(f"[build] {info['n_nodes']} vertices, {info['n_edges']} edges, "
        f"Np={info['node_pad']} W={info['ell_width']}, "
        f"{info['cut_edges']} cut edges, partition bytes {info['part_bytes']}")
    return path / "graph", (build_s or 0.0)


# -- the session ------------------------------------------------------------

def engine_config(cell: Cell):
    """The configuration's ``engine.config`` as the program's
    ``EngineConfig``: any of its fields, the rest at their defaults."""
    from repro.core import EngineConfig
    return EngineConfig(**cell.config["engine"]["config"])


def open_session(cell: Cell, gdir: Path, tracer=None):
    from repro.core import GraphSession
    from repro.obs.profile import NULL_PROFILER
    eng, store = cell.config["engine"], cell.config["store"]
    return GraphSession.open(
        str(gdir), engine=eng["engine"], heuristic=eng["heuristic"],
        config=engine_config(cell),
        cache_parts=store["cache_parts"],
        host_cache_parts=store["host_cache_parts"],
        read_ahead=bool(store["read_ahead"]),
        tracer=tracer, profiler=NULL_PROFILER)


def program_queries(cell: Cell):
    from repro.core.query import DisjunctiveQuery
    return {n: DisjunctiveQuery.from_json_dict(q) for n, q in cell.queries.items()}


def buckets(max_in_flight: int) -> List[int]:
    out, b = [], 1
    while b < max_in_flight:
        out.append(b)
        b *= 2
    return out + [b]


def warm(cell: Cell, sess, dq: dict) -> None:
    """Run every program the window can run once: each query; in an open
    loop, each scheduler batch bucket."""
    names = list(dq)
    if cell.mix["loop"] == "closed":
        for n in names:
            sess.submit(dq[n])
        return
    sched = sess.scheduler(heuristic=cell.mix.get("shared_heuristic"))
    try:
        for b in buckets(int(cell.mix["max_in_flight"])):
            for i in range(b):
                sched.admit(dq[names[i % len(names)]])
            sched.run()
    finally:
        sched.close()


# -- the window -------------------------------------------------------------

@dataclasses.dataclass
class Done:
    """One request of the window and what became of it (seconds from the
    window's start)."""

    query: str
    due: float                      # closed loop: when it was sent
    admitted: Optional[float] = None
    done: Optional[float] = None
    answers: Optional[np.ndarray] = None
    n_loads: int = 0
    error: Optional[str] = None

    @property
    def latency(self) -> Optional[float]:
        return None if self.done is None else self.done - self.due


class Profiler:
    """The ``--trace 1`` profile: the window's last seconds under
    ``jax.profiler``, with the harness's own calls annotated."""

    def __init__(self, enabled: bool):
        self.enabled = enabled
        self.dir: Optional[str] = None
        self.running = False
        self.marks: List[tuple] = []      # (name, t0, t1) perf_counter s
        self.pc0_ns = 0
        self._window = None

    @contextlib.contextmanager
    def mark(self, name: str):
        if not self.running:
            yield
            return
        import jax
        t0 = time.perf_counter()
        with jax.profiler.TraceAnnotation(name):
            yield
        self.marks.append((name, t0, time.perf_counter()))

    def start(self) -> None:
        if not self.enabled or self.running or self.dir is not None:
            return
        import jax
        self.dir = tempfile.mkdtemp(prefix="bench-trace-")
        opts = jax.profiler.ProfileOptions()
        opts.python_tracer_level = 0
        opts.host_tracer_level = 1
        jax.profiler.start_trace(self.dir, profiler_options=opts)
        self._window = jax.profiler.TraceAnnotation(tracereduce.WINDOW)
        self._window.__enter__()
        self.pc0_ns = time.perf_counter_ns()
        self.running = True

    def stop(self) -> None:
        if not self.running:
            return
        import jax
        self._window.__exit__(None, None, None)
        jax.profiler.stop_trace()
        self.running = False


def closed_loop(sess, dq, reqs, seconds: float, prof: Profiler) -> List[Done]:
    out: List[Done] = []
    t0 = time.perf_counter()
    for r in reqs:
        now = time.perf_counter() - t0
        if now >= seconds:
            break
        if now >= seconds - PROFILE_S:
            prof.start()
        rec = Done(query=r.query, due=now)
        try:
            with prof.mark("submit"):
                res = sess.submit(dq[r.query])
            rec.answers, rec.n_loads = res.answers, res.n_loads
        except Exception:
            rec.error = traceback.format_exc(limit=3)
        rec.done = time.perf_counter() - t0
        rec.admitted = rec.due
        out.append(rec)
    prof.stop()
    return out


def open_loop(sess, dq, reqs, seconds: float, mix: dict, prof: Profiler,
              drain_s: float = DRAIN_S):
    """Admit each request when due, pump one scheduler round at a time,
    and after the window's close serve what is pending for up to
    ``drain_s``.  Returns the requests and the scheduler's partition
    loads."""
    sched = sess.scheduler(heuristic=mix.get("shared_heuristic"))
    limit = int(mix["max_in_flight"])
    out = [Done(query=r.query, due=r.due_s) for r in reqs]
    queue: deque = deque()
    inflight: Dict[int, Done] = {}
    loads, nxt = 0, 0
    t0 = time.perf_counter()
    try:
        while True:
            now = time.perf_counter() - t0
            if now >= seconds - PROFILE_S and now < seconds:
                prof.start()
            if now >= seconds:
                prof.stop()
            while nxt < len(out) and out[nxt].due <= now:
                queue.append(out[nxt])
                nxt += 1
            while queue and len(inflight) < limit:
                rec = queue.popleft()
                with prof.mark("admit"):
                    qid = sched.admit(dq[rec.query])
                rec.admitted = time.perf_counter() - t0
                inflight[qid] = rec
            if inflight:
                with prof.mark("run"):
                    rep = sched.run(max_rounds=1)
                t = time.perf_counter() - t0
                loads += rep.n_loads
                for res in rep.results:
                    rec = inflight.pop(res.qid)
                    rec.done, rec.answers = t, res.answers
                    rec.n_loads = res.n_loads
            elif nxt < len(out) or now < seconds:
                until = out[nxt].due if nxt < len(out) else seconds
                with prof.mark("wait"):
                    time.sleep(max(0.0, until - (time.perf_counter() - t0)))
            elif not queue:
                break
            if now > seconds + drain_s:
                break
    except Exception:
        err = traceback.format_exc(limit=3)
        for rec in list(inflight.values()) + list(queue):
            rec.error = err
    finally:
        prof.stop()
        sched.close()
    for rec in out:
        if rec.done is None and rec.error is None:
            rec.error = f"not answered within {drain_s} s of the close"
    return out, loads


# -- metrics ----------------------------------------------------------------

@dataclasses.dataclass
class RunRecord:
    """What a per-layer metric reader reads."""

    loop: str
    seconds: float
    requests: List[Done]
    scheduler_loads: int                      # open loop: the workload's loads
    spans: Optional[List[Any]] = None         # the program's spans in the window
    trace: Optional[tracereduce.Summary] = None

    @property
    def completed(self) -> List[Done]:
        return [r for r in self.requests if r.done is not None and r.error is None]


def end_to_end(rec: RunRecord, setup_s: float) -> Dict[str, float]:
    lat = [r.latency for r in rec.completed]
    in_window = [r for r in rec.completed if r.done <= rec.seconds]
    out = {"setup_s": setup_s, "qps": len(in_window) / rec.seconds}
    if lat:
        p50, p90 = np.percentile(np.asarray(lat) * 1e3, [50, 90])
        out.update(latency_p50_ms=float(p50), latency_p90_ms=float(p90))
    return out


def read_metric(name: str, rec: RunRecord) -> Optional[float]:
    path = BENCH / "metrics" / f"{name}.py"
    spec = importlib.util.spec_from_file_location(
        "bench_metric_" + name.replace(".", "_").replace("-", "_"), path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod.read(rec)


def host_intervals(spans, prof: Profiler, offset_ns: int):
    """Program spans and harness marks on the trace's clock, as
    ``(name, start_ns, end_ns, depth)``: marks at depth 0, spans nested
    under them by their parent chain."""
    out = [(n, int(a * 1e9) + offset_ns, int(b * 1e9) + offset_ns, 0)
           for n, a, b in prof.marks]
    by_id = {s.span_id: s for s in spans or []}
    for s in spans or []:
        if s.t1 is None:
            continue
        depth, p = 1, s.parent_id
        while p in by_id:
            depth, p = depth + 1, by_id[p].parent_id
        out.append((s.name, int(s.t0 * 1e9) + offset_ns,
                    int(s.t1 * 1e9) + offset_ns, depth))
    return out


# -- correctness -------------------------------------------------------------

def mismatch(answers: np.ndarray, ref: np.ndarray) -> int:
    """Rows by which a served answer set departs from the reference's:
    every reference row must be served, once, and nothing else."""
    want = {tuple(r) for r in ref}
    got = [tuple(r) for r in np.asarray(answers)]
    extra = len(got) - len(set(got)) + sum(1 for r in set(got) if r not in want)
    real = sum(1 for r in set(got) if r in want)
    return extra + (len(want) - real)


def check(cell: Cell, seed: int, requests: List[Done]):
    """Compare every answered request with the reference; returns the
    numbers compared, each with its limit."""
    t0 = time.perf_counter()
    ga, _ = seed_graph(cell.config, seed)
    needed = {r.query for r in requests}
    refs = reference.match_all(ga, {n: cell.queries[n] for n in needed},
                               engine_config(cell).q_pad)
    rows = sum(mismatch(r.answers, refs[r.query])
               for r in requests if r.answers is not None)
    unanswered = sum(1 for r in requests if r.answers is None)
    log(f"[check] reference over {ga.n_nodes} vertices in "
        f"{time.perf_counter() - t0} s: "
        + ", ".join(f"{n}={v.shape[0]}" for n, v in sorted(refs.items())))
    return {"mismatched_rows": {"value": rows, "limit": 0},
            "unanswered": {"value": unanswered, "limit": 0}}


# -- the run ----------------------------------------------------------------

def run(args) -> dict:
    rehearse = bool(args.rehearse)
    cell = load_cell(args.workload, rehearse)
    if not rehearse:
        checkout_compile_cache()
    devs = devices(cell.chips, rehearse)
    try:
        import repro.launch.compile_cache  # noqa: F401
    except ImportError as e:
        raise BenchError(f"the program is missing: {e}") from e
    if not rehearse:
        log(f"[device] compile cache {enable_compile_cache()}")
    counter = CompileCounter()
    t_dev = time.perf_counter()
    log(f"[device] {devs[0].platform} {devs[0].device_kind} x{len(devs)}")

    gdir, build_s = graph_dir(cell, args.seed)
    t_open0 = time.perf_counter()
    tracer = None
    if args.trace:
        from repro.obs import Tracer
        tracer = Tracer()
    sess = open_session(cell, gdir, tracer)
    t_open1 = time.perf_counter()
    if cell.config["store"]["cache_parts"] is None:
        for pid in range(sess.k):
            sess.store.get(pid)
    t_stage = time.perf_counter()
    dq = program_queries(cell)
    c0 = counter.snapshot()
    warm(cell, sess, dq)
    c1 = counter.snapshot()
    reqs = traffic.requests(cell.mix, list(cell.queries), args.seed,
                            args.seconds)
    t_window = time.perf_counter()
    setup_s = t_window - T_PROCESS - build_s
    log(f"[setup] {setup_s} s: start-up {t_dev - T_PROCESS} s, open "
        f"{t_open1 - t_open0} s, stage {t_stage - t_open1} s, warm-up "
        f"{t_window - t_stage} s ({c1[0] - c0[0]} compiles or cache reads, "
        f"{c1[1] - c0[1]} lowerings, {c1[2] - c0[2]} s in them; persistent "
        f"cache {c1[3]} hits, {c1[4]} writes)")

    prof = Profiler(bool(args.trace))
    if cell.mix["loop"] == "closed":
        done = closed_loop(sess, dq, reqs, args.seconds, prof)
        sched_loads = 0
    else:
        done, sched_loads = open_loop(sess, dq, reqs, args.seconds, cell.mix, prof)
    t_end = time.perf_counter()
    c2 = counter.snapshot()
    log(f"[window] {len(done)} requests in {args.seconds} s (+"
        f"{t_end - t_window - args.seconds} s to drain): {c2[0] - c1[0]} "
        f"compiles or cache reads, {c2[1] - c1[1]} lowerings in the window")
    if cell.mix["loop"] == "open":
        late = [r.admitted - r.due for r in done if r.admitted is not None]
        if late:
            log(f"[window] generator lateness (admit - due): mean "
                f"{float(np.mean(late))} s, max {float(np.max(late))} s")
    peak = memory_peak(devs[:cell.chips])
    rec = RunRecord(loop=cell.mix["loop"], seconds=float(args.seconds),
                    requests=done, scheduler_loads=sched_loads)
    rec.spans = ([s for s in tracer.spans if s.t0 >= t_window]
                 if tracer is not None else None)
    device = {"platform": devs[0].platform, "kind": devs[0].device_kind,
              "count": len(devs), "memory_peak_bytes": peak}
    breakdown = None
    if args.trace:
        planes = tracereduce.load_xplane(prof.dir)
        shutil.rmtree(prof.dir, ignore_errors=True)
        if rehearse and not tracereduce.device_planes(planes):
            log("[trace] no TPU in the trace: device metrics not measured")
        else:
            lo, _ = tracereduce.window(planes)
            host = host_intervals(rec.spans, prof, lo - prof.pc0_ns)
            rec.trace = tracereduce.reduce(planes, host)
            device.update(busy_s=rec.trace.busy_s, window_s=rec.trace.window_s)
            breakdown = {"device_ops": [list(x) for x in rec.trace.top_ops],
                         "idle_gaps": [list(x) for x in rec.trace.idle_gaps]}
            log(f"[trace] modules {json.dumps(rec.trace.modules)}")
            log(f"[trace] idle by host activity "
                f"{json.dumps(rec.trace.idle_by_label)}")
    completed = rec.completed
    log(f"[window] {len(completed)} completed, "
        f"{sum(1 for r in completed if r.done <= args.seconds)} inside the "
        f"window; latency samples {len(completed)}")

    if args.trace:
        metrics = {}
        for m in cell.per_layer:
            v = read_metric(m["name"], rec)
            if v is not None:
                metrics[m["name"]] = {"value": float(v), "unit": m["unit"]}
    else:
        vals = end_to_end(rec, setup_s)
        metrics = {m["name"]: {"value": float(vals[m["name"]]), "unit": m["unit"]}
                   for m in cell.end_to_end if m["name"] in vals}
    del sess, tracer
    gc.collect()
    checks = check(cell, args.seed, done)
    failed = sum(1 for r in done if r.error is not None)
    for r in done:
        if r.error:
            print(f"[error] {r.query}: {r.error}", file=sys.stderr)
            break
    correct = all(c["value"] <= c["limit"] for c in checks.values())
    out = {"correct": correct, "attempted": len(done), "failed": failed,
           "metrics": metrics, "device": device}
    if breakdown is not None:
        out["breakdown"] = breakdown
    out["checks"] = checks
    return out


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    ap.add_argument("--rehearse", action="store_true",
                    help="run on the CPU at the sizes of bench/rehearsal.json")
    args = ap.parse_args(argv)
    if args.seed < 0:
        ap.error("--seed must be a whole number >= 0")
    try:
        out = run(args)
    except BenchError as e:
        print(f"bench: {e}", file=sys.stderr, flush=True)
        return 2
    for name, c in out["checks"].items():
        print(f"check {name} {c['value']} limit {c['limit']}",
              file=sys.stderr, flush=True)
    print(json.dumps(out), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
