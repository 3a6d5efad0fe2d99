"""Smoke test of the partitioned query server on a TPU, at the paper's scale.

    python chip_smoke.py [--seed N]          # one chip
    python chip_smoke.py --four-chips        # four chips, MapReduceMP only

The graph is the paper's synthetic deployment (Sec. 7): 400K vertices,
1.2M edges and 200 planted template instances, generated from ``--seed``
and split into k=4 partitions by ``kway_shem``.  The queries are the
paper's Q4-Q6 (``subgen_queries``), served through ``GraphSession``, the
entry point ``launch/serve.py`` drives.  Every answer set is checked
against the whole-graph oracle (``core/oracle.py``) with serve's
``--verify`` equality.  Phases, each logged on its own lines:

  device  the default backend must be a TPU; there is no CPU fallback
  build   generate and partition the graph (host seconds, partition bytes)
  single  one ``submit`` per query (OPAT, MAX-SN): loads, cold/warm,
          latency and compile seconds per query; every array in the
          session's device cache must live on the TPU
  shared  the queries cycled into a batch of 8 through
          ``submit_many``: answers identical to ``single``
  traced  the queries with a ``Tracer`` attached: every kernel cost
          attribution must succeed
  kernel  one query with the fused Pallas kernel (``use_pallas=True``):
          answers identical to ``single``, and the lowered evaluator holds
          the compiled kernel (``tpu_custom_call``), not the interpreter

``--four-chips`` runs only MapReduceMP with one partition per chip on a
4-device ``("part",)`` mesh, compared with OPAT on the same graph.

The persistent compilation cache is on (``launch/compile_cache.py``), so a
second run in the same place compiles less.  The last line of stdout is
``{"ok": true, "device": {"platform", "kind", "count"}}``; any failed
check exits non-zero without printing it.
"""
from __future__ import annotations

import argparse
import json
import sys
import time
from pathlib import Path

import numpy as np

sys.path.insert(0, str(Path(__file__).resolve().parent / "src"))

PAPER_SCALE = dict(n_nodes=400_000, n_edges=1_200_000, n_embed=200)
K = 4
SCHEME = "kway_shem"
CAP = 16384          # launch/serve.py's default evaluator capacity
BATCH = 8            # one scheduler bucket (core/scheduler.batch_bucket)

# lowering to StableHLO and XLA's compile (or a persistent cache read);
# tracing is left out because nested jits report nested trace events
_COMPILE_EVENTS = ("/jax/core/compile/jaxpr_to_mlir_module_duration",
                   "/jax/core/compile/backend_compile_duration")


class SmokeFailure(Exception):
    pass


def check(cond: bool, what: str) -> None:
    if not cond:
        raise SmokeFailure(what)


def log(msg: str) -> None:
    print(msg, flush=True)


class CompileClock:
    """Seconds JAX spent lowering and compiling (a persistent cache hit
    counts its read), and the cache hits, from JAX's monitoring events."""

    def __init__(self):
        import jax.monitoring
        self.seconds = 0.0
        self.cache_hits = 0
        jax.monitoring.register_event_duration_secs_listener(self._duration)
        jax.monitoring.register_event_listener(self._event)

    def _duration(self, event, secs, **_):
        if event in _COMPILE_EVENTS:
            self.seconds += secs

    def _event(self, event, **_):
        if event == "/jax/compilation_cache/cache_hits":
            self.cache_hits += 1


def device_phase() -> dict:
    import jax
    devs = jax.devices()
    d = devs[0]
    log(f"[device] platform={d.platform} kind={d.device_kind} "
        f"count={len(devs)}")
    check(d.platform == "tpu",
          f"no TPU: JAX's default device is on {d.platform!r}")
    return {"platform": d.platform, "kind": d.device_kind,
            "count": len(devs)}


def build_phase(seed: int, scale: dict):
    """The partitioned graph, its queries, and the oracle's answers."""
    from repro.core import build_partitions, partition_graph
    from repro.core.engine import part_to_device_dict
    from repro.core.oracle import match_disjunctive
    from repro.data.generators import subgen_like_graph, subgen_queries
    from repro.storage.host_cache import bundle_nbytes

    t0 = time.perf_counter()
    graph = subgen_like_graph(**scale, seed=seed)
    t1 = time.perf_counter()
    assign = partition_graph(graph, K, SCHEME, seed=seed)
    pg = build_partitions(graph, assign, K, scheme=SCHEME)
    t2 = time.perf_counter()
    log(f"[build] {graph.n_nodes} vertices, {graph.n_edges} edges "
        f"(seed {seed}): generated in {t1 - t0} s, partitioned k={K} "
        f"{SCHEME} in {t2 - t1} s (host)")
    log(f"[build] padded geometry: Np={pg.node_pad} W={pg.ell_width}, "
        f"{pg.cut_edges} cut edges")
    for p in pg.parts:
        nbytes = bundle_nbytes(part_to_device_dict(p), pg.g2l[p.pid])
        log(f"[build] partition {p.pid}: {p.n_core} core vertices, "
            f"{nbytes} bytes")
    queries = subgen_queries(graph)
    t3 = time.perf_counter()
    refs = {dq.name: match_disjunctive(graph, dq, q_pad=8) for dq in queries}
    log(f"[build] oracle: "
        + ", ".join(f"{n}={r.shape[0]}" for n, r in refs.items())
        + f" answers in {time.perf_counter() - t3} s (host)")
    return graph, pg, queries, refs


def _check_oracle(phase: str, name: str, answers, refs) -> None:
    from repro.launch.serve import oracle_match
    ok = oracle_match(answers, refs[name], None)
    log(f"[{phase}] {name}: oracle {refs[name].shape[0]} answers "
        f"{'MATCH' if ok else 'MISMATCH'}")
    check(ok, f"{phase}: {name} does not match the oracle")


def _session(pg, **kw):
    from repro.core import EngineConfig, GraphSession
    config = kw.pop("config", EngineConfig(cap=CAP))
    return GraphSession(pg=pg, heuristic="max-sn", config=config, **kw)


def single_phase(pg, queries, refs, clock: CompileClock):
    import jax
    sess = _session(pg, engine="opat")
    answers = {}
    for dq in queries:
        c0 = clock.seconds
        res = sess.submit(dq)
        ls = res.load_stats
        log(f"[single] {dq.name}: {res.n_answers} answers, "
            f"loads={[s.loads for s in res.stats]} cold={ls.cold_loads} "
            f"warm={ls.warm_loads} prefetch_hits={ls.prefetch_hits}, "
            f"latency {res.latency_s} s, compile {clock.seconds - c0} s")
        _check_oracle("single", dq.name, res.answers, refs)
        answers[dq.name] = res.answers
    # the store holds its device cache in _cache (obs/profile.py reads it
    # the same way); every staged array must be on the chip
    arrays = [sess.store.owner]
    for entry in sess.store._cache.values():
        arrays += [*entry.part.values(), entry.g2l]
    platforms = {d.platform for a in arrays for d in a.devices()}
    log(f"[single] {len(arrays)} cached device arrays on {sorted(platforms)}")
    check(platforms == {"tpu"}, f"cached arrays live on {platforms}")
    stats = jax.devices()[0].memory_stats() or {}
    log(f"[single] device peak_bytes_in_use="
        f"{stats.get('peak_bytes_in_use', 'not reported')}")
    return answers, sess


def shared_phase(sess, queries, answers) -> None:
    batch = [queries[i % len(queries)] for i in range(BATCH)]
    report = sess.submit_many(batch)
    lat = [r.latency_s for r in report.results]
    log(f"[shared] {len(batch)} queries in {report.wall_s} s, "
        f"{report.n_loads} loads, batch sizes {report.batch_sizes}, "
        f"latency p50 {float(np.percentile(lat, 50))} s "
        f"max {max(lat)} s")
    for dq, res in zip(batch, report.results):
        check(np.array_equal(res.answers, answers[dq.name]),
              f"shared: {dq.name} differs from the single-query answers")
    log(f"[shared] all {len(batch)} answer sets identical to single")


def traced_phase(pg, queries, answers) -> None:
    from repro.obs import Tracer
    sess = _session(pg, engine="opat", tracer=Tracer())
    for dq in queries:
        res = sess.submit(dq)
        check(np.array_equal(res.answers, answers[dq.name]),
              f"traced: {dq.name} differs from the single-query answers")
    costs = sess.profiler.kernel_costs
    spans = [s for s in sess.tracer.spans if s.name == "kernel.eval"]
    log(f"[traced] {len(sess.tracer.spans)} spans, {len(spans)} kernel.eval")
    for key, cost in costs.items():
        log(f"[traced] cost {key}: {json.dumps(cost)}")
    check(bool(costs), "traced: no kernel cost was attributed")
    bad = {k: c["cost_error"] for k, c in costs.items() if "cost_error" in c}
    check(not bad, f"traced: cost attribution failed: {bad}")


def kernel_phase(pg, graph, queries, answers, clock: CompileClock) -> None:
    from repro.core import EngineConfig, generate_plan
    from repro.core.plan import PlanArrays
    cfg = EngineConfig(cap=CAP, use_pallas=True)
    sess = _session(pg, engine="opat", config=cfg)
    dq = max(queries, key=lambda d: d.disjuncts[0].n_nodes)
    c0 = clock.seconds
    res = sess.submit(dq)
    log(f"[kernel] {dq.name}: {res.n_answers} answers, latency "
        f"{res.latency_s} s, compile {clock.seconds - c0} s")
    check(np.array_equal(res.answers, answers[dq.name]),
          f"kernel: {dq.name} differs from the jnp evaluator's answers")
    # lower the session's evaluator on a real partition: the fused kernel
    # must be a Mosaic custom call, which interpret mode never emits
    plan = generate_plan(dq.disjuncts[0], graph, sess.catalog)
    entry = sess.store.get(0)
    text = sess.engine._eval.lower(
        entry.part, entry.g2l, sess.store.owner,
        PlanArrays.from_plan(plan, pad_steps=cfg.s_pad),
        np.int32(plan.n_steps), np.full((CAP, cfg.q_pad), -1, np.int32),
        np.zeros(CAP, np.int32), np.zeros(CAP, bool), np.bool_(True),
    ).as_text()
    n_calls = text.count("tpu_custom_call")
    log(f"[kernel] lowered evaluator: {n_calls} tpu_custom_call")
    check(n_calls > 0, "kernel: the evaluator holds no compiled kernel")


def four_chip_phase(pg, queries, refs) -> None:
    import jax
    from repro.core.mapreduce_mp import make_part_mesh
    mesh = make_part_mesh(K)
    expect = list(mesh.devices.flat)
    check(len(set(expect)) == K and set(expect) <= set(jax.devices()),
          f"four: the mesh spans {expect}, not {K} devices")
    opat = _session(pg, engine="opat")
    mr = _session(pg, engine="mapreduce", mesh=mesh)
    for dq in queries:
        want = opat.submit(dq)
        got = mr.submit(dq)
        log(f"[four] {dq.name}: MapReduceMP {got.n_answers} answers in "
            f"{got.latency_s} s, OPAT {want.n_answers} in {want.latency_s} s")
        check(np.array_equal(got.answers, want.answers),
              f"four: MapReduceMP and OPAT differ on {dq.name}")
        _check_oracle("four", dq.name, got.answers, refs)
    # the stacked bundle: partition p must sit on mesh device p alone
    (entry,) = [e for e in mr.store._cache.values()
                if isinstance(e.key, tuple)]
    for name, arr in [*entry.part.items(), ("g2l", entry.g2l)]:
        placed = {s.index[0].start: s.device for s in arr.addressable_shards}
        check(sorted(placed) == list(range(K))
              and [placed[p] for p in range(K)] == expect,
              f"four: {name} shards placed as {placed}")
    log(f"[four] partitions 0..{K - 1} on devices "
        f"{[d.id for d in expect]}, one each")


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--four-chips", action="store_true",
                    help="run only MapReduceMP on four chips, compared "
                         "with OPAT")
    args = ap.parse_args(argv)
    try:
        device = device_phase()
        if args.four_chips:
            check(device["count"] == K,
                  f"--four-chips needs {K} chips, found {device['count']}")
        from repro.launch import compile_cache
        path = compile_cache.enable()
        n_cached = len(list(Path(path).glob("*"))) if Path(path).is_dir() else 0
        log(f"[device] compile cache {path}: {n_cached} entries")
        clock = CompileClock()
        graph, pg, queries, refs = build_phase(args.seed, PAPER_SCALE)
        if args.four_chips:
            four_chip_phase(pg, queries, refs)
        else:
            answers, sess = single_phase(pg, queries, refs, clock)
            shared_phase(sess, queries, answers)
            traced_phase(pg, queries, answers)
            kernel_phase(pg, graph, queries, answers, clock)
        log(f"[done] compile {clock.seconds} s in all, "
            f"{clock.cache_hits} persistent cache hits")
    except SmokeFailure as e:
        print(f"chip_smoke FAILED: {e}", file=sys.stderr, flush=True)
        return 1
    print(json.dumps({"ok": True, "device": device}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
