"""Every host stretch between two evaluations is a named span, the
evaluator's work is counted, and the spans have twins on a
``jax.profiler`` trace's clock (obs/trace.py, core/engine.traced_eval).
"""
import glob
import importlib.util
import os
import pathlib

import jax
import numpy as np
import pytest

from repro.core import (EngineConfig, GraphSession, MAX_YIELD_SHARED,
                        OPATEngine, PlanArrays, build_catalog,
                        build_partitions, generate_plan)
from repro.core.engine import EVAL_MODULE
from repro.data.generators import subgen_like_graph, subgen_queries
from repro.obs import Tracer, to_chrome_trace

ROOT = pathlib.Path(__file__).resolve().parent.parent
HOST_SPANS = ("query", "query.plan", "heuristics.rank", "opat.round",
              "store.load", "eval.inputs", "kernel.eval", "eval.launch",
              "eval.absorb")


@pytest.fixture(scope="module")
def setup():
    g = subgen_like_graph(n_nodes=250, n_edges=700, n_embed=10, seed=3)
    return g, {dq.name: dq for dq in subgen_queries(g)}


def make_session(g, **kw):
    return GraphSession(g, k=4, scheme="kway_shem", engine="opat", seed=1,
                        config=EngineConfig(cap=2048), **kw)


def _children(spans):
    out = {}
    for s in spans:
        out.setdefault(s.parent_id, []).append(s)
    return out


def _descendants(spans, root_id):
    kids = _children(spans)
    out, todo = [], [root_id]
    while todo:
        for s in kids.get(todo.pop(), []):
            out.append(s)
            todo.append(s.span_id)
    return out


def _trace_report():
    spec = importlib.util.spec_from_file_location(
        "trace_report", ROOT / "tools" / "trace_report.py")
    report = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(report)
    return report


def _annotations(log_dir):
    """``{name: [duration_s, ...]}`` of the host-plane events, by start."""
    from jax.profiler import ProfileData
    paths = glob.glob(os.path.join(log_dir, "**", "*.xplane.pb"),
                      recursive=True)
    assert len(paths) == 1, paths
    events = []
    for plane in ProfileData.from_file(paths[0]).planes:
        if plane.name.startswith("/device:"):
            continue
        for line in plane.lines:
            events += [(e.name, e.start_ns, e.duration_ns) for e in line.events]
    out = {}
    for name, start, dur in sorted(events, key=lambda e: e[1]):
        out.setdefault(name, []).append(dur / 1e9)
    return out


def test_every_context_span_has_one_annotation_twin(setup, tmp_path):
    g, dq = setup
    traced = make_session(g, tracer=Tracer())
    plain = make_session(g)
    for sess in (traced, plain):
        sess.submit(dq["Q5"])                          # compile outside
    traced.tracer.clear()
    jax.profiler.start_trace(str(tmp_path / "on"))
    traced.submit(dq["Q5"])
    jax.profiler.stop_trace()
    jax.profiler.start_trace(str(tmp_path / "off"))
    plain.submit(dq["Q5"])
    jax.profiler.stop_trace()

    spans = [s for s in traced.tracer.spans if s.name != "jit.compile"]
    assert {s.name for s in spans} == set(HOST_SPANS)
    twins = _annotations(str(tmp_path / "on"))
    for name in HOST_SPANS:
        mine = sorted((s for s in spans if s.name == name),
                      key=lambda s: s.t0)
        theirs = twins.get(name, [])
        assert len(theirs) == len(mine), name
        for s, d in zip(mine, theirs):
            assert abs(d - s.duration_s) <= max(0.1 * s.duration_s, 50e-6), \
                (name, d, s.duration_s)
    untraced = _annotations(str(tmp_path / "off"))
    assert not set(untraced) & set(HOST_SPANS)


def test_a_new_plan_shape_compiles_under_its_kernel_eval(setup):
    """Q4 and Q6 share a plan shape (3 slots, 2 steps), Q5 has another:
    only the first call of each shape compiles, and the compile nests
    under that call's ``kernel.eval``."""
    g, dq = setup
    tr = Tracer()
    sess = make_session(g, tracer=tr)

    def compiles_per_eval(query):
        tr.clear()
        sess.submit(dq[query])
        spans = tr.spans
        evals = sorted((s for s in spans if s.name == "kernel.eval"),
                       key=lambda s: s.t0)
        per = [sum(1 for d in _descendants(spans, k.span_id)
                   if d.name == "jit.compile") for k in evals]
        assert sum(1 for s in spans if s.name == "jit.compile") == sum(per)
        return per, spans

    first, spans = compiles_per_eval("Q4")
    assert first[0] >= 1 and not any(first[1:])
    comp = [s for s in spans if s.name == "jit.compile"]
    assert all(s.attrs["secs"] > 0 for s in comp)
    by_id = {s.span_id: s for s in spans}
    assert {by_id[s.parent_id].name for s in comp} == {"eval.launch"}
    assert not any(compiles_per_eval("Q6")[0])        # same shape: none
    again, _ = compiles_per_eval("Q5")                # new shape
    assert again[0] >= 1 and not any(again[1:])
    assert not any(compiles_per_eval("Q5")[0])


def test_trace_report_steady_state_leaves_out_the_compiling_call(setup):
    g, dq = setup
    tr = Tracer()
    sess = make_session(g, tracer=tr)
    sess.submit(dq["Q4"])
    sess.submit(dq["Q4"])
    report = _trace_report()
    events = [e for e in to_chrome_trace(tr)["traceEvents"]
              if e.get("ph") == "X"]
    compiled = report.compiled_span_ids(events)
    kernel = sorted((s for s in tr.spans if s.name == "kernel.eval"),
                    key=lambda s: s.t0)
    assert kernel[0].span_id in compiled
    assert not compiled & {s.span_id for s in kernel[1:]}
    assert report.check_counters(events) == []
    del events[[e["name"] for e in events].index("kernel.eval")]["args"][
        "n_iters"]
    assert report.check_counters(events)


def _eval_totals(spans):
    k = [s for s in spans if s.name == "kernel.eval"]
    return (sum(s.attrs["n_iters"] for s in k),
            sum(s.attrs["n_expanded"] for s in k))


def _stat_totals(results):
    stats = [s for r in results for s in r.stats]
    return (sum(s.eval_iters for s in stats),
            sum(s.rows_expanded for s in stats))


@pytest.mark.parametrize("path", ["opat", "scheduler"])
def test_run_stats_carry_the_evaluator_counters(setup, path):
    g, dq = setup
    queries = [dq[n] for n in ("Q4", "Q5", "Q6")]
    traced = make_session(g, tracer=Tracer())
    plain = make_session(g)
    if path == "opat":
        on = [traced.submit(q) for q in queries]
        off = [plain.submit(q) for q in queries]
    else:
        on = traced.submit_many(queries, heuristic=MAX_YIELD_SHARED).results
        off = plain.submit_many(queries, heuristic=MAX_YIELD_SHARED).results
    assert _stat_totals(on) == _eval_totals(traced.tracer.spans)
    assert _stat_totals(on)[0] > 0 and _stat_totals(on)[1] > 0
    for a, b in zip(on, off):
        assert [(s.eval_iters, s.rows_expanded) for s in a.stats] == \
            [(s.eval_iters, s.rows_expanded) for s in b.stats]


def test_mapreduce_stamps_trips_and_leaves_expansion_uncounted(setup):
    """MapReduceMP's SPMD program counts its trips but not the rows it
    expands: ``n_iters`` alone on its ``kernel.eval``, which
    ``trace_report --check`` accepts, and ``rows_expanded`` None."""
    from repro.core.mapreduce_mp import MapReduceMPEngine, make_part_mesh
    g, dq = setup
    pg = build_partitions(g, np.zeros(g.n_nodes, dtype=np.int32), 1)
    tr = Tracer()
    eng = MapReduceMPEngine(pg, make_part_mesh(1), EngineConfig(cap=2048),
                            tracer=tr)
    res = eng.run(generate_plan(dq["Q4"].disjuncts[0], g, build_catalog(g)))
    kernel = [s for s in tr.spans if s.name == "kernel.eval"]
    assert len(kernel) == 1 and "n_expanded" not in kernel[0].attrs
    assert res.stats.eval_iters == kernel[0].attrs["n_iters"] \
        == res.n_iterations > 0
    assert res.stats.rows_expanded is None
    assert _trace_report().check_counters(
        [e for e in to_chrome_trace(tr)["traceEvents"]
         if e.get("ph") == "X"]) == []


def test_single_and_batched_evaluators_lower_to_the_pinned_module(setup):
    g, dq = setup
    sess = make_session(g)
    eng: OPATEngine = sess.engine
    cfg = sess.config
    plan = generate_plan(dq["Q4"].disjuncts[0], g, build_catalog(g))
    pa = PlanArrays.from_plan(plan, pad_steps=cfg.s_pad)
    entry = sess.store.get(0)

    def inputs(*lead):
        return (np.full(lead + (cfg.cap, cfg.q_pad), -1, np.int32),
                np.zeros(lead + (cfg.cap,), np.int32),
                np.zeros(lead + (cfg.cap,), bool))

    single = eng._eval.lower(entry.part, entry.g2l, sess.store.owner, pa,
                             np.int32(plan.n_steps), *inputs(),
                             np.bool_(True))
    batched = eng.batched_evaluator().lower(
        entry.part, entry.g2l, sess.store.owner, PlanArrays.stack([pa, pa]),
        np.full(2, plan.n_steps, np.int32), *inputs(2), np.ones(2, bool))
    for lowered in (single, batched):
        assert f"module @{EVAL_MODULE} " in lowered.as_text()


@pytest.mark.parametrize("path", ["opat", "scheduler"])
def test_every_round_names_its_host_stretches(setup, path):
    g, dq = setup
    tr = Tracer()
    sess = make_session(g, tracer=tr)
    queries = [dq[n] for n in ("Q4", "Q5", "Q6")]
    if path == "opat":
        for q in queries:
            sess.submit(q)
        round_name = "opat.round"
    else:
        sess.submit_many(queries, heuristic=MAX_YIELD_SHARED)
        round_name = "scheduler.round"
    spans = sorted(tr.spans, key=lambda s: s.t0)
    kids = _children(spans)
    rounds = [s for s in spans if s.name == round_name]
    ranks = [s for s in spans if s.name == "heuristics.rank"]
    assert rounds and len(ranks) >= len(rounds)
    prev_end = float("-inf")
    for r in rounds:
        names = [c.name for c in kids.get(r.span_id, [])]
        for want in ("eval.inputs", "kernel.eval", "eval.absorb"):
            assert want in names, (want, names)
        assert names.index("eval.inputs") < names.index("kernel.eval") \
            < names.index("eval.absorb")
        for k in kids[r.span_id]:
            if k.name == "kernel.eval":
                assert "eval.launch" in [c.name for c in kids[k.span_id]]
                assert {"n_iters", "n_expanded", "rows"} <= set(k.attrs)
        # the partition this round loaded was chosen after the last round
        assert any(prev_end <= x.t0 and x.t1 <= r.t0 for x in ranks)
        prev_end = r.t1
    assert all("n_eligible" in x.attrs for x in ranks)
