"""Resource profiling (obs/profile.py) and the PR-10 observability
growth around it: profiling on/off yields byte-identical answers on
every engine, every kernel.eval span carries cost attribution, memory
accounting tracks live/peak bytes, the SLO burn-rate monitor follows
SRE semantics, byte counters cross-check against load counts, the
serve-JSON report speaks schema_version 3, and the EWMA trajectory
regression gate (benchmarks/regress.py) fails on real drift while
staying quiet inside its noise band.
"""
import json
import pathlib
import subprocess
import sys
import time

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import EngineConfig, GraphSession, match_disjunctive
from repro.core.metrics import RunStats, validate_run_residency
from repro.data.generators import subgen_like_graph, subgen_queries
from repro.launch.hlo_analysis import CHIP_PEAKS, RooflineTerms
from repro.obs import (NULL_PROFILER, NULL_TRACER, MetricsRegistry,
                       ResourceProfiler, SloBurnMonitor, Tracer,
                       ingest_session, resource_profile_snapshot)

ROOT = pathlib.Path(__file__).resolve().parent.parent


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
# the disabled path
# ---------------------------------------------------------------------------

def test_null_profiler_is_noop_singleton():
    assert not NULL_PROFILER.enabled
    NULL_PROFILER.sample_device(NULL_TRACER.span("x"), object())
    NULL_PROFILER.attribute_kernel(("a", "b"), None)
    NULL_PROFILER.stamp_kernel(NULL_TRACER.span("x"), ("a", "b"))
    assert NULL_PROFILER.observe_rss() == 0
    assert NULL_PROFILER.snapshot() == {"enabled": False}


def test_session_profiler_defaults(setup):
    g, _, _ = setup
    # no tracer -> profiling off; real tracer -> profiling on; an
    # explicit profiler always wins
    assert make_session(g).profiler is NULL_PROFILER
    assert make_session(g, tracer=Tracer()).profiler.enabled
    prof = ResourceProfiler()
    assert make_session(g, profiler=prof).profiler is prof


def test_disabled_profiler_overhead_under_5pct(setup):
    """The null-path cost of every profiler call a profiled scheduler
    batch would make must stay under 5% of the batch's wall time."""
    g, dqueries, _ = setup
    traced = make_session(g, tracer=Tracer())
    traced.submit_many(dqueries)                       # warm compile
    t0 = time.perf_counter()
    traced.submit_many(dqueries)
    wall = time.perf_counter() - t0
    # the profiler fires at most twice per recorded span (sample + stamp)
    n_calls = 2 * len(traced.tracer.spans)
    store = traced.store
    reps = 20000
    t0 = time.perf_counter()
    for _ in range(reps):
        NULL_PROFILER.sample_device(NULL_TRACER.span("kernel.eval"), store)
        NULL_PROFILER.stamp_kernel(NULL_TRACER.span("kernel.eval"),
                                   ("opat", "eval"))
    per_call = (time.perf_counter() - t0) / (2 * reps)
    assert n_calls * per_call < 0.05 * wall, (n_calls, per_call, wall)


# ---------------------------------------------------------------------------
# parity: profiling on/off is invisible to results
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("engine,k", [("opat", 4), ("traditional", 4),
                                      ("mapreduce", 1)])
def test_profiled_unprofiled_parity(setup, engine, k):
    g, dqueries, _ = setup
    plain = make_session(g, engine=engine, k=k)
    prof = make_session(g, engine=engine, k=k, tracer=Tracer())
    for dq in dqueries:
        r0 = plain.submit(dq, max_answers=5)
        r1 = prof.submit(dq, max_answers=5)
        assert np.array_equal(r0.answers, r1.answers), (engine, dq.name)
        for s0, s1 in zip(r0.stats, r1.stats):
            assert s0.loads == s1.loads
            assert s0.n_answers == s1.n_answers
    # and the profiled run actually profiled
    assert prof.profiler.kernel_costs


def test_profiled_unprofiled_parity_shared_scheduler(setup):
    g, dqueries, _ = setup
    plain = make_session(g)
    prof = make_session(g, tracer=Tracer())
    rep0 = plain.submit_many(dqueries)
    rep1 = prof.submit_many(dqueries)
    assert rep0.loads == rep1.loads
    for q0, q1 in zip(rep0.results, rep1.results):
        assert np.array_equal(q0.answers, q1.answers)
    keys = set(prof.profiler.kernel_costs)
    assert any(k.startswith("scheduler.") for k in keys), keys


# ---------------------------------------------------------------------------
# kernel cost attribution
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("engine,k,key", [
    ("opat", 4, "opat:eval"),
    ("traditional", 4, "traditional:veval"),
    ("mapreduce", 1, "mapreduce:eval"),
])
def test_every_kernel_span_carries_cost_attrs(setup, engine, k, key):
    g, dqueries, _ = setup
    sess = make_session(g, engine=engine, k=k, tracer=Tracer())
    for dq in dqueries:
        sess.submit(dq, max_answers=5)
    kspans = [s for s in sess.tracer.spans if s.name == "kernel.eval"]
    assert kspans
    for sp in kspans:
        assert sp.attrs["kernel_key"] == key
        for attr in ("cost_flops", "cost_bytes", "cost_t_bound_us",
                     "cost_dominant", "device_live_bytes"):
            assert attr in sp.attrs, (key, attr)
    cost = sess.profiler.kernel_costs[key]
    assert "cost_error" not in cost, cost
    assert cost["flops"] > 0 and cost["bytes"] > 0
    # the bound comes from the running device's published peaks; a device
    # with none (the CPU) gets FLOPs and bytes counted but no bound
    assert cost["device_kind"] == jax.devices()[0].device_kind
    if cost["device_kind"] in CHIP_PEAKS:
        assert cost["t_bound_us"] > 0
        assert cost["dominant"] in ("compute", "memory", "collective")
    else:
        assert cost["t_bound_us"] is None
        assert cost["dominant"] == "not measured"


def test_v5e_kind_gets_v5e_peaks(monkeypatch):
    """A TPU v5e device (kind "TPU v5 lite") is bounded by v5e's published
    peaks; RooflineTerms without peaks claims no bound."""
    class _V5e:
        device_kind = "TPU v5 lite"
    fn = jax.jit(lambda a, b: a @ b)
    args = (jax.ShapeDtypeStruct((256, 512), jnp.float32),
            jax.ShapeDtypeStruct((512, 1024), jnp.float32))
    monkeypatch.setattr(jax, "devices", lambda *a: [_V5e()])
    cost = ResourceProfiler().attribute_kernel(("mm", 1), fn, *args)
    assert "cost_error" not in cost, cost
    peaks = CHIP_PEAKS["TPU v5 lite"]
    assert (peaks.flops, peaks.hbm_bw, peaks.ici_bw) == (197e12, 819e9, 200e9)
    t_compute = cost["flops"] / peaks.flops
    t_memory = cost["bytes"] / peaks.hbm_bw
    assert cost["t_bound_us"] == pytest.approx(max(t_compute, t_memory) * 1e6)
    assert cost["dominant"] == ("compute" if t_compute > t_memory
                                else "memory")
    bare = RooflineTerms(device_flops=1.0, device_bytes=1.0,
                         device_coll_bytes=0.0, peaks=None)
    assert bare.t_bound is None and bare.dominant == "not measured"


def test_attribution_failure_degrades_not_raises():
    prof = ResourceProfiler()
    cost = prof.attribute_kernel(("broken", "fn"), object())  # no .lower
    assert cost["cost_error"]
    assert cost["flops"] == 0.0
    # memoized: the failure is computed once, stamped consistently
    assert prof.attribute_kernel(("broken", "fn"), object()) is cost
    tr = Tracer()
    with tr.span("kernel.eval") as sp:
        prof.stamp_kernel(sp, ("broken", "fn"))
    assert tr.spans[0].attrs["kernel_key"] == "broken:fn"
    assert tr.spans[0].attrs["cost_flops"] == 0.0


# ---------------------------------------------------------------------------
# memory accounting
# ---------------------------------------------------------------------------

def test_memory_accounting_peaks_and_live_bytes(setup):
    g, dqueries, _ = setup
    sess = make_session(g, tracer=Tracer())
    for dq in dqueries:
        sess.submit(dq, max_answers=5)
    prof = sess.profiler
    assert prof.peak_device_bytes > 0
    assert prof.observe_rss() > 0 and prof.peak_rss_bytes > 0
    live = [s.attrs["device_live_bytes"] for s in sess.tracer.spans
            if "device_live_bytes" in s.attrs]
    assert live and max(live) == prof.peak_device_bytes
    snap = prof.snapshot()
    assert snap["enabled"] and snap["peak_device_bytes"] > 0


def test_run_stats_byte_fields_and_crosschecks(setup):
    g, dqueries, _ = setup
    sess = make_session(g)
    res = sess.submit(dqueries[0], max_answers=5)
    s = res.stats[0]
    assert s.bytes_cold is not None
    assert (s.cold_loads > 0) == (s.bytes_cold > 0)
    out = validate_run_residency(s)
    assert out is not None and out["bytes_cold"] == s.bytes_cold
    # a byte-accounting path that was skipped fails the cross-check
    bad = RunStats(query="q", scheme="s", heuristic="h", loads=[0, 1],
                   l_ideal=2, n_answers=1, cold_loads=2, warm_loads=0,
                   prefetch_hits=0, bytes_cold=0)
    with pytest.raises(ValueError, match="bytes"):
        validate_run_residency(bad)
    # hand-built stats without byte fields still validate (None = absent)
    ok = RunStats(query="q", scheme="s", heuristic="h", loads=[0, 1],
                  l_ideal=2, n_answers=1, cold_loads=2, warm_loads=0,
                  prefetch_hits=0)
    assert validate_run_residency(ok)["cold"] == 2


def test_metrics_ingest_profile_gauges_and_byte_counters(setup):
    g, dqueries, _ = setup
    sess = make_session(g, tracer=Tracer())
    sess.submit_many(dqueries)
    reg = MetricsRegistry()
    ingest_session(reg, sess)
    snap = reg.snapshot()
    assert snap["repro_session_peak_device_bytes"] == \
        sess.profiler.peak_device_bytes
    assert snap["repro_session_peak_rss_bytes"] > 0
    assert snap["repro_store_host_bytes_total"] == \
        sess.load_stats.bytes_host
    # in-RAM session: no disk catalog, so no disk byte counter
    assert "repro_store_disk_bytes_total" not in snap
    # unprofiled session: no peak gauges
    reg2 = MetricsRegistry()
    ingest_session(reg2, make_session(g))
    assert "repro_session_peak_device_bytes" not in reg2.snapshot()


def test_disk_and_host_byte_counters_out_of_core(setup, tmp_path):
    g, dqueries, _ = setup
    make_session(g).save(str(tmp_path / "gd"))
    sess = GraphSession.open(str(tmp_path / "gd"), engine="opat", seed=1,
                             config=EngineConfig(cap=32768),
                             host_cache_parts=2, tracer=Tracer())
    res = sess.submit(dqueries[0], max_answers=5)
    s = res.stats[0]
    assert s.bytes_disk is not None and s.bytes_disk > 0
    assert s.bytes_host is not None and s.bytes_host > 0
    assert (s.disk_reads > 0) == (s.bytes_disk > 0)
    assert validate_run_residency(s)["bytes_disk"] == s.bytes_disk
    # the catalog-level byte counter reaches the registry and the
    # serve-JSON profile block
    reg = MetricsRegistry()
    ingest_session(reg, sess)
    snap = reg.snapshot()
    assert snap["repro_store_disk_bytes_total"] > 0
    block = resource_profile_snapshot(sess)
    assert block["bytes"]["disk_catalog"] >= block["bytes"]["disk"] > 0
    assert block["bytes"]["host"] == sess.load_stats.bytes_host


# ---------------------------------------------------------------------------
# SLO burn rate
# ---------------------------------------------------------------------------

def test_slo_burn_monitor_semantics():
    m = SloBurnMonitor(window=4, error_budget=0.25)
    assert m.burn_rate("interactive") == 0.0       # empty window
    for met in (True, True, False, True):
        m.observe("interactive", met)
    assert m.miss_fraction("interactive") == pytest.approx(0.25)
    assert m.burn_rate("interactive") == pytest.approx(1.0)
    # the window rolls: four more meets flush the miss out
    for _ in range(4):
        m.observe("interactive", True)
    assert m.burn_rate("interactive") == 0.0
    snap = SloBurnMonitor(window=2, error_budget=0.5)
    snap.observe("batch", False)
    s = snap.snapshot()["batch"]
    assert s["window"] == 1 and s["misses"] == 1
    assert s["burn_rate"] == pytest.approx(2.0)    # 1.0 miss / 0.5 budget
    with pytest.raises(ValueError):
        SloBurnMonitor(window=0)
    with pytest.raises(ValueError):
        SloBurnMonitor(error_budget=0.0)


def test_frontend_burn_rate_export(setup):
    from repro.serving import Request, parse_slo_spec
    g, dqueries, _ = setup
    sess = make_session(g, tracer=Tracer())
    fe = sess.frontend(slo_classes=parse_slo_spec("interactive=30"),
                       shed_policy="never")
    rep = fe.serve([Request(dq, slo_class="interactive")
                    for dq in dqueries])
    burn = rep.slo_burn["interactive"]
    assert burn["window"] == len(dqueries)
    assert burn["burn_rate"] == 0.0                # 30s deadline: all met
    # a sub-millisecond deadline misses everything: burn = 1/0.01 budget
    sess2 = make_session(g, tracer=Tracer())
    fe2 = sess2.frontend(slo_classes=parse_slo_spec("interactive=0.000001"),
                         shed_policy="never")
    rep2 = fe2.serve([Request(dq, slo_class="interactive")
                      for dq in dqueries])
    burn2 = rep2.slo_burn["interactive"]
    assert burn2["miss_fraction"] == 1.0
    assert burn2["burn_rate"] == pytest.approx(1.0 / 0.01)
    # the session kept it, and the registry exports it as a gauge
    assert sess2._slo_burn["interactive"]["burn_rate"] == \
        burn2["burn_rate"]
    reg = MetricsRegistry()
    ingest_session(reg, sess2)
    snap = reg.snapshot()
    assert snap["repro_frontend_slo_burn_rate{slo_class=interactive}"] == \
        pytest.approx(burn2["burn_rate"])
    block = resource_profile_snapshot(sess2)
    assert block["slo_burn"]["interactive"]["misses"] == len(dqueries)


# ---------------------------------------------------------------------------
# trajectory regression gate (benchmarks/regress.py + track.py growth)
# ---------------------------------------------------------------------------

def _traj_point(day, **over):
    pt = dict(utc_date=f"2026-07-{day:02d}", schema_version=1, n_trials=1,
              shared_b8_loads_per_query=0.5, shared_b8_qps=4.0,
              shared_b8_p95_ms=1000.0, oocore_disk_reads=20,
              kernel_speedup=None, kernel_backend="cpu")
    pt.update(over)
    return pt


def test_regress_clean_trajectory_passes():
    from benchmarks.regress import detect
    traj = [_traj_point(d, shared_b8_p95_ms=1000.0 + 20 * (d % 4),
                        shared_b8_qps=4.0 + 0.1 * (d % 3))
            for d in range(1, 9)]
    findings = detect(traj)
    assert all(f["status"] != "regression" for f in findings), findings
    # cpu kernel_speedup never gates: 0 usable points
    ks = next(f for f in findings if f["metric"] == "kernel_speedup")
    assert ks["status"] == "skipped"


def test_regress_fails_on_genuine_regression():
    from benchmarks.regress import detect
    traj = [_traj_point(d) for d in range(1, 8)]
    bad = detect(traj + [_traj_point(8, shared_b8_p95_ms=2000.0)])
    assert [f["metric"] for f in bad if f["status"] == "regression"] == \
        ["shared_b8_p95_ms"]
    # qps collapse trips its own metric
    bad2 = detect(traj + [_traj_point(8, shared_b8_qps=1.0)])
    assert any(f["metric"] == "shared_b8_qps"
               and f["status"] == "regression" for f in bad2)
    # deterministic counter drift gates too
    bad3 = detect(traj + [_traj_point(8, oocore_disk_reads=40)])
    assert any(f["metric"] == "oocore_disk_reads"
               and f["status"] == "regression" for f in bad3)


def test_regress_noise_stays_in_band():
    from benchmarks.regress import detect
    # within the 20% relative band AND the 75 ms absolute floor
    traj = [_traj_point(d) for d in range(1, 8)]
    ok = detect(traj + [_traj_point(8, shared_b8_p95_ms=1060.0,
                                    shared_b8_qps=3.7)])
    assert all(f["status"] != "regression" for f in ok), ok
    # a measured across-trial stddev widens the band past the floors
    noisy = [_traj_point(d, n_trials=3, shared_b8_p95_ms_std=150.0)
             for d in range(1, 8)]
    ok2 = detect(noisy + [_traj_point(8, shared_b8_p95_ms=1400.0,
                                      n_trials=3,
                                      shared_b8_p95_ms_std=150.0)])
    assert all(f["status"] != "regression" for f in ok2), ok2


def test_regress_too_few_points_passes_with_note():
    from benchmarks.regress import detect
    findings = detect([_traj_point(1)])
    assert all(f["status"] == "skipped" for f in findings)
    assert all("need 2" in f["note"] for f in findings)


def test_track_trajectory_dedupes_same_day(tmp_path):
    from benchmarks.track import append_trajectory, summary_point
    point = {
        "utc_date": "2026-08-09", "schema_version": 1, "n_trials": 2,
        "shared": [{"mode": "shared", "batch": 8, "loads_per_query": 0.5,
                    "qps": 4.0, "qps_std": 0.2, "p50_ms": 80.0,
                    "p95_ms": 120.0, "p95_ms_std": 5.0, "p99_ms": 140.0,
                    "cold_loads": 4, "warm_loads": 12}],
        "oocore": [{"mode": "out-of-core", "disk_reads": 20}],
        "kernel": {"speedup": 0.05, "backend": "cpu"},
    }
    sp = summary_point(point)
    assert sp["kernel_speedup"] is None          # cpu: suppressed
    assert sp["kernel_backend"] == "cpu"
    assert sp["shared_b8_p95_ms"] == 120.0
    assert sp["shared_b8_p95_ms_std"] == 5.0
    assert sp["n_trials"] == 2
    path = tmp_path / "traj.json"
    append_trajectory(str(path), point)
    append_trajectory(str(path), dict(point, n_trials=3))
    traj = json.loads(path.read_text())
    assert len(traj) == 1                        # same day: replaced
    assert traj[0]["n_trials"] == 3
    other = dict(point, utc_date="2026-08-10")
    append_trajectory(str(path), other)
    assert len(json.loads(path.read_text())) == 2


def test_track_merge_trials_stats():
    from benchmarks.track import _merge_trials
    runs = [[{"mode": "shared", "batch": 8, "cold_loads": 4,
              "p95_ms": 100.0, "qps": 4.0}],
            [{"mode": "shared", "batch": 8, "cold_loads": 4,
              "p95_ms": 110.0, "qps": 4.2}]]
    merged = _merge_trials(runs, ["mode", "batch"])
    assert merged[0]["p95_ms"] == pytest.approx(105.0)
    assert merged[0]["p95_ms_std"] > 0
    assert merged[0]["cold_loads"] == 4          # counters untouched
    # diverging counters are a nondeterminism bug, not noise
    runs[1][0]["cold_loads"] = 5
    with pytest.raises(SystemExit):
        _merge_trials(runs, ["mode", "batch"])


# ---------------------------------------------------------------------------
# serve-JSON schema v3 + trace_report --cost (end to end)
# ---------------------------------------------------------------------------

def test_resource_profile_snapshot_disabled(setup):
    g, _, _ = setup
    assert resource_profile_snapshot(make_session(g)) == {"enabled": False}


@pytest.mark.slow
def test_serve_json_schema_v3_and_cost_report(tmp_path):
    out = tmp_path / "report.json"
    trace = tmp_path / "trace.json"
    run = subprocess.run(
        [sys.executable, "-m", "repro.launch.serve", "--dataset",
         "synthetic", "--scale", "0.2", "--max-answers", "5",
         "--json", str(out), "--trace-out", str(trace), "--verify"],
        cwd=ROOT, capture_output=True, text=True,
        env={**__import__("os").environ, "PYTHONPATH": "src"})
    assert run.returncode == 0, run.stderr
    rep = json.loads(out.read_text())
    assert rep["schema_version"] == 3
    prof = rep["profile"]
    assert prof["enabled"] is True
    assert prof["peak_device_bytes"] > 0
    assert prof["kernel_costs"]["opat:eval"]["flops"] > 0
    assert prof["bytes"]["cold"] > 0
    # each query's record carries the evaluator's work, the totals of
    # the counters its kernel.eval spans were stamped with
    doc = json.loads(trace.read_text())
    kernel = [e["args"] for e in doc["traceEvents"]
              if e.get("ph") == "X" and e.get("name") == "kernel.eval"]
    for field, attr in (("eval_iters", "n_iters"),
                        ("rows_expanded", "n_expanded")):
        assert sum(q[field] for q in rep["queries"]) == \
            sum(k[attr] for k in kernel) > 0
    # the cost table joins measured time with the prediction
    cost = subprocess.run(
        [sys.executable, "tools/trace_report.py", str(trace), "--cost"],
        cwd=ROOT, capture_output=True, text=True)
    assert cost.returncode == 0, cost.stderr
    assert "opat:eval" in cost.stdout and "roofline" in cost.stdout
    # --check enforces cost attrs on every kernel span (all-or-none)
    chk = subprocess.run(
        [sys.executable, "tools/trace_report.py", str(trace), "--check"],
        cwd=ROOT, capture_output=True, text=True)
    assert chk.returncode == 0, chk.stderr
    # strip the attrs from one kernel span: the gate must fail
    doc = json.loads(trace.read_text())
    for e in doc["traceEvents"]:
        if e.get("ph") == "X" and e.get("name") == "kernel.eval":
            for k in ("kernel_key", "cost_flops", "cost_bytes",
                      "cost_t_bound_us", "cost_dominant"):
                e["args"].pop(k, None)
            break
    bad = tmp_path / "bad.json"
    bad.write_text(json.dumps(doc))
    chk2 = subprocess.run(
        [sys.executable, "tools/trace_report.py", str(bad), "--check"],
        cwd=ROOT, capture_output=True, text=True)
    assert chk2.returncode != 0
    assert "cost attrs" in chk2.stderr
