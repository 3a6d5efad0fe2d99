"""Benchmark trajectory tracking: one schema-versioned JSON point per run.

    PYTHONPATH=src python -m benchmarks.track [--out-dir .] [--no-gate]
    PYTHONPATH=src python -m benchmarks.run --track        (same thing)

Runs the smoke-sized sweeps (shared-load scheduling, out-of-core serving,
fused-kernel vs pure-jnp ref timing, roofline if dry-run artifacts exist),
emits ``BENCH_<utc-date>.json`` and appends a compact summary point to the
repo-root ``bench_trajectory.json``.  CI uploads the file as an artifact
and fails when a tracked metric regresses >20% against the last committed
``BENCH_*.json`` (deterministic counters gate hard; timing metrics also
need to clear an absolute noise floor, since CI runners are shared).

Schema (version 1):
  { "schema_version": 1, "utc_date": "...", "platform": {...},
    "shared":  [ {mode, batch, loads_per_query, cold_loads, warm_loads,
                  p50_ms, p95_ms, p99_ms, qps}, ... ],
    "oocore":  [ {mode, disk_reads, read_ahead_hits, cold_loads,
                  warm_loads, p50_ms, p95_ms, p99_ms}, ... ],
    "kernel":  {shape, ref_ms, fused_ms, speedup},
    "roofline": {available, note} }

(p99_ms joined within schema v1: the gate guards each timing key with a
presence check, so points committed before the key exists still compare
on the keys they have.  ``--trials N`` repeats the sweeps: timing keys
become across-trial means with ``<key>_std`` sample stddevs and the
point records ``n_trials`` — measured variance the EWMA regression
detector in benchmarks/regress.py sizes its noise bands from.  The
trajectory keeps ONE point per utc_date: a re-run replaces that day's
entry instead of double-weighting it.)
"""
from __future__ import annotations

import argparse
import datetime
import glob
import json
import os
import statistics
import sys
import time
from typing import Dict, List, Optional

sys.path.insert(0, "src")

SCHEMA_VERSION = 1

# >20% worse than the last committed point fails CI
REL_TOL = 0.20
# timing metrics additionally need to move by this much in absolute terms
# (shared CI runners jitter small numbers well past 20%)
ABS_MS_FLOOR = 75.0
ABS_QPS_FLOOR = 0.5


def _utc_date() -> str:
    return datetime.datetime.now(datetime.timezone.utc).strftime("%Y-%m-%d")


# -- collection --------------------------------------------------------------

def _collect_shared(seed: int) -> List[Dict]:
    from .common import run_shared_sweep
    res = run_shared_sweep(batch_sizes=(2, 8), seed=seed)
    if not (res.answers_identical and res.oracle_match):
        sys.exit("track: shared sweep answers diverged from the oracle")
    return [dict(mode=p.mode, batch=p.batch,
                 loads_per_query=round(p.loads_per_query, 4),
                 cold_loads=p.cold_loads, warm_loads=p.warm_loads,
                 p50_ms=round(p.p50_ms, 3), p95_ms=round(p.p95_ms, 3),
                 p99_ms=round(p.p99_ms, 3),
                 qps=round(p.qps, 4))
            for p in res.phases]


def _collect_oocore(seed: int) -> List[Dict]:
    from .common import run_oocore_sweep
    res = run_oocore_sweep(seed=seed)
    if not (res.answers_identical and res.oracle_match):
        sys.exit("track: oocore sweep answers diverged from the oracle")
    return [dict(mode=p.mode, disk_reads=p.disk_reads,
                 read_ahead_hits=p.read_ahead_hits,
                 cold_loads=p.cold_loads, warm_loads=p.warm_loads,
                 p50_ms=round(p.p50_ms, 3), p95_ms=round(p.p95_ms, 3),
                 p99_ms=round(p.p99_ms, 3))
            for p in res.phases]


def _collect_kernel(seed: int, reps: int = 5) -> Dict:
    """Fused Pallas kernel (interpret off-TPU) vs its pure-jnp ref twin on
    one fixed synthetic tile.  On TPU the speedup is the point of the
    kernel; on CPU interpret mode is a *correctness* path and slower than
    the ref — the trajectory records the ratio either way, tagged with the
    backend so points are only comparable within a platform."""
    import jax
    import jax.numpy as jnp
    import numpy as np
    from repro.core.plan import PlanArrays
    from repro.kernels import ops

    EB, W, Q, Np, S, V = 64, 128, 8, 64, 6, 1000
    rng = np.random.default_rng(seed)
    plan = PlanArrays(
        n_slots=Q, n_steps=S,
        start_slot=np.int32(0), start_label=np.int32(0),
        start_value_op=np.int32(0), start_value=np.float32(0),
        src_slot=rng.integers(0, Q, S).astype(np.int32),
        dst_slot=rng.integers(0, Q, S).astype(np.int32),
        edge_label=rng.integers(-1, 3, S).astype(np.int32),
        direction=rng.integers(0, 3, S).astype(np.int32),
        dst_label=rng.integers(-1, 3, S).astype(np.int32),
        dst_value_op=rng.integers(0, 7, S).astype(np.int32),
        dst_value=rng.normal(size=S).astype(np.float32),
        closes_cycle=rng.integers(0, 2, S).astype(np.int32))
    dst = rng.integers(-1, Np, size=(Np, W)).astype(np.int32)
    tables = (dst,
              rng.integers(-2, 3, size=(Np, W)).astype(np.int32),
              rng.integers(0, 3, size=(Np, W)).astype(np.int32),
              rng.integers(-2, 3, size=(Np, W)).astype(np.int32),
              rng.normal(size=(Np, W)).astype(np.float32),
              np.where(dst >= 0, rng.integers(0, V, size=(Np, W)),
                       -1).astype(np.int32))
    g2l = rng.integers(-1, Np, size=V).astype(np.int32)
    owner = rng.integers(0, 4, size=V).astype(np.int32)
    n_core = np.int32(Np // 2)
    rows = rng.integers(-1, V, size=(EB, Q)).astype(np.int32)
    step = rng.integers(0, S, size=EB).astype(np.int32)
    lidx = rng.integers(0, Np, size=EB).astype(np.int32)
    m = rng.random(EB) < 0.8
    n_steps = np.int32(S - 1)
    dlidx, downer = ops.denorm_locality(jnp.asarray(tables[5]),
                                        jnp.asarray(g2l), jnp.asarray(owner))
    # device-commit everything (incl. the PlanArrays pytree): numpy leaves
    # captured in a jit closure cannot be indexed by traced step values
    plan = jax.tree_util.tree_map(jnp.asarray, plan)
    tables = tuple(jnp.asarray(t) for t in tables)
    rows, step, lidx, m = map(jnp.asarray, (rows, step, lidx, m))
    g2l, owner = jnp.asarray(g2l), jnp.asarray(owner)

    fused = jax.jit(lambda: ops.fused_frontier(
        rows, step, lidx, m, *tables, dlidx, downer, g2l, owner, n_core,
        plan, n_steps))
    ref = jax.jit(lambda: ops.fused_frontier_ref(
        rows, step, lidx, m, *tables, g2l, owner, n_core, plan, n_steps))

    def _time(fn) -> float:
        jax.block_until_ready(fn())           # compile outside the clock
        t0 = time.perf_counter()
        for _ in range(reps):
            jax.block_until_ready(fn())
        return (time.perf_counter() - t0) / reps * 1000.0

    ref_ms = _time(ref)
    fused_ms = _time(fused)
    return dict(shape=dict(EB=EB, W=W, Q=Q, Np=Np),
                backend=jax.default_backend(),
                ref_ms=round(ref_ms, 3), fused_ms=round(fused_ms, 3),
                speedup=round(ref_ms / fused_ms, 4) if fused_ms else None)


def _collect_roofline(dryrun_dir: str) -> Dict:
    from . import roofline
    note = roofline.report(dryrun_dir)
    available = not note.startswith("(")
    return dict(available=available,
                note=None if available else note.strip())


# phase keys whose values are timing measurements (noisy across trials);
# everything else in a phase dict is a deterministic counter and must be
# identical on every trial of the same seed
_TIMING_KEYS = ("p50_ms", "p95_ms", "p99_ms", "qps", "ref_ms", "fused_ms")


def _merge_trials(runs: List[List[Dict]], id_keys: List[str]) -> List[Dict]:
    """Fold N trials of one sweep into its first trial's phase list:
    timing keys become the across-trial mean plus a ``<key>_std`` sample
    stddev; deterministic counters must agree across trials (same seed →
    same schedule) and a mismatch aborts — that's a real nondeterminism
    bug, not noise."""
    base = [dict(p) for p in runs[0]]
    if len(runs) == 1:
        return base
    for i, p in enumerate(base):
        for k in list(p):
            if k in _TIMING_KEYS:
                vals = [float(r[i][k]) for r in runs]
                p[k] = round(statistics.mean(vals), 3)
                p[k + "_std"] = round(statistics.stdev(vals), 3)
            elif k not in id_keys and any(r[i].get(k) != p[k]
                                          for r in runs[1:]):
                sys.exit(f"track: counter {k!r} diverged across trials of "
                         f"the same seed ({[r[i].get(k) for r in runs]}) — "
                         f"nondeterministic scheduling")
    return base


def collect(seed: int = 0, dryrun_dir: str = "results/dryrun",
            trials: int = 1) -> Dict:
    trials = max(1, int(trials))
    shared = _merge_trials([_collect_shared(seed) for _ in range(trials)],
                           ["mode", "batch"])
    oocore = _merge_trials([_collect_oocore(seed) for _ in range(trials)],
                           ["mode"])
    kruns = [_collect_kernel(seed) for _ in range(trials)]
    # "speedup" is derived from timing, so it rides the id-key exemption
    # and is recomputed from the merged means below
    kernel = _merge_trials([[k] for k in kruns],
                           ["shape", "backend", "speedup"])[0]
    if trials > 1 and kernel.get("fused_ms"):
        kernel["speedup"] = round(kernel["ref_ms"] / kernel["fused_ms"], 4)
    return {
        "schema_version": SCHEMA_VERSION,
        "utc_date": _utc_date(),
        "n_trials": trials,
        "shared": shared,
        "oocore": oocore,
        "kernel": kernel,
        "roofline": _collect_roofline(dryrun_dir),
    }


# -- regression gate ---------------------------------------------------------

def _phase_map(phases: List[Dict], keys: List[str]) -> Dict:
    return {tuple(p.get(k) for k in keys): p for p in phases}


def compare(current: Dict, baseline: Dict) -> List[str]:
    """Regressions of ``current`` vs ``baseline`` (empty list: gate green).

    Deterministic counters (loads per query, cold loads, disk reads) gate
    hard at >20%; timing metrics (p50/p95, q/s) must regress >20% AND by
    more than an absolute noise floor.
    """
    fails: List[str] = []
    if baseline.get("schema_version") != current.get("schema_version"):
        return []   # schema changed on purpose; nothing comparable

    def worse_counter(cur, base) -> bool:
        return cur > base * (1 + REL_TOL) and cur > base + 1

    def worse_ms(cur, base) -> bool:
        return cur > base * (1 + REL_TOL) and cur > base + ABS_MS_FLOOR

    def worse_qps(cur, base) -> bool:
        return cur < base * (1 - REL_TOL) and cur < base - ABS_QPS_FLOOR

    cur_s = _phase_map(current.get("shared", []), ["mode", "batch"])
    for key, b in _phase_map(baseline.get("shared", []),
                             ["mode", "batch"]).items():
        c = cur_s.get(key)
        if c is None:
            continue
        tag = f"shared[{key[0]},B={key[1]}]"
        if worse_counter(c["loads_per_query"], b["loads_per_query"]):
            fails.append(f"{tag}.loads_per_query {b['loads_per_query']} -> "
                         f"{c['loads_per_query']}")
        if worse_counter(c["cold_loads"], b["cold_loads"]):
            fails.append(f"{tag}.cold_loads {b['cold_loads']} -> "
                         f"{c['cold_loads']}")
        for k in ("p50_ms", "p95_ms", "p99_ms"):
            # presence-guarded: baselines written before p99_ms joined the
            # schema simply don't gate on it
            if k in c and k in b and worse_ms(c[k], b[k]):
                fails.append(f"{tag}.{k} {b[k]} -> {c[k]}")
        if worse_qps(c["qps"], b["qps"]):
            fails.append(f"{tag}.qps {b['qps']} -> {c['qps']}")

    cur_o = _phase_map(current.get("oocore", []), ["mode"])
    for key, b in _phase_map(baseline.get("oocore", []), ["mode"]).items():
        c = cur_o.get(key)
        if c is None:
            continue
        tag = f"oocore[{key[0]}]"
        for k in ("disk_reads", "cold_loads"):
            if worse_counter(c[k], b[k]):
                fails.append(f"{tag}.{k} {b[k]} -> {c[k]}")
        for k in ("p50_ms", "p95_ms", "p99_ms"):
            if k in c and k in b and worse_ms(c[k], b[k]):
                fails.append(f"{tag}.{k} {b[k]} -> {c[k]}")
    return fails


def last_committed(baseline_dir: str, exclude: Optional[str] = None) -> Optional[str]:
    """Path of the newest (lexicographically last dated) BENCH_*.json."""
    cands = sorted(glob.glob(os.path.join(baseline_dir, "BENCH_*.json")))
    if exclude is not None:
        ex = os.path.abspath(exclude)
        cands = [c for c in cands if os.path.abspath(c) != ex]
    return cands[-1] if cands else None


# -- trajectory --------------------------------------------------------------

def summary_point(point: Dict) -> Dict:
    """The compact per-run record appended to bench_trajectory.json.

    ``kernel_speedup`` is recorded only off-CPU: interpret-mode Pallas on
    CPU is a correctness path, so its ratio tracks interpreter overhead,
    not the kernel — comparing it across runs would gate on noise about
    the wrong thing (``kernel_backend`` still records where the point
    ran).  Timing metrics carry their across-trial stddev when the run
    measured more than one trial, so the regression detector
    (benchmarks/regress.py) can size its noise band from measured
    variance instead of guessing."""
    shared8 = next((p for p in point["shared"]
                    if p["mode"] == "shared" and p["batch"] == 8), None)
    ooc = next((p for p in point["oocore"] if p["mode"] == "out-of-core"),
               None)
    backend = point["kernel"].get("backend")
    out = {
        "utc_date": point["utc_date"],
        "schema_version": point["schema_version"],
        "n_trials": point.get("n_trials", 1),
        "shared_b8_loads_per_query": (shared8 or {}).get("loads_per_query"),
        "shared_b8_qps": (shared8 or {}).get("qps"),
        "shared_b8_p95_ms": (shared8 or {}).get("p95_ms"),
        "oocore_disk_reads": (ooc or {}).get("disk_reads"),
        "kernel_speedup": (point["kernel"]["speedup"]
                           if backend != "cpu" else None),
        "kernel_backend": backend,
    }
    for src, dst in (("qps_std", "shared_b8_qps_std"),
                     ("p95_ms_std", "shared_b8_p95_ms_std")):
        if shared8 and src in shared8:
            out[dst] = shared8[src]
    return out


def append_trajectory(path: str, point: Dict) -> None:
    """Append this run's summary — replacing, not duplicating, any entry
    already recorded for the same ``utc_date`` (re-runs within a day
    would otherwise double-weight that day in every EWMA/variance the
    regression detector computes)."""
    traj: List[Dict] = []
    if os.path.exists(path):
        with open(path) as f:
            traj = json.load(f)
    sp = summary_point(point)
    traj = [t for t in traj if t.get("utc_date") != sp["utc_date"]]
    traj.append(sp)
    with open(path, "w") as f:
        json.dump(traj, f, indent=2)
        f.write("\n")


# -- entrypoint --------------------------------------------------------------

def main(argv: Optional[List[str]] = None) -> None:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--out-dir", default=".",
                    help="where BENCH_<utc-date>.json is written")
    ap.add_argument("--baseline-dir", default=".",
                    help="where the last committed BENCH_*.json lives")
    ap.add_argument("--trajectory", default="bench_trajectory.json",
                    help="repo-root trajectory file to append to")
    ap.add_argument("--dryrun-dir", default="results/dryrun")
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--trials", type=int, default=1,
                    help="repeat each sweep N times: timing metrics "
                         "record their across-trial mean + stddev "
                         "(deterministic counters must agree), giving "
                         "the regression detector a measured noise band")
    ap.add_argument("--no-gate", action="store_true",
                    help="collect + emit but never fail on regression")
    args = ap.parse_args(argv)

    from repro.launch import compile_cache
    compile_cache.enable()

    print("== benchmark trajectory point (smoke size) ==", flush=True)
    point = collect(seed=args.seed, dryrun_dir=args.dryrun_dir,
                    trials=args.trials)

    os.makedirs(args.out_dir, exist_ok=True)
    out_path = os.path.join(args.out_dir,
                            f"BENCH_{point['utc_date']}.json")
    with open(out_path, "w") as f:
        json.dump(point, f, indent=2)
        f.write("\n")
    print(f"   wrote {out_path}")

    append_trajectory(args.trajectory, point)
    print(f"   appended to {args.trajectory}")

    base_path = last_committed(args.baseline_dir, exclude=out_path)
    if base_path is None:
        print("   no committed BENCH_*.json baseline; gate skipped")
        return
    with open(base_path) as f:
        baseline = json.load(f)
    fails = compare(point, baseline)
    print(f"   gate vs {base_path}: "
          f"{'PASS' if not fails else f'{len(fails)} regression(s)'}")
    for msg in fails:
        print("   -", msg)
    if fails and not args.no_gate:
        sys.exit(f"track: >{int(REL_TOL * 100)}% regression vs {base_path}")


if __name__ == "__main__":
    main()
