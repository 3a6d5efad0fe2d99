"""Benchmark driver: one section per paper table/figure + the roofline
report.

    PYTHONPATH=src python -m benchmarks.run [--scale 1.0] [--skip-sweep]

Writes CSVs to results/bench/ and prints the tables.  The OPAT sweep
(2 datasets x 6 schemes x 3 queries x 3 heuristics = 108 runs) takes a few
minutes at the default scale; --paper-scale regenerates paper-sized inputs
(hours — sized for a cluster, not this container).
"""
from __future__ import annotations

import argparse
import sys

sys.path.insert(0, "src")


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--scale", type=float, default=1.0)
    ap.add_argument("--paper-scale", action="store_true",
                    help="IMDB 1750K/5100K, synthetic 400K/1200K")
    ap.add_argument("--out", default="results/bench")
    ap.add_argument("--dryrun-dir", default="results/dryrun")
    ap.add_argument("--skip-sweep", action="store_true",
                    help="only print the roofline report")
    ap.add_argument("--shared-smoke", action="store_true",
                    help="only run the shared-vs-isolated scheduler sweep "
                         "(small batches; the CI throughput smoke)")
    ap.add_argument("--oocore-smoke", action="store_true",
                    help="only run the out-of-core sweep (save -> reopen "
                         "with a host cache below the graph's shard bytes;"
                         " the CI disk-tier smoke, gated on oracle match "
                         "and real disk/read-ahead traffic)")
    ap.add_argument("--track", action="store_true",
                    help="emit a BENCH_<utc-date>.json trajectory point "
                         "(smoke-size sweeps + kernel timing) and gate "
                         "against the last committed one — see track.py")
    ap.add_argument("--seed", type=int, default=0)
    args = ap.parse_args()

    from repro.launch import compile_cache
    compile_cache.enable()

    if args.track:
        from . import track
        track.main(["--seed", str(args.seed),
                    "--dryrun-dir", args.dryrun_dir])
        return

    from . import mp_scaling, paper_tables, roofline
    from .common import (build_workloads, run_budget_sweep, run_oocore_sweep,
                         run_shared_sweep, run_sweep, run_waw_sweep)

    if args.shared_smoke:
        print("== Shared-load scheduling (QueryScheduler, isolated vs "
              "shared) ==", flush=True)
        shared = run_shared_sweep(batch_sizes=(2, 8), seed=args.seed)
        print(f"   {len(shared.phases)} phases in {shared.wall_s:.1f}s")
        print(paper_tables.table_shared(shared, args.out))
        if not (shared.answers_identical and shared.oracle_match):
            sys.exit("shared-smoke: answer sets differ across modes or "
                     "mismatch the oracle")   # a real CI gate, like serve
        return

    if args.oocore_smoke:
        print("== Out-of-core serving (disk -> host LRU -> device LRU) ==",
              flush=True)
        oocore = run_oocore_sweep(seed=args.seed)
        print(f"   2 phases in {oocore.wall_s:.1f}s")
        print(paper_tables.table_oocore(oocore, args.out))
        ooc = oocore.phase("out-of-core")
        if not (oocore.answers_identical and oocore.oracle_match):
            sys.exit("oocore-smoke: answer sets differ across modes or "
                     "mismatch the oracle")   # a real CI gate, like serve
        if ooc.disk_reads <= 0 or ooc.read_ahead_hits <= 0:
            sys.exit("oocore-smoke: the out-of-core phase paid no disk "
                     f"reads ({ooc.disk_reads}) or no read-ahead hits "
                     f"({ooc.read_ahead_hits}) — the tier was not "
                     "exercised")
        return

    if not args.skip_sweep:
        scale = 600.0 if args.paper_scale else args.scale
        print(f"== building workloads (scale={scale}) ==", flush=True)
        workloads = build_workloads(scale=scale, seed=args.seed)
        for wl in workloads:
            print(f"   {wl.name}: {wl.graph.n_nodes} nodes, "
                  f"{wl.graph.n_edges} edges")
        print("== OPAT sweep (6 schemes x 3 heuristics x query batch) ==",
              flush=True)
        sweep = run_sweep(workloads, seed=args.seed)
        print(f"   {len(sweep.stats)} runs in {sweep.wall_s:.1f}s\n")

        print("== Table 3: h(D)^query_pschemes (mean load ratio across "
              "schemes) ==")
        print(paper_tables.table3(sweep, args.out), "\n")
        print("== Table 4: h(D)^pscheme_qbatch (mean load ratio per scheme) ==")
        print(paper_tables.table4(sweep, args.out), "\n")
        print("== Table 5: connected-components heuristic ==")
        print(paper_tables.table5(sweep, args.out), "\n")
        print("== Figures 7-10 (loads per query/scheme/heuristic) ==")
        print(paper_tables.figs_loads(sweep, args.out), "\n")

        failures = paper_tables.validate_claims(sweep)
        if failures:
            print("!! paper-claim validation FAILURES:")
            for f in failures:
                print("   -", f)
        else:
            print("paper-claim validation: all qualitative claims hold "
                  "(MAX-SN >= MIN-SN >= RANDOM; IMDB MAX==MIN; MIN-CC >= "
                  "MAX-CC)\n")

        print("== Response time vs K (answer budget, OPAT runner API) ==")
        budget = run_budget_sweep(workloads, seed=args.seed)
        print(f"   {len(budget.stats)} budget runs in {budget.wall_s:.1f}s")
        print(paper_tables.table_k_budget(budget, args.out), "\n")

        print("== Workload-aware repartitioning (WawPart loop, "
              "baseline vs waw) ==")
        waw = run_waw_sweep(seed=args.seed)
        print(f"   2 phases x {len(waw.baseline.stats)} queries in "
              f"{waw.wall_s:.1f}s")
        print(paper_tables.table_waw(waw, args.out), "\n")

        print("== Shared-load scheduling (QueryScheduler, isolated vs "
              "shared) ==")
        shared = run_shared_sweep(seed=args.seed)
        print(f"   {len(shared.phases)} phases in {shared.wall_s:.1f}s")
        print(paper_tables.table_shared(shared, args.out), "\n")

        print("== Out-of-core serving (disk -> host LRU -> device LRU) ==")
        oocore = run_oocore_sweep(seed=args.seed)
        print(f"   2 phases in {oocore.wall_s:.1f}s")
        print(paper_tables.table_oocore(oocore, args.out), "\n")

        print("== TraditionalMP / MapReduceMP scaling (Sec. 8-9) ==")
        print(mp_scaling.run(args.out, scale=args.scale, seed=args.seed), "\n")

    print("== Roofline (from multi-pod dry-run artifacts) ==")
    print(roofline.report(args.dryrun_dir, args.out))
    tuned = roofline.report(args.dryrun_dir, args.out, tag="tuned")
    if not tuned.startswith("("):
        print("\n== Roofline — tuned defaults (§Perf), train cells ==")
        print(tuned)


if __name__ == "__main__":
    main()
