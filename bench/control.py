"""The control of the comparison that decides ``correct``.

    python3 bench/control.py --workload <cell> --seeds <n> [<n> ...] [--requests N]

A control stands in for the program and breaks one guarantee that the
configuration states, to show that the comparison catches it.  The
guarantee is "every answer, including those that span partitions": the
control answers each request with the reference's answers less every
answer whose vertices lie in more than one partition (by the program's
assignment in the saved graph directory), the answers that a shortcut
evaluating each partition on its own would lose.  For each seed it builds
or opens the cell's data as a run does, answers the first ``--requests``
requests of the cell's traffic, and prints the numbers the harness
compares, as JSON, one line per seed.  The benchmark's runs never run it.
"""
from __future__ import annotations

import argparse
import json
import sys
from pathlib import Path

import numpy as np

sys.path.insert(0, str(Path(__file__).resolve().parent))

import run  # noqa: E402
import traffic  # noqa: E402


def spanning(rows: np.ndarray, assignment: np.ndarray) -> np.ndarray:
    """Mask of the rows whose bound vertices lie in two partitions or more."""
    rows = np.asarray(rows)
    if rows.shape[0] == 0:
        return np.zeros(0, bool)
    parts = np.where(rows >= 0, assignment[np.maximum(rows, 0)], -1)
    first = parts[:, :1]
    return ((parts != first) & (parts >= 0)).any(axis=1)


def readings(cell: run.Cell, seed: int, n_requests: int) -> dict:
    from repro.storage.format import DiskCatalog
    gdir, _ = run.graph_dir(cell, seed)
    assignment = np.asarray(DiskCatalog(str(gdir)).assignment)
    reqs = traffic.requests(cell.mix, list(cell.queries), seed, 1e9,
                            n_closed=n_requests)[:n_requests]
    ga, _ = run.seed_graph(cell.config, seed)
    refs = run.reference.match_all(ga, cell.queries, run.engine_config(cell).q_pad)
    done = [run.Done(query=r.query, due=0.0,
                     answers=refs[r.query][~spanning(refs[r.query], assignment)])
            for r in reqs]
    checks = run.check(cell, seed, done)
    span = {n: int(spanning(a, assignment).sum()) for n, a in refs.items()}
    return {"seed": seed, "requests": len(done), "spanning_answers": span,
            "checks": checks,
            "correct": all(c["value"] <= c["limit"] for c in checks.values())}


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", type=int, nargs="+", required=True)
    ap.add_argument("--requests", type=int, default=60)
    ap.add_argument("--rehearse", action="store_true")
    args = ap.parse_args(argv)
    cell = run.load_cell(args.workload, args.rehearse)
    for seed in args.seeds:
        print(json.dumps(readings(cell, seed, args.requests)), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
