"""Sweep an open-loop cell's offered rate to find its knee.

    python3 bench/knee.py --workload <open cell> --seed <n> --seconds <s> --rates <r> [<r> ...]

One process builds or opens the cell's data, stages and warms it as a run
does, then drives the cell's mix at each rate in turn for ``--seconds``,
serving what is pending for at most ``--drain`` seconds after each
window.  One JSON line per rate: the rate offered, the queries completed
inside the window per second, latency percentiles from the due time, the
mean wait in the front end, the requests still pending at the close, and
the mean latency of the window's last third over its first third (above
1 when the backlog grows).  The knee is the highest rate served without a
growing backlog; the cell's traffic file runs at about 4/5 of it.
Every answer is checked against the reference, as in a run.
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


def sweep_rate(cell, sess, dq, rate: float, seed: int, seconds: float,
               drain: float) -> dict:
    mix = dict(cell.mix, rate_qps=rate)
    reqs = traffic.requests(mix, list(cell.queries), seed, seconds)
    done, loads = run.open_loop(sess, dq, reqs, seconds, mix,
                                run.Profiler(False), drain_s=drain)
    ok = [r for r in done if r.done is not None and r.error is None]
    lat = np.asarray([r.latency for r in ok]) * 1e3
    third = max(1, len(ok) // 3)
    by_due = sorted(ok, key=lambda r: r.due)
    first = np.mean([r.latency for r in by_due[:third]])
    last = np.mean([r.latency for r in by_due[-third:]])
    return {
        "rate_qps": rate, "offered": len(done),
        "qps": sum(1 for r in ok if r.done <= seconds) / seconds,
        "latency_p50_ms": float(np.percentile(lat, 50)) if ok else None,
        "latency_p90_ms": float(np.percentile(lat, 90)) if ok else None,
        "queue_wait_ms": 1e3 * float(np.mean([r.admitted - r.due for r in done
                                              if r.admitted is not None])),
        "pending_at_close": sum(1 for r in done if r.done is None
                                or r.done > seconds),
        "latency_trend": float(last / first) if ok else None,
        "loads_per_query": loads / len(ok) if ok else None,
        "done": done,
    }


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, default=30.0)
    ap.add_argument("--drain", type=float, default=20.0)
    ap.add_argument("--rates", type=float, nargs="+", required=True)
    ap.add_argument("--rehearse", action="store_true")
    args = ap.parse_args(argv)
    cell = run.load_cell(args.workload, args.rehearse)
    if cell.mix["loop"] != "open":
        ap.error(f"{args.workload} is not an open-loop cell")
    if not args.rehearse:
        run.checkout_compile_cache()
    run.devices(cell.chips, args.rehearse)
    if not args.rehearse:
        run.enable_compile_cache()
    gdir, _ = run.graph_dir(cell, args.seed)
    sess = run.open_session(cell, gdir)
    for pid in range(sess.k):
        sess.store.get(pid)
    dq = run.program_queries(cell)
    run.warm(cell, sess, dq)
    done_all = []
    for rate in args.rates:
        r = sweep_rate(cell, sess, dq, rate, args.seed, args.seconds, args.drain)
        done_all += r.pop("done")
        print(json.dumps(r), flush=True)
    del sess
    checks = run.check(cell, args.seed, done_all)
    print(json.dumps({"checks": checks}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
