#!/usr/bin/env python
"""Explain a serve.py --trace-out Chrome trace: latency decomposition,
heuristic load-order rationale, admission verdicts, CI well-formedness.

The trace is the one source of truth for two questions the counters
can't answer:

  "what dominated latency?"  — every query root span is decomposed into
      the *self time* of its descendant spans (a child's duration minus
      its own children's durations), grouped by span name, so nested
      spans (jit.compile inside eval.launch inside kernel.eval inside
      opat.round) are never double-counted.  Store loads split by tier
      (cold/warm/prefetch).

  "why was P3 loaded before P1?" — heuristic decision records carry the
      full per-partition score breakdown (SNI term, completion-rate
      term, fairness-aging term, deadline-urgency term) that produced
      each ranking; this tool replays them, verifies the recorded
      winner really is the argmax of the recorded scores, and with
      ``--why A B`` prints the term-by-term comparison at every round
      where both partitions were candidates.

  "is the kernel near its roofline?" — ``kernel.eval`` spans carry the
      cost attribution stamped by obs/profile.py (predicted FLOPs/bytes
      from the bucket's lowered HLO plus the roofline-bound time);
      ``--cost`` joins that prediction with the measured steady-state
      wall time per compiled bucket: achieved FLOP/s, bound-vs-measured
      ratio (% of roofline), and the live-device-byte watermark.  A
      ``kernel.eval`` with a ``jit.compile`` descendant (the call that
      compiled, whichever it was) is excluded from the steady-state
      mean.

Modes:
    python tools/trace_report.py trace.json            # full report
    python tools/trace_report.py trace.json --why 3 1  # rank rationale
    python tools/trace_report.py trace.json --cost     # kernel cost table
    python tools/trace_report.py trace.json --check    # CI gate

``--check`` exits non-zero unless the trace is non-empty, every span
nests inside its recorded parent, every query root span is closed
(non-zero duration once it has children), every recorded heuristic
choice is score-consistent, and ``kernel.eval`` attrs are all-or-none:
if any span carries cost attrs, or one of the evaluator's counters
(``n_iters``, ``n_expanded``), every one must (a partially stamped trace
means a kernel call site bypassed ``core/engine.traced_eval``).
"""
from __future__ import annotations

import argparse
import json
import sys
from collections import defaultdict
from typing import Any, Dict, List, Optional

# nesting tolerance: perf_counter stamps of parent/child are taken a few
# statements apart; allow this much slack (microseconds) either side
NEST_TOL_US = 200.0


def load_trace(path: str) -> Dict[str, List[Dict[str, Any]]]:
    with open(path) as f:
        doc = json.load(f)
    events = doc.get("traceEvents", doc if isinstance(doc, list) else [])
    spans = [e for e in events if e.get("ph") == "X"]
    decisions = [e for e in events if e.get("ph") == "i"
                 and e.get("cat") == "decision"]
    return {"spans": spans, "decisions": decisions}


def index_spans(spans: List[Dict[str, Any]]):
    by_id: Dict[int, Dict[str, Any]] = {}
    children: Dict[Optional[int], List[Dict[str, Any]]] = defaultdict(list)
    for sp in spans:
        sid = sp.get("args", {}).get("span_id")
        if sid is not None:
            by_id[sid] = sp
        children[sp.get("args", {}).get("parent_id")].append(sp)
    return by_id, children


def _bucket(sp: Dict[str, Any]) -> str:
    """Aggregation key for the decomposition: store loads split by the
    residency tier the span recorded."""
    name = sp["name"]
    tier = sp.get("args", {}).get("tier")
    if name == "store.load" and tier:
        return f"store.load[{tier}]"
    return name


def decompose(root: Dict[str, Any], children) -> Dict[str, float]:
    """Self-time (µs) of the root and every descendant, by bucket."""
    out: Dict[str, float] = defaultdict(float)

    def walk(sp: Dict[str, Any]) -> None:
        sid = sp.get("args", {}).get("span_id")
        kids = children.get(sid, []) if sid is not None else []
        self_us = sp.get("dur", 0.0) - sum(k.get("dur", 0.0) for k in kids)
        out[_bucket(sp)] += max(self_us, 0.0)
        for k in kids:
            walk(k)

    sid = root.get("args", {}).get("span_id")
    for k in (children.get(sid, []) if sid is not None else []):
        walk(k)
    tracked = sum(out.values())
    out["(untracked)"] = max(root.get("dur", 0.0) - tracked, 0.0)
    return dict(out)


def fmt_us(us: float) -> str:
    return f"{us / 1000.0:9.2f} ms"


def report_queries(spans, children, top: int, name_filter: str) -> None:
    roots = [sp for sp in spans if sp["name"] == "query"]
    if name_filter:
        roots = [sp for sp in roots
                 if name_filter in str(sp.get("args", {}).get("query", ""))]
    if not roots:
        print("no query spans recorded")
        return
    print(f"== {len(roots)} queries ==")
    for sp in sorted(roots, key=lambda s: -s.get("dur", 0.0))[:top]:
        a = sp.get("args", {})
        label = a.get("query", "?")
        gen = a.get("generation")
        print(f"\nquery {label}"
              + (f" (generation {gen})" if gen is not None else "")
              + f": total {fmt_us(sp.get('dur', 0.0)).strip()},"
              f" answers={a.get('n_answers', '?')}"
              f" loads={a.get('n_loads', '?')}")
        parts = decompose(sp, children)
        total = max(sp.get("dur", 0.0), 1e-9)
        for bucket, us in sorted(parts.items(), key=lambda kv: -kv[1]):
            if us <= 0.0:
                continue
            print(f"  {bucket:<24} {fmt_us(us)}  {us / total:6.1%}")


def report_aggregate(spans) -> None:
    agg: Dict[str, List[float]] = defaultdict(list)
    for sp in spans:
        agg[_bucket(sp)].append(sp.get("dur", 0.0))
    print("\n== span totals (wall, unnested) ==")
    for name, durs in sorted(agg.items(),
                             key=lambda kv: -sum(kv[1])):
        print(f"  {name:<24} n={len(durs):5d}  total {fmt_us(sum(durs))}"
              f"  mean {fmt_us(sum(durs) / len(durs))}")


def _rank_records(decisions):
    return [d for d in decisions
            if d["name"] in ("heuristic.rank", "heuristic.rank_shared")]


def verify_rankings(decisions) -> List[str]:
    """Every recorded choice must be the argmax of its own recorded
    scores (ties allowed: the tie-break is random by design)."""
    problems = []
    for i, d in enumerate(_rank_records(decisions)):
        a = d.get("args", {})
        breakdown = a.get("breakdown", {})
        chosen = a.get("chosen")
        if not breakdown or chosen is None:
            continue
        best = max(v.get("score", 0.0) for v in breakdown.values())
        got = breakdown.get(str(chosen), breakdown.get(chosen, {}))
        if abs(got.get("score", 0.0) - best) > 1e-9 * max(1.0, abs(best)):
            problems.append(
                f"ranking #{i}: chosen P{chosen} score "
                f"{got.get('score')} != max score {best}")
    return problems


def report_rankings(decisions, top: int) -> None:
    recs = _rank_records(decisions)
    if not recs:
        return
    print(f"\n== heuristic rankings ({len(recs)} decisions) ==")
    for i, d in enumerate(recs[:top]):
        a = d.get("args", {})
        ranked = a.get("ranked", [])
        print(f"\n[{i}] {d['name']} heuristic={a.get('heuristic')}"
              f" -> loads {ranked}")
        breakdown = a.get("breakdown", {})
        for pid in ranked:
            b = breakdown.get(str(pid), breakdown.get(pid, {}))
            terms = ", ".join(f"{k}={b[k]:g}" if isinstance(b[k], float)
                              else f"{k}={b[k]}"
                              for k in ("sni", "completion_rate", "base",
                                        "fairness", "urgency")
                              if k in b)
            print(f"    P{pid}: score={b.get('score', 0.0):g}  ({terms})")
    if len(recs) > top:
        print(f"  ... {len(recs) - top} more (raise --top)")


def report_why(decisions, a_pid: str, b_pid: str) -> None:
    """Term-by-term comparison of two partitions at every ranking
    where both were candidates — the recorded answer to 'why was
    P{a} loaded before P{b}?'."""
    recs = _rank_records(decisions)
    seen = 0
    for i, d in enumerate(recs):
        args = d.get("args", {})
        breakdown = args.get("breakdown", {})
        a = breakdown.get(a_pid, breakdown.get(int(a_pid), None)
                          if a_pid.isdigit() else None)
        b = breakdown.get(b_pid, breakdown.get(int(b_pid), None)
                          if b_pid.isdigit() else None)
        if not a or not b:
            continue
        seen += 1
        ranked = args.get("ranked", [])
        pos = {str(p): j for j, p in enumerate(ranked)}
        first = a_pid if pos.get(a_pid, 1 << 30) < pos.get(b_pid, 1 << 30) \
            else b_pid
        print(f"\n[{i}] {d['name']} ({args.get('heuristic')}): "
              f"P{first} ranked first  (order {ranked})")
        keys = sorted(set(a) | set(b))
        for k in keys:
            va, vb = a.get(k, 0.0), b.get(k, 0.0)
            marker = "  <-- deciding term" if k == "score" and va != vb \
                else ""
            print(f"    {k:<16} P{a_pid}={va:g}  P{b_pid}={vb:g}{marker}")
        if a.get("score") == b.get("score"):
            print("    scores tie: order fell to the random tie-break")
    if not seen:
        print(f"P{a_pid} and P{b_pid} were never ranked together "
              f"in this trace")


def report_admissions(decisions, top: int) -> None:
    recs = [d for d in decisions if d["name"] == "frontend.admit"]
    if not recs:
        return
    print(f"\n== admission decisions ({len(recs)}) ==")
    for d in recs[:top]:
        a = d.get("args", {})
        pred = a.get("predicted_latency_s")
        dl = a.get("deadline_s")
        backlog = a.get("backlog_s")
        detail = []
        if pred is not None:
            detail.append(f"predicted={pred * 1000:.0f}ms")
        if backlog is not None:
            detail.append(f"backlog={backlog * 1000:.0f}ms")
        if dl is not None:
            detail.append(f"deadline={dl * 1000:.0f}ms"
                          if dl != float("inf") else "deadline=inf")
        if a.get("reason"):
            detail.append(f"reason={a['reason']}")
        print(f"  {a.get('query', '?'):<24} [{a.get('slo_class')}] "
              f"{a.get('outcome', '?'):<8} {' '.join(detail)}")
    if len(recs) > top:
        print(f"  ... {len(recs) - top} more (raise --top)")


_COST_ATTRS = ("kernel_key", "cost_flops", "cost_bytes",
               "cost_t_bound_us", "cost_dominant")


def _kernel_spans(spans):
    return [sp for sp in spans if sp["name"] == "kernel.eval"]


def compiled_span_ids(spans) -> set:
    """Ids of the spans with a ``jit.compile`` descendant."""
    by_id, _ = index_spans(spans)
    out = set()
    for sp in spans:
        if sp["name"] != "jit.compile":
            continue
        pid = sp.get("args", {}).get("parent_id")
        while pid is not None and pid not in out:
            out.add(pid)
            pid = by_id.get(pid, {}).get("args", {}).get("parent_id")
    return out


def report_cost(spans) -> None:
    """Per-compiled-bucket cost attribution: measured steady-state wall
    time joined with the predicted FLOPs/bytes/roofline bound the
    profiler stamped on every ``kernel.eval`` span."""
    compiled = compiled_span_ids(spans)
    groups: Dict[str, List[Dict[str, Any]]] = defaultdict(list)
    for sp in _kernel_spans(spans):
        key = sp.get("args", {}).get("kernel_key")
        if key is not None:
            groups[key].append(sp)
    if not groups:
        print("no cost-attributed kernel.eval spans (profiling off, or a "
              "pre-PR-10 trace)")
        return
    print(f"== kernel cost attribution ({len(groups)} compiled buckets) ==")
    print(f"  {'bucket':<20} {'calls':>5} {'steady ms':>10} "
          f"{'pred GFLOP':>10} {'pred GB':>8} {'achieved':>12} "
          f"{'roofline%':>12}  bound   {'peak dev MB':>11}")
    for key in sorted(groups):
        sps = groups[key]
        steady = [sp for sp in sps
                  if sp.get("args", {}).get("span_id") not in compiled]
        timed = steady if steady else sps  # single-call bucket: use it
        mean_us = sum(sp.get("dur", 0.0) for sp in timed) / len(timed)
        a = sps[0].get("args", {})
        flops = float(a.get("cost_flops", 0.0))
        nbytes = float(a.get("cost_bytes", 0.0))
        bound_us = a.get("cost_t_bound_us")
        dominant = a.get("cost_dominant", "?")
        # achieved throughput from the measured mean; roofline% is how
        # close measurement came to the model's bound (100% = at the
        # bound; <100% = overhead the roofline doesn't model).  A device
        # with no published peaks has no bound: "not measured".
        gflops = (flops / mean_us) / 1e3 if mean_us > 0 else 0.0
        roof = ("not measured" if bound_us is None
                else f"{100.0 * bound_us / mean_us:.2f}%" if mean_us > 0
                else "0.00%")
        live = max((float(sp.get("args", {}).get("device_live_bytes", 0.0))
                    for sp in sps), default=0.0)
        print(f"  {key:<20} {len(sps):>5} {mean_us / 1e3:>10.3f} "
              f"{flops / 1e9:>10.3f} {nbytes / 1e9:>8.3f} "
              f"{gflops:>8.2f} GF/s {roof:>12}  {dominant:<7}"
              f"{live / 1e6:>11.2f}")
    errs = sorted({(k, g[0].get("args", {}).get("cost_error"))
                   for k, g in groups.items()
                   if g[0].get("args", {}).get("cost_error")})
    for k, e in errs:
        print(f"  !! {k}: attribution failed ({e}) — costs read 0")


def check_cost_attribution(spans) -> List[str]:
    """All-or-none: once any ``kernel.eval`` span carries cost attrs,
    every one must — a partially stamped trace means one of the engines'
    kernel call sites bypassed the profiler."""
    kspans = _kernel_spans(spans)
    attributed = [sp for sp in kspans
                  if sp.get("args", {}).get("kernel_key") is not None]
    if not attributed:
        return []
    problems = []
    for sp in kspans:
        a = sp.get("args", {})
        missing = [k for k in _COST_ATTRS if k not in a]
        if missing:
            problems.append(
                f"kernel.eval span {a.get('span_id')} "
                f"(engine={a.get('engine')}) lacks cost attrs "
                f"{missing} while {len(attributed)} other kernel spans "
                f"are attributed")
    return problems


_COUNTER_ATTRS = ("n_iters", "n_expanded")


def check_counters(spans) -> List[str]:
    """All-or-none, counter by counter: once any ``kernel.eval`` span
    carries one of the evaluator's counters, every one must (MapReduceMP
    stamps ``n_iters`` alone)."""
    kspans = _kernel_spans(spans)
    return [f"kernel.eval span {sp.get('args', {}).get('span_id')} lacks "
            f"counter attr {k}"
            for k in _COUNTER_ATTRS
            if any(k in sp.get("args", {}) for sp in kspans)
            for sp in kspans if k not in sp.get("args", {})]


def check(trace) -> int:
    """CI gate: 0 iff the trace is non-empty, well-nested, every query
    span closed, every recorded ranking score-consistent, and the
    ``kernel.eval`` cost and counter attrs all-or-none."""
    spans, decisions = trace["spans"], trace["decisions"]
    errors: List[str] = []
    if not spans:
        errors.append("trace has no spans")
    by_id, children = index_spans(spans)
    for sp in spans:
        a = sp.get("args", {})
        pid = a.get("parent_id")
        if pid is None:
            continue
        parent = by_id.get(pid)
        if parent is None:
            errors.append(f"span {a.get('span_id')} ({sp['name']}) "
                          f"references missing parent {pid}")
            continue
        # a child recorded on another thread (read_ahead worker) never
        # carries a parent_id, so strict containment applies to the rest
        p0 = parent["ts"] - NEST_TOL_US
        p1 = parent["ts"] + parent.get("dur", 0.0) + NEST_TOL_US
        c0, c1 = sp["ts"], sp["ts"] + sp.get("dur", 0.0)
        if c0 < p0 or c1 > p1:
            errors.append(
                f"span {a.get('span_id')} ({sp['name']}) "
                f"[{c0:.1f}, {c1:.1f}]us escapes parent "
                f"{pid} ({parent['name']}) [{p0:.1f}, {p1:.1f}]us")
    for sp in spans:
        if sp["name"] != "query":
            continue
        sid = sp.get("args", {}).get("span_id")
        if sp.get("dur", 0.0) <= 0.0 and children.get(sid):
            errors.append(f"query span {sid} "
                          f"({sp.get('args', {}).get('query')}) has "
                          f"children but zero duration (never closed?)")
    errors.extend(verify_rankings(decisions))
    errors.extend(check_cost_attribution(spans))
    errors.extend(check_counters(spans))
    if errors:
        for e in errors[:20]:
            print(f"CHECK FAIL: {e}", file=sys.stderr)
        if len(errors) > 20:
            print(f"... {len(errors) - 20} more", file=sys.stderr)
        return 1
    n_q = sum(1 for sp in spans if sp["name"] == "query")
    print(f"trace OK: {len(spans)} spans ({n_q} queries), "
          f"{len(decisions)} decisions, all nested and score-consistent")
    return 0


def main() -> None:
    ap = argparse.ArgumentParser(
        description="explain a serve.py --trace-out trace")
    ap.add_argument("trace", help="Chrome trace-event JSON from "
                                  "serve.py --trace-out")
    ap.add_argument("--check", action="store_true",
                    help="CI gate: validate and exit (non-zero on a "
                         "malformed or inconsistent trace)")
    ap.add_argument("--why", nargs=2, metavar=("A", "B"),
                    help="explain why partition A was ranked before B "
                         "(term-by-term score comparison per round)")
    ap.add_argument("--cost", action="store_true",
                    help="per-kernel cost attribution table: measured "
                         "steady-state time vs the predicted FLOPs/bytes/"
                         "roofline bound stamped by the resource profiler")
    ap.add_argument("--query", default="",
                    help="only decompose queries whose name contains this")
    ap.add_argument("--top", type=int, default=10,
                    help="max queries / decisions to print (default 10)")
    args = ap.parse_args()

    trace = load_trace(args.trace)
    if args.check:
        sys.exit(check(trace))
    if args.why:
        report_why(trace["decisions"], args.why[0], args.why[1])
        return
    if args.cost:
        report_cost(trace["spans"])
        return
    spans = trace["spans"]
    _, children = index_spans(spans)
    report_queries(spans, children, args.top, args.query)
    report_aggregate(spans)
    if any(sp.get("args", {}).get("kernel_key") is not None
           for sp in _kernel_spans(spans)):
        print()
        report_cost(spans)
    report_rankings(trace["decisions"], args.top)
    report_admissions(trace["decisions"], args.top)
    problems = verify_rankings(trace["decisions"])
    if problems:
        print("\n!! score inconsistencies:")
        for p in problems:
            print(f"  {p}")
        sys.exit(1)


if __name__ == "__main__":
    main()
