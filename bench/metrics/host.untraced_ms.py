"""Host loop: mean time of a query that no span below it names, in ms.

Per completed ``query`` span: its duration less the union of the
intervals of all its descendants (clipped to the query's own interval).
"""


def read(run):
    spans = [s for s in run.spans or [] if s.t1 is not None]
    by_id = {s.span_id: s for s in spans}
    below = {}
    for s in spans:
        p = s.parent_id
        while p in by_id:
            if by_id[p].name == "query":
                below.setdefault(p, []).append((s.t0, s.t1))
            p = by_id[p].parent_id
    out = []
    for q in spans:
        if q.name != "query":
            continue
        covered, end = 0.0, q.t0
        for a, b in sorted(below.get(q.span_id, [])):
            a, b = max(a, end), min(b, q.t1)
            if b > a:
                covered += b - a
                end = b
        out.append(q.t1 - q.t0 - covered)
    return 1e3 * sum(out) / len(out) if out else None
