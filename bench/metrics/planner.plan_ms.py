"""Planner: mean duration of the program's ``query.plan`` spans, in ms.

A span covers one ``generate_plan`` call: a disjunct's plan in
``GraphSession.submit``, or an admitted disjunct's in the scheduler.
"""


def read(run):
    d = [s.t1 - s.t0 for s in run.spans or [] if s.name == "query.plan"
         and s.t1 is not None]
    return 1e3 * sum(d) / len(d) if d else None
