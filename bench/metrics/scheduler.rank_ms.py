"""Scheduler and heuristics: mean duration of a ``heuristics.rank`` span, in ms.

A span covers one choice of the next partition between two evaluations:
which partitions are eligible, their SNI and yield counts, and the
heuristic's ranking.  The last of a query's finds nothing eligible.
"""


def read(run):
    d = [s.t1 - s.t0 for s in run.spans or [] if s.name == "heuristics.rank"
         and s.t1 is not None]
    return 1e3 * sum(d) / len(d) if d else None
