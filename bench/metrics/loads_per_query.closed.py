"""Scheduler and heuristics: partition loads per completed query, closed loop.

The sum of ``RunStats.loads`` over the queries completed in the window
(``QueryResult.n_loads``: OPAT's load sequence under MAX-SN), over their
number.
"""


def read(run):
    if run.loop != "closed":
        return None
    done = run.completed
    return sum(r.n_loads for r in done) / len(done) if done else None
