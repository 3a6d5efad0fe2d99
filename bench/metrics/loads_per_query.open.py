"""Scheduler: workload partition loads per completed query, open loop.

The loads of every ``ScheduleReport`` the window's rounds produced (one
load advancing several queries counts once), over the queries completed.
"""


def read(run):
    if run.loop != "open":
        return None
    done = run.completed
    return run.scheduler_loads / len(done) if done else None
