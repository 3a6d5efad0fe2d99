"""Front end: mean time from a request's due time to its ``admit``, in ms.

Timed by the open-loop driver over ``GraphSession.scheduler()``: a request
due while a scheduler round runs, or while ``max_in_flight`` requests are
admitted, waits in the front end.  Open loop only.
"""


def read(run):
    if run.loop != "open":
        return None
    waits = [r.admitted - r.due for r in run.requests if r.admitted is not None]
    return 1e3 * sum(waits) / len(waits) if waits else None
