"""Evaluator: device time of one partition evaluation, in ms.

The durations of the evaluator's compiled module on the device plane of
the profiler trace, over its executions.  The jitted evaluator is
``evaluate`` in ``core/engine.py``; its module is ``jit_evaluate``, and the
scheduler's vmapped form of it is named after the same function.
"""

MODULES = ("jit_evaluate",)


def read(run):
    if run.trace is None:
        return None
    hits = [v for k, v in run.trace.modules.items() if k in MODULES]
    n = sum(c for _, c in hits)
    return 1e3 * sum(t for t, _ in hits) / n if n else None
