"""Evaluator: mean while-loop trips per evaluator call.

The ``n_iters`` attribute the program stamps on each ``kernel.eval`` span
(the evaluator's own trip counter, read back with its other scalars; the
lanes of a batched call summed).
"""


def read(run):
    n = [s.attrs["n_iters"] for s in run.spans or []
         if s.name == "kernel.eval" and "n_iters" in s.attrs]
    return sum(n) / len(n) if n else None
