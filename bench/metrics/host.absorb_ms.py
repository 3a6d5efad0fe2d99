"""Host loop: mean duration of an ``eval.absorb`` span, in ms.

A span covers the readback of the row buffers one evaluator call filled
and ``absorb_eval_outputs``: completed rows into the FAA, outgoing rows
routed to their partitions' IMA files and deduplicated.
"""


def read(run):
    d = [s.t1 - s.t0 for s in run.spans or [] if s.name == "eval.absorb"
         and s.t1 is not None]
    return 1e3 * sum(d) / len(d) if d else None
