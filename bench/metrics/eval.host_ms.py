"""Evaluator, host side: mean host time per evaluator call, in ms.

Per ``kernel.eval`` span: its ``eval.launch`` child (dispatch of the
jitted call, the transfer of its inputs, any compile) plus the
``eval.inputs`` span that built its padded inputs, the last one on the
same thread since the previous ``kernel.eval``.  Calls without an
``eval.launch`` child are not counted.
"""


def read(run):
    spans = [s for s in run.spans or [] if s.t1 is not None]
    launch = {s.parent_id: s.t1 - s.t0 for s in spans
              if s.name == "eval.launch"}
    total, n, pending = 0.0, 0, {}
    for s in sorted(spans, key=lambda s: s.t0):
        if s.name == "eval.inputs":
            pending[s.thread] = s.t1 - s.t0
        elif s.name == "kernel.eval":
            inputs = pending.pop(s.thread, 0.0)
            if s.span_id in launch:
                total += inputs + launch[s.span_id]
                n += 1
    return 1e3 * total / n if n else None
