"""Host loop: mean self time of a round, in ms.

A round is an ``opat.round`` (closed loop) or ``scheduler.round`` (open
loop) span.  Its self time is its duration less that of its
``store.load`` and ``kernel.eval`` children: choosing the partition is
outside it, and what is left is host work inside the round, such as
building the evaluator's inputs, staging the runner-up and routing the
outputs into the IMA/FAA (``absorb_eval_outputs``).
"""

ROUNDS = ("opat.round", "scheduler.round")
CHILDREN = ("store.load", "kernel.eval")


def read(run):
    spans = [s for s in run.spans or [] if s.t1 is not None]
    rounds = {s.span_id: s.t1 - s.t0 for s in spans if s.name in ROUNDS}
    for s in spans:
        if s.parent_id in rounds and s.name in CHILDREN:
            rounds[s.parent_id] -= s.t1 - s.t0
    return 1e3 * sum(rounds.values()) / len(rounds) if rounds else None
