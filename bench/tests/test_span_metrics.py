"""The readers of the host-stretch metrics, on a hand-built span list.

Times are written in ms.  One closed-loop query (id 1, 0-100 ms) with two
rounds, a second query (id 20, 200-210 ms) with no spans below it, and
one span never closed:

    query 1                 0-100
      query.plan            1-3
      heuristics.rank       4-5
      opat.round            5-40
        store.load          6-7
        eval.inputs         8-10
        kernel.eval        10-30   n_iters 3
          eval.launch      10-14
            jit.compile    11-13
        eval.absorb        31-35
      heuristics.rank      41-43
      opat.round           43-90
        eval.inputs        44-45
        kernel.eval        45-80   n_iters 5
          eval.launch      45-46
        eval.absorb        81-83
      heuristics.rank      91-92
    query 20              200-210
"""
from types import SimpleNamespace

import pytest

import run

SPANS = [  # (id, parent, name, t0, t1, attrs)
    (1, None, "query", 0, 100, {}),
    (2, 1, "query.plan", 1, 3, {}),
    (3, 1, "heuristics.rank", 4, 5, {}),
    (4, 1, "opat.round", 5, 40, {}),
    (5, 4, "store.load", 6, 7, {}),
    (6, 4, "eval.inputs", 8, 10, {}),
    (7, 4, "kernel.eval", 10, 30, {"n_iters": 3}),
    (8, 7, "eval.launch", 10, 14, {}),
    (9, 8, "jit.compile", 11, 13, {}),
    (10, 4, "eval.absorb", 31, 35, {}),
    (11, 1, "heuristics.rank", 41, 43, {}),
    (12, 1, "opat.round", 43, 90, {}),
    (13, 12, "eval.inputs", 44, 45, {}),
    (14, 12, "kernel.eval", 45, 80, {"n_iters": 5}),
    (15, 14, "eval.launch", 45, 46, {}),
    (16, 12, "eval.absorb", 81, 83, {}),
    (17, 1, "heuristics.rank", 91, 92, {}),
    (20, None, "query", 200, 210, {}),
    (21, None, "heuristics.rank", 300, None, {}),
]


def _record(rows):
    spans = [SimpleNamespace(span_id=i, parent_id=p, name=n, t0=a / 1e3,
                             t1=None if b is None else b / 1e3, attrs=at,
                             thread="MainThread")
             for i, p, n, a, b, at in rows]
    return run.RunRecord(loop="closed", seconds=51.0, requests=[],
                         scheduler_loads=0, spans=spans)


@pytest.mark.parametrize("name,want", [
    ("planner.plan_ms", 2.0),                 # one plan of 2 ms
    ("scheduler.rank_ms", 4.0 / 3),           # (1 + 2 + 1) / 3; open one left out
    ("eval.host_ms", 4.0),                    # ((2 + 4) + (1 + 1)) / 2
    ("host.absorb_ms", 3.0),                  # (4 + 2) / 2
    # query 1: 100 - (2 + 36 + 49 + 1) covered = 12; query 20: 10
    ("host.untraced_ms", 11.0),
    ("eval.iters_per_eval", 4.0),             # (3 + 5) / 2
])
def test_reader_on_hand_built_spans(name, want):
    assert run.read_metric(name, _record(SPANS)) == pytest.approx(want)


NEW = {"query.plan", "heuristics.rank", "eval.inputs", "eval.launch",
       "eval.absorb", "jit.compile"}


@pytest.mark.parametrize("name", ["planner.plan_ms", "scheduler.rank_ms",
                                  "eval.host_ms", "host.absorb_ms",
                                  "eval.iters_per_eval"])
def test_reader_finds_nothing_in_a_program_without_the_spans(name):
    """A program before these spans (and counters) reads nothing, and
    does not raise."""
    old = [(i, p, n, a, b, {}) for i, p, n, a, b, _ in SPANS if n not in NEW]
    assert run.read_metric(name, _record(old)) is None
    assert run.read_metric(name, _record([])) is None


def test_untraced_time_of_a_program_without_the_spans():
    """Without the new spans the rounds still cover query 1 from 5 to
    40 and from 43 to 90 ms: 100 - 82 = 18 ms, and 10 ms for query 20."""
    old = [r for r in SPANS if r[2] not in NEW]
    assert run.read_metric("host.untraced_ms", _record(old)) == \
        pytest.approx(14.0)
