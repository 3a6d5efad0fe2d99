"""The trace reduction: busy, idle, module and gap sums."""
import gzip
import json
from pathlib import Path

import pytest

import tracereduce as tr


def _trace(device_lines, host=()):
    planes = [tr.Plane("/device:TPU:0", [tr.Line(n, ev) for n, ev in device_lines]),
              tr.Plane("/host:CPU", [tr.Line("python", [(tr.WINDOW, 100, 900)]
                                             + list(host))])]
    return planes


def test_merge_and_gaps():
    assert tr.merge([(5, 9), (0, 3), (2, 4), (9, 10), (12, 12)]) == [(0, 4), (5, 10)]
    assert tr.gaps([(0, 4), (5, 10)], 0, 20) == [(4, 5), (10, 20)]


def test_reduce_on_a_synthetic_trace():
    ops = [("fusion.1", 150, 100), ("sort.2", 200, 100),   # overlap: 150..300
           ("fusion.1", 500, 100), ("copy.3", 950, 200)]   # clipped at 1000
    mods = [("jit_evaluate(12)", 150, 150), ("jit_evaluate(12)", 500, 100),
            ("jit_other(3)", 950, 200)]
    s = tr.reduce(_trace([("XLA Ops", ops), ("XLA Modules", mods)]),
                  host=[("kernel.eval", 300, 450, 2), ("opat.round", 0, 1000, 1)])
    assert s.window_s == pytest.approx(900e-9)
    assert s.busy_s == pytest.approx((150 + 100 + 50) * 1e-9)
    assert s.modules == {"jit_evaluate": (pytest.approx(250e-9), 2),
                         "jit_other": (pytest.approx(200e-9), 1)}
    assert dict(s.top_ops)["fusion.1"] == pytest.approx(200e-9)
    # gaps: 100..150 round, 300..500 kernel.eval (middle 400), 600..950 round
    assert s.idle_by_label == {"opat.round": pytest.approx(400e-9),
                               "kernel.eval": pytest.approx(200e-9)}
    assert s.idle_gaps[0] == ("opat.round", pytest.approx(350e-9))


def test_a_device_clock_apart_from_the_host_window():
    """Device events that miss the host's window entirely: the window is
    taken from the device's own events and no gap is labelled."""
    ops = [("a", 10_000, 100), ("b", 10_300, 200)]
    s = tr.reduce(_trace([("XLA Ops", ops)]), host=[("kernel.eval", 0, 10**6, 1)])
    assert s.window_s == pytest.approx(500e-9)
    assert s.busy_s == pytest.approx(300e-9)
    assert s.idle_by_label == {"none": pytest.approx(200e-9)}


def test_no_device_plane_is_an_error():
    planes = [tr.Plane("/host:CPU", [tr.Line("python", [(tr.WINDOW, 0, 10)])])]
    with pytest.raises(RuntimeError):
        tr.reduce(planes)



def test_a_recorded_tpu_slice():
    """400 ms of a traced run of the resident closed cell on a TPU v5e:
    the evaluator's module line (8 executions of ``jit_evaluate``), the
    harness's annotations and its window.  The sums were worked out by
    hand from the 8 events."""
    path = Path(__file__).parent / "data" / "tpu_slice.json.gz"
    with gzip.open(path, "rt") as f:
        planes = [tr.Plane(p["name"], [tr.Line(ln["name"], [tuple(e) for e in ln["events"]])
                                       for ln in p["lines"]])
                  for p in json.load(f)["planes"]]
    s = tr.reduce(planes)
    assert s.window_s == pytest.approx(0.4)
    assert s.busy_s == pytest.approx(352149896e-9)
    assert sum(s.idle_by_label.values()) == pytest.approx(47850104e-9)
    assert s.modules == {"jit_evaluate": (pytest.approx(322716160e-9), 7)}
