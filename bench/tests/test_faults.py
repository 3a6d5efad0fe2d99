"""A broken timed path makes ``correct`` false.

Each test drives a whole rehearsal run in this process (the harness's
look for a chip is the only step skipped) with one fault planted in the
program underneath, and reads the result line.  The faults are those a
cell of this benchmark can have: an answer altered where it is produced,
a step that leaves the query's state unchanged, and half of a batch left
out.  No cell spans chips, so there is no exchange between chips to drop.
"""
import json

import numpy as np
import pytest

import run
from repro.core import opat, scheduler, state
from repro.core.state import BindingBatch

CLOSED = ["subgen-400k-k4-resident.paper-closed", "subgen-400k-k4-ooc1.paper-closed"]
OPEN = "subgen-400k-k4-resident.paper-open"


@pytest.fixture(autouse=True)
def _all_cells(checkout_with_all_cells, monkeypatch):
    monkeypatch.setattr(run, "ROOT", checkout_with_all_cells)


def _line(capsys, cell, seed=31):
    assert run.main(["--workload", cell, "--seed", str(seed), "--seconds", "2",
                     "--trace", "0", "--rehearse"]) == 0
    return json.loads(capsys.readouterr().out.strip().splitlines()[-1])


@pytest.mark.parametrize("cell", CLOSED + [OPEN])
def test_an_altered_answer(cell, capsys, monkeypatch):
    orig = state.QueryState.unique_answers

    def altered(self):
        a = orig(self).copy()
        if a.shape[0]:
            a[0, 0] = (a[0, 0] + 1) % 1000
        return a
    monkeypatch.setattr(state.QueryState, "unique_answers", altered)
    line = _line(capsys, cell)
    assert line["correct"] is False
    assert line["checks"]["mismatched_rows"]["value"] > 0


@pytest.mark.parametrize("cell", [CLOSED[0], OPEN])
def test_a_step_that_leaves_the_state_unchanged(cell, capsys, monkeypatch):
    def unchanged(*args, **kwargs):
        return None
    monkeypatch.setattr(opat, "absorb_eval_outputs", unchanged)
    monkeypatch.setattr(scheduler, "absorb_eval_outputs", unchanged)
    assert _line(capsys, cell)["correct"] is False


def test_half_of_the_scheduler_batch_left_out(capsys, monkeypatch):
    """The jobs of the second half of a batched round lose their
    pending rows unevaluated; the rate is raised so that rounds batch."""
    orig = scheduler.QueryScheduler._eval_batch
    left_out = []

    def half(self, beval, entry, pid, batch):
        keep = batch[: (len(batch) + 1) // 2]
        for j in batch[len(keep):]:
            j.state.ima[pid] = BindingBatch.empty(self.session.config.q_pad)
            j.state.fresh_pending[pid] = False
            left_out.append(j)
        return orig(self, beval, entry, pid, keep)
    monkeypatch.setattr(scheduler.QueryScheduler, "_eval_batch", half)
    load = run.traffic.load_mix
    monkeypatch.setattr(run.traffic, "load_mix",
                        lambda name: dict(load(name), rate_qps=60.0))
    line = _line(capsys, OPEN)
    assert left_out and line["correct"] is False


def test_half_of_the_rows_of_an_evaluation_left_out(capsys, monkeypatch):
    """One query's evaluator batch: the second half of the rows handed to
    a partition evaluation is dropped."""
    orig = opat.OPATEngine._run_partition

    def half(self, entry, plan_arrays, n_steps, batch, seed_fresh, st):
        n = batch.n // 2
        batch = BindingBatch(rows=np.asarray(batch.rows)[:n],
                             step=np.asarray(batch.step)[:n])
        return orig(self, entry, plan_arrays, n_steps, batch, seed_fresh, st)
    monkeypatch.setattr(opat.OPATEngine, "_run_partition", half)
    assert _line(capsys, CLOSED[0])["correct"] is False
