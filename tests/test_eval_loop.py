"""The evaluator's while loop (core/engine.py) against a plain reference
loop that re-derives the active set of the whole work buffer on every trip.

The evaluator reads its active set off the work buffer's valid mask and
derives the next frontier vertex of the rows a trip selects only.  The
reference below derives every work row's frontier before each trip,
selects the active rows in index order (the tie order of ``lax.top_k``)
and expands them with the same tile step (``engine._expand_classify``).
``expand_block`` = 4 forces many trips.  Every ``EvalResult`` field must
agree element for element: the single, vmapped and fused-kernel
(interpret mode) forms, and forms whose buffers overflow ``cap``.  A
mutation that lets a non-local row into the work buffer (``keep`` without
its locality test) breaks the invariant the loop rests on, and the same
comparison has to catch it.
"""
import dataclasses

import jax
import numpy as np
import pytest

from repro.core import (EngineConfig, build_catalog, build_partitions,
                        generate_plan, partition_graph)
from repro.core import engine
from repro.core.graph import WILDCARD, GraphBuilder
from repro.core.plan import PlanArrays
from repro.core.query import Query, QueryEdge, QueryNode
from repro.core.state import apply_value_op

EB = 4
N_PLANS = 3

_classify = jax.jit(engine._expand_classify, static_argnames=("use_pallas",))


def _frontier(rows, step, valid, plan, n_steps, g2l_row, n_core):
    """Every row's active flag and next frontier vertex (global, local)."""
    s = np.clip(step, 0, plan.src_slot.shape[0] - 1)
    fg = rows[np.arange(rows.shape[0]), plan.src_slot[s]]
    lidx = np.where(fg >= 0, g2l_row[np.clip(fg, 0, g2l_row.shape[0] - 1)], -1)
    act = valid & (step < n_steps) & (lidx >= 0) & (lidx < n_core)
    return act, lidx, fg


def _append(bufs, n, srcs, mask):
    """Masked rows in order from slot ``n`` on, dropped past the end;
    returns (new n, overflowed)."""
    idx = np.flatnonzero(mask)
    size = bufs[0].shape[0]
    fit = idx[: size - n]
    for buf, src in zip(bufs, srcs):
        buf[n : n + fit.size] = src[fit]
    total = n + idx.size
    return min(total, size), total > size


def reference_evaluate(cfg, part, g2l_row, owner, plan, n_steps,
                       in_rows, in_step, in_valid, seed_fresh,
                       classify=_classify):
    part = {k: np.asarray(v) for k, v in part.items()}
    n_core = int(part["n_core"])
    Np, W = part["ell_dst"].shape
    Q, CAP = cfg.q_pad, cfg.cap
    WT = CAP + Np
    eb = min(cfg.expand_block, WT)
    aux = None
    if cfg.use_pallas:
        from repro.kernels import ops as kops
        aux = kops.denorm_locality(part["ell_dgid"], g2l_row, owner)

    start_ok = ((np.arange(Np) < n_core)
                & ((plan.start_label == WILDCARD)
                   | (part["node_label"] == plan.start_label))
                & apply_value_op(int(plan.start_value_op), part["node_value"],
                                 plan.start_value)
                & bool(seed_fresh))
    fresh = np.full((Np, Q), -1, np.int32)
    fresh[start_ok, plan.start_slot] = part["node_gid"][start_ok]
    wr = np.concatenate([in_rows, fresh])
    ws = np.concatenate([in_step, np.zeros(Np, np.int32)])
    wv = np.concatenate([in_valid, start_ok])

    cr = np.full((CAP, Q), -1, np.int32)
    orr = np.full((CAP, Q), -1, np.int32)
    os_ = np.zeros(CAP, np.int32)
    od = np.full(CAP, -1, np.int32)

    done0 = wv & (ws >= n_steps)
    act0, _, fg0 = _frontier(wr, ws, wv, plan, n_steps, g2l_row, n_core)
    dest0 = owner[np.clip(fg0, 0, owner.shape[0] - 1)]
    cn, o1 = _append([cr], 0, [wr], done0)
    on, o2 = _append([orr, os_, od], 0, [wr, ws, dest0], wv & ~done0 & ~act0)
    ovf = o1 | o2
    wv = wv & act0

    it = nx = 0
    while True:
        act, lidx, _ = _frontier(wr, ws, wv, plan, n_steps, g2l_row, n_core)
        if not act.any() or it >= cfg.max_inner_iters:
            break
        sel = np.argsort(~act, kind="stable")[:eb]
        m = act[sel]
        rows_b, step_b = wr[sel], ws[sel]
        wv[sel] &= ~m
        ok, dg, ns, nr, done, keep, outm, dest = jax.device_get(classify(
            rows_b, step_b, lidx[sel], m, part, g2l_row, owner, aux, plan,
            np.int32(n_steps), use_pallas=cfg.use_pallas))
        nr, ns = nr.reshape(-1, Q), ns.reshape(-1)
        cn, o1 = _append([cr], cn, [nr], done.reshape(-1))
        on, o2 = _append([orr, os_, od], on, [nr, ns, dest.reshape(-1)],
                         outm.reshape(-1))
        keep = keep.reshape(-1)
        kfree = min(eb * W, WT)
        ovf = ovf | o1 | o2 | (keep.sum() > (~wv).sum())
        free = np.argsort(wv, kind="stable")[:kfree]
        kept = np.flatnonzero(keep)[:kfree]
        tgt = free[: kept.size]
        wr[tgt], ws[tgt], wv[tgt] = nr[kept], ns[kept], True
        it += 1
        nx += int(m.sum())
    return engine.EvalResult(cr, np.int32(cn), orr, os_, od, np.int32(on),
                             np.bool_(ovf), np.int32(it), np.int32(nx))


def _random_graph(rng):
    b = GraphBuilder()
    n = 120
    for _ in range(n):
        val = float(rng.integers(0, 10)) if rng.random() < 0.5 else None
        b.add_node(f"L{int(rng.integers(0, 3))}", value=val)
    for _ in range(3 * n):
        s, d = rng.integers(0, n, size=2)
        if s != d:
            b.add_edge(int(s), int(d), f"E{int(rng.integers(0, 2))}",
                       directed=bool(rng.random() < 0.3))
    return b.build()


def _random_query(rng):
    """Four nodes on a random spanning tree, half the time with one more
    edge that closes a cycle."""
    nodes = [QueryNode("?" if rng.random() < 0.5 else f"L{rng.integers(0, 3)}",
                       value_op=str(rng.choice(["", "", "!=", "<", ">="])),
                       value=float(rng.integers(0, 10)))
             for _ in range(4)]
    pairs = [(int(rng.integers(0, i)), i) for i in range(1, 4)]
    if rng.random() < 0.5:
        extra = (0, 2) if (0, 2) not in pairs else (0, 3)
        if extra not in pairs:
            pairs.append(extra)
    edges = [QueryEdge(a, b, "?" if rng.random() < 0.5
                       else f"E{rng.integers(0, 2)}",
                       direction=int(rng.integers(0, 3)))
             for a, b in pairs]
    return Query(nodes=nodes, edges=edges)


@pytest.fixture(scope="module")
def setup():
    rng = np.random.default_rng(1405)
    g = _random_graph(rng)
    pg = build_partitions(g, partition_graph(g, 4, "kway_shem"), 4)
    cat = build_catalog(g)
    S = EngineConfig().s_pad
    plans = []
    for _ in range(N_PLANS):
        plan = generate_plan(_random_query(rng), g, cat)
        pa = PlanArrays.from_plan(plan, pad_steps=S)
        # one trace for every plan: the engine reads n_steps as an argument
        plans.append((dataclasses.replace(pa, n_slots=0, n_steps=S),
                      plan.n_steps, plan.n_slots))
    return g, pg, plans


def _inputs(rng, cfg, pg, pid, plan, n_steps, n_slots):
    """Incoming rows for partition ``pid``: the continuations another
    partition's fresh evaluation sends here, then random rows (some
    complete, some out of place) and invalid slots."""
    other = (pid + 1) % pg.k
    res = reference_evaluate(
        cfg, engine.part_to_device_dict(pg.parts[other]), pg.g2l[other],
        pg.owner, plan, n_steps,
        np.full((cfg.cap, cfg.q_pad), -1, np.int32),
        np.zeros(cfg.cap, np.int32), np.zeros(cfg.cap, bool), True)
    here = np.flatnonzero(res.out_dest[: int(res.out_n)] == pid)
    rows = np.full((cfg.cap, cfg.q_pad), -1, np.int32)
    step = np.zeros(cfg.cap, np.int32)
    n = min(here.size, cfg.cap // 2)
    rows[:n] = res.out_rows[here[:n]]
    step[:n] = res.out_step[here[:n]]
    end = n + cfg.cap // 4
    bound = rng.random((end - n, n_slots)) < 0.7
    rows[n:end, :n_slots] = np.where(
        bound, rng.integers(0, pg.graph.n_nodes, bound.shape), -1)
    step[n:end] = rng.integers(0, n_steps + 1, end - n)
    valid = (np.arange(cfg.cap) < end) & (rng.random(cfg.cap) < 0.85)
    return rows, step, valid


# form: (cap, use_pallas, vmapped); a vmapped call takes every plan as a
# lane, the single form one call per plan
FORMS = {
    "single": (512, False, False),
    "vmapped": (512, False, True),
    "fused_kernel": (512, True, False),
    "overflow": (8, False, False),
    "overflow_vmapped": (8, False, True),
    "overflow_fused_kernel": (8, True, False),
}


def _compare(setup, form, classify=_classify, **cfg_kw):
    """Run ``form`` of the evaluator and the reference on every partition
    and plan; returns the mismatched (pid, field) pairs, the reference's
    trips and its overflow flags."""
    g, pg, plans = setup
    cap, use_pallas, vmapped = FORMS[form]
    cfg = EngineConfig(cap=cap, expand_block=EB, use_pallas=use_pallas,
                       **cfg_kw)
    ev = engine.make_partition_evaluator(pg.node_pad, pg.ell_width, cfg)
    if vmapped:
        ev = jax.jit(jax.vmap(ev, in_axes=(None, None, None) + (0,) * 6))
    rng = np.random.default_rng(len(form))
    mismatched, trips, overflowed = [], [], []
    for pid in range(pg.k):
        part = engine.part_to_device_dict(pg.parts[pid])
        g2l, owner = pg.g2l[pid], pg.owner
        calls = []
        for i, (plan, n_steps, n_slots) in enumerate(plans):
            rows, step, valid = _inputs(rng, cfg, pg, pid, plan, n_steps,
                                        n_slots)
            calls.append((plan, np.int32(n_steps), rows, step, valid,
                          np.bool_((pid + i) % 2 == 0)))
        want = [reference_evaluate(cfg, part, g2l, owner, *c,
                                   classify=classify) for c in calls]
        if vmapped:
            stacked = [PlanArrays.stack([c[0] for c in calls])]
            stacked += [np.stack([c[i] for c in calls]) for i in range(1, 6)]
            out = jax.device_get(ev(part, g2l, owner, *stacked))
            got = [jax.tree.map(lambda x: x[i], out) for i in range(len(calls))]
        else:
            got = [jax.device_get(ev(part, g2l, owner, *c)) for c in calls]
        for w, r in zip(want, got):
            for field in engine.EvalResult._fields:
                a, b = getattr(r, field), getattr(w, field)
                if not (a.dtype == b.dtype and np.array_equal(a, b)):
                    mismatched.append((pid, field))
            trips.append(int(w.n_iters))
            overflowed.append(bool(w.overflow))
    return mismatched, trips, overflowed


@pytest.mark.parametrize("form", list(FORMS))
def test_loop_matches_per_trip_rederivation(setup, form):
    mismatched, trips, overflowed = _compare(setup, form)
    assert mismatched == [], form
    assert max(trips) >= 3          # the loop really took many trips
    assert any(overflowed) == form.startswith("overflow")


def test_comparison_catches_a_non_local_keep_row(setup, monkeypatch):
    """``keep`` without its locality test writes rows into the work buffer
    whose next frontier vertex lies in another partition.  The reference,
    which re-derives the active set, leaves them idle; the evaluator, which
    reads the active set off the valid mask, keeps selecting them."""
    original = engine._expand_classify

    def keep_skips_locality(*args, **kw):
        ok, dg, ns, nr, done, keep, outm, dest = original(*args, **kw)
        return ok, dg, ns, nr, done, ok & ~done, outm, dest

    monkeypatch.setattr(engine, "_expand_classify", keep_skips_locality)
    mutated = jax.jit(keep_skips_locality, static_argnames=("use_pallas",))
    mismatched, _, _ = _compare(setup, "single", classify=mutated,
                                max_inner_iters=200)
    assert ("n_iters" in {f for _, f in mismatched}), mismatched
