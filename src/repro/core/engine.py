"""The jitted within-partition evaluator shared by OPAT / TraditionalMP /
MapReduceMP.

One compiled function evaluates *any* partition of a given padded geometry:
it seeds fresh start-node bindings (when the partition is processed for the
first time), expands all local partial answers breadth-first following the
plan, and classifies every produced row as

  completed  -> appended to the FAA buffer,
  local      -> next frontier vertex owned here; kept in the work buffer,
  outgoing   -> next frontier vertex owned elsewhere; emitted with its
                destination partition id (the paper's PCA/IMA continuation).

All buffers are fixed capacity; saturation sets an ``overflow`` flag the
host checks (the host then re-runs with a bigger capacity — never silent).

TPU adaptation: the per-step expansion evaluates an [EB, W] tile (EB active
bindings x ELLPACK width W) of candidate edges *densely* — predicates are
branchless masks, a perfect VPU shape — instead of the pointer-chasing loop
a CPU implementation would use.  The tile-match inner block is exactly what
``kernels/frontier_expand.py`` implements as a Pallas kernel; ``use_pallas``
routes through it (interpret mode on CPU).
"""
from __future__ import annotations

import dataclasses
from typing import (Any, Callable, Dict, NamedTuple, Optional, Sequence,
                    Tuple)

import jax
import jax.numpy as jnp
import numpy as np

from .graph import DIR_BACKWARD, DIR_FORWARD, DIR_UNDIRECTED, PartitionArrays, WILDCARD
from .plan import PlanArrays
from .query import QDIR_ANY, QDIR_IN, QDIR_OUT
from .state import apply_value_op


# The evaluator's module name, in HLO and on a device trace's module line,
# for the single form and every vmapped form alike.
EVAL_MODULE = "jit_evaluate"


def jit_evaluator(fn: Callable) -> Callable:
    """``jax.jit`` of ``fn`` under the module name ``EVAL_MODULE``, so the
    name does not hang on whatever Python function is being wrapped."""
    def evaluate(*args):
        return fn(*args)
    evaluate.__name__ = evaluate.__qualname__ = EVAL_MODULE[len("jit_"):]
    return jax.jit(evaluate)


@dataclasses.dataclass(frozen=True)
class EngineConfig:
    """Static geometry for the compiled evaluator."""

    q_pad: int = 8            # binding row width (max query nodes)
    s_pad: int = 12           # padded plan length
    cap: int = 4096           # in/out/completed buffer capacity
    expand_block: int = 512   # active rows expanded per loop iteration (EB)
    max_inner_iters: int = 10_000
    use_pallas: bool = False


class EvalResult(NamedTuple):
    comp_rows: jax.Array      # [cap, Q]
    comp_n: jax.Array         # []
    out_rows: jax.Array       # [cap, Q]
    out_step: jax.Array       # [cap]
    out_dest: jax.Array       # [cap]
    out_n: jax.Array          # []
    overflow: jax.Array       # [] bool
    n_iters: jax.Array        # []
    n_expanded: jax.Array     # [] total candidate rows expanded


def _match_tile_jnp(rows_b, step_b, lidx_b, m,
                    ell_dst, ell_label, ell_dir,
                    node_label, node_value, node_gid,
                    plan, n_steps):
    """Dense [EB, W] candidate-edge match.  Returns (ok, dg, ns, nr)."""
    EB = rows_b.shape[0]
    Q = rows_b.shape[1]
    s = jnp.clip(step_b, 0, plan.src_slot.shape[0] - 1)
    p_el = plan.edge_label[s]          # [EB]
    p_dir = plan.direction[s]
    p_dlab = plan.dst_label[s]
    p_dop = plan.dst_value_op[s]
    p_dval = plan.dst_value[s]
    p_dst = plan.dst_slot[s]
    p_closes = plan.closes_cycle[s]

    lsafe = jnp.clip(lidx_b, 0, ell_dst.shape[0] - 1)
    ed = jnp.take(ell_dst, lsafe, axis=0)      # [EB, W] local dst
    el = jnp.take(ell_label, lsafe, axis=0)
    edir = jnp.take(ell_dir, lsafe, axis=0)

    edge_exists = ed >= 0
    elabel_ok = (p_el[:, None] == WILDCARD) | (el == p_el[:, None])
    dir_ok = ((p_dir[:, None] == QDIR_ANY)
              | (edir == DIR_UNDIRECTED)
              | ((p_dir[:, None] == QDIR_OUT) & (edir == DIR_FORWARD))
              | ((p_dir[:, None] == QDIR_IN) & (edir == DIR_BACKWARD)))

    dsafe = jnp.clip(ed, 0, node_label.shape[0] - 1)
    dl = jnp.take(node_label, dsafe)
    dv = jnp.take(node_value, dsafe)
    dg = jnp.take(node_gid, dsafe)            # global id of candidate dst

    dlabel_ok = (p_dlab[:, None] == WILDCARD) | (dl == p_dlab[:, None])
    dval_ok = apply_value_op(p_dop[:, None], dv, p_dval[:, None])
    # injectivity: candidate must not already be bound to another slot
    inj_ok = ~jnp.any(rows_b[:, None, :] == dg[:, :, None], axis=-1)

    bound_dst = jnp.take_along_axis(rows_b, p_dst[:, None], axis=1)  # [EB,1]
    cyc_ok = (p_closes[:, None] == 1) & (bound_dst == dg)
    new_ok = (p_closes[:, None] == 0) & dlabel_ok & dval_ok & inj_ok

    ok = (m[:, None] & (step_b[:, None] < n_steps)
          & edge_exists & elabel_ok & dir_ok & (cyc_ok | new_ok))

    # new rows: bind dst slot (unless cycle closure keeps bindings unchanged)
    col = jnp.arange(Q, dtype=jnp.int32)
    setcol = (col[None, None, :] == p_dst[:, None, None]) & (p_closes[:, None, None] == 0)
    nr = jnp.where(setcol, dg[:, :, None], rows_b[:, None, :])      # [EB, W, Q]
    ns = jnp.broadcast_to(step_b[:, None] + 1, ok.shape)            # [EB, W]
    return ok, dg, ns, nr


def _next_rows(rows_b, step_b, dg, ok_shape, plan):
    """New binding rows + steps (scatter-shaped; stays in jnp either way)."""
    Q = rows_b.shape[1]
    s = jnp.clip(step_b, 0, plan.src_slot.shape[0] - 1)
    p_dst = plan.dst_slot[s]
    p_closes = plan.closes_cycle[s]
    col = jnp.arange(Q, dtype=jnp.int32)
    setcol = (col[None, None, :] == p_dst[:, None, None]) & (p_closes[:, None, None] == 0)
    nr = jnp.where(setcol, dg[:, :, None], rows_b[:, None, :])
    ns = jnp.broadcast_to(step_b[:, None] + 1, ok_shape)
    return nr, ns


def _expand_classify(rows_b, step_b, lidx_b, m, part, g2l_row, owner, aux,
                     plan, n_steps, use_pallas):
    """Fused inner step: match an [EB, W] candidate tile AND classify every
    produced row as done / keep / outgoing (with destination pid).

    ``aux`` is the (ell_dlidx, ell_downer) pair from kops.denorm_locality
    when use_pallas (hoisted out of the while loop), else None.
    Returns ([EB, W]-shaped) ok, dg, ns, nr, done, keep, outm, dest.
    """
    n_core = part["n_core"]
    if use_pallas:
        from ..kernels import ops as kops
        ell_dlidx, ell_downer = aux
        ok, dg, done, keep, outm, dest = kops.fused_frontier(
            rows_b, step_b, lidx_b, m,
            part["ell_dst"], part["ell_label"], part["ell_dir"],
            part["ell_dlab"], part["ell_dval"], part["ell_dgid"],
            ell_dlidx, ell_downer, g2l_row, owner, n_core,
            plan, n_steps)
        nr, ns = _next_rows(rows_b, step_b, dg, ok.shape, plan)
        return ok, dg, ns, nr, done, keep, outm, dest

    ok, dg, ns, nr = _match_tile_jnp(
        rows_b, step_b, lidx_b, m,
        part["ell_dst"], part["ell_label"], part["ell_dir"],
        part["node_label"], part["node_value"], part["node_gid"],
        plan, n_steps)
    done = ok & (ns >= n_steps)
    s2 = jnp.clip(ns, 0, plan.src_slot.shape[0] - 1)
    nsrc = plan.src_slot[s2]                                   # [EB, W]
    fg = jnp.take_along_axis(nr, nsrc[:, :, None], axis=2)[:, :, 0]
    fg_safe = jnp.clip(fg, 0, g2l_row.shape[0] - 1)
    l2 = jnp.take(g2l_row, fg_safe)
    local = (l2 >= 0) & (l2 < n_core) & (fg >= 0)
    keep = ok & ~done & local
    outm = ok & ~done & ~local
    dest = jnp.take(owner, fg_safe)
    return ok, dg, ns, nr, done, keep, outm, dest


def make_partition_evaluator(node_pad: int, ell_width: int, cfg: EngineConfig):
    """Build the jitted evaluator.

    Geometry-agnostic: the padded node count ``Np`` and ELLPACK width ``W``
    are read off the *input array shapes* at trace time (``node_pad`` /
    ``ell_width`` are advisory — kept in the signature for callers that
    size buffers up front), so one returned callable serves partitions of
    any geometry; jit retraces per distinct shape.  This is what lets a
    pinned old generation and a freshly compacted generation with grown
    padding share one evaluator (storage/deltas.py).
    """

    Q, S = cfg.q_pad, cfg.s_pad
    CAP = cfg.cap

    def _frontier_local(rows, step, valid, plan, n_steps, g2l_row, n_core):
        """active mask + local index of each row's next frontier vertex."""
        s = jnp.clip(step, 0, S - 1)
        src_slot = plan.src_slot[s]
        fg = jnp.take_along_axis(rows, src_slot[:, None], axis=1)[:, 0]
        fg_safe = jnp.clip(fg, 0, g2l_row.shape[0] - 1)
        lidx = jnp.take(g2l_row, fg_safe)
        lidx = jnp.where(fg >= 0, lidx, -1)
        local = (lidx >= 0) & (lidx < n_core)
        act = valid & (step < n_steps) & local
        return act, lidx, fg

    def _append(buf_rows, buf_aux, buf_n, rows_flat, aux_flat, mask_flat, overflow):
        """Masked append into a fixed buffer via out-of-bounds-drop scatter."""
        cnt = jnp.cumsum(mask_flat.astype(jnp.int32)) - 1
        tgt = jnp.where(mask_flat, buf_n + cnt, buf_rows.shape[0])
        buf_rows = buf_rows.at[tgt].set(rows_flat, mode="drop")
        new_aux = []
        for b, a in zip(buf_aux, aux_flat):
            new_aux.append(b.at[tgt].set(a, mode="drop"))
        total = buf_n + mask_flat.sum(dtype=jnp.int32)
        overflow = overflow | (total > buf_rows.shape[0])
        return buf_rows, tuple(new_aux), jnp.minimum(total, buf_rows.shape[0]), overflow

    def evaluate(part: Dict[str, jax.Array], g2l_row: jax.Array,
                 owner: jax.Array, plan: PlanArrays, n_steps: jax.Array,
                 in_rows: jax.Array, in_step: jax.Array, in_valid: jax.Array,
                 seed_fresh: jax.Array) -> EvalResult:
        n_core = part["n_core"]
        pid = part["pid"]
        Np = part["node_label"].shape[0]   # static at trace time
        W = part["ell_dst"].shape[1]
        WT = CAP + Np  # work buffer: incoming rows + fresh seeds
        EB = min(cfg.expand_block, WT)  # can't select more rows than exist

        if cfg.use_pallas:
            # locality tables for the fused kernel: computed once per call,
            # hoisted out of the while loop (static python branch — cfg is
            # a closure constant, so the jnp path pays nothing)
            from ..kernels import ops as kops
            aux = kops.denorm_locality(part["ell_dgid"], g2l_row, owner)
        else:
            aux = None

        # ---- seed fresh start-node bindings (SNI entries with NULL vid) ----
        node_idx = jnp.arange(Np, dtype=jnp.int32)
        start_ok = ((node_idx < n_core)
                    & ((plan.start_label == WILDCARD)
                       | (part["node_label"] == plan.start_label))
                    & apply_value_op(plan.start_value_op, part["node_value"],
                                     plan.start_value)
                    & seed_fresh)
        col = jnp.arange(Q, dtype=jnp.int32)
        fresh_rows = jnp.where((col[None, :] == plan.start_slot) & start_ok[:, None],
                               part["node_gid"][:, None],
                               jnp.int32(-1))
        work_rows = jnp.concatenate([in_rows, fresh_rows], axis=0)          # [WT, Q]
        work_step = jnp.concatenate([in_step, jnp.zeros(Np, jnp.int32)])
        work_valid = jnp.concatenate([in_valid, start_ok])

        comp_rows = jnp.full((CAP, Q), -1, jnp.int32)
        comp_n = jnp.int32(0)
        out_rows = jnp.full((CAP, Q), -1, jnp.int32)
        out_step = jnp.zeros(CAP, jnp.int32)
        out_dest = jnp.full(CAP, -1, jnp.int32)
        out_n = jnp.int32(0)
        overflow = jnp.bool_(False)

        # ---- pre-classify: rows already complete, or frontier not local ----
        done0 = work_valid & (work_step >= n_steps)
        act0, _, fg0 = _frontier_local(work_rows, work_step, work_valid, plan,
                                       n_steps, g2l_row, n_core)
        outm0 = work_valid & ~done0 & ~act0
        dest0 = jnp.take(owner, jnp.clip(fg0, 0, owner.shape[0] - 1))
        comp_rows, _, comp_n, overflow = _append(
            comp_rows, (), comp_n, work_rows, (), done0, overflow)
        out_rows, (out_step, out_dest), out_n, overflow = _append(
            out_rows, (out_step, out_dest), out_n, work_rows,
            (work_step, dest0), outm0, overflow)
        # From here on every valid work row is active (step < n_steps, next
        # frontier vertex core-local), so the loop reads the active set off
        # ``wv`` and derives the frontier of the EB rows it selects only.
        # Rows enter the buffer just here and as ``keep`` rows in ``body``,
        # whose ``local`` is this same test; a new way of writing rows into
        # the buffer that skips ``keep`` would break the invariant.
        work_valid = work_valid & act0

        state = (work_rows, work_step, work_valid, comp_rows, comp_n,
                 out_rows, out_step, out_dest, out_n, overflow,
                 jnp.int32(0), jnp.int32(0))

        def cond(st):
            _wr, _ws, wv, *_, it, _nx = st
            return jnp.any(wv) & (it < cfg.max_inner_iters)

        def body(st):
            (wr, ws, wv, cr, cn, orr, os_, od, on, ovf, it, nx) = st
            # pick up to EB active rows: top_k on the mask is O(WT log EB)
            # vs the original full argsort's O(WT log WT) (§Perf-D2)
            _, sel = jax.lax.top_k(wv.astype(jnp.int32), EB)
            rows_b = jnp.take(wr, sel, axis=0)
            step_b = jnp.take(ws, sel)
            valid_b = jnp.take(wv, sel)
            m, lidx_b, _ = _frontier_local(rows_b, step_b, valid_b, plan,
                                           n_steps, g2l_row, n_core)
            # consume them
            wv = wv.at[sel].set(valid_b & ~m)

            (ok, dg, ns, nr, done_t, keep_t, outm_t, dest_t) = _expand_classify(
                rows_b, step_b, lidx_b, m, part, g2l_row, owner, aux,
                plan, n_steps, cfg.use_pallas)

            EBW = EB * W
            ok_f = ok.reshape(EBW)
            nr_f = nr.reshape(EBW, Q)
            ns_f = ns.reshape(EBW)
            done = done_t.reshape(EBW)
            keep = keep_t.reshape(EBW)
            outm = outm_t.reshape(EBW)
            dest = dest_t.reshape(EBW)

            cr, _, cn, ovf = _append(cr, (), cn, nr_f, (), done, ovf)
            orr, (os_, od), on, ovf = _append(orr, (os_, od), on, nr_f,
                                              (ns_f, dest), outm, ovf)
            # keep-rows go into free work slots; at most EBW are needed, so
            # top_k over the free mask replaces the full argsort (§Perf-D3)
            kfree = min(EBW, WT)
            _, free = jax.lax.top_k((~wv).astype(jnp.int32), kfree)
            n_free_needed = keep.sum(dtype=jnp.int32)
            n_free_have = (~wv).sum(dtype=jnp.int32)
            ovf = ovf | (n_free_needed > n_free_have)
            pos = jnp.cumsum(keep.astype(jnp.int32)) - 1
            tgt = jnp.where(keep & (pos < kfree), free[jnp.clip(pos, 0, kfree - 1)], WT)
            wr = wr.at[tgt].set(nr_f, mode="drop")
            ws = ws.at[tgt].set(ns_f, mode="drop")
            wv = wv.at[tgt].set(True, mode="drop")

            return (wr, ws, wv, cr, cn, orr, os_, od, on, ovf,
                    it + 1, nx + m.sum(dtype=jnp.int32))

        state = jax.lax.while_loop(cond, body, state)
        (_, _, _, cr, cn, orr, os_, od, on, ovf, it, nx) = state
        return EvalResult(cr, cn, orr, os_, od, on, ovf, it, nx)

    return jit_evaluator(evaluate)


# ---------------------------------------------------------------------------
# Host-side helpers shared by the OPAT / TraditionalMP orchestrators
# ---------------------------------------------------------------------------

class EvalCounts(NamedTuple):
    """An evaluator call's scalars on the host (one entry per lane for a
    vmapped call): what the host needs before it reads any row."""

    overflow: np.ndarray
    comp_n: np.ndarray
    out_n: np.ndarray
    n_iters: np.ndarray       # while-loop trips
    n_expanded: Optional[np.ndarray]  # binding rows expanded (None:
                                      # the program does not count them)


def read_counts(res: EvalResult) -> EvalCounts:
    """The scalars of an ``EvalResult`` in one ``jax.device_get``: the
    call's only sync before its rows are read."""
    return EvalCounts(*jax.device_get((res.overflow, res.comp_n, res.out_n,
                                       res.n_iters, res.n_expanded)))


def host_nbytes(*trees: Any) -> int:
    """Bytes of the host (numpy) leaves of ``trees``: what a call ships to
    the device (device-resident leaves count nothing)."""
    return sum(int(x.nbytes) for x in jax.tree_util.tree_leaves(trees)
               if isinstance(x, (np.ndarray, np.generic)))


def traced_eval(host: Any, key: Any, fn: Callable, args: Sequence[Any],
                read: Callable[[Any], EvalCounts] = read_counts,
                **attrs: Any) -> Tuple[Any, EvalCounts]:
    """One evaluator call as every engine and the scheduler make it: a
    ``kernel.eval`` span (``attrs`` plus the call's ``n_iters`` and
    ``n_expanded``) holding an ``eval.launch`` child up to the return of
    the jitted call, then the one sync that reads the scalars.

    ``host`` carries ``tracer``, ``profiler`` and ``store``.  The
    profiler attributes ``key`` on its first use (it keeps one cost per
    key); a compile the call triggers shows as a ``jit.compile`` span
    under ``eval.launch`` (obs/trace.py)."""
    with host.tracer.span("kernel.eval", **attrs) as ksp:
        with host.tracer.span("eval.launch"):
            host.profiler.attribute_kernel(key, fn, *args)
            out = fn(*args)
        counts = read(out)
        ksp.set(n_iters=int(np.sum(counts.n_iters)))
        if counts.n_expanded is not None:
            ksp.set(n_expanded=int(np.sum(counts.n_expanded)))
        host.profiler.stamp_kernel(ksp, key)
        host.profiler.sample_device(ksp, host.store)
    return out, counts


def read_rows(res: EvalResult, counts: EvalCounts) -> Dict[str, np.ndarray]:
    """The row buffers a call filled, in one ``jax.device_get``: the
    completed rows if any lane completed one, the outgoing rows, steps
    and destinations if any lane emitted one."""
    want = {}
    if np.any(counts.comp_n):
        want["comp_rows"] = res.comp_rows
    if np.any(counts.out_n):
        want.update(out_rows=res.out_rows, out_step=res.out_step,
                    out_dest=res.out_dest)
    return jax.device_get(want)


def part_to_device_dict(p: PartitionArrays) -> Dict[str, np.ndarray]:
    assert p.ell_dst is not None, "call PartitionArrays.to_ell() first"
    return dict(
        pid=np.int32(p.pid),
        n_core=np.int32(p.n_core),
        node_gid=p.node_gid,
        node_label=p.node_label,
        node_value=p.node_value,
        ell_dst=p.ell_dst,
        ell_label=p.ell_label,
        ell_dir=p.ell_dir,
        ell_dlab=p.ell_dlab,
        ell_dval=p.ell_dval,
        ell_dgid=p.ell_dgid,
    )


def plan_to_device(pa: PlanArrays) -> PlanArrays:
    return pa  # numpy arrays are fine as jit inputs; kept for symmetry


jax.tree_util.register_pytree_node(
    PlanArrays,
    lambda p: ((p.start_slot, p.start_label, p.start_value_op, p.start_value,
                p.src_slot, p.dst_slot, p.edge_label, p.direction, p.dst_label,
                p.dst_value_op, p.dst_value, p.closes_cycle),
               (p.n_slots, p.n_steps)),
    lambda aux, ch: PlanArrays(
        n_slots=aux[0], n_steps=aux[1], start_slot=ch[0], start_label=ch[1],
        start_value_op=ch[2], start_value=ch[3], src_slot=ch[4], dst_slot=ch[5],
        edge_label=ch[6], direction=ch[7], dst_label=ch[8], dst_value_op=ch[9],
        dst_value=ch[10], closes_cycle=ch[11]),
)
