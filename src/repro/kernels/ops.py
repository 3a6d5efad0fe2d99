"""Public jit'd wrappers around the Pallas kernels.

Each wrapper:
  * adapts engine-level arguments to the kernel's packed layout,
  * pads the lane dimension to 128 multiples (TPU tile alignment),
  * compiles the kernel on TPU and interprets it on the CPU backend only
    (interpret mode executes the kernel body in Python, so correctness is
    validated without a chip); any other backend is an error, never a
    silent fall back to the interpreter,
  * has a pure-jnp twin in ref.py used by the tests as the oracle.
"""
from __future__ import annotations


import jax
import jax.numpy as jnp

from . import ref
from .frontier_expand import (N_PINT, _P_ACTIVE, _P_CLOSES, _P_DIR, _P_DLAB,
                              _P_DOP, _P_DST, _P_EL, _P_STEP,
                              frontier_expand_pallas)
from .fused_frontier import (N_FPINT, _F_ACTIVE, _F_CLOSES, _F_DIR, _F_DLAB,
                             _F_DOP, _F_DST, _F_EL, _F_FGLIDX, _F_FGOWNER,
                             _F_ISLAST, _F_NCORE, _F_USEDG,
                             fused_frontier_pallas)
from .label_histogram import label_histogram_pallas

LANE = 128


def _interpret() -> bool:
    """True on the CPU backend, False on TPU; raises on any other backend."""
    backend = jax.default_backend()
    if backend == "tpu":
        return False
    if backend == "cpu":
        return True
    raise RuntimeError(f"the Pallas kernels target TPU; backend {backend!r} "
                       f"can neither compile them nor should it interpret "
                       f"them (use JAX_PLATFORMS=cpu for interpret mode)")


def _round_up(x: int, m: int) -> int:
    return ((x + m - 1) // m) * m


def frontier_expand(rows_b, step_b, lidx_b, m,
                    ell_dst, ell_label, ell_dir,
                    ell_dlab, ell_dval, ell_dgid,
                    plan, n_steps):
    """Engine-facing adapter with the same signature/semantics as the jnp
    match in engine._match_tile_jnp (minus row construction).

    Returns (ok [EB, W] bool, dg [EB, W] int32) for the ORIGINAL width W.
    """
    EB = rows_b.shape[0]
    Np, W = ell_dst.shape
    S = plan.src_slot.shape[0]

    s = jnp.clip(step_b, 0, S - 1)
    active = (m & (step_b < n_steps)).astype(jnp.int32)
    pint = jnp.zeros((EB, N_PINT), jnp.int32)
    pint = pint.at[:, _P_EL].set(plan.edge_label[s])
    pint = pint.at[:, _P_DIR].set(plan.direction[s])
    pint = pint.at[:, _P_DLAB].set(plan.dst_label[s])
    pint = pint.at[:, _P_DOP].set(plan.dst_value_op[s])
    pint = pint.at[:, _P_DST].set(plan.dst_slot[s])
    pint = pint.at[:, _P_CLOSES].set(plan.closes_cycle[s])
    pint = pint.at[:, _P_STEP].set(step_b)
    pint = pint.at[:, _P_ACTIVE].set(active)
    pflt = plan.dst_value[s].astype(jnp.float32)
    lidx = jnp.clip(lidx_b, 0, Np - 1).astype(jnp.int32)

    # pad the lane dim to 128 (padding edges: dst -1 -> never match)
    Wp = _round_up(W, LANE)
    if Wp != W:
        padw = [(0, 0), (0, Wp - W)]
        ell_dst = jnp.pad(ell_dst, padw, constant_values=-1)
        ell_label = jnp.pad(ell_label, padw, constant_values=-2)
        ell_dir = jnp.pad(ell_dir, padw)
        ell_dlab = jnp.pad(ell_dlab, padw, constant_values=-2)
        ell_dval = jnp.pad(ell_dval, padw, constant_values=jnp.nan)
        ell_dgid = jnp.pad(ell_dgid, padw, constant_values=-1)

    ok, dg = frontier_expand_pallas(
        lidx, pint, pflt, rows_b.astype(jnp.int32),
        ell_dst, ell_label, ell_dir, ell_dlab, ell_dval, ell_dgid,
        interpret=_interpret())
    return ok[:, :W].astype(bool), dg[:, :W]


def frontier_expand_ref(rows_b, step_b, lidx_b, m,
                        ell_dst, ell_label, ell_dir,
                        ell_dlab, ell_dval, ell_dgid,
                        plan, n_steps):
    """jnp oracle with the identical adapter signature (tests diff the two)."""
    S = plan.src_slot.shape[0]
    s = jnp.clip(step_b, 0, S - 1)
    return ref.frontier_expand_ref(
        rows_b, step_b, lidx_b, m,
        ell_dst, ell_label, ell_dir, ell_dlab, ell_dval, ell_dgid,
        plan.edge_label[s], plan.direction[s], plan.dst_label[s],
        plan.dst_value_op[s], plan.dst_value[s], plan.dst_slot[s],
        plan.closes_cycle[s], n_steps)


def denorm_locality(ell_dgid, g2l_row, owner):
    """Precompute the per-candidate locality tables the fused kernel needs.

    Denormalizes ``g2l_row[dst]`` / ``owner[dst]`` into two extra [Np, W]
    ELL-shaped tables so the kernel never performs a data-dependent gather.
    Call ONCE per evaluator invocation (outside the while loop) — the cost
    is amortized over every expansion iteration.

    Returns (ell_dlidx [Np, W] int32 — local idx of each candidate dst in
    this partition, -1 if absent/padded; ell_downer [Np, W] int32 — owner
    pid of each candidate dst).
    """
    dsafe = jnp.clip(ell_dgid, 0, g2l_row.shape[0] - 1)
    ell_dlidx = jnp.where(ell_dgid >= 0, jnp.take(g2l_row, dsafe),
                          jnp.int32(-1))
    ell_downer = jnp.take(owner, dsafe)
    return ell_dlidx.astype(jnp.int32), ell_downer.astype(jnp.int32)


def _fused_params(rows_b, step_b, m, g2l_row, owner, n_core, plan, n_steps):
    """Pack the per-binding SMEM scalars for the fused kernel."""
    EB = rows_b.shape[0]
    S = plan.src_slot.shape[0]
    V = g2l_row.shape[0]

    s = jnp.clip(step_b, 0, S - 1)
    active = (m & (step_b < n_steps)).astype(jnp.int32)
    ns = step_b + 1
    islast = (ns >= n_steps).astype(jnp.int32)
    s2 = jnp.clip(ns, 0, S - 1)
    nsrc = plan.src_slot[s2]            # src slot of the NEXT plan step
    p_dst = plan.dst_slot[s]
    p_closes = plan.closes_cycle[s]
    # next frontier = freshly-bound dst iff the next step expands from the
    # slot this (non-cycle) step binds; otherwise an already-bound vertex
    use_dg = ((nsrc == p_dst) & (p_closes == 0)).astype(jnp.int32)
    fg_sc = jnp.take_along_axis(rows_b, nsrc[:, None], axis=1)[:, 0]
    fg_safe = jnp.clip(fg_sc, 0, V - 1)
    fg_lidx = jnp.where(fg_sc >= 0, jnp.take(g2l_row, fg_safe), jnp.int32(-1))
    fg_owner = jnp.take(owner, fg_safe)

    pint = jnp.zeros((EB, N_FPINT), jnp.int32)
    pint = pint.at[:, _F_EL].set(plan.edge_label[s])
    pint = pint.at[:, _F_DIR].set(plan.direction[s])
    pint = pint.at[:, _F_DLAB].set(plan.dst_label[s])
    pint = pint.at[:, _F_DOP].set(plan.dst_value_op[s])
    pint = pint.at[:, _F_DST].set(p_dst)
    pint = pint.at[:, _F_CLOSES].set(p_closes)
    pint = pint.at[:, _F_ACTIVE].set(active)
    pint = pint.at[:, _F_ISLAST].set(islast)
    pint = pint.at[:, _F_USEDG].set(use_dg)
    pint = pint.at[:, _F_FGLIDX].set(fg_lidx)
    pint = pint.at[:, _F_FGOWNER].set(fg_owner)
    pint = pint.at[:, _F_NCORE].set(jnp.int32(n_core))
    pflt = plan.dst_value[s].astype(jnp.float32)
    return pint, pflt, nsrc


def fused_frontier(rows_b, step_b, lidx_b, m,
                   ell_dst, ell_label, ell_dir,
                   ell_dlab, ell_dval, ell_dgid,
                   ell_dlidx, ell_downer,
                   g2l_row, owner, n_core,
                   plan, n_steps):
    """Engine-facing adapter for the fused expand+classify kernel.

    Same adapter contract as frontier_expand, plus the two denormalized
    locality tables from denorm_locality and the partition's g2l/owner/
    n_core context.  Returns six [EB, W] arrays for the ORIGINAL width W:
    (ok, done, keep, out) bool, (dg, dest) int32.
    """
    Np, W = ell_dst.shape

    pint, pflt, _ = _fused_params(rows_b, step_b, m, g2l_row, owner, n_core,
                                  plan, n_steps)
    lidx = jnp.clip(lidx_b, 0, Np - 1).astype(jnp.int32)

    # pad the lane dim to 128 (padding edges: dst -1 -> never match)
    Wp = _round_up(W, LANE)
    if Wp != W:
        padw = [(0, 0), (0, Wp - W)]
        ell_dst = jnp.pad(ell_dst, padw, constant_values=-1)
        ell_label = jnp.pad(ell_label, padw, constant_values=-2)
        ell_dir = jnp.pad(ell_dir, padw)
        ell_dlab = jnp.pad(ell_dlab, padw, constant_values=-2)
        ell_dval = jnp.pad(ell_dval, padw, constant_values=jnp.nan)
        ell_dgid = jnp.pad(ell_dgid, padw, constant_values=-1)
        ell_dlidx = jnp.pad(ell_dlidx, padw, constant_values=-1)
        ell_downer = jnp.pad(ell_downer, padw)

    ok, dg, done, keep, outm, dest = fused_frontier_pallas(
        lidx, pint, pflt, rows_b.astype(jnp.int32),
        ell_dst, ell_label, ell_dir, ell_dlab, ell_dval, ell_dgid,
        ell_dlidx, ell_downer,
        interpret=_interpret())
    return (ok[:, :W].astype(bool), dg[:, :W], done[:, :W].astype(bool),
            keep[:, :W].astype(bool), outm[:, :W].astype(bool), dest[:, :W])


def fused_frontier_ref(rows_b, step_b, lidx_b, m,
                       ell_dst, ell_label, ell_dir,
                       ell_dlab, ell_dval, ell_dgid,
                       g2l_row, owner, n_core,
                       plan, n_steps):
    """jnp oracle with the identical adapter signature (tests diff the two)."""
    S = plan.src_slot.shape[0]
    s = jnp.clip(step_b, 0, S - 1)
    s2 = jnp.clip(step_b + 1, 0, S - 1)
    return ref.fused_frontier_ref(
        rows_b, step_b, lidx_b, m,
        ell_dst, ell_label, ell_dir, ell_dlab, ell_dval, ell_dgid,
        g2l_row, owner, n_core,
        plan.edge_label[s], plan.direction[s], plan.dst_label[s],
        plan.dst_value_op[s], plan.dst_value[s], plan.dst_slot[s],
        plan.closes_cycle[s], plan.src_slot[s2], n_steps)


def label_histogram(node_label, node_value, core_mask, label, value_op, value):
    return label_histogram_pallas(node_label, node_value, core_mask,
                                  label, value_op, value,
                                  interpret=_interpret())
