"""Pallas TPU kernel: FUSED frontier expansion + predicate filtering +
answer-emission classification (the whole engine inner step).

``frontier_expand.py`` fuses the *match* (one-edge expansion against the
plan step's predicates); the surrounding engine loop still classified every
produced row on the host side of the kernel boundary — three extra [EB*W]
gathers (next frontier vertex, its g2l local index, its owner) and the
done/keep/outgoing mask algebra ran as separate XLA ops.  This kernel fuses
all of it: one grid step consumes a (1, W) candidate tile and emits the
*routing decision* for every candidate —

  done  — the produced row completes the plan: append to the FAA,
  keep  — its next frontier vertex is core-local: stays in the work buffer,
  out   — owned elsewhere: emit to ``dest``'s IMA (the paper's PCA/IMA
          continuation),

so the engines' ``lax.while_loop`` body contains a single kernel launch
plus cheap scatter appends.

The fusion trick mirrors the denormalized dst attributes of the ELL
tables: the two data-dependent gathers the classification needs
(``g2l[dst]`` and ``owner[dst]``) are precomputed ONCE per evaluator call
as two extra [Np, W] tables (``ell_dlidx``, ``ell_downer`` — hoisted out
of the while loop, amortized over every iteration), and the per-binding
scalar cases (the next frontier is an already-bound vertex) ride in as
prefetched SMEM scalars.  The kernel itself therefore still performs NO
data-dependent gathers: each grid step touches eight (1, W) VMEM tiles
selected by the scalar-prefetch ``lidx`` BlockSpec index map, exactly the
Mosaic row-gather idiom of ``frontier_expand.py`` (tables viewed as
[Np, 1, W] so a one-row block meets Mosaic's tiling rule).

Layout notes (TPU target):
  * W padded to a lane multiple (128) by the ops.py wrapper,
  * per-binding scalars packed into ``pint`` [EB, 12] int32 + ``pflt``
    [EB] f32 in SMEM; all dynamic scalars (n_steps, n_core) are folded
    into per-row columns host-side so the kernel sees only static shapes,
  * outputs are int32 masks/ids — bool VMEM tiles are unsupported.

Validated against ref.fused_frontier_ref in interpret mode (CPU) over a
shape/dtype sweep including empty frontiers and all-filtered labels; see
tests/test_kernels.py.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from ..core.graph import DIR_BACKWARD, DIR_FORWARD, DIR_UNDIRECTED, WILDCARD
from ..core.query import (OP_EQ, OP_GE, OP_GT, OP_LE, OP_LT, OP_NE, OP_NONE,
                          QDIR_ANY, QDIR_IN, QDIR_OUT)

# packed int-param column layout (pint[:, _F_*])
(_F_EL, _F_DIR, _F_DLAB, _F_DOP, _F_DST, _F_CLOSES, _F_ACTIVE, _F_ISLAST,
 _F_USEDG, _F_FGLIDX, _F_FGOWNER, _F_NCORE) = range(12)
N_FPINT = 12


def _kernel(lidx_ref, pint_ref, pflt_ref, rows_ref,       # SMEM (prefetch)
            ed_ref, el_ref, edir_ref, dlab_ref, dval_ref, dgid_ref,
            dlidx_ref, downer_ref,                        # VMEM in (1, W)
            ok_ref, dg_ref, done_ref, keep_ref, out_ref, dest_ref,
            *, q_pad: int):
    i = pl.program_id(0)

    p_el = pint_ref[i, _F_EL]
    p_dir = pint_ref[i, _F_DIR]
    p_dlab = pint_ref[i, _F_DLAB]
    p_dop = pint_ref[i, _F_DOP]
    p_dst = pint_ref[i, _F_DST]
    p_closes = pint_ref[i, _F_CLOSES]
    # _F_ACTIVE folds m & (step < n_steps); _F_ISLAST folds
    # (step + 1 >= n_steps); _F_USEDG folds (next_src_slot == dst_slot)
    # & ~closes — all computed by the wrapper so the dynamic n_steps /
    # n_core scalars never have to enter the kernel as separate operands.
    active = pint_ref[i, _F_ACTIVE]
    islast = pint_ref[i, _F_ISLAST]
    use_dg = pint_ref[i, _F_USEDG]
    fg_lidx = pint_ref[i, _F_FGLIDX]    # g2l of the bound next-frontier
    fg_owner = pint_ref[i, _F_FGOWNER]  # owner of the bound next-frontier
    n_core = pint_ref[i, _F_NCORE]
    p_dval = pflt_ref[i]

    ed = ed_ref[0, :]
    el = el_ref[0, :]
    edir = edir_ref[0, :]
    dl = dlab_ref[0, :]
    dv = dval_ref[0, :]
    dg = dgid_ref[0, :]
    dlidx = dlidx_ref[0, :]      # g2l local index of each candidate dst
    downer = downer_ref[0, :]    # owner pid of each candidate dst

    # ---- the match (identical predicate algebra to frontier_expand) ----
    edge_exists = ed >= 0
    elabel_ok = (p_el == WILDCARD) | (el == p_el)
    dir_ok = ((p_dir == QDIR_ANY)
              | (edir == DIR_UNDIRECTED)
              | ((p_dir == QDIR_OUT) & (edir == DIR_FORWARD))
              | ((p_dir == QDIR_IN) & (edir == DIR_BACKWARD)))
    dlabel_ok = (p_dlab == WILDCARD) | (dl == p_dlab)

    finite = dv == dv
    cmp = (((p_dop == OP_EQ) & (dv == p_dval))
           | ((p_dop == OP_NE) & (dv != p_dval))
           | ((p_dop == OP_LT) & (dv < p_dval))
           | ((p_dop == OP_LE) & (dv <= p_dval))
           | ((p_dop == OP_GT) & (dv > p_dval))
           | ((p_dop == OP_GE) & (dv >= p_dval)))
    dval_ok = (p_dop == OP_NONE) | (finite & cmp)

    # injectivity: dg must differ from every bound slot (static Q unroll)
    already = jnp.zeros_like(dg, dtype=jnp.bool_)
    for q in range(q_pad):
        already = already | (dg == rows_ref[i, q])
    inj_ok = ~already

    bound_dst = rows_ref[i, p_dst]
    cyc_ok = (p_closes == 1) & (dg == bound_dst)
    new_ok = (p_closes == 0) & dlabel_ok & dval_ok & inj_ok
    ok = ((active == 1)
          & edge_exists & elabel_ok & dir_ok & (cyc_ok | new_ok))

    # ---- the classification (fused answer emission) ----
    # the produced row's next frontier vertex: the freshly-bound dst when
    # the next plan step expands from the slot this step binds, else an
    # already-bound vertex whose g2l/owner came in as SMEM scalars
    # dlidx/fg_lidx are -1 for unbound/absent vertices (the wrapper
    # denormalizes with that convention), so (lfg >= 0) subsumes the
    # fg >= 0 test of the jnp classification.
    lfg = jnp.where(use_dg == 1, dlidx, fg_lidx)
    local = (lfg >= 0) & (lfg < n_core)
    done = ok & (islast == 1)
    keep = ok & (islast == 0) & local
    outm = ok & (islast == 0) & ~local
    dest = jnp.where(use_dg == 1, downer, fg_owner)

    ok_ref[0, :] = ok.astype(jnp.int32)
    dg_ref[0, :] = dg
    done_ref[0, :] = done.astype(jnp.int32)
    keep_ref[0, :] = keep.astype(jnp.int32)
    out_ref[0, :] = outm.astype(jnp.int32)
    dest_ref[0, :] = dest


def fused_frontier_pallas(lidx, pint, pflt, rows,
                          ell_dst, ell_label, ell_dir,
                          ell_dlab, ell_dval, ell_dgid,
                          ell_dlidx, ell_downer,
                          *, interpret: bool):
    """Raw kernel invocation; ops.fused_frontier is the public wrapper.

    lidx [EB] int32 (clipped to [0, Np)), pint [EB, 12] int32, pflt [EB]
    f32, rows [EB, Q] int32, ell_* [Np, W] (W a lane multiple on TPU).
    Returns six [EB, W] int32 arrays: ok, dg, done, keep, out, dest.
    """
    EB = lidx.shape[0]
    Np, W = ell_dst.shape
    Q = rows.shape[1]

    # Mosaic tiles the last two block dims by (8, 128) unless a dim spans
    # its whole array, so a (1, W) row block of an [Np, W] table is
    # refused.  Tables are viewed as [Np, 1, W] and outputs as [EB, 1, W]:
    # the one-row block (None, 1, W) then spans the second-minor dim.
    ell_spec = pl.BlockSpec((None, 1, W),
                            lambda i, lidx_r, *_: (lidx_r[i], 0, 0))
    out_spec = pl.BlockSpec((None, 1, W), lambda i, *_: (i, 0, 0))

    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=4,           # lidx, pint, pflt, rows -> SMEM
        grid=(EB,),
        in_specs=[ell_spec] * 8,
        out_specs=[out_spec] * 6,
    )
    kernel = functools.partial(_kernel, q_pad=Q)
    shp = jax.ShapeDtypeStruct((EB, 1, W), jnp.int32)
    tables = (ell_dst, ell_label, ell_dir, ell_dlab, ell_dval, ell_dgid,
              ell_dlidx, ell_downer)
    outs = pl.pallas_call(
        kernel,
        grid_spec=grid_spec,
        out_shape=[shp] * 6,
        interpret=interpret,
    )(lidx, pint, pflt, rows, *(t.reshape(Np, 1, W) for t in tables))
    return [o.reshape(EB, W) for o in outs]
