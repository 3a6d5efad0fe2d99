"""Pallas TPU kernel: one-edge frontier expansion match (the engine hot spot).

Every engine iteration evaluates an [EB, W] tile of candidate edges — EB
active bindings x the ELLPACK adjacency width W — against the current plan
step's predicates.  This kernel fuses the whole match:

  * one row-gather of the 6 ELL tables per binding, expressed as a
    scalar-prefetch BlockSpec index_map (the Mosaic "gather rows" idiom used
    by MoE kernels): block (None, 1, W) of each table viewed as
    [Np, 1, W], block index taken from the prefetched ``lidx`` scalar
    vector;
  * all predicate evaluation (edge label, direction, dst label, dst value
    comparison, injectivity, cycle closure) as branchless VPU ops on the
    (1, W) tile in VMEM.

Because dst-node attributes are denormalized into the ELL tables at
partition-build time (graph.py), the kernel performs NO data-dependent
gathers — each grid step's working set is six (1, W) VMEM tiles, with the
DMA for step i+1 overlapped with compute for step i by the Pallas pipeline.

Layout notes (TPU target):
  * W is padded to a multiple of 128 by the ops.py wrapper (lane dim),
  * per-binding scalars (plan-step params, binding rows for the injectivity
    check) ride in SMEM via scalar prefetch, not VMEM,
  * outputs are int32 masks — bool VMEM tiles are not supported by Mosaic.

Validated against ref.frontier_expand_ref in interpret mode (CPU) over a
shape/dtype sweep; see tests/test_kernels.py.
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

# packed int-param column layout (pint[:, _P_*])
_P_EL, _P_DIR, _P_DLAB, _P_DOP, _P_DST, _P_CLOSES, _P_STEP, _P_ACTIVE = range(8)
N_PINT = 8


def _kernel(lidx_ref, pint_ref, pflt_ref, rows_ref,      # scalar prefetch (SMEM)
            ed_ref, el_ref, edir_ref, dlab_ref, dval_ref, dgid_ref,  # VMEM in
            ok_ref, dg_ref,                               # VMEM out
            *, q_pad: int):
    i = pl.program_id(0)

    p_el = pint_ref[i, _P_EL]
    p_dir = pint_ref[i, _P_DIR]
    p_dlab = pint_ref[i, _P_DLAB]
    p_dop = pint_ref[i, _P_DOP]
    p_dst = pint_ref[i, _P_DST]
    p_closes = pint_ref[i, _P_CLOSES]
    # _P_ACTIVE already folds m & (step < n_steps); computed by the wrapper
    # so the dynamic n_steps scalar never has to enter the kernel.
    active = pint_ref[i, _P_ACTIVE]
    p_dval = pflt_ref[i]

    ed = ed_ref[0, :]
    el = el_ref[0, :]
    edir = edir_ref[0, :]
    dl = dlab_ref[0, :]
    dv = dval_ref[0, :]
    dg = dgid_ref[0, :]

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
    ok_ref[0, :] = ok.astype(jnp.int32)
    dg_ref[0, :] = dg


def frontier_expand_pallas(lidx, pint, pflt, rows,
                           ell_dst, ell_label, ell_dir,
                           ell_dlab, ell_dval, ell_dgid,
                           *, interpret: bool):
    """Raw kernel invocation; ops.frontier_expand is the public wrapper.

    lidx [EB] int32 (clipped to [0, Np)), pint [EB, 8] int32, pflt [EB] f32,
    rows [EB, Q] int32, ell_* [Np, W] (W multiple of 128 on real TPU).
    Returns ok [EB, W] int32, dg [EB, W] int32.
    """
    EB = lidx.shape[0]
    Np, W = ell_dst.shape
    Q = rows.shape[1]

    # one-row blocks over [N, 1, W] views: see fused_frontier.py
    ell_spec = pl.BlockSpec((None, 1, W),
                            lambda i, lidx_r, *_: (lidx_r[i], 0, 0))
    out_spec = pl.BlockSpec((None, 1, W), lambda i, *_: (i, 0, 0))

    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=4,           # lidx, pint, pflt, rows -> SMEM
        grid=(EB,),
        in_specs=[ell_spec] * 6,
        out_specs=[out_spec, out_spec],
    )
    kernel = functools.partial(_kernel, q_pad=Q)
    shp = jax.ShapeDtypeStruct((EB, 1, W), jnp.int32)
    tables = (ell_dst, ell_label, ell_dir, ell_dlab, ell_dval, ell_dgid)
    ok, dg = pl.pallas_call(
        kernel,
        grid_spec=grid_spec,
        out_shape=[shp, shp],
        interpret=interpret,
    )(lidx, pint, pflt, rows, *(t.reshape(Np, 1, W) for t in tables))
    return ok.reshape(EB, W), dg.reshape(EB, W)
