"""Pallas TPU kernel: start-node label histogram (SNI metric, paper Sec. 5.1).

Counts core nodes matching (label, value predicate) — the one-pass metric
PGQP computes per partition to seed and update the SNI file.  Grid over node
blocks; each step reduces a (1, BN) VMEM tile to a partial count, and the
wrapper sums the [nb] partials (a two-level reduction keeps every block's
working set in VMEM and avoids cross-step accumulation hazards).

Mosaic tiles the last two block dims by (8, 128) unless a dim spans its
whole array, so the node blocks are viewed as [nb, 1, BN] and each partial
count is written as one broadcast (1, 128) lane row of an [nb, 1, 128]
output.
"""
from __future__ import annotations


import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from ..core.graph import WILDCARD
from ..core.query import (OP_EQ, OP_GE, OP_GT, OP_LE, OP_LT, OP_NE, OP_NONE)

BLOCK_N = 1024
LANE = 128


def _kernel(pint_ref, pflt_ref,         # scalar prefetch (SMEM)
            label_ref, value_ref, core_ref,   # VMEM (1, BN)
            out_ref):                   # VMEM (1, 128) partial count
    label = pint_ref[0]
    op = pint_ref[1]
    value = pflt_ref[0]

    lab = label_ref[...]
    val = value_ref[...]
    core = core_ref[...]

    ok = (core == 1) & ((label == WILDCARD) | (lab == label))
    finite = val == val
    cmp = (((op == OP_EQ) & (val == value))
           | ((op == OP_NE) & (val != value))
           | ((op == OP_LT) & (val < value))
           | ((op == OP_LE) & (val <= value))
           | ((op == OP_GT) & (val > value))
           | ((op == OP_GE) & (val >= value)))
    ok = ok & ((op == OP_NONE) | (finite & cmp))
    count = ok.astype(jnp.int32).sum(axis=1, keepdims=True)     # (1, 1)
    out_ref[...] = jnp.broadcast_to(count, out_ref.shape)


def label_histogram_pallas(node_label, node_value, core_mask,
                           label, value_op, value,
                           *, interpret: bool, block_n: int = BLOCK_N):
    """node_label [Np] i32, node_value [Np] f32, core_mask [Np] i32 (0/1).
    Returns scalar int32 count of matching core nodes."""
    Np = node_label.shape[0]
    nb = (Np + block_n - 1) // block_n
    pad = nb * block_n - Np
    shape = (nb, 1, block_n)
    lab = jnp.pad(node_label, (0, pad), constant_values=-2).reshape(shape)
    val = jnp.pad(node_value, (0, pad), constant_values=jnp.nan).reshape(shape)
    core = jnp.pad(core_mask.astype(jnp.int32), (0, pad)).reshape(shape)
    pint = jnp.stack([jnp.asarray(label, jnp.int32),
                      jnp.asarray(value_op, jnp.int32)])
    pflt = jnp.asarray(value, jnp.float32)[None]

    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=2,
        grid=(nb,),
        in_specs=[pl.BlockSpec((None, 1, block_n), lambda i, *_: (i, 0, 0))] * 3,
        out_specs=pl.BlockSpec((None, 1, LANE), lambda i, *_: (i, 0, 0)),
    )
    partials = pl.pallas_call(
        _kernel,
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((nb, 1, LANE), jnp.int32),
        interpret=interpret,
    )(pint, pflt, lab, val, core)
    return partials[:, 0, 0].sum(dtype=jnp.int32)
