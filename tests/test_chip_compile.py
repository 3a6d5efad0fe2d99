"""Compile the Pallas kernels and the partition evaluator for a TPU v5e.

Nothing runs here.  The TPU compiler installed with JAX compiles for a
``v5e:2x2`` topology that is described, not attached, and refuses what the
chip would refuse: a block shape off Mosaic's (8, 128) tiling, too much
VMEM, a program too large for HBM.  Interpret mode checks none of that, so
a kernel can pass every test in tests/test_kernels.py and still fail to
compile for the chip.

The kernels compile at a real width (EB=512 rows of W=128 lanes over
Np=65536 table rows); the evaluator at a small geometry, with and without
the fused kernel.  The topology is described inside a fixture of this one
file, never while a module is imported, so every pytest worker collects
the same tests and only the worker given this file loads the TPU library.
"""
import os
import re

import numpy as np
import pytest

import jax
import jax.numpy as jnp
from jax.sharding import SingleDeviceSharding

from repro.core.engine import EngineConfig, make_partition_evaluator
from repro.core.plan import PlanArrays
from repro.kernels import ops
from repro.kernels.frontier_expand import N_PINT, frontier_expand_pallas
from repro.kernels.fused_frontier import N_FPINT, fused_frontier_pallas
from repro.kernels.label_histogram import label_histogram_pallas

EB, W, NP, Q = 512, 128, 65536, 8


@pytest.fixture(scope="module")
def topo():
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies
    try:
        return topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.fixture(scope="module")
def one_chip(topo):
    # a compile for a described chip is written to the persistent cache
    # but cannot be read back without one: keep the cache off meanwhile
    from jax.experimental.compilation_cache import compilation_cache
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield SingleDeviceSharding(topo.devices[0])
    jax.config.update("jax_enable_compilation_cache", was)
    compilation_cache.reset_cache()


def _shape(sharding, shape, dtype=jnp.int32):
    return jax.ShapeDtypeStruct(shape, dtype, sharding=sharding)


def _compile(fn, *args):
    compiled = jax.jit(fn).lower(*args).compile()
    nbytes = sum(int(np.prod(a.shape)) * a.dtype.itemsize
                 for a in jax.tree.leaves(args))
    return compiled, nbytes


def _row_kernel_args(sharding, n_pint, n_tables):
    scalars = [_shape(sharding, (EB,)), _shape(sharding, (EB, n_pint)),
               _shape(sharding, (EB,), jnp.float32),
               _shape(sharding, (EB, Q))]
    tables = [_shape(sharding, (NP, W), jnp.float32 if i == 4 else jnp.int32)
              for i in range(n_tables)]
    return scalars + tables


@pytest.mark.parametrize("kernel,n_pint,n_tables", [
    (fused_frontier_pallas, N_FPINT, 8),
    (frontier_expand_pallas, N_PINT, 6),
], ids=["fused_frontier", "frontier_expand"])
def test_frontier_kernel_compiles_for_v5e(one_chip, kernel, n_pint, n_tables):
    args = _row_kernel_args(one_chip, n_pint, n_tables)
    compiled, nbytes = _compile(
        lambda *a: kernel(*a, interpret=False), *args)
    assert "tpu_custom_call" in compiled.as_text()
    # the [Np, 1, W] views are free: no table is copied, and none is
    # padded (padding the unit dim to 8 sublanes would add 7x the tables;
    # only the small per-row scalar arrays may round up)
    mem = compiled.memory_analysis()
    assert nbytes <= mem.argument_size_in_bytes < nbytes + NP * W
    assert mem.temp_size_in_bytes == 0


def test_label_histogram_compiles_for_v5e(one_chip):
    args = [_shape(one_chip, (NP,)), _shape(one_chip, (NP,), jnp.float32),
            _shape(one_chip, (NP,)), _shape(one_chip, ()),
            _shape(one_chip, ()), _shape(one_chip, (), jnp.float32)]
    compiled, _ = _compile(
        lambda *a: label_histogram_pallas(*a, interpret=False), *args)
    assert "tpu_custom_call" in compiled.as_text()


# the evaluator's small geometry: the while loop's work buffer holds
# EVAL_CAP incoming rows and EVAL_NP fresh seeds
EVAL_NP, EVAL_W, EVAL_V, EVAL_CAP = 1024, 8, 4096, 1024
WT = EVAL_CAP + EVAL_NP

_OP_NAME = re.compile(r'op_name="([^"]*/while/(?:cond|body)/[^"]*)"')
_OPCODE = re.compile(r"\s[a-z][a-z0-9-]*\(")
_DIMS = re.compile(r"[a-z]+[0-9]*\[([0-9,]*)\]")


def loop_gathers(hlo_text, rows):
    """Instructions of a while loop's cond or body that XLA lowered from a
    gather and whose result (or a part of a tuple result) has ``rows``
    rows."""
    found = []
    for line in hlo_text.splitlines():
        m = _OP_NAME.search(line)
        if not m or not m.group(1).endswith("gather") or " = " not in line:
            continue
        rhs = line.split(" = ", 1)[1]
        op = _OPCODE.search(rhs)
        shapes = _DIMS.findall(rhs[: op.start()] if op else rhs)
        if any(dims.split(",")[0] == str(rows) for dims in shapes):
            found.append(line.strip())
    return found


@pytest.fixture(scope="module", params=[False, True],
                ids=["jnp", "fused_kernel"])
def evaluator_hlo(one_chip, request):
    """The served evaluator compiled at a small geometry, with and without
    the fused kernel; its HLO text.  ops chooses interpret mode from the
    backend, which is the CPU here; the fused case steers it to the
    compiled kernel, as on the chip."""
    use_pallas = request.param
    cfg = EngineConfig(cap=EVAL_CAP, use_pallas=use_pallas)
    S = cfg.s_pad
    i32 = lambda *shape: _shape(one_chip, shape)
    f32 = lambda *shape: _shape(one_chip, shape, jnp.float32)
    np_, w = EVAL_NP, EVAL_W
    part = dict(pid=i32(), n_core=i32(), node_gid=i32(np_),
                node_label=i32(np_), node_value=f32(np_),
                ell_dst=i32(np_, w), ell_label=i32(np_, w),
                ell_dir=i32(np_, w), ell_dlab=i32(np_, w),
                ell_dval=f32(np_, w), ell_dgid=i32(np_, w))
    plan = PlanArrays(
        n_slots=4, n_steps=3, start_slot=i32(), start_label=i32(),
        start_value_op=i32(), start_value=f32(), src_slot=i32(S),
        dst_slot=i32(S), edge_label=i32(S), direction=i32(S),
        dst_label=i32(S), dst_value_op=i32(S), dst_value=f32(S),
        closes_cycle=i32(S))
    evaluate = make_partition_evaluator(np_, w, cfg)
    with pytest.MonkeyPatch.context() as mp:
        mp.setattr(ops, "_interpret", lambda: False)
        compiled = evaluate.lower(
            part, i32(EVAL_V), i32(EVAL_V), plan, i32(),
            i32(EVAL_CAP, cfg.q_pad), i32(EVAL_CAP),
            _shape(one_chip, (EVAL_CAP,), jnp.bool_),
            _shape(one_chip, (), jnp.bool_),
        ).compile()
    return use_pallas, compiled.as_text()


def test_partition_evaluator_compiles_for_v5e(evaluator_hlo):
    """The served evaluator at a small geometry."""
    use_pallas, hlo = evaluator_hlo
    assert ("tpu_custom_call" in hlo) == use_pallas


def test_evaluator_loop_gathers_no_work_buffer_rows(evaluator_hlo):
    """A trip of the while loop expands at most ``expand_block`` rows, so
    nothing in its cond or body gathers over the whole WT-row work buffer
    (a per-row frontier over every work row cost most of the evaluator's
    device time on the chip)."""
    _, hlo = evaluator_hlo
    assert "/while/body/" in hlo and "/while/cond/" in hlo
    assert loop_gathers(hlo, WT) == []
