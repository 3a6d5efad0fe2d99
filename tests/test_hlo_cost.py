"""Static HLO cost analyzer: exact on known programs (the roofline's
foundation — wrong here means wrong §Roofline)."""
import pytest

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

from repro.launch.hlo_cost import analyze_hlo_text


def _mesh_x():
    return jax.make_mesh((1,), ("x",), axis_types=(jax.sharding.AxisType.Auto,))


def _compile(f, *specs):
    return jax.jit(f).lower(*specs).compile()


def test_matmul_flops_exact():
    c = _compile(lambda a, b: a @ b,
                 jax.ShapeDtypeStruct((256, 512), jnp.float32),
                 jax.ShapeDtypeStruct((512, 1024), jnp.float32))
    r = analyze_hlo_text(c.as_text())
    assert r["flops"] == pytest.approx(2 * 256 * 512 * 1024, rel=0.01)
    # bytes: read a + b, write out
    assert r["bytes"] == pytest.approx(4 * (256 * 512 + 512 * 1024 + 256 * 1024),
                                       rel=0.05)


def test_scan_multiplies_trip_count():
    def scanned(a, ws):
        def body(x, w):
            return x @ w, None
        y, _ = jax.lax.scan(body, a, ws)
        return y
    c = _compile(scanned,
                 jax.ShapeDtypeStruct((128, 256), jnp.float32),
                 jax.ShapeDtypeStruct((12, 256, 256), jnp.float32))
    r = analyze_hlo_text(c.as_text())
    assert r["flops"] == pytest.approx(12 * 2 * 128 * 256 * 256, rel=0.02)


def test_nested_scan():
    def inner(x, ws):
        def body(x, w):
            return x @ w, None
        return jax.lax.scan(body, x, ws)[0]

    def outer(x, ws):
        def body(x, _):
            return inner(x, ws), None
        return jax.lax.scan(body, x, None, length=5)[0]
    c = _compile(outer,
                 jax.ShapeDtypeStruct((64, 64), jnp.float32),
                 jax.ShapeDtypeStruct((3, 64, 64), jnp.float32))
    r = analyze_hlo_text(c.as_text())
    assert r["flops"] == pytest.approx(5 * 3 * 2 * 64 * 64 * 64, rel=0.05)


def test_batched_dot_counts_batch_dims():
    c = _compile(lambda a, b: jnp.einsum("bij,bjk->bik", a, b),
                 jax.ShapeDtypeStruct((8, 32, 64), jnp.float32),
                 jax.ShapeDtypeStruct((8, 64, 16), jnp.float32))
    r = analyze_hlo_text(c.as_text())
    assert r["flops"] == pytest.approx(8 * 2 * 32 * 64 * 16, rel=0.02)


def test_collectives_counted_with_ring_factors():
    mesh = _mesh_x()
    def f(x):
        return jax.lax.psum(x, "x")
    sm = jax.jit(jax.shard_map(f, mesh=mesh, in_specs=P("x"), out_specs=P(),
                               check_vma=False))
    c = sm.lower(jax.ShapeDtypeStruct((1024,), jnp.float32)).compile()
    r = analyze_hlo_text(c.as_text())
    # all-reduce: 2 x operand bytes
    assert r["collective_bytes_total"] == pytest.approx(2 * 1024 * 4, rel=0.01)
    assert r["collective_op_executions"] == 1


def test_collective_inside_scan_multiplied():
    mesh = _mesh_x()
    def f(xs):
        def body(c, x):
            return c + jax.lax.psum(x, "x"), None
        out, _ = jax.lax.scan(body, jnp.zeros((64,), jnp.float32), xs)
        return out
    sm = jax.jit(jax.shard_map(f, mesh=mesh, in_specs=P(None, "x"),
                               out_specs=P("x"), check_vma=False))
    c = sm.lower(jax.ShapeDtypeStruct((7, 64), jnp.float32)).compile()
    r = analyze_hlo_text(c.as_text())
    assert r["collective_op_executions"] == pytest.approx(7, abs=0.1)


def test_elementwise_flops():
    c = _compile(lambda a: jnp.tanh(a) + a * 2.0,
                 jax.ShapeDtypeStruct((1000,), jnp.float32))
    r = analyze_hlo_text(c.as_text())
    # tanh + mul + add = 3 flops/elem (fusion internals are still counted)
    assert 2000 <= r["flops"] <= 4500
