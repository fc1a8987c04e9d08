"""The trip-count-aware HLO cost walker — the §Roofline/§Perf measurement
tool — validated against hand-computable programs."""
import jax
import jax.numpy as jnp
import pytest

from repro.launch import hlo_walk as HW


def _walk(f, *specs):
    comp = jax.jit(f).lower(*specs).compile()
    return HW.walk(comp.as_text())


def test_plain_dot_flops():
    x = jax.ShapeDtypeStruct((64, 128), jnp.float32)
    w = jax.ShapeDtypeStruct((128, 32), jnp.float32)
    res = _walk(lambda x, w: x @ w, x, w)
    assert res.flops == 2 * 64 * 128 * 32


def test_scan_trip_count_multiplies():
    x = jax.ShapeDtypeStruct((128, 256), jnp.float32)
    w = jax.ShapeDtypeStruct((256, 256), jnp.float32)

    def f(x, w):
        def body(c, _):
            return c @ w, None
        out, _ = jax.lax.scan(body, x, None, length=4)
        return out.sum()

    res = _walk(f, x, w)
    assert res.flops == 4 * 2 * 128 * 256 * 256
    assert 4 in res.while_trips


def test_nested_scan():
    x = jax.ShapeDtypeStruct((128, 256), jnp.float32)
    w = jax.ShapeDtypeStruct((256, 256), jnp.float32)

    def g(x, w):
        def outer(c, _):
            def inner(c2, _):
                return c2 @ w, None
            c2, _ = jax.lax.scan(inner, c, None, length=3)
            return c2, None
        out, _ = jax.lax.scan(outer, x, None, length=5)
        return out.sum()

    res = _walk(g, x, w)
    assert res.flops == 5 * 3 * 2 * 128 * 256 * 256
    assert sorted(res.while_trips) == [3, 5]


def test_cost_analysis_undercounts_scans_but_walker_does_not():
    """The motivating bug: XLA visits while bodies once."""
    x = jax.ShapeDtypeStruct((64, 64), jnp.float32)
    w = jax.ShapeDtypeStruct((64, 64), jnp.float32)

    def f(x, w):
        def body(c, _):
            return c @ w, None
        out, _ = jax.lax.scan(body, x, None, length=8)
        return out.sum()

    comp = jax.jit(f).lower(x, w).compile()
    ca = comp.cost_analysis().get("flops", 0)
    res = HW.walk(comp.as_text())
    one_dot = 2 * 64 * 64 * 64
    assert res.flops == 8 * one_dot
    assert ca < res.flops          # cost_analysis counted the body ~once


def test_bytes_scale_with_tensor_size():
    a = jax.ShapeDtypeStruct((1024, 1024), jnp.float32)
    small = _walk(lambda x: x + 1.0,
                  jax.ShapeDtypeStruct((32, 32), jnp.float32))
    big = _walk(lambda x: x + 1.0, a)
    assert big.bytes > 100 * small.bytes


def test_collective_parsing_on_sharded_program():
    """all-reduce bytes appear under SPMD (uses the session's 1 device —
    sharding over a single-device mesh still emits the SPMD structure; we
    assert no crash and sane totals)."""
    from repro.launch.mesh import make_mesh
    mesh = make_mesh((1,), ("d",))
    from jax.sharding import NamedSharding, PartitionSpec as P
    x = jax.ShapeDtypeStruct((64, 64), jnp.float32)
    with mesh:
        comp = jax.jit(lambda x: (x @ x).sum()).lower(x).compile()
    res = HW.walk(comp.as_text())
    assert res.flops == 2 * 64 * 64 * 64
    assert res.collective_bytes >= 0
