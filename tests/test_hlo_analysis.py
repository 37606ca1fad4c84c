"""The trip-count-aware HLO analyzer, validated on a program with
analytically known FLOPs (matmul under a scan)."""

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from repro.launch import hlo_analysis as H


def test_scan_matmul_flops_counted_with_trip_count():
    L, d = 12, 32

    def f(ws, x):
        def body(x, w):
            return jnp.tanh(x @ w), None
        x, _ = jax.lax.scan(body, x, ws)
        return x

    ws = jnp.zeros((L, d, d))
    x = jnp.zeros((4, d))
    lowered = jax.jit(f).lower(ws, x)
    flops, unresolved = H.flops_from_pre(lowered.as_text("hlo"))
    want = L * 2 * 4 * d * d
    assert unresolved == 0
    assert abs(flops - want) / want < 0.01, (flops, want)


def test_nested_scan_multiplies():
    Lo, Li, d = 5, 7, 16

    def f(x):
        def outer(x, _):
            def inner(x, _):
                return x @ jnp.eye(d), None
            x, _ = jax.lax.scan(inner, x, None, length=Li)
            return x, None
        x, _ = jax.lax.scan(outer, x, None, length=Lo)
        return x

    x = jnp.zeros((2, d))
    flops, unresolved = H.flops_from_pre(jax.jit(f).lower(x).as_text("hlo"))
    want = Lo * Li * 2 * 2 * d * d
    assert unresolved == 0
    assert abs(flops - want) / want < 0.01, (flops, want)


def test_unrolled_matmul_exact():
    a = jnp.zeros((8, 24))
    b = jnp.zeros((24, 40))
    flops, _ = H.flops_from_pre(
        jax.jit(lambda a, b: a @ b).lower(a, b).as_text("hlo"))
    assert flops == 2 * 8 * 24 * 40


def test_parse_hlo_finds_computations():
    def f(x):
        return jax.lax.scan(lambda c, _: (c * 2, None), x, None,
                            length=3)[0]
    text = jax.jit(f).lower(jnp.ones(4)).as_text("hlo")
    comps = H.parse_hlo(text)
    assert len(comps) >= 2     # entry + loop body/cond
    mult, unresolved = H._multipliers(comps)
    assert unresolved == 0
    assert max(mult.values()) == 3.0


def test_collective_parse_from_sharded_program():
    """An explicitly psum-ing shard_map program on 1 device still emits
    an all-reduce in the compiled HLO."""
    import jax
    from jax.sharding import PartitionSpec as P
    mesh = jax.make_mesh((1,), ("x",))

    def f(a):
        return jax.lax.psum(a, "x")

    sm = jax.shard_map(f, mesh=mesh, in_specs=P("x"), out_specs=P())
    compiled = jax.jit(sm).lower(jnp.ones((4,))).compile()
    hbm, coll, unresolved = H.bytes_from_post(compiled.as_text())
    assert hbm > 0
