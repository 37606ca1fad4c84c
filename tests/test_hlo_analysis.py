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


_MESH_HLO = """HloModule m

%add (a: f32[], b: f32[]) -> f32[] {
  %a = f32[] parameter(0)
  %b = f32[] parameter(1)
  ROOT %s = f32[] add(%a, %b)
}

%body (p: (s32[], f32[8])) -> (s32[], f32[8]) {
  %p = (s32[], f32[8]{0}) parameter(0)
  %i = s32[] get-tuple-element(%p), index=0
  %x = f32[8]{0} get-tuple-element(%p), index=1
  %r = f32[8]{0} all-reduce(%x), replica_groups={{0,1,2,3},{4,5,6,7}}, to_apply=%add
  %one = s32[] constant(1)
  %j = s32[] add(%i, %one)
  ROOT %t = (s32[], f32[8]{0}) tuple(%j, %r)
}

%cond (p: (s32[], f32[8])) -> pred[] {
  %p = (s32[], f32[8]{0}) parameter(0)
  %i = s32[] get-tuple-element(%p), index=0
  %n = s32[] constant(3)
  ROOT %lt = pred[] compare(%i, %n), direction=LT
}

ENTRY %main (x: f32[8], y: bf16[8], q: f32[4]) -> f32[8] {
  %x = f32[8]{0} parameter(0)
  %y = bf16[8]{0} parameter(1)
  %q = f32[4]{0} parameter(2)
  %zero = s32[] constant(0)
  %init = (s32[], f32[8]{0}) tuple(%zero, %x)
  %w = (s32[], f32[8]{0}) while(%init), condition=%cond, body=%body
  %g = bf16[16]{0} all-gather(%y), replica_groups=[4,2]<=[8], dimensions={0}
  %cp = f32[4]{0} collective-permute(%q), source_target_pairs={{0,1},{1,0}}
  ROOT %out = f32[8]{0} get-tuple-element(%w), index=1
}
"""


def test_collectives_by_axis_groups_and_trip_counts():
    """Each collective goes to the mesh axis its replica-group size
    names, in the list spelling (a group of 4: ``model``) and the iota
    one (``[4,2]<=[8]``, groups of 2: ``data``); one with no groups is
    split over every axis; size-1 axes get nothing; an op in a 3-trip
    ``while`` counts its bytes three times, as ``bytes_from_post`` does
    with the same walk."""
    axes = (("data", 2), ("model", 4), ("pipe", 1))
    got = H.collectives_by_axis([_MESH_HLO, _MESH_HLO], axes)
    assert got["counts"] == {"all-reduce": 2, "all-gather": 2,
                             "collective-permute": 2}
    # all-reduce f32[8] x 3 trips = 96 B, all-gather bf16[16] = 32 B,
    # collective-permute f32[4] = 16 B split over data and model
    assert got["per_axis"] == {
        "data": {"count": 4, "bytes": 2 * (32 + 8.0)},
        "model": {"count": 4, "bytes": 2 * (96 + 8.0)}}
    assert got["total_bytes"] == 2 * (96 + 32 + 16)
    _, coll, unresolved = H.bytes_from_post(_MESH_HLO)
    assert unresolved == 0
    assert (coll["all-reduce"], coll["all-gather"],
            coll["collective-permute"], coll["total"]) == (96, 32, 16, 144)
