"""HLO cost walker: trip-count-aware totals vs unrolled oracles."""
import jax
import jax.numpy as jnp
import pytest

from repro.launch.hlo_cost import HloCostModel, analyze
from repro.launch.mesh import make_mesh


def _body(c, w):
    return jnp.tanh(c @ w), None


def test_scan_equals_unrolled_flops():
    ws = jnp.zeros((8, 64, 64))
    x = jnp.ones((16, 64))

    def scanned(ws, x):
        return jax.lax.scan(_body, x, ws)[0]

    def unrolled(ws, x):
        for i in range(8):
            x, _ = _body(x, ws[i])
        return x

    a_s = analyze(jax.jit(scanned).lower(ws, x).compile().as_text())
    a_u = analyze(jax.jit(unrolled).lower(ws, x).compile().as_text())
    expected = 2 * 16 * 64 * 64 * 8
    assert a_s.flops == expected
    assert a_u.flops == expected


def test_xla_cost_analysis_undercounts_scans():
    """Documents WHY the walker exists: XLA counts loop bodies once."""
    ws = jnp.zeros((8, 64, 64))
    x = jnp.ones((16, 64))
    c = jax.jit(lambda ws, x: jax.lax.scan(_body, x, ws)[0]).lower(ws, x).compile()
    ca = c.cost_analysis()
    if isinstance(ca, (list, tuple)):          # older jax: one dict per device
        ca = ca[0]
    xla_flops = ca["flops"]
    assert xla_flops < 2 * 16 * 64 * 64 * 8 / 2   # at least 2x under


def test_nested_scan_with_grad():
    def body2(c, w):
        def inner(ci, wc):
            return jnp.tanh(ci @ wc), None
        return jax.lax.scan(inner, c, jnp.stack([w, w]))[0], None

    ws = jnp.zeros((8, 64, 64))
    x = jnp.ones((16, 64))
    fn = jax.jit(jax.grad(lambda ws, x: jnp.sum(jax.lax.scan(body2, x, ws)[0]),
                          argnums=0))
    a = analyze(fn.lower(ws, x).compile().as_text())
    fwd = 2 * 16 * 64 * 64 * 8 * 2
    assert a.flops == 3 * fwd          # fwd + 2 transpose matmuls per dot


def test_collectives_scaled_by_trip_count():
    import numpy as np
    mesh = make_mesh((1,), ("data",))   # single device: psum still lowers
    from jax.sharding import NamedSharding, PartitionSpec as P

    hlo = """
HloModule test, entry_computation_layout={()->f32[4]{0}}

%body (p: (s32[], f32[4])) -> (s32[], f32[4]) {
  %p = (s32[], f32[4]{0}) parameter(0)
  %i = s32[] get-tuple-element(%p), index=0
  %x = f32[4]{0} get-tuple-element(%p), index=1
  %ar = f32[4]{0} all-reduce(%x), replica_groups={}, to_apply=%sum
  %one = s32[] constant(1)
  %ni = s32[] add(%i, %one)
  ROOT %t = (s32[], f32[4]{0}) tuple(%ni, %ar)
}

%cond (p: (s32[], f32[4])) -> pred[] {
  %p = (s32[], f32[4]{0}) parameter(0)
  %i = s32[] get-tuple-element(%p), index=0
  %n = s32[] constant(10)
  ROOT %lt = pred[] compare(%i, %n), direction=LT
}

%sum (a: f32[], b: f32[]) -> f32[] {
  %a = f32[] parameter(0)
  %b = f32[] parameter(1)
  ROOT %s = f32[] add(%a, %b)
}

ENTRY %main () -> f32[4] {
  %c = f32[4]{0} constant({1,2,3,4})
  %zero = s32[] constant(0)
  %t0 = (s32[], f32[4]{0}) tuple(%zero, %c)
  %w = (s32[], f32[4]{0}) while(%t0), condition=%cond, body=%body, backend_config={"known_trip_count":{"n":"10"}}
  ROOT %out = f32[4]{0} get-tuple-element(%w), index=1
}
"""
    cost = analyze(hlo)
    assert cost.coll_counts.get("all-reduce") == 10
    assert cost.coll_bytes["all-reduce"] == 10 * 16
    assert cost.wire_bytes == 2.0 * 10 * 16     # all-reduce wire factor


def test_dot_flops_with_batch_dims():
    x = jnp.ones((4, 16, 32))
    w = jnp.ones((4, 32, 8))
    fn = jax.jit(lambda a, b: jnp.einsum("bik,bkj->bij", a, b))
    a = analyze(fn.lower(x, w).compile().as_text())
    assert a.flops == 2 * 4 * 16 * 8 * 32
