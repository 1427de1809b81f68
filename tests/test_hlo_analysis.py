"""Tests for the trip-count-aware HLO analyzer behind the roofline terms."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.launch import hlo_analysis as H


def _compile(fn, *avals):
    return jax.jit(fn).lower(*avals).compile()


class TestFlops:
    def test_plain_dot_matches_cost_analysis(self):
        a = jax.ShapeDtypeStruct((64, 128), jnp.float32)
        b = jax.ShapeDtypeStruct((128, 32), jnp.float32)
        c = _compile(lambda x, y: x @ y, a, b)
        got = H.analyze_hlo(c.as_text()).flops
        want = c.cost_analysis()["flops"]
        assert got == pytest.approx(want, rel=1e-6)
        assert got == 2 * 64 * 128 * 32

    def test_scan_multiplies_by_trip_count(self):
        """cost_analysis counts a while body ONCE; the analyzer must scale
        by the known trip count (the whole point of the module)."""
        ws = jax.ShapeDtypeStruct((6, 64, 64), jnp.float32)
        x = jax.ShapeDtypeStruct((8, 64), jnp.float32)

        def f(ws, x):
            def body(x, w):
                return jnp.tanh(x @ w), None
            return jax.lax.scan(body, x, ws)[0]

        c = _compile(f, ws, x)
        got = H.analyze_hlo(c.as_text()).flops
        one_layer = 2 * 8 * 64 * 64
        assert got == pytest.approx(6 * one_layer, rel=0.05)
        # and cost_analysis demonstrably does NOT scale
        assert c.cost_analysis()["flops"] == pytest.approx(one_layer,
                                                           rel=0.05)

    def test_nested_scan_multiplies(self):
        w = jax.ShapeDtypeStruct((3, 4, 32, 32), jnp.float32)
        x = jax.ShapeDtypeStruct((8, 32), jnp.float32)

        def f(w, x):
            def outer(x, wg):
                def inner(x, wi):
                    return jnp.tanh(x @ wi), None
                return jax.lax.scan(inner, x, wg)[0], None
            return jax.lax.scan(outer, x, w)[0]

        c = _compile(f, w, x)
        got = H.analyze_hlo(c.as_text()).flops
        assert got == pytest.approx(12 * 2 * 8 * 32 * 32, rel=0.05)


class TestCollectiveParsing:
    SNIPPET = """
HloModule test

%wide.body (p: (s32[], f32[16,256])) -> (s32[], f32[16,256]) {
  %p = (s32[], f32[16,256]) parameter(0)
  %g = f32[16,256]{1,0} get-tuple-element(%p), index=1
  %ag = f32[16,512]{1,0} all-gather(%g), channel_id=1, replica_groups=[4,2]<=[8], dimensions={1}
  %ar = f32[] all-reduce(%c), channel_id=2, replica_groups={{0,1,2,3},{4,5,6,7}}, to_apply=%add
  ROOT %t = (s32[], f32[16,256]) tuple(%i, %g)
}

ENTRY %main (a: f32[16,256]) -> f32[16,256] {
  %a = f32[16,256]{1,0} parameter(0)
  %w = (s32[], f32[16,256]) while(%t0), condition=%cond, body=%wide.body, backend_config={"known_trip_count":{"n":"5"}}
  %rs = f32[16,64]{1,0} reduce-scatter(%a), channel_id=3, replica_groups=[2,4]<=[8], dimensions={1}
  ROOT %o = f32[16,256]{1,0} get-tuple-element(%w), index=1
}
"""

    def test_group_sizes_and_trip_counts(self):
        a = H.analyze_hlo(self.SNIPPET)
        coll = a.collectives
        # all-gather: result 16*512*4 bytes, group 2 -> operand 16384, x5 trips
        assert coll["all-gather"]["bytes"] == pytest.approx(
            16 * 512 * 4 / 2 * 5)
        assert coll["all-gather"]["count"] == 5
        # all-reduce scalar: 4 bytes x 5
        assert coll["all-reduce"]["bytes"] == pytest.approx(4 * 5)
        # reduce-scatter in entry: result 16*64*4, group 4 -> operand x4
        assert coll["reduce-scatter"]["bytes"] == pytest.approx(
            16 * 64 * 4 * 4)

    def test_shape_bytes_tuples_and_layouts(self):
        assert H._shape_bytes("f32[16,256]{1,0}") == 16 * 256 * 4
        assert H._shape_bytes("(s32[], bf16[8,4]{1,0})") == 4 + 64
        assert H._shape_bytes("pred[]") == 1


class TestHBMBytes:
    def test_fusion_boundary_counting(self):
        """Elementwise chains fuse: HBM bytes ~ inputs + outputs, not
        per-op sums."""
        x = jax.ShapeDtypeStruct((1024, 1024), jnp.float32)
        c = _compile(lambda x: jnp.tanh(jnp.sin(x) * 2 + 1), x)
        got = H.analyze_hlo(c.as_text()).hbm_bytes
        # one read + one write (4 MiB each) within a small factor
        assert got <= 4 * 1024 * 1024 * 4
