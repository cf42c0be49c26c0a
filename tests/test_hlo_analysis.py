"""Unit tests for the trip-count-aware HLO analyzer (string fixtures +
a live compile on a small forced-multi-device mesh)."""
import textwrap

import pytest

from repro.launch.hlo_analysis import (aggregate, parse_hlo,
                                       parse_type_bytes)

FIXTURE = textwrap.dedent("""
    HloModule jit_step

    %body (p: (s32[], f32[8,16])) -> (s32[], f32[8,16]) {
      %p = (s32[], f32[8,16]) parameter(0)
      %i = s32[] get-tuple-element(%p), index=0
      %x = f32[8,16] get-tuple-element(%p), index=1
      %w = f32[16,16] constant({...})
      %dot.1 = f32[8,16] dot(%x, %w), lhs_contracting_dims={1}, rhs_contracting_dims={0}
      %ar = f32[8,16] all-reduce(%dot.1), replica_groups=[2,4]<=[8], to_apply=%add
      %one = s32[] constant(1)
      %i2 = s32[] add(%i, %one)
      ROOT %t = (s32[], f32[8,16]) tuple(%i2, %ar)
    }

    %cond (p: (s32[], f32[8,16])) -> pred[] {
      %p = (s32[], f32[8,16]) parameter(0)
      %i = s32[] get-tuple-element(%p), index=0
      %n = s32[] constant(10)
      ROOT %lt = pred[] compare(%i, %n), direction=LT
    }

    %add (a: f32[], b: f32[]) -> f32[] {
      %a = f32[] parameter(0)
      %b = f32[] parameter(1)
      ROOT %s = f32[] add(%a, %b)
    }

    ENTRY %main (x: f32[8,16]) -> f32[8,16] {
      %x = f32[8,16] parameter(0)
      %ag = f32[64,16] all-gather(%x), replica_groups=[1,8]<=[8], dimensions={0}
      %zero = s32[] constant(0)
      %init = (s32[], f32[8,16]) tuple(%zero, %x)
      %w = (s32[], f32[8,16]) while(%init), condition=%cond, body=%body, backend_config={"known_trip_count":{"n":"10"}}
      ROOT %out = f32[8,16] get-tuple-element(%w), index=1
    }
""")


class TestParser:
    def test_type_bytes(self):
        assert parse_type_bytes("f32[8,16]") == 8 * 16 * 4
        assert parse_type_bytes("bf16[2,3]{1,0}") == 12
        assert parse_type_bytes("(s32[], f32[4])") == 4 + 16
        assert parse_type_bytes("pred[]") == 1

    def test_entry_detection_and_trip_count(self):
        comps = parse_hlo(FIXTURE, n_devices=8)
        agg = aggregate(comps)
        assert agg["entry"] == "main"
        # dot: 2 * 8 * 16 * 16 flops, x10 trips
        assert agg["dot_flops"] == pytest.approx(2 * 8 * 16 * 16 * 10)

    def test_collective_ring_bytes(self):
        comps = parse_hlo(FIXTURE, n_devices=8)
        agg = aggregate(comps)
        b = agg["collective_bytes"]
        # all-gather: output 64*16*4 bytes * (8-1)/8, once
        assert b["all-gather"] == pytest.approx(64 * 16 * 4 * 7 / 8)
        # all-reduce inside the loop: 2 * in_bytes * (4-1)/4 * 10 trips
        assert b["all-reduce"] == pytest.approx(
            2 * (8 * 16 * 4) * 3 / 4 * 10)
        assert agg["collective_counts"]["all-reduce"] == 10

    def test_f32_normalization_tracks_f32_flows(self):
        comps = parse_hlo(FIXTURE, n_devices=8)
        agg = aggregate(comps)
        total = sum(agg["collective_bytes"].values())
        # everything in the fixture is f32 => normalized = half
        assert agg["collective_bytes_bf16norm"] == pytest.approx(total / 2)

    def test_mem_bytes_counts_loop_body_with_trips(self):
        comps = parse_hlo(FIXTURE, n_devices=8)
        agg = aggregate(comps)
        # dot in the body alone contributes (in+in+out) * 10
        dot_traffic = (8 * 16 * 4 + 16 * 16 * 4 + 8 * 16 * 4) * 10
        assert agg["mem_bytes"] >= dot_traffic


class TestLiveCompile:
    def test_matches_cost_analysis_on_unrolled(self):
        """Parser dot flops == XLA cost_analysis on a loop-free program."""
        import subprocess
        import sys
        import os
        code = textwrap.dedent("""
            import os
            os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
            import sys
            sys.path.insert(0, %r)
            import jax, jax.numpy as jnp
            from jax.sharding import PartitionSpec as P
            from repro.launch.hlo_analysis import analyze_compiled
            mesh = jax.make_mesh((2, 4), ("data", "model"),
                                 axis_types=(jax.sharding.AxisType.Auto,)*2)
            def f(x, w1, w2):
                return jnp.sum((x @ w1) @ w2)
            x = jax.ShapeDtypeStruct((32, 64), jnp.float32)
            w1 = jax.ShapeDtypeStruct((64, 128), jnp.float32)
            w2 = jax.ShapeDtypeStruct((128, 64), jnp.float32)
            with jax.set_mesh(mesh):
                c = jax.jit(f, in_shardings=(P("data", None),
                                             P(None, "model"),
                                             P("model", None)),
                            out_shardings=P()).lower(x, w1, w2).compile()
            agg = analyze_compiled(c, 8)
            ca = c.cost_analysis()
            print(agg["dot_flops"], ca["flops"])
        """ % os.path.join(os.path.dirname(os.path.dirname(
            os.path.abspath(__file__))), "src"))
        out = subprocess.run([sys.executable, "-c", code],
                             capture_output=True, text=True, timeout=300)
        assert out.returncode == 0, out.stderr[-2000:]
        dot, cost = map(float, out.stdout.split())
        # dots dominate this program; parser must be within the elementwise
        # share of cost_analysis
        assert dot == pytest.approx(cost, rel=0.2)
