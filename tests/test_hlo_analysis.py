"""The roofline engine's HLO analyzer, validated on programs with known
analytic costs (this is the instrument every §Roofline number flows through,
so it gets its own tests)."""
import numpy as np
import pytest

from repro.launch import hlo_analysis as H


def test_shape_bytes():
    assert H._shape_bytes("f32[256,256]{1,0}") == 256 * 256 * 4
    assert H._shape_bytes("bf16[2,3]{1,0}") == 12
    assert H._shape_bytes("(f32[4]{0}, s32[])") == 20
    assert H._shape_bytes("pred[]") == 1


def test_group_size_parsing():
    assert H._group_size("replica_groups=[4,2]<=[8]", 8) == 2
    assert H._group_size("replica_groups=[2,4]<=[4,2]T(1,0)", 8) == 4
    assert H._group_size("replica_groups={{0,1,2,3}}", 8) == 4
    assert H._group_size("no groups here", 16) == 16


SCAN_HLO = """
HloModule test

%add (a: f32[], b: f32[]) -> f32[] {
  %a = f32[] parameter(0)
  %b = f32[] parameter(1)
  ROOT %s = f32[] add(%a, %b)
}

%body (t: (s32[], f32[8,16])) -> (s32[], f32[8,16]) {
  %t = (s32[], f32[8,16]) parameter(0)
  %i = s32[] get-tuple-element(%t), index=0
  %x = f32[8,16]{1,0} get-tuple-element(%t), index=1
  %w = f32[16,16]{1,0} constant({...})
  %d = f32[8,16]{1,0} dot(%x, %w), lhs_contracting_dims={1}, rhs_contracting_dims={0}
  %ar = f32[8,16]{1,0} all-reduce(%d), replica_groups=[4,2]<=[8], to_apply=%add
  %one = s32[] constant(1)
  %ni = s32[] add(%i, %one)
  ROOT %out = (s32[], f32[8,16]) tuple(%ni, %ar)
}

%cond (t: (s32[], f32[8,16])) -> pred[] {
  %t = (s32[], f32[8,16]) parameter(0)
  %i = s32[] get-tuple-element(%t), index=0
  %n = s32[] constant(5)
  ROOT %lt = pred[] compare(%i, %n), direction=LT
}

ENTRY %main (x: f32[8,16]) -> f32[8,16] {
  %x = f32[8,16]{1,0} parameter(0)
  %z = s32[] constant(0)
  %t = (s32[], f32[8,16]) tuple(%z, %x)
  %w2 = (s32[], f32[8,16]) while(%t), condition=%cond, body=%body
  ROOT %r = f32[8,16]{1,0} get-tuple-element(%w2), index=1
}
"""


def test_while_trip_count_and_flops():
    st = H.analyze(SCAN_HLO, n_devices=8)
    assert st.while_trips == {"w2": 5}
    # dot: 2 * 8*16 * 16 = 4096 flops per iteration, 5 iterations
    assert st.flops == 5 * 2 * 8 * 16 * 16
    # all-reduce f32[8,16] group 2: wire = 2*(1/2)*512 = 512 bytes x5
    assert st.collective_bytes == pytest.approx(5 * 512)
    assert st.collective_by_type["all-reduce"]["count"] == 5


def test_collective_wire_formulas():
    base = """
HloModule t
%add (a: f32[], b: f32[]) -> f32[] {
  %a = f32[] parameter(0)
  %b = f32[] parameter(1)
  ROOT %s = f32[] add(%a, %b)
}
ENTRY %main (x: f32[4,8]) -> f32[4,8] {
  %x = f32[4,8]{1,0} parameter(0)
  %OP
  ROOT %r = f32[4,8]{1,0} get-tuple-element(%o), index=0
}
"""
    # all-gather result 4x global: per-device result f32[16,8] with g=4
    ag = base.replace("%OP", "%o = (f32[16,8]{1,0}) all-gather(%x), "
                      "replica_groups=[2,4]<=[8], dimensions={0}")
    st = H.analyze(ag, n_devices=8)
    assert st.collective_bytes == pytest.approx((3 / 4) * 16 * 8 * 4)

    # reduce-scatter: result f32[1,8], g=4 -> wire = (g-1) * result
    rs = base.replace("%OP", "%o = (f32[1,8]{1,0}) reduce-scatter(%x), "
                      "replica_groups=[2,4]<=[8], to_apply=%add")
    st = H.analyze(rs, n_devices=8)
    assert st.collective_bytes == pytest.approx(3 * 1 * 8 * 4)

    # collective-permute: wire = size
    cp = base.replace("%OP", "%o = (f32[4,8]{1,0}) collective-permute(%x), "
                      "source_target_pairs={{0,1}}")
    st = H.analyze(cp, n_devices=8)
    assert st.collective_bytes == pytest.approx(4 * 8 * 4)


def test_fusion_bodies_excluded_from_bytes_but_dots_counted():
    hlo = """
HloModule t
%fused (p: f32[8,8]) -> f32[8,8] {
  %p = f32[8,8]{1,0} parameter(0)
  %w = f32[8,8]{1,0} constant({...})
  ROOT %d = f32[8,8]{1,0} dot(%p, %w), lhs_contracting_dims={1}, rhs_contracting_dims={0}
}
ENTRY %main (x: f32[8,8]) -> f32[8,8] {
  %x = f32[8,8]{1,0} parameter(0)
  ROOT %f = f32[8,8]{1,0} fusion(%x), kind=kOutput, calls=%fused
}
"""
    st = H.analyze(hlo, n_devices=1)
    assert st.flops == 2 * 8 * 8 * 8            # dot inside fusion counted
    # bytes: only the fusion line (result + operand), not the internal dot
    assert st.bytes_accessed == pytest.approx(2 * 8 * 8 * 4)


def test_real_program_flops_match_analytic():
    """End-to-end: compiled scan-of-matmuls in a subprocess with 8 devices."""
    import os
    import subprocess
    import sys
    import textwrap
    root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    code = textwrap.dedent("""
        import os
        os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
        import jax, jax.numpy as jnp
        from jax.sharding import NamedSharding, PartitionSpec as P
        from repro.launch.hlo_analysis import analyze
        from repro.launch.mesh import make_mesh
        mesh = make_mesh((4, 2), ("data", "model"))
        W = jax.ShapeDtypeStruct((6, 512, 256), jnp.bfloat16,
                                 sharding=NamedSharding(mesh, P(None, "model", None)))
        A = jax.ShapeDtypeStruct((64, 512), jnp.bfloat16,
                                 sharding=NamedSharding(mesh, P("data", "model")))
        def f(a, w):
            def body(x, wi):
                y = x @ wi
                y = jax.lax.with_sharding_constraint(
                    y, NamedSharding(mesh, P("data", None)))
                return jnp.pad(y, ((0,0),(0,256)))[:, :512].astype(x.dtype), None
            x, _ = jax.lax.scan(body, a, w)
            return x.sum()
        comp = jax.jit(f).lower(A, W).compile()
        st = analyze(comp.as_text(), n_devices=8)
        expect = 6 * 2 * 64 * 512 * 256 / 8
        assert abs(st.flops - expect) / expect < 0.01, (st.flops, expect)
        assert st.while_trips and list(st.while_trips.values())[0] == 6
        print("OK", st.flops)
    """)
    out = subprocess.run([sys.executable, "-c", code], capture_output=True,
                         text=True, timeout=300,
                         env=dict(os.environ, PYTHONPATH=os.path.join(root, "src")))
    assert out.returncode == 0, out.stderr
    assert "OK" in out.stdout
