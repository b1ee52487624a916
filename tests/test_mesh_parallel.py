"""Ring attention + pipeline parallelism vs single-device oracles
(subprocess SPMD, like test_distributed)."""
from tests.test_distributed import run_spmd


def test_ring_attention_matches_full_attention():
    run_spmd("""
        from repro.core.comm import Comm
        from repro.mesh.ring import ring_attention
        from repro.kernels import ref

        rng = np.random.default_rng(0)
        B, S, Hq, Hkv, D = 2, 256, 4, 2, 32
        q = jnp.asarray(rng.normal(size=(B, S, Hq, D)), jnp.float32)
        k = jnp.asarray(rng.normal(size=(B, S, Hkv, D)), jnp.float32)
        v = jnp.asarray(rng.normal(size=(B, S, Hkv, D)), jnp.float32)
        want = ref.flash_attention(q, k, v, causal=True)

        mesh = make_mesh((8,), ("sp",))
        def body(q, k, v):
            return ring_attention(q, k, v, Comm("sp"), causal=True)
        got = jax.jit(jax.shard_map(
            body, mesh=mesh,
            in_specs=(P(None, "sp", None, None),) * 3,
            out_specs=P(None, "sp", None, None), check_vma=False))(q, k, v)
        np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                                   rtol=2e-4, atol=2e-4)
        print("ring attention OK")
    """)


def test_pipeline_matches_sequential():
    run_spmd("""
        from repro.mesh.pipeline import (pipeline_apply, reference_apply,
                                         bubble_fraction)
        rng = np.random.default_rng(0)
        n_stages, n_micro, mb, d = 4, 6, 2, 16
        params = {"w": jnp.asarray(rng.normal(size=(n_stages, d, d)) * 0.2,
                                   jnp.float32),
                  "b": jnp.asarray(rng.normal(size=(n_stages, d)) * 0.1,
                                   jnp.float32)}
        x = jnp.asarray(rng.normal(size=(n_micro, mb, d)), jnp.float32)

        def stage_fn(p, h):
            return jnp.tanh(h @ p["w"] + p["b"])

        mesh = make_mesh((4,), ("pod",))
        got = pipeline_apply(stage_fn, params, x, mesh, axis="pod")
        want = reference_apply(stage_fn, params, x)
        np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                                   rtol=1e-5, atol=1e-5)
        assert abs(bubble_fraction(4, 6) - 3/9) < 1e-9
        print("pipeline OK")
    """, n_devices=4)
