"""Tensor-parallel serving over a device mesh: token-stream parity.

Sharded engines (params + paged KV heads over a 1-D ("model",) mesh) must
emit greedy token streams identical to the single-device engine — dense and
MoE families, at tp=2 and tp=4, with the Pallas paged-attention kernel in
the loop and under forced preemption.  Recurrent families run slot-parallel
(batch over the mesh) and must match too.

Subprocess SPMD via ``--xla_force_host_platform_device_count=8`` (the main
pytest process must keep 1 device), like :mod:`tests.test_distributed`.
"""
import pytest

from repro.configs import smoke_config
from repro.launch.mesh import make_mesh
from repro.models.api import build_model
from tests.test_distributed import run_spmd

_STREAMS = """
    from repro.configs import smoke_config
    from repro.models.api import build_model
    from repro.serve import ServeEngine

    def streams(model, params, mesh, n_req=4, max_new=6, **kw):
        kw.setdefault("max_slots", 4); kw.setdefault("max_len", 64)
        eng = ServeEngine(model, params, mesh=mesh, **kw)
        prompts = ([5, 17, 33, 2, 9], [100, 200, 300], [7] * 11,
                   [1, 2, 3, 4])[:n_req]
        for p in prompts:
            eng.submit(p, max_new_tokens=max_new)
        done = eng.run_until_drained()
        eng.close()
        assert all(r.error is None for r in done)
        return {r.rid: r.output for r in done}, eng
"""


def test_tp_paged_parity_dense_and_moe():
    """tp=2 and tp=4 paged engines match the tp=1 (no-mesh) engine
    token-for-token on the dense and MoE smoke configs."""
    run_spmd(_STREAMS + """
    for arch in ("qwen2-7b", "qwen3-moe-235b-a22b"):
        cfg = smoke_config(arch).replace(remat="none", n_heads=8,
                                         n_kv_heads=4)
        model = build_model(cfg)
        params = model.init(jax.random.PRNGKey(0))
        want, _ = streams(model, params, None, paged=True, page_size=8,
                          prefill_chunk=16)
        for tp in (2, 4):
            mesh = make_mesh((tp,), ("model",))
            got, eng = streams(model, params, mesh, paged=True, page_size=8,
                               prefill_chunk=16)
            assert eng.tp == tp
            assert got == want, (arch, tp)
    print("tp paged parity OK")
    """)


def test_tp_parity_under_preemption_and_pallas():
    """A pool at the single-request minimum forces preemption on the
    sharded engine too; the recompute policy keeps streams identical.
    Second half: the Pallas paged-attention kernel inside the shard_map
    body (interpret mode on CPU) matches as well."""
    run_spmd(_STREAMS + """
    cfg = smoke_config("qwen2-7b").replace(remat="none", n_heads=8,
                                           n_kv_heads=4)
    model = build_model(cfg)
    params = model.init(jax.random.PRNGKey(0))

    def go(mesh):
        eng = ServeEngine(model, params, max_slots=2, max_len=64, paged=True,
                          page_size=16, num_pages=4, prefill_chunk=16,
                          mesh=mesh)
        eng.submit([5, 17, 33, 2, 9, 1, 2, 3], max_new_tokens=30)
        eng.submit([100, 200, 300, 4, 5, 6, 7, 8], max_new_tokens=30)
        done = eng.run_until_drained()
        eng.close()
        return {r.rid: r.output for r in done}, eng.stats["preemptions"]

    want, pre1 = go(None)
    got, pre2 = go(make_mesh((2,), ("model",)))
    assert pre1 >= 1 and pre2 >= 1, (pre1, pre2)
    assert got == want

    want, _ = streams(model, params, None, paged=True, page_size=16,
                      prefill_chunk=16, use_pallas_attention=True)
    got, _ = streams(model, params, make_mesh((2,), ("model",)),
                     paged=True, page_size=16, prefill_chunk=16,
                     use_pallas_attention=True)
    assert got == want
    print("preemption + pallas tp parity OK")
    """)


def test_tp_prefix_cache_parity():
    """Prefix sharing is host-side page-table policy: the sharded engine
    reads shared pages through the same gather ops, so cache-on streams at
    tp=2/4 are bit-identical to the tp=1 cache-off reference — replay
    (full-prompt hits) and copy-on-write included — and the host-side
    cache counters are identical at every tp."""
    run_spmd("""
    from repro.configs import smoke_config
    from repro.models.api import build_model
    from repro.serve import ServeEngine

    cfg = smoke_config("qwen2-7b").replace(remat="none", n_heads=8,
                                           n_kv_heads=4)
    model = build_model(cfg)
    params = model.init(jax.random.PRNGKey(0))
    P = list(range(1, 25))                  # 1 full + 1 partial page
    # wave 2's twin P-requests share P's parked pages (replay) and then
    # diverge-proof COW on the partial last page; wave 3 diverges mid-page
    waves = ([P], [P, P], [P[:20] + [77, 78]])

    def run(mesh, prefix_cache, num_pages=None, max_len=128, max_new=12):
        eng = ServeEngine(model, params, max_slots=2, max_len=max_len,
                          paged=True, page_size=16, prefill_chunk=16,
                          num_pages=num_pages, prefix_cache=prefix_cache,
                          mesh=mesh)
        for wave in waves:
            for p in wave:
                eng.submit(p, max_new_tokens=max_new)
            eng.run_until_drained()
        outs = {r.rid: r.output for r in eng.finished}
        assert all(r.error is None for r in eng.finished)
        eng.close()
        return outs, eng.stats

    want, _ = run(None, False)
    base, s1 = run(None, True)
    assert base == want
    assert s1["prefix_hits"] >= 3 and s1["cow_copies"] >= 1, s1
    for tp in (2, 4):
        got, stats = run(make_mesh((tp,), ("model",)), True)
        assert got == want, tp
        # the host-side policy is mesh-invariant, counter for counter
        for k in ("prefix_hits", "prefix_hit_tokens", "cow_copies",
                  "evictions"):
            assert stats[k] == s1[k], (tp, k, stats[k], s1[k])

    # forced preemption with sharing in play (pool at the one-request
    # minimum): parked-page re-matching survives sharding too
    waves = ([[5, 17, 33, 2, 9, 1, 2, 3], [100, 200, 300, 4, 5, 6, 7, 8]],
             [[5, 17, 33, 2, 9, 1, 2, 3]])
    want, s_off = run(None, False, num_pages=4, max_len=64, max_new=30)
    assert s_off["preemptions"] >= 1
    got, s_tp = run(make_mesh((2,), ("model",)), True, num_pages=4,
                    max_len=64, max_new=30)
    assert got == want and s_tp["prefix_hits"] >= 1
    print("tp prefix-cache parity OK")
    """)


def test_tp_spec_decode_parity():
    """Speculative decode is host-side policy plus one extra batched device
    call: sharded spec-on streams (dense + MoE) must equal the tp=1
    spec-OFF reference bit-for-bit, under forced preemption too, and the
    draft counters (proposed / accepted / acceptance_rate) must be
    mesh-invariant — the same drafts are proposed and accepted at every
    tp."""
    run_spmd("""
    from repro.configs import smoke_config
    from repro.models.api import build_model
    from repro.serve import ServeEngine

    for arch in ("qwen2-7b", "qwen3-moe-235b-a22b"):
        cfg = smoke_config(arch).replace(remat="none", n_heads=8,
                                         n_kv_heads=4)
        model = build_model(cfg)
        params = model.init(jax.random.PRNGKey(0))

        def streams(mesh, **kw):
            eng = ServeEngine(model, params, max_slots=4, max_len=64,
                              prefill_chunk=16, page_size=8, paged=True,
                              mesh=mesh, **kw)
            prompts = ([5, 17, 33, 5, 17, 33, 5, 17], [7] * 11,
                       [1, 2, 3, 4, 1, 2, 3, 4, 1, 2],
                       [9, 9, 8, 8, 9, 9, 8, 8])
            for p in prompts:
                eng.submit(p, max_new_tokens=10)
            done = eng.run_until_drained()
            eng.close()
            assert all(r.error is None for r in done)
            return {r.rid: r.output for r in done}, eng.stats

        want, _ = streams(None)
        got1, s1 = streams(None, spec_decode="ngram")
        assert got1 == want, (arch, "tp=1 spec parity")
        assert s1["draft_proposed"] > 0
        got2, s2 = streams(make_mesh((2,), ("model",)),
                           spec_decode="ngram")
        assert got2 == want, (arch, "tp=2 spec parity")
        for k in ("draft_proposed", "draft_accepted", "acceptance_rate"):
            assert s1[k] == s2[k], (arch, k, s1[k], s2[k])
        # fused multi-query kernel inside the shard_map body: spec verify +
        # decode + prefill all through Pallas, still the same streams
        got3, s3 = streams(make_mesh((2,), ("model",)),
                           spec_decode="ngram", use_pallas_attention=True)
        assert got3 == want, (arch, "tp=2 spec+pallas parity")
        assert s3["draft_proposed"] > 0

    # forced preemption with speculation on: verify windows never evict
    # anyone plain decode would have kept, and streams still match
    cfg = smoke_config("qwen2-7b").replace(remat="none", n_heads=8,
                                           n_kv_heads=4)
    model = build_model(cfg)
    params = model.init(jax.random.PRNGKey(0))

    def tight(mesh, **kw):
        eng = ServeEngine(model, params, max_slots=2, max_len=64, paged=True,
                          page_size=16, num_pages=4, prefill_chunk=16,
                          mesh=mesh, **kw)
        eng.submit([5, 17, 33, 2, 9, 1, 2, 3], max_new_tokens=30)
        eng.submit([100, 200, 300, 4, 5, 6, 7, 8], max_new_tokens=30)
        done = eng.run_until_drained()
        eng.close()
        return {r.rid: r.output for r in done}, eng.stats["preemptions"]

    want, pre = tight(None)
    assert pre >= 1
    got, _ = tight(make_mesh((2,), ("model",)), spec_decode="ngram")
    assert got == want
    print("tp spec-decode parity OK")
    """)


def test_tp_quant_parity():
    """int8 KV pages under tensor parallelism: the scale leaves shard over
    the head axis exactly like K/V, quantization happens inside the
    shard_map body on each device's own heads, and per-row scales commute
    with the head split — so quant-on tp=2/4 streams are bit-identical to
    the quant-on tp=1 streams (dense + MoE, Pallas kernel included).
    Weights-only int8 dequant also commutes with the Megatron param split
    (per-tensor scalar scale, replicated), so it must match too."""
    run_spmd(_STREAMS + """
    for arch in ("qwen2-7b", "qwen3-moe-235b-a22b"):
        cfg = smoke_config(arch).replace(remat="none", n_heads=8,
                                         n_kv_heads=4)
        model = build_model(cfg)
        params = model.init(jax.random.PRNGKey(0))
        want, eng1 = streams(model, params, None, paged=True, page_size=8,
                             prefill_chunk=16, kv_quant="int8")
        assert eng1.stats["kv_quant"] == "int8"
        for tp in (2, 4):
            mesh = make_mesh((tp,), ("model",))
            got, eng = streams(model, params, mesh, paged=True, page_size=8,
                               prefill_chunk=16, kv_quant="int8")
            assert eng.tp == tp
            assert got == want, (arch, tp, "kv quant tp parity")
        got, _ = streams(model, params, make_mesh((2,), ("model",)),
                         paged=True, page_size=8, prefill_chunk=16,
                         kv_quant="int8", use_pallas_attention=True)
        assert got == want, (arch, "kv quant + pallas tp parity")

    cfg = smoke_config("qwen2-7b").replace(remat="none", n_heads=8,
                                           n_kv_heads=4)
    model = build_model(cfg)
    params = model.init(jax.random.PRNGKey(0))
    want, _ = streams(model, params, None, paged=True, page_size=8,
                      prefill_chunk=16, kv_quant="int8", weight_quant="int8")
    got, eng = streams(model, params, make_mesh((2,), ("model",)),
                       paged=True, page_size=8, prefill_chunk=16,
                       kv_quant="int8", weight_quant="int8")
    assert eng.stats["weight_quant"] == "int8"
    assert got == want, "weight quant tp parity"
    print("tp quant parity OK")
    """)


def test_slot_parallel_recurrent_family():
    """rwkv6 has no KV to shard; the mesh engine shards decode SLOTS over
    the devices instead (params replicated, state batch-sharded) and the
    per-slot math is unchanged — streams match exactly."""
    run_spmd(_STREAMS + """
    cfg = smoke_config("rwkv6-3b").replace(remat="none")
    model = build_model(cfg)
    params = model.init(jax.random.PRNGKey(0))
    want, enga = streams(model, params, None)
    assert not enga.paged
    for tp in (2, 4):
        got, eng = streams(model, params, make_mesh((tp,), ("model",)))
        assert not eng.paged and eng.tp == tp
        assert got == want, tp

    # regression: a dense-FORCED DecoderLM must also run slot-parallel with
    # replicated params — applying its Megatron TP specs to the comm-less
    # dense step would silently zero half the KV heads
    cfg = smoke_config("qwen2-7b").replace(remat="none")
    model = build_model(cfg)
    params = model.init(jax.random.PRNGKey(0))
    want, _ = streams(model, params, None, paged=False)
    got, eng = streams(model, params, make_mesh((2,), ("model",)),
                       paged=False)
    assert not eng.paged
    assert got == want
    print("slot-parallel parity OK")
    """)


def test_init_model_creates_params_in_serving_shardings():
    """The launcher's init under a mesh: every weight is created in the
    sharding the engine serves it with (TP-split leaves never exist whole
    on one device), matches a plain ``model.init`` from the same seed to
    float32 rounding, and serves the streams its own values serve
    unsharded."""
    run_spmd(_STREAMS + """
    from repro.launch.serve import init_model
    from repro.serve.engine import serve_param_specs
    cfg = smoke_config("qwen2-7b").replace(remat="none", n_heads=8,
                                           n_kv_heads=4)
    mesh = make_mesh((4,), ("model",))
    model, params = init_model(cfg, seed=3, mesh=mesh)
    plain = model.init(jax.random.PRNGKey(3))
    leaves, tdef = jax.tree_util.tree_flatten(params)
    specs = tdef.flatten_up_to(
        serve_param_specs(model, plain, mesh, paged=True))
    n_split = 0
    for a, w, s in zip(leaves, tdef.flatten_up_to(plain), specs):
        assert a.sharding.is_equivalent_to(NamedSharding(mesh, s), a.ndim)
        np.testing.assert_allclose(np.asarray(a), np.asarray(w), rtol=1e-6)
        n_split += any(sh.data.shape != a.shape for sh in a.addressable_shards)
    assert n_split > 0
    want, _ = streams(model, jax.tree_util.tree_map(np.asarray, params),
                      None, paged=True, page_size=8, prefill_chunk=16)
    got, _ = streams(model, params, mesh, paged=True, page_size=8,
                     prefill_chunk=16)
    assert got == want
    print("init_model shardings OK")
    """)


def test_tp_divisibility_validation():
    """Host-side (no mesh needed): indivisible head/expert counts raise
    with every offending dimension named."""
    model = build_model(smoke_config("qwen2-7b"))     # hq=4, hkv=2
    with pytest.raises(ValueError, match="padded_kv_heads=2"):
        model.validate_serve_mesh(tp=4)
    model.validate_serve_mesh(tp=2)                   # 2 divides everything
    model.validate_serve_mesh(tp=1)                   # tp=1 never validates
    moe = build_model(smoke_config("qwen3-moe-235b-a22b"))  # E=8
    with pytest.raises(ValueError, match="n_experts=8"):
        moe.validate_serve_mesh(tp=3)


def test_mesh_engine_argument_validation():
    """mesh= and rules= are mutually exclusive, and a mesh without a
    'model' axis is rejected (1-device meshes keep this in-process)."""
    import jax
    from repro.serve import ServeEngine
    model = build_model(smoke_config("rwkv6-3b").replace(remat="none"))
    params = model.init(jax.random.PRNGKey(0))
    mesh = make_mesh((1,), ("model",))
    eng = ServeEngine(model, params, max_slots=3, max_len=32, mesh=mesh)
    eng.close()
    with pytest.raises(ValueError, match="not both"):
        ServeEngine(model, params, max_slots=3, max_len=32, mesh=mesh,
                    rules=object())
    with pytest.raises(ValueError, match="'model' axis"):
        ServeEngine(model, params, max_slots=2, max_len=32,
                    mesh=make_mesh((1,), ("data",)))
