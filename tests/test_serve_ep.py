"""Expert-parallel serving over a 2-D ("expert", "model") mesh: parity.

Engines that partition whole MoE experts over an ``ep``-sized "expert" axis
(all-to-all dispatch/combine, replicated routing) must emit greedy token
streams bit-identical to the single-device engine — at ep=2, ep=4 and the
composed tp=2 x ep=2 mesh, with the prefix cache, forced preemption, int8 KV
quantization, speculative decode and load-aware expert re-placement in the
loop.  Per-expert telemetry must be mesh-invariant (routing is replicated).

Subprocess SPMD via ``--xla_force_host_platform_device_count=8`` (the main
pytest process must keep 1 device), like :mod:`tests.test_distributed`.
"""
from tests.test_distributed import run_spmd

_STREAMS = """
    from repro.configs import smoke_config
    from repro.models.api import build_model
    from repro.serve import ServeEngine

    def ep_mesh(ep, tp=1):
        return make_mesh((ep, tp), ("expert", "model"))

    def streams(model, params, mesh, n_req=4, max_new=6, **kw):
        kw.setdefault("max_slots", 4); kw.setdefault("max_len", 96)
        eng = ServeEngine(model, params, mesh=mesh, paged=True, **kw)
        prompts = ([5, 17, 33, 2, 9], [7] * 9, [1, 2, 3] * 4,
                   [100, 200, 300, 4, 5, 6, 7])[:n_req]
        for p in prompts:
            eng.submit(p, max_new_tokens=max_new)
        done = eng.run_until_drained()
        eng.close()
        assert all(r.error is None for r in done)
        return {r.rid: r.output for r in done}, eng

    MOE = smoke_config("qwen3-moe-235b-a22b").replace(remat="none")
"""


def test_ep_paged_parity_and_telemetry():
    """ep=2, ep=4 and tp=2 x ep=2 MoE engines match the single-device
    engine token-for-token, and the per-expert telemetry (routed / dropped
    / per-expert counts) is identical at every mesh — routing is replicated
    so the measurements are global facts, not per-rank samples."""
    run_spmd(_STREAMS + """
    model = build_model(MOE)
    params = model.init(jax.random.PRNGKey(0))
    want, ref = streams(model, params, None, page_size=16, prefill_chunk=32)
    assert ref.stats["moe_tokens_routed"] > 0
    for ep, tp in ((2, 1), (4, 1), (2, 2)):
        got, eng = streams(model, params, ep_mesh(ep, tp), page_size=16,
                           prefill_chunk=32)
        assert (eng.ep, eng.tp) == (ep, tp)
        assert got == want, (ep, tp)
        for k in ("moe_tokens_routed", "moe_dropped_tokens", "expert_tokens"):
            assert eng.stats[k] == ref.stats[k], (ep, tp, k)

    # legacy 1-D ("model",) mesh is untouched by the expert axis
    got, eng = streams(model, params, make_mesh((2,), ("model",)),
                       page_size=16, prefill_chunk=32)
    assert (eng.ep, eng.tp) == (1, 2) and got == want

    # dense families refuse an expert axis up front, with the fix named
    dense = build_model(smoke_config("qwen2-7b").replace(remat="none"))
    dp = dense.init(jax.random.PRNGKey(0))
    try:
        ServeEngine(dense, dp, max_slots=2, max_len=32, paged=True,
                    mesh=ep_mesh(2))
        raise AssertionError("dense + ep=2 must refuse")
    except ValueError as e:
        assert "dense family" in str(e) and "--mesh tp=N" in str(e)
    # ...and the expert axis needs the paged MoE path
    try:
        ServeEngine(dense, dp, max_slots=2, max_len=32, paged=False,
                    mesh=ep_mesh(2))
        raise AssertionError("non-paged + ep=2 must refuse")
    except ValueError as e:
        assert "paged" in str(e)
    print("ep paged parity OK")
    """)


def test_ep_parity_prefix_cache_and_preemption():
    """Prefix sharing and the preemption/recompute policy are host-side;
    under an expert mesh the streams and host counters stay identical."""
    run_spmd(_STREAMS + """
    model = build_model(MOE)
    params = model.init(jax.random.PRNGKey(0))

    P = list(range(1, 25))
    waves = ([P], [P, P], [P[:20] + [77, 78]])

    def run(mesh, prefix_cache, num_pages=None, max_len=128, max_new=12,
            max_slots=2):
        eng = ServeEngine(model, params, max_slots=max_slots, max_len=max_len,
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
    assert base == want and s1["prefix_hits"] >= 3
    got, s2 = run(ep_mesh(2), True)
    assert got == want
    for k in ("prefix_hits", "prefix_hit_tokens", "cow_copies", "evictions"):
        assert s2[k] == s1[k], k

    # pool at the single-request minimum forces preemption on the expert
    # mesh too; the recompute policy keeps streams identical
    waves = ([[5, 17, 33, 2, 9, 1, 2, 3], [100, 200, 300, 4, 5, 6, 7, 8]],)
    want, s_off = run(None, False, num_pages=4, max_len=64, max_new=30)
    assert s_off["preemptions"] >= 1
    got, s_ep = run(ep_mesh(2, 2), False, num_pages=4, max_len=64, max_new=30)
    assert got == want and s_ep["preemptions"] >= 1
    print("ep prefix + preemption parity OK")
    """)


def test_ep_parity_quant_and_spec_decode():
    """int8 KV pages and ngram speculative decode compose with the expert
    axis: quant-on ep=2 streams equal quant-on serial streams, spec-on ep=2
    equals the spec-OFF serial reference, and the draft counters are
    mesh-invariant."""
    run_spmd(_STREAMS + """
    model = build_model(MOE)
    params = model.init(jax.random.PRNGKey(0))

    want, _ = streams(model, params, None, page_size=8, prefill_chunk=16,
                      kv_quant="int8")
    got, eng = streams(model, params, ep_mesh(2), page_size=8,
                       prefill_chunk=16, kv_quant="int8")
    assert eng.stats["kv_quant"] == "int8" and got == want
    got, _ = streams(model, params, ep_mesh(2, 2), page_size=8,
                     prefill_chunk=16, kv_quant="int8")
    assert got == want, "kv quant ep x tp parity"

    plain, _ = streams(model, params, None, page_size=8, prefill_chunk=16,
                       max_new=10)
    spec1, e1 = streams(model, params, None, page_size=8, prefill_chunk=16,
                        max_new=10, spec_decode="ngram")
    assert spec1 == plain and e1.stats["draft_proposed"] > 0
    spec2, e2 = streams(model, params, ep_mesh(2), page_size=8,
                        prefill_chunk=16, max_new=10, spec_decode="ngram")
    assert spec2 == plain, "ep spec parity"
    for k in ("draft_proposed", "draft_accepted", "acceptance_rate"):
        assert e1.stats[k] == e2.stats[k], k
    print("ep quant + spec parity OK")
    """)


def test_ep_placement_rebalance_parity():
    """Load-aware re-placement on a live expert mesh: the weight
    permutation + dispatch-map swap between ticks leaves token streams
    bitwise unchanged at ep=2 and ep=4, and re-placement reduces (or at
    worst preserves) the measured rank imbalance."""
    run_spmd(_STREAMS + """
    model = build_model(MOE)
    params = model.init(jax.random.PRNGKey(0))
    want, ref = streams(model, params, None, page_size=16, prefill_chunk=32)
    for ep in (2, 4):
        got, eng = streams(model, params, ep_mesh(ep), page_size=16,
                           prefill_chunk=32, placement_interval=2)
        assert got == want, ep
        assert eng.stats["placement_updates"] >= 1
        assert eng.placement is not None
        assert eng.stats["expert_tokens"] == ref.stats["expert_tokens"]
        # the live plan is a full slot assignment (every physical slot holds
        # some expert's weights) and every non-evicted expert is reachable
        pe = eng.placement.phys_expert
        assert sorted(set(pe.tolist())) and (pe >= 0).all()
        assert eng.stats["expert_imbalance"] >= 1.0
    print("ep placement parity OK")
    """)
