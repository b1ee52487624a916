"""Expert capacity, mesh validation and load-aware placement (host-side).

Single-device tier-1 coverage for the expert-parallel serving stack:
``capacity()`` edge cases, ``validate_serve_mesh`` (1-D and 2-D meshes),
the ``plan_placement`` rebalancer (skew gains, hot-expert replication,
zero-traffic eviction, determinism), ``apply_placement`` as a pure weight
permutation, the replicated-combine == single-copy bitwise property, and
engine-level drop telemetry + placement stream parity.  Multi-device parity
lives in :mod:`tests.test_serve_ep`.
"""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import smoke_config
from repro.core.comm import SerialComm
from repro.models import moe as M
from repro.models.api import build_model
from repro.serve import ServeEngine
from repro.serve.placement import (PlacementPlan, apply_placement,
                                   identity_plan, imbalance, plan_placement)


def test_capacity_edge_cases():
    """cf scales the balanced budget; cf < 1 under-provisions on purpose;
    the floor is 4 (aligned); top_k > n_experts can never route."""
    # balanced split * cf, rounded up to a multiple of 4
    assert M.capacity(64, 4, 8, 1.0) == 32
    assert M.capacity(64, 4, 8, 1.25) == 40
    # cf < 1 deliberately under-provisions (drops are counted, not hidden)
    assert M.capacity(64, 4, 8, 0.5) == 16
    # tiny token counts clamp to the aligned floor, never 0
    assert M.capacity(1, 4, 8, 1.25) == 4
    assert M.capacity(0, 2, 8, 1.0) == 4
    with pytest.raises(ValueError, match="top_k=9 > n_experts=8"):
        M.capacity(16, 9, 8, 1.0)


def test_validate_serve_mesh_and_alias():
    """Every indivisible dimension is named; dense families refuse an
    expert axis outright; ``tp`` alone validates a 1-D ("model",) mesh."""
    dense = build_model(smoke_config("qwen2-7b"))      # hq=4, hkv=2
    moe = build_model(smoke_config("qwen3-moe-235b-a22b"))  # E=8

    dense.validate_serve_mesh(tp=2)                    # divides everything
    moe.validate_serve_mesh(tp=2, ep=4)                # 8 experts over 8 ways
    moe.validate_serve_mesh(tp=1, ep=8)
    with pytest.raises(ValueError, match="padded_kv_heads=2"):
        dense.validate_serve_mesh(tp=4)
    with pytest.raises(ValueError, match="n_experts=8"):
        moe.validate_serve_mesh(tp=1, ep=3)
    with pytest.raises(ValueError, match="n_experts=8"):
        moe.validate_serve_mesh(tp=2, ep=8)            # ep*tp = 16 > 8
    with pytest.raises(ValueError, match="dense family"):
        dense.validate_serve_mesh(tp=1, ep=2)
    # a 1-D mesh: ep keeps its default of 1
    dense.validate_serve_mesh(tp=2)
    with pytest.raises(ValueError, match="padded_kv_heads=2"):
        dense.validate_serve_mesh(tp=4)


def test_plan_placement_skew_gain_and_determinism():
    """Adjacent hot experts (worst case for the identity layout) rebalance
    to >= 1.5x lower max/mean; plans are bit-deterministic."""
    counts = [1000, 900, 10, 10, 10, 10, 10, 10]
    before = imbalance(identity_plan(8, 2).rank_loads(counts))
    plan = plan_placement(counts, ep=2)
    after = imbalance(plan.rank_loads(counts))
    assert before / after >= 1.5, (before, after)
    # token conservation: a plan only moves load, it never loses any
    assert plan.rank_loads(counts).sum() == sum(counts)
    # determinism: same window -> bit-identical plan
    again = plan_placement(counts, ep=2)
    for f in ("phys_expert", "slot_a", "slot_b", "split_q"):
        assert np.array_equal(getattr(plan, f), getattr(again, f)), f


def test_plan_placement_replication_and_eviction():
    """A dominant expert is replicated (split_q set, second slot) by
    evicting a zero-traffic expert; evicted experts read slot -1."""
    counts = [5000, 0, 10, 10, 0, 10, 10, 10]
    plan = plan_placement(counts, ep=2)
    h = 0
    assert plan.slot_a[h] != plan.slot_b[h] and plan.split_q[h] > 0
    evicted = [e for e in range(8) if plan.slot_a[e] < 0]
    assert evicted and all(counts[e] == 0 for e in evicted)
    gain = (imbalance(identity_plan(8, 2).rank_loads(counts))
            / imbalance(plan.rank_loads(counts)))
    assert gain >= 1.5, gain
    # replicate=False keeps one slot per expert (pure permutation)
    pure = plan_placement(counts, ep=2, replicate=False)
    assert (pure.slot_a == pure.slot_b).all() and (pure.split_q == 0).all()
    assert sorted(pure.phys_expert.tolist()) == list(range(8))
    with pytest.raises(ValueError, match="not divisible"):
        plan_placement([1, 2, 3], ep=2)


def test_plan_placement_heterogeneous_ranks():
    """Measured per-rank seconds/token feed find_optimal_workload: the 2x
    slower rank gets the lighter half of the experts."""
    counts = [300, 300, 300, 300, 20, 20, 20, 20]
    even = plan_placement(counts, ep=2).rank_loads(counts)
    assert abs(int(even[0]) - int(even[1])) <= 40, even  # uniform: balanced
    plan = plan_placement(counts, ep=2, rank_time_per_token=[1.0, 2.0])
    loads = plan.rank_loads(counts)
    assert loads[0] > loads[1], loads                    # fast rank loaded up


def test_identity_plan_matches_identity_placement():
    """The engine's no-op plan and the module-level identity dispatch map
    are the same (3, E) integers — the bitwise-parity anchor."""
    assert np.array_equal(identity_plan(8, 2).dispatch_arrays(),
                          M.identity_placement(8))


def test_apply_placement_permutes_weight_stacks():
    """apply_placement is a pure permutation of the expert axis of the
    stacked MoE leaves (router untouched), including int8 weight leaves."""
    rng = np.random.default_rng(0)
    gate = rng.standard_normal((2, 4, 3, 5)).astype(np.float32)  # (L,E,d,f)
    down = rng.standard_normal((2, 4, 5, 3)).astype(np.float32)
    q8 = {"q8": rng.integers(-127, 127, (2, 4, 3, 5), dtype=np.int8),
          "s8": np.float32(0.02)}
    params = {"blocks": {"attn": "keep", "moe": {
        "router": "keep", "gate": gate, "up": q8, "down": down}}}
    perm = np.array([2, 0, 3, 1])
    plan = PlacementPlan(4, 2, perm, np.argsort(perm), np.argsort(perm),
                         np.zeros(4, np.int64))
    out = apply_placement(params, plan)
    assert np.array_equal(out["blocks"]["moe"]["gate"], gate[:, perm])
    assert np.array_equal(out["blocks"]["moe"]["down"], down[:, perm])
    assert np.array_equal(out["blocks"]["moe"]["up"]["q8"], q8["q8"][:, perm])
    assert out["blocks"]["moe"]["up"]["s8"] == q8["s8"]  # per-tensor scale
    assert out["blocks"]["moe"]["router"] == "keep"      # routing is logical
    assert out["blocks"]["attn"] == "keep"
    # original tree untouched; unassigned slots / dense trees refuse
    assert np.array_equal(params["blocks"]["moe"]["gate"], gate)
    bad = PlacementPlan(4, 2, np.array([2, 0, 3, -1]), perm, perm,
                        np.zeros(4, np.int64))
    with pytest.raises(ValueError, match="unassigned"):
        apply_placement(params, bad)
    with pytest.raises(ValueError, match="no expert-stacked"):
        apply_placement({"blocks": {"attn": "x"}}, plan)


def test_replicated_combine_matches_single_copy():
    """Property: splitting a hot expert's capacity rows across two physical
    slots (both holding its weights) combines to the BITWISE same output,
    aux loss and telemetry as the single-copy dispatch — each capacity row
    is computed exactly once either way.  Expert E-1 is pinned out of the
    router's top_k so its eviction provably drops nothing."""
    cfg = smoke_config("qwen3-moe-235b-a22b")
    E, d, eff = cfg.n_experts, cfg.d_model, cfg.expert_d_ff
    for seed in range(3):
        ks = jax.random.split(jax.random.PRNGKey(seed), 5)
        # column 0 of x is the constant 1 so wr[0, E-1] = -100 pins logit
        # E-1 at -100 for every token: expert E-1 never routes
        x = jax.random.normal(ks[0], (37, d), jnp.float32).at[:, 0].set(1.0)
        wr = (jax.random.normal(ks[1], (d, E), jnp.float32) * 0.2
              ).at[:, E - 1].set(0.0).at[0, E - 1].set(-100.0)
        wg = jax.random.normal(ks[2], (E, d, eff), jnp.float32) * 0.1
        wu = jax.random.normal(ks[3], (E, d, eff), jnp.float32) * 0.1
        wd = jax.random.normal(ks[4], (E, eff, d), jnp.float32) * 0.1
        y0, aux0, s0 = M._dispatch_compute_combine(
            x, wr, wg, wu, wd, cfg, SerialComm())
        counts = np.asarray(s0["tokens"])
        assert counts[E - 1] == 0 and counts.sum() == 37 * cfg.top_k
        # the identity map reproduces the unplaced integer slots exactly
        yi, auxi, si = M._dispatch_compute_combine(
            x, wr, wg, wu, wd, cfg, SerialComm(),
            placement=jnp.asarray(M.identity_placement(E)))
        assert (np.asarray(yi) == np.asarray(y0)).all()
        assert float(auxi) == float(aux0)
        # replicate the hottest expert h into evicted E-1's slot at three
        # different q8 split points; weights permuted to match
        h = int(counts.argmax())
        for q in (64, 128, 200):
            pl = M.identity_placement(E)
            pl[1, h] = E - 1
            pl[2, h] = q
            pl[0, E - 1] = pl[1, E - 1] = -1
            idx = np.arange(E)
            idx[E - 1] = h                    # slot E-1 holds h's weights
            yr, auxr, sr = M._dispatch_compute_combine(
                x, wr, wg[idx], wu[idx], wd[idx], cfg, SerialComm(),
                placement=jnp.asarray(pl))
            assert (np.asarray(yr) == np.asarray(y0)).all(), (seed, q)
            assert float(auxr) == float(aux0)
            assert np.array_equal(np.asarray(sr["tokens"]), counts)
            assert np.array_equal(np.asarray(sr["dropped"]),
                                  np.asarray(s0["dropped"]))


def _streams(model, params, **kw):
    eng = ServeEngine(model, params, max_slots=4, max_len=96, paged=True,
                      page_size=16, prefill_chunk=32, **kw)
    for p in ([5, 17, 33, 2, 9], [7] * 9, [1, 2, 3] * 4,
              [100, 200, 300, 4, 5, 6, 7]):
        eng.submit(p, max_new_tokens=6)
    done = eng.run_until_drained()
    eng.close()
    assert all(r.error is None for r in done)
    return {r.rid: r.output for r in done}, eng


def test_engine_drop_telemetry_serial_path():
    """Capacity-factor drops are counted on the plain single-device path:
    cf=0.5 under-provisions the dispatch and the engine's stats surface
    routed/dropped totals plus per-expert counts."""
    cfg = smoke_config("qwen3-moe-235b-a22b").replace(remat="none",
                                                      capacity_factor=0.5)
    model = build_model(cfg)
    params = model.init(jax.random.PRNGKey(0))
    _, eng = _streams(model, params)
    s = eng.stats
    assert s["moe_dropped_tokens"] > 0
    assert s["moe_tokens_routed"] == sum(s["expert_tokens"]) > 0
    assert len(s["expert_tokens"]) == cfg.n_experts
    assert s["expert_imbalance"] >= 1.0
    # dense engines carry the same keys, at zero
    dense = build_model(smoke_config("qwen2-7b").replace(remat="none"))
    _, deng = _streams(dense, dense.init(jax.random.PRNGKey(0)))
    assert deng.stats["moe_tokens_routed"] == 0
    assert deng.stats["expert_tokens"] == []


def test_engine_placement_stream_parity_single_device():
    """Re-placing experts every 2 ticks (weight permutation + dispatch map)
    leaves the greedy token streams bitwise unchanged, and dense engines
    refuse update_placement with a clear error."""
    cfg = smoke_config("qwen3-moe-235b-a22b").replace(remat="none")
    model = build_model(cfg)
    params = model.init(jax.random.PRNGKey(0))
    want, ref = _streams(model, params)
    got, eng = _streams(model, params, placement_interval=2)
    assert got == want
    assert eng.stats["placement_updates"] >= 1
    assert eng.placement is not None
    assert sorted(eng.placement.phys_expert.tolist()) == list(range(8))
    # telemetry is placement-invariant (routing stays logical)
    assert eng.stats["moe_tokens_routed"] == ref.stats["moe_tokens_routed"]
    assert eng.stats["expert_tokens"] == ref.stats["expert_tokens"]

    dense = build_model(smoke_config("qwen2-7b").replace(remat="none"))
    deng = ServeEngine(dense, dense.init(jax.random.PRNGKey(0)), max_slots=2,
                       max_len=32, paged=True, page_size=16)
    with pytest.raises(ValueError, match="expert placement"):
        deng.update_placement()
    deng.close()
