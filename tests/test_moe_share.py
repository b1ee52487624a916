"""An expert layer that holds a share of the experts, as one chip of an
expert-parallel deployment does: it routes over every expert, computes only
its own experts' part, and drops nothing.

The share test ties the layer to the model: the parts that all the shares
give add up to what the uncut layer gives, computed plainly (dense over the
experts, every assignment kept).  The engine tests serve such a share
through the paged path and read its telemetry and spans.
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
from tests.test_serve_trace import read_trace

E, HELD, K = 16, 4, 4
SHARE = smoke_config("qwen3-moe-235b-a22b").replace(
    remat="none", n_experts=E, top_k=K, n_held_experts=HELD)


def _uncut_layer(x, wr, wg, wu, wd, k):
    """The whole routed layer, plainly: softmax over every expert, top-k,
    renormalised gates, every assignment computed."""
    probs = jax.nn.softmax(x @ wr, axis=-1)
    top_p, top_e = jax.lax.top_k(probs, k)
    gate = top_p / top_p.sum(-1, keepdims=True)
    w = (jax.nn.one_hot(top_e, wr.shape[1]) * gate[..., None]).sum(1)
    y = jnp.zeros_like(x)
    for e in range(wr.shape[1]):
        y = y + w[:, e:e + 1] * ((jax.nn.silu(x @ wg[e]) * (x @ wu[e]))
                                 @ wd[e])
    return y


@pytest.mark.parametrize("held", [1, 4, 8])
@pytest.mark.parametrize("tokens", [1, 5, 37])
def test_shares_sum_to_the_uncut_layer(held, tokens):
    cfg = SHARE.replace(n_held_experts=held)
    d, f = cfg.d_model, cfg.expert_d_ff
    ks = jax.random.split(jax.random.PRNGKey(tokens * 10 + held), 5)
    x = jax.random.normal(ks[0], (1, tokens, d), jnp.float32)
    wr = jax.random.normal(ks[1], (d, E), jnp.float32) * 0.3
    wg = jax.random.normal(ks[2], (E, d, f), jnp.float32) * d ** -0.5
    wu = jax.random.normal(ks[3], (E, d, f), jnp.float32) * d ** -0.5
    wd = jax.random.normal(ks[4], (E, f, d), jnp.float32) * f ** -0.5
    total, routed = 0.0, 0
    for lo in range(0, E, held):
        part = cfg.replace(first_held_expert=lo)
        sl = slice(lo, lo + held)
        y, _, stats = M.moe_apply_expert_parallel(
            {"router": wr, "gate": wg[sl], "up": wu[sl], "down": wd[sl]},
            x, part, SerialComm())
        assert int(stats["dropped"].sum()) == 0
        routed = np.asarray(stats["tokens"])      # routing is global
        total = total + y
    assert routed.sum() == tokens * K
    want = _uncut_layer(x[0], wr, wg, wu, wd, K)
    np.testing.assert_allclose(np.asarray(total[0]), np.asarray(want),
                               rtol=1e-5, atol=1e-5)


def test_share_tree_holds_its_experts_and_the_whole_router():
    params = jax.eval_shape(build_model(SHARE).init, jax.random.PRNGKey(0))
    moe = params["blocks"]["moe"]
    L, d, f = SHARE.n_layers, SHARE.d_model, SHARE.expert_d_ff
    assert moe["router"].shape == (L, d, E)
    assert moe["gate"].shape == moe["up"].shape == (L, HELD, d, f)
    assert moe["down"].shape == (L, HELD, f, d)
    with pytest.raises(ValueError, match="not a range"):
        build_model(SHARE.replace(first_held_expert=E - 2)).param_defs()


def _engine(cfg, params, **kw):
    return ServeEngine(build_model(cfg), params, max_slots=16, max_len=96,
                       paged=True, page_size=16, prefill_chunk=32, **kw)


@pytest.mark.parametrize("batch", [1, 4, 16])
def test_share_drops_nothing_when_every_token_picks_expert_0(batch):
    """A zero router ties every expert, so every token picks the first K
    (expert 0 first): all of them land on four held experts at once, past
    any balanced capacity, and none is dropped."""
    model = build_model(SHARE)
    params = model.init(jax.random.PRNGKey(0))
    params["blocks"]["moe"]["router"] = jnp.zeros_like(
        params["blocks"]["moe"]["router"])
    eng = _engine(SHARE, params)
    rng = np.random.default_rng(batch)
    for _ in range(batch):
        eng.submit(rng.integers(0, SHARE.vocab, 40).tolist(),
                   max_new_tokens=4)
    done = eng.run_until_drained()
    eng.close()
    assert len(done) == batch and all(r.error is None for r in done)
    s = eng.stats
    assert s["moe_dropped_tokens"] == 0
    routed = s["moe_tokens_routed"]
    assert routed > 0 and s["expert_tokens"][0] == routed // K
    assert s["moe_local_assignments"] == routed
    assert s["moe_remote_assignments"] == 0
    if batch == 16:
        # a layer that holds every expert keeps its capacity budget: the
        # same routing overflows it
        full = SHARE.replace(n_held_experts=0)
        fp = build_model(full).init(jax.random.PRNGKey(0))
        fp["blocks"]["moe"]["router"] = params["blocks"]["moe"]["router"]
        eng = _engine(full, fp)
        for _ in range(batch):
            eng.submit(rng.integers(0, SHARE.vocab, 40).tolist(),
                       max_new_tokens=4)
        eng.run_until_drained()
        eng.close()
        assert eng.stats["moe_dropped_tokens"] > 0


def test_share_engine_writes_moe_stats_and_splits_assignments(tmp_path):
    cfg = SHARE.replace(first_held_expert=8)
    model = build_model(cfg)
    params = model.init(jax.random.PRNGKey(1))
    eng = _engine(cfg, params)
    with jax.profiler.trace(str(tmp_path)):
        for p in ([5, 17, 33, 2, 9] * 7, [100, 200, 300], [7] * 20):
            eng.submit(p, max_new_tokens=6)
        done = eng.run_until_drained()
    eng.close()
    assert all(r.error is None for r in done)
    spans, _ = read_trace(str(tmp_path))
    # one span a prefill chunk, inside its serve.prefill, and one a decode
    # step, inside its serve.sample
    stats_spans = [(s, e) for n, s, e, _ in spans if n == "serve.moe_stats"]
    outer = [(s, e) for n, s, e, _ in spans
             if n in ("serve.prefill", "serve.sample")]
    assert len(stats_spans) == len(outer) > eng.stats["chunk_prefills"]
    for s, e in stats_spans:
        assert any(os_ <= s and e <= oe for os_, oe in outer)
    s = eng.stats
    per_expert = np.asarray(s["expert_tokens"])
    assert s["moe_local_assignments"] == per_expert[8:12].sum() > 0
    assert s["moe_remote_assignments"] == per_expert.sum() - \
        per_expert[8:12].sum() > 0
    assert s["moe_local_assignments"] + s["moe_remote_assignments"] == \
        s["moe_tokens_routed"]
    assert s["moe_dropped_tokens"] == 0


def test_share_refuses_load_aware_placement():
    model = build_model(SHARE)
    params = model.init(jax.random.PRNGKey(0))
    with pytest.raises(ValueError, match="placement_interval"):
        _engine(SHARE, params, placement_interval=2)
    eng = _engine(SHARE, params)
    with pytest.raises(ValueError, match="holds a share"):
        eng.update_placement()
    eng.close()
