"""Paged serving subsystem: page pool, scheduler policy, and token-for-token
parity of the paged engine against the dense-cache engine across families."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import smoke_config
from repro.models.api import build_model
from repro.serve import PagePool, PagedLeafSpec, ServeEngine
from repro.serve import pages as PG
from repro.serve.scheduler import Scheduler


# ---------------------------------------------------------------------------
# PagePool host accounting
# ---------------------------------------------------------------------------

def _tiny_pool(num_pages=4, page_size=8):
    specs = {"k": PagedLeafSpec((2,), (3, 4), jnp.float32)}
    return PagePool(specs, num_pages=num_pages, page_size=page_size)


def test_pool_storage_shapes_include_trash_page():
    pool = _tiny_pool(num_pages=4, page_size=8)
    assert pool.storage["k"].shape == (2, 5, 8, 3, 4)   # 4 pages + trash
    assert pool.trash_page == 4


def test_pool_alloc_free_and_high_water():
    pool = _tiny_pool(num_pages=4)
    a = pool.alloc(3)
    assert a == [0, 1, 2] and pool.pages_in_use == 3
    assert pool.alloc(2) is None            # all-or-nothing: 1 < 2 stays put
    assert pool.pages_in_use == 3
    b = pool.alloc(1)
    assert b == [3] and pool.high_water == 4
    pool.free(a)
    assert pool.pages_in_use == 1 and pool.high_water == 4
    c = pool.alloc(3)                       # FIFO recycling is deterministic
    assert c == [0, 1, 2]


def test_scatter_gather_roundtrip():
    rng = np.random.default_rng(0)
    storage = jnp.zeros((5, 4, 2, 3))                   # (N=5, ps=4, suffix)
    chunk = jnp.asarray(rng.normal(size=(8, 2, 3)), jnp.float32)
    storage = PG.scatter_chunk(storage, jnp.asarray([3, 1]), chunk,
                               page_size=4)
    tok = jnp.asarray(rng.normal(size=(1, 2, 3)), jnp.float32)
    storage = PG.scatter_token(storage, jnp.asarray([1]), jnp.asarray([2]),
                               tok)
    got = PG.gather_pages(storage, jnp.asarray([[3, 1]]))
    want = np.asarray(chunk).copy()
    want[4 + 2] = np.asarray(tok[0])        # token landed in page 1, slot 2
    np.testing.assert_allclose(np.asarray(got[0]), want)


# ---------------------------------------------------------------------------
# Scheduler policy (host-only, no device work)
# ---------------------------------------------------------------------------

class _Req:
    def __init__(self, rid, n):
        self.rid, self.prompt, self.output = rid, np.arange(n, dtype=np.int32), []


def test_scheduler_admission_reserves_pages_all_or_nothing():
    pool = _tiny_pool(num_pages=4, page_size=8)
    s = Scheduler(max_slots=2, max_len=32, pool=pool, prefill_chunk=8)
    s.submit(_Req(0, 20))                   # ceil(21/8) = 3 pages
    s.submit(_Req(1, 20))
    admits, rejects = s.admit()
    assert [slot for slot, _ in admits] == [0] and not rejects
    assert pool.pages_in_use == 3
    assert len(s.queue) == 1                # head blocks until pages drain
    s.release(0)
    assert pool.pages_in_use == 0
    admits, _ = s.admit()
    assert [slot for slot, _ in admits] == [0]


def test_scheduler_chunks_are_page_aligned_and_interleaved():
    pool = _tiny_pool(num_pages=8, page_size=8)
    s = Scheduler(max_slots=2, max_len=64, pool=pool, prefill_chunk=16,
                  chunks_per_tick=2)
    s.submit(_Req(0, 30))                   # padded 32 -> chunks 16+16
    s.submit(_Req(1, 10))                   # padded 16 -> one chunk
    s.admit()
    jobs = s.next_chunks()
    assert [(j.slot, j.start, len(j.tokens)) for j in jobs] == [
        (0, 0, 16), (1, 0, 16)]             # round-robin across slots
    assert not jobs[0].is_last and jobs[1].is_last
    assert jobs[1].n_valid == 10            # right-padded to the page grid
    for j in jobs:
        s.chunk_done(j)
    jobs = s.next_chunks()
    assert [(j.slot, j.start, j.is_last) for j in jobs] == [(0, 16, True)]
    s.chunk_done(jobs[0])
    assert s.live_slots() == [0, 1]
    assert int(s.lengths[0]) == 30 and int(s.lengths[1]) == 10


def test_scheduler_preempts_youngest_on_exhaustion():
    pool = _tiny_pool(num_pages=4, page_size=8)
    s = Scheduler(max_slots=2, max_len=32, pool=pool, prefill_chunk=8)
    s.submit(_Req(0, 14))                   # 2 pages
    s.submit(_Req(1, 14))                   # 2 pages
    s.admit()
    for _ in range(2):
        for j in s.next_chunks():
            s.chunk_done(j)
    assert s.live_slots() == [0, 1] and pool.pages_in_use == 4
    s.lengths[0] = 16                       # slot 0 crosses a page boundary
    preempted, cow, _ = s.ensure_decode_pages()
    assert [slot for slot, _ in preempted] == [1]   # youngest admitted
    assert cow == []                        # exclusive pages: no copies
    assert s.status[1] == "free" and len(s.queue) == 1
    assert s.queue[0].rid == 1              # requeued at the head
    assert int(s.n_pages[0]) == 3           # slot 0 got its page


def test_scheduler_single_resident_exhaustion_raises():
    pool = _tiny_pool(num_pages=4, page_size=8)
    s = Scheduler(max_slots=2, max_len=32, pool=pool)
    s.submit(_Req(0, 14))
    s.admit()
    for j in s.next_chunks():
        s.chunk_done(j)
    pool.alloc(2)                           # drain the pool externally
    s.lengths[0] = 16
    with pytest.raises(RuntimeError, match="page pool exhausted"):
        s.ensure_decode_pages()


def test_scheduler_pool_too_small_for_max_len():
    pool = _tiny_pool(num_pages=2, page_size=8)
    with pytest.raises(ValueError, match="cannot hold one"):
        Scheduler(max_slots=2, max_len=32, pool=pool)   # needs 4 pages


# ---------------------------------------------------------------------------
# Engine parity: paged == dense == aligned reference, across families
# ---------------------------------------------------------------------------

PROMPTS = [[5, 17, 33, 2, 9], [100, 200, 300], [7] * 11]


def _run(model, params, paged, **kw):
    eng = ServeEngine(model, params, max_slots=3, max_len=128, paged=paged,
                      **kw)
    for p in PROMPTS:
        eng.submit(p, max_new_tokens=6)
    done = eng.run_until_drained()
    eng.close()
    return {r.rid: r.output for r in done}, eng


@pytest.fixture(scope="module", params=["qwen2-7b", "qwen3-moe-235b-a22b"])
def family(request):
    cfg = smoke_config(request.param).replace(remat="none")
    model = build_model(cfg)
    return model, model.init(jax.random.PRNGKey(0))


def test_paged_engine_token_parity(family):
    """Dense-cache engine and paged engine emit identical greedy streams
    (dense + MoE families; chunked prefill exercised via a small chunk)."""
    model, params = family
    dense, _ = _run(model, params, False)
    paged, eng = _run(model, params, True, page_size=16, prefill_chunk=16)
    assert dense == paged
    # the headline win: pages in use stayed far below the dense reservation
    dense_pages = 3 * 128 // 16
    assert eng.pool.high_water < dense_pages // 2


def test_paged_engine_parity_under_preemption():
    """A pool sized at the single-request minimum forces preemption; the
    recompute policy keeps greedy output streams bit-identical."""
    cfg = smoke_config("qwen2-7b").replace(remat="none")
    model = build_model(cfg)
    params = model.init(jax.random.PRNGKey(0))

    def go(paged, **kw):
        eng = ServeEngine(model, params, max_slots=2, max_len=64,
                          paged=paged, **kw)
        eng.submit([5, 17, 33, 2, 9, 1, 2, 3], max_new_tokens=30)
        eng.submit([100, 200, 300, 4, 5, 6, 7, 8], max_new_tokens=30)
        done = eng.run_until_drained()
        eng.close()
        return {r.rid: r.output for r in done}, eng

    want, _ = go(False)
    got, eng = go(True, page_size=16, num_pages=4, prefill_chunk=16)
    assert got == want
    assert eng.stats["preemptions"] >= 1


def test_paged_engine_pallas_kernel_parity(family, monkeypatch):
    """The fused multi-query kernel behind prefill + decode
    (use_pallas_attention=True) emits streams identical to the jnp
    gather-fallback engine, dense + MoE, prefix cache on and off — and the
    kernel path never touches ``gather_pages``: the whole point is that the
    page gather happens on-chip via the prefetched table, so HBM
    materialization of the cache would be a silent perf regression."""
    model, params = family
    for prefix_cache in (False, True):
        want, _ = _run(model, params, True, page_size=16, prefill_chunk=16,
                       prefix_cache=prefix_cache)
        real = PG.gather_pages
        calls = []

        def counting(storage, tables, *, n_prefix=0):
            calls.append(tables.shape)
            return real(storage, tables, n_prefix=n_prefix)

        monkeypatch.setattr(PG, "gather_pages", counting)
        got, _ = _run(model, params, True, page_size=16, prefill_chunk=16,
                      prefix_cache=prefix_cache, use_pallas_attention=True)
        monkeypatch.undo()
        assert got == want, prefix_cache
        assert calls == [], calls           # no HBM gather on the hot path


def test_paged_engine_pallas_parity_under_preemption():
    """Forced preemption + recompute with the kernel on: streams stay
    bit-identical to the kernel-off run and the pool is conserved."""
    cfg = smoke_config("qwen2-7b").replace(remat="none")
    model = build_model(cfg)
    params = model.init(jax.random.PRNGKey(0))

    def go(**kw):
        eng = ServeEngine(model, params, max_slots=2, max_len=64, paged=True,
                          page_size=16, num_pages=4, prefill_chunk=16, **kw)
        eng.submit([5, 17, 33, 2, 9, 1, 2, 3], max_new_tokens=30)
        eng.submit([100, 200, 300, 4, 5, 6, 7, 8], max_new_tokens=30)
        done = eng.run_until_drained()
        eng.close()
        return {r.rid: r.output for r in done}, eng

    want, eng_off = go()
    got, eng_on = go(use_pallas_attention=True)
    assert eng_off.stats["preemptions"] >= 1
    assert eng_on.stats["preemptions"] >= 1
    assert got == want
    pool = eng_on.pool
    assert pool.pages_free + pool.pages_cached == pool.num_pages


def test_pallas_attention_flag_validated_at_construction():
    """use_pallas_attention is checked once in __init__: a paged-capable
    family forced to paged=False and a recurrent family (no paged KV cache,
    ever) both fail fast with an error naming the family — not mid-tick
    inside a jitted call."""
    cfg = smoke_config("qwen2-7b").replace(remat="none")
    model = build_model(cfg)
    params = model.init(jax.random.PRNGKey(0))
    with pytest.raises(ValueError, match="paged=False"):
        ServeEngine(model, params, paged=False, use_pallas_attention=True)

    rcfg = smoke_config("rwkv6-3b").replace(remat="none")
    rmodel = build_model(rcfg)
    rparams = rmodel.init(jax.random.PRNGKey(0))
    with pytest.raises(ValueError, match="recurrent/window"):
        ServeEngine(rmodel, rparams, use_pallas_attention=True)


def test_pallas_attention_default_is_the_gather_on_cpu():
    """Unset, the paged engine attends through the jnp gather off a TPU."""
    cfg = smoke_config("qwen2-7b").replace(remat="none")
    model = build_model(cfg)
    params = model.init(jax.random.PRNGKey(0))
    assert jax.default_backend() == "cpu"
    eng = ServeEngine(model, params, max_slots=2, max_len=64, paged=True)
    assert eng.use_pallas_attention is False
    eng.close()


def test_pallas_attention_default_is_the_kernel_on_tpu(monkeypatch):
    """Unset on a TPU, the paged engine takes the kernel; a family with no
    paged KV cache stays on its dense path instead of being refused."""
    cfg = smoke_config("qwen2-7b").replace(remat="none")
    model = build_model(cfg)
    params = model.init(jax.random.PRNGKey(0))
    rcfg = smoke_config("rwkv6-3b").replace(remat="none")
    rmodel = build_model(rcfg)
    rparams = rmodel.init(jax.random.PRNGKey(0))
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    eng = ServeEngine(model, params, max_slots=2, max_len=64, paged=True)
    assert eng.use_pallas_attention is True
    eng.close()
    dense = ServeEngine(model, params, max_slots=2, max_len=64, paged=False)
    assert dense.use_pallas_attention is False
    dense.close()
    rec = ServeEngine(rmodel, rparams, max_slots=2, max_len=64)
    assert rec.use_pallas_attention is False
    rec.close()


@pytest.mark.parametrize("backend", ["cpu", "tpu"])
@pytest.mark.parametrize("flag", [False, True])
def test_pallas_attention_explicit_flag_wins(monkeypatch, backend, flag):
    cfg = smoke_config("qwen2-7b").replace(remat="none")
    model = build_model(cfg)
    params = model.init(jax.random.PRNGKey(0))
    monkeypatch.setattr(jax, "default_backend", lambda: backend)
    eng = ServeEngine(model, params, max_slots=2, max_len=64, paged=True,
                      use_pallas_attention=flag)
    assert eng.use_pallas_attention is flag
    eng.close()


def test_decode_live_pages_counts_each_live_slots_pages():
    """``decode_live_pages`` sums ceil(len / page_size) over live slots
    (len: the positions the slot's window attends to), tick by tick; a
    dead slot's stale length counts nothing, and ``decode_table_pages``
    counts the whole table every step."""
    cfg = smoke_config("qwen2-7b").replace(remat="none")
    model = build_model(cfg)
    params = model.init(jax.random.PRNGKey(0))
    ps = 8
    eng = ServeEngine(model, params, max_slots=3, max_len=64, paged=True,
                      page_size=ps, spec_decode="ngram", spec_k=3)
    table = eng.sched.table.size
    assert table == 3 * 64 // ps
    # (live slots, cached lengths, drafts) of three hand-built steps; slot 1
    # holds a stale length throughout and is never live
    ticks = [([0, 2], {0: 7, 2: 16}, {}),
             ([0, 2], {0: 8, 2: 17}, {}),
             ([2], {2: 40}, {2: [4, 5, 6]})]
    want = 0
    for n, (live, lens, drafts) in enumerate(ticks, 1):
        eng.sched.lengths[:] = 0
        eng.sched.lengths[1] = 50
        for slot, ln in lens.items():
            eng.sched.lengths[slot] = ln
        _, _, got = eng._step_inputs(live, drafts, [], bool(drafts))
        step = sum(-(-(lens[s] + 1 + len(drafts.get(s, ()))) // ps)
                   for s in live)
        assert got == step
        want += step
        assert eng.stats["decode_live_pages"] == want
        assert eng.stats["decode_table_pages"] == n * table
    # 8 and 17 attended positions, then 9 and 18, then 40 + 4
    assert want == (1 + 3) + (2 + 3) + 6
    eng.close()


def test_recurrent_family_keeps_dense_path():
    """rwkv6 has O(1) decode state — the engine auto-selects the dense slot
    path and still matches itself run-to-run; paged=True is refused."""
    cfg = smoke_config("rwkv6-3b").replace(remat="none")
    model = build_model(cfg)
    params = model.init(jax.random.PRNGKey(0))
    assert not model.supports_paged_decode()
    with pytest.raises(ValueError, match="no paged KV cache"):
        ServeEngine(model, params, paged=True)
    a, enga = _run(model, params, None)
    assert not enga.paged
    b, _ = _run(model, params, None)
    assert a == b and len(a) == 3


def test_chunked_prefill_keeps_decode_flowing():
    """While a long prompt prefills chunk-by-chunk, an already-live request
    keeps emitting tokens every tick (the anti-stall property)."""
    cfg = smoke_config("qwen2-7b").replace(remat="none")
    model = build_model(cfg)
    params = model.init(jax.random.PRNGKey(0))
    eng = ServeEngine(model, params, max_slots=2, max_len=128, paged=True,
                      page_size=16, prefill_chunk=16, chunks_per_tick=1)
    eng.submit([9, 8, 7], max_new_tokens=24)
    eng.run_until_drained(max_ticks=2)          # short request is live
    short = eng.sched.slot_req[0]
    eng.submit(list(range(1, 100)), max_new_tokens=4)   # 99 tokens: 7 chunks
    n0 = len(short.output)
    for _ in range(6):                          # six ticks of chunked prefill
        eng.tick()
    n1 = len(short.output)
    assert n1 - n0 == 6                         # one token per tick, no stall
    long_req = eng.sched.slot_req[1]
    assert long_req is not None and not long_req.output   # still prefilling
    done = eng.run_until_drained()
    eng.close()
    by_len = {len(r.prompt): r for r in done}
    assert len(by_len[3].output) == 24 and len(by_len[99].output) == 4
    assert eng.stats["chunk_prefills"] >= 7


# ---------------------------------------------------------------------------
# Prefill-failure page accounting (regression: a request that errors
# mid-chunked-prefill must hand every reserved page back to the pool)
# ---------------------------------------------------------------------------

def _pool_conserved(eng):
    """free + cached-unreferenced + held partitions the pool, and the
    slots' table references account for every refcount."""
    pool = eng.pool
    return (pool.pages_free + pool.pages_cached + pool.pages_in_use
            == pool.num_pages
            and eng.sched.held_pages()
            == sum(pool.ref(p) for p in range(pool.num_pages)))


def test_prefill_sampler_failure_returns_pages():
    """The last-chunk lm-head/sampler path is error-isolated too: a sampler
    that raises on the first token retires the request with ``req.error``,
    frees its pages, and never stalls the other requests."""
    cfg = smoke_config("qwen2-7b").replace(remat="none")
    model = build_model(cfg)
    params = model.init(jax.random.PRNGKey(0))
    eng = ServeEngine(model, params, max_slots=2, max_len=128, paged=True,
                      page_size=16, prefill_chunk=16)

    def bad_sampler(key, logits):
        raise RuntimeError("sampler exploded")

    eng.submit(list(range(1, 40)), max_new_tokens=4, sampler=bad_sampler)
    eng.submit([1, 2, 3], max_new_tokens=3)
    done = eng.run_until_drained()
    eng.close()
    assert _pool_conserved(eng) and eng.pool.pages_in_use == 0
    bad = [r for r in done if r.error is not None]
    good = [r for r in done if r.error is None]
    assert len(bad) == 1 and "sampler exploded" in str(bad[0].error)
    assert len(good) == 1 and len(good[0].output) == 3


def test_prefill_device_failure_mid_chunk_returns_pages():
    """An error in the Nth prefill chunk's device call releases the slot's
    whole reservation (pool invariant holds every tick)."""
    cfg = smoke_config("qwen2-7b").replace(remat="none")
    model = build_model(cfg)
    params = model.init(jax.random.PRNGKey(0))
    eng = ServeEngine(model, params, max_slots=2, max_len=128, paged=True,
                      page_size=16, prefill_chunk=16, chunks_per_tick=1)
    orig, calls = eng._prefill_chunk, {"n": 0}

    def flaky(*a, **kw):
        calls["n"] += 1
        if calls["n"] == 2:
            raise RuntimeError("injected chunk failure")
        return orig(*a, **kw)

    eng._prefill_chunk = flaky
    eng.submit(list(range(1, 50)), max_new_tokens=4)    # 49 tokens: 4 chunks
    while eng.tick():
        assert _pool_conserved(eng)
    eng.close()
    assert eng.pool.pages_in_use == 0
    (req,) = eng.finished
    assert req.error is not None and not req.output


def test_prefill_failure_with_donated_storage_recovers():
    """The engine donates the pool storage into the jitted calls, so a
    call that raises may already have CONSUMED the buffers.  The engine
    must detect that, evict residents (recompute flavor) and rebuild zeroed
    storage — the surviving request's greedy stream still matches an
    unfailed run token for token."""
    cfg = smoke_config("qwen2-7b").replace(remat="none")
    model = build_model(cfg)
    params = model.init(jax.random.PRNGKey(0))

    def run(inject):
        eng = ServeEngine(model, params, max_slots=2, max_len=128,
                          paged=True, page_size=16, prefill_chunk=16,
                          chunks_per_tick=1)
        if inject:
            orig, calls = eng._prefill_chunk, {"n": 0}

            def flaky(*a, **kw):
                calls["n"] += 1
                if calls["n"] == 3:
                    for leaf in jax.tree_util.tree_leaves(eng.pool.storage):
                        leaf.delete()       # simulate consumed donation
                    raise RuntimeError("injected donated failure")
                return orig(*a, **kw)

            eng._prefill_chunk = flaky
        eng.submit([9, 8, 7, 6], max_new_tokens=6)       # resident victim
        eng.submit(list(range(1, 40)), max_new_tokens=4)  # fails mid-prefill
        done = eng.run_until_drained()
        eng.close()
        assert _pool_conserved(eng)
        assert eng.pool.pages_in_use == 0
        assert not eng.pool.storage_deleted()
        return {len(r.prompt): (r.output, r.error is not None) for r in done}

    want = run(False)
    got = run(True)
    assert got[39][1] and not got[39][0]         # failed request, no output
    assert not want[39][1]
    assert got[4] == want[4]                     # victim's stream unchanged


def test_decode_sampler_failure_is_isolated():
    """A per-request sampler that works for the first token but raises on a
    later decode tick retires only that request (req.error set, pages
    freed); the other live slots keep decoding."""
    cfg = smoke_config("qwen2-7b").replace(remat="none")
    model = build_model(cfg)
    params = model.init(jax.random.PRNGKey(0))
    eng = ServeEngine(model, params, max_slots=2, max_len=128, paged=True,
                      page_size=16, prefill_chunk=16)
    calls = {"n": 0}

    def flaky_sampler(key, logits):
        calls["n"] += 1
        if calls["n"] >= 3:
            raise RuntimeError("sampler died mid-decode")
        return jnp.argmax(logits).astype(jnp.int32)

    eng.submit([5, 17, 33], max_new_tokens=10, sampler=flaky_sampler)
    eng.submit([1, 2, 3], max_new_tokens=10)
    done = eng.run_until_drained()
    eng.close()
    assert eng.pool.pages_in_use == 0
    bad = [r for r in done if r.error is not None]
    good = [r for r in done if r.error is None]
    assert len(bad) == 1 and "mid-decode" in str(bad[0].error)
    assert 1 <= len(bad[0].output) < 10          # died after emitting some
    assert len(good) == 1 and len(good[0].output) == 10


def test_decode_failure_with_donated_storage_recovers():
    """A decode-tick failure still raises (engine-level), but if the
    raising call consumed the donated storage the engine recovers first:
    residents are evicted recompute-style, so simply ticking on completes
    every stream bit-identically to an unfailed run."""
    cfg = smoke_config("qwen2-7b").replace(remat="none")
    model = build_model(cfg)
    params = model.init(jax.random.PRNGKey(0))

    def run(inject):
        eng = ServeEngine(model, params, max_slots=2, max_len=128,
                          paged=True, page_size=16, prefill_chunk=16)
        if inject:
            orig, calls = eng._decode_paged, {"n": 0}

            def flaky(*a, **kw):
                calls["n"] += 1
                if calls["n"] == 2:
                    for leaf in jax.tree_util.tree_leaves(eng.pool.storage):
                        leaf.delete()
                    raise RuntimeError("injected decode failure")
                return orig(*a, **kw)

            eng._decode_paged = flaky
        eng.submit([9, 8, 7, 6], max_new_tokens=6)
        eng.submit([5, 4, 3], max_new_tokens=6)
        if inject:
            with pytest.raises(RuntimeError, match="injected"):
                eng.run_until_drained()
            assert not eng.pool.storage_deleted()    # recovered already
        done = eng.run_until_drained()
        eng.close()
        assert _pool_conserved(eng)
        return {len(r.prompt): r.output for r in done}, eng

    want, _ = run(False)
    got, eng = run(True)
    assert got == want
    assert eng.stats["preemptions"] >= 1


def test_paged_state_specs_match_pool_storage():
    cfg = smoke_config("qwen2-7b").replace(remat="none")
    model = build_model(cfg)
    pool = PagePool(model.paged_leaf_specs(), num_pages=6, page_size=16)
    specs = model.paged_state_specs(6, 16)
    shapes = jax.tree_util.tree_map(lambda a: a.shape, pool.storage)
    spec_shapes = jax.tree_util.tree_map(
        lambda s: s.shape, specs, is_leaf=lambda x: hasattr(x, "spec"))
    assert shapes == spec_shapes


# ---------------------------------------------------------------------------
# The stacked pool through the layer scan: each program writes only its rows
# ---------------------------------------------------------------------------

PAGE, N_POOL, P_TABLE = 4, 12, 4           # pool: 12 pages + trash page 12


def _phase_inputs(phase):
    """One call's inputs for 3 slots (slot 2 dead) over tables of 4 pages:
    the program's arguments and the (page, offset) rows it writes."""
    trash = N_POOL
    tables = np.array([[3, 7, 1, 9], [0, 4, 10, 2], [trash] * P_TABLE],
                      np.int32)
    if phase == "prefill":
        start, C = 4, 8                    # pages 1 and 2 of slot 0's table
        pages = tables[0, start // PAGE:(start + C) // PAGE]
        tokens = np.arange(1, C + 1, dtype=np.int32)[None] * 7 % 500
        rows = [(int(pg), o) for pg in pages for o in range(PAGE)]
        return (tables[0], pages, np.int32(start), tokens), rows
    lengths = np.array([5, 9, 0], np.int32)
    C = 1 if phase == "decode" else 4
    pos = lengths[:, None] + np.arange(C)
    # slot 1's window ends in padding (positions past its 2 real tokens)
    real = np.array([[True] * C, [True, True] + [False] * (C - 2)
                     if C > 1 else [True], [False] * C])
    wp = np.where(real, np.take_along_axis(tables, pos // PAGE, 1), trash)
    wo = np.where(real, pos % PAGE, np.arange(C) % PAGE)
    tokens = (np.arange(3 * C, dtype=np.int32).reshape(3, C) * 11 + 3) % 500
    rows = sorted({(int(p), int(o)) for p, o in zip(wp.ravel(), wo.ravel())})
    if phase == "decode":
        wp, wo = wp[:, 0], wo[:, 0]
    return (tables, lengths, tokens, wp.astype(np.int32),
            wo.astype(np.int32)), rows


def _random_pool(model, quant, key):
    """Pool storage filled with random values, so a stray write shows."""
    specs = model.paged_state_specs(N_POOL, PAGE, quant)
    leaves, tree = jax.tree_util.tree_flatten(
        specs, is_leaf=lambda x: hasattr(x, "spec"))
    out = []
    for i, s in enumerate(leaves):
        k = jax.random.fold_in(key, i)
        if jnp.issubdtype(s.dtype, jnp.integer):
            out.append(jax.random.randint(k, s.shape, -127, 128, s.dtype))
        else:
            out.append(jax.random.uniform(k, s.shape, s.dtype, 0.5, 1.5))
    return jax.tree_util.tree_unflatten(tree, out)


def _per_layer_slice_program(model, phase, params, storage, args, quant,
                             use_pallas):
    """The paged program with the pool as the layer scan's xs/ys: each
    layer's slice of the pool goes in, is written and comes back out."""
    from repro.models import layers as L
    from repro.models import transformer as T
    cfg = model.cfg
    if phase == "prefill":
        row, pages, start, tokens = args
        C = tokens.shape[1]
        positions, tables, q_offset = start + jnp.arange(C), row[None], start

        def scatter(st, val):
            return PG.scatter_chunk(st, pages, val[0], page_size=PAGE)
    else:
        tables, lengths, tokens, wp, wo = args
        C = tokens.shape[1]
        positions, q_offset = lengths[:, None] + jnp.arange(C), lengths
        if phase == "decode":
            def scatter(st, val):
                return PG.scatter_token(st, wp, wo, val[:, 0])
        else:
            def scatter(st, val):
                return PG.scatter_window(st, wp, wo, val)

    def write(kv, k, v):
        return T._write_kv(kv, k, v, quant, scatter)

    def body(carry, xs):
        x, tok, drp = carry
        p, kv = xs
        x, kv, ms = T._paged_block(p, x, cfg, None, positions=positions,
                                   kv=kv, tables=tables, q_offset=q_offset,
                                   write=write, use_pallas=use_pallas)
        return (x, tok + ms["tokens"], drp + ms["dropped"]), kv

    z = jnp.zeros((cfg.n_experts,), jnp.int32)
    x = T.embed_tokens(params, tokens, cfg, None)
    (x, tok, drp), storage = jax.lax.scan(body, (x, z, z),
                                          (params["blocks"], storage))
    x = L.rmsnorm(params["final_norm"], x, use_pallas=cfg.use_pallas)
    if phase != "prefill":
        x = T.lm_logits(params, x, cfg, None)
    return storage, x, {"expert_tokens": tok, "expert_dropped": drp}


@pytest.mark.parametrize("kv_quant", ["off", "int8"])
@pytest.mark.parametrize("phase", ["decode", "verify", "prefill"])
@pytest.mark.parametrize("attention,head_dim", [
    ("gather", 16), ("kernel", 128), ("kernel", 16)],
    ids=["gather", "kernel", "kernel-narrow-head"])
def test_paged_program_writes_only_its_rows(phase, kv_quant, attention,
                                            head_dim):
    """One call of a paged program changes only the (layer, page, offset)
    rows it targets, in every layer (the layer's own trash page for dead
    and pad positions); every other row of every layer stays bitwise the
    same.  Its outputs equal the per-layer-slice formulation's, bit for
    bit: with the gather, and with the kernel reading the stacked pool at
    head_dim 128 and at head_dim 16 (which the kernel pads to lanes)."""
    from repro.serve.quant import make_kv_quant
    cfg = smoke_config("qwen2-7b").replace(remat="none", n_layers=3,
                                           head_dim=head_dim)
    model = build_model(cfg)
    params = model.init(jax.random.PRNGKey(0))
    quant = make_kv_quant(kv_quant)
    use_pallas = attention == "kernel"
    args, rows = _phase_inputs(phase)
    args = tuple(jnp.asarray(a) for a in args)
    before = _random_pool(model, quant, jax.random.PRNGKey(1))
    if phase == "decode":
        # the one-token verify window, (B,) write targets as the engine has
        def program(p, st, tb, ln, t, wp, wo, rules, **kw):
            return model.paged_verify(p, st, tb, ln, t, wp[:, None],
                                      wo[:, None], rules, **kw)
    else:
        program = {"verify": model.paged_verify,
                   "prefill": model.paged_prefill_chunk}[phase]
    got = jax.jit(lambda p, st, *a: program(
        p, st, *a, None, use_pallas=use_pallas, quant=quant))(
        params, before, *args)
    want = jax.jit(lambda p, st, *a: _per_layer_slice_program(
        model, phase, p, st, a, quant, use_pallas))(params, before, *args)
    jax.tree_util.tree_map(np.testing.assert_array_equal, got, want)

    pages, offs = (np.array(c) for c in zip(*rows))
    for name, leaf in got[0].items():
        new, old = np.asarray(leaf), np.asarray(before[name])
        assert new.shape[:2] == (cfg.n_layers, N_POOL + 1), name
        changed = np.any((new != old).reshape(new.shape[:3] + (-1,)), -1)
        target = np.zeros_like(changed)
        target[:, pages, offs] = True
        assert not np.any(changed & ~target), (name, np.argwhere(changed
                                                                 & ~target))
        # every layer wrote its own rows (the trash page's too)
        assert np.all(changed[:, pages, offs]), name
