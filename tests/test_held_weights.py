"""Served weights held in the compute dtype: a leaf that every program reads
through a cast to the compute dtype is cast once, when the engine is built,
and the programs compute bit for bit what they computed from the wider
tree; norm scales, LayerNorm biases and the MoE router stay as stored."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import smoke_config
from repro.models.api import build_model
from repro.models.module import hold_params
from repro.serve import ServeEngine


def _noisy_init(model, seed=0):
    """``model.init``'s float32 weights with noise on every leaf, so no leaf
    is bfloat16-valued (norm scales and biases included)."""
    params = model.init(jax.random.PRNGKey(seed))
    leaves, tree = jax.tree_util.tree_flatten(params)
    key = jax.random.PRNGKey(seed + 1)
    return jax.tree_util.tree_unflatten(tree, [
        a + 0.05 * jax.random.normal(jax.random.fold_in(key, i), a.shape)
        for i, a in enumerate(leaves)])


def _names(params, dtype):
    return sorted(jax.tree_util.keystr(path) for path, a
                  in jax.tree_util.tree_leaves_with_path(params)
                  if a.dtype == dtype)


def _same(got, want):
    jax.tree_util.tree_map(np.testing.assert_array_equal, got, want)


def _kept_norms_and_router(path):
    return path[-1] in ("scale", "router")


# arch -> the leaves held as stored (float32): norm scales (rmsnorm, the
# qk-norms), the router; zamba keeps the leaves it declares itself (its
# mamba stacks, embedding and head) and holds only its shared block's
PAGED = ["qwen3-1.7b", "qwen2-7b", "qwen3-moe-235b-a22b",
         "llava-next-mistral-7b"]
KEPT = {arch: _kept_norms_and_router for arch in PAGED + ["gemma3-4b"]}
KEPT["zamba2-7b"] = lambda path: path[-1] == "scale" or path[0] != "shared"


def _pool(eng, key):
    """Pool storage of random values (the program donates it: one a call)."""
    return {name: jax.random.uniform(jax.random.fold_in(key, i), a.shape,
                                     jnp.float32, -1, 1).astype(a.dtype)
            for i, (name, a) in enumerate(sorted(eng.pool.storage.items()))}


def _paged_outputs(eng, params):
    """The decode, 5-token verify and prefill-chunk programs' outputs (the
    chunk's hidden states through the logits head) for ``params``."""
    B, P = eng.sched.table.shape
    n = eng.pool.num_pages
    tables = jnp.asarray(np.arange(B * P).reshape(B, P) % n, jnp.int32)
    lengths = jnp.asarray(np.arange(B) * 4 + 5, jnp.int32)
    pl, key = eng._place_arr, jax.random.PRNGKey(7)
    out = {}
    for name, w in (("decode", 1), ("verify", 5)):
        toks = jnp.asarray(np.arange(B * w).reshape(B, w) * 7 % 200,
                           jnp.int32)
        wp = jnp.asarray(np.arange(B * w).reshape(B, w) % n, jnp.int32)
        wo = jnp.asarray(np.arange(B * w).reshape(B, w) % eng.pool.page_size,
                         jnp.int32)
        if w == 1:
            out[name] = eng._decode_paged(params, _pool(eng, key), tables,
                                          lengths, toks, wp[:, 0], wo[:, 0],
                                          pl)
        else:
            out[name] = eng._verify_paged(params, _pool(eng, key), tables,
                                          lengths, toks, wp, wo, pl)
    C, ps = eng.sched.prefill_chunk, eng.pool.page_size
    toks = jnp.asarray(np.arange(C)[None] * 3 % 200, jnp.int32)
    st, hidden, tel = eng._prefill_chunk(params, _pool(eng, key), tables[0],
                                         tables[0, 1:1 + C // ps],
                                         jnp.int32(ps), toks, pl)
    out["prefill"] = (st, eng._lm_head(params, hidden), tel)
    return out


def _dense_outputs(eng, params):
    """The dense path's prefill and decode programs' outputs."""
    toks = jnp.asarray([[3, 5, 7, 9, 11, 13, 2, 4]] * eng.max_slots,
                       jnp.int32)
    return {"prefill": eng._prefill(params, {"tokens": toks}),
            "decode": eng._decode(params, eng._fresh_state(), toks[:, :1],
                                  jnp.int32(8))}


@pytest.mark.parametrize("arch", list(KEPT))
def test_held_weights_compute_the_same_logits(arch):
    """Every program the engine runs on the tree it holds gives, bit for
    bit, what it gives on the float32 tree it was handed; the leaves kept
    in float32 are exactly the ones read in float32.  A leaf declared read
    as the compute dtype but read wider somewhere fails here."""
    cfg = smoke_config(arch).replace(remat="none", dtype="bfloat16")
    model = build_model(cfg)
    params = _noisy_init(model)
    paged = arch in PAGED
    kw = dict(page_size=8, prefill_chunk=16) if paged else dict(paged=False)
    eng = ServeEngine(model, params, max_slots=3, max_len=64, **kw)
    held = eng.params
    kept = sorted(jax.tree_util.keystr(path) for path, _
                  in jax.tree_util.tree_leaves_with_path(params)
                  if KEPT[arch]([k.key for k in path]))
    assert _names(held, jnp.float32) == kept
    assert _names(held, jnp.bfloat16) and not set(_names(
        held, jnp.bfloat16)) & set(kept)
    run = _paged_outputs if paged else _dense_outputs
    _same(run(eng, held), run(eng, params))
    eng.close()


def _bytes(tree):
    return sum(a.size * a.dtype.itemsize
               for a in jax.tree_util.tree_leaves(tree))


def test_weight_counters_read_the_bytes_held_and_cast():
    """``weight_bytes`` is the bytes the engine holds; ``weight_bytes_cast``
    the bytes of those held in the compute dtype after arriving wider:
    every leaf but the norm scales for a float32 dense tree served in
    bfloat16, none for a tree stored in bfloat16 or served in float32."""
    cfg = smoke_config("qwen3-1.7b").replace(remat="none",
                                             dtype="bfloat16")
    model = build_model(cfg)
    params = model.init(jax.random.PRNGKey(0))
    scales = [a for path, a in jax.tree_util.tree_leaves_with_path(params)
              if path[-1].key == "scale"]
    eng = ServeEngine(model, params, max_slots=2, max_len=32, page_size=8)
    cast = (_bytes(params) - _bytes(scales)) // 2
    assert eng.stats["weight_bytes_cast"] == cast > 0
    assert eng.stats["weight_bytes"] == _bytes(scales) + cast \
        == _bytes(eng.params)
    eng.close()

    stored_bf16 = model.init(jax.random.PRNGKey(0), dtype=jnp.bfloat16)
    f32_compute = build_model(cfg.replace(dtype="float32"))
    for m, p in ((model, stored_bf16), (f32_compute, params)):
        eng = ServeEngine(m, p, max_slots=2, max_len=32, page_size=8)
        assert eng.stats["weight_bytes_cast"] == 0
        assert eng.stats["weight_bytes"] == _bytes(p)
        assert eng.params is p
        eng.close()


def test_int8_weights_are_not_held_in_the_compute_dtype():
    """The int8 path keeps its payload and its dequant-on-apply: nothing is
    cast, and the counter reads the payload's bytes."""
    cfg = smoke_config("qwen2-7b").replace(remat="none", dtype="bfloat16")
    model = build_model(cfg)
    eng = ServeEngine(model, model.init(jax.random.PRNGKey(0)),
                      max_slots=2, max_len=32, page_size=8,
                      weight_quant="int8")
    assert eng.stats["weight_bytes_cast"] == 0
    assert eng.stats["weight_bytes"] == _bytes(eng.params)
    eng.close()


def test_shapes_are_held_as_shapes():
    """A tree of shapes (what compile checks serve) is held as shapes of the
    held dtypes, on the same shardings."""
    cfg = smoke_config("qwen3-moe-235b-a22b").replace(remat="none",
                                                      dtype="bfloat16")
    model = build_model(cfg)
    params = model.init(jax.random.PRNGKey(0))
    shapes = jax.eval_shape(lambda: params)
    held, cast = hold_params(model.param_defs(), params, cfg.dtype)
    held_shapes, cast_shapes = hold_params(model.param_defs(), shapes,
                                           cfg.dtype)
    assert cast == cast_shapes > 0
    assert all(isinstance(a, jax.ShapeDtypeStruct)
               for a in jax.tree_util.tree_leaves(held_shapes))
    assert jax.tree_util.tree_map(lambda a: (a.shape, a.dtype), held) \
        == jax.tree_util.tree_map(lambda a: (a.shape, a.dtype), held_shapes)


def test_replacement_keeps_held_dtypes_and_programs():
    """Re-placing the experts of an engine that holds all of them permutes
    the held tree: the dtypes stay the held ones, and no program is lowered
    again."""
    cfg = smoke_config("qwen3-moe-235b-a22b").replace(remat="none",
                                                      dtype="bfloat16")
    model = build_model(cfg)
    eng = ServeEngine(model, model.init(jax.random.PRNGKey(0)), max_slots=3,
                      max_len=64, page_size=8, prefill_chunk=16)
    dtypes = jax.tree_util.tree_map(lambda a: a.dtype, eng.params)
    progs = (eng._decode_paged, eng._prefill_chunk, eng._lm_head)

    def serve():
        for p in ([5, 17, 33, 2, 9], [100, 200, 300], [7] * 11):
            eng.submit(p, max_new_tokens=4)
        done = eng.run_until_drained()
        assert all(r.error is None for r in done)

    serve()
    compiled = [f._cache_size() for f in progs]
    assert eng.update_placement() is not None
    assert jax.tree_util.tree_map(lambda a: a.dtype, eng.params) == dtypes
    serve()
    assert [f._cache_size() for f in progs] == compiled
    eng.close()
