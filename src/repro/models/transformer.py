"""Decoder-only LM (dense + MoE variants) with scan-over-layers.

Distribution scheme: batch->data(+pod), sequence->model
(context parallelism; KV all-gathered, cheap under GQA), MLP/vocab/experts
TP over model, weights FSDP-stored over data.  All sharding is expressed
through logical ``constrain`` calls so the same code runs single-device
(rules=None) and on the production meshes.
"""
from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

from repro.core.comm import SerialComm
from repro.mesh.axes import AxisRules, constrain
from repro.models import layers as L
from repro.models import attention as A
from repro.models import moe as M
from repro.models.module import Param

BIG_WINDOW = 1 << 30

# Serving-TP transport default: the serial transport makes every collective
# the identity, so the single-device paged path below is byte-for-byte the
# code that ran before the mesh existed (the paper's serial/parallel duality).
_SERIAL = SerialComm()


# ---------------------------------------------------------------------------
# Parameter definitions
# ---------------------------------------------------------------------------

def stack_defs(defs, n: int):
    """Prepend a stacked ``layers`` axis to every Param in a layer def tree."""
    def stack(p: Param) -> Param:
        return Param((n,) + tuple(p.shape), P("layers", *p.spec), init=p.init,
                     scale=p.scale, dtype=p.dtype)
    return jax.tree_util.tree_map(stack, defs, is_leaf=lambda x: isinstance(x, Param))


def block_defs(cfg) -> dict:
    d = {
        "ln1": L.rmsnorm_def(cfg.d_model),
        "attn": A.attention_def(cfg),
        "ln2": L.rmsnorm_def(cfg.d_model),
    }
    if cfg.n_experts:
        d["moe"] = M.moe_def(cfg)
        if cfg.dense_residual:
            d["mlp"] = L.mlp_def(cfg.d_model, cfg.d_ff)
    else:
        d["mlp"] = L.mlp_def(cfg.d_model, cfg.d_ff)
    return d


def transformer_defs(cfg) -> dict:
    return {
        "embed": {"table": Param((cfg.padded_vocab, cfg.d_model),
                                 P("vocab", "embed_w"), init="small")},
        "blocks": stack_defs(block_defs(cfg), cfg.n_layers),
        "final_norm": L.rmsnorm_def(cfg.d_model),
        "unembed": {"w": Param((cfg.d_model, cfg.padded_vocab),
                               P("embed_w", "vocab"), init="small")},
    }


def layer_windows(cfg) -> jnp.ndarray:
    """(L,) per-layer attention window (BIG_WINDOW = global)."""
    return jnp.asarray(
        [cfg.window_for_layer(i) or BIG_WINDOW for i in range(cfg.n_layers)],
        jnp.int32)


# ---------------------------------------------------------------------------
# One block
# ---------------------------------------------------------------------------

def block_apply(params, x, cfg, rules, *, positions, window,
                cache_k=None, cache_v=None, cache_pos=None):
    """Pre-norm block.  Returns (x, new_k, new_v) where new_k/new_v are the
    (possibly cache-updated) K/V for this layer (train: fresh; decode: cache).
    """
    h = L.rmsnorm(params["ln1"], x, use_pallas=cfg.use_pallas)
    h = constrain(h, P("batch", "seq", None), rules)
    q, k, v = A.qkv_project(params["attn"], h, cfg, positions,
                            rules=rules)

    if cache_k is not None:
        # decode: write new k/v at cache_pos, attend over the full cache.
        # cache_pos may be scalar (aligned decode) or (B,) (ragged slots —
        # continuous batching: every slot sits at its own length).
        if jnp.ndim(cache_pos) == 1:
            upd = jax.vmap(
                lambda c, x, p: jax.lax.dynamic_update_slice_in_dim(
                    c, x, p, axis=0))
            new_k = upd(cache_k, k, cache_pos)
            new_v = upd(cache_v, v, cache_pos)
        else:
            new_k = jax.lax.dynamic_update_slice_in_dim(cache_k, k, cache_pos,
                                                        axis=1)
            new_v = jax.lax.dynamic_update_slice_in_dim(cache_v, v, cache_pos,
                                                        axis=1)
        new_k = constrain(new_k, P("batch", "kv_seq", None, None), rules)
        new_v = constrain(new_v, P("batch", "kv_seq", None, None), rules)
        kv_len = cache_pos + q.shape[1]
        o = A.gqa_attention(q, new_k, new_v, causal=True, window=window,
                            q_offset=cache_pos, kv_valid_len=kv_len,
                            kv_chunk=max(cache_k.shape[1], 1),
                            use_pallas=False)
    else:
        # train/prefill: q is sequence-sharded; gather K/V across model axis
        new_k = constrain(k, P("batch", None, None, None), rules)
        new_v = constrain(v, P("batch", None, None, None), rules)
        o = A.gqa_attention(q, new_k, new_v, causal=True, window=window,
                            kv_chunk=cfg.kv_chunk, use_pallas=cfg.use_pallas)

    o = constrain(o, P("batch", "seq", None, None), rules)
    x = x + A.out_project(params["attn"], o)

    h = L.rmsnorm(params["ln2"], x, use_pallas=cfg.use_pallas)
    h = constrain(h, P("batch", "seq", None), rules)
    aux = jnp.zeros((), jnp.float32)
    if cfg.n_experts:
        y, aux = M.moe_apply(params["moe"], h, cfg, rules)
        if cfg.dense_residual:
            y = y + L.mlp(params["mlp"], h)
    else:
        y = L.mlp(params["mlp"], h)
    y = constrain(y, P("batch", "seq", None), rules)
    return x + y, new_k, new_v, aux


def _remat(fn, cfg):
    if cfg.remat == "none":
        return fn
    if cfg.remat == "dots":
        policy = jax.checkpoint_policies.dots_with_no_batch_dims_saveable
    else:
        policy = jax.checkpoint_policies.nothing_saveable
    return jax.checkpoint(fn, policy=policy)


# ---------------------------------------------------------------------------
# Forward passes
# ---------------------------------------------------------------------------

def embed_tokens(params, tokens, cfg, rules):
    x = L.embed(params["embed"], tokens).astype(jnp.dtype(cfg.dtype))
    return constrain(x, P("batch", "seq", None), rules)


def forward(params, cfg, rules, tokens=None, inputs_embeds=None):
    """Training/scoring forward (no cache).  Returns (hidden, aux_loss)."""
    x = inputs_embeds if inputs_embeds is not None \
        else embed_tokens(params, tokens, cfg, rules)
    S = x.shape[1]
    positions = jnp.arange(S)
    windows = layer_windows(cfg)

    def body(carry, xs):
        x, aux = carry
        p, w = xs
        x, _, _, a = block_apply(p, x, cfg, rules, positions=positions,
                                 window=w)
        return (x, aux + a), None

    (x, aux), _ = jax.lax.scan(_remat(body, cfg),
                               (x, jnp.zeros((), jnp.float32)),
                               (params["blocks"], windows))
    x = L.rmsnorm(params["final_norm"], x, use_pallas=cfg.use_pallas)
    return x, aux / max(cfg.n_layers, 1)


def lm_logits(params, hidden, cfg, rules):
    logits = jnp.einsum("bsd,dv->bsv", hidden,
                        params["unembed"]["w"].astype(hidden.dtype),
                        preferred_element_type=jnp.float32)
    return constrain(logits, P("batch", None, "vocab"), rules)


def loss_from_hidden(unembed_w, hidden, labels, cfg, rules,
                     loss_chunks: int = 8):
    """Cross-entropy from final hidden states with sequence-chunked,
    rematerialized logits.  Shared by every architecture family.

    The loss region is vocab-parallel (Megatron-style): hidden is resharded
    to (batch: data, seq: full) so logits shard over vocab ("model") and the
    softmax reductions psum across it; the seq dim is free for chunking."""
    hidden = constrain(hidden, P("batch", None, None), rules)
    labels = constrain(labels, P("batch", None), rules)
    S = hidden.shape[1]
    chunks = loss_chunks if S % loss_chunks == 0 and S >= loss_chunks else 1
    c = S // chunks

    def chunk_loss(h_c, l_c):
        logits = jnp.einsum("bsd,dv->bsv", h_c, unembed_w.astype(h_c.dtype),
                            preferred_element_type=jnp.float32)
        logits = constrain(logits, P("batch", None, "vocab"), rules)
        return _masked_ce_sums(logits, l_c, cfg)

    chunk_loss = jax.checkpoint(chunk_loss)

    hs = hidden.reshape(hidden.shape[0], chunks, c, -1).swapaxes(0, 1)
    ls = labels.reshape(labels.shape[0], chunks, c).swapaxes(0, 1)

    def body(carry, xs):
        tot, cnt = carry
        t, n = chunk_loss(*xs)
        return (tot + t, cnt + n), None

    (tot, cnt), _ = jax.lax.scan(body, (jnp.zeros((), jnp.float32),) * 2,
                                 (hs, ls))
    return tot / jnp.maximum(cnt, 1.0), cnt


def lm_loss(params, cfg, rules, tokens=None, labels=None, inputs_embeds=None,
            loss_chunks: int = 8):
    hidden, aux = forward(params, cfg, rules, tokens=tokens,
                          inputs_embeds=inputs_embeds)
    ce, cnt = loss_from_hidden(params["unembed"]["w"], hidden, labels, cfg,
                               rules, loss_chunks)
    loss = ce
    if cfg.n_experts:
        loss = loss + cfg.moe_aux_weight * aux
    return loss, {"ce": ce, "aux": aux, "tokens": cnt}


def _masked_ce_sums(logits, labels, cfg):
    """(sum nll, count) with padded-vocab masking, TP-safe (one-hot gold)."""
    v = logits.shape[-1]
    if cfg.padded_vocab > cfg.vocab:
        pad = jnp.arange(v) >= cfg.vocab
        logits = jnp.where(pad, -1e30, logits)
    m = jax.lax.stop_gradient(jnp.max(logits, axis=-1, keepdims=True))
    lse = jnp.log(jnp.sum(jnp.exp(logits - m), axis=-1)) + m[..., 0]
    onehot = jax.nn.one_hot(jnp.maximum(labels, 0), v, dtype=logits.dtype)
    gold = jnp.sum(logits * onehot, axis=-1)
    nll = lse - gold
    w = (labels >= 0).astype(jnp.float32)
    if cfg.z_loss:
        nll = nll + cfg.z_loss * lse ** 2
    return jnp.sum(nll * w), jnp.sum(w)


# ---------------------------------------------------------------------------
# Serving: prefill + decode
# ---------------------------------------------------------------------------

def uses_window_cache(cfg) -> bool:
    """Sliding-window archs (gemma3 5:1) keep ring caches of size `window`
    for local layers — at 500k context that is a ~7x cache cut (29 of 34
    layers hold 1024 entries instead of 524288)."""
    return bool(cfg.local_window and cfg.global_every)


def layer_groups(cfg):
    """(global layer indices, local layer indices)."""
    glob = [i for i in range(cfg.n_layers) if cfg.window_for_layer(i) is None]
    loc = [i for i in range(cfg.n_layers) if cfg.window_for_layer(i) is not None]
    return glob, loc


def init_cache(cfg, batch: int, max_len: int, dtype=jnp.bfloat16):
    hkv, hd = cfg.padded_kv_heads, cfg.head_dim
    if not uses_window_cache(cfg):
        shape = (cfg.n_layers, batch, max_len, hkv, hd)
        return {"k": jnp.zeros(shape, dtype), "v": jnp.zeros(shape, dtype)}
    glob, loc = layer_groups(cfg)
    W = min(cfg.local_window, max_len)
    return {
        "k": jnp.zeros((len(glob), batch, max_len, hkv, hd), dtype),
        "v": jnp.zeros((len(glob), batch, max_len, hkv, hd), dtype),
        # ring buffers: slot W-1 always holds the newest position
        "k_loc": jnp.zeros((len(loc), batch, W, hkv, hd), dtype),
        "v_loc": jnp.zeros((len(loc), batch, W, hkv, hd), dtype),
    }


def cache_specs(cfg):
    s = P("layers", "batch", "kv_seq", None, None)
    if not uses_window_cache(cfg):
        return {"k": s, "v": s}
    return {"k": s, "v": s, "k_loc": s, "v_loc": s}


def prefill(params, cfg, rules, tokens=None, inputs_embeds=None,
            max_len: Optional[int] = None):
    """Run the prompt, build the cache.  Returns (cache, hidden (B,S,d)).

    The full hidden sequence is returned (not just the last position) so
    callers with right-padded prompts can read the hidden state at their own
    valid length (the serving engine's bucketed prefill does)."""
    x = inputs_embeds if inputs_embeds is not None \
        else embed_tokens(params, tokens, cfg, rules)
    B, S = x.shape[0], x.shape[1]
    max_len = max_len or S
    positions = jnp.arange(S)
    windows = layer_windows(cfg)

    def body(x, xs):
        p, w = xs
        x, k, v, _ = block_apply(p, x, cfg, rules, positions=positions,
                                 window=w)
        if max_len > S:
            pad = [(0, 0), (0, max_len - S), (0, 0), (0, 0)]
            k, v = jnp.pad(k, pad), jnp.pad(v, pad)
        k = constrain(k, P("batch", "kv_seq", None, None), rules)
        v = constrain(v, P("batch", "kv_seq", None, None), rules)
        return x, (k, v)

    x, (ks, vs) = jax.lax.scan(_remat(body, cfg), x,
                               (params["blocks"], windows))
    x = L.rmsnorm(params["final_norm"], x, use_pallas=cfg.use_pallas)
    if not uses_window_cache(cfg):
        return {"k": ks, "v": vs}, x
    # compress local layers to their ring windows (right-aligned: slot W-1
    # = newest position; short prompts left-pad with masked zeros)
    glob, loc = layer_groups(cfg)
    W = min(cfg.local_window, max_len)
    take = min(W, S)
    k_loc = ks[jnp.asarray(loc)][:, :, S - take:S]
    v_loc = vs[jnp.asarray(loc)][:, :, S - take:S]
    pad = [(0, 0), (0, 0), (W - take, 0), (0, 0), (0, 0)]
    return {"k": ks[jnp.asarray(glob)], "v": vs[jnp.asarray(glob)],
            "k_loc": jnp.pad(k_loc, pad), "v_loc": jnp.pad(v_loc, pad)}, x


# ---------------------------------------------------------------------------
# Serving: paged KV cache (pool storage instead of per-slot dense buffers)
# ---------------------------------------------------------------------------

def _write_kv(kv, k, v, quant, scatter):
    """Commit fresh K/V into page storage through a pure
    ``scatter(leaf_storage, vals) -> leaf_storage`` op.

    With a ``quant`` policy the values are quantized first and the
    per-row scales scatter through the SAME op into their sibling
    ``k_scale``/``v_scale`` leaves — the policy supplies the numerics,
    this helper only routes blocks to leaves (the function-centric split),
    so prefill chunks, decode tokens and verify windows all write
    quantized pages with one code path.
    """
    if quant is None:
        return dict(kv, k=scatter(kv["k"], k), v=scatter(kv["v"], v))
    qk, sk = quant.quantize(k)
    qv, sv = quant.quantize(v)
    return dict(kv, k=scatter(kv["k"], qk), v=scatter(kv["v"], qv),
                k_scale=scatter(kv["k_scale"], sk),
                v_scale=scatter(kv["v_scale"], sv))


def _paged_block(p, x, cfg, rules, *, positions, kv, tables,
                 q_offset, write, layer=None, use_pallas=False, comm=_SERIAL,
                 ep_comm=None, placement=None):
    """One decoder block against paged KV storage.

    ``kv`` is the pool storage tree the block writes into — ``{"k", "v"}``
    pages, plus ``{"k_scale", "v_scale"}`` per-row scale leaves when the
    cache is quantized.  ``write(kv, k, v) -> kv`` commits the fresh K/V
    into pages — a whole-chunk scatter during prefill, a per-slot token
    scatter during decode, a per-slot window scatter during verify — so
    this block stays agnostic of which phase it runs in.  With ``layer``,
    ``kv`` is the pool of every layer, (L·N, page_size, ...), and attention
    reads that layer's pages (:func:`_layer_pages`).  Attention is one call
    for all three phases:
    :func:`repro.models.attention.paged_window_attention` with ``q_offset``
    tokens cached before the query window, fused Pallas kernel or jnp
    gather fallback per ``use_pallas`` (both dequantize scale leaves when
    present: the kernel in its VMEM tile, the fallback after its gather).

    ``comm`` is the serving-TP transport (Megatron attention/MLP TP inside a
    ``shard_map`` body): the block then sees its local head / ff / expert
    shard of the weights and the KV pages, computes attention entirely on
    local heads, and reassembles the residual stream with one ``psum`` after
    each of the two projections back to d_model.  The serial transport makes
    both psums the identity, so this is one code path for both worlds.

    ``ep_comm`` is the expert-parallel transport: expert weights arrive
    partitioned E/ep per rank over that axis and the MoE block exchanges
    its dispatch buffer through ``all_to_all`` (see
    :func:`repro.models.moe.moe_apply_expert_parallel`); ``placement`` is
    the (3, E) expert→slot dispatch map.  Returns ``(x, kv, moe_stats)``
    where ``moe_stats`` is the per-expert token/drop telemetry (zeros for
    dense blocks).
    """
    with jax.named_scope("attention"):
        h = L.rmsnorm(p["ln1"], x, use_pallas=cfg.use_pallas)
        q, k, v = A.qkv_project(p["attn"], h, cfg, positions, rules=rules)
        kv = write(kv, k, v)
        pages = kv if layer is None else _layer_pages(kv, layer,
                                                      cfg.n_layers)
        o = A.paged_window_attention(q, pages["k"], pages["v"], tables,
                                     q_offset, k_scale=pages.get("k_scale"),
                                     v_scale=pages.get("v_scale"),
                                     layer=layer, use_pallas=use_pallas)
        x = x + comm.all_reduce_sum(A.out_project(p["attn"], o))

    with jax.named_scope("mlp"):
        h = L.rmsnorm(p["ln2"], x, use_pallas=cfg.use_pallas)
        moe_stats = M.empty_expert_stats(cfg.n_experts)
        if cfg.n_experts:
            if comm.axis is None and ep_comm is None and rules is not None:
                # training-style rules path: moe_apply owns its own
                # shard_map
                y, _ = M.moe_apply(p["moe"], h, cfg, rules)
            else:
                # serving: expert-sharded (ep axis) and/or GEMM-sharded (tp
                # axis), replicated activations; output already combined
                y, _, moe_stats = M.moe_apply_expert_parallel(
                    p["moe"], h, cfg, _SERIAL if ep_comm is None else ep_comm,
                    shard_comm=comm if comm.axis is not None else None,
                    placement=placement)
            if cfg.dense_residual:
                y = y + comm.all_reduce_sum(L.mlp(p["mlp"], h))
        else:
            y = comm.all_reduce_sum(L.mlp(p["mlp"], h))
    return x + y, kv, moe_stats


def _layer_pages(pool, layer, n_layers):
    """Layer ``layer``'s pages of a pool of every layer, (L·N, page_size,
    ...), as attention reads them: the value leaves as the stacked pool (L,
    N, ...), a view that moves no bytes, read in place; the scale leaves
    (1/D of the values') as the layer's own (N, ...) slice."""
    n_pages = pool["k"].shape[0] // n_layers
    return {name: (jax.lax.dynamic_slice_in_dim(a, layer * n_pages, n_pages)
                   if name.endswith("_scale")
                   else a.reshape((n_layers, n_pages) + a.shape[1:]))
            for name, a in pool.items()}


def _paged_layers(params, cfg, rules, x, storage, scatter, *, positions,
                  tables, q_offset, use_pallas, comm, quant, ep_comm,
                  placement):
    """Every decoder block of a paged program, over the stacked pool.

    ``storage`` leaves are (L, N, page_size, ...), and the whole pool is
    the layer scan's carry.  Layer ``l`` writes through
    ``scatter(leaf, vals, off)`` on the pool viewed as (L·N, page_size,
    ...) — a merge of the two leading dims, which moves no bytes — with
    every page id it writes offset by ``off`` = ``l·N``, so its trash page
    is its own; attention reads layer ``l``'s pages of the stacked pool
    through the layer's own ``tables``.  So each layer scatters only the
    program's new rows into the pool, in place, and no layer's pages are
    sliced out, written back or copied.  The int8 scale leaves share the
    (L, N) dims and ride along; attention gets the layer's slice of them
    (:func:`_layer_pages`), a 1/D share of its pages.

    Returns (storage, hidden, telemetry) with telemetry the layer-summed
    per-expert ``{"expert_tokens", "expert_dropped"}`` int32 counts
    ((0,)-shaped for dense models).
    """
    n_pages = storage["k"].shape[1]
    flat = jax.tree_util.tree_map(
        lambda a: a.reshape((-1,) + a.shape[2:]), storage)

    def body(carry, p):
        x, tok, drp, kv, layer = carry

        def put(st, val):
            return scatter(st, val, layer * n_pages)

        x, kv, ms = _paged_block(
            p, x, cfg, rules, positions=positions, kv=kv, tables=tables,
            q_offset=q_offset,
            write=lambda kv, k, v: _write_kv(kv, k, v, quant, put),
            layer=layer, use_pallas=use_pallas, comm=comm, ep_comm=ep_comm,
            placement=placement)
        return (x, tok + ms["tokens"], drp + ms["dropped"], kv,
                layer + 1), None

    z = jnp.zeros((cfg.n_experts,), jnp.int32)
    (x, tok, drp, flat, _), _ = jax.lax.scan(
        body, (x, z, z, flat, jnp.int32(0)), params["blocks"])
    storage = jax.tree_util.tree_map(lambda a, s: a.reshape(s.shape),
                                     flat, storage)
    return storage, x, {"expert_tokens": tok, "expert_dropped": drp}


def paged_prefill_chunk(params, cfg, rules, storage, table_row, pages_chunk,
                        start, tokens, use_pallas=False, comm=None,
                        quant=None, ep_comm=None, placement=None,
                        embeds=None):
    """Prefill one page-aligned prompt chunk into paged storage.

    storage: {"k","v"} of (L, N, page_size, Hkv, D) — plus per-row
    {"k_scale","v_scale"} (L, N, page_size, Hkv) leaves when ``quant`` is
    set;  table_row: (P,) the slot's page table;  pages_chunk:
    (C // page_size,) pages covering positions [start, start + C);
    tokens: (1, C) (right-padded — the validity length masks pad garbage,
    exactly like bucketed dense prefill).  Returns (storage, hidden
    (1, C, d), telemetry) where telemetry is the layer-summed per-expert
    ``{"expert_tokens", "expert_dropped"}`` int32 counts ((0,)-shaped for
    dense models).  Chunks attend causally to every previously prefilled
    page, which is what lets long prompts prefill incrementally between
    decode ticks.  ``use_pallas`` routes attention through the fused
    multi-query kernel (W = C window, per-row causal offsets) instead of
    the jnp gather fallback.

    The stacked pool is the layer scan's carry (:func:`_paged_layers`):
    layer ``l`` scatters the chunk's rows into its pages ``pages_chunk`` in
    place and attends to its pages of the stacked pool.

    ``quant`` is the KV quantization policy (quantize-on-write; attention
    dequantizes through the scale leaves) — prefilled pages hold the SAME
    int8 content a decode write would produce, which is what keeps
    prefix-cache sharing exact under quantization.

    With a mesh ``comm`` (inside ``shard_map``): params/storage arrive
    head-sharded, hidden stays replicated (see :func:`_paged_block`).

    ``embeds`` opens the encoder-attached (VLM) path: a (1, C, d) buffer of
    precomputed embeddings spliced in wherever ``tokens`` is negative (the
    scheduler's image pseudo-tokens).  Real token positions still read the
    embedding table, so a chunk can mix image-prefix and text positions;
    with ``embeds=None`` the function is byte-identical to the text-only
    path — the zero-special-cases contract the multimodal tier rides on.
    """
    from repro.serve import pages as PG
    assert not uses_window_cache(cfg), "paged decode is global-attention only"
    page_size = storage["k"].shape[2]
    if embeds is None:
        x = embed_tokens(params, tokens, cfg, rules)
    else:
        x = embed_tokens(params, jnp.maximum(tokens, 0), cfg, rules)
        x = jnp.where((tokens < 0)[..., None], embeds.astype(x.dtype), x)
    C = x.shape[1]

    def scatter(st, val, off):
        return PG.scatter_chunk(st, pages_chunk + off, val[0],
                                page_size=page_size)

    storage, x, telemetry = _paged_layers(
        params, cfg, rules, x, storage, scatter,
        positions=start + jnp.arange(C), tables=table_row[None],
        q_offset=start, use_pallas=use_pallas,
        comm=_SERIAL if comm is None else comm, quant=quant,
        ep_comm=ep_comm, placement=placement)
    x = L.rmsnorm(params["final_norm"], x, use_pallas=cfg.use_pallas)
    return storage, x, telemetry


def paged_verify_chunk(params, cfg, rules, storage, tables, lengths, tokens,
                       write_pages, write_offs, use_pallas=False, comm=None,
                       quant=None, ep_comm=None, placement=None):
    """Score a per-slot window of candidate tokens in ONE batched forward —
    the speculative-decode verify step, and at C == 1 the decode step.

    tokens: (B, C) — position 0 is each slot's next input token (its K/V is
    not yet cached), positions 1..C-1 are draft continuations (right-padded
    for slots with shorter windows); tables: (B, P); lengths: (B,) tokens
    already cached (= the absolute position of tokens[:, 0]);
    write_pages/write_offs: (B, C) per-position K/V targets — pad and
    dead-slot positions point at the pool's trash page, so the SPMD call
    keeps static shapes while rejected/padded K/V never lands in a live
    page it wasn't meant for.

    Returns (storage, logits (B, C, V), telemetry — as in
    :func:`paged_prefill_chunk`): logits[:, i] is the target
    distribution for the token FOLLOWING tokens[:, i] — what the
    speculative acceptance rule scores draft i+1 against (and the
    correction/bonus is sampled from).  C == 1 is the decode step.
    ``quant`` quantizes each written row's K/V (scales land in the
    storage's scale leaves).

    The stacked pool is the layer scan's carry (:func:`_paged_layers`):
    layer ``l`` scatters the window's rows into its pages ``write_pages`` in
    place and attends to its pages of the stacked pool.

    Causality makes padding safe: query i attends keys <= lengths + i, and
    every real position's K/V is written (to its real page) before
    attention runs, while pad positions can only influence pad logits.
    ``use_pallas`` scores the whole window with the fused multi-query
    kernel (same per-row causal rule), keeping spec-on/spec-off greedy
    bit-parity intact.

    With a mesh ``comm`` (inside ``shard_map``): params/storage
    head-sharded, one psum after each residual projection; the unembed
    arrives vocab-sharded and the local logits are reassembled with a
    single tiled ``all_gather`` — the one collective at the logits head.
    """
    from repro.serve import pages as PG
    assert not uses_window_cache(cfg), "paged decode is global-attention only"
    comm = _SERIAL if comm is None else comm
    x = embed_tokens(params, tokens, cfg, rules)
    C = x.shape[1]

    def scatter(st, val, off):
        return PG.scatter_window(st, write_pages + off, write_offs, val)

    storage, x, telemetry = _paged_layers(
        params, cfg, rules, x, storage, scatter,
        positions=lengths[:, None] + jnp.arange(C), tables=tables,
        q_offset=lengths, use_pallas=use_pallas, comm=comm, quant=quant,
        ep_comm=ep_comm, placement=placement)
    with jax.named_scope("head"):
        x = L.rmsnorm(params["final_norm"], x, use_pallas=cfg.use_pallas)
        logits = comm.all_gather(lm_logits(params, x, cfg, rules),
                                 axis=-1, tiled=True)
    return storage, logits, telemetry


def _window_decode_step(params, cfg, rules, cache, tokens, pos):
    """Decode with mixed caches: full KV for global layers, ring buffers of
    size W for sliding-window layers (aligned decode only: scalar ``pos``)."""
    assert jnp.ndim(pos) == 0, "window-cache decode is aligned-only"
    glob, loc = layer_groups(cfg)
    g_of = {i: glob.index(i) for i in glob}
    l_of = {i: loc.index(i) for i in loc}
    W = cache["k_loc"].shape[2]

    x = embed_tokens(params, tokens, cfg, rules)
    x = constrain(x, P("batch", None, None), rules)
    positions = jnp.asarray(pos)[..., None] + jnp.arange(1)

    new_g_k, new_g_v = list(range(len(glob))), list(range(len(glob)))
    new_l_k, new_l_v = list(range(len(loc))), list(range(len(loc)))
    for i in range(cfg.n_layers):
        p = jax.tree_util.tree_map(lambda a: a[i], params["blocks"])
        h = L.rmsnorm(p["ln1"], x)
        q, k, v = A.qkv_project(p["attn"], h, cfg, positions, rules=rules)
        if i in g_of:                                  # global: normal cache
            g = g_of[i]
            nk = jax.lax.dynamic_update_slice_in_dim(cache["k"][g], k, pos,
                                                     axis=1)
            nv = jax.lax.dynamic_update_slice_in_dim(cache["v"][g], v, pos,
                                                     axis=1)
            nk = constrain(nk, P("batch", "kv_seq", None, None), rules)
            nv = constrain(nv, P("batch", "kv_seq", None, None), rules)
            o = A.gqa_attention(q, nk, nv, causal=True, q_offset=pos,
                                kv_valid_len=pos + 1,
                                kv_chunk=max(nk.shape[1], 1))
            new_g_k[g], new_g_v[g] = nk, nv
        else:                                          # local: ring buffer
            l = l_of[i]
            nk = jnp.concatenate([cache["k_loc"][l][:, 1:], k], axis=1)
            nv = jnp.concatenate([cache["v_loc"][l][:, 1:], v], axis=1)
            nk = constrain(nk, P("batch", "kv_seq", None, None), rules)
            nv = constrain(nv, P("batch", "kv_seq", None, None), rules)
            o = A.gqa_attention(q, nk, nv, causal=True,
                                window=cfg.local_window, q_offset=pos,
                                k_start=pos - W + 1, kv_chunk=W)
            new_l_k[l], new_l_v[l] = nk, nv
        x = x + A.out_project(p["attn"], o)
        h = L.rmsnorm(p["ln2"], x)
        x = x + L.mlp(p["mlp"], h)

    x = L.rmsnorm(params["final_norm"], x, use_pallas=cfg.use_pallas)
    logits = lm_logits(params, x, cfg, rules)
    new_cache = {"k": jnp.stack(new_g_k), "v": jnp.stack(new_g_v),
                 "k_loc": jnp.stack(new_l_k), "v_loc": jnp.stack(new_l_v)}
    return new_cache, logits


def decode_step(params, cfg, rules, cache, tokens, pos):
    """One token for every sequence.  tokens: (B, 1); pos: scalar int32
    (aligned) or (B,) int32 (ragged slots).  Returns (cache, logits)."""
    if uses_window_cache(cfg):
        return _window_decode_step(params, cfg, rules, cache, tokens, pos)
    x = embed_tokens(params, tokens, cfg, rules)
    x = constrain(x, P("batch", None, None), rules)
    positions = jnp.asarray(pos)[..., None] + jnp.arange(1)
    windows = layer_windows(cfg)

    def body(x, xs):
        p, w, ck, cv = xs
        x, nk, nv, _ = block_apply(p, x, cfg, rules, positions=positions,
                                   window=w, cache_k=ck, cache_v=cv,
                                   cache_pos=pos)
        return x, (nk, nv)

    x, (ks, vs) = jax.lax.scan(body, x, (params["blocks"], windows,
                                         cache["k"], cache["v"]))
    x = L.rmsnorm(params["final_norm"], x, use_pallas=cfg.use_pallas)
    logits = lm_logits(params, x, cfg, rules)
    return {"k": ks, "v": vs}, logits
