"""Whisper-style encoder-decoder transformer (conv frontend stubbed).

Per the assignment brief the audio frontend is a STUB: inputs are precomputed
frame embeddings ``(B, n_audio_frames, d_model)`` (what the two conv layers
would produce), fed straight into the bidirectional encoder.  The decoder has
causal self-attention plus cross-attention over the encoder output, LayerNorm
(not RMSNorm) and biased GELU MLPs, matching the published architecture.

Serving: the cross-attention K/V are computed ONCE from the encoder output at
prefill and reused every decode step (standard enc-dec serving split); the
self-attention cache grows like a decoder-only LM's.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

from repro.mesh.axes import constrain
from repro.models import attention as A
from repro.models import layers as L
from repro.models import transformer as T
from repro.models.module import Param


# ---------------------------------------------------------------------------
# Parameter definitions
# ---------------------------------------------------------------------------

def _attn_def(cfg, *, cross: bool = False) -> dict:
    d, hd, h = cfg.d_model, cfg.head_dim, cfg.n_heads
    defs = {
        "wq": Param((d, h, hd), P("embed_w", "q_heads", "head_dim")),
        "wk": Param((d, h, hd), P("embed_w", "kv_heads", "head_dim")),
        "wv": Param((d, h, hd), P("embed_w", "kv_heads", "head_dim")),
        "wo": Param((h, hd, d), P("q_heads", "head_dim", "embed_w")),
        "bq": Param((h, hd), P("q_heads", "head_dim"), init="zeros"),
        "bv": Param((h, hd), P("kv_heads", "head_dim"), init="zeros"),
        "bo": Param((d,), P(None), init="zeros"),
    }
    return defs


def _enc_block_def(cfg) -> dict:
    return {
        "ln1": L.layernorm_def(cfg.d_model),
        "attn": _attn_def(cfg),
        "ln2": L.layernorm_def(cfg.d_model),
        "mlp": L.mlp_plain_def(cfg.d_model, cfg.d_ff),
    }


def _dec_block_def(cfg) -> dict:
    return {
        "ln1": L.layernorm_def(cfg.d_model),
        "self_attn": _attn_def(cfg),
        "ln_x": L.layernorm_def(cfg.d_model),
        "cross_attn": _attn_def(cfg, cross=True),
        "ln2": L.layernorm_def(cfg.d_model),
        "mlp": L.mlp_plain_def(cfg.d_model, cfg.d_ff),
    }


def whisper_defs(cfg) -> dict:
    return {
        "enc_blocks": T.stack_defs(_enc_block_def(cfg), cfg.n_layers),
        "enc_norm": L.layernorm_def(cfg.d_model),
        "embed": {"table": Param((cfg.padded_vocab, cfg.d_model),
                                 P("vocab", "embed_w"), init="small")},
        "dec_blocks": T.stack_defs(_dec_block_def(cfg), cfg.decoder_layers),
        "dec_norm": L.layernorm_def(cfg.d_model),
        # whisper ties the unembedding to the token embedding; we keep a
        # separate head for TP-friendly vocab sharding symmetry with the LMs.
        "unembed": {"w": Param((cfg.d_model, cfg.padded_vocab),
                               P("embed_w", "vocab"), init="small")},
    }


# ---------------------------------------------------------------------------
# Attention helpers (MHA with q/v biases, whisper style: no k bias)
# ---------------------------------------------------------------------------

def _project_qkv(p, xq, xkv, dtype):
    q = jnp.einsum("bsd,dhe->bshe", xq, p["wq"].astype(dtype)) + p["bq"].astype(dtype)
    k = jnp.einsum("bsd,dhe->bshe", xkv, p["wk"].astype(dtype))
    v = jnp.einsum("bsd,dhe->bshe", xkv, p["wv"].astype(dtype)) + p["bv"].astype(dtype)
    return q, k, v


def _out(p, o):
    return jnp.einsum("bshe,hed->bsd", o, p["wo"].astype(o.dtype)) \
        + p["bo"].astype(o.dtype)


def _mha(p, xq, xkv, cfg, *, causal, q_offset=0, kv_valid_len=None,
         cache_k=None, cache_v=None, cache_pos=None):
    """Self- or cross-attention.  Returns (out, new_k, new_v)."""
    q, k, v = _project_qkv(p, xq, xkv, xq.dtype)
    if cache_k is not None:
        k = jax.lax.dynamic_update_slice_in_dim(cache_k, k, cache_pos, axis=1)
        v = jax.lax.dynamic_update_slice_in_dim(cache_v, v, cache_pos, axis=1)
    o = A.gqa_attention(q, k, v, causal=causal, q_offset=q_offset,
                        kv_valid_len=kv_valid_len, kv_chunk=cfg.kv_chunk,
                        use_pallas=cfg.use_pallas and cache_k is None
                        and kv_valid_len is None)
    return _out(p, o), k, v


def _cross(p, xq, enc_k, enc_v, cfg):
    """Cross-attention against precomputed encoder K/V."""
    dtype = xq.dtype
    q = jnp.einsum("bsd,dhe->bshe", xq, p["wq"].astype(dtype)) + p["bq"].astype(dtype)
    o = A.gqa_attention(q, enc_k, enc_v, causal=False, kv_chunk=cfg.kv_chunk,
                        use_pallas=False)
    return _out(p, o)


def _cross_kv(p, enc_out):
    dtype = enc_out.dtype
    k = jnp.einsum("bsd,dhe->bshe", enc_out, p["wk"].astype(dtype))
    v = jnp.einsum("bsd,dhe->bshe", enc_out, p["wv"].astype(dtype)) \
        + p["bv"].astype(dtype)
    return k, v


# ---------------------------------------------------------------------------
# Encoder / decoder stacks
# ---------------------------------------------------------------------------

def encode(params, cfg, rules, frames):
    """frames: (B, F, d) precomputed frame embeddings (stub frontend)."""
    pos = L.sinusoidal_pos(jnp.arange(frames.shape[1]), cfg.d_model)
    x = frames + pos.astype(frames.dtype)
    x = constrain(x, P("batch", "frames", None), rules)

    def body(x, p):
        h = L.layernorm(p["ln1"], x)
        o, _, _ = _mha(p["attn"], h, h, cfg, causal=False)
        x = x + o
        h = L.layernorm(p["ln2"], x)
        return x + L.mlp_plain(p["mlp"], h), None

    x, _ = jax.lax.scan(T._remat(body, cfg), x, params["enc_blocks"])
    return L.layernorm(params["enc_norm"], x)


def decode_train(params, cfg, rules, tokens, enc_out):
    """Teacher-forced decoder forward -> final hidden."""
    x = L.embed(params["embed"], tokens).astype(jnp.dtype(cfg.dtype))
    x = x + L.sinusoidal_pos(jnp.arange(x.shape[1]),
                             cfg.d_model).astype(x.dtype)
    x = constrain(x, P("batch", "seq", None), rules)

    def body(x, p):
        h = L.layernorm(p["ln1"], x)
        o, _, _ = _mha(p["self_attn"], h, h, cfg, causal=True)
        x = x + o
        h = L.layernorm(p["ln_x"], x)
        ek, ev = _cross_kv(p["cross_attn"], enc_out)
        x = x + _cross(p["cross_attn"], h, ek, ev, cfg)
        h = L.layernorm(p["ln2"], x)
        return x + L.mlp_plain(p["mlp"], h), None

    x, _ = jax.lax.scan(T._remat(body, cfg), x, params["dec_blocks"])
    return L.layernorm(params["dec_norm"], x)


def loss(params, cfg, rules, frames, tokens, labels, loss_chunks: int = 8):
    enc_out = encode(params, cfg, rules, frames)
    hidden = decode_train(params, cfg, rules, tokens, enc_out)
    ce, cnt = T.loss_from_hidden(params["unembed"]["w"], hidden, labels, cfg,
                                 rules, loss_chunks)
    return ce, {"ce": ce, "tokens": cnt}


# ---------------------------------------------------------------------------
# Serving
# ---------------------------------------------------------------------------

def init_state(cfg, batch: int, max_len: int, dtype=jnp.bfloat16):
    h, hd, Ld = cfg.n_heads, cfg.head_dim, cfg.decoder_layers
    F = cfg.n_audio_frames
    return {
        "self_k": jnp.zeros((Ld, batch, max_len, h, hd), dtype),
        "self_v": jnp.zeros((Ld, batch, max_len, h, hd), dtype),
        "cross_k": jnp.zeros((Ld, batch, F, h, hd), dtype),
        "cross_v": jnp.zeros((Ld, batch, F, h, hd), dtype),
    }


def state_specs(cfg):
    s = P(None, "batch", "kv_seq", None, None)
    c = P(None, "batch", "frames", None, None)
    return {"self_k": s, "self_v": s, "cross_k": c, "cross_v": c}


def prefill(params, cfg, rules, frames, tokens, max_len: int):
    """Encode audio, precompute cross K/V, run the decoder prompt."""
    B, S = tokens.shape
    enc_out = encode(params, cfg, rules, frames)
    x = L.embed(params["embed"], tokens).astype(jnp.dtype(cfg.dtype))
    x = x + L.sinusoidal_pos(jnp.arange(S), cfg.d_model).astype(x.dtype)

    sks, svs, cks, cvs = [], [], [], []
    Ld = cfg.decoder_layers
    for i in range(Ld):
        p = jax.tree_util.tree_map(lambda a: a[i], params["dec_blocks"])
        h = L.layernorm(p["ln1"], x)
        cache_k = jnp.zeros((B, max_len, cfg.n_heads, cfg.head_dim),
                            jnp.dtype(cfg.dtype))
        o, k, v = _mha(p["self_attn"], h, h, cfg, causal=True,
                       kv_valid_len=S, cache_k=cache_k,
                       cache_v=jnp.zeros_like(cache_k),
                       cache_pos=jnp.asarray(0, jnp.int32))
        x = x + o
        ek, ev = _cross_kv(p["cross_attn"], enc_out)
        h = L.layernorm(p["ln_x"], x)
        x = x + _cross(p["cross_attn"], h, ek, ev, cfg)
        h = L.layernorm(p["ln2"], x)
        x = x + L.mlp_plain(p["mlp"], h)
        sks.append(k); svs.append(v); cks.append(ek); cvs.append(ev)
    x = L.layernorm(params["dec_norm"], x)
    state = {"self_k": jnp.stack(sks), "self_v": jnp.stack(svs),
             "cross_k": jnp.stack(cks), "cross_v": jnp.stack(cvs)}
    return state, x


def _pos_embed(positions, dim: int):
    """Sinusoidal embeddings for batched position arrays: (B,) or (B, C)
    -> positions.shape + (dim,).  Whisper has no rope — absolute positions
    enter the decoder only through these additive embeddings, which is what
    lets the paged path reuse the page-table machinery unchanged."""
    flat = L.sinusoidal_pos(positions.reshape(-1), dim)
    return flat.reshape(positions.shape + (dim,))


def encode_chunk(params, cfg, rules, frames, start, n_valid):
    """Encode ONE audio chunk — the streaming unit of chunked encode.

    frames: (1, Cf, d) right-padded frame embeddings covering absolute
    positions [start, start + Cf); ``n_valid`` masks the right-pad.
    Attention is confined to the chunk (block-diagonal streaming
    approximation — exact whenever the whole clip fits one chunk, which the
    SMOKE configs guarantee and the parity tests rely on).  Returns the
    encoder output for the chunk, (1, Cf, d), ready for
    :func:`cross_kv_chunk`.
    """
    Cf = frames.shape[1]
    pos = L.sinusoidal_pos(start + jnp.arange(Cf), cfg.d_model)
    x = frames + pos.astype(frames.dtype)

    def body(x, p):
        h = L.layernorm(p["ln1"], x)
        o, _, _ = _mha(p["attn"], h, h, cfg, causal=False,
                       kv_valid_len=n_valid)
        x = x + o
        h = L.layernorm(p["ln2"], x)
        return x + L.mlp_plain(p["mlp"], h), None

    x, _ = jax.lax.scan(T._remat(body, cfg), x, params["enc_blocks"])
    return L.layernorm(params["enc_norm"], x)


def cross_kv_chunk(params, cfg, enc_chunk):
    """Cross-attention K/V for one encoder-output chunk, all layers at once.

    Cross K/V is a per-position linear map of the encoder output (wk, wv
    only — whisper has no k bias), so chunk-wise computation is EXACT
    regardless of chunking.  enc_chunk: (1, Cf, d) -> k, v: (Ld, Cf, h, hd)
    — shaped for a page scatter with ``n_prefix=1``.
    """
    k, v = jax.vmap(lambda p: _cross_kv(p, enc_chunk))(
        params["dec_blocks"]["cross_attn"])
    return k[:, 0], v[:, 0]


def scatter_cross(storage, pages, k, v, *, page_size: int, quant=None):
    """Commit one chunk's cross K/V into its cross pages (write-once).

    storage: {"cross_k","cross_v"} of (Ld, N, page_size, h, hd) — plus
    per-row {"cross_k_scale","cross_v_scale"} leaves when ``quant`` is set;
    pages: (n,) int32;  k/v: (Ld, n * page_size, h, hd) right-padded.
    Quantize-on-write mirrors the self-attention pools, so int8 cross pages
    compose with the same scale-leaf machinery.
    """
    from repro.serve import pages as PG

    def sc(st, val):
        return PG.scatter_chunk(st, pages, val, page_size=page_size,
                                n_prefix=1)

    if quant is None:
        return dict(storage, cross_k=sc(storage["cross_k"], k),
                    cross_v=sc(storage["cross_v"], v))
    qk, sk = quant.quantize(k)
    qv, sv = quant.quantize(v)
    return dict(storage, cross_k=sc(storage["cross_k"], qk),
                cross_v=sc(storage["cross_v"], qv),
                cross_k_scale=sc(storage["cross_k_scale"], sk),
                cross_v_scale=sc(storage["cross_v_scale"], sv))


def _paged_dec_block(p, x, cfg, *, kv, tables, q_offset, write,
                     cross_kv, cross_tables, frames_len, use_pallas=False):
    """One whisper decoder block against paged storage.

    Self-attention mirrors :func:`repro.models.transformer._paged_block`
    (write fresh K/V through ``write``, attend through
    :func:`paged_window_attention`); between it and the MLP sits the
    cross-attention read: gather this layer's cross-KV pages (read-only —
    written once by the encode path), dequantize scale leaves when present,
    and run non-causal attention masked to each slot's ``frames_len``
    (0 frames -> a zero contribution, which is what keeps dead decode slots
    safe against the trash page).
    """
    from repro.optim.compress import int8_decompress
    from repro.serve import pages as PG
    dtype = x.dtype
    h = L.layernorm(p["ln1"], x)
    q, k, v = _project_qkv(p["self_attn"], h, h, dtype)
    kv = write(kv, k, v)
    o = A.paged_window_attention(q, kv["k"], kv["v"], tables, q_offset,
                                 k_scale=kv.get("k_scale"),
                                 v_scale=kv.get("v_scale"),
                                 use_pallas=use_pallas)
    x = x + _out(p["self_attn"], o)

    h = L.layernorm(p["ln_x"], x)
    cq = jnp.einsum("bsd,dhe->bshe", h,
                    p["cross_attn"]["wq"].astype(dtype)) \
        + p["cross_attn"]["bq"].astype(dtype)
    ck = PG.gather_pages(cross_kv["cross_k"], cross_tables)
    cv = PG.gather_pages(cross_kv["cross_v"], cross_tables)
    if "cross_k_scale" in cross_kv:
        ck = int8_decompress(ck, PG.gather_pages(cross_kv["cross_k_scale"],
                                                 cross_tables),
                             axis=-1, dtype=dtype)
        cv = int8_decompress(cv, PG.gather_pages(cross_kv["cross_v_scale"],
                                                 cross_tables),
                             axis=-1, dtype=dtype)
    o = A.gqa_attention(cq, ck, cv, causal=False, kv_valid_len=frames_len,
                        kv_chunk=max(ck.shape[1], 1), use_pallas=False)
    x = x + _out(p["cross_attn"], o)

    h = L.layernorm(p["ln2"], x)
    return x + L.mlp_plain(p["mlp"], h), kv


def _no_moe():
    return {"expert_tokens": jnp.zeros((0,), jnp.int32),
            "expert_dropped": jnp.zeros((0,), jnp.int32)}


def paged_prefill_chunk(params, cfg, rules, storage, table_row, pages_chunk,
                        start, tokens, cross_storage, cross_row, frames_len,
                        use_pallas=False, quant=None):
    """Prefill one decoder-prompt chunk against paged self + cross storage.

    Same contract as :func:`repro.models.transformer.paged_prefill_chunk`
    (tokens (1, C) right-padded, pages_chunk covering [start, start + C)),
    plus the read-only cross side: ``cross_storage`` {"cross_k","cross_v"}
    pages, ``cross_row`` (Pc,) the slot's cross page table, ``frames_len``
    scalar valid frames.  Positions are sinusoidal at absolute offsets (no
    rope), so chunked prefill matches the dense decoder bit-for-bit.
    Returns (self_storage, hidden (1, C, d), telemetry).
    """
    from repro.serve import pages as PG
    page_size = storage["k"].shape[2]
    x = L.embed(params["embed"], tokens).astype(jnp.dtype(cfg.dtype))
    C = x.shape[1]
    positions = start + jnp.arange(C)
    x = x + L.sinusoidal_pos(positions, cfg.d_model).astype(x.dtype)
    tables = table_row[None]                                    # (1, P)
    cross_tables = cross_row[None]                              # (1, Pc)
    flen = jnp.asarray(frames_len)[None]                        # (1,)

    def write(kv, k, v):
        return T._write_kv(
            kv, k[0], v[0], quant,
            lambda st, val: PG.scatter_chunk(st, pages_chunk, val,
                                             page_size=page_size))

    def body(x, xs):
        p, kv, ckv = xs
        x, kv = _paged_dec_block(p, x, cfg, kv=kv, tables=tables,
                                 q_offset=start, write=write,
                                 cross_kv=ckv, cross_tables=cross_tables,
                                 frames_len=flen, use_pallas=use_pallas)
        return x, kv

    x, storage = jax.lax.scan(body, x, (params["dec_blocks"], storage,
                                        cross_storage))
    x = L.layernorm(params["dec_norm"], x)
    return storage, x, _no_moe()


def paged_verify_chunk(params, cfg, rules, storage, tables, lengths, tokens,
                       write_pages, write_offs, cross_storage, cross_tables,
                       frames_len, use_pallas=False, quant=None):
    """Score a (B, C) candidate window in one forward (speculative verify)
    — :func:`repro.models.transformer.paged_verify_chunk` plus the cross
    read.  C == 1 is the decode step.  ``cross_tables`` (B, Pc) and
    ``frames_len`` (B,) give each slot's cross read (dead slots: trash page
    + 0 frames).  Returns (storage, logits (B, C, V), telemetry)."""
    from repro.serve import pages as PG
    x = L.embed(params["embed"], tokens).astype(jnp.dtype(cfg.dtype))
    C = x.shape[1]
    positions = lengths[:, None] + jnp.arange(C)                # (B, C)
    x = x + _pos_embed(positions, cfg.d_model).astype(x.dtype)

    def write(kv, k, v):
        return T._write_kv(
            kv, k, v, quant,
            lambda st, val: PG.scatter_window(st, write_pages, write_offs,
                                              val))

    def body(x, xs):
        p, kv, ckv = xs
        x, kv = _paged_dec_block(p, x, cfg, kv=kv, tables=tables,
                                 q_offset=lengths, write=write,
                                 cross_kv=ckv, cross_tables=cross_tables,
                                 frames_len=frames_len,
                                 use_pallas=use_pallas)
        return x, kv

    x, storage = jax.lax.scan(body, x, (params["dec_blocks"], storage,
                                        cross_storage))
    x = L.layernorm(params["dec_norm"], x)
    logits = T.lm_logits(params, x, cfg, rules)
    return storage, logits, _no_moe()


def decode_step(params, cfg, rules, state, tokens, pos):
    """One new token against the self cache + fixed cross K/V."""
    x = L.embed(params["embed"], tokens).astype(jnp.dtype(cfg.dtype))
    x = x + L.sinusoidal_pos(pos + jnp.arange(1), cfg.d_model).astype(x.dtype)
    x = constrain(x, P("batch", None, None), rules)

    def body(x, xs):
        p, sk, sv, ck, cv = xs
        h = L.layernorm(p["ln1"], x)
        o, nk, nv = _mha(p["self_attn"], h, h, cfg, causal=True,
                         q_offset=pos, kv_valid_len=pos + 1,
                         cache_k=sk, cache_v=sv, cache_pos=pos)
        x = x + o
        h = L.layernorm(p["ln_x"], x)
        x = x + _cross(p["cross_attn"], h, ck, cv, cfg)
        h = L.layernorm(p["ln2"], x)
        return x + L.mlp_plain(p["mlp"], h), (nk, nv)

    x, (nk, nv) = jax.lax.scan(
        body, x, (params["dec_blocks"], state["self_k"], state["self_v"],
                  state["cross_k"], state["cross_v"]))
    x = L.layernorm(params["dec_norm"], x)
    logits = jnp.einsum("bsd,dv->bsv", x,
                        params["unembed"]["w"].astype(x.dtype),
                        preferred_element_type=jnp.float32)
    logits = constrain(logits, P("batch", None, "vocab"), rules)
    new_state = {"self_k": nk, "self_v": nv,
                 "cross_k": state["cross_k"], "cross_v": state["cross_v"]}
    return new_state, logits
