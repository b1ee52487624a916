"""Grouped-query attention with KV-chunked online softmax (jnp "flash").

Memory never exceeds O(Sq x kv_chunk) per head group, which is what makes the
32k-prefill and 500k shapes lowerable; the Pallas kernel
(:mod:`repro.kernels.flash_attention`) implements the same blocking for real
TPUs, and this function is its oracle-equivalent fallback (``use_pallas``
selects the kernel on TPU runtimes).

Supports: GQA (grouped KV heads without materializing repeats), causal and
sliding-window masks (gemma3's 5:1 local:global via per-layer ``window``),
QK-norm (qwen3/gemma3), additive QKV bias (qwen2), decode against a
fixed-capacity KV cache with a validity length.
"""
from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

from repro.models.module import Param
from repro.models import layers as L

NEG_INF = -1e30


def attention_def(cfg) -> dict:
    """Parameter tree for one attention block (padded head counts)."""
    d, hd = cfg.d_model, cfg.head_dim
    hq, hkv = cfg.padded_q_heads, cfg.padded_kv_heads
    defs = {
        "wq": Param((d, hq, hd), P("embed_w", "q_heads", "head_dim")),
        "wk": Param((d, hkv, hd), P("embed_w", "kv_heads", "head_dim")),
        "wv": Param((d, hkv, hd), P("embed_w", "kv_heads", "head_dim")),
        "wo": Param((hq, hd, d), P("q_heads", "head_dim", "embed_w")),
    }
    if cfg.qkv_bias:
        defs["bq"] = Param((hq, hd), P("q_heads", "head_dim"), init="zeros")
        defs["bk"] = Param((hkv, hd), P("kv_heads", "head_dim"), init="zeros")
        defs["bv"] = Param((hkv, hd), P("kv_heads", "head_dim"), init="zeros")
    if cfg.qk_norm:
        defs["q_norm"] = L.rmsnorm_def(hd)
        defs["k_norm"] = L.rmsnorm_def(hd)
    return defs


def qkv_project(params, x, cfg, positions, rules=None):
    """x: (B,S,d) -> q (B,S,Hq,D), k/v (B,S,Hkv,D), rotary applied.

    q/k/v are pinned seq-sharded right after the projection: without the pin,
    GSPMD may satisfy the downstream gathered-KV constraint by all-gathering
    ``x`` (d_model wide) instead of the 2·Hkv·hd-wide K/V — a 16-32x larger
    transfer under GQA (observed on qwen3-moe: 4 GB vs 268 MB per layer)."""
    from repro.mesh.axes import constrain as _c
    q = jnp.einsum("bsd,dhe->bshe", x, params["wq"].astype(x.dtype))
    k = jnp.einsum("bsd,dhe->bshe", x, params["wk"].astype(x.dtype))
    v = jnp.einsum("bsd,dhe->bshe", x, params["wv"].astype(x.dtype))
    q = _c(q, P("batch", "seq", None, None), rules)
    k = _c(k, P("batch", "seq", None, None), rules)
    v = _c(v, P("batch", "seq", None, None), rules)
    if cfg.qkv_bias:
        q = q + params["bq"].astype(q.dtype)
        k = k + params["bk"].astype(k.dtype)
        v = v + params["bv"].astype(v.dtype)
    if cfg.qk_norm:
        q = L.rmsnorm(params["q_norm"], q)
        k = L.rmsnorm(params["k_norm"], k)
    if cfg.rope_theta:
        q = L.rope(q, positions, theta=cfg.rope_theta)
        k = L.rope(k, positions, theta=cfg.rope_theta)
    return q, k, v


def out_project(params, o):
    return jnp.einsum("bshe,hed->bsd", o, params["wo"].astype(o.dtype))


def _mask_bias(q_pos, k_pos, *, causal: bool, window: Optional[int],
               kv_valid_len=None):
    """Additive mask in f32, broadcastable against scores (B,Hkv,G,Sq,Sk).

    ``q_pos``: (Sq,) or (B, Sq) — per-batch offsets enable ragged decode
    (continuous batching: every slot at a different position).
    ``kv_valid_len``: None, scalar, or (B,).
    Returns (Sq, Sk) or (B, 1, 1, Sq, Sk).
    """
    qp = q_pos[..., :, None]                       # (..., Sq, 1)
    kp = k_pos[None, :]                            # (1, Sk)
    ok = jnp.ones(jnp.broadcast_shapes(qp.shape, kp.shape), bool)
    if causal:
        ok &= qp >= kp
    if window is not None:
        ok &= (qp - kp) < window
    ok &= kp >= 0                                  # ring caches: unfilled slots
    if kv_valid_len is not None:
        kv = jnp.asarray(kv_valid_len)
        kv = kv[..., None, None]                   # (..., 1, 1)
        ok &= kp < kv
    mask = jnp.where(ok, 0.0, NEG_INF).astype(jnp.float32)
    if mask.ndim == 3:                             # (B, Sq, Sk) -> broadcast
        mask = mask[:, None, None]
    return mask


def paged_window_attention(q, k_pages, v_pages, tables, n_cached, *,
                           k_scale=None, v_scale=None, layer=None,
                           use_pallas: bool = False):
    """Attention for a window of queries against paged KV storage — the ONE
    model-side paged-attention path (decode W=1, speculative verify, and
    page-aligned chunked prefill all route here).

    q: (B, W, Hq, D); k_pages/v_pages: (N, page_size, Hkv, D);
    tables: (B, P) int32 page ids; ``n_cached``: scalar or (B,) int32 tokens
    cached BEFORE the window (= window position 0's absolute position).
    Window position w attends to cached positions plus window positions
    <= w; every window token's K/V must be written to its page before the
    call.  Returns (B, W, Hq, D).

    ``use_pallas`` (what the serving engine passes on a TPU) routes
    through the fused multi-query Pallas kernel
    (:mod:`repro.kernels.paged_attention`), which copies each slot's live
    pages into VMEM itself and applies the per-row causal offset there;
    the fallback materializes the gather with jnp advanced indexing and
    reuses :func:`gqa_attention`'s masked path — identical math, the
    kernel-parity oracle on the model side.

    Head counts are whatever the caller holds: under tensor-parallel serving
    this runs inside a ``shard_map`` body where Hq/Hkv are the LOCAL shard
    (Hq_global/tp, Hkv_global/tp) and the pages carry only local KV heads —
    attention is embarrassingly parallel across the head axis, so no
    collective appears here.

    ``k_scale``/``v_scale``: optional (N, page_size, Hkv) per-(row, head)
    dequantization scales for int8 pages.  The kernel path fuses the
    multiply into the VMEM page tile (the page stream stays int8 in HBM);
    this fallback dequantizes right after the gather — same math, the
    quantized kernel's parity oracle.

    ``layer``: with it, ``k_pages``/``v_pages`` are a stacked pool of
    layers, (L, N, page_size, Hkv, D), and attention reads layer
    ``layer``'s pages (a traced int32) in place: none are sliced out of the
    pool.  ``tables`` and the scale leaves are that layer's own.
    """
    Hq, Hkv = q.shape[2], k_pages.shape[-2]
    if Hkv == 0 or Hq % Hkv:
        raise ValueError(
            f"Hq={Hq} must be a positive multiple of Hkv={Hkv}; under "
            "serving TP both must divide by tp so each shard keeps whole "
            "GQA groups")
    W = q.shape[1]
    if use_pallas:
        from repro.kernels import ops as kops
        lengths = jnp.broadcast_to(
            jnp.asarray(n_cached, jnp.int32) + 1, (q.shape[0],))
        return kops.paged_attention_mq(q, k_pages, v_pages, tables, lengths,
                                       k_scale, v_scale, layer)
    from repro.optim.compress import int8_decompress
    from repro.serve import pages as PG
    at = tables
    if layer is not None:
        # the stacked pool viewed as (L·N, ...): the layer's pages lie
        # layer·N pages in
        at = tables + layer * k_pages.shape[1]
        k_pages, v_pages = (a.reshape((-1,) + a.shape[2:])
                            for a in (k_pages, v_pages))
    k = PG.gather_pages(k_pages, at)                # (B, P*page_size, Hkv, D)
    v = PG.gather_pages(v_pages, at)
    if k_scale is not None:
        k = int8_decompress(k, PG.gather_pages(k_scale, tables),
                            axis=-1, dtype=q.dtype)
        v = int8_decompress(v, PG.gather_pages(v_scale, tables),
                            axis=-1, dtype=q.dtype)
    return gqa_attention(q, k, v, causal=True, q_offset=n_cached,
                         kv_valid_len=n_cached + W,
                         kv_chunk=max(k.shape[1], 1))


def paged_decode_attention(q, k_pages, v_pages, tables, lengths, *,
                           k_scale=None, v_scale=None,
                           use_pallas: bool = False):
    """Decode attention against paged KV storage (one query per sequence):
    the W=1 window of :func:`paged_window_attention`.

    q: (B, 1, Hq, D); ``lengths``: (B,) int32 valid-KV counts *including*
    the current token (already written to its page).
    """
    return paged_window_attention(q, k_pages, v_pages, tables, lengths - 1,
                                  k_scale=k_scale, v_scale=v_scale,
                                  use_pallas=use_pallas)


def gqa_attention(q, k, v, *, causal: bool = True,
                  window: Optional[int] = None,
                  q_offset=0,
                  kv_valid_len=None,
                  k_start=None,
                  kv_chunk: int = 1024,
                  use_pallas: bool = False):
    """Online-softmax GQA.

    q: (B, Sq, Hq, D);  k, v: (B, Sk, Hkv, D), Hq % Hkv == 0.
    ``q_offset``: absolute position of q[0] (decode: current length).
    ``kv_valid_len``: live prefix of the KV buffers (decode caches).
    ``k_start``: absolute position of k[0] (sliding-window ring caches hold
    the LAST Sk positions; entries with negative positions are masked).
    Returns (B, Sq, Hq, D).
    """
    B, Sq, Hq, D = q.shape
    Sk, Hkv = k.shape[1], k.shape[2]
    G = Hq // Hkv
    scale = D ** -0.5

    if use_pallas and Sq == Sk and kv_valid_len is None:
        from repro.kernels import ops as kops
        return kops.flash_attention(q, k, v, causal=causal, window=window)

    qg = q.reshape(B, Sq, Hkv, G, D) * scale
    q_pos = jnp.asarray(q_offset)[..., None] + jnp.arange(Sq)

    def block(acc_m_l, kc, vc, k_pos):
        acc, m, l = acc_m_l
        s = jnp.einsum("bqhgd,bkhd->bhgqk", qg, kc,
                       preferred_element_type=jnp.float32)
        s = s + _mask_bias(q_pos, k_pos, causal=causal, window=window,
                           kv_valid_len=kv_valid_len)
        m_new = jnp.maximum(m, s.max(axis=-1))
        p = jnp.exp(s - m_new[..., None])
        corr = jnp.exp(m - m_new)
        l = l * corr + p.sum(axis=-1)
        pv = jnp.einsum("bhgqk,bkhd->bhgqd", p.astype(kc.dtype), vc,
                        preferred_element_type=jnp.float32)
        acc = acc * corr[..., None] + pv
        return (acc, m_new, l)

    acc0 = jnp.zeros((B, Hkv, G, Sq, D), jnp.float32)
    m0 = jnp.full((B, Hkv, G, Sq), NEG_INF, jnp.float32)
    l0 = jnp.zeros((B, Hkv, G, Sq), jnp.float32)

    k0 = 0 if k_start is None else k_start
    if Sk <= kv_chunk or Sk % kv_chunk != 0:
        acc, m, l = block((acc0, m0, l0), k, v, k0 + jnp.arange(Sk))
    elif k_start is not None:
        raise NotImplementedError("k_start with chunked KV not needed: "
                                  "window caches fit one chunk")
    else:
        n_chunks = Sk // kv_chunk
        ks = k.reshape(B, n_chunks, kv_chunk, Hkv, D).swapaxes(0, 1)
        vs = v.reshape(B, n_chunks, kv_chunk, Hkv, D).swapaxes(0, 1)
        offs = jnp.arange(n_chunks) * kv_chunk

        def body(carry, xs):
            kc, vc, off = xs
            k_pos = off + jnp.arange(kv_chunk)
            return block(carry, kc, vc, k_pos), None

        (acc, m, l), _ = jax.lax.scan(body, (acc0, m0, l0), (ks, vs, offs))

    out = acc / jnp.maximum(l[..., None], 1e-30)          # (B,Hkv,G,Sq,D)
    out = out.transpose(0, 3, 1, 2, 4).reshape(B, Sq, Hq, D)
    return out.astype(q.dtype)
