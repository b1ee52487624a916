"""Paged multi-query attention: gather K/V through a page table, on-chip.

A window of W queries per sequence attends to a KV prefix that lives in
non-contiguous fixed-size pages (:mod:`repro.serve.pages`).  Instead of
materializing the gathered (B, S, Hkv, D) cache in HBM — the jnp fallback in
:mod:`repro.models.attention` — the kernel streams each sequence's pages
HBM->VMEM directly via a scalar-prefetched page table: BlockSpec index maps
read ``table[b, p]`` to pick the page, so the DMA engine performs the gather
and the online-softmax state (acc, m, l) never leaves VMEM scratch.

Grid = (B, pages_per_seq) with pages innermost.  Each step DMAs one whole
page (all KV heads) and loops over the heads statically: one
(W*G, page_size) score tile per head (G = grouped q heads per KV head, W
query rows stacked head-major so row r serves window position ``r // G``).
The causal rule is per row: window position w may read KV positions
``< lengths[b] + w`` — ``lengths`` counts valid KV entries *including*
window position 0's token (all W tokens' K/V must be written to their
pages before the call).  Pages
past the LAST row's limit are skipped with ``pl.when`` — cost is
O(lengths + W), not O(pages_per_seq), which is the whole point of paging.
Rows whose limit ends before a visited page contribute nothing (their
probabilities are zeroed, not renormalized with exp(0)); dead slots
(length 0) produce a zero row 0.

W = 1 is the single-query decode kernel: same per-head math, page gate
and mask as every wider window.

Tensor-parallel serving runs this kernel INSIDE a ``shard_map`` body: q and
the page storage arrive head-sharded (Hq/tp, Hkv/tp local heads), the page
table and lengths replicated, and the in-kernel head loop runs over the
local head count — each device streams only its own head shard's pages,
which is what makes the paged step's HBM traffic scale 1/tp.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
import jax.experimental.pallas.tpu as pltpu

NEG_INF = -1e30


def _paged_mq_kernel(tbl_ref, len_ref, q_ref, k_ref, v_ref, *rest,
                     scale: float, page_size: int, n_heads: int,
                     n_pages: int, window: int, group: int,
                     quantized: bool = False):
    if quantized:
        ks_ref, vs_ref, o_ref, acc_ref, m_ref, l_ref = rest
    else:
        o_ref, acc_ref, m_ref, l_ref = rest
    b = pl.program_id(0)
    p = pl.program_id(1)
    length = len_ref[b]

    @pl.when(p == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)
        m_ref[...] = jnp.full_like(m_ref, NEG_INF)
        l_ref[...] = jnp.zeros_like(l_ref)

    # Skip pages no row can see: the deepest-reaching row (w = window-1)
    # reads KV positions < length + window - 1.
    @pl.when(p * page_size < length + window - 1)
    def _compute():
        # One DMA brings the whole page (every KV head); heads are static
        # slices of that VMEM tile, so each page leaves HBM once.
        for h in range(n_heads):
            q = q_ref[0, h].astype(jnp.float32) * scale      # (W*G, D)
            k = k_ref[0, :, h, :].astype(jnp.float32)        # (page_size, D)
            v = v_ref[0, :, h, :].astype(jnp.float32)
            if quantized:
                # fused dequant: the int8 page tile was DMA'd HBM->VMEM (the
                # bandwidth win) and the per-(row, head) scale is applied
                # here in VMEM — the full-precision K/V never exists in HBM
                k = k * ks_ref[0, :, h:h + 1]                # (page_size, 1)
                v = v * vs_ref[0, :, h:h + 1]
            s = jax.lax.dot_general(q, k, (((1,), (1,)), ((), ())),
                                    preferred_element_type=jnp.float32)
            k_pos = p * page_size + jax.lax.broadcasted_iota(
                jnp.int32, s.shape, 1)
            w_row = jax.lax.broadcasted_iota(jnp.int32, s.shape, 0) // group
            valid = k_pos < length + w_row
            s = jnp.where(valid, s, NEG_INF)

            m_prev = m_ref[h]                                # (W*G, 1)
            m_new = jnp.maximum(m_prev, s.max(axis=-1, keepdims=True))
            pr = jnp.exp(s - m_new)
            # A row may be fully masked on a visited page (its limit ends on
            # an earlier page): m_new stays NEG_INF and exp(s - m_new) would
            # be exp(0) = 1.  Zero masked probabilities explicitly — a
            # bitwise no-op for live rows, where exp(NEG_INF - finite)
            # underflows to 0.
            pr = jnp.where(valid, pr, 0.0)
            corr = jnp.exp(m_prev - m_new)
            l_ref[h] = l_ref[h] * corr + pr.sum(axis=-1, keepdims=True)
            acc_ref[h] = (acc_ref[h] * corr
                          + jax.lax.dot_general(
                              pr, v, (((1,), (0,)), ((), ())),
                              preferred_element_type=jnp.float32))
            m_ref[h] = m_new

    @pl.when(p == n_pages - 1)
    def _finish():
        l = jnp.maximum(l_ref[...], 1e-30)
        o_ref[0] = (acc_ref[...] / l).astype(o_ref.dtype)


def paged_attention_mq(q, k_pages, v_pages, tables, lengths, *,
                       k_scale=None, v_scale=None,
                       interpret: bool = False):
    """q: (B, W, Hq, D); k_pages/v_pages: (N, page_size, Hkv, D);
    tables: (B, P) int32 page ids; lengths: (B,) int32 valid-KV counts for
    window position 0 (including its own token) -> (B, W, Hq, D).

    Window position w attends to KV positions < lengths + w (per-row causal
    offset); rows past a sequence's data (pad rows, dead slots) are never
    read by callers and may hold garbage softmaxed over trash pages.

    ``k_scale``/``v_scale``: optional (N, page_size, Hkv) per-(row, head)
    dequantization scales for int8 pages.  They ride the same
    scalar-prefetched page-table index map as their value pages and are
    applied to the K/V tile in VMEM right after the DMA — the page stream
    out of HBM stays int8, which is where the 4x bandwidth cut happens.
    """
    B, W, Hq, D = q.shape
    N, page_size, Hkv, _ = k_pages.shape
    P = tables.shape[1]
    assert Hq % Hkv == 0, (Hq, Hkv)
    assert (k_scale is None) == (v_scale is None), "pass both scales or none"
    quantized = k_scale is not None
    G = Hq // Hkv
    scale = D ** -0.5

    # (B, W, Hkv, G, D) -> (B, Hkv, W, G, D) -> rows stacked head-major:
    # row r of the (W*G, D) tile is window position r // G, grouped head r % G.
    qg = (q.reshape(B, W, Hkv, G, D).transpose(0, 2, 1, 3, 4)
          .reshape(B, Hkv, W * G, D))
    tables = tables.astype(jnp.int32)
    lengths = lengths.astype(jnp.int32)

    def q_index(b, p, tbl, ln):
        return (b, 0, 0, 0)

    def kv_index(b, p, tbl, ln):
        return (tbl[b, p], 0, 0, 0)

    def scale_index(b, p, tbl, ln):
        return (tbl[b, p], 0, 0)

    kernel = functools.partial(_paged_mq_kernel, scale=scale,
                               page_size=page_size, n_heads=Hkv, n_pages=P,
                               window=W, group=G, quantized=quantized)
    # Every block spans its array's last two dims in full, the layout the
    # TPU compiler accepts for any Hkv, W*G and D.
    in_specs = [
        pl.BlockSpec((1, Hkv, W * G, D), q_index),
        pl.BlockSpec((1, page_size, Hkv, D), kv_index),
        pl.BlockSpec((1, page_size, Hkv, D), kv_index),
    ]
    operands = [qg, k_pages, v_pages]
    if quantized:
        in_specs += [pl.BlockSpec((1, page_size, Hkv), scale_index),
                     pl.BlockSpec((1, page_size, Hkv), scale_index)]
        operands += [k_scale, v_scale]
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=2,
        grid=(B, P),
        in_specs=in_specs,
        out_specs=pl.BlockSpec((1, Hkv, W * G, D), q_index),
        scratch_shapes=[
            pltpu.VMEM((Hkv, W * G, D), jnp.float32),
            pltpu.VMEM((Hkv, W * G, 1), jnp.float32),
            pltpu.VMEM((Hkv, W * G, 1), jnp.float32),
        ],
    )
    out = pl.pallas_call(
        kernel,
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((B, Hkv, W * G, D), q.dtype),
        interpret=interpret,
        name="paged_attention",
    )(tables, lengths, *operands)
    return (out.reshape(B, Hkv, W, G, D).transpose(0, 2, 1, 3, 4)
            .reshape(B, W, Hq, D))


def paged_attention(q, k_pages, v_pages, tables, lengths, *,
                    k_scale=None, v_scale=None, interpret: bool = False):
    """Single-query decode: q (B, Hq, D) -> (B, Hq, D).  W=1 window of
    :func:`paged_attention_mq`; ``lengths`` includes the current token."""
    return paged_attention_mq(q[:, None], k_pages, v_pages, tables, lengths,
                              k_scale=k_scale, v_scale=v_scale,
                              interpret=interpret)[:, 0]
