"""Paged multi-query attention: each slot's live pages, straight from HBM.

A window of W queries per sequence attends to a KV prefix that lives in
non-contiguous fixed-size pages (:mod:`repro.serve.pages`).  Instead of
materializing the gathered (B, S, Hkv, D) cache in HBM — the jnp fallback in
:mod:`repro.models.attention` — the kernel copies each slot's live pages
HBM->VMEM itself and keeps the online-softmax state (acc, m, l) in VMEM
scratch.  Its cost follows the slots' live lengths, not the table width P.

Grid = (B,), one step per slot; (B, row blocks) where a slot's query rows
exceed ``ROW_BLOCK``, each step then the rows of some whole KV heads (and
the slot's pages read once a step).  Inside it a ``fori_loop`` runs over the
slot's live blocks only: ``cdiv(lengths[b] + W - 1, block_tokens)`` of
them, read from the scalar-prefetched ``lengths``, so a slot whose window
reads nothing does no iterations.  A block is ``_block_pages`` whole pages
(at least 128 tokens).  Its pages are brought in by one manual DMA per page
and per leaf, from ``table[b, i]`` into a double buffer: block i+1's copies
are in flight while block i computes.  A page past the window's reach is
not copied.

Per block the kernel loops over the KV heads statically: one
(W*G, block_tokens) score tile per head (G = grouped q heads per KV head, W
query rows stacked head-major so row r serves window position ``r // G``).
The causal rule is per row: window position w may read KV positions
``< lengths[b] + w`` — ``lengths`` counts valid KV entries *including*
window position 0's token (all W tokens' K/V must be written to their
pages before the call).  Masked scores become NEG_INF and masked
probabilities are zeroed, not renormalized with exp(0), so a row whose
limit ends before a visited block contributes nothing; V rows past the
window's reach are zeroed too, so whatever a block's unread tail holds
(stale VMEM, pages of other sequences) never reaches the output.  A slot
with nothing to read (length 0) produces a zero row 0.

W = 1 is the single-query decode kernel: same per-head math, block loop
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
BLOCK_TOKENS = 128          # a block's least width: one MXU tile of keys
CHUNK_ROWS = 128            # query rows scored against a block at once
ROW_BLOCK = 4096            # query rows one grid step holds (whole KV heads)
# A wide window's call holds ~6 MB of VMEM as the v5e compiler counts it
# for a described chip, and the chip's own count has read twice such a
# figure: room above the 16 MB default scoped limit.
VMEM_LIMIT = 32 * 2**20


def _block_pages(page_size: int, n_table: int) -> int:
    """Whole pages per block: at least BLOCK_TOKENS tokens, at most the
    table."""
    return max(1, min(-(-BLOCK_TOKENS // page_size), n_table))


def _scale_row(buf, slot, block_pages: int, page_rows: int):
    """A block's per-(token, head) scales as one (1, bt*Hkv) lane row:
    the pages' rows side by side, each cut to its ``page_rows`` lanes."""
    return jnp.concatenate([buf[slot, j][:, :page_rows]
                            for j in range(block_pages)], axis=-1)


def _paged_mq_kernel(tbl_ref, len_ref, base_ref, q_ref, k_hbm, v_hbm, *rest,
                     scale: float, page_size: int, block_pages: int,
                     n_heads: int, n_table: int, window: int, group: int,
                     quantized: bool, row_blocks: int):
    # value pages lie ``base`` pages into their (stacked) pool, scale pages
    # at the table's own ids: see :func:`_paged_mq`
    base = base_ref[0]
    if quantized:
        (ks_hbm, vs_hbm, o_ref, k_buf, v_buf, ks_buf, vs_buf, sems,
         acc_ref, m_ref, l_ref) = rest
        leaves = ((k_hbm, k_buf, base), (v_hbm, v_buf, base),
                  (ks_hbm, ks_buf, 0), (vs_hbm, vs_buf, 0))
    else:
        o_ref, k_buf, v_buf, sems, acc_ref, m_ref, l_ref = rest
        leaves = ((k_hbm, k_buf, base), (v_hbm, v_buf, base))
    b = pl.program_id(0)
    length = len_ref[b]
    # the deepest-reaching row (w = window-1) reads positions < reach
    reach = length + window - 1
    bt = block_pages * page_size
    page_rows = page_size * n_heads         # a page's rows: (token, head)
    rows = window * group                   # query rows of one KV head
    # this step's query rows: whole KV heads, from head ``h0`` on
    step_heads = n_heads // row_blocks
    h0 = pl.program_id(1) * step_heads if row_blocks > 1 else 0
    n_blocks = jnp.minimum((reach + bt - 1) // bt,
                           -(-n_table // block_pages))

    def copy_block(blk, slot, start: bool):
        """Start (or wait for) the DMAs of block ``blk`` into buffer
        ``slot``: one per page and leaf, skipping pages past the reach.
        A loop, not unrolled code: each unrolled page would be traced
        anew for every program that holds the kernel."""
        def page_copies(j, carry):
            idx = blk * block_pages + j
            page = tbl_ref[b * n_table + jnp.minimum(idx, n_table - 1)]
            row = pl.multiple_of(j * page_rows, page_rows)

            @pl.when(jnp.logical_and(idx < n_table,
                                     idx * page_size < reach))
            def _():
                for src, buf, first in leaves:
                    # values land as rows of the block, scales as a page
                    dst = (buf.at[slot, pl.ds(row, page_rows)]
                           if buf.ndim == 3 else buf.at[slot, j])
                    cp = pltpu.make_async_copy(src.at[first + page], dst,
                                               sems.at[slot])
                    if start:
                        cp.start()
                    else:
                        cp.wait()
            return carry

        jax.lax.fori_loop(0, block_pages, page_copies, 0)

    acc_ref[...] = jnp.zeros_like(acc_ref)
    m_ref[...] = jnp.full_like(m_ref, NEG_INF)
    l_ref[...] = jnp.zeros_like(l_ref)

    @pl.when(n_blocks > 0)
    def _prefetch_first():
        copy_block(0, 0, start=True)

    def body(i, carry):
        slot = i % 2

        @pl.when(i + 1 < n_blocks)
        def _prefetch_next():
            copy_block(i + 1, 1 - slot, start=True)

        copy_block(i, slot, start=False)

        # Block rows are (token, head) pairs, row c = token c // n_heads of
        # the block, KV head c % n_heads: the pool's own order, so a page
        # lands in VMEM as one contiguous slab.
        k = k_buf[slot].astype(q_ref.dtype)              # (bt*Hkv, D)
        v = v_buf[slot].astype(q_ref.dtype)
        # the block's rows as lanes of a score tile: head and position
        col = jax.lax.broadcasted_iota(jnp.int32, (1, bt * n_heads), 1)
        col_head, col_tok = col % n_heads, i * bt + col // n_heads
        # rows past the window's reach may hold anything (stale VMEM, a
        # page never copied): zero them so 0 * garbage cannot reach acc
        tok = i * bt + jax.lax.broadcasted_iota(
            jnp.int32, (bt * n_heads, 1), 0) // n_heads
        v = jnp.where(tok < reach, v, jnp.zeros_like(v))
        if quantized:
            # fused dequant: the int8 pages crossed HBM->VMEM (the
            # bandwidth win); int8 values are exact in q's dtype, and the
            # per-(token, head) scales, one lane per block row, multiply
            # the scores (K) and the probabilities (V) in float32
            ks = _scale_row(ks_buf, slot, block_pages, page_rows)
            vs = _scale_row(vs_buf, slot, block_pages, page_rows)
            vs = jnp.where(col_tok < reach, vs, 0.0)

        # Query rows are (head, window position, group) — row r serves
        # KV head h0 + r // rows and window position (r % rows) // group —
        # and are scored in chunks against every block row; a score counts
        # only where the row's head is the column's.  A chunk is the most
        # whole heads that divide the step's heads and fit CHUNK_ROWS, or
        # CHUNK_ROWS rows of one head whose rows exceed it.
        per_chunk = max(d for d in range(1, step_heads + 1)
                        if step_heads % d == 0
                        and (d == 1 or d * rows <= CHUNK_ROWS))
        n = per_chunk * rows
        if rows > CHUNK_ROWS and rows % CHUNK_ROWS == 0:
            n = CHUNK_ROWS

        def attend(r0):
            row = r0 + jax.lax.broadcasted_iota(jnp.int32, (n, 1), 0)
            valid = jnp.logical_and(
                col_head == h0 + row // rows,
                col_tok < length + (row % rows) // group)    # (n, bt*Hkv)
            s = jax.lax.dot_general(q_ref[0, pl.ds(r0, n)], k,
                                    (((1,), (1,)), ((), ())),
                                    preferred_element_type=jnp.float32)
            s = s * (ks * scale) if quantized else s * scale
            s = jnp.where(valid, s, NEG_INF)

            m_prev = m_ref[pl.ds(r0, n)]                 # (n, 1)
            m_new = jnp.maximum(m_prev, s.max(axis=-1, keepdims=True))
            pr = jnp.exp(s - m_new)
            # A row may be fully masked in a visited block (its limit ends
            # in an earlier one): m_new stays NEG_INF and exp(s - m_new)
            # would be exp(0) = 1.  Zero masked probabilities explicitly —
            # a bitwise no-op for live rows, where exp(NEG_INF - finite)
            # underflows to 0.
            pr = jnp.where(valid, pr, 0.0)
            corr = jnp.exp(m_prev - m_new)
            l_ref[pl.ds(r0, n)] = (l_ref[pl.ds(r0, n)] * corr
                                   + pr.sum(axis=-1, keepdims=True))
            pv = pr * vs if quantized else pr
            acc_ref[pl.ds(r0, n)] = (
                acc_ref[pl.ds(r0, n)] * corr
                + jax.lax.dot_general(pv.astype(v.dtype), v,
                                      (((1,), (0,)), ((), ())),
                                      preferred_element_type=jnp.float32))
            m_ref[pl.ds(r0, n)] = m_new

        n_chunks = step_heads * rows // n
        if n_chunks > 1 and n % 16 == 0:
            # a loop, not unrolled code: one chunk's temporaries in VMEM
            def chunk(j, carry):
                attend(pl.multiple_of(j * n, 16))
                return carry
            jax.lax.fori_loop(0, n_chunks, chunk, 0)
        else:
            for j in range(n_chunks):
                attend(j * n)
        return carry

    jax.lax.fori_loop(0, n_blocks, body, 0)
    l = jnp.maximum(l_ref[...], 1e-30)
    o_ref[0] = (acc_ref[...] / l).astype(o_ref.dtype)


def paged_attention_mq(q, k_pages, v_pages, tables, lengths, *,
                       k_scale=None, v_scale=None, layer=None,
                       interpret: bool = False):
    """q: (B, W, Hq, D); k_pages/v_pages: (N, page_size, Hkv, D);
    tables: (B, P) int32 page ids; lengths: (B,) int32 valid-KV counts for
    window position 0 (including its own token) -> (B, W, Hq, D).

    Window position w attends to KV positions < lengths + w (per-row causal
    offset); rows past a sequence's data (pad rows, dead slots) are never
    read by callers and may hold garbage softmaxed over trash pages.

    Scores and P·V run on the MXU in q's dtype with float32 accumulation;
    the softmax state is float32.

    ``k_scale``/``v_scale``: optional (N, page_size, Hkv) per-(row, head)
    dequantization scales for int8 pages.  Each page's scales are copied
    beside its values and applied in VMEM, to the scores (K) and to the
    probabilities (V) — the page stream out of HBM stays int8, which is
    where the 4x bandwidth cut happens.

    ``layer``: with it, ``k_pages``/``v_pages`` are a stacked pool of
    layers, (L, N, page_size, Hkv, D), whose layer ``layer`` (a traced
    int32) the kernel reads in place; ``tables`` and the scale leaves are
    that layer's own.  What a call copies whole (its scale leaves as lane
    rows, a head dim padded to lanes) is thus one layer's at most.
    """
    D = q.shape[-1]
    if layer is None:
        k_pages, v_pages, layer = k_pages[None], v_pages[None], 0
    assert q.shape[2] % k_pages.shape[3] == 0, (q.shape, k_pages.shape)
    assert (k_scale is None) == (v_scale is None), "pass both scales or none"
    if D % 128 == 0:
        return _paged_mq(q, k_pages, v_pages, tables, lengths, k_scale,
                         v_scale, layer, scale=D ** -0.5,
                         interpret=interpret)
    # the TPU copies only whole 128-lane rows out of HBM: zero-pad the head
    # dim (a copy of the layer's pages, for heads narrower than a lane tile
    # only; zeros change no dot product)
    pad = ((0, 0),) * 4 + ((0, -D % 128),)
    q = jnp.pad(q, pad[1:])
    k_pages, v_pages = (jnp.pad(x[layer][None], pad)
                        for x in (k_pages, v_pages))
    return _paged_mq(q, k_pages, v_pages, tables, lengths, k_scale, v_scale,
                     0, scale=D ** -0.5, interpret=interpret)[..., :D]


def _paged_mq(q, k_pages, v_pages, tables, lengths, k_scale, v_scale,
              layer, *, scale: float, interpret: bool):
    B, W, Hq, D = q.shape
    n_layers, N, page_size, Hkv, _ = k_pages.shape
    P = tables.shape[1]
    quantized = k_scale is not None
    G = Hq // Hkv
    R = Hkv * W * G
    bp = _block_pages(page_size, P)
    bt = bp * page_size
    # the grid splits a slot's query rows into steps of whole KV heads, as
    # many as fit ROW_BLOCK rows (one at least): wide windows of many heads
    # (a prefill chunk at a GQA group of 16) would not fit VMEM at once
    step_heads = max(d for d in range(1, Hkv + 1) if Hkv % d == 0
                     and (d == 1 or d * W * G <= ROW_BLOCK))
    row_blocks = Hkv // step_heads
    RB = R // row_blocks

    # (B, W, Hkv, G, D) -> (B, Hkv, W, G, D) -> rows stacked head-major:
    # row r is KV head r // (W*G), window position (r % (W*G)) // G.
    qg = (q.reshape(B, W, Hkv, G, D).transpose(0, 2, 1, 3, 4)
          .reshape(B, R, D))
    # flat: a 2-D table in scalar memory pads its rows
    tables = tables.astype(jnp.int32).reshape(-1)
    lengths = lengths.astype(jnp.int32)
    # a page as (token, head) rows: the pool's own layout, a free reshape;
    # the layers' pools one after another, layer ``layer``'s from page
    # ``base`` on
    rows = (n_layers * N, page_size * Hkv)
    base = (jnp.asarray(layer, jnp.int32) * N).reshape(1)
    operands = [qg, k_pages.reshape(rows + (D,)),
                v_pages.reshape(rows + (D,))]
    buffers = [pltpu.VMEM((2, bt * Hkv, D), k_pages.dtype),
               pltpu.VMEM((2, bt * Hkv, D), v_pages.dtype)]
    if quantized:
        # A page's scales as one lane row, padded to whole lane tiles: the
        # TPU copies only tile-aligned slices out of HBM.  A small copy of
        # the layer's scale leaf (1/D of its values' elements), not of the
        # pages.
        lanes = -(-page_size * Hkv // 128) * 128
        for sc in (k_scale, v_scale):
            sc = sc.reshape(N, 1, page_size * Hkv)
            operands.append(jnp.pad(
                sc, ((0, 0), (0, 0), (0, lanes - page_size * Hkv))))
            buffers.append(pltpu.VMEM((2, bp, 1, lanes), sc.dtype))

    if row_blocks == 1:
        grid = (B,)

        def rows_index(b, tbl, ln, base):
            return (b, 0, 0)
    else:
        grid = (B, row_blocks)

        def rows_index(b, j, tbl, ln, base):
            return (b, j, 0)

    kernel = functools.partial(
        _paged_mq_kernel, scale=scale, page_size=page_size,
        block_pages=bp, n_heads=Hkv, n_table=P, window=W, group=G,
        quantized=quantized, row_blocks=row_blocks)
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=3,
        grid=grid,
        # q and out blocks span their arrays' last dim in full and whole
        # KV heads of rows, the layout the TPU compiler accepts for any
        # Hkv, W*G and D
        in_specs=[pl.BlockSpec((1, RB, D), rows_index)]
        + [pl.BlockSpec(memory_space=pltpu.HBM)] * (len(operands) - 1),
        out_specs=pl.BlockSpec((1, RB, D), rows_index),
        scratch_shapes=buffers + [
            pltpu.SemaphoreType.DMA((2,)),
            pltpu.VMEM((RB, D), jnp.float32),
            pltpu.VMEM((RB, 1), jnp.float32),
            pltpu.VMEM((RB, 1), jnp.float32),
        ],
    )
    out = pl.pallas_call(
        kernel,
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((B, R, D), q.dtype),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel",) * len(grid),
            vmem_limit_bytes=VMEM_LIMIT),
        interpret=interpret,
        name="paged_attention",
    )(tables, lengths, base, *operands)
    return (out.reshape(B, Hkv, W, G, D).transpose(0, 2, 1, 3, 4)
            .reshape(B, W, Hq, D))


def paged_attention(q, k_pages, v_pages, tables, lengths, *,
                    k_scale=None, v_scale=None, interpret: bool = False):
    """Single-query decode: q (B, Hq, D) -> (B, Hq, D).  W=1 window of
    :func:`paged_attention_mq`; ``lengths`` includes the current token."""
    return paged_attention_mq(q[:, None], k_pages, v_pages, tables, lengths,
                              k_scale=k_scale, v_scale=v_scale,
                              interpret=interpret)[:, 0]
