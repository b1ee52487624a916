"""Paged KV-cache storage: refcounted page pool + radix prefix cache.

This is the paper's dynamic-population append/delete applied to *memory*
instead of walkers: the pool's pages are the capacity, requests allocate
pages as they enter and grow, and free them as they leave.  The engine's
footprint becomes ``pages_in_use x page_size`` tokens instead of
``max_slots x max_len`` — short requests stop paying for the longest one.

Pages are **refcounted and content-addressed**: a :class:`PrefixCache`
(radix trie keyed by page-sized token chunks) maps shared prompt prefixes
to pages already holding their K/V, so N requests with a common system
prompt hold ONE copy of its pages.  Lifecycle of a page:

    free ──alloc──> held (rc=1) ──incref──> shared (rc>1)
      ^                │ register (full, content known)
      │                v
      └──evict(LRU)── cached (rc=0, in the index) ──match+incref──> held

A held page that is still registered may be re-shared by a later match;
an unreferenced cached page is an LRU eviction candidate whenever the
free list runs short.  Writers never mutate a shared page: the serving
layer copies it first (:func:`copy_pages`, copy-on-write) or — when it is
the page's only holder — unregisters it and writes in place.

Layering contract (function-centric): this module never looks inside a
model.  A model describes each decode-cache leaf with a
:class:`PagedLeafSpec` (leading dims / trailing dims / dtype around the
token axis) and the pool materializes storage of shape
``prefix + (num_pages, page_size) + suffix`` per leaf.  The pure functions
:func:`scatter_chunk`, :func:`scatter_token`, :func:`gather_pages` and
:func:`copy_pages` are the only ways device code touches that storage, so
the same pool serves the dense, MoE and VLM cache families unchanged.

Host-side bookkeeping (free list, refcounts, radix index, LRU clock) is
deterministic: identical request streams produce identical page tables —
which is what makes cache-on-vs-off token parity testable.
"""

from __future__ import annotations

__all__ = ["CrossKVPool", "KVHandoff", "N_TRASH",
           "PagePool", "PagedLeafSpec", "PrefixCache",
           "copy_pages", "gather_pages", "scatter_chunk",
           "scatter_token", "scatter_window", "tree_deleted",
           "write_slot"]

import dataclasses
import heapq
from collections import deque
from typing import Any, Callable, Optional

import jax
import jax.numpy as jnp
import numpy as np


@dataclasses.dataclass(frozen=True)
class PagedLeafSpec:
    """One decode-cache leaf, described around its token axis.

    A dense cache leaf ``(L, B, S, H, D)`` becomes
    ``prefix=(L,), suffix=(H, D)`` — batch and sequence axes are replaced
    by the pool's ``(num_pages, page_size)`` pair.
    """
    prefix: tuple
    suffix: tuple
    dtype: Any

    def storage_shape(self, num_pages: int, page_size: int) -> tuple:
        """prefix + (num_pages, page_size) + suffix — the pool array shape."""
        return tuple(self.prefix) + (num_pages, page_size) + tuple(self.suffix)


def _is_spec(x) -> bool:
    return isinstance(x, PagedLeafSpec)


def tree_deleted(tree) -> bool:
    """True if any array leaf was consumed by a raising donated call
    (jit donation: the callee took the buffers before failing)."""
    return any(getattr(leaf, "is_deleted", lambda: False)()
               for leaf in jax.tree_util.tree_leaves(tree))


# extra never-allocated page absorbing dead-slot decode writes; storage is
# always materialized with ``num_pages + N_TRASH`` pages
N_TRASH = 1


class _PrefixNode:
    """One cached page: a page-sized token chunk hanging off its parent."""
    __slots__ = ("key", "page", "parent", "children", "last_use")

    def __init__(self, key: bytes, page: int, parent):
        self.key = key                  # the ps int32 tokens, as bytes
        self.page = page
        self.parent = parent
        self.children: dict[bytes, _PrefixNode] = {}
        self.last_use = 0


class PrefixCache:
    """Radix index over cached pages: token-chunk content -> page id.

    Each node is one FULL page whose K/V content is final (its ``page_size``
    tokens are known); a path from the root spells out a token prefix at
    page granularity.  :meth:`match` additionally shares a *partial* last
    page when a cached child covers the request's whole remaining prompt —
    the case that makes decode-time copy-on-write reachable (two requests
    with the same prompt share its final, partially-filled page until one
    of them decodes into it).

    The cache is an index only — refcounts and the free list live on the
    :class:`PagePool`, which consults the index on allocation (LRU leaf
    eviction of unreferenced pages) and on release (parking).
    """

    def __init__(self, page_size: int):
        self.page_size = page_size
        self._root = _PrefixNode(b"", -1, None)
        self._by_page: dict[int, _PrefixNode] = {}
        self._clock = 0

    def __contains__(self, page: int) -> bool:
        return page in self._by_page

    def __len__(self) -> int:
        return len(self._by_page)

    def _tick(self) -> int:
        self._clock += 1
        return self._clock

    def _walk(self, toks: np.ndarray, depth: int) -> Optional[_PrefixNode]:
        """The node spelling ``toks[:depth * page_size]`` (root for 0)."""
        ps, cur = self.page_size, self._root
        for i in range(depth):
            cur = cur.children.get(toks[i * ps:(i + 1) * ps].tobytes())
            if cur is None:
                return None
        return cur

    def match(self, toks: np.ndarray) -> tuple[list[int], int]:
        """Longest cached prefix of ``toks``: (page ids, tokens covered).

        Walks full-page chunks, then tries one partial step: a child whose
        content begins with the ENTIRE remaining prompt extends the match
        to ``len(toks)`` (the sharer's tail page covers our last tokens).
        Matched nodes get their LRU stamp bumped.
        """
        toks = np.ascontiguousarray(toks, np.int32)
        total, ps = len(toks), self.page_size
        cur, pages, k = self._root, [], 0
        while (k + 1) * ps <= total:
            child = cur.children.get(toks[k * ps:(k + 1) * ps].tobytes())
            if child is None:
                break
            child.last_use = self._tick()
            pages.append(child.page)
            cur, k = child, k + 1
        rem = total - k * ps
        if rem > 0:
            pre = toks[k * ps:].tobytes()
            cands = [c for c in cur.children.values()
                     if c.key.startswith(pre)]
            if cands:
                best = max(cands, key=lambda c: c.last_use)
                best.last_use = self._tick()
                pages.append(best.page)
                return pages, total
        return pages, k * ps

    def insert(self, toks: np.ndarray, depth: int, page: int) -> bool:
        """Register ``page`` as chunk ``depth`` of sequence ``toks``.

        First registration wins: an existing node for the same chunk (from
        another request that computed the same prefix) keeps its page and
        only gets an LRU bump.  Returns True iff ``page`` was registered.
        Registration requires the parent chain to exist (chunks register
        in order, so it does — unless an unregistered ancestor blocked it).
        """
        toks = np.ascontiguousarray(toks, np.int32)
        ps = self.page_size
        if (depth + 1) * ps > len(toks) or page in self._by_page:
            return False
        cur = self._walk(toks, depth)
        if cur is None:
            return False
        key = toks[depth * ps:(depth + 1) * ps].tobytes()
        node = cur.children.get(key)
        if node is not None:
            node.last_use = self._tick()
            return False
        node = _PrefixNode(key, int(page), cur)
        node.last_use = self._tick()
        cur.children[key] = node
        self._by_page[int(page)] = node
        return True

    def touch(self, page: int) -> None:
        """Refresh a cached page's LRU timestamp (prefix re-match)."""
        node = self._by_page.get(page)
        if node is not None:
            node.last_use = self._tick()

    def forget(self, page: int) -> list[int]:
        """Unregister ``page`` AND its whole subtree (descendants spell
        longer sequences through the mutated page — their chain is broken).
        Returns every unregistered page id, ``page`` first."""
        node = self._by_page.get(page)
        if node is None:
            return []
        del node.parent.children[node.key]
        dropped, stack = [], [node]
        while stack:
            nd = stack.pop()
            dropped.append(nd.page)
            del self._by_page[nd.page]
            stack.extend(nd.children.values())
        return dropped

    def evict_leaves(self, n: int, evictable: Callable[[int], bool]
                     ) -> list[int]:
        """Drop up to ``n`` LRU *leaf* nodes whose page passes ``evictable``
        (the pool passes "refcount == 0").  Leaf-first keeps every surviving
        chain matchable; freeing a parent would orphan its descendants.

        One scan seeds a heap of current leaves; evicting a node can only
        expose its parent as the next candidate, so the loop stays linear
        instead of rescanning the whole index per page."""
        heap = [(nd.last_use, nd.page) for nd in self._by_page.values()
                if not nd.children]
        heapq.heapify(heap)
        dropped: list[int] = []
        while heap and len(dropped) < n:
            stamp, page = heapq.heappop(heap)
            nd = self._by_page.get(page)
            if nd is None or nd.children or not evictable(page):
                continue
            if nd.last_use != stamp:        # touched since seeding: re-queue
                heapq.heappush(heap, (nd.last_use, page))
                continue
            parent = nd.parent
            del parent.children[nd.key]
            del self._by_page[page]
            dropped.append(page)
            if parent is not self._root and not parent.children:
                heapq.heappush(heap, (parent.last_use, parent.page))
        return dropped


class PagePool:
    """Refcounted fixed-size KV pages with a FIFO free list.

    One extra *trash* page (index ``num_pages``) is always allocated so
    batched decode can keep dead slots in the SPMD step: their token writes
    land in the trash page instead of corrupting a live one.

    With ``prefix_cache=True`` the pool carries a :class:`PrefixCache`:
    pages whose refcount drops to zero while registered are *parked* in the
    cache (evictable LRU) instead of returning to the free list, and
    :meth:`alloc` transparently evicts parked pages when the free list runs
    short.  ``pages_free + pages_cached + pages_in_use == num_pages``
    always — the partition the property tests check.
    """

    def __init__(self, leaf_specs, *, num_pages: int, page_size: int,
                 shardings=None, prefix_cache: bool = False):
        """``shardings``: optional pytree of ``jax.sharding.Sharding``
        matching ``leaf_specs`` — mesh serving materializes the KV storage
        already partitioned (heads over the "model" axis) so no leaf ever
        exists unsharded on one device."""
        assert num_pages >= 1 and page_size >= 1
        self.leaf_specs = leaf_specs
        self.num_pages = num_pages
        self.page_size = page_size
        self.trash_page = num_pages            # valid index, never allocated
        self._shardings = shardings
        self.storage = self._fresh_storage()
        self._free: deque[int] = deque(range(num_pages))
        self._free_set: set[int] = set(self._free)
        self._ref = np.zeros(num_pages, np.int64)
        self.prefix = PrefixCache(page_size) if prefix_cache else None
        self._n_cached = 0
        self.evictions = 0
        self._high_water = 0

    def _fresh_storage(self):
        def zeros(s):
            return jnp.zeros(
                s.storage_shape(self.num_pages + N_TRASH, self.page_size),
                s.dtype)
        if self._shardings is None:
            return jax.tree_util.tree_map(zeros, self.leaf_specs,
                                          is_leaf=_is_spec)
        return jax.tree_util.tree_map(
            lambda s, sh: jax.device_put(zeros(s), sh),
            self.leaf_specs, self._shardings, is_leaf=_is_spec)

    def storage_deleted(self) -> bool:
        """True if any storage buffer was consumed (a jitted call with
        donation that raised after taking its arguments)."""
        return tree_deleted(self.storage)

    def reset_storage(self) -> None:
        """Rebuild zeroed storage with the original shapes/shardings.  The
        KV *contents* are gone — callers must evict every resident request
        first (recompute-style re-prefill preserves their streams); the
        prefix cache is flushed for the same reason (its entries point at
        content that no longer exists)."""
        self.storage = self._fresh_storage()
        self.flush_cache()

    def flush_cache(self) -> None:
        """Drop every prefix-cache entry.  Parked (unreferenced) pages
        return to the free list; held pages just lose their registration."""
        if self.prefix is None:
            return
        cached = list(self.prefix._by_page)
        self.prefix = PrefixCache(self.page_size)
        for p in cached:
            if self._ref[p] == 0:
                self._free_push(p)
        self._n_cached = 0

    # -- host-side accounting -------------------------------------------------

    @property
    def pages_free(self) -> int:
        """Pages on the free list (unreferenced, unregistered)."""
        return len(self._free)

    @property
    def pages_cached(self) -> int:
        """Registered pages no request holds (LRU eviction candidates)."""
        return self._n_cached

    @property
    def pages_in_use(self) -> int:
        """Pages referenced by at least one slot (free + cached excluded)."""
        return self.num_pages - len(self._free) - self._n_cached

    @property
    def high_water(self) -> int:
        """Max pages simultaneously referenced since construction."""
        return self._high_water

    def ref(self, page: int) -> int:
        """Current refcount of one page."""
        return int(self._ref[page])

    def _free_push(self, page: int) -> None:
        self._free.append(int(page))
        self._free_set.add(int(page))

    def _note_usage(self) -> None:
        self._high_water = max(self._high_water, self.pages_in_use)

    def alloc(self, n: int) -> Optional[list[int]]:
        """Pop ``n`` exclusive pages (refcount 1), or None if exhausted
        (allocate-all-or-nothing).  When the free list runs short, LRU
        unreferenced cached pages are evicted to cover the shortfall."""
        if n < 0:
            return None
        if len(self._free) < n and self.prefix is not None:
            dropped = self.prefix.evict_leaves(
                n - len(self._free), lambda p: self._ref[p] == 0)
            for p in dropped:
                self._n_cached -= 1
                self._free_push(p)
            self.evictions += len(dropped)
        if len(self._free) < n:
            return None
        pages = [self._free.popleft() for _ in range(n)]
        for p in pages:
            self._free_set.discard(p)
            self._ref[p] = 1
        self._note_usage()
        return pages

    def incref(self, pages) -> None:
        """Take a reference on already-registered pages (a prefix match).
        Unreferenced cached pages move from the cache partition to held."""
        for p in pages:
            p = int(p)
            assert 0 <= p < self.num_pages, p
            if self._ref[p] == 0:
                if self.prefix is None or p not in self.prefix:
                    raise ValueError(
                        f"incref of page {p} that is neither held nor cached")
                self._n_cached -= 1
            self._ref[p] += 1
        self._note_usage()

    def decref(self, pages) -> None:
        """Drop one reference per page.  A page reaching refcount zero is
        parked in the prefix cache if registered (it stays matchable and
        becomes an LRU eviction candidate), else returned to the free list.
        Decref below zero raises — the refcount twin of a double free."""
        for p in pages:
            p = int(p)
            if not 0 <= p < self.num_pages:
                raise ValueError(f"decref of invalid page id {p}")
            if self._ref[p] <= 0:
                raise ValueError(
                    f"decref of page {p} below zero (double release)")
            self._ref[p] -= 1
            if self._ref[p] == 0:
                if self.prefix is not None and p in self.prefix:
                    self._n_cached += 1
                    self.prefix.touch(p)
                else:
                    self._free_push(p)

    def unregister(self, page: int) -> None:
        """Drop ``page`` (and any cached descendants) from the prefix index
        — the write-in-place path when its single holder is about to mutate
        it.  Unreferenced descendants return to the free list."""
        if self.prefix is None:
            return
        for q in self.prefix.forget(page):
            if self._ref[q] == 0:
                self._n_cached -= 1
                self._free_push(q)

    def free(self, pages) -> None:
        """Return exclusively-held pages to the free list.  Freeing a page
        already on the free list, or one still shared (refcount > 1),
        raises instead of silently corrupting the FIFO order — release
        paths must go through :meth:`decref`."""
        for p in pages:
            p = int(p)
            if not 0 <= p < self.num_pages:
                raise ValueError(f"free of invalid page id {p}")
            if p in self._free_set:
                raise ValueError(f"double free of page {p}")
            if self._ref[p] > 1:
                raise ValueError(
                    f"free of page {p} with refcount {int(self._ref[p])}; "
                    "shared pages are released via decref")
            if self.prefix is not None and p in self.prefix:
                if self._ref[p] == 0:       # was parked in the cache
                    self._n_cached -= 1
                for q in self.prefix.forget(p):
                    if q != p and self._ref[q] == 0:    # orphaned descendants
                        self._n_cached -= 1
                        self._free_push(q)
            self._ref[p] = 0
            self._free_push(p)

    def tokens_capacity(self) -> int:
        """Total token positions the pool can hold."""
        return self.num_pages * self.page_size


class CrossKVPool(PagePool):
    """Refcounted pages for encoder–decoder *cross-attention* K/V.

    Whisper-style serving computes each request's cross K/V exactly once
    (from the audio encoder's output) and then only ever *reads* it during
    decode — so this pool is a deliberately narrowed :class:`PagePool`:

    * **Read-only after prefill.** Pages are written once by the encode
      path's scatter and never mutated, so copy-on-write never applies and
      the pool refuses a prefix cache (cross K/V is keyed by audio content,
      not by token prefixes — the radix index would never match it).
    * **Refcounts still matter.** Release and preemption go through the
      same ``alloc`` / ``decref`` / ``free`` lifecycle as self-attention
      pages, so the conservation invariant ``pages_free + pages_in_use ==
      num_pages`` holds under forced preemption (property-tested).
    * **Quantization composes.** int8 cross pages carry per-(row, head)
      scale leaves exactly like self-attention pages
      (:func:`repro.serve.quant.quantize_leaf_specs`); the decode-time
      gather dequantizes after the read.

    The trash page exists here too: dead decode slots point their cross
    page table at it (with ``frames_len = 0`` masking the whole read).
    """

    def __init__(self, leaf_specs, *, num_pages: int, page_size: int,
                 shardings=None, prefix_cache: bool = False):
        if prefix_cache:
            raise ValueError(
                "CrossKVPool does not support a prefix cache: cross K/V is "
                "content-addressed by audio, not by token prefixes")
        super().__init__(leaf_specs, num_pages=num_pages,
                         page_size=page_size, shardings=shardings,
                         prefix_cache=False)


@dataclasses.dataclass
class KVHandoff:
    """A completed prefill's KV in flight between two pools — the unit of
    disaggregated prefill/decode transfer (:mod:`repro.serve.disagg`).

    The prefiller gathers the request's pages into a contiguous chunk
    (``kv``: one leaf per pool leaf, shaped ``prefix + (n * page_size,) +
    suffix`` — int8 payloads travel WITH their scale leaves, since scales
    are ordinary pool leaves), takes one extra reference per source page,
    and releases the slot.  The held references pin the source pages —
    they may stay registered in the prefiller's prefix cache and be
    re-shared by later admissions, but can never be evicted or reallocated
    — until the decoder has scattered the chunk into its own pool and the
    coordinator calls :meth:`release`.  ``release`` is idempotent: the
    in-flight references are dropped exactly once, so a retry loop that
    races a preemption can never double-free.
    """
    req: Any                    # the Request, with its first token appended
    length: int                 # prefilled positions (first token excluded)
    kv: Any                     # gathered storage pytree (see above)
    pages: list                 # source page ids holding the in-flight refs
    pool: Any                   # source PagePool
    released: bool = False

    def release(self) -> None:
        """Drop the handoff's page references (idempotent)."""
        if self.released:
            return
        self.released = True
        self.pool.decref(self.pages)


# ---------------------------------------------------------------------------
# Pure device ops (jit-safe; storage in, storage out)
# ---------------------------------------------------------------------------

def _pfx(n_prefix: int) -> tuple:
    return (slice(None),) * n_prefix


def _check_write_dtype(storage, vals, op: str):
    """Scatter writes must arrive already in the pool's storage dtype.

    The old behavior silently ``.astype``'d the values — which turned a
    missing quantization step (f32 K/V written into an int8 pool) or a
    precision mismatch (f32 into bf16) into wrong cached numbers with no
    error.  Conversions are now explicit at the call site: the model
    writes K/V in the cache dtype, and a quantization policy produces the
    int8 payload + scale before the scatter.  Dtypes are static under
    ``jit``, so this raises at trace time, not per step.
    """
    if jnp.dtype(vals.dtype) != jnp.dtype(storage.dtype):
        raise TypeError(
            f"{op}: value dtype {jnp.dtype(vals.dtype).name} != storage "
            f"dtype {jnp.dtype(storage.dtype).name}; convert (or quantize) "
            "explicitly before the scatter — implicit lossy casts are not "
            "performed")


def scatter_chunk(storage, pages, chunk, *, page_size: int, n_prefix: int = 0):
    """Write a page-aligned token chunk into its pages.

    storage: (prefix..., N, page_size, suffix...)
    pages:   (n,) int32 page ids
    chunk:   (prefix..., n * page_size, suffix...) — in the storage dtype
    """
    _check_write_dtype(storage, chunk, "scatter_chunk")
    n = pages.shape[0]
    pre = chunk.shape[:n_prefix]
    suf = chunk.shape[n_prefix + 1:]
    # written as a page of (token, head) rows, the layout the attention
    # kernel reads: a scatter of narrow (Hkv, D) tiles (few KV heads, or
    # one page, which becomes an update of a slice) has the TPU compiler
    # lay the whole pool out anew around it
    rows = (page_size * (suf[0] if suf else 1),) + suf[1:]
    blk = chunk.reshape(pre + (n,) + rows)
    idx = _pfx(n_prefix) + (pages,)
    flat = storage.reshape(storage.shape[:n_prefix + 1] + rows)
    return flat.at[idx].set(blk).reshape(storage.shape)


def scatter_token(storage, pages, offs, vals, *, n_prefix: int = 0):
    """Write one token per slot at (page, offset) — the decode-step write.

    storage: (prefix..., N, page_size, suffix...)
    pages, offs: (B,) int32;   vals: (prefix..., B, suffix...) — in the
    storage dtype
    """
    _check_write_dtype(storage, vals, "scatter_token")
    idx = _pfx(n_prefix) + (pages, offs)
    return storage.at[idx].set(vals)


def scatter_window(storage, pages, offs, vals, *, n_prefix: int = 0):
    """Write a per-slot window of tokens at (page, offset) pairs — the
    speculative-verify write (C candidate positions per slot committed in
    one scatter; pad / dead positions point at the trash page).

    storage: (prefix..., N, page_size, suffix...)
    pages, offs: (B, C) int32;   vals: (prefix..., B, C, suffix...)
    """
    B, C = pages.shape
    pre = vals.shape[:n_prefix]
    suf = vals.shape[n_prefix + 2:]
    flat = vals.reshape(pre + (B * C,) + suf)
    return scatter_token(storage, pages.reshape(-1), offs.reshape(-1), flat,
                         n_prefix=n_prefix)


def gather_pages(storage, tables, *, n_prefix: int = 0):
    """Gather each slot's pages back into a contiguous view.

    storage: (prefix..., N, page_size, suffix...);  tables: (B, P) int32
    -> (prefix..., B, P * page_size, suffix...)
    """
    B, P = tables.shape
    idx = _pfx(n_prefix) + (tables,)
    g = storage[idx]                  # (prefix..., B, P, page_size, suffix...)
    pre = g.shape[:n_prefix]
    suf = g.shape[n_prefix + 3:]
    return g.reshape(pre + (B, P * storage.shape[n_prefix + 1]) + suf)


def copy_pages(storage, leaf_specs, src, dst):
    """Copy whole pages ``src[i] -> dst[i]`` in every leaf — the
    copy-on-write device op.  ``src``/``dst``: (n,) int32 page ids; sources
    are read before any destination is written (XLA gather then scatter),
    so disjoint copies from one shared source are safe in a single call.
    """
    def leaf(st, spec):
        n = len(spec.prefix)
        return st.at[_pfx(n) + (dst,)].set(st[_pfx(n) + (src,)])

    return jax.tree_util.tree_map(leaf, storage, leaf_specs)


# ---------------------------------------------------------------------------
# Dense per-slot state store (the degenerate "one page per slot" layout)
# ---------------------------------------------------------------------------

def write_slot(state, slot_state, slot: int):
    """Write a (B=1) prefill state into slot ``slot`` of the batched state.

    The dense-path replacement for splice-by-``dynamic_update_slice``: every
    leaf has batch on axis 1 (stacked caches and recurrent O(1) states
    alike); a leaf with a sequence axis (axis 2) shorter than the slot's
    is zero-padded — the validity length masks the tail.
    """
    def leaf(dst, src):
        if src.ndim >= 3 and src.shape[2] < dst.shape[2]:
            pad = [(0, 0)] * src.ndim
            pad[2] = (0, dst.shape[2] - src.shape[2])
            src = jnp.pad(src, pad)
        return dst.at[:, slot].set(src[:, 0].astype(dst.dtype))

    return jax.tree_util.tree_map(leaf, state, slot_state)
