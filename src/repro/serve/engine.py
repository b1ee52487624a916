"""Continuous-batching serving engine over a paged KV cache.

This is the paper's §3.2 *dynamic population* pattern applied to inference
twice over: requests are walkers that enter (prefill), live (decode steps),
and leave (EOS / length), and — since this engine went paged — **memory** is
a population too: fixed-size KV pages are allocated as requests enter and
grow, and freed as they leave, so the device footprint is ``pages_in_use``
instead of ``max_slots x max_len``.

Layering (see README "Serving architecture"):

* :mod:`repro.serve.pages`   — refcounted `PagePool` storage + radix
  `PrefixCache` + pure scatter/gather/copy device ops; model-agnostic
  (parameterized by each model's cache leaf specs).
* :mod:`repro.serve.scheduler` — host-side policy: FIFO admission that
  matches the longest cached prompt prefix and reserves only the uncached
  remainder (all-or-nothing), **chunked prefill** starting at the match
  boundary (long prompts prefill in page-aligned chunks interleaved with
  decode ticks, so one 2k prompt never stalls token emission for live
  slots), **copy-on-write** when a decode write targets a shared page,
  and preemption of the youngest request when the pool runs dry
  (recompute-style: generated tokens are re-prefilled on re-admission,
  preserving greedy streams; full clean pages park in the prefix cache).
* :mod:`repro.serve.spec`  — speculative decode drafters: plain functions
  ``propose(tokens, k)`` guessing continuation tokens.  When a drafter is
  configured, decode ticks with proposals run ONE batched verify forward
  (``paged_verify``) scoring every slot's window, emit the accepted
  prefix + one correction/bonus token each (greedy acceptance keeps
  streams bit-identical to per-token decode; ``spec_temperature > 0``
  rejection-samples without changing the target distribution), and roll
  over-reserved pages back to the pool.
* this module — pure execution: jitted device calls driven by the
  scheduler's plan, each named program built by one helper in one
  execution context (weight transform, attention kernel, KV quantization,
  mesh).  The paged phases are the prefill chunk (``paged_prefill_chunk``)
  and the verify window (``paged_verify``), whose one-token case is the
  decode step: each writes its tokens' K/V through (page, offset) targets
  and attends through the page table (Pallas kernel
  :mod:`repro.kernels.paged_attention` on a TPU, jnp gather elsewhere);
  dead slots write to the pool's trash page so the SPMD tick keeps static
  shapes.

Families whose decode state is per-token KV (dense / MoE / VLM stacked
caches) run paged; recurrent-state families (rwkv6, mamba2/zamba) and
mixed window/ring caches (gemma3) keep the dense per-slot path — their
state is O(1) or ring-shaped, so there is nothing to page.  Both paths
share the scheduler; the dense path prefills whole prompts concurrently on
the :class:`repro.core.runtime.ThreadFarmExecutor`.

A failed prefill retires its request with ``req.error`` set and never
aborts the tick (pass ``strict=True`` to re-raise after the tick's healthy
work is committed).
"""

from __future__ import annotations

__all__ = ["Request", "ServeEngine", "encoder_prefix_tokens",
           "serve_param_specs"]

import dataclasses
import functools
import itertools
import time
from typing import Callable, Optional

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import NamedSharding, PartitionSpec as P

from repro.core.comm import Comm
from repro.core.runtime import ThreadFarmExecutor
from repro.serve import pages as PG
from repro.serve import quant as QZ
from repro.serve import spec as SP
from repro.serve.pages import PagePool
from repro.serve.sampling import (greedy, spec_rejection_sample,
                                  spec_verify_greedy)
from repro.serve.scheduler import (EncodeJob, FREE, Scheduler,
                                   prefill_tokens)


@dataclasses.dataclass
class Request:
    """One generation request: identity, prompt, sampling policy, optional
    encoder payload — plus the engine-side bookkeeping of its progress."""
    rid: int
    prompt: np.ndarray                     # (prompt_len,) int32
    max_new_tokens: int = 32
    eos_id: Optional[int] = None
    sampler: Optional[Callable] = None     # per-request (key, logits) -> tok
    seed: Optional[int] = None             # per-request RNG stream: token i
    #                                        is sampled with
    #                                        fold_in(PRNGKey(seed), i), so a
    #                                        sampled stream reproduces
    #                                        independent of admission order
    encoder_input: Optional[np.ndarray] = None
    #                                        precomputed encoder embeddings:
    #                                        (n_image_tokens, d_model) patch
    #                                        embeds for a VLM, (n_frames,
    #                                        d_model) audio frames for enc-dec
    # filled by the engine:
    encoder_tokens: Optional[np.ndarray] = None
    #                                        VLM only: strictly-negative
    #                                        pseudo-tokens hashing the image
    #                                        content (see
    #                                        encoder_prefix_tokens)
    output: list = dataclasses.field(default_factory=list)
    submitted_at: float = 0.0
    first_token_at: Optional[float] = None
    done_at: Optional[float] = None
    error: Optional[BaseException] = None  # set if prefill failed


def _jit(name: str, fn, *, donate=(), mesh=None, in_specs=None,
         out_specs=None):
    """``jax.jit`` of ``fn`` as the program ``jit_<name>``: the name a
    profiler trace gives its module and its ops.  With a ``mesh`` the body
    runs under ``shard_map`` with the given specs."""
    fn.__name__ = fn.__qualname__ = name
    if mesh is not None:
        fn = jax.shard_map(fn, mesh=mesh, in_specs=in_specs,
                           out_specs=out_specs, check_vma=False)
    return jax.jit(fn, donate_argnums=donate)


# host spans (``serve.*``) in the profiler's trace, on the device trace's
# clock; well under a microsecond each while no trace is being taken
_span = jax.profiler.TraceAnnotation


def _bucket(n: int, buckets=(32, 64, 128, 256, 512, 1024, 2048, 4096)) -> int:
    for b in buckets:
        if n <= b:
            return b
    return ((n + 4095) // 4096) * 4096


def serve_param_specs(model, params, mesh, *, paged: bool):
    """PartitionSpecs a mesh engine serves float ``params`` with on
    ``mesh``.

    Paged families run head-sharded TP (+ expert-partitioned EP) per
    ``model.serve_param_specs``, once ``validate_serve_mesh`` has named
    every dim the mesh does not divide.  Slot-parallel serving runs the
    unchanged step fn per shard, so params must be REPLICATED whatever the
    family's TP specs would say (a dense-forced DecoderLM included).
    ``params`` may be shapes (``jax.eval_shape``): only its tree and ranks
    are read."""
    if paged:
        ep = int(mesh.shape["expert"]) if "expert" in mesh.axis_names else 1
        model.validate_serve_mesh(tp=int(mesh.shape["model"]), ep=ep)
        return model.serve_param_specs(ep=ep)
    return jax.tree_util.tree_map(lambda a: P(*([None] * a.ndim)), params)


def encoder_prefix_tokens(enc: np.ndarray) -> np.ndarray:
    """Deterministic strictly-negative pseudo-tokens for an image prefix.

    The prefix cache keys pages on token bytes, so an image prefix needs a
    token sequence that (a) can never collide with real vocab ids — every
    real token is >= 0, every pseudo-token strictly negative — and (b) is a
    pure content hash of the embeddings: the same image always maps to the
    same pseudo-tokens, so shared-image chats hit the radix index exactly
    like shared text prompts, while distinct images collide with
    probability ~2**-128 (blake2b seeds the token draw)."""
    import hashlib
    enc = np.ascontiguousarray(np.asarray(enc, np.float32))
    digest = hashlib.blake2b(enc.tobytes(), digest_size=16).digest()
    rng = np.random.default_rng(int.from_bytes(digest, "little"))
    draw = rng.integers(0, 2**31 - 1, size=len(enc), dtype=np.int64)
    return (-1 - draw).astype(np.int32)


class ServeEngine:
    """Continuous-batching engine: ``submit()`` requests, ``tick()`` the
    serving loop (admission → encode/prefill chunks → batched decode or
    spec-verify → retire), collect :attr:`finished`.  One instance per
    role; see the module docstring for the layer map and
    ``docs/ARCHITECTURE.md`` for the request lifecycle."""

    def __init__(self, model, params, *, max_slots: int = 8,
                 max_len: int = 512, rules=None, sampler: Callable = None,
                 prefill_workers: int = 4, paged: Optional[bool] = None,
                 page_size: int = 16, num_pages: Optional[int] = None,
                 prefill_chunk: int = 64, chunks_per_tick: int = 2,
                 prefix_cache: bool = True,
                 spec_decode=None, spec_k: int = 4,
                 spec_temperature: float = 0.0,
                 strict: bool = False,
                 use_pallas_attention: Optional[bool] = None,
                 mesh=None, kv_quant=None, weight_quant=None,
                 prefill_only: bool = False, placement_interval: int = 0):
        self.model, self.params, self.rules = model, params, rules
        self.max_slots, self.max_len = max_slots, max_len
        self.strict = strict
        if paged is None:
            paged = model.supports_paged_decode()
        elif paged and not model.supports_paged_decode():
            raise ValueError(
                f"{model.cfg.name} ({model.cfg.family}) has no paged KV "
                "cache; construct with paged=False")
        self.paged = bool(paged)
        self.prefix_cache = bool(prefix_cache) and self.paged
        # -- encoder-attached serving (VLM image prefixes, enc-dec audio) ----
        # An enc-dec family serves paged-only: the dense per-slot path has
        # nowhere to hold the cross-attention K/V.  Prefix caching is
        # silently DISABLED for enc-dec — decoder self-KV depends on the
        # audio through cross-attention, so token-keyed page sharing would
        # alias different clips (documented in docs/ARCHITECTURE.md).
        if not self.paged and getattr(model.cfg, "is_encoder_decoder", False):
            raise ValueError(
                f"{model.cfg.name} ({model.cfg.family}) is encoder-decoder "
                "and serves through the paged engine only (cross-KV pages); "
                "drop paged=False")
        self._enc_dec = self.paged and bool(
            getattr(model.cfg, "is_encoder_decoder", False))
        if self._enc_dec:
            self.prefix_cache = False
        self._n_image = int(getattr(model.cfg, "n_image_tokens", 0) or 0) \
            if self.paged and model.cfg.family == "vlm" else 0
        if self._enc_dec or self._n_image:
            model.validate_serve_encoder(page_size=page_size,
                                         max_len=max_len,
                                         prefix_cache=self.prefix_cache)
        self.cross_pool = None

        # -- flag validation (one place, construction time) ------------------
        # Every engine-level capability flag is checked here so misuse fails
        # fast with one clear error instead of surfacing mid-tick inside a
        # jitted call.  The fused paged-attention kernel serves all three
        # paged phases (decode W=1, speculative verify windows, chunked
        # prefill), so ``use_pallas_attention`` composes freely with
        # ``spec_decode`` — but it has no meaning for families whose decode
        # state is not paged KV (recurrent rwkv6/mamba2 scans, sliding-window
        # ring caches, or ``paged=False``), and silently ignoring it there
        # would misreport what kernel actually ran.  Unset, the paged path
        # takes the kernel on a TPU, where it compiles and reads only each
        # slot's live pages, and the jnp gather everywhere else (on the CPU
        # the kernel would run in the Pallas interpreter).
        if use_pallas_attention is None:
            use_pallas_attention = (self.paged
                                    and jax.default_backend() == "tpu")
        self.use_pallas_attention = bool(use_pallas_attention)
        if self.use_pallas_attention and not self.paged:
            raise ValueError(
                f"use_pallas_attention requires the paged KV engine: "
                f"{model.cfg.name} ({model.cfg.family}) "
                + ("was constructed with paged=False"
                   if model.supports_paged_decode() else
                   "is a recurrent/window family with no paged KV cache, "
                   "so no paged-attention kernel can ever apply")
                + "; drop the flag or use a paged family")
        if spec_decode not in (None, "off", False) and self.paged \
                and sampler is not None:
            raise ValueError(
                "spec_decode supports the default greedy sampler "
                "(spec_temperature=0, bit-identical streams) or "
                "built-in temperature rejection sampling "
                "(spec_temperature > 0); a custom engine-wide sampler "
                "cannot be verified and would be silently ignored — "
                "drop it (per-request samplers remain supported)")
        # A prefill-only engine is the producer half of disaggregated
        # serving (repro.serve.disagg): it admits and chunk-prefills as
        # usual, but instead of decoding it packages each completed
        # prefill's pages as a KVHandoff for a decoder to inject.  Handoff
        # moves whole refcounted pages, so it only exists in paged mode —
        # and speculation is meaningless on an engine that never decodes.
        self.prefill_only = bool(prefill_only)
        self.handoffs: list[PG.KVHandoff] = []
        if self.prefill_only and not self.paged:
            raise ValueError(
                f"prefill_only requires the paged KV engine: "
                f"{model.cfg.name} ({model.cfg.family}) has no pages to "
                "hand off; drop the flag or use a paged family")
        if self.prefill_only and spec_decode not in (None, "off", False):
            raise ValueError(
                "spec_decode on a prefill_only engine would never run "
                "(speculation happens at decode); configure the drafter on "
                "the decoder side")
        if self.prefill_only and self._enc_dec:
            raise ValueError(
                f"prefill_only on {model.cfg.name} (enc-dec) has no cross-KV "
                "handoff: the decoder half could never read the audio pages; "
                "serve enc-dec monolithic")
        # KV quantization (int8 pages + per-row scale leaves) is a property
        # of the PAGED storage layout; the dense per-slot path has no pool
        # to hold the scale leaves in.
        self.kv_quant = QZ.make_kv_quant(kv_quant)
        if self.kv_quant is not None and not self.paged:
            raise ValueError(
                f"kv_quant={getattr(self.kv_quant, 'name', kv_quant)!r} "
                f"requires the paged KV engine: {model.cfg.name} "
                f"({model.cfg.family}) "
                + ("was constructed with paged=False"
                   if model.supports_paged_decode() else
                   "has no paged KV cache to quantize")
                + "; drop the flag or use a paged family")
        # Weights-only int8 (dequant-on-apply) is wired through the paged
        # serving wrappers only; the self-K drafter slices raw float param
        # leaves and cannot see through {"q8","s8"} payloads.
        if weight_quant in (None, "off", False):
            self.weight_quant = None
        elif weight_quant == "int8":
            if not self.paged:
                raise ValueError(
                    "weight_quant='int8' is wired through the paged serving "
                    "path only; drop the flag or use a paged family")
            if isinstance(spec_decode, str) \
                    and spec_decode.partition("-")[0] == "self":
                raise ValueError(
                    "weight_quant='int8' cannot build the self-K drafter "
                    "(it slices raw float param leaves); use the ngram "
                    "drafter or pass a pre-built drafter object")
            self.weight_quant = "int8"
        else:
            raise ValueError(
                f"unknown weight_quant {weight_quant!r}; want 'int8' or "
                "'off'")

        # -- weights-only int8 ------------------------------------------------
        # Quantize BEFORE any device placement so only the int8 payload ever
        # lands in HBM; the full-precision weights are rebuilt transiently
        # inside each jitted call (dequant-on-apply).
        if self.weight_quant:
            flt = [a for a in jax.tree_util.tree_leaves(params)
                   if hasattr(a, "dtype")
                   and jnp.issubdtype(a.dtype, jnp.floating)]
            wq_dtype = flt[0].dtype if flt else jnp.dtype(jnp.float32)
            wq_src = params                    # pre-quant tree for spec mirroring
            params = QZ.quantize_params(params)
            deq = functools.partial(QZ.dequantize_params, dtype=wq_dtype)
        else:
            wq_src = None
            deq = lambda p: p                                   # noqa: E731
        self.params = params
        kvq = self.kv_quant

        # -- device mesh (tensor/expert-parallel serving) --------------------
        # ``mesh=None`` keeps every code path byte-identical to the
        # single-device engine.  With a 1-D ("model",) mesh, paged families
        # run head-sharded TP under shard_map (params + KV pages partitioned
        # per ``model.serve_param_specs()`` / ``paged_storage_specs()``);
        # a 2-D ("expert", "model") mesh additionally PARTITIONS whole
        # experts over the "expert" axis (all-to-all dispatch/combine, see
        # moe_apply_expert_parallel);  dense-state families run
        # slot-parallel (params replicated, decode batch sharded).  The
        # scheduler and page tables stay host-side and replicated either way.
        self.mesh = mesh
        self._param_shardings = None
        pspecs = None
        if mesh is not None:
            if rules is not None:
                raise ValueError(
                    "pass either mesh= (serving TP) or rules=, not both")
            if "model" not in mesh.axis_names:
                raise ValueError(
                    f"serving mesh needs a 'model' axis, got {mesh.axis_names}")
            self.tp = int(mesh.shape["model"])
            self.ep = int(mesh.shape["expert"]) \
                if "expert" in mesh.axis_names else 1
            if self.paged:
                # head-sharded TP (+ expert-partitioned EP): family specs
                pspecs = serve_param_specs(model, params, mesh, paged=True)
                if self.weight_quant:
                    # int8 payload keeps the weight's spec; scalar scales
                    # replicate — dequant commutes with sharding, so tp=N
                    # streams stay equal to tp=1
                    pspecs = QZ.quantize_param_specs(pspecs, wq_src)
            else:
                if self.ep > 1:
                    raise ValueError(
                        f"expert-parallel serving needs the paged MoE path: "
                        f"{model.cfg.name} ({model.cfg.family}) is serving "
                        "non-paged (slot-parallel); drop the expert axis")
                pspecs = serve_param_specs(model, params, mesh, paged=False)
            self._param_shardings = jax.tree_util.tree_map(
                lambda s: NamedSharding(mesh, s), pspecs)
            self.params = params = jax.device_put(params,
                                                  self._param_shardings)
        else:
            self.tp = 1
            self.ep = 1

        # -- load-aware expert placement -------------------------------------
        # Dispatch goes through a (3, E) expert->physical-slot map passed as
        # a TRACED argument to every jitted step (re-placement never
        # recompiles); weights are permuted host-side to match.  The
        # identity map reproduces the unplaced integer slot indices exactly.
        from repro.serve import placement as PL
        n_exp = model.cfg.n_experts if (self.paged and model.cfg.n_experts) \
            else 0
        if placement_interval and n_exp and not model.cfg.holds_all_experts:
            raise ValueError(
                f"{model.cfg.name} holds {model.cfg.held_experts} of its "
                f"{n_exp} experts: load-aware placement moves experts "
                "between slots that all hold weights; drop "
                "placement_interval")
        self.placement = None                   # PlacementPlan once updated
        self.placement_interval = int(placement_interval)
        self._params_unplaced = self.params     # pristine expert order
        self._id_plan = PL.identity_plan(n_exp, self.ep) if n_exp else None
        self._place_arr = jnp.asarray(
            self._id_plan.dispatch_arrays() if n_exp
            else np.zeros((3, 0), np.int32))
        self._expert_tokens = np.zeros(n_exp, np.int64)   # lifetime
        self._expert_window = np.zeros(n_exp, np.int64)   # since re-place

        self._prefill_farm = ThreadFarmExecutor(
            num_workers=max(1, prefill_workers))
        self.sampler = sampler or (lambda key, logits: greedy(
            logits, true_vocab=model.cfg.vocab))

        # -- speculative decode ----------------------------------------------
        # A drafter proposes up to spec_k continuation tokens per live slot;
        # one batched verify forward scores every proposal and the engine
        # emits the accepted prefix + one correction/bonus token.  Families
        # without a paged KV cache fall back to plain per-token decode (the
        # drafter is simply never consulted).
        self.spec_k = max(1, int(spec_k))
        self.spec_temperature = float(spec_temperature)
        if spec_decode in (None, "off", False):
            self.drafter = None
        elif not self.paged:
            self.drafter = None          # recurrent/window family fallback
        else:
            self.drafter = spec_decode if not isinstance(spec_decode, str) \
                else SP.make_drafter(spec_decode, model=model, params=params)
        # the per-position argmax the greedy acceptance rule scores against
        # (jitted: it runs on every verify tick)
        self._verify_argmax = _jit(
            "verify_argmax",
            lambda logits: greedy(logits, true_vocab=model.cfg.vocab))
        # jitted logits head so weight dequant-on-apply also covers the
        # host-driven prefill tail (identity deq when weights are float)
        self._lm_head = _jit(
            "lm_head", lambda p, h: model.lm_head(deq(p), h, rules))

        self.last_token = np.zeros(max_slots, np.int32)
        self.finished: list[Request] = []
        self._ticks_run = 0         # the serve.tick span's number
        self._rid = itertools.count()
        self._key = jax.random.PRNGKey(0)
        self.stats = {"ticks": 0, "tokens": 0, "prefills": 0,
                      "chunk_prefills": 0, "preemptions": 0,
                      "prefix_hits": 0, "prefix_hit_tokens": 0,
                      "cow_copies": 0, "evictions": 0, "pages_high_water": 0,
                      "draft_proposed": 0, "draft_accepted": 0,
                      "acceptance_rate": 0.0,
                      "kv_handoffs": 0, "kv_injections": 0,
                      "encode_chunks": 0,
                      "kv_quant": self.kv_quant.name if self.kv_quant
                      else "off",
                      "weight_quant": self.weight_quant or "off",
                      "kv_bytes_per_token": QZ.kv_bytes_per_token(
                          model.paged_leaf_specs(kvq)) if self.paged else 0,
                      "moe_tokens_routed": 0, "moe_dropped_tokens": 0,
                      "moe_local_assignments": 0,
                      "moe_remote_assignments": 0,
                      "expert_tokens": [0] * n_exp,
                      "expert_imbalance": 0.0, "placement_updates": 0,
                      "decode_live_pages": 0, "decode_table_pages": 0}

        # -- the engine's programs -------------------------------------------
        # One execution context for every program that takes (params, state,
        # *inputs): the weight transform, the attention kernel, the KV
        # quantization and, on a mesh, the collectives (``rules`` is None
        # there).  ``program`` builds each of them.
        rep = P()
        ctx = dict(use_pallas=self.use_pallas_attention, quant=kvq,
                   comm=None, ep_comm=None)
        if mesh is not None:
            ctx.update(comm=Comm("model"),
                       ep_comm=Comm("expert")
                       if "expert" in mesh.axis_names else None)

        def program(name, call, in_specs, out_specs):
            """The program ``jit_<name>`` of ``call(params, state,
            *inputs)``, the weight transform applied to the params and the
            state donated, so that XLA updates the KV buffers in place (no
            full-pool copy per tick).  On a mesh the body runs under
            ``shard_map``, the params sharded by ``pspecs`` and the state
            and inputs by ``in_specs``."""
            return _jit(name, lambda p, *a: call(deq(p), *a), donate=(1,),
                        mesh=mesh, in_specs=(pspecs,) + in_specs,
                        out_specs=out_specs)

        if self.paged:
            if num_pages is None:       # dense-equivalent budget by default
                num_pages = -(-max_slots * max_len // page_size)
            sspecs = shardings = None
            if mesh is not None:
                sspecs = model.paged_storage_specs(kvq)
                shardings = jax.tree_util.tree_map(
                    lambda s: NamedSharding(mesh, s), sspecs,
                    is_leaf=lambda x: isinstance(x, P))
            self.pool = PagePool(model.paged_leaf_specs(kvq),
                                 num_pages=num_pages, page_size=page_size,
                                 shardings=shardings,
                                 prefix_cache=self.prefix_cache)
            # COW copies move whole pages along the (replicated) page axis —
            # on a mesh each shard copies its local heads independently
            self._cow_copy = _jit(
                "cow_copy",
                lambda st, s, d: PG.copy_pages(st, self.pool.leaf_specs, s, d),
                donate=(0,), mesh=mesh, in_specs=(sspecs, rep, rep),
                out_specs=sspecs)

            # The paged programs: (params, storage, *inputs, placement map,
            # *extras) -> (storage, output, MoE telemetry), every input
            # replicated on a mesh.  Enc-dec's extras are its read-only
            # cross-KV storage (not donated: it is not returned, and
            # donating would delete the live buffers), page tables and valid
            # frames.  Decode is the verify window one token wide.
            def paged_specs(n_in):
                return (sspecs,) + (rep,) * n_in, (sspecs, rep, rep)

            n_x = 3 if self._enc_dec else 0

            def extras(*x):
                if not x:
                    return {}
                cst, ctb, fl = x
                return {"cross": dict(storage=cst, tables=ctb, frames_len=fl)}

            self._decode_paged = program(
                "decode_paged",
                lambda p, st, tb, ln, t, wp, wo, pl, *x: model.paged_verify(
                    p, st, tb, ln, t, wp[:, None], wo[:, None], rules,
                    placement=pl, **ctx, **extras(*x)),
                *paged_specs(6 + n_x))
            self._verify_paged = program(
                "verify_paged",
                lambda p, st, tb, ln, t, wp, wo, pl, *x: model.paged_verify(
                    p, st, tb, ln, t, wp, wo, rules, placement=pl, **ctx,
                    **extras(*x)),
                *paged_specs(6 + n_x))
            self._prefill_chunk = program(
                "prefill_chunk",
                lambda p, st, row, pg, s0, t, pl, *x:
                model.paged_prefill_chunk(p, st, row, pg, s0, t, rules,
                                          placement=pl, **ctx, **extras(*x)),
                *paged_specs(5 + n_x))
            if self._n_image:
                # a program of its own, so that the embeds-free prefill
                # stays the text-only engine's program; the embeds replicate
                # like the token chunk (heads shard inside the model)
                self._prefill_chunk_embeds = program(
                    "prefill_chunk_embeds",
                    lambda p, st, row, pg, s0, t, pl, em:
                    model.paged_prefill_chunk(p, st, row, pg, s0, t, rules,
                                              placement=pl, embeds=em, **ctx),
                    *paged_specs(6))
            cross_kw = {}
            if self._enc_dec:
                # one read-only cross-KV pool sized for every slot holding
                # a full-length clip; per-request allocation is
                # ceil(n_frames / page_size), so shorter clips leave slack
                F = int(model.cfg.n_audio_frames)
                self.cross_pool = PG.CrossKVPool(
                    model.cross_leaf_specs(kvq),
                    num_pages=max_slots * (-(-F // page_size)),
                    page_size=page_size)
                cross_kw = dict(cross_pool=self.cross_pool, max_frames=F)
                # encoder + cross-KV projection: pure compute (no donated
                # state), farmed over the ThreadFarmExecutor like dense
                # prefills; the scatter into pool pages is applied
                # serially afterwards (cross storage donated HERE only)
                self._encode_chunk = _jit(
                    "encode_chunk",
                    lambda p, fr, s0, nv: model.cross_kv_chunk(
                        deq(p),
                        model.encode_chunk(deq(p), fr, s0, nv, rules)))
                self._scatter_cross = _jit(
                    "scatter_cross",
                    lambda st, pg, k, v: model.scatter_cross(
                        st, pg, k, v, page_size=page_size, quant=kvq),
                    donate=(0,))
            self.sched = Scheduler(max_slots=max_slots, max_len=max_len,
                                   pool=self.pool,
                                   prefill_chunk=prefill_chunk,
                                   chunks_per_tick=chunks_per_tick,
                                   **cross_kw)
        else:
            self.pool = None
            self.sched = Scheduler(max_slots=max_slots, max_len=max_len)
            st_specs = None
            self._fresh_state = lambda: model.init_decode_state(
                max_slots, max_len)
            if mesh is not None:
                if max_slots % self.tp:
                    raise ValueError(
                        f"slot-parallel serving shards slots over the mesh: "
                        f"max_slots={max_slots} must divide by tp={self.tp}")
                st_specs = model.serve_state_specs(max_slots, max_len)
                st_sh = jax.tree_util.tree_map(
                    lambda s: NamedSharding(mesh, s), st_specs,
                    is_leaf=lambda x: isinstance(x, P))
                self._fresh_state = lambda: jax.device_put(
                    model.init_decode_state(max_slots, max_len), st_sh)
            self.state = self._fresh_state()
            # slot-parallel on a mesh: each shard decodes its own slots
            self._decode = program(
                "decode",
                lambda p, s, t, pos: model.decode_step(p, s, t, pos, rules),
                (st_specs, P("model", None), P("model")),
                (st_specs, P("model", None, None)))
            self._prefill = _jit(
                "prefill", lambda p, b: model.prefill(p, b, rules, max_len))

    # -- compat views --------------------------------------------------------

    @property
    def queue(self) -> list:
        """Requests admitted-but-waiting (scheduler FIFO view)."""
        return self.sched.queue

    @property
    def slot_req(self) -> list:
        """Per-slot resident request (None for a free slot)."""
        return self.sched.slot_req

    @property
    def storage(self):
        """The paged KV storage pytree (lives on the pool — there is only
        one copy; every tick writes its functional update back)."""
        return self.pool.storage if self.pool is not None else None

    # -- request lifecycle ---------------------------------------------------

    def submit(self, prompt, max_new_tokens: int = 32,
               eos_id: Optional[int] = None,
               sampler: Optional[Callable] = None,
               seed: Optional[int] = None,
               encoder_input=None) -> int:
        """Enqueue one request; returns its rid.

        ``encoder_input`` attaches precomputed encoder embeddings: for a
        VLM, the ``(n_image_tokens, d_model)`` image-patch embeddings
        (served as a pseudo-token prefix — see
        :func:`encoder_prefix_tokens`); for an enc-dec audio family, the
        ``(n_frames, d_model)`` audio frames (``1 <= n_frames <=
        n_audio_frames``), encoded in streaming chunks into read-only
        cross-KV pages.  Text-only families reject it."""
        prompt = np.asarray(prompt, np.int32)
        enc_tok = None
        if encoder_input is not None:
            cfg = self.model.cfg
            if not self.paged:
                raise ValueError(
                    "encoder_input requires the paged engine (the dense "
                    "path prefills token batches only)")
            encoder_input = np.asarray(encoder_input, np.float32)
            if encoder_input.ndim != 2 \
                    or encoder_input.shape[-1] != cfg.d_model:
                raise ValueError(
                    f"encoder_input must be (n, d_model={cfg.d_model}), "
                    f"got {encoder_input.shape}")
            if self._enc_dec:
                F = int(cfg.n_audio_frames)
                if not 1 <= len(encoder_input) <= F:
                    raise ValueError(
                        f"{cfg.name}: encoder_input carries "
                        f"{len(encoder_input)} audio frames; want 1..{F} "
                        "(n_audio_frames)")
            elif self._n_image:
                if len(encoder_input) != self._n_image:
                    raise ValueError(
                        f"{cfg.name}: encoder_input carries "
                        f"{len(encoder_input)} image tokens; want exactly "
                        f"n_image_tokens={self._n_image}")
                enc_tok = encoder_prefix_tokens(encoder_input)
            else:
                raise ValueError(
                    f"{cfg.name} ({cfg.family}) takes no encoder_input: "
                    "only VLM and enc-dec audio families are "
                    "encoder-attached")
        elif self._enc_dec:
            raise ValueError(
                f"{self.model.cfg.name} (enc-dec) requires encoder_input: "
                "the decoder cross-attends into the audio's cross-KV pages")
        total = len(prompt) + (0 if enc_tok is None else len(enc_tok))
        if total >= self.max_len:
            # reject at the source: an oversized prompt can never decode
            what = "prompt length" if enc_tok is None \
                else "image prefix + prompt length"
            raise ValueError(
                f"{what} {total} >= max_len {self.max_len}")
        req = Request(next(self._rid), prompt, max_new_tokens, eos_id,
                      sampler, seed, encoder_input=encoder_input)
        req.encoder_tokens = enc_tok
        req.submitted_at = time.perf_counter()
        self.sched.submit(req)
        return req.rid

    # -- sampling ------------------------------------------------------------

    @staticmethod
    def _seeded_key(req: Request):
        """A seeded request's key for its NEXT token depends only on
        (seed, tokens emitted so far) — never on tick count, slot id or
        admission order, so sampled streams reproduce run to run."""
        return jax.random.fold_in(jax.random.PRNGKey(req.seed),
                                  len(req.output))

    def _sample_batch(self, logits_last, slots):
        """Sample every live slot: one batched draw with the engine default,
        overridden row-wise for requests carrying their own sampler and/or
        seed.  A per-row draw that raises is isolated — returns (tokens,
        [(slot, error), ...]); the engine's own batched sampler failing
        raises."""
        self._key, sub = jax.random.split(self._key)
        nxt = np.array(jax.device_get(self.sampler(sub, logits_last)))
        errors = []
        for slot in slots:
            req = self.sched.slot_req[slot]
            if req is None or (req.sampler is None and req.seed is None):
                continue
            k = self._seeded_key(req) if req.seed is not None \
                else jax.random.fold_in(sub, slot)
            fn = req.sampler or self.sampler
            try:
                nxt[slot] = int(jax.device_get(fn(k, logits_last[slot])))
            except BaseException as e:              # noqa: BLE001
                errors.append((slot, e))
        return nxt, errors

    def _sample_one(self, req: Request, logits_row) -> int:
        if req.seed is not None:
            sub = self._seeded_key(req)
        else:
            self._key, sub = jax.random.split(self._key)
        fn = req.sampler or self.sampler
        return int(jax.device_get(fn(sub, logits_row)))

    # -- retirement ----------------------------------------------------------

    def _retire(self, slot: int):
        """Walker ``delete``: slot capacity (and its pages) return to the
        pool."""
        req = self.sched.slot_req[slot]
        req.done_at = time.perf_counter()
        self.finished.append(req)
        self.sched.release(slot)

    def _check_retire(self, slot: int, tok: int) -> bool:
        req = self.sched.slot_req[slot]
        hit_eos = req.eos_id is not None and tok == req.eos_id
        if (hit_eos or len(req.output) >= req.max_new_tokens
                or self.sched.lengths[slot] >= self.max_len - 1):
            self._retire(slot)
            return True
        return False

    def _emit_first_token(self, slot: int, tok: int):
        """Bookkeeping for the token sampled off a completed prefill
        (EOS / budget checked immediately — a request may finish here).
        A prefill-only engine hands surviving requests off to a decoder
        instead of keeping the slot live."""
        req = self.sched.slot_req[slot]
        if req.first_token_at is None:
            req.first_token_at = time.perf_counter()
        req.output.append(tok)
        self.last_token[slot] = tok
        self.stats["tokens"] += 1
        self.stats["prefills"] += 1
        if self.prefill_only:
            # instant EOS / one-token budget / max_len still retire here —
            # there is nothing left for a decoder to do
            if not self._check_retire(slot, tok):
                self._handoff(slot)
            return
        self._check_retire(slot, tok)

    # -- disaggregated prefill/decode: page handoff ---------------------------

    def _gather_slot_kv(self, row: np.ndarray):
        """Gather one slot's pages into a contiguous chunk per pool leaf
        (``prefix + (n * page_size,) + suffix``).  Runs eagerly, not jitted:
        the page count varies per request, and a jit here would compile one
        program per count; the gathered buffers are independent of the
        pool's (possibly donated) storage, so a later storage recovery
        cannot invalidate an in-flight handoff."""
        tables = jnp.asarray(np.asarray(row, np.int32)[None])

        def leaf(st, spec):
            n = len(spec.prefix)
            return jnp.squeeze(
                PG.gather_pages(st, tables, n_prefix=n), axis=n)

        return jax.tree_util.tree_map(leaf, self.pool.storage,
                                      self.pool.leaf_specs)

    def _handoff(self, slot: int):
        """Package a completed prefill for a decoder: gather the slot's KV,
        take one in-flight reference per source page (they may stay
        registered and be re-shared by the prefix cache meanwhile, but a
        referenced page can never be evicted or reallocated), then release
        the slot — full clean pages also park in the prefix index exactly
        as a monolithic retirement would.  The KVHandoff owns the in-flight
        references until its ``release()``."""
        req = self.sched.slot_req[slot]
        total = int(self.sched.lengths[slot])
        n_kv = -(-total // self.pool.page_size)
        pages = [int(p) for p in self.sched.table[slot, :n_kv]]
        kv = self._gather_slot_kv(self.sched.table[slot, :n_kv])
        self.pool.incref(pages)
        self.sched.release(slot)
        self.stats["kv_handoffs"] += 1
        self.handoffs.append(PG.KVHandoff(req=req, length=total, kv=kv,
                                          pages=pages, pool=self.pool))

    def inject_prefilled(self, handoff: PG.KVHandoff) -> bool:
        """Decoder half of the page handoff: bind a prefilled request into
        a LIVE slot by scattering the gathered KV chunk into freshly
        allocated pages — no recompute.  All-or-nothing like admission:
        returns False (taking nothing) when no slot is free or the pool
        cannot yield ``(length + page_size) // page_size`` pages right now;
        the caller retries after a tick drains capacity.  On success the
        handoff's source references are NOT dropped — the caller owns
        ``handoff.release()`` (idempotent), which lets delivery race
        preemption without a double-free."""
        if not self.paged:
            raise ValueError("page handoff requires the paged KV engine")
        req, total = handoff.req, handoff.length
        assert req.output, "handoff carries the prefill's first token"
        slot = next((s for s in range(self.max_slots)
                     if self.sched.status[s] == FREE), None)
        if slot is None:
            return False
        ps = self.pool.page_size
        pages = self.pool.alloc((total + ps) // ps)
        if pages is None:
            return False
        n_kv = -(-total // ps)
        pg = jnp.asarray(np.asarray(pages[:n_kv], np.int32))

        def leaf(st, spec, chunk):
            return PG.scatter_chunk(st, pg, chunk, page_size=ps,
                                    n_prefix=len(spec.prefix))

        self.pool.storage = jax.tree_util.tree_map(
            leaf, self.pool.storage, self.pool.leaf_specs, handoff.kv)
        self.sched.bind_prefilled(slot, req, pages, total)
        self.last_token[slot] = req.output[-1]
        self.stats["kv_injections"] += 1
        return True

    def _retire_error(self, req: Request, err: BaseException):
        req.error = err
        req.done_at = time.perf_counter()
        self.finished.append(req)

    def _reject_errors(self, rejects) -> list:
        def why(r):
            if len(r.prompt) == 0:
                return "empty prompt has nothing to prefill"
            return f"prompt length {len(r.prompt)} >= max_len {self.max_len}"
        return [(r, ValueError(why(r))) for r in rejects]

    def _commit_decode(self, live, nxt, sample_errors) -> list:
        """Book one decoded token (``_sample_batch``'s draw) for every live
        slot.  Slots whose per-request sampler raised are retired instead
        (their pages return to the pool); returns their (req, error)
        pairs."""
        self.stats["ticks"] += 1
        bad = {slot for slot, _ in sample_errors}
        for slot in live:
            if slot in bad:
                continue
            req = self.sched.slot_req[slot]
            tok = int(nxt[slot])
            req.output.append(tok)
            self.last_token[slot] = tok
            self.sched.lengths[slot] += 1
            self.stats["tokens"] += 1
            self._check_retire(slot, tok)
        errors = []
        for slot, e in sample_errors:
            req = self.sched.slot_req[slot]
            self.sched.release(slot)
            errors.append((req, e))
        return errors

    def _evict_residents(self):
        """Preempt every resident request — youngest first, so the OLDEST
        lands back at the queue head and FIFO order resumes intact."""
        resident = [s for s in range(self.max_slots)
                    if self.sched.slot_req[s] is not None]
        for slot in sorted(resident,
                           key=lambda s: -int(self.sched.admitted_at[s])):
            self.sched.preempt(slot)

    def _recover_donated_storage(self):
        """A raising jitted call may already have CONSUMED the donated
        storage buffers (donated for in-place KV updates).  The KV contents
        are unrecoverable, so evict every resident request — recompute
        flavor: their generated tokens re-prefill on re-admission, so
        greedy streams survive — and rebuild zeroed storage with the
        original shapes/shardings.  When the call failed before running
        (the buffers are intact) this is a no-op and the healthy slots keep
        their caches."""
        if self.pool is None or not self.pool.storage_deleted():
            return
        self._evict_residents()
        self.pool.reset_storage()

    def _recover_donated_cross(self):
        """Cross-KV twin of :meth:`_recover_donated_storage`: a raising
        scatter may have consumed the donated cross storage.  Evicted
        residents re-encode on re-admission (recompute flavor — same
        contract as self-KV recovery)."""
        if self.cross_pool is None or not self.cross_pool.storage_deleted():
            return
        self._evict_residents()
        self.cross_pool.reset_storage()

    def _recover_donated_state(self):
        """Dense-path twin of :meth:`_recover_donated_storage`: a raising
        donated decode call may have consumed the per-slot state buffers."""
        if not PG.tree_deleted(self.state):
            return
        self._evict_residents()
        self.state = self._fresh_state()

    def _raise_or_record(self, errors):
        """Errored requests are always retired with ``req.error`` set; under
        ``strict=True`` the tick then raises (healthy work is already
        committed)."""
        for req, err in errors:
            self._retire_error(req, err)
        if errors and self.strict:
            rids = [req.rid for req, _ in errors]
            raise RuntimeError(
                f"prefill failed for request(s) {rids}; each request's "
                f".error holds its exception") from errors[0][1]

    # -- paged tick ----------------------------------------------------------

    def _tick_paged(self) -> bool:
        with _span("serve.schedule"):
            _, rejects = self.sched.admit()
            jobs = self.sched.next_chunks()
        errors = self._reject_errors(rejects)

        failed = set()
        enc_jobs = [j for j in jobs if isinstance(j, EncodeJob)]
        jobs = [j for j in jobs if not isinstance(j, EncodeJob)]
        if enc_jobs:
            # streaming chunked encode: the bidirectional encoder + cross-KV
            # projection are pure compute with no donated state, so chunks
            # for different requests overlap on the prefill farm (Executor
            # protocol); the scatter into cross pages applies serially,
            # BEFORE any decoder chunk of the same tick reads them
            def enc_guarded(job):
                try:
                    return self._encode_chunk(
                        self.params, jnp.asarray(job.frames[None]),
                        np.int32(job.start), np.int32(job.n_valid))
                except BaseException as e:                  # noqa: BLE001
                    return e
            results, _ = self._prefill_farm.map_callables(
                [functools.partial(enc_guarded, j) for j in enc_jobs])
            for job, res in zip(enc_jobs, results):
                if job.slot in failed \
                        or self.sched.slot_req[job.slot] is not job.req:
                    continue
                try:
                    if isinstance(res, BaseException):
                        raise res
                    k, v = res
                    self.cross_pool.storage = self._scatter_cross(
                        self.cross_pool.storage, jnp.asarray(job.pages),
                        k, v)
                    self.sched.encode_done(job)
                    self.stats["encode_chunks"] += 1
                except BaseException as e:                  # noqa: BLE001
                    failed.add(job.slot)
                    self.sched.release(job.slot)
                    errors.append((job.req, e))
                    self._recover_donated_cross()
        for job in jobs:
            # skip slots that failed earlier this tick — or whose request
            # was evicted by a storage recovery (slot freed or re-assigned)
            if job.slot in failed or self.sched.slot_req[job.slot] is not job.req:
                continue
            # the WHOLE per-job path is error-isolated: a request that dies
            # mid-chunked-prefill — in the device call, the lm head or its
            # own sampler — must hand every reserved page back to the pool
            # (release) instead of aborting the tick holding them
            with _span("serve.prefill", rid=job.req.rid, start=job.start,
                       n=job.n_valid):
                try:
                    prefill, extras = self._prefill_chunk, \
                        self._cross_inputs(slot=job.slot)
                    if job.embeds is not None:
                        prefill, extras = self._prefill_chunk_embeds, \
                            (jnp.asarray(job.embeds[None]),)
                    storage, hidden, tel = prefill(
                        self.params, self.pool.storage,
                        jnp.asarray(self.sched.table[job.slot]),
                        jnp.asarray(job.pages), np.int32(job.start),
                        jnp.asarray(job.tokens[None]), self._place_arr,
                        *extras)
                    self.pool.storage = storage
                    self._account_moe(tel)
                    self.sched.chunk_done(job)
                    self.stats["chunk_prefills"] += 1
                    if job.is_last:
                        i = job.n_valid - 1
                        logits = self._lm_head(self.params, hidden[:, i:i + 1])
                        tok = self._sample_one(job.req, logits[0, -1])
                except BaseException as e:                      # noqa: BLE001
                    failed.add(job.slot)
                    self.sched.release(job.slot)
                    errors.append((job.req, e))
                    self._recover_donated_storage()
                    continue
                if job.is_last:
                    self._emit_first_token(job.slot, tok)

        live = self.sched.live_slots()
        cow = []
        drafts = {}
        if live and self.drafter is not None:
            drafts = self._propose_drafts(live)
        if live:
            with _span("serve.pages"):
                # may preempt the youngest and/or schedule copy-on-write
                # moves; draft windows reserve their extra write pages
                # best-effort (never preempting — speculation can't evict
                # anyone)
                _, cow, granted = self.sched.ensure_decode_pages(
                    extra={s: len(d) for s, d in drafts.items()} or None)
                drafts = {s: d[:granted.get(s, 0)]
                          for s, d in drafts.items()
                          if self.sched.slot_req[s] is not None
                          and granted.get(s, 0) > 0}
                live = self.sched.live_slots()
                # a COW'd slot preempted later in the same pass already
                # gave its copy page back — don't write into it
                cow = [(s, a, b) for s, a, b in cow
                       if self.sched.slot_req[s] is not None]
        self.stats["preemptions"] = self.sched.preemptions
        if live:
            # sampled speculation (spec_temperature > 0) must route EVERY
            # tick through the verify commit — otherwise no-draft ticks
            # would fall back to the engine's greedy sampler and the
            # stream would mix greedy and temperature-sampled tokens
            verify = bool(drafts) or (self.drafter is not None
                                      and self.spec_temperature > 0)
            with _span("serve.upload"):
                args, cow_args, live_pages = self._step_inputs(
                    live, drafts, cow, verify)
            try:
                with _span("serve.step", live_pages=live_pages):
                    if cow:     # copies strictly before this tick's writes
                        self.pool.storage = self._cow_copy(
                            self.pool.storage, *cow_args)
                    step = self._verify_paged if verify \
                        else self._decode_paged
                    self.pool.storage, logits, tel = step(
                        self.params, self.pool.storage, *args)
                with _span("serve.sample"):
                    self._account_moe(tel)
                    if verify:
                        picked = self._verify_picks(logits)
                    else:
                        picked = self._sample_batch(logits[:, -1], live)
                with _span("serve.commit"):
                    if verify:
                        errors += self._commit_verify(live, drafts, logits,
                                                      *picked)
                    else:
                        errors += self._commit_decode(live, *picked)
            except BaseException:
                # a decode/commit failure still raises (engine-level, not
                # one request's fault) — but first un-brick the engine if
                # the raising call consumed the donated storage (evicted
                # residents resume recompute-style on the next tick), and
                # retire this tick's already-released prefill failures so
                # their clients see req.error instead of a vanished request
                self._recover_donated_storage()
                for req, err in errors:
                    self._retire_error(req, err)
                raise

        self.stats.update(
            prefix_hits=self.sched.prefix_hits,
            prefix_hit_tokens=self.sched.prefix_hit_tokens,
            cow_copies=self.sched.cow_copies,
            evictions=self.pool.evictions,
            pages_high_water=self.pool.high_water)
        proposed = self.stats["draft_proposed"]
        self.stats["acceptance_rate"] = (
            self.stats["draft_accepted"] / proposed if proposed else 0.0)
        if (self.placement_interval and self._expert_tokens.size
                and self.stats["ticks"] % self.placement_interval == 0
                and self._expert_window.sum()):
            self.update_placement()
        self._raise_or_record(errors)
        return bool(live) or self.sched.has_work()

    def _step_inputs(self, live, drafts, cow, verify: bool):
        """The device arrays of one decode (or verify) step after the
        params and storage, and of its copy-on-write moves: every slot's
        page table, length, input tokens and write targets (dead slots
        write to the trash page); and the pages its attention reads.

        Those are Σ ceil(len / page_size) over live slots, ``len`` the
        positions a slot's window attends to (cached tokens plus the
        step's own).  The stats ``decode_live_pages`` and
        ``decode_table_pages`` (max_slots × the table's width) sum them
        over decode and verify steps: the share of the table that
        attention over live pages reads."""
        ps = self.pool.page_size
        B = self.max_slots
        # verify width: the widest granted draft + 1, bucketed to two
        # compile shapes (half / full window) so a tick whose drafts
        # are short doesn't pay the full spec_k+1-wide forward
        C = self._spec_width(max(len(d) for d in drafts.values())
                             + 1) if drafts else 1
        wpages = np.full((B, C), self.pool.trash_page, np.int32)
        woffs = np.zeros((B, C), np.int32)
        lens = np.zeros(B, np.int32)
        toks = np.zeros((B, C), np.int32)
        live_pages = 0
        for slot in live:
            ln = int(self.sched.lengths[slot])
            lens[slot] = ln
            toks[slot, 0] = self.last_token[slot]
            d = drafts.get(slot)
            nd = 0 if d is None else len(d)
            live_pages += -(-(ln + nd + 1) // ps)
            if nd:
                toks[slot, 1:1 + nd] = d
            for i in range(nd + 1):
                wpages[slot, i] = self.sched.table[slot, (ln + i) // ps]
                woffs[slot, i] = (ln + i) % ps
        if not verify:
            wpages, woffs = wpages[:, 0], woffs[:, 0]
        args = (jnp.asarray(self.sched.table), jnp.asarray(lens),
                jnp.asarray(toks), jnp.asarray(wpages), jnp.asarray(woffs),
                self._place_arr) + self._cross_inputs(live=live)
        cow_args = (jnp.asarray([a for _, a, _ in cow], jnp.int32),
                    jnp.asarray([b for _, _, b in cow], jnp.int32)) \
            if cow else ()
        self.stats["decode_live_pages"] += live_pages
        self.stats["decode_table_pages"] += self.sched.table.size
        return args, cow_args, live_pages

    def _cross_inputs(self, *, slot=None, live=()) -> tuple:
        """An enc-dec program's trailing inputs: the read-only cross-KV
        storage, then the page table and valid audio frames of ``slot``
        (a prefill chunk) or of every slot (a decode or verify step, with
        ``live`` its live slots).  Other families have none."""
        if not self._enc_dec:
            return ()
        if slot is not None:
            return (self.cross_pool.storage,
                    jnp.asarray(self.sched.cross_table[slot]),
                    np.int32(self.sched.enc_total[slot]))
        # dead slots keep frames_len=0: every cross read is fully masked
        # (attention renormalizes to zeros), so their stale table rows are
        # never observable
        frames = np.zeros(self.max_slots, np.int32)
        for s in live:
            frames[s] = self.sched.enc_total[s]
        return (self.cross_pool.storage, jnp.asarray(self.sched.cross_table),
                jnp.asarray(frames))

    # -- expert telemetry + load-aware placement -----------------------------

    def _account_moe(self, tel) -> None:
        """Fold one step's per-expert telemetry into engine stats (counts
        are replicated across the mesh, so any shard's copy is global).
        Assignments to the experts this model holds are local, the rest
        remote: the work of the chips that hold those experts."""
        if self._expert_tokens.size == 0:
            return
        with _span("serve.moe_stats"):
            t, d = jax.device_get((tel["expert_tokens"],
                                   tel["expert_dropped"]))
        t = np.asarray(t, np.int64)
        d = np.asarray(d, np.int64)
        cfg = self.model.cfg
        local = int(t[cfg.first_held_expert:
                      cfg.first_held_expert + cfg.held_experts].sum())
        self._expert_tokens += t
        self._expert_window += t
        self.stats["moe_tokens_routed"] += int(t.sum())
        self.stats["moe_dropped_tokens"] += int(d.sum())
        self.stats["moe_local_assignments"] += local
        self.stats["moe_remote_assignments"] += int(t.sum()) - local
        self.stats["expert_tokens"] = self._expert_tokens.tolist()
        if self._expert_window.sum():
            from repro.serve import placement as PL
            plan = self.placement or self._id_plan
            self.stats["expert_imbalance"] = PL.imbalance(
                plan.rank_loads(self._expert_window))

    def update_placement(self, plan=None):
        """Re-place experts between ticks from the measured token window.

        ``plan=None`` computes one with
        :func:`repro.serve.placement.plan_placement` (hot-expert
        replication on); an explicit :class:`PlacementPlan` is applied
        as-is.  The expert-stacked weight leaves are permuted from the
        PRISTINE (identity-order) params — plans never compose — and the
        dispatch map swaps in as a traced argument, so no recompile.
        Returns the active plan (``None`` when the window was empty)."""
        from repro.serve import placement as PL
        if self._expert_tokens.size == 0:
            raise ValueError(
                f"{self.model.cfg.name}: expert placement needs a paged "
                "MoE model")
        if not self.model.cfg.holds_all_experts:
            raise ValueError(
                f"{self.model.cfg.name}: expert placement needs every "
                "expert's weights; this model holds a share")
        if plan is None:
            if not self._expert_window.sum():
                return None
            plan = PL.plan_placement(self._expert_window, self.ep)
        params = PL.apply_placement(self._params_unplaced, plan)
        if self._param_shardings is not None:
            params = jax.device_put(params, self._param_shardings)
        self.params = params
        self.placement = plan
        self._place_arr = jnp.asarray(plan.dispatch_arrays())
        self._expert_window[:] = 0
        self.stats["placement_updates"] += 1
        return plan

    # -- speculative decode --------------------------------------------------

    def _spec_width(self, need: int) -> int:
        half = 1 + (self.spec_k + 1) // 2
        return half if need <= half else self.spec_k + 1

    def _propose_drafts(self, live) -> dict:
        """Ask the drafter for up to ``spec_k`` continuation tokens per
        spec-eligible live slot.  The budget caps keep parity with plain
        decode position-exact: never draft past the request's remaining
        token budget or into ``max_len``'s last writable position.  A
        drafter raising (or proposing nothing) just means no drafts for
        that slot this tick — proposals are best-effort by contract."""
        drafts = {}
        for slot in live:
            req = self.sched.slot_req[slot]
            if req.sampler is not None:
                continue        # black-box per-request sampler: unverifiable
            budget = min(self.spec_k,
                         req.max_new_tokens - len(req.output) - 1,
                         self.max_len - 2 - int(self.sched.lengths[slot]))
            if budget <= 0:
                continue
            try:
                prop = np.asarray(self.drafter.propose(
                    prefill_tokens(req), budget), np.int32).reshape(-1)
            except BaseException:                       # noqa: BLE001
                continue        # a sloppy drafter costs nothing
            if prop.size:
                drafts[slot] = prop[:budget]
        return drafts

    def _verify_picks(self, logits):
        """A verify forward's result on the host: the per-position argmax
        for greedy acceptance, or the logits and this tick's key for
        rejection sampling (which never reads the argmax)."""
        if self.spec_temperature <= 0:
            return np.array(jax.device_get(self._verify_argmax(logits))), None
        self._key, tick_key = jax.random.split(self._key)
        return np.asarray(jax.device_get(logits)), tick_key

    def _commit_verify(self, live, drafts, logits, host, tick_key) -> list:
        """Book a verify forward's emitted tokens for every live slot:
        the accepted draft prefix + one correction/bonus each (a slot
        without drafts emits exactly its plain decoded token).  ``host``
        and ``tick_key`` are :meth:`_verify_picks`'s.  Per-token
        bookkeeping mirrors :meth:`_commit_decode`, so retirement (EOS /
        budget / max_len) happens at the same stream position speculation
        on or off; afterwards every surviving slot hands its
        over-reserved verify pages back to the pool."""
        self.stats["ticks"] += 1
        greedy_mode = self.spec_temperature <= 0
        errors = []
        for slot in live:
            req = self.sched.slot_req[slot]
            d = drafts.get(slot)
            nd = 0 if d is None else len(d)
            draft = [] if d is None else [int(t) for t in d]
            if req.sampler is not None:
                # black-box sampler (no drafts were proposed for it):
                # one token off position 0, error-isolated like
                # _sample_batch's per-row draws
                try:
                    accepted, emitted = 0, [self._sample_one(req,
                                                             logits[slot, 0])]
                except BaseException as e:              # noqa: BLE001
                    self.sched.release(slot)
                    errors.append((req, e))
                    continue
            elif not greedy_mode:
                if req.seed is not None:
                    base = jax.random.PRNGKey(req.seed)
                    keys = [jax.random.fold_in(base, len(req.output) + i)
                            for i in range(nd + 1)]
                else:
                    keys = [jax.random.fold_in(tick_key,
                                               slot * (self.spec_k + 2) + i)
                            for i in range(nd + 1)]
                accepted, emitted = spec_rejection_sample(
                    keys, host[slot, :nd + 1], draft,
                    temperature=self.spec_temperature,
                    true_vocab=self.model.cfg.vocab)
            else:
                accepted, emitted = spec_verify_greedy(host[slot], draft)
            self.stats["draft_proposed"] += nd
            self.stats["draft_accepted"] += accepted
            for tok in emitted:
                tok = int(tok)
                req.output.append(tok)
                self.last_token[slot] = tok
                self.sched.lengths[slot] += 1
                self.stats["tokens"] += 1
                if self._check_retire(slot, tok):
                    break
            self.sched.rollback_verify_pages(slot)
        return errors

    # -- dense tick (recurrent / window-cache families) ----------------------

    def _prefill_one(self, job, key):
        """One request's whole-prompt prefill + first token — a
        self-contained farm task (pure device work; jitted dispatch releases
        the GIL, so bucketed prefills for different requests overlap)."""
        L = job.n_valid
        bucket = min(_bucket(L), self.max_len)
        toks = np.zeros((1, bucket), np.int32)
        toks[0, :L] = job.tokens[:L]                 # right-pad into bucket
        cache, hidden = self._prefill(self.params,
                                      {"tokens": jnp.asarray(toks)})
        # right-padding: cache rows beyond L hold pad garbage, but
        # lengths[slot] = L masks them out (kv_valid_len) and later decode
        # tokens overwrite them in order.
        logits = self._lm_head(self.params, hidden[:, L - 1:L])
        fn = job.req.sampler or self.sampler
        tok = int(jax.device_get(fn(key, logits[0, -1])))
        return cache, tok

    def _tick_dense(self) -> bool:
        _, rejects = self.sched.admit()
        errors = self._reject_errors(rejects)

        jobs = self.sched.next_chunks()          # dense: whole-prompt jobs
        if jobs:
            keys = []
            for _ in jobs:                       # keys drawn in slot order
                self._key, sub = jax.random.split(self._key)
                keys.append(sub)

            def guarded(job, key):
                # isolate failures so one bad request cannot drop the
                # other concurrently admitted requests
                try:
                    return self._prefill_one(job, key)
                except BaseException as e:                  # noqa: BLE001
                    return e

            results, _ = self._prefill_farm.map_callables(
                [functools.partial(guarded, job, key)
                 for job, key in zip(jobs, keys)])
            for job, res in zip(jobs, results):
                if isinstance(res, BaseException):
                    self.sched.release(job.slot)
                    errors.append((job.req, res))
                    continue
                cache, tok = res
                self.state = PG.write_slot(self.state, cache, job.slot)
                self.sched.chunk_done(job)
                self._emit_first_token(job.slot, tok)

        live = self.sched.live_slots()
        if live:
            toks = jnp.asarray(self.last_token.reshape(-1, 1))
            pos = jnp.asarray(self.sched.lengths.astype(np.int32))
            try:
                with _span("serve.step"):
                    self.state, logits = self._decode(self.params,
                                                      self.state, toks, pos)
                with _span("serve.sample"):
                    picked = self._sample_batch(logits[:, -1], live)
                with _span("serve.commit"):
                    errors += self._commit_decode(live, *picked)
            except BaseException:
                self._recover_donated_state()
                for req, err in errors:
                    self._retire_error(req, err)
                raise

        self._raise_or_record(errors)
        return bool(live) or self.sched.has_work()

    # -- the tick: one SPMD decode step for all live slots --------------------

    def tick(self) -> bool:
        """One serving step; True while the engine still has work."""
        self._ticks_run += 1
        with _span("serve.tick", tick=self._ticks_run):
            return self._tick_paged() if self.paged else self._tick_dense()

    def run_until_drained(self, max_ticks: int = 10_000):
        """Tick until idle; returns the finished requests."""
        for _ in range(max_ticks):
            busy = self.tick()
            if not busy and not self.sched.has_work():
                break
        return self.finished

    # -- lifecycle -----------------------------------------------------------

    def close(self):
        """Release the prefill farm's worker threads.  The engine stays
        usable — the pool is transparently recreated on the next admit."""
        self._prefill_farm.shutdown()

    def __enter__(self):
        return self

    def __exit__(self, *exc):
        self.close()

    def __del__(self):
        try:
            self.close()
        except Exception:       # interpreter teardown: best effort only
            pass
