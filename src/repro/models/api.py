"""Unified model API — the function-centric face of every architecture.

``build_model(cfg)`` returns a :class:`Model` whose members are plain
functions (loss / prefill / decode_step), so the generic machinery
(:mod:`repro.train`, :mod:`repro.serve`, :mod:`repro.launch.dryrun`) composes
them exactly the way the paper's ``solve_problem`` composes ``initialize`` /
``func`` / ``finalize``: the framework never looks inside the model, it only
calls the supplied functions.

Batch conventions per family (assignment brief: modality frontends are stubs,
``input_specs`` provides precomputed embeddings):

  dense/moe:  {tokens (B,S) i32, labels (B,S) i32}
  vlm:        {tokens (B,S-I) i32, image_embeds (B,I,d) act-dtype, labels (B,S)}
  hybrid/ssm: {tokens (B,S) i32, labels (B,S) i32}
  audio:      {frames (B,F,d) act-dtype, tokens (B,S) i32, labels (B,S) i32}
"""

from __future__ import annotations

__all__ = ["ArraySpec", "DecoderLM", "HybridLM",
           "Model", "RwkvLM", "VLM",
           "Whisper", "build_model"]

import dataclasses
from typing import Any, Callable, Optional

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

from repro.configs.base import ModelConfig, ShapeConfig
from repro.mesh.axes import AxisRules, logical_to_sharding
from repro.models import transformer as T
from repro.models import rwkv_lm as RW
from repro.models import whisper as W
from repro.models import zamba as Z
from repro.models.module import Param, abstract_params, init_params, param_count


@dataclasses.dataclass(frozen=True)
class ArraySpec:
    """Shape/dtype/logical-partition declaration of one input array."""
    shape: tuple
    dtype: Any
    spec: P

    def abstract(self, mesh=None, rules: AxisRules | None = None):
        """The matching ShapeDtypeStruct (sharded when ``mesh`` given)."""
        if mesh is None:
            return jax.ShapeDtypeStruct(self.shape, self.dtype)
        return jax.ShapeDtypeStruct(
            self.shape, self.dtype,
            sharding=logical_to_sharding(self.spec, mesh, rules))


# -- serving-mesh sharding rules (1-D ("model",) tensor-parallel mesh) -------
#
# The serving engine's device mesh has a single "model" axis.  Families with
# a per-token KV cache run Megatron-style TP: attention heads, MLP ff, the
# vocab and the experts shard over "model"; everything else (norm scales,
# router, the embedding table — its lookup needs every row) is replicated.
# Families without paged KV (recurrent / window caches) run slot-parallel
# instead: params replicated, decode-state batch axis sharded over "model".
SERVE_TP_AXES: dict = {
    "q_heads": "model",
    "kv_heads": "model",
    "mlp": "model",
    "vocab": "model",
    "experts": "model",
}


def _map_param_spec(spec: P, table) -> P:
    return P(*(table.get(ax) for ax in spec))


def _is_param(x) -> bool:
    return isinstance(x, Param)


def _tokens(B, S):
    return ArraySpec((B, S), jnp.int32, P("batch", "seq"))


def _labels(B, S):
    return ArraySpec((B, S), jnp.int32, P("batch", "seq"))


class Model:
    """One architecture, bound to its family's functional implementation."""

    def __init__(self, cfg: ModelConfig):
        self.cfg = cfg

    # -- parameters ----------------------------------------------------------
    def param_defs(self) -> dict:
        """Pytree of (shape, logical partition) pairs for every weight."""
        raise NotImplementedError

    def init(self, key, dtype=jnp.float32):
        """Random weights matching :meth:`param_defs` (host-local)."""
        return init_params(self.param_defs(), key, dtype=dtype)

    def abstract_params(self, mesh, rules, dtype=jnp.float32):
        """ShapeDtypeStructs with shardings — for eval_shape / checkpoint
        restore without materialising weights."""
        return abstract_params(self.param_defs(), mesh, rules, dtype=dtype)

    def n_params(self) -> int:
        """Total scalar parameter count."""
        return param_count(self.param_defs())

    def n_active_params(self) -> int:
        """Params touched per token (= n_params except for MoE)."""
        return self.n_params()

    # -- training ------------------------------------------------------------
    def loss(self, params, batch: dict, rules) -> tuple[jax.Array, dict]:
        """Mean next-token loss on ``batch`` -> (scalar, metrics dict)."""
        raise NotImplementedError

    def train_batch_specs(self, shape: ShapeConfig) -> dict[str, ArraySpec]:
        """:class:`ArraySpec` per training-batch key (tokens, labels, …)."""
        raise NotImplementedError

    # -- serving -------------------------------------------------------------
    def prefill_batch_specs(self, shape: ShapeConfig) -> dict[str, ArraySpec]:
        """The training specs minus ``labels`` — what prefill consumes."""
        specs = dict(self.train_batch_specs(shape))
        specs.pop("labels")
        return specs

    def prefill(self, params, batch: dict, rules, max_len: int):
        """-> (decode_state, last_hidden)."""
        raise NotImplementedError

    def init_decode_state(self, batch: int, max_len: int):
        """Fresh (empty) per-slot decode state for a dense batch."""
        raise NotImplementedError

    def decode_state_specs(self, batch: int, max_len: int) -> Any:
        """Pytree of ArraySpec matching init_decode_state."""
        raise NotImplementedError

    def decode_step(self, params, state, tokens, pos, rules):
        """tokens (B,1) -> (new_state, logits (B,1,V))."""
        raise NotImplementedError

    # -- paged serving (stacked-cache families only) -------------------------
    # Recurrent-state families (rwkv6, mamba2/zamba) keep their O(1)
    # per-slot state path: there is no per-token KV to page.

    def supports_paged_decode(self) -> bool:
        """Whether the family has a per-token KV cache that can page."""
        return False

    def paged_leaf_specs(self, quant=None):
        """Pytree of :class:`repro.serve.pages.PagedLeafSpec` describing the
        per-token KV leaves around the pool's (num_pages, page_size) axes.
        With a ``quant`` policy the value leaves use its storage dtype and
        per-row scale leaves ride along (see :mod:`repro.serve.quant`)."""
        raise NotImplementedError(f"{self.cfg.family} has no paged KV cache")

    def paged_state_specs(self, num_pages: int, page_size: int, quant=None):
        """Pytree of ArraySpec matching the PagePool storage (incl. the
        trash page at index ``num_pages``).  Derived from
        :meth:`paged_leaf_specs` so the pool layout has one source of
        truth; unsupported families raise through it."""
        from repro.serve import pages as PG

        def leaf(s):
            shape = s.storage_shape(num_pages + PG.N_TRASH, page_size)
            return ArraySpec(shape, s.dtype, P(*([None] * len(shape))))

        return jax.tree_util.tree_map(
            leaf, self.paged_leaf_specs(quant),
            is_leaf=lambda x: isinstance(x, PG.PagedLeafSpec))

    def paged_prefill_chunk(self, params, storage, table_row, pages_chunk,
                            start, tokens, rules, *,
                            use_pallas: bool = False, comm=None, quant=None,
                            ep_comm=None, placement=None, embeds=None,
                            cross=None):
        """Prefill tokens (1, C) at positions [start, start+C) into pages.

        ``embeds``: optional (1, C, d) precomputed embeddings spliced in at
        negative-token positions (the VLM image-prefix path); ``cross``:
        optional ``{"storage", "tables", "frames_len"}`` read-only
        cross-attention pages (the enc-dec path).  Both default to None and
        change NOTHING for text-only families."""
        raise NotImplementedError(f"{self.cfg.family} has no paged KV cache")

    def paged_verify(self, params, storage, tables, lengths, tokens,
                     write_pages, write_offs, rules, *,
                     use_pallas: bool = False, comm=None, quant=None,
                     ep_comm=None, placement=None, cross=None):
        """Score a (B, C) window of candidate tokens per slot in one
        batched forward (position 0 = the next input, 1..C-1 = drafts):
        the speculative-decode verify, and at C == 1 the decode step.
        ``write_pages``/``write_offs`` are (B, C) per-position K/V targets
        (pads and dead slots -> trash page).  Returns (new_storage, logits
        (B, C, V), moe telemetry).  Families without a paged KV cache
        decode on the dense path (the engine never calls this for them)."""
        raise NotImplementedError(f"{self.cfg.family} has no paged KV cache")

    # -- serving-mesh sharding rules -----------------------------------------

    def serve_param_specs(self, ep: int = 1):
        """Pytree of mesh ``PartitionSpec`` for the params during
        tensor-parallel PAGED serving — part of the paged protocol, like
        :meth:`paged_leaf_specs`.  ``ep > 1`` targets a 2-D ("expert",
        "model") mesh: expert-stacked weights shard over BOTH axes (expert
        major).  Families without a paged KV cache never need this: the
        engine's slot-parallel fallback replicates params directly from the
        array tree."""
        raise NotImplementedError(
            f"{self.cfg.family} has no TP serving specs (engine "
            "slot-parallel mode replicates params instead)")

    def serve_state_specs(self, batch: int, max_len: int):
        """Mesh specs for the dense decode state under slot-parallel mesh
        serving: every leaf's logical "batch" axis shards over "model",
        everything else is replicated — each device decodes its own slots
        with the unchanged serial step function."""
        def leaf(a: ArraySpec) -> P:
            return P(*("model" if ax == "batch" else None for ax in a.spec))
        return jax.tree_util.tree_map(
            leaf, self.decode_state_specs(batch, max_len),
            is_leaf=lambda x: isinstance(x, ArraySpec))

    def paged_storage_specs(self, quant=None):
        """Mesh specs for the PagePool storage under TP serving: the leading
        suffix axis of every :meth:`paged_leaf_specs` leaf (the KV-head axis
        by convention — scale leaves included, their suffix is exactly
        (Hkv,)) shards over "model"."""
        from repro.serve import pages as PG

        def leaf(s: PG.PagedLeafSpec) -> P:
            n_pre = len(s.prefix)
            return P(*([None] * (n_pre + 2) + ["model"]
                       + [None] * (len(s.suffix) - 1)))
        return jax.tree_util.tree_map(
            leaf, self.paged_leaf_specs(quant),
            is_leaf=lambda x: isinstance(x, PG.PagedLeafSpec))

    def validate_serve_mesh(self, tp: int = 1, ep: int = 1) -> None:
        """Raise with EVERY indivisible dimension named for a (tp, ep)
        serving mesh.  ``tp`` shards heads / ff / vocab (and, combined with
        ``ep``, the expert stack); ``ep`` partitions whole experts, so a
        dense family with ep > 1 is refused outright."""
        cfg = self.cfg
        if ep > 1 and not cfg.n_experts:
            raise ValueError(
                f"{cfg.name} ({cfg.family}) is a dense family with no "
                f"experts: expert-parallel ep={ep} cannot apply — drop the "
                "expert axis (--mesh tp=N)")
        if tp <= 1 and ep <= 1:
            return
        bad = []
        if self.supports_paged_decode():
            if tp > 1:
                dims = {"padded_q_heads": cfg.padded_q_heads,
                        "padded_kv_heads": cfg.padded_kv_heads,
                        "padded_vocab": cfg.padded_vocab}
                if not cfg.n_experts or cfg.dense_residual:
                    dims["d_ff"] = cfg.d_ff
                bad += [f"{k}={v} (tp={tp})" for k, v in dims.items()
                        if v % tp]
            if cfg.n_experts and cfg.held_experts % (ep * tp):
                # experts shard over BOTH axes (tp slices expert rows even
                # on a 1-D mesh), so the product must divide the stack
                shards = (f"ep*tp={ep * tp}" if ep > 1 else f"tp={tp}")
                what = ("n_experts" if cfg.holds_all_experts
                        else "held_experts")
                bad.append(f"{what}={cfg.held_experts} ({shards})")
        elif ep > 1:
            bad.append(f"family={cfg.family} has no paged expert path "
                       f"(ep={ep})")
        if bad:
            raise ValueError(
                f"{cfg.name}: serving mesh (tp={tp}, ep={ep}) does not "
                "divide " + ", ".join(bad))

    def validate_serve_encoder(self, *, page_size: int, max_len: int,
                               prefix_cache: bool = False) -> None:
        """Raise (with the fix spelled out) when the family's encoder
        geometry cannot serve under the given paged layout — the
        construction-time twin of :meth:`validate_serve_mesh` for the
        encoder-attached families (VLM image prefixes, whisper audio
        frames).  Text-only families have no encoder: no-op."""

    def lm_head(self, params, hidden, rules):
        """Project final hidden states to vocab logits."""
        return T.lm_logits(params, hidden, self.cfg, rules)


# ---------------------------------------------------------------------------
# Dense / MoE decoder-only LMs (also base for VLM)
# ---------------------------------------------------------------------------

class DecoderLM(Model):
    """Decoder-only transformer LM (dense or MoE): the full paged-serving
    protocol — prefill chunks and verify windows (decode is the one-token
    window)."""

    def param_defs(self):
        return T.transformer_defs(self.cfg)

    def n_active_params(self):
        cfg = self.cfg
        if not cfg.n_experts:
            return self.n_params()
        expert_p = 3 * cfg.d_model * cfg.expert_d_ff
        inactive = cfg.n_layers * (cfg.n_experts - cfg.top_k) * expert_p
        return self.n_params() - inactive

    def loss(self, params, batch, rules):
        return T.lm_loss(params, self.cfg, rules, tokens=batch["tokens"],
                         labels=batch["labels"])

    def train_batch_specs(self, shape):
        B, S = shape.global_batch, shape.seq_len
        return {"tokens": _tokens(B, S), "labels": _labels(B, S)}

    def prefill(self, params, batch, rules, max_len):
        return T.prefill(params, self.cfg, rules, tokens=batch["tokens"],
                         max_len=max_len)

    def init_decode_state(self, batch, max_len):
        return T.init_cache(self.cfg, batch, max_len,
                            dtype=jnp.dtype(self.cfg.dtype))

    def decode_state_specs(self, batch, max_len):
        cfg = self.cfg
        spec = P(None, "batch", "kv_seq", None, None)
        hkv, hd = cfg.padded_kv_heads, cfg.head_dim
        dt = jnp.dtype(cfg.dtype)
        if not T.uses_window_cache(cfg):
            a = ArraySpec((cfg.n_layers, batch, max_len, hkv, hd), dt, spec)
            return {"k": a, "v": a}
        glob, loc = T.layer_groups(cfg)
        W = min(cfg.local_window, max_len)
        g = ArraySpec((len(glob), batch, max_len, hkv, hd), dt, spec)
        l = ArraySpec((len(loc), batch, W, hkv, hd), dt, spec)
        return {"k": g, "v": g, "k_loc": l, "v_loc": l}

    def decode_step(self, params, state, tokens, pos, rules):
        return T.decode_step(params, self.cfg, rules, state, tokens, pos)

    # -- paged serving -------------------------------------------------------
    # Shared by dense, MoE and (token-prompt) VLM: the stacked (L, ·, ·,
    # Hkv, D) cache pages identically; only gemma3-style mixed window/ring
    # caches stay on the dense path.

    def supports_paged_decode(self) -> bool:
        return not T.uses_window_cache(self.cfg)

    def paged_leaf_specs(self, quant=None):
        from repro.serve.pages import PagedLeafSpec
        from repro.serve.quant import quantize_leaf_specs
        cfg = self.cfg
        leaf = PagedLeafSpec((cfg.n_layers,),
                             (cfg.padded_kv_heads, cfg.head_dim),
                             jnp.dtype(cfg.dtype))
        return quantize_leaf_specs({"k": leaf, "v": leaf}, quant)

    def paged_prefill_chunk(self, params, storage, table_row, pages_chunk,
                            start, tokens, rules, *,
                            use_pallas: bool = False, comm=None, quant=None,
                            ep_comm=None, placement=None, embeds=None,
                            cross=None):
        assert cross is None, "decoder-only families have no cross-KV pages"
        return T.paged_prefill_chunk(params, self.cfg, rules, storage,
                                     table_row, pages_chunk, start, tokens,
                                     use_pallas=use_pallas, comm=comm,
                                     quant=quant, ep_comm=ep_comm,
                                     placement=placement, embeds=embeds)

    def paged_verify(self, params, storage, tables, lengths, tokens,
                     write_pages, write_offs, rules, *,
                     use_pallas: bool = False, comm=None, quant=None,
                     ep_comm=None, placement=None, cross=None):
        assert cross is None, "decoder-only families have no cross-KV pages"
        return T.paged_verify_chunk(params, self.cfg, rules, storage, tables,
                                    lengths, tokens, write_pages, write_offs,
                                    use_pallas=use_pallas, comm=comm,
                                    quant=quant, ep_comm=ep_comm,
                                    placement=placement)

    def serve_param_specs(self, ep: int = 1):
        """Megatron TP over the serving mesh: attention heads, MLP ff,
        experts and the unembed vocab shard over "model"; norms, router and
        the embedding table (gathered row lookup) stay replicated.  With
        ``ep > 1`` the mesh is 2-D ("expert", "model") and the expert stack
        shards over both axes, expert-major — each rank holds
        E/(ep*tp) whole experts' weight rows."""
        table = dict(SERVE_TP_AXES)
        if ep > 1:
            table["experts"] = ("expert", "model")
        specs = jax.tree_util.tree_map(
            lambda p: _map_param_spec(p.spec, table),
            self.param_defs(), is_leaf=_is_param)
        specs["embed"]["table"] = P(None, None)
        return specs


class VLM(DecoderLM):
    """LLaVA-style: precomputed anyres patch embeddings prepended to text."""

    def _embeds(self, params, batch, rules):
        txt = T.embed_tokens(params, batch["tokens"], self.cfg, rules)
        img = batch["image_embeds"].astype(txt.dtype)
        return jnp.concatenate([img, txt], axis=1)

    def loss(self, params, batch, rules):
        x = self._embeds(params, batch, rules)
        return T.lm_loss(params, self.cfg, rules, inputs_embeds=x,
                         labels=batch["labels"])

    def train_batch_specs(self, shape):
        cfg = self.cfg
        B, S = shape.global_batch, shape.seq_len
        I = cfg.n_image_tokens
        assert S > I, (S, I)
        return {
            "tokens": _tokens(B, S - I),
            "image_embeds": ArraySpec((B, I, cfg.d_model),
                                      jnp.dtype(cfg.dtype),
                                      P("batch", "seq", None)),
            "labels": _labels(B, S),
        }

    def prefill(self, params, batch, rules, max_len):
        x = self._embeds(params, batch, rules)
        return T.prefill(params, self.cfg, rules, inputs_embeds=x,
                         max_len=max_len)

    def validate_serve_encoder(self, *, page_size: int, max_len: int,
                               prefix_cache: bool = False) -> None:
        """The image prefix occupies ``n_image_tokens`` leading positions of
        every image request, so it must (a) leave room for text + at least
        one generated token inside ``max_len`` and (b) — when the prefix
        cache shares image pages between requests — tile exactly into
        pages, or the boundary page would mix image and per-request text
        content and never be sharable."""
        cfg = self.cfg
        I = cfg.n_image_tokens
        if I + 1 >= max_len:
            raise ValueError(
                f"{cfg.name}: n_image_tokens={I} leaves no room inside "
                f"max_len={max_len} for a text prompt plus one generated "
                f"token; raise max_len to at least {I + 2} (--max-len)")
        if prefix_cache and I % page_size:
            fix = max(d for d in range(1, page_size + 1) if I % d == 0)
            raise ValueError(
                f"{cfg.name}: n_image_tokens={I} is not a multiple of "
                f"page_size={page_size}, so image-prefix pages can never be "
                "shared through the prefix cache (the boundary page would "
                "mix image and text content).  Fix: pass a page size that "
                f"divides {I} — e.g. --page-size {fix} — or disable "
                "--prefix-cache")


# ---------------------------------------------------------------------------
# Hybrid (zamba2), SSM (rwkv6), audio (whisper)
# ---------------------------------------------------------------------------

class HybridLM(Model):
    """Mamba/attention hybrid (zamba-style): recurrent per-slot state, so
    it serves on the dense path only (no per-token KV to page)."""

    def param_defs(self):
        return Z.zamba_defs(self.cfg)

    def loss(self, params, batch, rules):
        return Z.lm_loss(params, self.cfg, rules, batch["tokens"],
                         batch["labels"])

    def train_batch_specs(self, shape):
        B, S = shape.global_batch, shape.seq_len
        return {"tokens": _tokens(B, S), "labels": _labels(B, S)}

    def prefill(self, params, batch, rules, max_len):
        return Z.prefill(params, self.cfg, rules, batch["tokens"], max_len)

    def init_decode_state(self, batch, max_len):
        return Z.init_state(self.cfg, batch, max_len,
                            dtype=jnp.dtype(self.cfg.dtype))

    def decode_state_specs(self, batch, max_len):
        cfg = self.cfg
        specs = Z.state_specs(cfg)
        seg = cfg.n_layers // cfg.shared_attn_every
        k = cfg.shared_attn_every
        H, Pd, N, K = (cfg.ssm_heads, cfg.ssm_head_dim, cfg.ssm_state,
                       cfg.conv_kernel)
        hkv, hd = cfg.padded_kv_heads, cfg.head_dim
        dt = jnp.dtype(cfg.dtype)
        return {
            "mamba": {
                "ssm": ArraySpec((seg, k, batch, H, N, Pd), jnp.float32,
                                 specs["mamba"]["ssm"]),
                "conv": {
                    "x": ArraySpec((seg, k, batch, K - 1, cfg.d_inner),
                                   jnp.float32, specs["mamba"]["conv"]["x"]),
                    "B": ArraySpec((seg, k, batch, K - 1, N), jnp.float32,
                                   specs["mamba"]["conv"]["B"]),
                    "C": ArraySpec((seg, k, batch, K - 1, N), jnp.float32,
                                   specs["mamba"]["conv"]["C"]),
                },
            },
            "attn_cache": {
                "k": ArraySpec((seg, batch, max_len, hkv, hd), dt,
                               specs["attn_cache"]["k"]),
                "v": ArraySpec((seg, batch, max_len, hkv, hd), dt,
                               specs["attn_cache"]["v"]),
            },
        }

    def decode_step(self, params, state, tokens, pos, rules):
        return Z.decode_step(params, self.cfg, rules, state, tokens, pos)


class RwkvLM(Model):
    """RWKV-style linear-attention LM: O(1) recurrent decode state, dense
    serving path only."""

    def param_defs(self):
        return RW.rwkv_lm_defs(self.cfg)

    def loss(self, params, batch, rules):
        return RW.lm_loss(params, self.cfg, rules, batch["tokens"],
                          batch["labels"])

    def train_batch_specs(self, shape):
        B, S = shape.global_batch, shape.seq_len
        return {"tokens": _tokens(B, S), "labels": _labels(B, S)}

    def prefill(self, params, batch, rules, max_len):
        return RW.prefill(params, self.cfg, rules, batch["tokens"])

    def init_decode_state(self, batch, max_len):
        return RW.init_state(self.cfg, batch)

    def decode_state_specs(self, batch, max_len):
        cfg = self.cfg
        Lh, H, hd, d = cfg.n_layers, cfg.rwkv_heads, cfg.rwkv_head_dim, cfg.d_model
        sp = RW.state_specs(cfg)
        return {
            "wkv": ArraySpec((Lh, batch, H, hd, hd), jnp.float32, sp["wkv"]),
            "tm_prev": ArraySpec((Lh, batch, 1, d), jnp.float32, sp["tm_prev"]),
            "cm_prev": ArraySpec((Lh, batch, 1, d), jnp.float32, sp["cm_prev"]),
        }

    def decode_step(self, params, state, tokens, pos, rules):
        return RW.decode_step(params, self.cfg, rules, state, tokens, pos)


class Whisper(Model):
    """Encoder-decoder audio model: bidirectional frame encoder + causal
    token decoder with cross-attention.  Serves paged-only — the decoder's
    self-KV pages normally while cross-K/V (computed once per clip via
    :meth:`encode_chunk` / :meth:`cross_kv_chunk`) lives in a read-only
    :class:`repro.serve.pages.CrossKVPool`."""

    def param_defs(self):
        return W.whisper_defs(self.cfg)

    def loss(self, params, batch, rules):
        return W.loss(params, self.cfg, rules, batch["frames"],
                      batch["tokens"], batch["labels"])

    def train_batch_specs(self, shape):
        cfg = self.cfg
        B, S = shape.global_batch, shape.seq_len
        return {
            "frames": ArraySpec((B, cfg.n_audio_frames, cfg.d_model),
                                jnp.dtype(cfg.dtype),
                                P("batch", "frames", None)),
            "tokens": _tokens(B, S),
            "labels": _labels(B, S),
        }

    def prefill(self, params, batch, rules, max_len):
        return W.prefill(params, self.cfg, rules, batch["frames"],
                         batch["tokens"], max_len)

    def init_decode_state(self, batch, max_len):
        return W.init_state(self.cfg, batch, max_len,
                            dtype=jnp.dtype(self.cfg.dtype))

    def decode_state_specs(self, batch, max_len):
        cfg = self.cfg
        dt = jnp.dtype(cfg.dtype)
        h, hd, Ld, F = cfg.n_heads, cfg.head_dim, cfg.decoder_layers, cfg.n_audio_frames
        sp = W.state_specs(cfg)
        return {
            "self_k": ArraySpec((Ld, batch, max_len, h, hd), dt, sp["self_k"]),
            "self_v": ArraySpec((Ld, batch, max_len, h, hd), dt, sp["self_v"]),
            "cross_k": ArraySpec((Ld, batch, F, h, hd), dt, sp["cross_k"]),
            "cross_v": ArraySpec((Ld, batch, F, h, hd), dt, sp["cross_v"]),
        }

    def decode_step(self, params, state, tokens, pos, rules):
        return W.decode_step(params, self.cfg, rules, state, tokens, pos)

    def lm_head(self, params, hidden, rules):
        return T.lm_logits(params, hidden, self.cfg, rules)

    # -- paged serving (enc-dec: self-KV pages + read-only cross-KV pages) ---
    # The decoder's self-attention cache pages exactly like a decoder-only
    # LM's; the cross-attention K/V (one linear map of the encoder output
    # per layer, computed once) lives in a separate read-only
    # :class:`repro.serve.pages.CrossKVPool` and every paged call takes a
    # ``cross={"storage", "tables", "frames_len"}`` bundle.

    def supports_paged_decode(self) -> bool:
        return True

    def paged_leaf_specs(self, quant=None):
        from repro.serve.pages import PagedLeafSpec
        from repro.serve.quant import quantize_leaf_specs
        cfg = self.cfg
        leaf = PagedLeafSpec((cfg.decoder_layers,),
                             (cfg.n_heads, cfg.head_dim),
                             jnp.dtype(cfg.dtype))
        return quantize_leaf_specs({"k": leaf, "v": leaf}, quant)

    def cross_leaf_specs(self, quant=None):
        """Leaf specs for the cross-KV pool (pages over audio-frame rows
        instead of token rows; otherwise identical machinery — int8 scale
        leaves ride along the same way)."""
        from repro.serve.pages import PagedLeafSpec
        from repro.serve.quant import quantize_leaf_specs
        cfg = self.cfg
        leaf = PagedLeafSpec((cfg.decoder_layers,),
                             (cfg.n_heads, cfg.head_dim),
                             jnp.dtype(cfg.dtype))
        return quantize_leaf_specs({"cross_k": leaf, "cross_v": leaf}, quant)

    def encode_chunk(self, params, frames, start, n_valid, rules):
        """Run the bidirectional encoder over ONE audio chunk (streaming
        chunked encode; see :func:`repro.models.whisper.encode_chunk`)."""
        return W.encode_chunk(params, self.cfg, rules, frames, start, n_valid)

    def cross_kv_chunk(self, params, enc_chunk):
        """Encoder-output chunk (1, Cf, d) -> cross K/V (Ld, Cf, h, hd)."""
        return W.cross_kv_chunk(params, self.cfg, enc_chunk)

    def scatter_cross(self, storage, pages, k, v, *, page_size: int,
                      quant=None):
        """Write one chunk's cross K/V into its pages (quantize-on-write)."""
        return W.scatter_cross(storage, pages, k, v, page_size=page_size,
                               quant=quant)

    def paged_prefill_chunk(self, params, storage, table_row, pages_chunk,
                            start, tokens, rules, *,
                            use_pallas: bool = False, comm=None, quant=None,
                            ep_comm=None, placement=None, embeds=None,
                            cross=None):
        assert embeds is None, "whisper prompts are token-only"
        assert cross is not None, "enc-dec prefill needs cross-KV pages"
        return W.paged_prefill_chunk(
            params, self.cfg, rules, storage, table_row, pages_chunk, start,
            tokens, cross["storage"], cross["tables"], cross["frames_len"],
            use_pallas=use_pallas, quant=quant)

    def paged_verify(self, params, storage, tables, lengths, tokens,
                     write_pages, write_offs, rules, *,
                     use_pallas: bool = False, comm=None, quant=None,
                     ep_comm=None, placement=None, cross=None):
        assert cross is not None, "enc-dec verify needs cross-KV pages"
        return W.paged_verify_chunk(
            params, self.cfg, rules, storage, tables, lengths, tokens,
            write_pages, write_offs, cross["storage"], cross["tables"],
            cross["frames_len"], use_pallas=use_pallas, quant=quant)

    def validate_serve_mesh(self, tp: int = 1, ep: int = 1) -> None:
        if tp > 1 or ep > 1:
            raise ValueError(
                f"{self.cfg.name} (audio/enc-dec) serves single-device only "
                f"in this release: mesh (tp={tp}, ep={ep}) is not wired for "
                "the cross-KV pool (see ROADMAP item 5 follow-ups) — drop "
                "--mesh")

    def validate_serve_encoder(self, *, page_size: int, max_len: int,
                               prefix_cache: bool = False) -> None:
        """Audio frames must fit the cross-KV page layout: at least one
        page of frames, and the decoder needs max_len >= 2 (prompt + one
        generated token).  The prefix cache never applies — decoder self-KV
        depends on the audio through cross-attention, so token-keyed
        sharing would alias different clips (the engine disables it)."""
        cfg = self.cfg
        if cfg.n_audio_frames < 1:
            raise ValueError(
                f"{cfg.name}: n_audio_frames={cfg.n_audio_frames} — an "
                "enc-dec request needs at least one audio frame")
        if max_len < 2:
            raise ValueError(
                f"{cfg.name}: max_len={max_len} cannot hold a decoder "
                "prompt plus one generated token; raise --max-len")


_FAMILIES: dict[str, type[Model]] = {
    "dense": DecoderLM,
    "moe": DecoderLM,
    "vlm": VLM,
    "hybrid": HybridLM,
    "ssm": RwkvLM,
    "audio": Whisper,
}


def build_model(cfg: ModelConfig) -> Model:
    """The family's :class:`Model` subclass bound to ``cfg``."""
    return _FAMILIES[cfg.family](cfg)
