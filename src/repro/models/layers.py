"""Shared neural-net layers (functional; params are plain pytrees)."""
from __future__ import annotations

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

from repro.models.module import Param


# ---------------------------------------------------------------------------
# Norms
# ---------------------------------------------------------------------------

def rmsnorm_def(dim: int) -> dict:
    return {"scale": Param((dim,), P(None), init="ones")}

def rmsnorm(params, x, *, eps: float = 1e-6, use_pallas: bool = False):
    if use_pallas:
        from repro.kernels import ops as kops
        return kops.rmsnorm(x, params["scale"], eps=eps)
    xf = x.astype(jnp.float32)
    var = jnp.mean(xf * xf, axis=-1, keepdims=True)
    y = xf * jax.lax.rsqrt(var + eps)
    return (y * params["scale"].astype(jnp.float32)).astype(x.dtype)


def layernorm_def(dim: int) -> dict:
    return {"scale": Param((dim,), P(None), init="ones"),
            "bias": Param((dim,), P(None), init="zeros")}

def layernorm(params, x, *, eps: float = 1e-5):
    xf = x.astype(jnp.float32)
    mu = jnp.mean(xf, axis=-1, keepdims=True)
    var = jnp.var(xf, axis=-1, keepdims=True)
    y = (xf - mu) * jax.lax.rsqrt(var + eps)
    return (y * params["scale"].astype(jnp.float32)
            + params["bias"].astype(jnp.float32)).astype(x.dtype)


# ---------------------------------------------------------------------------
# Embedding / unembedding
# ---------------------------------------------------------------------------

def embedding_def(vocab: int, dim: int) -> dict:
    return {"table": Param((vocab, dim), P("vocab", "embed"), init="small")}

def embed(params, tokens):
    return jnp.take(params["table"], tokens, axis=0)

def unembed(params, x):
    """Tied unembedding: (B,S,d) @ (V,d)^T -> (B,S,V)."""
    return jnp.einsum("bsd,vd->bsv", x, params["table"],
                      preferred_element_type=jnp.float32)


def linear_def(d_in: int, d_out: int, spec: P, *, bias: bool = False,
               init: str = "normal") -> dict:
    d = {"w": Param((d_in, d_out), spec, init=init)}
    if bias:
        bias_axis = spec[-1] if len(spec) else None
        d["b"] = Param((d_out,), P(bias_axis), init="zeros")
    return d

def linear(params, x):
    y = x @ params["w"].astype(x.dtype)
    if "b" in params:
        y = y + params["b"].astype(y.dtype)
    return y


# ---------------------------------------------------------------------------
# Gated MLP (SwiGLU family)
# ---------------------------------------------------------------------------

def mlp_def(d_model: int, d_ff: int) -> dict:
    return {
        "gate": Param((d_model, d_ff), P("embed_w", "mlp"),
                      read_as_compute=True),
        "up": Param((d_model, d_ff), P("embed_w", "mlp"),
                    read_as_compute=True),
        "down": Param((d_ff, d_model), P("mlp", "embed_w"),
                      read_as_compute=True),
    }

def mlp(params, x, *, activation=jax.nn.silu):
    g = x @ params["gate"].astype(x.dtype)
    u = x @ params["up"].astype(x.dtype)
    return (activation(g) * u) @ params["down"].astype(x.dtype)


def mlp_plain_def(d_model: int, d_ff: int) -> dict:
    """Non-gated FFN with biases (whisper-style)."""
    return {
        "up": Param((d_model, d_ff), P("embed_w", "mlp")),
        "up_b": Param((d_ff,), P("mlp"), init="zeros"),
        "down": Param((d_ff, d_model), P("mlp", "embed_w")),
        "down_b": Param((d_model,), P(None), init="zeros"),
    }

def mlp_plain(params, x, *, activation=jax.nn.gelu):
    h = activation(x @ params["up"].astype(x.dtype)
                   + params["up_b"].astype(x.dtype))
    return h @ params["down"].astype(x.dtype) + params["down_b"].astype(x.dtype)


def sinusoidal_pos(positions, dim: int, *, base: float = 10000.0):
    """(S,) -> (S, dim) sinusoidal embeddings (whisper enc/dec)."""
    half = dim // 2
    freqs = base ** (-jnp.arange(half, dtype=jnp.float32) / max(half - 1, 1))
    ang = positions[:, None].astype(jnp.float32) * freqs[None, :]
    return jnp.concatenate([jnp.sin(ang), jnp.cos(ang)], axis=-1)


# ---------------------------------------------------------------------------
# Rotary position embedding
# ---------------------------------------------------------------------------

def rope(x, positions, *, theta: float = 10000.0):
    """x: (..., S, H, D); positions: (..., S)."""
    d = x.shape[-1]
    half = d // 2
    freqs = theta ** (-jnp.arange(0, half, dtype=jnp.float32) / half)
    ang = positions[..., None].astype(jnp.float32) * freqs      # (..., S, half)
    cos = jnp.cos(ang)[..., None, :]                            # (..., S, 1, half)
    sin = jnp.sin(ang)[..., None, :]
    x1, x2 = x[..., :half], x[..., half:]
    out = jnp.concatenate(
        [x1 * cos - x2 * sin, x2 * cos + x1 * sin], axis=-1)
    return out.astype(x.dtype)


# ---------------------------------------------------------------------------
# Loss
# ---------------------------------------------------------------------------

def cross_entropy(logits, labels, *, true_vocab: int | None = None,
                  z_loss: float = 0.0):
    """Stable CE in f32.  ``labels < 0`` positions are masked out.

    ``true_vocab``: when the vocab axis is padded for TP divisibility, the
    padded tail is excluded from the partition function.
    """
    logits = logits.astype(jnp.float32)
    v = logits.shape[-1]
    if true_vocab is not None and true_vocab < v:
        pad_mask = jnp.arange(v) >= true_vocab
        logits = jnp.where(pad_mask, -1e30, logits)
    m = jax.lax.stop_gradient(jnp.max(logits, axis=-1, keepdims=True))
    shifted = logits - m
    lse = jnp.log(jnp.sum(jnp.exp(shifted), axis=-1)) + m[..., 0]
    gold = jnp.take_along_axis(
        logits, jnp.maximum(labels, 0)[..., None], axis=-1)[..., 0]
    nll = lse - gold
    if z_loss:
        nll = nll + z_loss * lse ** 2
    weights = (labels >= 0).astype(jnp.float32)
    total = jnp.sum(nll * weights)
    denom = jnp.maximum(jnp.sum(weights), 1.0)
    return total / denom
