"""Plain reference of the Qwen2 / Qwen3 dense decoder, and its weights.

Imports nothing of the program under test.  The layer equations follow the
published Hugging Face ``Qwen2ForCausalLM`` / ``Qwen3ForCausalLM``:

    x = embed[tokens]
    per layer:  h = rms(x) * ln1
                q, k, v = h Wq (+ bq), h Wk (+ bk), h Wv (+ bv)   # qwen2: bias
                q, k = rms(q) * q_norm, rms(k) * k_norm   # qwen3, per head
                q, k = rope(q), rope(k)          # rotate-half, rope_theta
                x += softmax(q k^T / sqrt(D) + causal) v  Wo   # GQA groups
                h = rms(x) * ln2
                x += (silu(h Wg) * (h Wu)) Wd
    logits = (rms(x) * final_norm) W_head      # W_head = embed^T when tied

The weights are drawn from the seed on the device in one jitted call, as
float32 arrays holding bfloat16 values (the published checkpoints are
bfloat16): every matrix N(0, 1/fan_in), the embedding N(0, 1/d) (it is
the head's transpose when tied), biases 0.2 N(0, 1) and norm scales
1 + 0.1 N(0, 1).  With these the logits stay near unit scale at any
width, and a tied head does not simply repeat the input token.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp


def dims(c: dict) -> dict:
    d = int(c["hidden_size"])
    hq = int(c["num_attention_heads"])
    hd = int(c.get("head_dim") or d // hq)
    return dict(L=int(c["num_hidden_layers"]), d=d, hq=hq,
                hkv=int(c["num_key_value_heads"]), hd=hd,
                f=int(c["intermediate_size"]), v=int(c["vocab_size"]),
                bias=c["model_type"] == "qwen2", qk_norm=c["model_type"] == "qwen3",
                tied=bool(c["tie_word_embeddings"]),
                eps=float(c["rms_norm_eps"]), theta=float(c["rope_theta"]))


def seed_key(seed: int):
    """A PRNG key from any non-negative seed (all 64 bits count)."""
    seed = int(seed)
    return jax.random.fold_in(jax.random.PRNGKey(seed & 0xFFFFFFFF),
                              seed >> 32)


def weight_shapes(c: dict) -> dict:
    m = dims(c)
    L, d, hq, hkv, hd, f, v = (m[k] for k in ("L", "d", "hq", "hkv", "hd",
                                              "f", "v"))
    layers = {"ln1": (L, d), "ln2": (L, d),
              "wq": (L, d, hq * hd), "wk": (L, d, hkv * hd),
              "wv": (L, d, hkv * hd), "wo": (L, hq * hd, d),
              "wg": (L, d, f), "wu": (L, d, f), "wd": (L, f, d)}
    if m["bias"]:
        layers.update(bq=(L, hq * hd), bk=(L, hkv * hd), bv=(L, hkv * hd))
    if m["qk_norm"]:
        layers.update(q_norm=(L, hd), k_norm=(L, hd))
    out = {"embed": (v, d), "layers": layers, "final_norm": (d,)}
    if not m["tied"]:
        out["head"] = (d, v)
    return out


def make_weights(c: dict, key):
    """All weights from ``key`` (call inside ``jax.jit``)."""
    shapes = weight_shapes(c)
    flat, tree = jax.tree_util.tree_flatten_with_path(
        shapes, is_leaf=lambda x: isinstance(x, tuple))
    out = []
    for i, (path, shape) in enumerate(flat):
        name = path[-1].key
        z = jax.random.normal(jax.random.fold_in(key, i), shape, jnp.float32)
        if name in ("ln1", "ln2", "q_norm", "k_norm", "final_norm"):
            w = 1.0 + 0.1 * z
        elif name in ("bq", "bk", "bv"):
            w = 0.2 * z
        elif name == "embed":               # (vocab, d): rows as the head's
            w = z * shape[-1] ** -0.5
        else:                       # (..., fan_in, fan_out)
            w = z * shape[-2] ** -0.5
        out.append(w.astype(jnp.bfloat16).astype(jnp.float32))
    return jax.tree_util.tree_unflatten(tree, out)


def weight_specs(c: dict, axis: str) -> dict:
    """How the weights split over a mesh ``axis`` for the reference on
    several chips: every matrix and the embedding along their widest
    (head, feature or vocabulary) axis, norms replicated."""
    from jax.sharding import PartitionSpec as P
    split_in = {"wo", "wd"}

    def spec(path, shape):
        name = path[-1].key
        if len(shape) == 1 or name in ("ln1", "ln2", "q_norm", "k_norm"):
            return P()
        if name == "embed":
            return P(axis, None)
        if name in split_in:
            return P(None, axis, None)
        return P(*([None] * (len(shape) - 1)), axis)
    return jax.tree_util.tree_map_with_path(
        spec, weight_shapes(c), is_leaf=lambda x: isinstance(x, tuple))


def head_matrix(w):
    return w["head"] if "head" in w else w["embed"].T


def _rms(x, scale, eps):
    x = x.astype(jnp.float32)
    return x * jax.lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + eps) * scale


def _rope(x, pos, theta):
    half = x.shape[-1] // 2
    inv = theta ** (-jnp.arange(half, dtype=jnp.float32) / half)
    ang = pos[:, None].astype(jnp.float32) * inv            # (S, half)
    cos, sin = jnp.cos(ang)[:, None], jnp.sin(ang)[:, None]
    x1, x2 = x[..., :half], x[..., half:]
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], -1)


def hidden_states(c: dict, w, tokens, *, mm=None):
    """Final normed hidden states (S, d) of one sequence.  ``mm(a, b)`` is
    the product with a weight matrix, float32 at full precision by
    default."""
    m = dims(c)
    mm = mm or functools.partial(jnp.matmul,
                                 precision=jax.lax.Precision.HIGHEST)
    S = tokens.shape[0]
    pos = jnp.arange(S)
    causal = pos[:, None] >= pos[None, :]
    g = m["hq"] // m["hkv"]

    def layer(x, p):
        h = _rms(x, p["ln1"], m["eps"])
        q, k, v = mm(h, p["wq"]), mm(h, p["wk"]), mm(h, p["wv"])
        if m["bias"]:
            q, k, v = q + p["bq"], k + p["bk"], v + p["bv"]
        q = q.reshape(S, m["hq"], m["hd"])
        k = k.reshape(S, m["hkv"], m["hd"])
        v = v.reshape(S, m["hkv"], m["hd"])
        if m["qk_norm"]:
            q, k = _rms(q, p["q_norm"], m["eps"]), _rms(k, p["k_norm"], m["eps"])
        q, k = _rope(q, pos, m["theta"]), _rope(k, pos, m["theta"])
        q = q.reshape(S, m["hkv"], g, m["hd"])
        s = jnp.einsum("qhgd,khd->hgqk", q, k,
                       precision=jax.lax.Precision.HIGHEST) * m["hd"] ** -0.5
        s = jnp.where(causal, s, -jnp.inf)
        a = jax.nn.softmax(s, axis=-1)
        o = jnp.einsum("hgqk,khd->qhgd", a, v,
                       precision=jax.lax.Precision.HIGHEST)
        x = x + mm(o.reshape(S, m["hq"] * m["hd"]), p["wo"])
        h = _rms(x, p["ln2"], m["eps"])
        x = x + mm(jax.nn.silu(mm(h, p["wg"])) * mm(h, p["wu"]), p["wd"])
        return x, None

    x = w["embed"][tokens].astype(jnp.float32)
    x, _ = jax.lax.scan(layer, x, w["layers"])
    return _rms(x, w["final_norm"], m["eps"])


E4M3_MAX = 448.0         # largest finite float8 e4m3 value


def fp8_rounded(a, axis=None):
    """``a`` rounded to float8 e4m3, with one scale per slice along ``axis``
    (one for the whole array when None) that maps the slice's largest
    magnitude to e4m3's largest: the precision step below the published
    bfloat16."""
    keep = axis is not None
    amax = jnp.max(jnp.abs(a), axis=axis, keepdims=keep)
    s = jnp.maximum(amax, 1e-30) / E4M3_MAX
    return (a / s).astype(jnp.float8_e4m3fn).astype(jnp.float32) * s


def fp8_matmul(a, b):
    """Activations rounded to e4m3 one scale a row, the weight matrix one
    scale for the whole; products summed in float32."""
    return jnp.matmul(fp8_rounded(a, -1), fp8_rounded(b),
                      precision=jax.lax.Precision.HIGHEST)


def served_gaps(c: dict, w, tokens, positions, targets, valid):
    """For each checked position: how far the logit of the token that was
    served lies below the reference's best logit.  ``tokens`` (S,) is the
    prompt followed by the served tokens (right-padded), ``positions`` (K,)
    index the logits that chose ``targets`` (K,); ``valid`` (K,) masks pad
    entries (gap 0)."""
    h = hidden_states(c, w, tokens)[positions]
    logits = jnp.matmul(h, head_matrix(w),
                        precision=jax.lax.Precision.HIGHEST)
    best = logits.max(-1)
    got = jnp.take_along_axis(logits, targets[:, None], -1)[:, 0]
    return jnp.where(valid, best - got, 0.0)


def control_gaps(c: dict, w, tokens, positions, valid):
    """The control: the same positions computed with float8 e4m3 operands
    in every projection and the head; each position's gap is read for the
    token this lower precision puts first, against the float32
    reference."""
    h = hidden_states(c, w, tokens)[positions]
    ref = jnp.matmul(h, head_matrix(w), precision=jax.lax.Precision.HIGHEST)
    hl = hidden_states(c, w, tokens, mm=fp8_matmul)[positions]
    low = fp8_matmul(hl, head_matrix(w))
    pick = jnp.argmax(low, -1)
    got = jnp.take_along_axis(ref, pick[:, None], -1)[:, 0]
    return jnp.where(valid, ref.max(-1) - got, 0.0)
