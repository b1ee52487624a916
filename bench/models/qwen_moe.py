"""Plain reference of the Qwen3-MoE decoder at one chip's share of an
expert-parallel layer, and its weights.

Imports nothing of the program under test.  Attention is the published
Hugging Face ``Qwen3MoeForCausalLM``'s, as ``bench/models/qwen.py`` writes
it for Qwen3 (qk-norm, rotate-half RoPE, GQA, no biases); the MLP of every
layer is the routed expert layer:

    p = softmax(h W_router)                  # over all n_experts, float32
    top = the num_experts_per_tok largest p  # distinct experts
    g = p_top / sum(p_top)                   # norm_topk_prob
    y = sum over e in top, e held here, of  g_e (silu(h Wg_e) * (h Wu_e)) Wd_e

The configuration holds a share of the experts (``num_experts`` of the
``deployment``'s ``num_experts``, from its ``first_expert`` on), as one
chip of an expert-parallel deployment does: routing covers every expert,
and the experts held elsewhere add nothing here, in the reference as in
the program.  Every held expert is computed for every token and weighted
by its gate (zero where the token did not pick it).

The weights are drawn from the seed as in ``bench/models/qwen.py`` and
stored as bfloat16, which holds their values exactly; each layer is cast
to float32 as the layer scan reaches it.  Attention is computed in blocks
of query rows and the head in blocks of the vocabulary, so that the
published widths fit one chip beside the weights.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from bench.models.qwen import E4M3_MAX, _rms, _rope, fp8_matmul, fp8_rounded
from bench.models.qwen import seed_key  # noqa: F401  (bench/run.py reads it)

QUERY_BLOCK = 512       # query rows whose attention scores are held at once
VOCAB_BLOCKS = 8        # the head's logits are computed in this many blocks
HIGHEST = jax.lax.Precision.HIGHEST


def dims(c: dict) -> dict:
    dep = c["deployment"]
    if not c["norm_topk_prob"] or c["tie_word_embeddings"] \
            or c["mlp_only_layers"] or int(c["decoder_sparse_step"]) != 1:
        raise ValueError("the reference computes every layer as a routed "
                         "expert layer with renormalised top-k gates and an "
                         "untied head")
    d = int(c["hidden_size"])
    return dict(L=int(c["num_hidden_layers"]), d=d,
                hq=int(c["num_attention_heads"]),
                hkv=int(c["num_key_value_heads"]), hd=int(c["head_dim"]),
                f=int(c["moe_intermediate_size"]), v=int(c["vocab_size"]),
                E=int(dep["num_experts"]), H=int(c["num_experts"]),
                lo=int(dep["first_expert"]), k=int(c["num_experts_per_tok"]),
                eps=float(c["rms_norm_eps"]), theta=float(c["rope_theta"]))


def weight_shapes(c: dict) -> dict:
    m = dims(c)
    L, d, hq, hkv, hd, f, v, E, H = (m[k] for k in ("L", "d", "hq", "hkv",
                                                    "hd", "f", "v", "E", "H"))
    layers = {"ln1": (L, d), "ln2": (L, d),
              "wq": (L, d, hq * hd), "wk": (L, d, hkv * hd),
              "wv": (L, d, hkv * hd), "wo": (L, hq * hd, d),
              "q_norm": (L, hd), "k_norm": (L, hd),
              "router": (L, d, E),
              "wg": (L, H, d, f), "wu": (L, H, d, f), "wd": (L, H, f, d)}
    return {"embed": (v, d), "layers": layers, "final_norm": (d,),
            "head": (d, v)}


def make_weights(c: dict, key):
    """All weights from ``key``, bfloat16 (call inside ``jax.jit``)."""
    shapes = weight_shapes(c)
    flat, tree = jax.tree_util.tree_flatten_with_path(
        shapes, is_leaf=lambda x: isinstance(x, tuple))
    out = []
    for i, (path, shape) in enumerate(flat):
        name = path[-1].key
        z = jax.random.normal(jax.random.fold_in(key, i), shape, jnp.float32)
        if name in ("ln1", "ln2", "q_norm", "k_norm", "final_norm"):
            w = 1.0 + 0.1 * z
        elif name == "embed":               # (vocab, d): rows as the head's
            w = z * shape[-1] ** -0.5
        else:                       # (..., fan_in, fan_out)
            w = z * shape[-2] ** -0.5
        out.append(w.astype(jnp.bfloat16))
    return jax.tree_util.tree_unflatten(tree, out)


def _attention(m, q, k, v, pos):
    """Causal GQA over one sequence, ``QUERY_BLOCK`` query rows at a time.
    q: (S, hkv, g, hd); k, v: (S, hkv, hd) -> (S, hq * hd)."""
    S = q.shape[0]
    qb = QUERY_BLOCK if S % QUERY_BLOCK == 0 else S

    def block(i):
        qi = jax.lax.dynamic_slice_in_dim(q, i * qb, qb, 0)
        pi = i * qb + jnp.arange(qb)
        s = jnp.einsum("qhgd,khd->hgqk", qi, k,
                       precision=HIGHEST) * m["hd"] ** -0.5
        s = jnp.where(pi[:, None] >= pos[None, :], s, -jnp.inf)
        a = jax.nn.softmax(s, axis=-1)
        return jnp.einsum("hgqk,khd->qhgd", a, v, precision=HIGHEST)
    o = jax.lax.map(block, jnp.arange(S // qb))
    return o.reshape(S, m["hq"] * m["hd"])


def _experts(m, h, p, mm):
    """The held experts' part of the routed layer's output, (S, d)."""
    probs = jax.nn.softmax(mm(h, p["router"]), axis=-1)       # (S, E)
    top_p, top_e = jax.lax.top_k(probs, m["k"])
    gate = top_p / top_p.sum(-1, keepdims=True)
    # each held expert's gate in each token's sum: 0 where not picked
    wts = (jax.nn.one_hot(top_e - m["lo"], m["H"], dtype=jnp.float32)
           * gate[..., None]).sum(1)                           # (S, H)

    def expert(y, xs):
        wg, wu, wd, wt = xs
        return y + wt[:, None] * mm(jax.nn.silu(mm(h, wg)) * mm(h, wu),
                                    wd), None
    y, _ = jax.lax.scan(expert, jnp.zeros_like(h),
                        (p["wg"], p["wu"], p["wd"], wts.T))
    return y


def hidden_states(c: dict, w, tokens, *, mm=None):
    """Final normed hidden states (S, d) of one sequence, float32.  ``mm(a,
    b)`` is the product with a weight matrix, float32 at full precision by
    default."""
    m = dims(c)
    mm = mm or functools.partial(jnp.matmul, precision=HIGHEST)
    S = tokens.shape[0]
    pos = jnp.arange(S)
    g = m["hq"] // m["hkv"]

    def layer(x, p):
        p = jax.tree_util.tree_map(lambda a: a.astype(jnp.float32), p)
        h = _rms(x, p["ln1"], m["eps"])
        q = mm(h, p["wq"]).reshape(S, m["hq"], m["hd"])
        k = mm(h, p["wk"]).reshape(S, m["hkv"], m["hd"])
        v = mm(h, p["wv"]).reshape(S, m["hkv"], m["hd"])
        q, k = _rms(q, p["q_norm"], m["eps"]), _rms(k, p["k_norm"], m["eps"])
        q, k = _rope(q, pos, m["theta"]), _rope(k, pos, m["theta"])
        o = _attention(m, q.reshape(S, m["hkv"], g, m["hd"]), k, v, pos)
        x = x + mm(o, p["wo"])
        return x + _experts(m, _rms(x, p["ln2"], m["eps"]), p, mm), None

    x = w["embed"][tokens].astype(jnp.float32)
    x, _ = jax.lax.scan(layer, x, w["layers"])
    return _rms(x, w["final_norm"].astype(jnp.float32), m["eps"])


def _head_blocks(w):
    """(n, vb, block): the head as ``n`` blocks of ``vb`` columns (one block
    when the vocabulary does not divide into ``VOCAB_BLOCKS``);
    ``block(i)`` is block ``i`` in float32."""
    v = w["head"].shape[1]
    n = VOCAB_BLOCKS if v % VOCAB_BLOCKS == 0 else 1
    vb = v // n

    def block(i):
        return jax.lax.dynamic_slice_in_dim(w["head"], i * vb, vb,
                                            1).astype(jnp.float32)
    return n, vb, block


def served_gaps(c: dict, w, tokens, positions, targets, valid):
    """For each checked position: how far the logit of the token that was
    served lies below the reference's best logit.  ``tokens`` (S,) is the
    prompt followed by the served tokens (right-padded), ``positions`` (K,)
    index the logits that chose ``targets`` (K,); ``valid`` (K,) masks pad
    entries (gap 0)."""
    h = hidden_states(c, w, tokens)[positions]
    n, vb, head = _head_blocks(w)

    def block(i):
        lg = jnp.matmul(h, head(i), precision=HIGHEST)
        j = targets - i * vb
        got = jnp.take_along_axis(lg, jnp.clip(j, 0, vb - 1)[:, None], -1)
        return lg.max(-1), jnp.where((j >= 0) & (j < vb), got[:, 0],
                                     -jnp.inf)
    best, got = jax.lax.map(block, jnp.arange(n))
    return jnp.where(valid, best.max(0) - got.max(0), 0.0)


def control_gaps(c: dict, w, tokens, positions, valid):
    """The control: the same positions computed with float8 e4m3 operands
    in every projection, the router, the experts and the head; each
    position's gap is read for the token this lower precision puts first,
    against the float32 reference."""
    h = hidden_states(c, w, tokens)[positions]
    hl = fp8_rounded(hidden_states(c, w, tokens, mm=fp8_matmul)[positions],
                     -1)
    # one scale for the whole head, as fp8_matmul gives a weight matrix
    s = jnp.maximum(jnp.max(jnp.abs(w["head"])).astype(jnp.float32),
                    1e-30) / E4M3_MAX
    n, _, head = _head_blocks(w)

    def block(i):
        hb = head(i)
        ref = jnp.matmul(h, hb, precision=HIGHEST)
        low = jnp.matmul(hl, (hb / s).astype(jnp.float8_e4m3fn)
                         .astype(jnp.float32) * s, precision=HIGHEST)
        pick = jnp.argmax(low, -1)
        return (ref.max(-1), low.max(-1),
                jnp.take_along_axis(ref, pick[:, None], -1)[:, 0])
    ref_best, low_best, ref_at_pick = jax.lax.map(block, jnp.arange(n))
    # the block holding the low-precision first choice (first on a tie, as
    # one argmax over the whole vocabulary takes)
    b = jnp.argmax(low_best, 0)
    got = jnp.take_along_axis(ref_at_pick, b[None], 0)[0]
    return jnp.where(valid, ref_best.max(0) - got, 0.0)
