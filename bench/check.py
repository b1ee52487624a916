"""The comparison that decides ``correct`` for a served model.

After the window closes and the program's state is freed, a sample of the
requests the window finished (drawn from the seed, the longest always in
it, one request a slot before any slot gives a second) is run once
through the plain float32 reference, prompt followed by the served tokens.
At every served position the number read is how far the logit of the
served token lies below the reference's best logit there; the number
compared is the widest such gap over the sample (``max_gap``), against
the cell's limit from ``bench/checks/<cell>.json``.  That is exact for
greedy decoding, which every cell uses.

With ``control=True`` the same positions are also read for the token a
lower-precision reference (float8 e4m3 operands) puts first; that reading
goes through the same comparison (:func:`within`) and must fail it.
"""
from __future__ import annotations

import functools

import numpy as np

BUCKET = 1024          # reference sequence lengths pad to a multiple of this
MAX_CHECKED = 2048     # served positions per request the reference reads
SAMPLE_REQUESTS = 16   # finished requests the reference reads per run


def sample(records, t0: float, t1: float, seed: int, *,
           n: int = SAMPLE_REQUESTS):
    """Up to ``n`` requests finished in the window: the longest (prompt +
    output) first, then a seeded draw that takes one request from every
    slot before it takes a second from any."""
    done = [r for r in records if r.done is not None and r.error is None
            and t0 <= r.done <= t1]
    if not done:
        return []
    done.sort(key=lambda r: -(r.spec.prompt_len + len(r.req.output)))
    rng = np.random.default_rng([int(seed), 3])
    order = [done[0]] + [done[1 + i] for i in rng.permutation(len(done) - 1)]
    seen, first, again = set(), [], []
    for r in order:
        (again if r.slot in seen else first).append(r)
        seen.add(r.slot)
    return (first + again)[:n]


def within(max_gap, limit: float) -> bool:
    """The comparison: something was checked, and its widest gap is inside
    the limit."""
    return max_gap is not None and max_gap <= limit


def served_ok(rec) -> str | None:
    """What is wrong with a finished request's output, if anything."""
    out = rec.req.output
    if len(out) != rec.spec.output_len:
        return f"served {len(out)} tokens, asked for {rec.spec.output_len}"
    return None


def gaps(c: dict, ref, weights, recs, *, control: bool = False):
    """Per request: (served-token gaps, control gaps or None)."""
    import jax
    import jax.numpy as jnp
    served = jax.jit(functools.partial(ref.served_gaps, c))
    ctrl = jax.jit(functools.partial(ref.control_gaps, c))
    vocab = int(c["vocab_size"])
    out = []
    for rec in recs:
        prompt = np.asarray(rec.req.prompt, np.int64)
        toks = np.asarray(rec.req.output, np.int64)
        if toks.min() < 0 or toks.max() >= vocab:
            raise ValueError("served token id outside the vocabulary")
        seq = np.concatenate([prompt, toks[:-1]])
        S = -(-len(seq) // BUCKET) * BUCKET
        K = min(S, MAX_CHECKED)
        n = min(len(toks), K)
        pos = np.zeros(K, np.int32)
        tgt = np.zeros(K, np.int32)
        valid = np.zeros(K, bool)
        pos[:n] = len(prompt) - 1 + np.arange(n)
        tgt[:n] = toks[:n]
        valid[:n] = True
        padded = np.zeros(S, np.int32)
        padded[:len(seq)] = seq
        args = (jnp.asarray(padded), jnp.asarray(pos))
        g = np.asarray(served(weights, *args, jnp.asarray(tgt),
                              jnp.asarray(valid)))[:n]
        cg = np.asarray(ctrl(weights, *args, jnp.asarray(valid)))[:n] \
            if control else None
        out.append((g, cg))
    return out
