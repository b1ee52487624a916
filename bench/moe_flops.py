"""Operations a served token needs in a configuration whose expert layer
holds a share of the experts (``bench/models/qwen_moe.py``).

Counts multiply-adds as two operations, as ``bench/flops.py`` does.  Per
token, per layer: the attention projections and the router (2 x their
weights) and attention (2 x heads x head_dim for q.k and again for p.v,
per position attended).  The output head is counted only where logits are
produced: every decode step, and the last prompt position of a prefill.
Work recomputed after a preemption and positions served from the prefix
cache are not counted.

The held experts' work is read from the engine's ``moe_local_assignments``
counter over the window: each assignment a held expert computed is 3
products of ``hidden x moe_intermediate`` (gate, up, down).  The rows of
the dispatch buffer that no assignment fills are not counted.
"""
from __future__ import annotations

from bench.models.qwen_moe import dims


def layer_matmul_params(c: dict) -> int:
    """Attention projections and router of one layer."""
    m = dims(c)
    d, hq, hkv, hd = m["d"], m["hq"], m["hkv"], m["hd"]
    return d * (hq + 2 * hkv) * hd + hq * hd * d + d * m["E"]


def token_flops(c: dict, context: int, *, head: bool) -> float:
    """One token that attends to ``context`` positions (itself included),
    without its experts."""
    m = dims(c)
    per_layer = 2 * layer_matmul_params(c) + 4 * m["hq"] * m["hd"] * context
    return m["L"] * per_layer + (2 * m["d"] * m["v"] if head else 0)


def prefill_flops(c: dict, start: int, end: int) -> float:
    """Prompt positions ``start..end-1`` computed, logits at ``end-1``,
    without their experts."""
    m = dims(c)
    n = end - start
    if n <= 0:
        return 0.0
    ctx = (start + 1 + end) * n / 2           # sum of (p + 1) over positions
    return (m["L"] * (2 * layer_matmul_params(c) * n
                      + 4 * m["hq"] * m["hd"] * ctx)
            + 2 * m["d"] * m["v"])


def assignment_flops(c: dict) -> float:
    """One token's assignment to one expert."""
    m = dims(c)
    return 6.0 * m["d"] * m["f"]


def window_flops(c: dict, window) -> float | None:
    """Useful model operations of the tokens computed in ``window``; None
    when the engine has no ``moe_local_assignments`` counter."""
    key = "moe_local_assignments"
    if key not in window.stats0 or key not in window.stats1:
        return None
    total = (window.stats1[key] - window.stats0[key]) * assignment_flops(c)
    for rec in window.records:
        P = rec.spec.prompt_len
        if rec.admissions:
            t, length, cached = rec.admissions[0]
            if window.t0 <= t <= window.t1:
                total += prefill_flops(c, int(round(cached)), length)
        for j, tj in enumerate(rec.token_times):
            if j and window.t0 < tj <= window.t1:
                # decode step j: input at position P + j - 1
                total += token_flops(c, P + j, head=True)
    return total
