"""The one traffic generator: reads a mix's parameters from its data file.

A mix file (``bench/traffic/<mix>.json``) fixes the *set* of request sizes
and inter-arrival gaps from its own ``size_seed``; the run's ``--seed``
only permutes that set, in blocks of ``block`` consecutive requests, and
draws the token ids.  So every seed serves the same work in another order,
and two seeds differ far less than two independent draws would.

Keys of a mix file:

* ``loop``: ``"open"`` (arrivals on a schedule, ``rate_per_s``, Poisson
  gaps) or ``"closed"`` (``clients`` callers, each sending its next request
  when the last completes).
* ``requests``, ``block``, ``size_seed``: the fixed set and its blocking
  (``block`` 1: one fixed order for every seed).
* ``sessions`` (optional): ``count`` shared system prompts with Zipf
  (``zipf_s``) popularity and lengths uniform in ``prefix_len`` [lo, hi].
* ``prompt_len`` / ``output_len``: lognormal ``median`` and ``sigma``,
  clipped to [``min``, ``max``].  ``prompt_len`` is the unshared tail.
* ``engine``: the serving knobs this mix is deployed with (``max_len``
  bounds prompt + output).
* ``warm_sessions``: prefill every system prompt during set-up.
"""
from __future__ import annotations

import dataclasses

import numpy as np


def poisson_arrivals(n: int, rate: float, rng) -> np.ndarray:
    """Cumulative exponential inter-arrivals (copy of
    ``repro.serve.traffic.poisson_arrivals``)."""
    return np.cumsum(rng.exponential(1.0 / rate, size=n))


def _lognormal(rng, n: int, p: dict) -> np.ndarray:
    x = np.exp(np.log(p["median"]) + p["sigma"] * rng.standard_normal(n))
    return np.clip(np.rint(x), p["min"], p["max"]).astype(np.int64)


def _block_perm(rng, n: int, block: int) -> np.ndarray:
    """A permutation of range(n) that moves entries only within their
    block of ``block`` consecutive indices."""
    out = np.arange(n)
    for lo in range(0, n, block):
        out[lo:lo + block] = lo + rng.permutation(min(block, n - lo))
    return out


@dataclasses.dataclass(frozen=True)
class RequestSpec:
    index: int
    session: int            # -1: no shared system prompt
    prefix_len: int
    tail_len: int
    output_len: int

    @property
    def prompt_len(self) -> int:
        return self.prefix_len + self.tail_len


class Traffic:
    """One run's traffic: the mix's fixed size set in the order and with
    the token ids of ``seed``."""

    def __init__(self, mix: dict, seed: int, vocab: int):
        self.mix, self.seed, self.vocab = mix, int(seed), int(vocab)
        n = int(mix["requests"])
        max_len = int(mix["engine"]["max_len"])
        sizes = np.random.default_rng(int(mix["size_seed"]))
        sess = mix.get("sessions")
        if sess:
            k = int(sess["count"])
            lo, hi = sess["prefix_len"]
            self.prefix_lens = sizes.integers(lo, hi + 1, size=k)
            pop = 1.0 / np.arange(1, k + 1) ** float(sess["zipf_s"])
            session = sizes.choice(k, size=n, p=pop / pop.sum())
        else:
            self.prefix_lens = np.zeros(0, np.int64)
            session = np.full(n, -1)
        tails = _lognormal(sizes, n, mix["prompt_len"])
        outs = _lognormal(sizes, n, mix["output_len"])
        gaps = np.diff(poisson_arrivals(n, mix["rate_per_s"], sizes),
                       prepend=0.0) if mix["loop"] == "open" else np.zeros(n)
        specs = []
        for i in range(n):
            pre = int(self.prefix_lens[session[i]]) if session[i] >= 0 else 0
            out = int(outs[i])
            # prompt + output must stay below max_len (the engine retires a
            # request at max_len - 1): shorten the tail, then the output
            tail = int(min(tails[i], max(mix["prompt_len"]["min"],
                                         max_len - 2 - pre - out)))
            out = int(min(out, max_len - 2 - pre - tail))
            specs.append((int(session[i]), pre, tail, out))
        run = np.random.default_rng([self.seed, 0])
        block = int(mix["block"])
        order = _block_perm(run, n, block)
        self._specs = [specs[j] for j in order]
        self.gaps = gaps[_block_perm(run, n, block)]

    def __len__(self) -> int:
        return len(self._specs)

    def spec(self, i: int) -> RequestSpec:
        session, pre, tail, out = self._specs[i % len(self._specs)]
        return RequestSpec(i, session, pre, tail, out)

    def session_prompt(self, k: int) -> np.ndarray:
        rng = np.random.default_rng([self.seed, 1, k])
        return rng.integers(0, self.vocab, int(self.prefix_lens[k]),
                            dtype=np.int64).astype(np.int32)

    def prompt(self, i: int) -> np.ndarray:
        s = self.spec(i)
        rng = np.random.default_rng([self.seed, 2, i])
        tail = rng.integers(0, self.vocab, s.tail_len,
                            dtype=np.int64).astype(np.int32)
        if s.session < 0:
            return tail
        return np.concatenate([self.session_prompt(s.session), tail])

    def due_offsets(self, n: int) -> np.ndarray:
        """Open loop: due times of requests 0..n-1, seconds after the
        window opens (the gap set repeats past ``requests``)."""
        reps = -(-n // len(self.gaps))
        return np.cumsum(np.tile(self.gaps, reps)[:n])
