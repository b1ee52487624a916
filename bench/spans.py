"""The serving engine's own spans and named programs in a profiler trace ->
device time per program, the decode step's device time, and device-idle
time inside each tick split by the host phase it fell in.

    python3 bench/spans.py <trace dir> [--chips N]

reads the one ``.xplane.pb`` under a directory the JAX profiler wrote
(``jax.profiler.trace``, or ``bench/trace.py``'s ``start``/``stop``) and
prints one JSON object: what ``bench/trace.py`` reduces, these keys, and
the three numbers of ``summary``.  A trace without the benchmark's
``bench.window`` span is read from its first ``serve.tick`` to its last.

``load`` is ``bench/trace.py``'s plus ``"spans"``: the engine's ``serve.*``
host spans with their stats.  ``reduce`` is pure Python over that dict, so
it is tested on synthetic and recorded event lists.  The window is the
benchmark's ``bench.window`` span.  Programs are the device planes' ``XLA
Modules`` events, named ``jit_<name>`` by the engine (``serve/engine.py``);
a trailing ``(<fingerprint>)`` is dropped.

Host and device timestamps in one trace differ by an offset of about a
millisecond, and the host phases inside a tick last a few.  So the
attribution of idle time to phases first moves the device's idle
intervals onto the host clock by ``clock_offset_ms``, estimated from the
trace itself: a tick's ``serve.sample`` span ends once the step program
it waits on (``STEPS``) has ended on the device, so its end less that
step's end is the offset plus the host's wake-up after the step; the
least of these over the window's ticks is taken, each sample paired with
the step ending nearest to it where that step's nearest sample is it.
"""
from __future__ import annotations

import argparse
import bisect
import glob
import json
import os
import statistics
import sys

DECODE = "jit_decode_paged"
STEPS = (DECODE, "jit_verify_paged", "jit_decode")  # serve.sample waits
PREFILL = ("jit_prefill_chunk", "jit_lm_head")


def load(log_dir: str) -> dict:
    """``bench/trace.py``'s ``load`` plus ``"spans"``: every ``serve.*``
    host span as ``(name, start_ns, dur_ns, stats)``."""
    from jax.profiler import ProfileData

    from bench import trace as TR
    ev = TR.load(log_dir)
    path, = glob.glob(os.path.join(log_dir, "**", "*.xplane.pb"),
                      recursive=True)
    spans = []
    for plane in ProfileData.from_file(path).planes:
        if plane.name.startswith("/host:"):
            for line in plane.lines:
                spans.extend((e.name, e.start_ns, e.duration_ns,
                              dict(e.stats))
                             for e in line.events
                             if e.name.startswith("serve."))
    ev["spans"] = spans
    return ev


def program(name: str) -> str:
    """``jit_decode_paged(1234)`` -> ``jit_decode_paged``."""
    return name.split("(", 1)[0]


def _innermost(spans):
    """Properly nested ``(name, start, dur, ...)`` spans -> pieces
    ``(name, start, dur)`` that do not overlap, each labelled by the
    innermost span covering it."""
    out, stack, t = [], [], None
    for name, s, d, *_ in sorted(spans, key=lambda x: (x[1], -x[2])):
        while stack and stack[-1][1] <= s:
            n, e = stack.pop()
            if e > t:
                out.append((n, t, e - t))
                t = e
        if stack and s > t:
            out.append((stack[-1][0], t, s - t))
        stack.append((name, s + d))
        t = s
    while stack:
        n, e = stack.pop()
        if e > t:
            out.append((n, t, e - t))
            t = e
    return out


def _nearest(xs, t):
    """The element of the sorted, non-empty ``xs`` nearest to ``t``."""
    i = bisect.bisect_left(xs, t)
    return min(xs[max(0, i - 1):i + 1], key=lambda x: abs(x - t))


def _clock_offset(sample_ends, step_ends):
    """Least (end of a ``serve.sample`` span - end of a step program) over
    pairs that are each other's nearest; None without a pair.  A host
    that stalls long after its step has ended leaves its sample nearer the
    next step than its own; that next step has its own sample nearer, so
    such a pair is not mutual and drops out."""
    samples, steps = sorted(sample_ends), sorted(step_ends)
    if not samples or not steps:
        return None
    return min((h - e for h in samples for e in (_nearest(steps, h),)
                if _nearest(samples, e) == h), default=None)


def reduce(ev: dict, n_chips: int) -> dict:
    """``module_s`` {program: device s in the window, mean over chips};
    ``decode_step_ms`` (median ``jit_decode_paged`` run, mean over chips);
    ``ticks`` (``serve.tick`` spans starting in the window);
    ``clock_offset_ms`` (host - device); ``idle_in_tick`` {span: s of the
    first chip's idle time inside ``serve.tick`` spans, by the innermost
    ``serve.*`` span covering it once moved onto the host clock}."""
    from bench import trace as TR
    win = [(s, d) for n, s, d in ev["host"] if n == "bench.window"]
    if len(win) != 1:
        raise RuntimeError(f"trace holds {len(win)} bench.window spans")
    lo, hi = win[0][0], win[0][0] + win[0][1]
    planes = sorted(ev["devices"])[:n_chips]
    if len(planes) < n_chips:
        raise RuntimeError(f"trace has {len(planes)} device planes, the "
                           f"cell uses {n_chips}")
    modules = ev.get("modules", {})
    module_ns: dict[str, float] = {}
    steps = []
    for p in planes:
        runs = [(program(n), s, d) for n, s, d in modules.get(p, [])]
        for n, s, d in runs:
            clipped = min(s + d, hi) - max(s, lo)
            if clipped > 0:
                module_ns[n] = module_ns.get(n, 0.0) + clipped
        mine = [d for n, s, d in runs if n == DECODE and lo <= s < hi]
        if mine:
            steps.append(statistics.median(mine))
    spans = [x for x in ev.get("spans", []) if lo <= x[1] < hi]
    ticks = [x for x in spans if x[0] == "serve.tick"]
    offset = _clock_offset(
        [s + d for n, s, d, _ in spans if n == "serve.sample"],
        [s + d for n, s, d in modules.get(planes[0], [])
         if program(n) in STEPS])
    shift = offset or 0.0
    merged = TR._union(((s, s + d) for _, s, d in ev["devices"][planes[0]]),
                       lo, hi)
    edges = [lo] + [t for iv in merged for t in iv] + [hi]
    label = TR._Cover(_innermost(spans))
    idle: dict[str, float] = {}
    for a, b in zip(edges[::2], edges[1::2]):
        if b > a:
            label.split(a + shift, b + shift, idle)
    idle.pop("outside", None)
    return {
        "module_s": {n: t / len(planes) * 1e-9
                     for n, t in sorted(module_ns.items(),
                                        key=lambda kv: -kv[1])},
        "decode_step_ms": sum(steps) / len(steps) * 1e-6 if steps else None,
        "ticks": len(ticks),
        "clock_offset_ms": None if offset is None else offset * 1e-6,
        "idle_in_tick": {n: t * 1e-9 for n, t in
                         sorted(idle.items(), key=lambda kv: -kv[1])},
    }


def summary(sp: dict, busy_s: float) -> dict:
    """The three per-layer numbers ``reduce`` gives, each None where the
    trace holds nothing to read (a program without ``serve.*`` spans or
    named programs):

    - ``decode_step_ms``: median device time of one ``jit_decode_paged``;
    - ``prefill_share``: device s in ``jit_prefill_chunk`` + ``jit_lm_head``
      / device busy s (``bench/trace.py``), %;
    - ``tick_idle_ms``: device-idle time inside ``serve.tick`` spans /
      ticks, ms.
    """
    mod = sp["module_s"]
    prefill = sum(mod.get(n, 0.0) for n in PREFILL)
    return {
        "decode_step_ms": sp["decode_step_ms"],
        "prefill_share": 100.0 * prefill / busy_s
        if busy_s > 0 and any(n in mod for n in PREFILL) else None,
        "tick_idle_ms": 1e3 * sum(sp["idle_in_tick"].values()) / sp["ticks"]
        if sp["ticks"] else None,
    }


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("trace_dir")
    ap.add_argument("--chips", type=int, default=1)
    args = ap.parse_args(argv)
    from bench import trace as TR
    ev = load(args.trace_dir)
    if not any(n == "bench.window" for n, _, _ in ev["host"]):
        ticks = [(s, s + d) for n, s, d, _ in ev["spans"]
                 if n == "serve.tick"]
        if not ticks:
            ap.error(f"{args.trace_dir}: no bench.window or serve.tick span")
        lo, hi = min(s for s, _ in ticks), max(e for _, e in ticks)
        ev["host"].append(("bench.window", lo, hi - lo))
    tr = TR.reduce(ev, n_chips=args.chips)
    sp = reduce(ev, n_chips=args.chips)
    print(json.dumps(dict(tr, **sp, summary=summary(sp, tr["busy_s"]))))
    return 0


if __name__ == "__main__":
    sys.path.insert(0, os.path.dirname(os.path.dirname(
        os.path.abspath(__file__))))
    sys.exit(main())
