"""Load drivers: submit a mix's requests to a ``ServeEngine``, tick it, and
record on the host clock when each token reached the host.

The drivers call only ``ServeEngine.submit`` and ``ServeEngine.tick`` (and
read ``Request`` objects and ``eng.stats`` / ``eng.sched`` counters).  A
token's time is the host clock right after the ``tick()`` that emitted it
returns: the engine has already copied the sampled ids to the host by then.
Host spans (``bench.tick``, ``bench.submit``, ``bench.observe``,
``bench.wait``, ``bench.window``) go into the profiler's trace when one is
being taken, so idle device time can be labelled by what the host did.
"""
from __future__ import annotations

import dataclasses
import time
from typing import Optional

import numpy as np

from bench.loadgen import RequestSpec, Traffic

now = time.perf_counter


def _span(name: str):
    import jax
    return jax.profiler.TraceAnnotation(name)


@dataclasses.dataclass
class Record:
    """One request as the client sees it."""
    spec: RequestSpec
    client: int
    due: float                      # host time the request was due
    submitted: float                # host time ``submit`` was called
    req: object                     # the engine's Request
    token_times: list = dataclasses.field(default_factory=list)
    done: Optional[float] = None
    error: Optional[str] = None
    admitted: bool = False          # resident in a slot right now
    admissions: list = dataclasses.field(default_factory=list)
    #                                 (host time, prefill tokens, cached)
    resume_len: int = 0             # prefill length after a preemption
    slot: int = -1                  # the slot it was last admitted to


@dataclasses.dataclass
class Window:
    """What one measured window saw.  ``t0``/``t1`` bound it on the host
    clock; ``t1`` is taken after the pool storage is ready."""
    t0: float
    t1: float
    records: list
    tick_live: list                 # live slots at the start of each tick
    tick_s: list                    # each tick's host time, seconds
    tick_prefill: list              # whether the tick ran a prefill chunk
    stats0: dict
    stats1: dict
    lateness: list                  # open loop: submit - due, seconds

    @property
    def seconds(self) -> float:
        return self.t1 - self.t0


class Driver:
    """Feeds one engine and keeps every request's record."""

    def __init__(self, eng, traffic: Traffic):
        self.eng, self.traffic = eng, traffic
        self.records: list[Record] = []
        self.inflight: list[Record] = []
        self.tick_live: list[int] = []
        self.tick_s: list[float] = []
        self.tick_prefill: list[bool] = []

    def clear_ticks(self) -> None:
        self.tick_live.clear()
        self.tick_s.clear()
        self.tick_prefill.clear()

    def submit(self, index: int, due: float, client: int = -1) -> Record:
        spec = self.traffic.spec(index)
        with _span("bench.submit"):
            prompt = self.traffic.prompt(index)
            t = now()
            rid = self.eng.submit(prompt, max_new_tokens=spec.output_len)
        req = self.eng.queue[-1]
        if req.rid != rid:
            raise RuntimeError("submitted request is not at the queue tail")
        rec = Record(spec, client, due, t, req)
        self.records.append(rec)
        self.inflight.append(rec)
        return rec

    def busy(self) -> bool:
        return self.eng.sched.has_work()

    def tick(self) -> list[Record]:
        """One engine tick; returns the records that finished in it."""
        self.tick_live.append(len(self.eng.sched.live_slots()))
        hits0 = self.eng.sched.prefix_hit_tokens
        chunks0 = self.eng.stats["chunk_prefills"]
        t_start = now()
        with _span("bench.tick"):
            self.eng.tick()
        t = now()
        self.tick_s.append(t - t_start)
        self.tick_prefill.append(self.eng.stats["chunk_prefills"] > chunks0)
        with _span("bench.observe"):
            return self._observe(t, self.eng.sched.prefix_hit_tokens - hits0)

    def _observe(self, t: float, hit_tokens: int) -> list[Record]:
        resident = {id(r): i for i, r in enumerate(self.eng.sched.slot_req)
                    if r is not None}
        new = []
        finished, still = [], []
        for rec in self.inflight:
            req = rec.req
            n = len(req.output)
            if n > len(rec.token_times):
                rec.token_times.extend([t] * (n - len(rec.token_times)))
            in_slot = id(req) in resident or req.done_at is not None
            if in_slot and not rec.admitted:
                new.append(rec)
                rec.slot = resident.get(id(req), rec.slot)
            elif rec.admitted and not in_slot:      # preempted
                rec.resume_len = rec.spec.prompt_len + n
            rec.admitted = in_slot
            if req.error is not None:
                rec.error, rec.done = repr(req.error), t
            elif req.done_at is not None:
                rec.done = t
            if rec.done is None:
                still.append(rec)
            else:
                finished.append(rec)
        # the tick's prefix-cache hits, split over the requests it admitted
        for rec in new:
            rec.admissions.append((t, rec.resume_len or rec.spec.prompt_len,
                                   hit_tokens / len(new)))
        self.inflight = still
        return finished

    def close(self) -> float:
        import jax
        jax.block_until_ready(self.eng.pool.storage)
        return now()


def _window(drv: Driver, t0: float, t1: float, stats0: dict,
            lateness: list) -> Window:
    return Window(t0, t1, drv.records, list(drv.tick_live),
                  list(drv.tick_s), list(drv.tick_prefill), stats0,
                  dict(drv.eng.stats), lateness)


class OpenLoop:
    """Open loop: requests are due on the mix's schedule whatever the
    server does; each is timed from its due time.  The schedule starts
    ``preroll_s`` (a mix key) before the window, so the window sees the
    load it would in steady service; that lead-in is set-up."""

    def __init__(self, drv: Driver, seconds: float):
        mix = drv.traffic.mix
        self.drv, self.seconds = drv, seconds
        self.preroll = float(mix.get("preroll_s", 0.0))
        n = int(float(mix["rate_per_s"]) * (seconds + self.preroll) * 2) + 64
        self.due = now() + drv.traffic.due_offsets(n)
        self.i = 0
        self.lateness = []

    def _serve_until(self, t_end: float) -> None:
        drv, due = self.drv, self.due
        while True:
            t = now()
            if t >= t_end:
                return
            while self.i < len(due) and due[self.i] <= t:
                rec = drv.submit(self.i, float(due[self.i]))
                self.lateness.append(rec.submitted - rec.due)
                self.i += 1
            if drv.busy():
                drv.tick()
            else:
                nxt = min(due[self.i] if self.i < len(due) else t_end, t_end)
                with _span("bench.wait"):
                    time.sleep(max(0.0, nxt - now()))

    def preroll_load(self) -> None:
        self._serve_until(self.due[0] - self.drv.traffic.gaps[0]
                          + self.preroll)

    def window(self) -> Window:
        drv = self.drv
        drv.clear_ticks()
        stats0 = dict(drv.eng.stats)
        self.lateness = []
        with _span("bench.window"):
            t0 = now()
            self._serve_until(t0 + self.seconds)
            t1 = drv.close()
        return _window(drv, t0, t1, stats0, self.lateness)


def start_closed(drv: Driver, clients: int) -> None:
    """Closed loop set-up: every client sends its first request, and the
    engine runs (each client sending its next request when one completes)
    until every first request has started decoding."""
    first = [drv.submit(c, now(), client=c) for c in range(clients)]
    sent = list(range(clients))
    while not all(r.token_times for r in first):
        for rec in drv.tick():
            c = rec.client
            sent[c] += clients
            drv.submit(sent[c], rec.done, client=c)


def run_closed(drv: Driver, clients: int, seconds: float) -> Window:
    """Closed loop: each client sends its next request as soon as its last
    one completes (request ``client + k * clients`` is its k-th)."""
    sent = {c: max(r.spec.index for r in drv.records if r.client == c)
            for c in range(clients)}
    drv.clear_ticks()
    stats0 = dict(drv.eng.stats)
    with _span("bench.window"):
        t0 = now()
        t_end = t0 + seconds
        while now() < t_end:
            for rec in drv.tick():
                c = rec.client
                sent[c] += clients
                drv.submit(sent[c], rec.done, client=c)
        t1 = drv.close()
    return _window(drv, t0, t1, stats0, [])


def window_samples(w: Window) -> dict:
    """The window's samples: TTFT of every request due in the window (one
    with no first token by the close enters with its wait so far), every
    gap between consecutive tokens whose later token falls in the window,
    and the output tokens that reached the host in the window."""
    ttft, itl = [], []
    tokens = 0
    for rec in w.records:
        if w.t0 <= rec.due < w.t1:
            first = rec.token_times[0] if rec.token_times else None
            if first is not None and first <= w.t1:
                ttft.append(first - rec.due)
            else:
                ttft.append(w.t1 - rec.due)
        times = rec.token_times
        for j, tj in enumerate(times):
            if w.t0 < tj <= w.t1:
                tokens += 1
                if j:
                    itl.append(tj - times[j - 1])
    return {"ttft": ttft, "itl": itl, "tokens": tokens}


def summarize_lateness(lateness) -> str:
    if not lateness:
        return "generator lateness: closed loop (no schedule)"
    a = np.asarray(lateness)
    return (f"generator lateness: n={a.size} median={np.median(a) * 1e3:.3f} "
            f"ms max={a.max() * 1e3:.3f} ms")


def summarize_ticks(w: Window) -> str:
    """The window's tick times on the host, split by whether the tick ran a
    prefill chunk, and the host time spent outside ticks."""
    a = np.asarray(w.tick_s, float) * 1e3
    pre = np.asarray(w.tick_prefill, bool)
    parts = []
    for name, x in (("decode only", a[~pre]), ("with prefill", a[pre])):
        if x.size:
            parts.append(f"{name} n={x.size} p50={np.median(x):.3f} "
                         f"p90={np.percentile(x, 90):.3f} max={x.max():.3f} "
                         f"sum={x.sum() / 1e3:.3f} s")
    return (f"tick ms: {'; '.join(parts)}; outside ticks "
            f"{w.seconds - a.sum() / 1e3:.3f} s")
