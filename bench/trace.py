"""Profiler trace of the window -> device busy time, top operations, idle
gaps labelled by what the host was doing, collective time.

``load`` reads the ``.xplane.pb`` the JAX profiler writes into a plain
event list; ``reduce`` is pure Python over that list, so it is tested on a
small recorded trace.  Device events are the ``XLA Ops`` line of each
``/device:TPU:<n>`` plane; host spans are the benchmark's own
``bench.*`` annotations.  The window is the ``bench.window`` span.  Device
and host timestamps in one trace were seen to differ by about a
millisecond, so a gap's label is only as good as that.
"""
from __future__ import annotations

import bisect
import glob
import os

COLLECTIVES = ("all-reduce", "all-gather", "reduce-scatter", "all-to-all",
               "collective-permute")
N_TOP = 10


def start(log_dir: str) -> None:
    import jax
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    opts.host_tracer_level = 1
    jax.profiler.start_trace(log_dir, profiler_options=opts)


def stop() -> None:
    import jax
    jax.profiler.stop_trace()


def _op_name(name: str) -> str:
    """``%fusion.12 = bf16[...] fusion(...)`` -> ``fusion.12``."""
    head = name.split(" = ", 1)[0].strip()
    return head[1:] if head.startswith("%") else head


def load(log_dir: str) -> dict:
    """{"devices": {plane: [(op, start_ns, dur_ns), ...]},
    "modules": {plane: [(program, start_ns, dur_ns), ...]},
    "host": [(span, start_ns, dur_ns), ...]}"""
    from jax.profiler import ProfileData
    paths = glob.glob(os.path.join(log_dir, "**", "*.xplane.pb"),
                      recursive=True)
    if len(paths) != 1:
        raise RuntimeError(f"expected one trace file under {log_dir}, "
                           f"found {len(paths)}")
    pd = ProfileData.from_file(paths[0])
    devices, modules, host = {}, {}, []
    for plane in pd.planes:
        if plane.name.startswith("/device:TPU:"):
            for line in plane.lines:
                if line.name == "XLA Ops":
                    devices[plane.name] = [
                        (_op_name(e.name), e.start_ns, e.duration_ns)
                        for e in line.events]
                elif line.name == "XLA Modules":
                    modules[plane.name] = [
                        (e.name, e.start_ns, e.duration_ns)
                        for e in line.events]
        elif plane.name.startswith("/host:"):
            for line in plane.lines:
                host.extend((e.name, e.start_ns, e.duration_ns)
                            for e in line.events
                            if e.name.startswith("bench."))
    return {"devices": devices, "modules": modules, "host": host}


def _union(intervals, lo, hi):
    """Merged [start, end) intervals clipped to [lo, hi)."""
    out = []
    for s, e in sorted((max(s, lo), min(e, hi)) for s, e in intervals):
        if e <= s:
            continue
        if out and s <= out[-1][1]:
            out[-1][1] = max(out[-1][1], e)
        else:
            out.append([s, e])
    return out


class _Cover:
    """Which of a set of non-overlapping intervals covers a time."""

    def __init__(self, items):
        self.items = sorted(items, key=lambda x: x[1])
        self.starts = [s for _, s, _ in self.items]

    def __call__(self, t, default="outside"):
        i = bisect.bisect_right(self.starts, t) - 1
        if i >= 0:
            name, s, d = self.items[i]
            if t < s + d:
                return name
        return default

    def split(self, a, b, out: dict, default="outside") -> None:
        """Add to ``out`` how much of [a, b) each interval covers."""
        i = max(0, bisect.bisect_right(self.starts, a) - 1)
        t = a
        while t < b:
            while i < len(self.items) and sum(self.items[i][1:]) <= t:
                i += 1
            if i < len(self.items) and self.items[i][1] <= t:
                name, s, d = self.items[i]
                end = min(b, s + d)
            else:
                name = default
                end = min(b, self.items[i][1]) if i < len(self.items) else b
            out[name] = out.get(name, 0.0) + (end - t)
            t = end


def reduce(ev: dict, n_chips: int) -> dict:
    win = [(s, d) for n, s, d in ev["host"] if n == "bench.window"]
    if len(win) != 1:
        raise RuntimeError(f"trace holds {len(win)} bench.window spans")
    lo, hi = win[0][0], win[0][0] + win[0][1]
    planes = sorted(ev["devices"])[:n_chips]
    if len(planes) < n_chips:
        raise RuntimeError(f"trace has {len(planes)} device planes, the "
                           f"cell uses {n_chips}")
    busy = []
    per_op: dict[str, float] = {}
    for p in planes:
        busy.append(sum(e - s for s, e in _union(
            ((s, s + d) for _, s, d in ev["devices"][p]), lo, hi)))
    first = sorted(ev["devices"][planes[0]], key=lambda e: e[1])
    program = _Cover(ev.get("modules", {}).get(planes[0], []))
    coll = 0.0
    for i, (name, s, d) in enumerate(first):
        if s + d <= lo or s >= hi:
            continue
        if i + 1 < len(first) and first[i + 1][1] < s + d:
            continue        # a loop or call whose body's ops follow it
        key = f"{program(s, '?')}/{name}"
        per_op[key] = per_op.get(key, 0.0) + d
        if any(k in name for k in COLLECTIVES):
            coll += d
    merged = _union(((s, s + d) for _, s, d in first), lo, hi)
    label = _Cover([x for x in ev["host"] if x[0] != "bench.window"])
    idle: dict[str, float] = {}
    edges = [lo] + [t for iv in merged for t in iv] + [hi]
    for a, b in zip(edges[::2], edges[1::2]):
        if b > a:
            label.split(a, b, idle)
    top = sorted(per_op.items(), key=lambda kv: -kv[1])[:N_TOP]
    gaps = sorted(idle.items(), key=lambda kv: -kv[1])[:N_TOP]
    busy0 = busy[0]
    return {
        "window_s": (hi - lo) * 1e-9,
        "busy_s": sum(busy) / len(busy) * 1e-9,
        "device_ops": [[n, t * 1e-9] for n, t in top],
        "idle_gaps": [[n, t * 1e-9] for n, t in gaps],
        "collective_s": coll * 1e-9,
        "busy0_s": busy0 * 1e-9,
    }
