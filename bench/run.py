"""Run one benchmark cell on the chips of this host.

    python3 bench/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

From the root of a checkout.  The run refuses to start without a TPU (or
with fewer chips than the cell asks for): it exits non-zero and prints no
result.  Otherwise it builds the cell's model and engine from the seed,
warms every shape the cell's traffic uses, measures one window of
``--seconds`` seconds, checks the served tokens against the plain
reference, and prints one JSON object as the last line of standard
output.  ``--trace 1`` reports the cell's per-layer metrics from a
profiler trace of the window instead of its end-to-end metrics.

``--control`` is for setting a cell's limit, not for measuring: it also
reads the lower-precision control on the same sample and judges it by the
same comparison (``control.correct``, which has to come out false).
"""
from __future__ import annotations

import argparse
import gc
import json
import os
import pathlib
import shutil
import sys
import tempfile
import time

ROOT = pathlib.Path(__file__).resolve().parents[1]


def _process_age() -> float:
    """Seconds since this process started (Linux ``/proc``)."""
    with open("/proc/self/stat") as f:
        start_ticks = int(f.read().rsplit(")", 1)[1].split()[19])
    with open("/proc/uptime") as f:
        up = float(f.read().split()[0])
    return up - start_ticks / os.sysconf("SC_CLK_TCK")


def log(*a):
    print(*a, file=sys.stderr, flush=True)


class _CompileCounter:
    """Counts programs lowered (traced or compiled) while ``on``."""

    EVENTS = ("/jax/core/compile/jaxpr_to_mlir_module_duration",
              "/jax/core/compile/backend_compile_duration")

    def __init__(self):
        import jax
        self.on, self.n = False, 0
        jax.monitoring.register_event_duration_secs_listener(self._listen)

    def _listen(self, name, *_a, **_k):
        if self.on and name in self.EVENTS:
            self.n += 1

    def close(self):
        import jax
        jax.monitoring.unregister_event_duration_listener(self._listen)


class _GcTimer:
    """Counts the garbage collector's passes and their seconds."""

    def __init__(self):
        self.n, self.seconds, self._t = 0, 0.0, 0.0
        gc.callbacks.append(self._cb)

    def _cb(self, phase, _info):
        if phase == "start":
            self._t = time.perf_counter()
        else:
            self.n += 1
            self.seconds += time.perf_counter() - self._t

    def close(self):
        gc.callbacks.remove(self._cb)


def _warm(drv) -> None:
    """Every shape the cell's traffic uses: each prefill chunk length the
    chunker can cut (multiples of the page size up to ``prefill_chunk``),
    the lm head and first-token sample, and the batched decode step."""
    import numpy as np
    eng = drv.eng
    step = eng.pool.page_size
    rng = np.random.default_rng(0)
    for n in range(step, eng.sched.prefill_chunk + 1, step):
        eng.submit(rng.integers(0, drv.traffic.vocab, n), max_new_tokens=2)
    while eng.sched.has_work():
        eng.tick()
    eng.finished.clear()
    eng.pool.flush_cache()


def run_cell(cell, seed: int, seconds: float, trace: bool, *,
             control: bool = False, system=None, free_device: bool = False):
    """One run of ``cell``; returns the result dict (the JSON line).
    ``system`` stands in for ``build_engine`` (tests plant faults with it);
    ``free_device`` deletes every array left on the device before the
    reference runs (the whole process belongs to the run)."""
    import importlib

    import jax

    from bench import check as CHK
    from bench import driver as D
    from bench import flops, peaks, spec
    from bench import trace as TR
    from bench.loadgen import Traffic

    c, mix = cell.config, cell.mix
    ref = importlib.import_module(f"bench.models.{c['reference']}")
    sut = importlib.import_module(f"bench.models.{c['reference']}_program")
    devices = jax.devices()[:cell.chips]
    knobs = dict(c["deployment"]["engine"], **mix["engine"])
    build = system or sut.build_engine
    eng = build(c, knobs, seed, tp=int(c["deployment"]["tp"]))
    traffic = Traffic(mix, seed, int(c["vocab_size"]))
    drv = D.Driver(eng, traffic)
    _warm(drv)
    if mix.get("warm_sessions"):
        for k in range(len(traffic.prefix_lens)):
            eng.submit(traffic.session_prompt(k), max_new_tokens=1)
        while eng.sched.has_work():
            eng.tick()
        eng.finished.clear()
    if mix["loop"] == "closed":
        D.start_closed(drv, int(mix["clients"]))
    else:
        loop = D.OpenLoop(drv, seconds)
        loop.preroll_load()
    jax.block_until_ready(eng.pool.storage)
    # what set-up made stays: the collector's full passes skip it in the
    # window, as a server does once it has warmed up
    gc.collect()
    gc.freeze()

    compiles = _CompileCounter()
    collections = _GcTimer()
    tdir = tempfile.mkdtemp(prefix="bench-trace-") if trace else None
    if trace:
        TR.start(tdir)
    setup_s = _process_age()
    compiles.on = True
    if mix["loop"] == "open":
        win = loop.window()
    else:
        win = D.run_closed(drv, int(mix["clients"]), seconds)
    compiles.close()
    collections.close()
    gc.unfreeze()
    if trace:
        TR.stop()
    log(f"[bench] window {win.seconds:.3f} s, setup {setup_s:.3f} s, "
        f"ticks {win.stats1['ticks'] - win.stats0['ticks']}, "
        f"programs lowered in the window {compiles.n}, preemptions "
        f"{win.stats1['preemptions'] - win.stats0['preemptions']}, "
        f"pages high-water {win.stats1['pages_high_water']}/"
        f"{eng.pool.num_pages}")
    log("[bench] " + D.summarize_lateness(win.lateness))
    log("[bench] " + D.summarize_ticks(win))
    log(f"[bench] garbage collections in the window: {collections.n}, "
        f"{collections.seconds:.3f} s")

    dev = devices[0]
    mem = [(d.memory_stats() or {}).get("peak_bytes_in_use", -1)
           for d in devices]
    # every request the window served: due in it, or still in flight at
    # its opening (a closed loop's first requests)
    attempted = [r for r in win.records if r.due < win.t1
                 and (r.done is None or r.done >= win.t0)]
    failed = [r for r in attempted if r.error is not None]
    samples = D.window_samples(win)

    # free the program's state before the reference runs
    drv.eng = eng = None
    gc.collect()
    if free_device:
        for a in jax.live_arrays():
            a.delete()
    log(f"[bench] device bytes in use before the reference: "
        f"{(dev.memory_stats() or {}).get('bytes_in_use')}")

    picked = CHK.sample(win.records, win.t0, win.t1, seed)
    wrong = [m for m in (CHK.served_ok(r) for r in picked) if m]
    t_ref = time.perf_counter()
    out_shardings = None
    if cell.chips > 1:              # the reference's weights span the chips
        from jax.sharding import AxisType, NamedSharding, PartitionSpec
        mesh = jax.make_mesh((cell.chips,), ("x",),
                             axis_types=(AxisType.Auto,))
        out_shardings = jax.tree_util.tree_map(
            lambda s: NamedSharding(mesh, s), ref.weight_specs(c, "x"),
            is_leaf=lambda x: isinstance(x, PartitionSpec))
    weights = jax.jit(lambda k: ref.make_weights(c, k),
                      out_shardings=out_shardings)(ref.seed_key(seed))
    got = CHK.gaps(c, ref, weights, picked, control=control)
    del weights
    n_checked = sum(len(g) for g, _ in got)
    # null when nothing could be checked (no request finished in the window)
    max_gap = max(float(g.max()) for g, _ in got) if n_checked else None
    log(f"[bench] reference over {len(picked)} requests, {n_checked} served "
        f"tokens in {time.perf_counter() - t_ref:.1f} s")

    run = dict(cell=cell, window=win, samples=samples, setup_s=setup_s,
               chips=cell.chips, peaks=peaks.peaks_for(dev.device_kind)
               if dev.platform == "tpu" else None,
               knobs=knobs, flops=lambda: flops.window_flops(c, win),
               trace=None)
    result_device = {"platform": dev.platform, "kind": dev.device_kind,
                     "count": len(devices), "memory_peak_bytes": max(mem)}
    breakdown = None
    if trace:
        tr = TR.reduce(TR.load(tdir), n_chips=cell.chips)
        shutil.rmtree(tdir, ignore_errors=True)
        run["trace"] = tr
        result_device.update(busy_s=tr["busy_s"], window_s=tr["window_s"])
        breakdown = {"device_ops": tr["device_ops"],
                     "idle_gaps": tr["idle_gaps"]}
    metrics = {}
    for m in (cell.per_layer if trace else cell.end_to_end):
        v = spec.reader(cell.root, m["name"])(run)
        if v is not None:
            metrics[m["name"]] = {"value": v, "unit": m["unit"]}

    limit = float(cell.limits["max_logit_gap"])
    checks = {
        "max_logit_gap": {"value": max_gap, "limit": limit},
        "failed_requests": {"value": len(failed), "limit": 0},
        "short_outputs": {"value": len(wrong), "limit": 0},
        "checked_tokens": {"value": n_checked, "limit": 1},
    }
    correct = CHK.within(max_gap, limit) and not failed and not wrong
    result = {"correct": correct, "attempted": len(attempted),
              "failed": len(failed), "metrics": metrics,
              "device": result_device}
    if breakdown is not None:
        result["breakdown"] = breakdown
    if control:
        cmax = max(float(cg.max()) for _, cg in got) if n_checked else None
        result["control"] = {"max_logit_gap": cmax, "limit": limit,
                             "correct": CHK.within(cmax, limit)}
        log(f"[bench] control max_logit_gap {cmax!r} limit {limit!r} "
            f"correct {result['control']['correct']}")
    result["check"] = checks
    for name, v in checks.items():
        log(f"check {name} {v['value']!r} limit {v['limit']!r}")
    return result


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    ap.add_argument("--control", action="store_true",
                    help="also read the lower-precision control")
    args = ap.parse_args(argv)
    if args.seed < 0:
        ap.error("--seed must be non-negative")

    sys.path[:0] = [str(ROOT), str(ROOT / "src")]
    from bench import spec
    cell = spec.load_cell(ROOT, args.workload)

    import jax
    devices = jax.devices()
    if devices[0].platform != "tpu" or len(devices) < cell.chips:
        log(f"bench: {args.workload} needs {cell.chips} TPU chip(s); JAX "
            f"sees {len(devices)} {devices[0].platform} device(s)")
        return 3
    from repro.launch.serve import enable_compile_cache
    enable_compile_cache()
    # every program the cell uses goes to the persistent cache, so only the
    # first run in a checkout compiles
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)

    result = run_cell(cell, args.seed, args.seconds, bool(args.trace),
                      control=args.control, free_device=True)
    print(json.dumps(result), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
